"""Training attention's share of its roofline: the device time of the
events that do it (kernels/train_attention/*.json) against the FLOPs
and bytes of causal attention at the cell's shapes."""
import metrics_lib


def read(run):
    return metrics_lib.roofline_share(run, "train_attention")
