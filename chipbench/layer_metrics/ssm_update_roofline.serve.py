"""The decode state update's share of its roofline: the device time of
the ``ssm_update`` events (kernels/ssm_decode_update/*.json) against the
state bytes of the tokens decoded in the window."""
import metrics_lib


def read(run):
    return metrics_lib.roofline_share(run, "ssm_decode_update")
