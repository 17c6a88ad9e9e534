"""The attention layers' dense part's share of its roofline: the FLOPs
of the four projections forward and backward
(kernels/train_attn_dense/ops.py) against the device time under the
scope ``attn``, both directions (``scope_lib``), less the events of the
attention kernels themselves (kernels/train_attention/*.json, what
``attention_roofline.train`` reads)."""
import metrics_lib
from layer_metrics import scope_lib


def read(run):
    flash, _ = metrics_lib.work_seconds(run, "train_attention")
    if run.rehearsal:
        flash = 0.0         # the stand-in pattern matches every product
    return scope_lib.roofline(run, "attn_dense_roofline.train",
                              "train_attn_dense", "attn", less=flash)
