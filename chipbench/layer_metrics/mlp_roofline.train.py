"""The feed-forward layers' share of their roofline: the FLOPs of their
products forward and backward (kernels/train_mlp/ops.py) against the
device time under the scope ``mlp``, both directions (``scope_lib``) —
the layer's norm, activation, residual and casts are in that time."""
from layer_metrics import scope_lib


def read(run):
    return scope_lib.roofline(run, "mlp_roofline.train", "train_mlp", "mlp")
