"""Share of the device's busy time under the scopes ``head`` and
``sample``: the output projection of ticks and prefill members and the
tick's argmax or sampler (``scope_lib``)."""
from layer_metrics import scope_lib


def read(run):
    return scope_lib.share(run, "head_device_share.serve", "head", "sample")
