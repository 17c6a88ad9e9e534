"""Share of the device's busy time under the scope ``attn_window``: the
window attention layers of ticks and prefill members — their norm,
projections, rotation and paged read over the latest ``sliding_window``
positions (``scope_lib``)."""
from layer_metrics import scope_lib


def read(run):
    return scope_lib.share(run, "attn_window_device_share.serve",
                           "attn_window")
