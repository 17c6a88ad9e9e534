"""Share of the device's busy time under the scope ``attn_full``: the
full attention layers of ticks and prefill members — their norm,
projections, rotation and paged read over the whole context
(``scope_lib``)."""
from layer_metrics import scope_lib


def read(run):
    return scope_lib.share(run, "attn_full_device_share.serve", "attn_full")
