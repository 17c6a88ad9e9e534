"""Share of the device's busy time under the scopes ``moe`` (the routed
experts: router, sorted groups, grouped products), ``moe_latent`` (the
two latent projections round them) and ``moe_shared`` (the shared
expert), over ticks and prefill members (``scope_lib``): whether the
latent expert layer does most of a tick's work."""
from layer_metrics import scope_lib


def read(run):
    return scope_lib.share(run, "latent_moe_device_share.serve", "moe",
                           "moe_latent", "moe_shared")
