"""Share of the device's busy time under the scope ``mla_absorb``: the
two products that fold ``W_kvb`` into the query and out of the result
round the latent decode read (``scope_lib``)."""
from layer_metrics import scope_lib


def read(run):
    return scope_lib.share(run, "mla_absorb_device_share.serve",
                           "mla_absorb")
