"""Share of the device's busy time whose instruction resolves to a named
program scope (``scope_lib``): what is left is nobody's by name — an
instruction without a scope, one two programs scope differently, one no
published table has."""
from layer_metrics import scope_lib


def read(run):
    return scope_lib.share(run, "scope_named_share.train")
