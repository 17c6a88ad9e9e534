"""Share of the device's busy time under the scope ``mla_up_project``:
a prefill chunk's up-projection of the latent rows it gathers
(``scope_lib``)."""
from layer_metrics import scope_lib


def read(run):
    return scope_lib.share(run, "mla_up_project_device_share.serve",
                           "mla_up_project")
