"""Share of the device's busy time under the scopes ``head`` and
``loss``, both directions — with a tied head the embedding's backward
too, which then holds the head's weight gradient (``scope_lib``)."""
from layer_metrics import scope_lib

METRIC = "head_loss_device_share.train"


def read(run):
    got = scope_lib.by_scope(run, METRIC)
    if got is None:
        return None
    sec = scope_lib.seconds(got, "head", "loss")
    if run.model_cfg.get("tie_word_embeddings"):
        sec += scope_lib.seconds(got, "embed", direction="bwd")
    return 100.0 * sec / got["busy"] if sec > 0 else None
