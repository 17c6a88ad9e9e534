"""Share of the token positions the window's prefill chunk calls
computed (slots x chunk each) that were new prompt tokens."""
from layer_metrics import span_lib


def read(run):
    reg = span_lib.prefill(run, "prefill_useful_token_share.serve",
                           "serving.prefill.tokens_useful",
                           "serving.prefill.tokens_computed")
    if reg is None:
        return None
    return 100.0 * reg["serving.prefill.tokens_useful"] \
        / reg["serving.prefill.tokens_computed"]
