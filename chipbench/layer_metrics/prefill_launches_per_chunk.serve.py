"""Eager-op dispatches per prefill chunk call in the window
(``serving.prefill.launches`` over the calls)."""
from layer_metrics import span_lib


def read(run):
    reg = span_lib.prefill(run, "prefill_launches_per_chunk.serve",
                           "serving.prefill.launches")
    if reg is None:
        return None
    return reg["serving.prefill.launches"] \
        / reg["serving.prefill_chunk_ms.count"]
