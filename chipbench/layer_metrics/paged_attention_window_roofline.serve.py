"""Decode attention's share of its roofline where page tables go by
layer kind: the device time of the ``paged_decode`` events
(kernels/paged_decode_attention_window/*.json) against the KV bytes of
the in-window pages in window layers and of all live pages in full
layers, over the window's ticks."""
import metrics_lib
from layer_metrics import span_lib


def read(run):
    reg = span_lib.registry(run, "paged_attention_window_roofline.serve",
                            "serving.kv.context_token_ticks",
                            "serving.kv.window.token_ticks")
    if reg is None or reg["serving.kv.context_token_ticks"] <= 0:
        return None
    return metrics_lib.roofline_share(run, "paged_decode_attention_window")
