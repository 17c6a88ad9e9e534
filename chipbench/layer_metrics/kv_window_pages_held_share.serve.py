"""Pages the slots' window rings held over the pages one shared page
table would have held for the window layers, summed over the window's
ticks: what page tables by layer kind save."""
from layer_metrics import span_lib


def read(run):
    reg = span_lib.registry(run, "kv_window_pages_held_share.serve",
                            "serving.kv.window.page_ticks_held",
                            "serving.kv.window.page_ticks_full_equiv")
    if reg is None or reg["serving.kv.window.page_ticks_full_equiv"] <= 0:
        return None
    return 100.0 * reg["serving.kv.window.page_ticks_held"] \
        / reg["serving.kv.window.page_ticks_full_equiv"]
