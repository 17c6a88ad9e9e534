"""Share of the KV pages held or promised to admitted requests that
hold tokens, summed over the window's ticks."""
from layer_metrics import span_lib


def read(run):
    reg = span_lib.registry(run, "kv_pages_claimed_share.serve",
                            "serving.kv.page_ticks_in_use",
                            "serving.kv.page_ticks_reserved")
    if reg is None or reg["serving.kv.page_ticks_reserved"] <= 0:
        return None
    return 100.0 * reg["serving.kv.page_ticks_in_use"] \
        / reg["serving.kv.page_ticks_reserved"]
