"""Decode attention over page tables by layer kind: a decoded token reads
the K and V of its whole context in a full_attention layer and of its
latest ``sliding_window`` positions in a sliding_attention layer, once
each, in the pages' type; QK^T and PV are 2 FLOP a multiply-add over all
query heads.  The sums over the window's ticks are the program's own
(``serving.kv.context_token_ticks``, ``serving.kv.window.token_ticks``):
a kernel that still walks pages that fell out of the window takes longer
for these bytes and reads low.  The sums are scaled to the traced window
(``window_lib``)."""
from __future__ import annotations

from layer_metrics import window_lib


def work(run):
    cfg = run.model_cfg
    kinds = cfg["layer_types"]
    el = {"bfloat16": 2, "float32": 4}[run.cell["engine"]["cache_dtype"]]
    reg = window_lib.counts(run, tick=("serving.kv.context_token_ticks",
                                       "serving.kv.window.token_ticks"))
    seen = kinds.count("full_attention") \
        * reg["serving.kv.context_token_ticks"] \
        + kinds.count("sliding_attention") \
        * reg["serving.kv.window.token_ticks"]
    kv = 2 * cfg["num_kv_heads"] * cfg["head_dim"] * el
    return {"flops": 4.0 * cfg["num_heads"] * cfg["head_dim"] * seen,
            "bytes": float(kv) * seen}
