"""The routed experts' grouped products (gate and up fused, then down):
6 h F FLOP a (token, held expert) pair; the bytes are the three matrices
of every expert that had a pair, read once a program (the program's own
counts: ``serving.moe.experts_hit`` is summed over layers and programs),
plus a pair's activations in and out of the two products.  A kernel that
reads an expert's weights once a row tile instead of once reads low.
The counts are scaled to the traced window (``window_lib``)."""
from __future__ import annotations

from layer_metrics import window_lib


def work(run):
    cfg = run.model_cfg
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    el = {"bfloat16": 2, "float32": 4}[run.cell["weights_dtype"]]
    reg = window_lib.counts(run, split=(
        ("serving.moe.pairs_local", "serving.moe.prefill.pairs"),
        ("serving.moe.experts_hit", "serving.moe.prefill.experts_hit")))
    pairs = reg["serving.moe.pairs_local"]
    hit = reg["serving.moe.experts_hit"]
    return {"flops": 6.0 * h * f * pairs,
            "bytes": float(el) * (3.0 * h * f * hit
                                  + 2.0 * (h + f) * pairs)}
