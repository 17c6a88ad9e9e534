"""The LATENT routed experts' grouped products (the relu² up product,
then down, both inside the ``moe_latent_size``-wide latent): 4 L F FLOP
a (token, held expert) pair; the bytes are the two matrices of every
expert that had a pair, read once a program (the program's own counts:
``serving.moe.experts_hit`` is summed over layers and programs), plus a
pair's activations in and out of the two products.  The latent
projections around them are dense products of every token, not
counted here.  The counts are scaled to the traced window
(``window_lib``)."""
from __future__ import annotations

from layer_metrics import window_lib


def work(run):
    cfg = run.model_cfg
    lat, f = cfg["moe_latent_size"], cfg["intermediate_size"]
    el = {"bfloat16": 2, "float32": 4}[run.cell["weights_dtype"]]
    reg = window_lib.counts(run, split=(
        ("serving.moe.pairs_local", "serving.moe.prefill.pairs"),
        ("serving.moe.experts_hit", "serving.moe.prefill.experts_hit")))
    pairs = reg["serving.moe.pairs_local"]
    hit = reg["serving.moe.experts_hit"]
    return {"flops": 4.0 * lat * f * pairs,
            "bytes": float(el) * (2.0 * lat * f * hit
                                  + 2.0 * (lat + f) * pairs)}
