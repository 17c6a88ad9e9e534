"""Single-token latent attention in the absorbed form: a decoded token
reads the ONE cached row of every position of its context once a layer,
``kv_lora_rank + qk_rope_head_dim`` values in the pages' type — 576 x 2
bytes whatever lanes the row lives in, so a store that pads its rows
reads low — and does 2 FLOP a multiply-add for every head's score over
the whole row and every head's weighted sum over its latent part: 2 H
(576 + 512) a position.  The absorb products (``W_uk`` on the query,
``W_uv`` on the output: 2 x 2 H 128 x 512 FLOP a token a layer, whatever
the context) are XLA's and are left out on both sides: neither their
time nor their work is here.  The sum of contexts over the window's
ticks is the program's own (``serving.kv.context_token_ticks``), scaled
to the traced window (``window_lib``)."""
from __future__ import annotations

from layer_metrics import window_lib


def work(run):
    cfg = run.model_cfg
    el = {"bfloat16": 2, "float32": 4}[run.cell["engine"]["cache_dtype"]]
    reg = window_lib.counts(run, tick=("serving.kv.context_token_ticks",))
    seen = cfg["num_layers"] * reg["serving.kv.context_token_ticks"]
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return {"flops": 2.0 * cfg["num_heads"] * (rank + rope + rank) * seen,
            "bytes": float((rank + rope) * el) * seen}
