"""Decode attention over the paged cache of a hybrid decoder: as
``kernels/paged_decode_attention``, counted over the layers that
``layer_types`` calls attention and no others — every decoded token
reads the K and V of its whole context once, in the pages' type, in each
of them; QK^T and PV are 2 FLOP a multiply-add over all query heads."""
from __future__ import annotations


def work(run):
    cfg = run.model_cfg
    h, heads = cfg["hidden_size"], cfg["num_heads"]
    layers = cfg["layer_types"].count("attention")
    el = {"bfloat16": 2, "float32": 4, "int8": 1}[
        run.cell["engine"]["cache_dtype"]]
    ctx = run.records["decode_ctx"]        # summed context of the tokens
    per_token = 2 * layers * cfg["num_kv_heads"] * (h // heads) * el
    return {"flops": 4.0 * layers * h * ctx,
            "bytes": float(per_token) * ctx}
