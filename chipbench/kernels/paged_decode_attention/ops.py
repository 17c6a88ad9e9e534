"""Decode attention over the paged cache: every decoded token reads the
K and V of its whole context once, in the pages' type, in every layer;
QK^T and PV are 2 FLOP a multiply-add over all query heads."""
from __future__ import annotations


def work(run):
    cfg = run.model_cfg
    h, heads = cfg["hidden_size"], cfg["num_heads"]
    kv_heads = cfg.get("num_kv_heads", heads)
    el = {"bfloat16": 2, "float32": 4, "int8": 1}[
        run.cell["engine"]["cache_dtype"]]
    ctx = run.records["decode_ctx"]        # summed context of the tokens
    per_token = 2 * cfg["num_layers"] * kv_heads * (h // heads) * el
    return {"flops": 4.0 * cfg["num_layers"] * h * ctx,
            "bytes": float(per_token) * ctx}
