"""The four projections of self-attention in a training step (query,
key, value, output), forward and backward, at the cell's shapes: (2 + 2
kv_share) d^2 multiply-adds a token a layer forward, twice that backward:
24 d^2 FLOP a token a layer with as many key-value heads as query heads.
The scores and the weighted values are kernels/train_attention's.
Bytes: the weights read forward and backward and their gradients
written, the projections' inputs and outputs, in the compute type."""
from __future__ import annotations


def work(run):
    cfg, mix = run.model_cfg, run.mix
    d = cfg["hidden_size"]
    kv_share = cfg.get("num_kv_heads", cfg["num_heads"]) / cfg["num_heads"]
    # a layer's projection weights: as many multiply-adds a token
    params = (2.0 + 2.0 * kv_share) * d * d
    tokens = mix["batch"] * mix["seq"]
    steps, layers = run.records["steps"], cfg["num_layers"]
    el = 2 if run.cell["compute_dtype"] == "bfloat16" else 4
    weights = 3 * params * el
    acts = 3 * tokens * (4.0 + 2.0 * kv_share) * d * el
    return {"flops": 3.0 * 2.0 * params * tokens * layers * steps,
            "bytes": float(weights + acts) * layers * steps}
