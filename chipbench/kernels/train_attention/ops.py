"""Causal self-attention of a training step, forward and backward, at
the cell's shapes.  Forward: QK^T and PV over the causal half, 2 FLOP a
multiply-add; backward: dV, dP, dQ, dK, twice forward (the flash
backward's recomputation of P does not count).  Bytes: Q, K, V, O once
each forward; Q, K, V, O, dO read and dQ, dK, dV written backward, in
the compute type."""
from __future__ import annotations


def work(run):
    cfg, mix = run.model_cfg, run.mix
    b, s, h = mix["batch"], mix["seq"], cfg["hidden_size"]
    kv_share = cfg.get("num_kv_heads", cfg["num_heads"]) / cfg["num_heads"]
    steps, layers = run.records["steps"], cfg["num_layers"]
    fwd = 4.0 * b * s * (s + 1) / 2.0 * h
    el = 2                                     # bfloat16
    qo, kv = b * s * h * el, b * s * h * el * kv_share
    bytes_ = (2 * qo + 2 * kv) + (3 * qo + 2 * kv + qo + 2 * kv)
    return {"flops": 3.0 * fwd * layers * steps,
            "bytes": float(bytes_) * layers * steps}
