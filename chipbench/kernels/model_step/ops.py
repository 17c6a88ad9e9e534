"""Operations of the whole model per token, from the configuration's
sizes alone: 2 FLOP per weight of every matrix product (the output head
counted once, embedding look-ups not at all) plus attention's two
products over the context.  Backward is twice forward; recomputed
operations do not count."""
from __future__ import annotations


def matmul_weights(cfg):
    """Weights that a token multiplies in one forward pass."""
    h, m, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    n = cfg["num_layers"]
    if "num_kv_heads" in cfg:       # grouped-query attention, SwiGLU
        kv = cfg["num_kv_heads"] * (h // cfg["num_heads"])
        layer = 2 * h * h + 2 * h * kv + 3 * h * m
    else:                           # fused QKV, two-matrix MLP
        layer = 4 * h * h + 2 * h * m
    return n * layer + h * v


def forward_flops(cfg, tokens, context_sum):
    """``tokens`` tokens whose context lengths add up to ``context_sum``
    (each token attends to its context: QK^T and PV, 2 FLOP each per
    head dimension)."""
    return 2.0 * matmul_weights(cfg) * tokens + \
        4.0 * cfg["num_layers"] * cfg["hidden_size"] * context_sum


def train_flops(cfg, batch, seq, steps):
    tokens = batch * seq * steps
    ctx = steps * batch * seq * (seq + 1) / 2.0     # causal
    return 3.0 * forward_flops(cfg, tokens, ctx)
