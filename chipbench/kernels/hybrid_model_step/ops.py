"""Operations of a hybrid decoder per token, by layer kind from
``layer_types``: 2 FLOP per weight of every matrix product (a Mamba-2
layer's two projections, an attention layer's four, the shared SwiGLU
MLP of every layer, the tied head once; embedding look-ups not at all),
attention's two products over the context for the attention layers
only, and the recurrence's ~6 FLOP per state element for the Mamba-2
layers (its convolution's 2 x 4 a channel is not counted)."""
from __future__ import annotations


def matmul_weights(cfg):
    """Weights that a token multiplies in one forward pass."""
    h, m, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    heads, n = cfg["mamba_n_heads"], cfg["mamba_d_state"]
    d_inner = heads * cfg["mamba_d_head"]
    kv = cfg["num_kv_heads"] * (h // cfg["num_heads"])
    mlp = 3 * h * m
    mixer = {
        "mamba": h * (2 * d_inner + 2 * cfg["mamba_n_groups"] * n + heads)
        + d_inner * h,
        "attention": 2 * h * h + 2 * h * kv}
    return sum(mixer[kind] + mlp for kind in cfg["layer_types"]) + h * v


def forward_flops(cfg, tokens, context_sum):
    """``tokens`` tokens whose context lengths add up to ``context_sum``
    (a token attends to its context in the attention layers: QK^T and
    PV, 2 FLOP each per head dimension)."""
    kinds = cfg["layer_types"]
    state = cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]
    return (2.0 * matmul_weights(cfg) + 6.0 * state * kinds.count("mamba")) \
        * tokens + 4.0 * kinds.count("attention") * cfg["hidden_size"] \
        * context_sum
