"""Operations of a per-layer stack whose blocks are a Mamba-2 mixer, an
attention mixer or a latent expert layer each (``layer_types``), as one
chip's share runs it: 2 FLOP per weight of every matrix product a token
meets whatever it is routed to (a Mamba-2 layer's two projections, an
attention layer's four, an expert layer's router, its two latent
projections and its shared expert, the sliced untied head once;
embedding look-ups not at all), the recurrence's ~6 FLOP per state
element in a Mamba-2 layer (its convolution's 2 x 4 a channel is not
counted), 4 L F per (token, held expert) pair the latent routed experts
computed — the program's own count of them — and attention's two
products over the positions the attention layers see."""
from __future__ import annotations


def dense_weights(cfg):
    """Weights every token multiplies in one forward pass."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, n = cfg["mamba_n_heads"], cfg["mamba_d_state"]
    d_inner = heads * cfg["mamba_d_head"]
    qd = cfg["num_heads"] * cfg["head_dim"]
    kvd = cfg["num_kv_heads"] * cfg["head_dim"]
    per_kind = {
        "mamba": h * (2 * d_inner + 2 * cfg["mamba_n_groups"] * n + heads)
        + d_inner * h,
        "attention": 2 * h * qd + 2 * h * kvd,
        "moe": h * cfg["num_experts_published"]
        + 2 * h * cfg["moe_latent_size"]
        + cfg["num_shared_experts"] * 2 * h * cfg["shared_intermediate_size"]}
    return sum(per_kind[kind] for kind in cfg["layer_types"]) + h * v


def forward_flops(cfg, tokens, pairs, context):
    """``tokens`` tokens and ``pairs`` routed pairs (summed over the
    layers); ``context``: over those tokens, the sum of the positions one
    attention layer sees."""
    kinds = cfg["layer_types"]
    state = cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]
    qd = cfg["num_heads"] * cfg["head_dim"]
    return (2.0 * dense_weights(cfg) + 6.0 * state * kinds.count("mamba")) \
        * tokens \
        + 4.0 * cfg["moe_latent_size"] * cfg["intermediate_size"] * pairs \
        + 4.0 * qd * kinds.count("attention") * context
