"""Operations of a decoder with latent attention and sparse experts, as
one chip's share runs it: 2 FLOP per weight of every matrix product a
token meets whatever it is routed to (an attention layer's query,
latent, up- and output projections, a dense layer's SwiGLU, an expert
layer's router and shared experts, the sliced untied head once;
embedding look-ups not at all), 6 h F per (token, held expert) pair the
routed experts computed — the program's own count of them — and
attention's two products over the positions a layer sees.

Attention is counted in the MODEL's form, the up-projected one: a head's
score is ``nope + rope`` wide and its value ``v`` wide, 2 FLOP a
multiply-add, and ``W_kvb`` is met once a token.  The absorbed form the
program decodes with does 2 H (r + rope + r) a position instead (3.4
times as many, for a twentieth of the bytes), and a prefill chunk
up-projects its context once a chunk: both are the mechanism's price and
neither is useful work, so neither is counted here
(``kernels/mla_decode_attention/ops.py`` counts the kernel's own)."""
from __future__ import annotations


def dense_weights(cfg):
    """Weights every token multiplies in one forward pass."""
    h, nh = cfg["hidden_size"], cfg["num_heads"]
    r, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    rope, v = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    layers, dense = cfg["num_layers"], cfg["first_k_dense_replace"]
    attention = h * nh * (nope + rope) + h * (r + rope) \
        + r * nh * (nope + v) + nh * v * h
    expert_layer = h * cfg["num_experts_published"] \
        + cfg["num_shared_experts"] * 3 * h * cfg["intermediate_size"]
    return layers * attention \
        + dense * 3 * h * cfg["dense_intermediate_size"] \
        + (layers - dense) * expert_layer + h * cfg["vocab_size"]


def forward_flops(cfg, tokens, pairs, context):
    """``tokens`` tokens and ``pairs`` routed pairs (summed over the
    layers); ``context``: over those tokens, the sum of the positions one
    layer's attention sees."""
    per_position = 2.0 * cfg["num_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])
    return 2.0 * dense_weights(cfg) * tokens \
        + 6.0 * cfg["hidden_size"] * cfg["intermediate_size"] * pairs \
        + per_position * cfg["num_layers"] * context
