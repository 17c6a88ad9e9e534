"""Operations of a sparse-expert decoder with window and full attention
layers, as one chip's share runs it: 2 FLOP per weight of every matrix
product a token meets whatever it is routed to (an attention layer's
four projections, the router, the shared experts, the sliced tied head
once; embedding look-ups not at all), 6 h F per (token, held expert)
pair the routed experts computed — the program's own count of them —
and attention's two products over the positions a layer sees: the whole
context in a full layer, the latest ``sliding_window`` of it in a window
layer."""
from __future__ import annotations


def dense_weights(cfg):
    """Weights every token multiplies in one forward pass."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    qd = cfg["num_heads"] * cfg["head_dim"]
    kvd = cfg["num_kv_heads"] * cfg["head_dim"]
    layer = 2 * h * qd + 2 * h * kvd + h * cfg["num_experts_published"] \
        + cfg["num_shared_experts"] * 3 * h * f
    return cfg["num_layers"] * layer + h * cfg["vocab_size"]


def forward_flops(cfg, tokens, pairs, full_context, window_context):
    """``tokens`` tokens and ``pairs`` routed pairs (summed over the
    layers); ``full_context`` / ``window_context``: over those tokens, the
    sum of the positions a full layer's and a window layer's attention
    sees (one layer of each kind)."""
    kinds = cfg["layer_types"]
    qd = cfg["num_heads"] * cfg["head_dim"]
    att = kinds.count("full_attention") * full_context \
        + kinds.count("sliding_attention") * window_context
    return 2.0 * dense_weights(cfg) * tokens \
        + 6.0 * cfg["hidden_size"] * cfg["intermediate_size"] * pairs \
        + 4.0 * qd * att
