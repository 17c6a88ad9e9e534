"""Granite 4.0-H decoder (IBM, 2025; ``model_type`` granitemoehybrid
with no experts): Mamba-2 layers (Dao & Gu 2024, arXiv:2405.21060) and
grouped-query attention layers WITHOUT positional rotation, in the order
``layer_types`` gives, every layer closed by the same SwiGLU MLP; four
scalar multipliers (embedding, residual, attention, logits); embedding
and head tied.

The Mamba-2 mixer is the recurrence as written, one token at a time in
a ``lax.scan`` (``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t =
S_t C_t + D x_t``), float32 throughout — not the chunked form the
program prefills with.  ``reference/run.py`` calls ``layer`` without an
index: the layer's kind is told by its leaves.

**Seeded weights give the recurrence a memory.**  ``make_weights`` draws
every leaf as ``mean + N(0, std)`` with mean 0 or 1, so the published
initial ranges (``A`` in [1, 16], ``dt`` in [0.001, 0.1]: per-token
decays of 0.2 to 0.999) cannot be drawn as they are.  Drawn instead:
``A_log ~ N(0, 3)``, ``dt_bias ~ N(0, 1)``; with ``initializer_range``
0.02 the token's own ``dt`` varies by ~0.9.  The per-token decay
``exp(-softplus(dt + dt_bias) exp(A_log))`` then has, over heads
(computed with 10^6 draws): 2 % of heads above 0.999, 5 % above 0.9966,
10 % above 0.989, 25 % above 0.929, the median head 0.54, 75 % above 0.006
— 16 of the 64 heads remember for 14 tokens or more, seven for 80 or
more, three or four for 250 or more, and the upper half forgets at once,
as a head with ``A = 16`` all but does.  ``dt_bias`` is kept
narrow because a large ``dt`` also scales the token's input and would
drown the remembering heads in the gated norm.  The planted faults of
``tests/test_faults_hybrid.py`` (a state not reset, pad tokens fed to
the recurrence) show that ``served_logit_gap`` sees this memory.

**The embedding is drawn at ``initializer_range / embedding_multiplier``**,
so that the multiplied embedding enters the stream at the scale the
blocks add to it.  Drawn at ``initializer_range`` itself, 12 E[token]
would be the whole stream, the tied head would give the last token's own
logit ~45 sigma of advantage, every request would repeat its last prompt
token, and the served argmax could not see a fault in any layer.  For
the same reason the output projections are drawn at ``initializer_range``
with no 1/sqrt(2 layers) of their own: ``residual_multiplier`` is the
family's depth scaling, and 80 branches of 0.22 then outweigh the
embedding (the last token's own logit keeps ~0.5 sigma of advantage).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import mm_f32

A_LOG_STD = 3.0
DT_BIAS_STD = 1.0
CONV_STD = 0.5          # 4 taps: unit gain through the convolution


def _sizes(cfg):
    heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    d_inner = heads * p
    return heads, p, g, n, d_inner, d_inner + 2 * g * n


def weight_spec(cfg):
    h, m, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    std = cfg["initializer_range"]
    kinds = cfg["layer_types"]
    if len(kinds) != cfg["num_layers"]:
        raise ValueError(f"{len(kinds)} layer_types for "
                         f"{cfg['num_layers']} layers")
    out_std = std       # residual_multiplier is the family's depth scaling
    heads, p, g, n, d_inner, conv_dim = _sizes(cfg)
    hd = h // cfg["num_heads"]
    kv = cfg["num_kv_heads"] * hd
    spec = {"model.embed_tokens.weight":
            ((v, h), "normal", std / cfg["embedding_multiplier"]),
            "model.norm.weight": ((h,), "ones", std)}
    for i, kind in enumerate(kinds):
        q = f"model.layers.{i}."
        spec.update({
            q + "input_layernorm.weight": ((h,), "ones", std),
            q + "post_attention_layernorm.weight": ((h,), "ones", std),
            q + "shared_mlp.input_linear.weight":
                ((h, 2 * m), "normal", std),
            q + "shared_mlp.output_linear.weight":
                ((m, h), "normal", out_std)})
        if kind == "mamba":
            spec.update({
                q + "mamba.in_proj.weight":
                    ((h, d_inner + conv_dim + heads), "normal", std),
                q + "mamba.conv1d.weight":
                    ((conv_dim, cfg["mamba_d_conv"]), "normal", CONV_STD),
                q + "mamba.conv1d.bias": ((conv_dim,), "zeros", std),
                q + "mamba.dt_bias": ((heads,), "zeros", DT_BIAS_STD),
                q + "mamba.A_log": ((heads,), "normal", A_LOG_STD),
                q + "mamba.D": ((heads,), "ones", std),
                q + "mamba.norm.weight": ((d_inner,), "ones", std),
                q + "mamba.out_proj.weight":
                    ((d_inner, h), "normal", out_std)})
        elif kind == "attention":
            spec.update({
                q + "self_attn.q_proj.weight": ((h, h), "normal", std),
                q + "self_attn.k_proj.weight": ((h, kv), "normal", std),
                q + "self_attn.v_proj.weight": ((h, kv), "normal", std),
                q + "self_attn.o_proj.weight":
                    ((h, h), "normal", out_std)})
        else:
            raise ValueError(f"layer {i}: unknown kind {kind!r}")
    return spec


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w.astype(jnp.float32)


def embed(params, ids, cfg):
    return cfg["embedding_multiplier"] * \
        params["model.embed_tokens.weight"].astype(jnp.float32)[ids]


def layer_names(cfg, i):
    q = f"model.layers.{i}."
    mixer = ("mamba.in_proj.weight", "mamba.conv1d.weight",
             "mamba.conv1d.bias", "mamba.dt_bias", "mamba.A_log",
             "mamba.D", "mamba.norm.weight", "mamba.out_proj.weight") \
        if cfg["layer_types"][i] == "mamba" else \
        ("self_attn.q_proj.weight", "self_attn.k_proj.weight",
         "self_attn.v_proj.weight", "self_attn.o_proj.weight")
    return [q + s for s in (
        "input_layernorm.weight", "post_attention_layernorm.weight",
        "shared_mlp.input_linear.weight",
        "shared_mlp.output_linear.weight") + mixer]


def _attention(y, w, cfg, mm):
    """Grouped-query causal attention, no rotation, scores scaled by
    ``attention_multiplier`` (not 1/sqrt(head))."""
    b, s, h = y.shape
    nh, nkv = cfg["num_heads"], cfg["num_kv_heads"]
    d = h // nh
    q = mm(y, w["self_attn.q_proj.weight"]).reshape(b, s, nh, d)
    k = mm(y, w["self_attn.k_proj.weight"]).reshape(b, s, nkv, d)
    v = mm(y, w["self_attn.v_proj.weight"]).reshape(b, s, nkv, d)
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)
    hi = jax.lax.Precision.HIGHEST
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hi) * \
        cfg["attention_multiplier"]
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None, None], sc,
                   -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v,
                   precision=hi)
    return mm(a.reshape(b, s, h), w["self_attn.o_proj.weight"])


def _mamba(y, w, cfg, mm, n_valid=None, keep=None):
    """The mixer's output and the state after the last token — or, with
    ``n_valid``, after that many tokens: the positions past them carry
    ``dt = 0``.  ``keep`` rounds the state after every token: the
    lower-precision control of the state's type."""
    b, s, _ = y.shape
    heads, p, g, n, d_inner, conv_dim = _sizes(cfg)
    taps = cfg["mamba_d_conv"]
    f32 = jnp.float32
    proj = mm(y, w["mamba.in_proj.weight"])
    z, xbc, dt = jnp.split(proj, [d_inner, d_inner + conv_dim], axis=-1)
    # causal depthwise convolution: tap k weighs the token taps-1-k back
    wc = w["mamba.conv1d.weight"].astype(f32)
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(padded[:, k:k + s] * wc[:, k] for k in range(taps))
    xbc = jax.nn.silu(conv + w["mamba.conv1d.bias"].astype(f32))
    x, bm, cm = jnp.split(xbc, [d_inner, d_inner + g * n], axis=-1)
    x = x.reshape(b, s, heads, p)
    bm = jnp.repeat(bm.reshape(b, s, g, n), heads // g, axis=2)
    cm = jnp.repeat(cm.reshape(b, s, g, n), heads // g, axis=2)
    dt = jax.nn.softplus(dt + w["mamba.dt_bias"].astype(f32))
    if n_valid is not None:
        dt = jnp.where((jnp.arange(s) < n_valid)[None, :, None], dt, 0.0)
    a = -jnp.exp(w["mamba.A_log"].astype(f32))

    def token(state, inp):
        x_t, b_t, c_t, dt_t = inp          # [b,H,P] [b,H,N] [b,H,N] [b,H]
        state = state * jnp.exp(dt_t * a)[:, :, None, None] + \
            (dt_t[:, :, None] * x_t)[..., None] * b_t[:, :, None, :]
        if keep is not None:
            state = keep(state)
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)

    first = lambda t: jnp.moveaxis(t, 1, 0)  # noqa: E731
    last, ys = jax.lax.scan(token, jnp.zeros((b, heads, p, n), f32),
                            (first(x), first(bm), first(cm), first(dt)))
    out = jnp.moveaxis(ys, 0, 1) + \
        w["mamba.D"].astype(f32)[None, None, :, None] * x
    # the gate goes in before the norm, which is over all channels
    out = out.reshape(b, s, d_inner) * jax.nn.silu(z)
    out = _rms(out, w["mamba.norm.weight"], cfg["rms_norm_eps"])
    return mm(out, w["mamba.out_proj.weight"]), last


def layer_and_state(x, w, cfg, mm=mm_f32, n_valid=None, keep=None):
    """(the layer's output, the recurrent state [B, H, P, N] a Mamba-2
    layer holds after ``n_valid`` tokens — None for an attention layer)."""
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    y = _rms(x, w["input_layernorm.weight"], eps)
    if "mamba.in_proj.weight" in w:
        mixed, state = _mamba(y, w, cfg, mm, n_valid, keep)
    else:
        mixed, state = _attention(y, w, cfg, mm), None
    x = x + res * mixed
    y = _rms(x, w["post_attention_layernorm.weight"], eps)
    gate, up = jnp.split(mm(y, w["shared_mlp.input_linear.weight"]), 2,
                         axis=-1)
    return x + res * mm(jax.nn.silu(gate) * up,
                        w["shared_mlp.output_linear.weight"]), state


def layer(x, w, cfg, mm=mm_f32):
    return layer_and_state(x, w, cfg, mm)[0]


def head(params, x, cfg, mm=mm_f32):
    y = _rms(x, params["model.norm.weight"], cfg["rms_norm_eps"])
    return mm(y, params["model.embed_tokens.weight"].T) / \
        cfg["logits_scaling"]


HEAD_NAMES = ("model.norm.weight", "model.embed_tokens.weight")
EMBED_NAMES = ("model.embed_tokens.weight",)
