"""Cohere ``cohere2_moe`` decoder (Command A+ 218B-A25B, CohereLabs
2026-05), one chip's share of it, as plain jax.numpy in float32.

Per layer, with ``h`` hidden, ``H`` query heads, ``H_kv`` KV heads of
``D``, window ``W``, ``E`` published experts of which ``k`` a token and
``S`` shared, expert width ``F``:

    n = LayerNorm(x)            (x - mean) / sqrt(var + eps) * w, no bias
    x <- x + Attn_i(n) + FFN(n)                 (parallel block)

    Attn_i: q = n Wq [H, D]; k, v = n Wk, n Wv [H_kv, D]; query head j
      reads KV head j // (H / H_kv); scores / sqrt(D); causal.
      sliding_attention: q, k rotated by position, theta, over the
        INTERLEAVED pairs (x_2m, x_2m+1); key t visible to query s iff
        s - W < t <= s.
      full_attention: no rotation; every t <= s.
    FFN: r = n Wr [E] in float32; s = sigmoid(r); T = the k largest;
      g_e = s_e / sum_T s; f_e(n) = (silu(n Wg_e) * (n Wu_e)) Wd_e;
      FFN(n) = sum_{e in T} g_e f_e(n) + (1/S) sum_j f^shared_j(n).

    logits = logit_scale * LayerNorm_f(x) E_emb^T       (tied)

**The chip's share.**  ``held_experts`` = [first, count]: the layer
routes over all E, normalises over all k chosen, and computes
``sum_{e in T, e held} g_e f_e(n)`` plus the shared term.  What the
absent experts would add is left out and the partial result goes on to
the next layer — in the program alike.

Departures from the published description, each for the harness's sake:

- ``reference/run.py`` calls ``layer`` without an index and every layer
  has the same leaves, so the stream carries its layer counter: ``embed``
  returns ``(x, 0)``, ``layer`` maps ``(x, i)`` to ``(x', i + 1)`` and
  reads the layer's kind from ``layer_types[i]`` (a traced look-up: the
  rotation and the window's lower bound are computed and selected),
  ``head`` drops the counter.
- attention runs in blocks of ``Q_BLOCK`` queries (the harness pads a
  request to the slot length: 128 x 8192^2 float32 scores are 34 GB
  whole); a block's scores are dense over all keys.
- the expert part is dense over the HELD experts with the gate as a
  mask (0 where the token did not choose the expert), one expert at a
  time.
- the head and the embedding are the vocabulary's slice the chip holds.

**Seeded weights.**  Every leaf is ``mean + N(0, std)`` at
``initializer_range`` itself; the norm scales are ``1 + N(0, std)``.  No
projection is depth-scaled.  At 0.02 and hidden 4096 a router logit has
std ~1.3 over tokens after the unit-variance norm, so sigmoid scores
spread over (0.05, 0.95) and the top 8 of 128 change from token to
token: all held experts are hit, unevenly.  Two things follow.  (1) The
head is tied: a token's own logit is ``|e|^2 / sigma_x`` against a
spread of ``sqrt(h) sigma_e`` for the rest, so what the layers add to
the stream has to outweigh the embedding ~30 times or every request
repeats its last token and no fault shows in the served argmax; the
four layers add 0.8, 1.0, 1.8 and 2.5 an element (read on the chip, PR
32) to an embedding of 0.02.  (2) A router is a discontinuity:
bfloat16's rounding of the normed stream moves a router logit by ~0.003
against a spacing of ~0.08 at the eighth of 128 scores, so ~4 % of
tokens a layer choose another expert than the float32 reference does,
and each such flip moves the stream by ~4 %.  ``served_logit_gap``
therefore reads the program's own flips beside its roundings and cannot
be tight; the routed experts are held by a number of their own,
``routed_gap`` (``drive_serve_moe.py``), read through ``routed_part``
below at the positions where program and reference chose the same
experts.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import mm_f32

Q_BLOCK = 256
_HI = jax.lax.Precision.HIGHEST


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_heads"], cfg["num_kv_heads"],
            cfg["head_dim"], cfg["intermediate_size"])


def weight_spec(cfg):
    h, nh, nkv, d, f = _dims(cfg)
    n, std = cfg["num_layers"], cfg["initializer_range"]
    held = cfg["held_experts"][1]
    shared = cfg["num_shared_experts"]
    spec = {"model.embed_tokens.weight":
            ((cfg["vocab_size"], h), "normal", std),
            "model.norm.weight": ((h,), "ones", std)}
    for i in range(n):
        p = f"model.layers.{i}."
        spec.update({
            p + "input_layernorm.weight": ((h,), "ones", std),
            p + "self_attn.q_proj.weight": ((h, nh * d), "normal", std),
            p + "self_attn.k_proj.weight": ((h, nkv * d), "normal", std),
            p + "self_attn.v_proj.weight": ((h, nkv * d), "normal", std),
            p + "self_attn.o_proj.weight": ((nh * d, h), "normal", std),
            p + "mlp.gate.weight":
                ((h, cfg["num_experts_published"]), "normal", std),
            p + "mlp.experts.gate_proj": ((held, h, f), "normal", std),
            p + "mlp.experts.up_proj": ((held, h, f), "normal", std),
            p + "mlp.experts.down_proj": ((held, f, h), "normal", std),
            p + "mlp.shared_experts.gate_proj":
                ((shared, h, f), "normal", std),
            p + "mlp.shared_experts.up_proj":
                ((shared, h, f), "normal", std),
            p + "mlp.shared_experts.down_proj":
                ((shared, f, h), "normal", std)})
    return spec


def layer_names(cfg, i):
    p = f"model.layers.{i}."
    return [p + s for s in (
        "input_layernorm.weight", "self_attn.q_proj.weight",
        "self_attn.k_proj.weight", "self_attn.v_proj.weight",
        "self_attn.o_proj.weight", "mlp.gate.weight",
        "mlp.experts.gate_proj", "mlp.experts.up_proj",
        "mlp.experts.down_proj", "mlp.shared_experts.gate_proj",
        "mlp.shared_experts.up_proj", "mlp.shared_experts.down_proj")]


def _layer_norm(x, w, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope_pairs(x, theta):
    """Rotate [B, S, H, D] by position over the pairs (x_2m, x_2m+1)."""
    b, s, h, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]   # [S, D/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _attention(q, k, v, window):
    """q [B,S,H,D], k/v [B,S,Hkv,D]; ``window``: a traced int, the number
    of latest positions a query sees (S or more: all).  In blocks of
    ``Q_BLOCK`` queries, each dense over all keys."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    blk = Q_BLOCK if s % Q_BLOCK == 0 else s
    qb = q.reshape(b, s // blk, blk, hkv, rep, d).transpose(1, 0, 2, 3, 4, 5)
    kp = jnp.arange(s)[None, :]

    def one(args):
        qi, start = args
        sc = jnp.einsum("bqhrd,bkhd->bhrqk", qi, k, precision=_HI) \
            / math.sqrt(d)
        qp = start + jnp.arange(blk)[:, None]
        mask = (kp <= qp) & (kp > qp - window)
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhrqk,bkhd->bqhrd", p, v, precision=_HI)

    out = jax.lax.map(one, (qb, jnp.arange(0, s, blk)))
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, h * d)


def _expert(y, wg, wu, wd, mm):
    return mm(jax.nn.silu(mm(y, wg)) * mm(y, wu), wd)


def _routed(y, w, cfg, mm, expert_mm=None):
    """The held routed experts' part of ``FFN(y)`` and the experts each
    token chose ([B, S, k]); ``expert_mm`` is the experts' product where
    it is not the router's (the expert-only control)."""
    expert_mm = expert_mm or mm
    k = cfg["num_experts_per_tok"]
    first, held = cfg["held_experts"]
    scores = jax.nn.sigmoid(mm(y, w["mlp.gate.weight"]))        # [B,S,E]
    top, idx = jax.lax.top_k(scores, k)
    gates = top / jnp.sum(top, -1, keepdims=True)
    # gate of every published expert for every token, 0 where not chosen
    dense = jnp.sum(jax.nn.one_hot(idx, scores.shape[-1],
                                   dtype=jnp.float32)
                    * gates[..., None], axis=-2)                # [B,S,E]
    out = jnp.zeros_like(y)
    for e in range(held):
        f = _expert(y, w["mlp.experts.gate_proj"][e],
                    w["mlp.experts.up_proj"][e],
                    w["mlp.experts.down_proj"][e], expert_mm)
        out = out + dense[..., first + e, None] * f
    return out, idx


def _ffn(y, w, cfg, mm):
    out, _ = _routed(y, w, cfg, mm)
    shared = cfg["num_shared_experts"]
    for j in range(shared):
        out = out + _expert(y, w["mlp.shared_experts.gate_proj"][j],
                            w["mlp.shared_experts.up_proj"][j],
                            w["mlp.shared_experts.down_proj"][j],
                            mm) / shared
    return out


def embed(params, ids, cfg):
    x = params["model.embed_tokens.weight"].astype(jnp.float32)[ids]
    return x, jnp.int32(0)


def layer(stream, w, cfg, mm=mm_f32):
    x, i = stream
    b, s, h = x.shape
    _, nh, nkv, d, _ = _dims(cfg)
    sliding = jnp.asarray([kind == "sliding_attention"
                           for kind in cfg["layer_types"]])[i]
    y = _layer_norm(x, w["input_layernorm.weight"], cfg["layer_norm_eps"])
    q = mm(y, w["self_attn.q_proj.weight"]).reshape(b, s, nh, d)
    k = mm(y, w["self_attn.k_proj.weight"]).reshape(b, s, nkv, d)
    v = mm(y, w["self_attn.v_proj.weight"]).reshape(b, s, nkv, d)
    theta = cfg["rope_theta"]
    q = jnp.where(sliding, _rope_pairs(q, theta), q)
    k = jnp.where(sliding, _rope_pairs(k, theta), k)
    window = jnp.where(sliding, cfg["sliding_window"], s + 1)
    a = mm(_attention(q, k, v, window), w["self_attn.o_proj.weight"])
    return x + a + _ffn(y, w, cfg, mm), i + 1


def routed_part(stream, w, cfg, mm=mm_f32, expert_mm=None):
    """What ``drive_serve_moe`` holds the routed experts by: the normed
    input of the layer ``stream`` is about to enter, the held routed
    experts' part of its FFN for that input, and the chosen experts."""
    y = _layer_norm(stream[0], w["input_layernorm.weight"],
                    cfg["layer_norm_eps"])
    out, idx = _routed(y, w, cfg, mm, expert_mm)
    return y, out, idx


def head(params, stream, cfg, mm=mm_f32):
    x, _ = stream
    y = _layer_norm(x, params["model.norm.weight"], cfg["layer_norm_eps"])
    return cfg["logit_scale"] * mm(y, params["model.embed_tokens.weight"].T)


HEAD_NAMES = ("model.norm.weight", "model.embed_tokens.weight")
EMBED_NAMES = ("model.embed_tokens.weight",)
