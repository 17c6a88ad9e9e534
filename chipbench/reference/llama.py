"""Llama-architecture decoder as Mistral-7B uses it (Jiang et al. 2023,
arXiv:2310.06825; v0.3 config: no sliding window): RMS-norm pre-norm,
rotary positions (rotate-half), grouped-query attention, SwiGLU MLP,
untied output head."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import causal_attention, mm_f32


def weight_spec(cfg):
    h, m, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    n, std = cfg["num_layers"], cfg["initializer_range"]
    kv = cfg["num_kv_heads"] * (h // cfg["num_heads"])
    out_std = std / math.sqrt(2 * n)
    spec = {"llama.embed_tokens.weight": ((v, h), "normal", std),
            "llama.norm.weight": ((h,), "ones", std),
            "lm_head.weight": ((h, v), "normal", std)}
    for i in range(n):
        p = f"llama.layers.{i}."
        spec.update({
            p + "input_layernorm.weight": ((h,), "ones", std),
            p + "self_attn.q_proj.weight": ((h, h), "normal", std),
            p + "self_attn.k_proj.weight": ((h, kv), "normal", std),
            p + "self_attn.v_proj.weight": ((h, kv), "normal", std),
            p + "self_attn.o_proj.weight": ((h, h), "normal", out_std),
            p + "post_attention_layernorm.weight": ((h,), "ones", std),
            p + "mlp.gate_proj.weight": ((h, m), "normal", std),
            p + "mlp.up_proj.weight": ((h, m), "normal", std),
            p + "mlp.down_proj.weight": ((m, h), "normal", out_std)})
    return spec


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w.astype(jnp.float32)


def _rope(x, theta):
    b, s, h, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def embed(params, ids, cfg):
    return params["llama.embed_tokens.weight"].astype(jnp.float32)[ids]


def layer_names(cfg, i):
    p = f"llama.layers.{i}."
    return [p + s for s in (
        "input_layernorm.weight", "self_attn.q_proj.weight",
        "self_attn.k_proj.weight", "self_attn.v_proj.weight",
        "self_attn.o_proj.weight", "post_attention_layernorm.weight",
        "mlp.gate_proj.weight", "mlp.up_proj.weight",
        "mlp.down_proj.weight")]


def layer(x, w, cfg, mm=mm_f32):
    b, s, h = x.shape
    nh, nkv = cfg["num_heads"], cfg["num_kv_heads"]
    d = h // nh
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    y = _rms(x, w["input_layernorm.weight"], eps)
    q = mm(y, w["self_attn.q_proj.weight"]).reshape(b, s, nh, d)
    k = mm(y, w["self_attn.k_proj.weight"]).reshape(b, s, nkv, d)
    v = mm(y, w["self_attn.v_proj.weight"]).reshape(b, s, nkv, d)
    a = causal_attention(_rope(q, theta), _rope(k, theta), v)
    x = x + mm(a.reshape(b, s, h), w["self_attn.o_proj.weight"])
    y = _rms(x, w["post_attention_layernorm.weight"], eps)
    y = jax.nn.silu(mm(y, w["mlp.gate_proj.weight"])) * \
        mm(y, w["mlp.up_proj.weight"])
    return x + mm(y, w["mlp.down_proj.weight"])


def head(params, x, cfg, mm=mm_f32):
    return mm(_rms(x, params["llama.norm.weight"], cfg["rms_norm_eps"]),
              params["lm_head.weight"])


HEAD_NAMES = ("llama.norm.weight", "lm_head.weight")
EMBED_NAMES = ("llama.embed_tokens.weight",)
