"""GPT-2/GPT-3 decoder (Radford et al. 2019; Brown et al. 2020): learned
positions, pre-LayerNorm blocks, fused QKV projection laid out
[3, heads, head_dim], tanh-GELU MLP, tied output head."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import causal_attention, mm_f32


def weight_spec(cfg):
    h, m, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    n, std = cfg["num_layers"], cfg["initializer_range"]
    out_std = std / math.sqrt(2 * n)
    spec = {"gpt.wte.weight": ((v, h), "normal", std),
            "gpt.wpe.weight": ((cfg["max_seq_len"], h), "normal", std),
            "gpt.ln_f.weight": ((h,), "ones", std),
            "gpt.ln_f.bias": ((h,), "zeros", std)}
    for i in range(n):
        p = f"gpt.h.{i}."
        spec.update({
            p + "ln_1.weight": ((h,), "ones", std),
            p + "ln_1.bias": ((h,), "zeros", std),
            p + "attn.qkv_proj.weight": ((h, 3 * h), "normal", std),
            p + "attn.qkv_proj.bias": ((3 * h,), "zeros", std),
            p + "attn.out_proj.weight": ((h, h), "normal", out_std),
            p + "attn.out_proj.bias": ((h,), "zeros", std),
            p + "ln_2.weight": ((h,), "ones", std),
            p + "ln_2.bias": ((h,), "zeros", std),
            p + "mlp.fc_in.weight": ((h, m), "normal", std),
            p + "mlp.fc_in.bias": ((m,), "zeros", std),
            p + "mlp.fc_out.weight": ((m, h), "normal", out_std),
            p + "mlp.fc_out.bias": ((h,), "zeros", std)})
    return spec


def _ln(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32) \
        + b.astype(jnp.float32)


def embed(params, ids, cfg):
    s = ids.shape[1]
    return params["gpt.wte.weight"].astype(jnp.float32)[ids] + \
        params["gpt.wpe.weight"].astype(jnp.float32)[jnp.arange(s)][None]


def layer_names(cfg, i):
    p = f"gpt.h.{i}."
    return [p + s for s in (
        "ln_1.weight", "ln_1.bias", "attn.qkv_proj.weight",
        "attn.qkv_proj.bias", "attn.out_proj.weight", "attn.out_proj.bias",
        "ln_2.weight", "ln_2.bias", "mlp.fc_in.weight", "mlp.fc_in.bias",
        "mlp.fc_out.weight", "mlp.fc_out.bias")]


def layer(x, w, cfg, mm=mm_f32):
    """One block; ``w`` holds that layer's leaves under their suffixes."""
    b, s, h = x.shape
    nh = cfg["num_heads"]
    eps = cfg["layer_norm_eps"]
    f32 = jnp.float32
    y = _ln(x, w["ln_1.weight"], w["ln_1.bias"], eps)
    qkv = mm(y, w["attn.qkv_proj.weight"]) + \
        w["attn.qkv_proj.bias"].astype(f32)
    qkv = qkv.reshape(b, s, 3, nh, h // nh)
    a = causal_attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2])
    x = x + mm(a.reshape(b, s, h), w["attn.out_proj.weight"]) + \
        w["attn.out_proj.bias"].astype(f32)
    y = _ln(x, w["ln_2.weight"], w["ln_2.bias"], eps)
    y = mm(y, w["mlp.fc_in.weight"]) + w["mlp.fc_in.bias"].astype(f32)
    y = jax.nn.gelu(y, approximate=True)
    return x + mm(y, w["mlp.fc_out.weight"]) + \
        w["mlp.fc_out.bias"].astype(f32)


def head(params, x, cfg, mm=mm_f32):
    x = _ln(x, params["gpt.ln_f.weight"], params["gpt.ln_f.bias"],
            cfg["layer_norm_eps"])
    return mm(x, params["gpt.wte.weight"].T)


HEAD_NAMES = ("gpt.ln_f.weight", "gpt.ln_f.bias", "gpt.wte.weight")
EMBED_NAMES = ("gpt.wte.weight", "gpt.wpe.weight")
