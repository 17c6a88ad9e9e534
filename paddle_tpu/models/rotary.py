"""Rotary positions by frequency table, as the families that rotate by
YaRN share them (``models/sarvam_mla.py``, ``models/mellum.py``).

A rotation turns each PAIR of a head's values by ``pos * inv_freq[m]``.
The pairs are interleaved, ``(x_2m, x_2m+1)`` (DeepSeek's and GPT-J's
layout), or halves, ``(x_m, x_{m+D/2})`` (transformers' ``rotate_half``).
cos and sin may be multiplied by a factor (YaRN's attention factor).

YaRN's frequencies (``yarn_inv_freq``) blend the plain ``theta^(-2m/D)``
and the same over ``factor`` along a linear ramp between the correction
dims of ``beta_fast`` and ``beta_slow`` over the original context; the
same in transformers' ``yarn`` and DeepSeek's ``deepseek_yarn``.  They
differ in what multiplies cos and sin (``yarn_cos_sin_scale``) and in
DeepSeek's softmax scale (``yarn_softmax_scale``).
"""
from __future__ import annotations

import math

import jax.numpy as jnp


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_softmax_scale(q_head_dim, scaling):
    """``q_head_dim^-1/2 * mscale(factor, mscale_all_dim)^2``."""
    m = _yarn_mscale(scaling["factor"], scaling.get("mscale_all_dim", 0.0)) \
        if scaling.get("mscale_all_dim", 0.0) else 1.0
    return m * m / math.sqrt(q_head_dim)


def yarn_cos_sin_scale(scaling):
    """What multiplies cos and sin: ``attention_factor`` where the
    scaling names one, else ``mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)`` (1 for equal multipliers; ``0.1 ln(factor) + 1``
    with neither, as transformers' default)."""
    if "attention_factor" in scaling:
        return float(scaling["attention_factor"])
    f = scaling["factor"]
    return _yarn_mscale(f, scaling.get("mscale", 1.0)) / \
        _yarn_mscale(f, scaling.get("mscale_all_dim", 0.0))


def default_inv_freq(dim, theta):
    """float32 [dim / 2]: ``theta^(-2m/dim)``."""
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def yarn_inv_freq(dim, theta, scaling):
    """float32 [dim / 2]: per rotated pair, the blend of the plain
    frequency ``theta^(-2m/dim)`` (kept where the pair turns more than
    ``beta_fast`` times over the original context) and the same over
    ``factor`` (where it turns fewer than ``beta_slow`` times), linear
    between the two correction dims."""
    factor = float(scaling["factor"])
    orig = scaling["original_max_position_embeddings"]

    def correction_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    plain = default_inv_freq(dim, theta)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def rope_pairs(x, pos, inv_freq, mult=1.0, halves=False):
    """Rotate ``x`` [B, S, ..., D] by ``pos`` [B, S] (or [S]) at
    ``inv_freq`` [D / 2] over the interleaved pairs ``(x_2m, x_2m+1)``,
    or with ``halves`` over ``(x_m, x_{m+D/2})``; cos and sin times
    ``mult``.  float32 arithmetic, ``x``'s type back."""
    ang = pos.astype(jnp.float32)[..., None] * inv_freq          # [.., D/2]
    if ang.ndim == 2:
        ang = ang[None]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[-1:])
    cos, sin = jnp.cos(ang) * mult, jnp.sin(ang) * mult
    xf = x.astype(jnp.float32)
    if halves:
        half = x.shape[-1] // 2
        first, second = xf[..., :half], xf[..., half:]
        return jnp.concatenate([first * cos - second * sin,
                                second * cos + first * sin],
                               axis=-1).astype(x.dtype)
    even, odd = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                    axis=-1)
    return out.reshape(x.shape).astype(x.dtype)
