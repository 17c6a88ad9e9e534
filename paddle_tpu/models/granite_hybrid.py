"""Granite 4.0-H: a hybrid decoder whose layers are Mamba-2 mixers or
grouped-query attention, by ``layer_types``, each closed by one SwiGLU
MLP (IBM, 2025; HF ``granitemoehybrid`` without experts).

What sets it apart from the Llama family here: a layer may keep a
RECURRENT state per sequence (the last ``d_conv - 1`` pre-activation
``xBC`` rows and the SSM state, float32) instead of keys
and values; attention carries no positional rotation and scales its
scores by ``attention_multiplier``; the embedding, every residual branch
and the logits carry fixed multipliers; embedding and head are tied.

Cache contract (one dict per layer, as ``caches``):

- attention layers take what Llama's do — a paged dict
  (``serving.PagedKVCache``) or a dense ``{"k", "v", "offset"}``;
- Mamba layers take ``{"conv_state": [R, K-1, C], "ssm_state": [R, H/k,
  N, k P] (lane-packed: ``pallas/ssm.py``), "state_rows": int32 [B] or
  None, "valid_len": int32 [B] or None}``: batch row ``b`` reads and
  writes state row ``state_rows[b]`` (None: row ``b``), and only its
  first ``valid_len[b]`` positions are real (None: all) — a pad
  position carries ``dt = 0`` and stays out of the convolution's window,
  so it leaves the state as it was.

``GraniteHybridConfig.layer_states()`` tells a cache manager which
layers keep which state; ``init_caches`` builds the dense caches
``generate()`` decodes against.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from ..observability.tracing import scope

from ..core.dispatch import apply_op
from ..nn import Layer, Linear, RMSNorm
from ..nn import functional as F
from ..nn.initializer import Assign, Constant, Normal, ParamAttr
from ..pallas import ssm as _ssm
from ..tensor_ops import manipulation as MA
from .decoder import (DecoderForCausalLM, GroupedQueryAttention,
                      checked_layer_types, init_caches)


class Mamba2Layers:
    """What a config whose ``"mamba"`` layers are Mamba-2 mixers (its
    ``mamba_*`` fields) derives from them: the mixer's widths and what
    each layer keeps between calls."""

    @property
    def d_inner(self):
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    def layer_states(self, dtype):
        """Per layer, what a sequence keeps between calls: None for keys
        and values behind a page table, or ``{name: (shape, dtype)}`` of
        the fixed-size state a recurrent layer keeps per sequence
        (``dtype``: the activations' type, for the convolution's window;
        the SSM state is float32 whatever that is: a recurrence of 1,000+
        steps rounds at every one of them).  The SSM state is stored
        lane-packed (``pallas.ssm.state_row_shape``); its third member is
        the shape a row is read back in (``PagedKVCache.read_state``)."""
        state = {
            "conv_state": ((self.mamba_d_conv - 1, self.conv_dim), dtype),
            "ssm_state": _ssm.state_spec(self.mamba_n_heads,
                                         self.mamba_d_head,
                                         self.mamba_d_state,
                                         self.mamba_n_groups)}
        return [dict(state) if kind == "mamba" else None
                for kind in self.layer_types]


@dataclass
class GraniteHybridConfig(Mamba2Layers):
    vocab_size: int = 100352
    hidden_size: int = 2048
    num_layers: int = 40
    #: "mamba" | "attention" per layer; None -> attention at 5, 15, ...
    layer_types: list | None = None
    num_heads: int = 32
    num_kv_heads: int = 8
    intermediate_size: int = 8192         # shared_intermediate_size
    max_seq_len: int = 4096
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    embedding_multiplier: float = 12.0
    logits_scaling: float = 8.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    tie_word_embeddings: bool = True

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = ["attention" if i % 10 == 5 else "mamba"
                                for i in range(self.num_layers)]
        self.layer_types = checked_layer_types(self, ("mamba", "attention"))
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be divisible by num_kv_heads")
        if self.mamba_n_heads * self.mamba_d_head != \
                self.mamba_expand * self.hidden_size:
            raise ValueError(
                "mamba_n_heads * mamba_d_head must equal mamba_expand * "
                "hidden_size")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError(
                "mamba_n_heads must be divisible by mamba_n_groups")
        if not self.tie_word_embeddings:
            raise ValueError("the family ties embedding and head")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads


TINY_GRANITE_HYBRID = dict(
    vocab_size=256, hidden_size=64, num_layers=8,
    layer_types=["mamba", "mamba", "attention", "mamba"] * 2,
    num_heads=4, num_kv_heads=2, intermediate_size=128, max_seq_len=128,
    mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16, mamba_expand=1,
    mamba_chunk_size=8)


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


class _MixDims(NamedTuple):
    """What the mixer's arithmetic needs of the config, hashable."""
    heads: int
    head_dim: int
    groups: int
    state: int
    taps: int
    chunk: int
    eps: float
    #: groups of channels the gated norm normalises apart (1: all of
    #: them, Granite's form)
    norm_groups: int = 1


@functools.partial(jax.jit, static_argnames=("dims",))
def _mamba2_mix(proj, conv_w, conv_b, dt_bias, a_log, d_skip, norm_w,
                conv_state, ssm_state, rows, valid, *, dims):
    """The mixer between its two projections.  ``proj`` [B, S, d_inner +
    conv_dim + H] is ``in_proj``'s output; returns (y [B, S, d_inner] in
    ``proj``'s type, conv_state', ssm_state') — the states None when
    none came in (a whole sequence from an empty state)."""
    f32 = jnp.float32
    b, s, _ = proj.shape
    heads, p, g, n, taps, chunk, eps, norm_groups = dims
    d_inner = heads * p
    conv_dim = d_inner + 2 * g * n
    z, xbc, dt = jnp.split(proj.astype(f32),
                           [d_inner, d_inner + conv_dim], axis=-1)
    cached = conv_state is not None
    if cached and rows is None:
        rows = jnp.arange(b, dtype=jnp.int32)
    pos = jnp.arange(s, dtype=jnp.int32)[None, :]
    real = None if valid is None else pos < valid[:, None]      # [B, S]

    # causal depthwise convolution over the window [kept rows | chunk]
    kept = conv_state[rows].astype(f32) if cached else \
        jnp.zeros((b, taps - 1, conv_dim), f32)
    window = jnp.concatenate([kept, xbc], axis=1)       # [B, K-1+S, C]
    wc = conv_w.astype(f32)
    conv = sum(window[:, k:k + s] * wc[:, k] for k in range(taps))
    xbc = jax.nn.silu(conv + conv_b.astype(f32))
    x, bm, cm = jnp.split(xbc, [d_inner, d_inner + g * n], axis=-1)
    x = x.reshape(b, s, heads, p)
    bm = bm.reshape(b, s, g, n)
    cm = cm.reshape(b, s, g, n)
    dt = _softplus(dt + dt_bias.astype(f32))
    if real is not None:
        dt = jnp.where(real[..., None], dt, 0.0)   # the state passes through
    a = -jnp.exp(a_log.astype(f32))

    if not cached:
        state0 = jnp.zeros((b,) + _ssm.state_row_shape(heads, p, n, g), f32)
        _, y = _ssm.ssd_chunked(state0, x, dt, a, bm, cm, chunk)
        new_conv = new_ssm = None
    else:
        if s == 1:
            # decoding: the recurrence as written, the state in place
            new_ssm, y = _ssm.ssm_step(ssm_state, rows, x[:, 0], dt[:, 0],
                                       a, bm[:, 0], cm[:, 0])
            y = y[:, None]
        else:
            # the rows are read ONCE, behind a barrier: fused into each of
            # its consumers, the gather keeps the whole array alive past
            # the write below, and XLA copies it (twice a layer a call)
            state0 = jax.lax.optimization_barrier(ssm_state[rows])
            state1, y = _ssm.ssd_chunked(
                state0.astype(f32), x, dt, a, bm, cm, chunk)
            new_ssm = ssm_state.at[rows].set(state1.astype(ssm_state.dtype))
        # the window now ends on the row's last real token
        n_real = jnp.full((b,), s, jnp.int32) if valid is None else valid
        tail = jax.vmap(lambda w, k: jax.lax.dynamic_slice_in_dim(
            w, k, taps - 1, axis=0))(window, n_real)
        new_conv = conv_state.at[rows].set(tail.astype(conv_state.dtype))
    y = y + d_skip.astype(f32)[None, None, :, None] * x
    # gate, then the norm over all channels — or over each group of
    # ``d_inner / norm_groups`` of them on its own
    y = y.reshape(b, s, d_inner) * jax.nn.silu(z)
    if norm_groups == 1:
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                              + eps) * norm_w.astype(f32)
    else:
        y = y.reshape(b, s, norm_groups, d_inner // norm_groups)
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                              + eps)
        y = y.reshape(b, s, d_inner) * norm_w.astype(f32)
    return y.astype(proj.dtype), new_conv, new_ssm


class _DepthwiseConv(Layer):
    """The mixer's causal depthwise convolution: its two leaves, under
    the checkpoint's names; the mixer applies them."""

    def __init__(self, channels, taps):
        super().__init__()
        self.weight = self.create_parameter(
            (channels, taps),
            default_initializer=Normal(0.0, 1.0 / math.sqrt(taps)))
        self.bias = self.create_parameter(
            (channels,), is_bias=True, default_initializer=Constant(0.0))


class _GateNorm(Layer):
    def __init__(self, channels):
        super().__init__()
        self.weight = self.create_parameter(
            (channels,), default_initializer=Constant(1.0))


class GraniteMamba2Mixer(Layer):
    """The Mamba-2 mixer between its projections.  ``norm_groups``: the
    groups of channels its gated norm normalises apart (1: all of them,
    as Granite; ``nemotron_h`` normalises each of its ``n_groups``)."""

    def __init__(self, config: GraniteHybridConfig, norm_groups=1):
        super().__init__()
        if config.d_inner % norm_groups:
            raise ValueError(f"{config.d_inner} channels do not split "
                             f"into {norm_groups} norm groups")
        self.norm_groups = int(norm_groups)
        self.config = cfg = config
        h, heads = cfg.hidden_size, cfg.mamba_n_heads
        w_init = ParamAttr(initializer=Normal(0.0, cfg.initializer_range))
        out_init = ParamAttr(initializer=Normal(
            0.0, cfg.initializer_range / math.sqrt(2 * cfg.num_layers)))
        self.in_proj = Linear(h, cfg.d_inner + cfg.conv_dim + heads,
                              weight_attr=w_init, bias_attr=False)
        self.conv1d = _DepthwiseConv(cfg.conv_dim, cfg.mamba_d_conv)
        # Mamba-2's own ranges: A in [1, 16], dt in [0.001, 0.1]
        steps = np.exp(np.linspace(math.log(1e-3), math.log(1e-1), heads))
        self.dt_bias = self.create_parameter(
            (heads,), default_initializer=Assign(
                (steps + np.log(-np.expm1(-steps))).astype(np.float32)))
        self.A_log = self.create_parameter(
            (heads,), default_initializer=Assign(
                np.log(np.linspace(1.0, 16.0, heads)).astype(np.float32)))
        self.D = self.create_parameter(
            (heads,), default_initializer=Constant(1.0))
        self.norm = _GateNorm(cfg.d_inner)
        self.out_proj = Linear(cfg.d_inner, h, weight_attr=out_init,
                               bias_attr=False)

    def forward(self, x, cache=None):
        cfg = self.config
        cached = cache is not None
        args = (self.in_proj(x), self.conv1d.weight, self.conv1d.bias,
                self.dt_bias, self.A_log, self.D, self.norm.weight,
                cache["conv_state"] if cached else None,
                cache["ssm_state"] if cached else None,
                cache.get("state_rows") if cached else None,
                cache.get("valid_len") if cached else None)

        dims = _MixDims(cfg.mamba_n_heads, cfg.mamba_d_head,
                        cfg.mamba_n_groups, cfg.mamba_d_state,
                        cfg.mamba_d_conv, cfg.mamba_chunk_size,
                        cfg.rms_norm_eps, self.norm_groups)

        def fn(*arrays):
            out = _mamba2_mix(*arrays, dims=dims)
            return out if cached else out[0]

        out = apply_op("mamba2_mix", fn, args)
        if cached:
            out, cache["conv_state"], cache["ssm_state"] = out
        return self.out_proj(out)


class GraniteAttention(GroupedQueryAttention):
    """Grouped-query attention with no positional rotation; scores are
    scaled by ``attention_multiplier``."""

    @property
    def scale(self):
        return self.config.attention_multiplier


class GraniteSharedMLP(Layer):
    """SwiGLU with gate and up in one matrix: ``output_linear(silu(a) *
    b)``, ``a, b = split(input_linear(x))``."""

    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        h, m = config.hidden_size, config.intermediate_size
        w_init = ParamAttr(initializer=Normal(0.0, config.initializer_range))
        out_init = ParamAttr(initializer=Normal(
            0.0, config.initializer_range / math.sqrt(2 * config.num_layers)))
        self.input_linear = Linear(h, 2 * m, weight_attr=w_init,
                                   bias_attr=False)
        self.output_linear = Linear(m, h, weight_attr=out_init,
                                    bias_attr=False)

    def forward(self, x):
        gate, up = MA.split(self.input_linear(x), 2, axis=-1)
        return self.output_linear(F.silu(gate) * up)


class GraniteHybridBlock(Layer):
    def __init__(self, config: GraniteHybridConfig, kind):
        super().__init__()
        self.kind = kind
        self.residual = config.residual_multiplier
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        if kind == "mamba":
            self.mamba = GraniteMamba2Mixer(config)
        else:
            self.self_attn = GraniteAttention(config)
        self.post_attention_layernorm = RMSNorm(
            config.hidden_size, epsilon=config.rms_norm_eps)
        self.shared_mlp = GraniteSharedMLP(config)

    def forward(self, x, cache=None):
        y = self.input_layernorm(x)
        if self.kind == "mamba":
            with scope("mamba"):
                x = x + self.mamba(y, cache=cache) * self.residual
        else:
            with scope("attn"):
                x = x + self.self_attn(y, cache=cache) * self.residual
        with scope("mlp"):
            x = x + self.shared_mlp(self.post_attention_layernorm(x)) \
                * self.residual
        return x


class GraniteHybridForCausalLM(DecoderForCausalLM):
    """The shell over ``GraniteHybridBlock``, logits / ``logits_scaling``."""

    block = GraniteHybridBlock
    init_caches = init_caches

    @staticmethod
    def logit_multiplier(config):
        return 1.0 / config.logits_scaling
