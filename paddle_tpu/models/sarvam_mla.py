"""``sarvam_mla`` decoders (Sarvam-105B): multi-head LATENT attention,
one leading dense layer, then layers of sparse experts with a shared
expert, a selection bias and a routed scaling factor.

Blocks are sequential pre-norm: ``x <- x + Attn(RMSNorm(x))``, then ``x
<- x + FFN(RMSNorm(x))``; a final RMSNorm, an untied head.

**Latent attention** (``H`` heads; ``rank`` = ``kv_lora_rank``, ``nope``,
``rope``, ``v`` the head's widths).  ``q = W_q x`` -> ``[H, nope +
rope]``, a learned RMSNorm over each head's ``nope + rope`` values
(``use_qk_norm``), then the rope part rotated.  ``[c, k_r] = W_kva x``
(``rank + rope``); ``c <- RMSNorm(c)``; ``k_r`` is one rotary key for
all heads.  The CACHED ROW of a token is ``[c, rope(k_r)]`` — no heads,
no V.  ``[k_nope,h, v_h] = W_kvb,h c``; a head's key is ``[k_nope,h,
rope(k_r)]``; ``o = W_o concat_h softmax(q_h . k_h * scale, causal)
v_h``.  Serving reads the rows through
``incubate.nn.functional.paged_latent_attention``: a prefill chunk
up-projects each gathered block of rows, a decode step runs the absorbed
form (``W_kvb``'s halves moved onto query and output) through the Pallas
kernel of ``pallas/mla.py``.

**Rotation**: ``deepseek_yarn`` over the ``rope`` dims, interleaved
pairs ``(x_2m, x_2m+1)``: per frequency a blend of ``theta^(-2m/rope)``
and the same over ``factor`` by the linear ramp between the correction
dims of ``beta_fast`` and ``beta_slow`` (``models/rotary.py``); the
cos/sin multiplier ``mscale / mscale_all_dim`` form is 1 for the
published values; the softmax scale is ``(nope + rope)^-1/2 * (0.1 *
mscale_all_dim * ln(factor) + 1)^2`` (``yarn_softmax_scale``).

**Expert layers**: ``s = sigmoid(W_r x)`` in float32 over all published
experts; ``T = top_k(s + b)`` with the per-expert bias ``b`` in the
SELECTION only; ``g_e = routed_scaling_factor * s_e / sum_{j in T}
s_j``; ``FFN(x) = sum_{e in T, e held} g_e f_e(x) + f_shared(x)``, each
``f`` a SwiGLU of ``moe_intermediate_size``.  ``held_experts`` = (first,
count) is one chip's share exactly as in ``models/cohere_moe.py``: the
routed part is ``pallas.moe.routed_experts``, what absent experts would
add is left out, and the shares of a layer add up to the whole layer
(tests/test_sarvam_mla.py).

Cache contract: one paged LATENT dict a layer (``serving.PagedKVCache``
built with ``layer_latents=config.layer_latents()``), or none.  A dict
that carries ``valid_len`` gets an expert layer's routing counts back
under ``moe_counts``.

Leaf names follow the family's checkpoints (``self_attn.q_proj``,
``kv_a_proj_with_mqa``, ``kv_a_layernorm``, ``kv_b_proj``, ``o_proj``;
``mlp.gate.weight`` the router).  Departures: Linear weights are [in,
out]; ``self_attn.q_norm.weight`` is the query norm's name here; the
selection bias is ``mlp.gate.expert_bias``; routed and shared experts
are STACKED as in ``cohere_moe`` (``mlp.experts.{gate,up,down}_proj``
[E_held, in, out], ``mlp.shared_experts.*`` [S, in, out]).
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
from ..observability.tracing import scope

from ..core.dispatch import apply_op
from ..nn import Layer, Linear, RMSNorm
from ..nn import functional as F
from ..nn.initializer import Normal, ParamAttr
from .decoder import DecoderForCausalLM, checked_held_experts
from .rotary import (
    rope_pairs, yarn_cos_sin_scale, yarn_inv_freq, yarn_softmax_scale)
from .sparse_experts import SparseExpertMLP
from ..tensor_ops import manipulation as MA
from ..incubate.nn import functional as IF


def _yarn():
    return {"type": "deepseek_yarn", "factor": 40.0, "beta_fast": 32.0,
            "beta_slow": 1.0, "mscale": 1.0, "mscale_all_dim": 1.0,
            "original_max_position_embeddings": 4096}


@dataclass
class SarvamMLAConfig:
    vocab_size: int = 262144
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 64
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    #: leading layers whose feed-forward part is one dense SwiGLU
    first_k_dense_replace: int = 1
    #: width of the dense layers' SwiGLU
    intermediate_size: int = 16384
    #: width of one expert (routed and shared alike)
    moe_intermediate_size: int = 2048
    #: experts the router scores (its width)
    num_experts_published: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    #: (first, count) of the routed experts held here; None -> all
    held_experts: tuple | None = None
    routed_scaling_factor: float = 2.5
    rope_theta: float = 10000.0
    rope_scaling: dict = field(default_factory=_yarn)
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 131072
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False

    def __post_init__(self):
        self.held_experts = checked_held_experts(self)
        if self.tie_word_embeddings:
            raise ValueError("the family's head is untied")
        if self.qk_rope_head_dim % 2:
            raise ValueError("the rotated dims come in pairs")
        kind = self.rope_scaling.get("type")
        if kind != "deepseek_yarn":
            raise ValueError(f"rope_scaling type {kind!r}: the family "
                             "rotates by deepseek_yarn")

    @property
    def q_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def head_dim(self):
        """The cached row's width (the published key's meaning here:
        ``kv_lora_rank + qk_rope_head_dim``), not a head size."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def layer_latents(self):
        """Per layer, the width of the ONE row its attention caches a
        token — what a cache manager sizes a layer's pages by."""
        return [self.head_dim] * self.num_layers

    @property
    def softmax_scale(self):
        return yarn_softmax_scale(self.q_head_dim, self.rope_scaling)


TINY_SARVAM_MLA = dict(
    vocab_size=256, hidden_size=64, num_layers=3, num_heads=4,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=32,
    num_experts_published=16, num_experts_per_tok=4, num_shared_experts=1,
    held_experts=(0, 4), max_seq_len=64, initializer_range=0.1,
    rope_scaling=dict(_yarn(), factor=4.0,
                      original_max_position_embeddings=16))


def _whole_sequence_latent_attention(q, row, w_kvb, nope, scale):
    """Causal latent attention over a whole sequence with no cache, the
    up-projected form in float32: the lane of the tests and of
    ``generate(use_cache=False)``; serving reads pages."""
    b, s, h, _ = q.shape
    rank = w_kvb.shape[0]
    f32 = jnp.float32
    kv = jnp.matmul(row[..., :rank].astype(f32), w_kvb.astype(f32)) \
        .reshape(b, s, h, -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(row[:, :, None, rank:].astype(f32),
                          (b, s, h, row.shape[-1] - rank))], axis=-1)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q.astype(f32), k) * scale
    mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    p = jax.nn.softmax(jnp.where(mask, sc, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, kv[..., nope:]).astype(q.dtype)


class SarvamMLAAttention(Layer):
    def __init__(self, config: SarvamMLAConfig):
        super().__init__()
        self.config = config
        h, nh = config.hidden_size, config.num_heads
        w_init = ParamAttr(initializer=Normal(0.0, config.initializer_range))
        self.q_proj = Linear(h, nh * config.q_head_dim, weight_attr=w_init,
                             bias_attr=False)
        self.q_norm = RMSNorm(config.q_head_dim,
                              epsilon=config.rms_norm_eps)
        self.kv_a_proj_with_mqa = Linear(h, config.head_dim,
                                         weight_attr=w_init, bias_attr=False)
        self.kv_a_layernorm = RMSNorm(config.kv_lora_rank,
                                      epsilon=config.rms_norm_eps)
        self.kv_b_proj = Linear(
            config.kv_lora_rank,
            nh * (config.qk_nope_head_dim + config.v_head_dim),
            weight_attr=w_init, bias_attr=False)
        self.o_proj = Linear(nh * config.v_head_dim, h, weight_attr=w_init,
                             bias_attr=False)

    def forward(self, x, cache=None):
        cfg = self.config
        b, s, _ = x.shape
        nh, rank, nope = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
        q = self.q_norm(MA.reshape(self.q_proj(x),
                                   [b, s, nh, cfg.q_head_dim]))
        ckr = self.kv_a_proj_with_mqa(x)
        c = self.kv_a_layernorm(ckr[:, :, :rank])
        off = None if cache is None else cache["offset"]
        theta, scaling = cfg.rope_theta, cfg.rope_scaling
        rope = cfg.qk_rope_head_dim

        def rotate(qa, ca, kra, *off):
            pos = jnp.arange(s, dtype=jnp.int32)
            if off:
                pos = off[0].astype(jnp.int32).reshape(-1, 1) + pos[None]
            inv = yarn_inv_freq(rope, theta, scaling)
            mult = yarn_cos_sin_scale(scaling)
            q_r = rope_pairs(qa[..., nope:], pos, inv, mult)
            k_r = rope_pairs(kra, pos, inv, mult)
            return (jnp.concatenate([qa[..., :nope], q_r], axis=-1),
                    jnp.concatenate([ca, k_r.astype(ca.dtype)], axis=-1))

        args = (q, c, ckr[:, :, rank:])
        q, row = apply_op("rope_latent", rotate,
                          args if off is None else args + (off,))
        w_kvb = self.kv_b_proj.weight
        scale = cfg.softmax_scale
        if cache is None:
            out = apply_op(
                "whole_sequence_latent_attention",
                lambda qa, ra, wa: _whole_sequence_latent_attention(
                    qa, ra, wa, nope, scale), (q, row, w_kvb))
        elif "latent_pool" in cache:
            out = IF.paged_latent_attention(q, row, w_kvb, cache,
                                            nope_dim=nope, scale=scale)
        else:
            raise NotImplementedError(
                "SarvamMLAAttention reads a latent page store "
                "(serving.PagedKVCache with layer_latents="
                "config.layer_latents()) or none")
        return self.o_proj(MA.reshape(out, [b, s, nh * cfg.v_head_dim]))


class SarvamDenseMLP(Layer):
    """SwiGLU: ``down(silu(gate(x)) * up(x))``."""

    def __init__(self, config: SarvamMLAConfig):
        super().__init__()
        h, m = config.hidden_size, config.intermediate_size
        w_init = ParamAttr(initializer=Normal(0.0, config.initializer_range))
        self.gate_proj = Linear(h, m, weight_attr=w_init, bias_attr=False)
        self.up_proj = Linear(h, m, weight_attr=w_init, bias_attr=False)
        self.down_proj = Linear(m, h, weight_attr=w_init, bias_attr=False)

    def forward(self, x, cache=None):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class SarvamSparseMLP(SparseExpertMLP):
    """``models/sparse_experts.py``'s layer at this family's settings: a
    selection bias in the choice, the scaling factor on the gates, the
    shared experts' outputs added."""

    def __init__(self, config: SarvamMLAConfig):
        super().__init__(
            config.hidden_size, config.moe_intermediate_size,
            config.num_experts_published, config.held_experts,
            config.num_experts_per_tok, config.num_shared_experts,
            config.initializer_range, selection_bias=True,
            gate_scale=config.routed_scaling_factor, shared_reduce="sum")
        self.config = config


class SarvamMLABlock(Layer):
    def __init__(self, config: SarvamMLAConfig, dense):
        super().__init__()
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.self_attn = SarvamMLAAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps)
        self.mlp = SarvamDenseMLP(config) if dense \
            else SarvamSparseMLP(config)

    def forward(self, x, cache=None):
        with scope("attn_latent"):
            x = x + self.self_attn(self.input_layernorm(x), cache=cache)
        with scope("mlp"):
            x = x + self.mlp(self.post_attention_layernorm(x), cache=cache)
        return x


class SarvamMLAForCausalLM(DecoderForCausalLM):
    """The shell over ``SarvamMLABlock``, its first layers dense."""

    block = SarvamMLABlock

    @staticmethod
    def layer_kinds(config):
        return [i < config.first_k_dense_replace
                for i in range(config.num_layers)]
