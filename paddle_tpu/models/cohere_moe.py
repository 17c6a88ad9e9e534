"""Cohere ``cohere2_moe`` decoders (Command A+): parallel-residual blocks
whose attention kind is per layer and whose feed-forward part is sparse.

Per layer ``n = LayerNorm(x)`` (mean-subtracting, scale only), then ``x
<- x + Attn_i(n) + FFN(n)``: attention and the feed-forward part read the
same norm and both add to the residual.

- ``Attn_i`` is grouped-query attention without bias.  A
  ``sliding_attention`` layer rotates q and k by position over
  INTERLEAVED pairs ``(x_2m, x_2m+1)`` and lets query ``s`` see key ``t``
  iff ``s - window < t <= s``; a ``full_attention`` layer carries no
  rotation and sees every ``t <= s``.
- ``FFN(n) = sum_{e in T} g_e f_e(n) + mean_j f^shared_j(n)`` with ``T``
  the ``num_experts_per_tok`` largest SIGMOID scores of a float32 router
  over all ``num_experts_published`` experts, ``g`` those scores
  normalised to sum 1, and ``f`` gated-SiLU experts.

**One chip's share.**  ``held_experts`` = (first, count) names the
consecutive routed experts whose weights this instance holds.  The layer
routes over ALL published experts, normalises over all the chosen, and
computes ``sum_{e in T, e held} g_e f_e(n)`` plus the shared term: what
the absent experts would add is left out, and that partial result goes
on to the next layer.  Nothing stands in for absent chips; the shares of
a layer add up to the whole layer (tests/test_cohere_moe.py).

Cache contract: one paged dict per layer (``serving.PagedKVCache``);
window layers' dicts carry ``window`` and a page table of their own
(``CohereMoeConfig.layer_windows`` tells the cache manager which).  A
paged dict that carries ``valid_len`` gets the layer's routing counts
back under ``moe_counts``.

Leaf names follow the published checkpoint
(``model.layers.<i>.input_layernorm.weight``,
``self_attn.{q,k,v,o}_proj.weight``, ``mlp.gate.weight`` for the router).
Departures: Linear weights are [in, out] as everywhere in this repo (the
checkpoint's are [out, in]); the routed experts are STACKED
(``mlp.experts.{gate_proj,up_proj,down_proj}``: [E_held, in, out], no
``.weight`` suffix and no expert index in the name) and so are the shared
experts (``mlp.shared_experts.*``: [S, in, out]); the head is the
embedding (tied), so there is no ``lm_head.weight``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from ..observability.tracing import scope

from ..core.dispatch import apply_op
from ..nn import Layer, Linear, Embedding, LayerNorm, LayerList
from ..nn import functional as F
from ..nn.initializer import Normal, ParamAttr
from .sparse_experts import SparseExpertMLP
from ..tensor_ops import manipulation as MA
from ..incubate.nn import functional as IF


@dataclass
class CohereMoeConfig:
    vocab_size: int = 262144
    hidden_size: int = 4096
    num_layers: int = 32
    #: "sliding_attention" | "full_attention" per layer; None -> three
    #: sliding then one full, repeated
    layer_types: list | None = None
    num_heads: int = 128
    num_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 4096
    rope_theta: float = 50000.0
    layer_norm_eps: float = 1e-5
    #: width of one expert (routed and shared alike)
    intermediate_size: int = 4096
    #: experts the router scores (its width)
    num_experts_published: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    #: (first, count) of the routed experts held here; None -> all
    held_experts: tuple | None = None
    logit_scale: float = 1.0
    max_seq_len: int = 8192
    initializer_range: float = 0.02
    tie_word_embeddings: bool = True

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = ["full_attention" if i % 4 == 3
                                else "sliding_attention"
                                for i in range(self.num_layers)]
        self.layer_types = list(self.layer_types)
        if len(self.layer_types) != self.num_layers:
            raise ValueError(f"{len(self.layer_types)} layer_types for "
                             f"{self.num_layers} layers")
        bad = set(self.layer_types) - {"sliding_attention", "full_attention"}
        if bad:
            raise ValueError(f"unknown layer kinds {sorted(bad)}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be divisible by num_kv_heads")
        if self.held_experts is None:
            self.held_experts = (0, self.num_experts_published)
        first, count = (int(v) for v in self.held_experts)
        if first < 0 or count < 1 or \
                first + count > self.num_experts_published:
            raise ValueError(
                f"held_experts {self.held_experts} is not a run of the "
                f"{self.num_experts_published} published experts")
        self.held_experts = (first, count)
        if self.num_experts_per_tok > self.num_experts_published:
            raise ValueError("more experts a token than experts")
        if not self.tie_word_embeddings:
            raise ValueError("the family ties embedding and head")

    @property
    def num_experts_held(self):
        return self.held_experts[1]

    def layer_windows(self):
        """Per layer, how many of the latest positions its attention
        sees (None: all of them) — what a cache manager sizes a layer's
        pages by."""
        return [self.sliding_window if kind == "sliding_attention" else None
                for kind in self.layer_types]


TINY_COHERE_MOE = dict(
    vocab_size=256, hidden_size=64, num_layers=8, num_heads=4,
    num_kv_heads=2, head_dim=16, sliding_window=8, intermediate_size=32,
    num_experts_published=16, num_experts_per_tok=4, num_shared_experts=2,
    held_experts=(0, 4), max_seq_len=64, initializer_range=0.1)


def rope_interleaved(x, pos, theta):
    """Rotate ``x`` [B, S, H, D] by ``pos`` [B, S] (or [S]) over the
    pairs ``(x_2m, x_2m+1)`` at frequency ``theta ** (-2m / D)``;
    float32 arithmetic, ``x``'s type back."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[..., None] * inv               # [.., D/2]
    if ang.ndim == 2:
        ang = ang[None]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    xf = x.astype(jnp.float32)
    even, odd = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                    axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _whole_sequence_attention(q, k, v, window):
    """Causal grouped-query attention over a whole sequence with no
    cache, float32 scores: the reference lane of the tests and of
    ``generate(use_cache=False)``; serving reads pages."""
    b, s, h, d = q.shape
    h_kv = k.shape[2]
    qg = q.astype(jnp.float32).reshape(b, s, h_kv, h // h_kv, d)
    sc = jnp.einsum("bqhrd,bkhd->bhrqk", qg, k.astype(jnp.float32)) \
        / math.sqrt(d)
    qp = jnp.arange(s)[:, None]
    kp = jnp.arange(s)[None, :]
    mask = kp <= qp
    if window is not None:
        mask &= kp > qp - window
    p = jax.nn.softmax(jnp.where(mask, sc, -1e30), axis=-1)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", p, v.astype(jnp.float32))
    return out.reshape(b, s, h, d).astype(q.dtype)


class CohereMoeAttention(Layer):
    def __init__(self, config: CohereMoeConfig, kind):
        super().__init__()
        self.config = config
        #: a window layer rotates by position; a full layer carries none
        self.rotary = kind == "sliding_attention"
        self.window = config.sliding_window if self.rotary else None
        h, d = config.hidden_size, config.head_dim
        w_init = ParamAttr(initializer=Normal(0.0, config.initializer_range))
        out_init = ParamAttr(initializer=Normal(
            0.0, config.initializer_range / math.sqrt(2 * config.num_layers)))
        self.q_proj = Linear(h, config.num_heads * d, weight_attr=w_init,
                             bias_attr=False)
        self.k_proj = Linear(h, config.num_kv_heads * d, weight_attr=w_init,
                             bias_attr=False)
        self.v_proj = Linear(h, config.num_kv_heads * d, weight_attr=w_init,
                             bias_attr=False)
        self.o_proj = Linear(config.num_heads * d, h, weight_attr=out_init,
                             bias_attr=False)

    def forward(self, x, cache=None):
        cfg = self.config
        b, s, _ = x.shape
        d = cfg.head_dim
        q = MA.reshape(self.q_proj(x), [b, s, cfg.num_heads, d])
        k = MA.reshape(self.k_proj(x), [b, s, cfg.num_kv_heads, d])
        v = MA.reshape(self.v_proj(x), [b, s, cfg.num_kv_heads, d])
        window = self.window
        if self.rotary:
            theta = cfg.rope_theta
            off = None if cache is None else cache["offset"]

            def rotate(qa, ka, *off):
                pos = jnp.arange(s, dtype=jnp.int32)
                if off:
                    pos = off[0].astype(jnp.int32).reshape(-1, 1) + pos[None]
                return (rope_interleaved(qa, pos, theta),
                        rope_interleaved(ka, pos, theta))

            q, k = apply_op("rope_interleaved", rotate,
                            (q, k) if off is None else (q, k, off))
        if cache is None:
            out = apply_op(
                "whole_sequence_attention",
                lambda qa, ka, va: _whole_sequence_attention(qa, ka, va,
                                                             window),
                (q, k, v))
        elif "page_table" in cache:
            if cache.get("window") != window:
                raise ValueError(
                    f"the layer's window is {window} and its cache's "
                    f"{cache.get('window')}: build the cache with "
                    "layer_windows=config.layer_windows()")
            out = IF.paged_cache_attention(q, k, v, cache)
        else:
            raise NotImplementedError(
                "CohereMoeAttention reads a paged cache "
                "(serving.PagedKVCache) or none; a dense {'k', 'v'} cache "
                "has no window")
        return self.o_proj(MA.reshape(out, [b, s, cfg.num_heads * d]))


class CohereSparseMLP(SparseExpertMLP):
    """``models/sparse_experts.py``'s layer at this family's settings:
    no selection bias, gates that sum to 1, the shared experts'
    "average"."""

    def __init__(self, config: CohereMoeConfig):
        std = config.initializer_range
        super().__init__(
            config.hidden_size, config.intermediate_size,
            config.num_experts_published, config.held_experts,
            config.num_experts_per_tok, config.num_shared_experts, std,
            down_std=std / math.sqrt(2 * config.num_layers))
        self.config = config


class CohereMoeBlock(Layer):
    def __init__(self, config: CohereMoeConfig, kind):
        super().__init__()
        self.kind = kind
        self.input_layernorm = LayerNorm(
            config.hidden_size, epsilon=config.layer_norm_eps,
            bias_attr=False)
        self.self_attn = CohereMoeAttention(config, kind)
        self.mlp = CohereSparseMLP(config)

    def forward(self, x, cache=None):
        n = self.input_layernorm(x)
        with scope("attn_window" if self.kind == "sliding_attention"
                   else "attn_full"):
            a = self.self_attn(n, cache=cache)
        return x + a + self.mlp(n, cache=cache)


class CohereMoeModel(Layer):
    def __init__(self, config: CohereMoeConfig):
        super().__init__()
        self.config = config
        emb_init = ParamAttr(initializer=Normal(0.0,
                                                config.initializer_range))
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size,
                                      weight_attr=emb_init)
        self.layers = LayerList([CohereMoeBlock(config, kind)
                                 for kind in config.layer_types])
        self.norm = LayerNorm(config.hidden_size,
                              epsilon=config.layer_norm_eps, bias_attr=False)

    def forward(self, input_ids, caches=None):
        with scope("embed"):
            x = self.embed_tokens(input_ids)
        for i, blk in enumerate(self.layers):
            x = blk(x, cache=None if caches is None else caches[i])
        return self.norm(x)


class CohereMoeForCausalLM(Layer):
    def __init__(self, config: CohereMoeConfig):
        super().__init__()
        self.config = config
        self.model = CohereMoeModel(config)

    def forward(self, input_ids, labels=None, caches=None):
        hidden = self.model(input_ids, caches=caches)
        with scope("head"):
            logits = F.linear(hidden, self.model.embed_tokens.weight.T)
            if self.config.logit_scale != 1.0:
                logits = logits * self.config.logit_scale
        if labels is not None:
            with scope("loss"):
                loss = F.cross_entropy(
                    MA.reshape(logits, [-1, self.config.vocab_size]),
                    MA.reshape(labels, [-1]))
            return logits, loss
        return logits

    def generate(self, input_ids, max_new_tokens=32, temperature=0.0,
                 top_k=None, top_p=None, repetition_penalty=None,
                 use_cache=True, eos_token_id=None):
        """``models.generation.generate``; with ``use_cache=True`` it
        refuses by name (its dense caches have no window) — serve
        through ``serving.Engine``, whose pages do."""
        from .generation import generate
        return generate(self, input_ids, max_new_tokens=max_new_tokens,
                        temperature=temperature, top_k=top_k,
                        top_p=top_p, repetition_penalty=repetition_penalty,
                        use_cache=use_cache, eos_token_id=eos_token_id)

    def num_params(self):
        return sum(p.size for p in self.parameters())
