"""Mellum 2 decoders (JetBrains Mellum2-12B-A2.5B): sequential pre-norm
blocks of grouped-query attention, window or full by layer, each closed
by a softmax-routed expert layer.

Per layer ``i`` of kind ``layer_types[i]``::

    h = x + Attn_i(RMSNorm(x))
    y = h + MoE(RMSNorm(h))

then a final RMSNorm and an untied head; the embedding is not scaled.

- ``Attn_i`` is grouped-query attention without bias, scores scaled by
  ``head_dim^-1/2``.  Both kinds rotate q and k by position over the
  HALVES ``(x_m, x_{m+D/2})``, each by its own entry of
  ``rope_parameters``: a ``sliding_attention`` layer at the plain
  frequencies of its ``rope_theta`` (``"default"``), and it lets query
  ``s`` see key ``t`` iff ``s - window < t <= s``; a ``full_attention``
  layer by YaRN (``"yarn"``: ``models/rotary.py``, cos and sin times
  ``attention_factor``), and it sees every ``t <= s``.  The attention
  itself, its window and its cache dispatch are Cohere's
  (``CohereMoeAttention``): only the rotation is this family's.
- ``MoE(n) = sum_{e in T, e held} g_e f_e(n)`` with ``p = softmax(n
  W_r)`` in float32 over all ``num_experts_published`` experts, ``T``
  its ``num_experts_per_tok`` largest, ``g = p_T / sum p_T``
  (``norm_topk_prob``) and ``f`` gated-SiLU experts; no shared experts
  (``models/sparse_experts.py`` with ``scoring="softmax"``).

``held_experts`` = (first, count) names the experts this instance holds,
as in ``models/cohere_moe.py``; by default all of them.

Cache contract: one paged dict per layer (``serving.PagedKVCache``);
window layers' dicts carry ``window`` and a ring of pages of their own
(``MellumConfig.layer_windows``).  A paged dict that carries
``valid_len`` gets the layer's routing counts back under ``moe_counts``.

Leaf names follow the published checkpoint
(``model.embed_tokens.weight``, ``model.layers.<i>.input_layernorm``,
``self_attn.{q,k,v,o}_proj``, ``post_attention_layernorm``,
``mlp.gate.weight`` the router, ``model.norm``, ``lm_head.weight``).
Departures: Linear weights are [in, out]; the experts are STACKED
(``mlp.experts.{gate_proj,up_proj,down_proj}``: [E_held, in, out], no
``.weight`` suffix and no expert index in the name).
"""
from __future__ import annotations

from dataclasses import dataclass, field

from ..nn import Layer, Linear, Embedding, RMSNorm, LayerList
from ..nn import functional as F
from ..nn.initializer import Normal, ParamAttr
from ..observability.tracing import scope
from ..tensor_ops import manipulation as MA
from .cohere_moe import CohereMoeAttention
from .rotary import default_inv_freq, rope_pairs, yarn_cos_sin_scale, \
    yarn_inv_freq
from .sparse_experts import SparseExpertMLP

LAYER_KINDS = ("sliding_attention", "full_attention")


def _rope_parameters():
    return {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000.0, "factor": 16.0,
            "original_max_position_embeddings": 8192, "beta_fast": 32.0,
            "beta_slow": 1.0, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000.0}}


@dataclass
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    num_layers: int = 28
    #: "sliding_attention" | "full_attention" per layer; None -> three
    #: sliding then one full, repeated
    layer_types: list | None = None
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    #: per layer kind, its rotation: "rope_type" ("default" or "yarn"),
    #: "rope_theta" and YaRN's keys
    rope_parameters: dict = field(default_factory=_rope_parameters)
    rms_norm_eps: float = 1e-6
    #: width of one expert
    intermediate_size: int = 896
    #: experts the router scores (its width)
    num_experts_published: int = 64
    num_experts_per_tok: int = 8
    #: (first, count) of the experts held here; None -> all
    held_experts: tuple | None = None
    max_seq_len: int = 131072
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = ["full_attention" if i % 4 == 3
                                else "sliding_attention"
                                for i in range(self.num_layers)]
        self.layer_types = list(self.layer_types)
        if len(self.layer_types) != self.num_layers:
            raise ValueError(f"{len(self.layer_types)} layer_types for "
                             f"{self.num_layers} layers")
        bad = set(self.layer_types) - set(LAYER_KINDS)
        if bad:
            raise ValueError(f"unknown layer kinds {sorted(bad)}")
        for kind in set(self.layer_types):
            rope = self.rope_parameters.get(kind)
            if rope is None or rope.get("rope_type") not in ("default",
                                                             "yarn"):
                raise ValueError(
                    f"rope_parameters[{kind!r}] = {rope!r}: a layer kind "
                    "rotates by 'default' or 'yarn'")
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
        if self.tie_word_embeddings:
            raise ValueError("the family's head is untied")

    def layer_windows(self):
        """Per layer, how many of the latest positions its attention
        sees (None: all of them) — what a cache manager sizes a layer's
        pages by."""
        return [self.sliding_window if kind == "sliding_attention" else None
                for kind in self.layer_types]


TINY_MELLUM = dict(
    vocab_size=256, hidden_size=64, num_layers=8, num_heads=4,
    num_kv_heads=2, head_dim=16, sliding_window=8, intermediate_size=32,
    num_experts_published=16, num_experts_per_tok=4, max_seq_len=64,
    initializer_range=0.1, rope_parameters={
        "full_attention": dict(_rope_parameters()["full_attention"],
                               rope_theta=10000.0, factor=4.0,
                               original_max_position_embeddings=16,
                               attention_factor=1.1386294361119891),
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 10000.0}})


def rotation(rope, dim):
    """(inverse frequencies [dim / 2], what multiplies cos and sin) of
    one layer kind's ``rope_parameters`` entry."""
    if rope["rope_type"] == "yarn":
        return (yarn_inv_freq(dim, rope["rope_theta"], rope),
                yarn_cos_sin_scale(rope))
    return default_inv_freq(dim, rope["rope_theta"]), 1.0


class MellumAttention(CohereMoeAttention):
    """``CohereMoeAttention`` (window by kind, paged dispatch) whose
    layers of BOTH kinds rotate, over the halves, by their kind's
    ``rope_parameters``."""

    rotation_name = "rope_halves"

    def __init__(self, config: MellumConfig, kind):
        super().__init__(config, kind)
        self.rotary = True
        self.rope = config.rope_parameters[kind]

    def rotate(self, x, pos):
        inv, mult = rotation(self.rope, x.shape[-1])
        return rope_pairs(x, pos, inv, mult, halves=True)


class MellumSparseMLP(SparseExpertMLP):
    """``models/sparse_experts.py``'s layer at this family's settings:
    softmax scores renormalised over the chosen, no shared expert."""

    def __init__(self, config: MellumConfig):
        super().__init__(
            config.hidden_size, config.intermediate_size,
            config.num_experts_published, config.held_experts,
            config.num_experts_per_tok, 0, config.initializer_range,
            scoring="softmax")
        self.config = config


class MellumBlock(Layer):
    def __init__(self, config: MellumConfig, kind):
        super().__init__()
        self.kind = kind
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.self_attn = MellumAttention(config, kind)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps)
        self.mlp = MellumSparseMLP(config)

    def forward(self, x, cache=None):
        with scope("attn_window" if self.kind == "sliding_attention"
                   else "attn_full"):
            x = x + self.self_attn(self.input_layernorm(x), cache=cache)
        return x + self.mlp(self.post_attention_layernorm(x), cache=cache)


class MellumModel(Layer):
    def __init__(self, config: MellumConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=ParamAttr(initializer=Normal(
                0.0, config.initializer_range)))
        self.layers = LayerList([MellumBlock(config, kind)
                                 for kind in config.layer_types])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids, caches=None):
        with scope("embed"):
            x = self.embed_tokens(input_ids)
        for i, blk in enumerate(self.layers):
            x = blk(x, cache=None if caches is None else caches[i])
        return self.norm(x)


class MellumForCausalLM(Layer):
    def __init__(self, config: MellumConfig):
        super().__init__()
        self.config = config
        self.model = MellumModel(config)
        self.lm_head = Linear(
            config.hidden_size, config.vocab_size, bias_attr=False,
            weight_attr=ParamAttr(initializer=Normal(
                0.0, config.initializer_range)))

    def forward(self, input_ids, labels=None, caches=None):
        hidden = self.model(input_ids, caches=caches)
        with scope("head"):
            logits = self.lm_head(hidden)
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
