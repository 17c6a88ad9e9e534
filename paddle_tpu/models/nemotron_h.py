"""``nemotron_h`` decoders (Nemotron-3-Super): a per-layer stack whose
every block is ONE mixer or feed-forward part, by ``layer_types``.

Blocks are sequential pre-norm with one norm and one residual each:
``x <- x + Mixer_l(RMSNorm(x))``; a final RMSNorm, an untied head; the
embedding is not scaled.  ``Mixer_l`` is, by the layer's kind:

- ``"mamba"``: the Mamba-2 mixer of ``models/granite_hybrid.py``
  (``GraniteMamba2Mixer``) with ``mamba_n_groups`` groups of B/C (head
  ``i`` reads group ``i // (heads / groups)``) and its gated norm over
  each group of ``d_inner / n_groups`` channels on its own;
- ``"attention"``: grouped-query attention with no positional rotation
  (``GraniteAttention``), scores scaled by ``head_dim^-1/2``;
- ``"moe"``: a LatentMoE (``models/sparse_experts.py``): ``s =
  sigmoid(W_r x)`` in float32 over all published experts, ``T =
  top_k(s + b)`` with the bias in the selection alone, ``g_e = factor
  s_e / sum_T s_j``; the routed experts are non-gated ``relu2`` MLPs
  that live in the ``moe_latent_size``-wide latent: ``out = W_lat_up
  sum_{e in T, e held} g_e W2_e relu(W1_e W_lat_down x)^2 + W2_s
  relu(W1_s x)^2`` (the shared expert at full width).

**One chip's share.**  ``held_experts`` = (first, count) names the
consecutive routed experts this instance holds; the layer routes over
all, and what absent experts would add is left out.  ``W_lat_up`` is
linear, so the chips' shares, each through it, add up to the whole
layer with the shared expert counted once (tests/test_nemotron_h.py).

Cache contract (one dict per layer): an attention layer's is paged
(``serving.PagedKVCache``) or a dense ``{"k", "v", "offset"}``; a Mamba
layer's holds its state rows (``models/granite_hybrid.py``); an expert
layer HOLDS NOTHING (``NemotronHConfig.storeless_layers``): its dict
carries ``valid_len`` at most and gets the routing counts back under
``moe_counts``.

Leaf names follow the family's checkpoints (``backbone.embeddings``,
``backbone.layers.<i>.norm``, ``.mixer.*``, ``backbone.norm_f``,
``lm_head``; ``mixer.fc1_latent_proj`` / ``fc2_latent_proj``).
Departures: Linear weights are [in, out]; the selection bias is
``mixer.gate.expert_bias``; routed and shared experts are STACKED
(``mixer.experts.{up,down}_proj`` [E_held, in, out],
``mixer.shared_experts.{up,down}_proj`` [1, in, out]).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax.numpy as jnp

from ..core.tensor import Tensor
from ..nn import Layer, Linear, Embedding, RMSNorm, LayerList
from ..nn import functional as F
from ..nn.initializer import Normal, ParamAttr
from ..observability.tracing import scope
from ..tensor_ops import manipulation as MA
from ..pallas import ssm as _ssm
from .granite_hybrid import GraniteAttention, GraniteMamba2Mixer
from .sparse_experts import SparseExpertMLP

LAYER_KINDS = ("mamba", "attention", "moe")
#: the scope a block's work is named by
_SCOPES = {"mamba": "mamba", "attention": "attn", "moe": "mlp"}


@dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_layers: int = 88
    #: "mamba" | "attention" | "moe" per layer
    layer_types: list | None = None
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    max_seq_len: int = 262144
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 8
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    #: width of one routed expert (it lives in the latent)
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    #: experts the router scores (its width)
    num_experts_published: int = 512
    num_experts_per_tok: int = 22
    num_shared_experts: int = 1
    #: (first, count) of the routed experts held here; None -> all
    held_experts: tuple | None = None
    routed_scaling_factor: float = 5.0
    tie_word_embeddings: bool = False

    def __post_init__(self):
        if self.layer_types is None:
            raise ValueError("layer_types: the family's pattern names "
                             "each layer's kind")
        self.layer_types = list(self.layer_types)
        if len(self.layer_types) != self.num_layers:
            raise ValueError(f"{len(self.layer_types)} layer_types for "
                             f"{self.num_layers} layers")
        bad = set(self.layer_types) - set(LAYER_KINDS)
        if bad:
            raise ValueError(f"unknown layer kinds {sorted(bad)}")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be divisible by num_kv_heads")
        if self.num_heads * self.head_dim != self.hidden_size:
            raise ValueError("num_heads * head_dim must equal hidden_size")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError(
                "mamba_n_heads must be divisible by mamba_n_groups")
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

    @property
    def d_inner(self):
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def attention_multiplier(self):
        """What ``GraniteAttention`` scales its scores by."""
        return 1.0 / math.sqrt(self.head_dim)

    @property
    def num_experts_held(self):
        return self.held_experts[1]

    def layer_states(self, dtype):
        """Per layer, what a sequence keeps between calls
        (``GraniteHybridConfig.layer_states``): the Mamba layers'
        recurrent state, None for the attention layer's pages and for
        the expert layers (which hold nothing: ``storeless_layers``)."""
        state = {
            "conv_state": ((self.mamba_d_conv - 1, self.conv_dim), dtype),
            "ssm_state": _ssm.state_spec(self.mamba_n_heads,
                                         self.mamba_d_head,
                                         self.mamba_d_state,
                                         self.mamba_n_groups)}
        return [dict(state) if kind == "mamba" else None
                for kind in self.layer_types]

    def storeless_layers(self):
        """Per layer, whether it holds nothing between calls (a
        feed-forward part alone)."""
        return [kind == "moe" for kind in self.layer_types]


TINY_NEMOTRON_H = dict(
    vocab_size=256, hidden_size=64, num_layers=5,
    layer_types=["moe", "mamba", "moe", "mamba", "attention"],
    num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=128,
    mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_n_groups=2,
    mamba_chunk_size=8, moe_intermediate_size=48, moe_latent_size=32,
    moe_shared_expert_intermediate_size=96, num_experts_published=16,
    num_experts_per_tok=6, held_experts=(0, 8), initializer_range=0.1)


class NemotronLatentMoE(SparseExpertMLP):
    """``models/sparse_experts.py``'s layer at this family's settings:
    relu2 experts in the latent, a selection bias, the scaling factor on
    the gates, one relu2 shared expert of its own width at full width."""

    def __init__(self, config: NemotronHConfig):
        super().__init__(
            config.hidden_size, config.moe_intermediate_size,
            config.num_experts_published, config.held_experts,
            config.num_experts_per_tok, config.num_shared_experts,
            config.initializer_range, selection_bias=True,
            gate_scale=config.routed_scaling_factor, shared_reduce="sum",
            expert_act="relu2", latent_size=config.moe_latent_size,
            shared_width=config.moe_shared_expert_intermediate_size)
        self.config = config


class NemotronHBlock(Layer):
    def __init__(self, config: NemotronHConfig, kind):
        super().__init__()
        self.kind = kind
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        if kind == "mamba":
            self.mixer = GraniteMamba2Mixer(
                config, norm_groups=config.mamba_n_groups)
        elif kind == "attention":
            self.mixer = GraniteAttention(config)
        else:
            self.mixer = NemotronLatentMoE(config)

    def forward(self, x, cache=None):
        with scope(_SCOPES[self.kind]):
            return x + self.mixer(self.norm(x), cache=cache)


class NemotronHModel(Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        self.embeddings = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=ParamAttr(initializer=Normal(
                0.0, config.initializer_range)))
        self.layers = LayerList([NemotronHBlock(config, kind)
                                 for kind in config.layer_types])
        self.norm_f = RMSNorm(config.hidden_size,
                              epsilon=config.rms_norm_eps)

    def forward(self, input_ids, caches=None):
        with scope("embed"):
            x = self.embeddings(input_ids)
        for i, blk in enumerate(self.layers):
            x = blk(x, cache=None if caches is None else caches[i])
        return self.norm_f(x)


class NemotronHForCausalLM(Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        self.backbone = NemotronHModel(config)
        self.lm_head = Linear(
            config.hidden_size, config.vocab_size, bias_attr=False,
            weight_attr=ParamAttr(initializer=Normal(
                0.0, config.initializer_range)))

    def forward(self, input_ids, labels=None, caches=None):
        hidden = self.backbone(input_ids, caches=caches)
        with scope("head"):
            logits = self.lm_head(hidden)
        if labels is not None:
            with scope("loss"):
                loss = F.cross_entropy(
                    MA.reshape(logits, [-1, self.config.vocab_size]),
                    MA.reshape(labels, [-1]))
            return logits, loss
        return logits

    def init_caches(self, batch, max_len, dtype="float32"):
        """Dense per-layer caches for ``generate()``: keys and values for
        the attention layers, an empty recurrent state a row for the
        Mamba layers, nothing for the expert layers."""
        from .generation import init_kv_caches
        cfg = self.config
        caches = []
        for state, bare in zip(cfg.layer_states(dtype),
                               cfg.storeless_layers()):
            if bare:
                cache = {}
            elif state is None:
                cache = init_kv_caches(1, batch, max_len, cfg.num_kv_heads,
                                       cfg.head_dim, dtype=dtype)[0]
            else:
                cache = {name: Tensor(jnp.zeros((batch,) + tuple(spec[0]),
                                                spec[1]))
                         for name, spec in state.items()}
            caches.append(cache)
        # one clock for all layers: ``generate`` advances the first's
        offset = Tensor(jnp.zeros((), jnp.int32))
        for cache in caches:
            cache["offset"] = offset
        return caches

    def generate(self, input_ids, max_new_tokens=32, temperature=0.0,
                 top_k=None, top_p=None, repetition_penalty=None,
                 use_cache=True, eos_token_id=None):
        from .generation import generate
        return generate(self, input_ids, max_new_tokens=max_new_tokens,
                        temperature=temperature, top_k=top_k,
                        top_p=top_p, repetition_penalty=repetition_penalty,
                        use_cache=use_cache, eos_token_id=eos_token_id)

    def num_params(self):
        return sum(p.size for p in self.parameters())
