"""The decoder shell the served families share, and their one
grouped-query attention.

A family module keeps its config, its tiny preset, its block and a
``DecoderForCausalLM`` naming the block and its checkpoints' leaves:
``<root>.<embed>.weight``, ``<root>.layers.<i>.*``, ``<root>.<norm>.weight``
and ``lm_head.weight`` unless the head is the embedding.  Parameters are
drawn in construction order: embedding, layers, final norm, head.

What a family or a fault of the benchmark's controls replaces is read at
call time: ``window`` and ``rotary`` set after construction, the module
functions a rotation calls, ``IF.paged_cache_attention``.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from ..core.dispatch import apply_op
from ..core.tensor import Tensor
from ..incubate.nn import functional as IF
from ..nn import Embedding, Layer, LayerList, Linear, RMSNorm
from ..nn import functional as F
from ..nn.initializer import Normal, ParamAttr
from ..observability.tracing import scope
from ..tensor_ops import linalg as LA
from ..tensor_ops import manipulation as MA


class GroupedQueryAttention(Layer):
    """Grouped-query attention without bias over ``config.num_heads``
    query heads of ``config.head_dim`` and ``config.num_kv_heads`` key
    and value heads.  ``window``: a query sees the latest ``window``
    positions (None: all of them); ``rotary``: q and k are rotated by
    position (``rotate_qk``)."""

    #: the op the rotation of ``rotate_qk`` is traced as
    rotation_name = None
    #: what scales the scores (None: ``head_dim^-1/2``)
    scale = None
    #: why a dense ``{"k", "v"}`` cache is refused (None: it is read)
    dense_refusal = None

    def __init__(self, config, window=None, rotary=False):
        super().__init__()
        self.config = config
        self.window = window
        self.rotary = rotary
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

    def rotate_qk(self, q, k, cache):
        """q and k, each rotated by the family's ``rotate(x, pos)`` (a
        raw [B, S, H, D] array, positions [B, S] or [S]) at their
        positions: the cache's offsets onward, 0 onward without one."""
        s = q.shape[1]
        off = None if cache is None else cache["offset"]

        def rotate(qa, ka, *off):
            pos = jnp.arange(s, dtype=jnp.int32)
            if off:
                pos = off[0].astype(jnp.int32).reshape(-1, 1) + pos[None]
            return self.rotate(qa, pos), self.rotate(ka, pos)

        return apply_op(self.rotation_name, rotate,
                        (q, k) if off is None else (q, k, off))

    def whole_sequence(self, q, k, v):
        """Causal attention over a whole sequence with no cache: the
        flash kernel."""
        # K/V stay at num_kv_heads: the flash kernels index the shared kv
        # head natively (q_head // n_rep in the BlockSpecs), so GQA keeps
        # its K/V HBM-traffic win end to end (reference keeps kv heads
        # distinct in fusion/gpu/masked_multihead_attention.cu).
        # Head-major layout: the relayout fuses into the projections.
        from ..pallas.flash_attention import flash_attention as _fa
        out = _fa(LA.transpose(q, [0, 2, 1, 3]),
                  LA.transpose(k, [0, 2, 1, 3]),
                  LA.transpose(v, [0, 2, 1, 3]), causal=True,
                  training=self.training, scale=self.scale, head_major=True)
        return LA.transpose(out, [0, 2, 1, 3])

    def forward(self, x, cache=None):
        cfg = self.config
        b, s, _ = x.shape
        d = cfg.head_dim
        q = MA.reshape(self.q_proj(x), [b, s, cfg.num_heads, d])
        k = MA.reshape(self.k_proj(x), [b, s, cfg.num_kv_heads, d])
        v = MA.reshape(self.v_proj(x), [b, s, cfg.num_kv_heads, d])
        if self.rotary:
            q, k = self.rotate_qk(q, k, cache)
        if cache is None:
            out = self.whole_sequence(q, k, v)
        elif "page_table" in cache:
            if cache.get("window") != self.window:
                raise ValueError(
                    f"the layer's window is {self.window} and its cache's "
                    f"{cache.get('window')}: build the cache with "
                    "layer_windows=config.layer_windows()")
            out = IF.paged_cache_attention(q, k, v, cache, scale=self.scale)
        elif self.dense_refusal is not None:
            raise NotImplementedError(self.dense_refusal)
        else:
            # cache stores PRE-repeat K/V (num_kv_heads) — the MMHA op
            # groups Q heads natively, so GQA keeps its memory win
            out, cache["k"], cache["v"] = IF.masked_multihead_attention(
                q, k, v, cache["k"], cache["v"], cache["offset"],
                scale=self.scale)
        return self.o_proj(MA.reshape(out, [b, s, cfg.num_heads * d]))


class DecoderModel(Layer):
    """The embedding (times ``config.embedding_multiplier`` where the
    family has one), the blocks and the final norm, under the leaf names
    and hooks of ``family``, a ``DecoderForCausalLM`` subclass."""

    def __init__(self, config, family):
        super().__init__()
        self.config = config
        self._names = (family.embed_name, family.norm_name)
        self.multiplier = getattr(config, "embedding_multiplier", None)
        setattr(self, family.embed_name, Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=ParamAttr(initializer=Normal(
                0.0, config.initializer_range))))
        self.layers = LayerList([family.block(config, kind)
                                 for kind in family.layer_kinds(config)])
        setattr(self, family.norm_name, family.final_norm(config))

    @property
    def embedding(self):
        return getattr(self, self._names[0])

    def forward(self, input_ids, caches=None):
        with scope("embed"):
            x = self.embedding(input_ids)
            if self.multiplier is not None:
                x = x * self.multiplier
        for i, blk in enumerate(self.layers):
            x = blk(x, cache=None if caches is None else caches[i])
        return getattr(self, self._names[1])(x)


class DecoderForCausalLM(Layer):
    """A family's decoder and head: ``forward(input_ids, labels=None,
    caches=None)`` gives the logits [B, S, V] (and the mean cross-entropy
    against ``labels``); ``caches`` holds one dict a layer.  The head is
    ``lm_head``, or the embedding where ``config.tie_word_embeddings``,
    times ``logit_multiplier(config)``."""

    #: built as ``block(config, kind)`` for each of ``layer_kinds(config)``
    block = None
    #: the leaf names: the root layer, its embedding, its final norm
    root_name, embed_name, norm_name = "model", "embed_tokens", "norm"

    def __init__(self, config):
        super().__init__()
        self.config = config
        setattr(self, self.root_name, DecoderModel(config, type(self)))
        self.lm_head = None if config.tie_word_embeddings else Linear(
            config.hidden_size, config.vocab_size, bias_attr=False,
            weight_attr=self.head_attr(config))

    @staticmethod
    def layer_kinds(config):
        return config.layer_types

    @staticmethod
    def final_norm(config):
        return RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    @staticmethod
    def logit_multiplier(config):
        return 1.0

    @staticmethod
    def head_attr(config):
        """The untied head's initializer."""
        return ParamAttr(initializer=Normal(0.0, config.initializer_range))

    def forward(self, input_ids, labels=None, caches=None):
        model = getattr(self, self.root_name)
        hidden = model(input_ids, caches=caches)
        with scope("head"):
            if self.lm_head is not None:
                logits = self.lm_head(hidden)
            else:
                logits = F.linear(hidden, model.embedding.weight.T)
            mult = self.logit_multiplier(self.config)
            if mult != 1.0:
                logits = logits * mult
        if labels is not None:
            with scope("loss"):
                loss = F.cross_entropy(
                    MA.reshape(logits, [-1, self.config.vocab_size]),
                    MA.reshape(labels, [-1]))
            return logits, loss
        return logits

    def generate(self, input_ids, *args, **kwargs):
        """``models.generation.generate``.  With ``use_cache=True`` it
        refuses by name the families its dense key/value caches cannot
        serve: window layers (``cohere_moe``, ``mellum``: the caches have
        no window) and latent rows (``sarvam_mla``).  Serve those through
        ``serving.Engine``, whose pages do, or pass ``use_cache=False``."""
        from .generation import generate
        return generate(self, input_ids, *args, **kwargs)

    def num_params(self):
        return sum(p.size for p in self.parameters())


def init_caches(self, batch, max_len, dtype="float32"):
    """Dense per-layer caches for ``generate()`` of a family whose layers
    do not all keep keys and values (a family's ``init_caches``): keys
    and values for the attention layers, an empty recurrent state a row
    where ``config.layer_states`` names one, nothing for the layers
    ``config.storeless_layers`` says hold nothing."""
    from .generation import init_kv_caches
    cfg = self.config
    bare = cfg.storeless_layers() if hasattr(cfg, "storeless_layers") \
        else [False] * cfg.num_layers
    caches = []
    for state, nothing in zip(cfg.layer_states(dtype), bare):
        if nothing:
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


def checked_layer_types(config, kinds):
    """``config.layer_types`` as a list, refused unless it names one of
    ``kinds`` for each of ``config.num_layers`` layers."""
    types = list(config.layer_types)
    if len(types) != config.num_layers:
        raise ValueError(f"{len(types)} layer_types for "
                         f"{config.num_layers} layers")
    bad = set(types) - set(kinds)
    if bad:
        raise ValueError(f"unknown layer kinds {sorted(bad)}")
    return types


def checked_held_experts(config):
    """``config.held_experts`` as (first, count), the run of consecutive
    routed experts an instance holds (None: all of them); refused outside
    the published experts, as is a config that routes a token to more
    experts than there are."""
    held = config.held_experts
    if held is None:
        held = (0, config.num_experts_published)
    first, count = (int(v) for v in held)
    if first < 0 or count < 1 or \
            first + count > config.num_experts_published:
        raise ValueError(
            f"held_experts {held} is not a run of the "
            f"{config.num_experts_published} published experts")
    if config.num_experts_per_tok > config.num_experts_published:
        raise ValueError("more experts a token than experts")
    return first, count
