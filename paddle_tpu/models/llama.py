"""Llama model family (Llama-2 architecture: RMSNorm pre-norm, rotary
position embeddings, SwiGLU MLP, optional grouped-query attention).

Reference capability: PaddleNLP Llama trained via Fleet hybrid parallelism
— BASELINE.md config 4 (Llama-2 7B, TP×PP on v5p-32).  TPU-native design:
rope and RMS norm run through the fused Pallas kernels
(paddle_tpu/pallas/fused.py), attention through the Pallas flash kernel;
GQA repeats K/V heads on the fly (one broadcast, fused by XLA) so the
flash kernel sees equal Q/K/V shapes.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

from ..observability.tracing import scope

from ..nn import Layer, Linear, Embedding, RMSNorm, LayerList
from ..nn import functional as F
from ..nn.initializer import Normal, ParamAttr
from ..tensor_ops import manipulation as MA
from ..tensor_ops import linalg as LA
from ..incubate.nn import functional as IF


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 0             # 0 -> num_heads (MHA); < heads = GQA
    intermediate_size: int = 0        # 0 -> llama default (8h/3 rounded)
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    use_flash_attention: bool = True
    tie_word_embeddings: bool = False

    def __post_init__(self):
        if self.num_kv_heads == 0:
            self.num_kv_heads = self.num_heads
        if self.intermediate_size == 0:
            # llama: 2/3 * 4h rounded up to a multiple of 256
            m = int(8 * self.hidden_size / 3)
            self.intermediate_size = 256 * ((m + 255) // 256)
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be divisible by num_kv_heads")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads


LLAMA2_7B = dict(hidden_size=4096, num_layers=32, num_heads=32,
                 intermediate_size=11008)
LLAMA2_13B = dict(hidden_size=5120, num_layers=40, num_heads=40,
                  intermediate_size=13824)
LLAMA2_70B = dict(hidden_size=8192, num_layers=80, num_heads=64,
                  num_kv_heads=8, intermediate_size=28672)
TINY_LLAMA = dict(hidden_size=128, num_layers=2, num_heads=4,
                  num_kv_heads=2, intermediate_size=384, vocab_size=512,
                  max_seq_len=256)


def llama_config(name: str, **overrides) -> LlamaConfig:
    presets = {"llama2-7b": LLAMA2_7B, "llama2-13b": LLAMA2_13B,
               "llama2-70b": LLAMA2_70B, "tiny": TINY_LLAMA}
    cfg = dict(presets[name])
    cfg.update(overrides)
    return LlamaConfig(**cfg)


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        h, d = config.hidden_size, config.head_dim
        kv = config.num_kv_heads * d
        w_init = ParamAttr(initializer=Normal(0.0, config.initializer_range))
        out_init = ParamAttr(initializer=Normal(
            0.0, config.initializer_range / math.sqrt(2 * config.num_layers)))
        self.q_proj = Linear(h, h, weight_attr=w_init, bias_attr=False)
        self.k_proj = Linear(h, kv, weight_attr=w_init, bias_attr=False)
        self.v_proj = Linear(h, kv, weight_attr=w_init, bias_attr=False)
        self.o_proj = Linear(h, h, weight_attr=out_init, bias_attr=False)

    def forward(self, x, cache=None):
        cfg = self.config
        b, s, h = x.shape
        d = cfg.head_dim
        q = MA.reshape(self.q_proj(x), [b, s, cfg.num_heads, d])
        k = MA.reshape(self.k_proj(x), [b, s, cfg.num_kv_heads, d])
        v = MA.reshape(self.v_proj(x), [b, s, cfg.num_kv_heads, d])
        if cache is not None:
            from ..tensor_ops import creation
            off = cache["offset"]
            pos = creation.arange(s, dtype="int32")
            if len(getattr(off, "shape", [])) == 1:
                # per-slot offsets (serving): [B, S] rope positions
                pos = MA.reshape(off, [b, 1]) + MA.reshape(pos, [1, s])
            else:
                pos = pos + off
            q, k, _ = IF.fused_rotary_position_embedding(
                q, k, position_ids=pos, rotary_emb_base=cfg.rope_theta)
        else:
            q, k, _ = IF.fused_rotary_position_embedding(
                q, k, rotary_emb_base=cfg.rope_theta)
        if cache is not None:
            # cache stores PRE-repeat K/V (num_kv_heads) — the MMHA op
            # groups Q heads natively, so GQA keeps its memory win
            if "page_table" in cache:
                out = IF.paged_cache_attention(q, k, v, cache)
            else:
                out, cache["k"], cache["v"] = IF.masked_multihead_attention(
                    q, k, v, cache["k"], cache["v"], cache["offset"])
        else:
            # K/V stay at num_kv_heads: the flash kernels index the shared
            # kv head natively (q_head // n_rep in the BlockSpecs), so GQA
            # keeps its K/V HBM-traffic win end to end (reference keeps kv
            # heads distinct in fusion/gpu/masked_multihead_attention.cu).
            # Head-major layout: the relayout fuses into the projections.
            from ..pallas.flash_attention import flash_attention as _fa
            qh = LA.transpose(q, [0, 2, 1, 3])
            kh = LA.transpose(k, [0, 2, 1, 3])
            vh = LA.transpose(v, [0, 2, 1, 3])
            out = _fa(qh, kh, vh, causal=True, training=self.training,
                      head_major=True)
            out = LA.transpose(out, [0, 2, 1, 3])
        return self.o_proj(MA.reshape(out, [b, s, h]))


class LlamaMLP(Layer):
    """SwiGLU: down(silu(gate(x)) * up(x)) (reference: llama modeling;
    fused epilogue is XLA's job — one gate+up matmul would also fit the
    fused_bias_act pattern)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, m = config.hidden_size, config.intermediate_size
        w_init = ParamAttr(initializer=Normal(0.0, config.initializer_range))
        out_init = ParamAttr(initializer=Normal(
            0.0, config.initializer_range / math.sqrt(2 * config.num_layers)))
        self.gate_proj = Linear(h, m, weight_attr=w_init, bias_attr=False)
        self.up_proj = Linear(h, m, weight_attr=w_init, bias_attr=False)
        self.down_proj = Linear(m, h, weight_attr=out_init, bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaBlock(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, x, cache=None):
        # scopes name a compiled program's operations by layer kind in
        # an xprof view (docs/OBSERVABILITY.md, "Names on the device")
        with scope("attn"):
            x = x + self.self_attn(self.input_layernorm(x), cache=cache)
        with scope("mlp"):
            x = x + self.mlp(self.post_attention_layernorm(x))
        return x


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        emb_init = ParamAttr(initializer=Normal(0.0,
                                                config.initializer_range))
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size,
                                      weight_attr=emb_init)
        self.layers = LayerList([LlamaBlock(config)
                                 for _ in range(config.num_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids, caches=None):
        with scope("embed"):
            x = self.embed_tokens(input_ids)
        for i, blk in enumerate(self.layers):
            x = blk(x, cache=None if caches is None else caches[i])
        return self.norm(x)


class LlamaForCausalLM(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  bias_attr=False)

    def forward(self, input_ids, labels=None, caches=None):
        hidden = self.llama(input_ids, caches=caches)
        with scope("head"):
            if self.lm_head is not None:
                logits = self.lm_head(hidden)
            else:
                logits = F.linear(hidden,
                                  self.llama.embed_tokens.weight.T)
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
        """KV-cache incremental decoding (models/generation.py)."""
        from .generation import generate
        return generate(self, input_ids, max_new_tokens=max_new_tokens,
                        temperature=temperature, top_k=top_k,
                        top_p=top_p, repetition_penalty=repetition_penalty,
                        use_cache=use_cache, eos_token_id=eos_token_id)

    def num_params(self):
        return sum(p.size for p in self.parameters())

    def flops_per_token(self, seq_len=None):
        cfg = self.config
        s = seq_len or cfg.max_seq_len
        return 6 * self.num_params() + \
            12 * cfg.num_layers * cfg.hidden_size * s
