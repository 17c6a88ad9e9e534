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

from ..nn import Layer, Linear, RMSNorm
from ..nn import functional as F
from ..nn.initializer import Normal, ParamAttr
from ..tensor_ops import creation
from ..tensor_ops import manipulation as MA
from ..incubate.nn import functional as IF
from .decoder import DecoderForCausalLM, GroupedQueryAttention


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


class LlamaAttention(GroupedQueryAttention):
    """``GroupedQueryAttention`` whose q and k turn by neox rotary
    positions at ``rope_theta``, through the fused rope op; the whole
    sequence runs on the flash kernel."""

    def __init__(self, config: LlamaConfig):
        super().__init__(config, rotary=True)

    def rotate_qk(self, q, k, cache):
        pos = None
        if cache is not None:
            b, s = q.shape[0], q.shape[1]
            off = cache["offset"]
            pos = creation.arange(s, dtype="int32")
            if len(getattr(off, "shape", [])) == 1:
                # per-slot offsets (serving): [B, S] rope positions
                pos = MA.reshape(off, [b, 1]) + MA.reshape(pos, [1, s])
            else:
                pos = pos + off
        q, k, _ = IF.fused_rotary_position_embedding(
            q, k, position_ids=pos, rotary_emb_base=self.config.rope_theta)
        return q, k


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
    def __init__(self, config: LlamaConfig, kind=None):
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


class LlamaForCausalLM(DecoderForCausalLM):
    """The shell over ``LlamaBlock``; an untied head keeps the default init."""

    block = LlamaBlock
    root_name = "llama"

    @staticmethod
    def layer_kinds(config):
        return [None] * config.num_layers

    @staticmethod
    def head_attr(config):
        return None

    def flops_per_token(self, seq_len=None):
        cfg = self.config
        s = seq_len or cfg.max_seq_len
        return 6 * self.num_params() + \
            12 * cfg.num_layers * cfg.hidden_size * s
