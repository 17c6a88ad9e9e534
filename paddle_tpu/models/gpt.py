"""GPT model family — the flagship decoder-only transformer.

Reference capability: PaddleNLP GPT-2/GPT-3 trained via Fleet hybrid
parallelism (the driver's benchmark configs, BASELINE.md).  TPU-native
design: pre-LN decoder with causal flash attention (Pallas kernel),
bf16-friendly, and mesh-shardable — every Linear/Embedding accepts
tensor-parallel sharding through paddle_tpu.distributed.fleet layers when
constructed with an `mp_degree > 1` mesh (see models/gpt_parallel.py).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np
from ..observability.tracing import scope

from ..nn import Layer, Linear, Embedding, LayerNorm, Dropout, LayerList
from ..nn import functional as F
from ..nn.initializer import Normal, Constant
from ..nn.initializer import ParamAttr
from ..tensor_ops import manipulation as MA
from ..tensor_ops import linalg as LA
from ..tensor_ops import creation


@dataclass
class GPTConfig:
    vocab_size: int = 50304           # padded to multiple of 128 for the MXU
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 1024
    intermediate_size: int = 0        # 0 -> 4*hidden
    dropout: float = 0.0
    attn_dropout: float = 0.0
    layer_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    tie_word_embeddings: bool = True
    use_flash_attention: bool = True
    use_recompute: bool = False       # activation checkpointing per block

    def __post_init__(self):
        if self.intermediate_size == 0:
            self.intermediate_size = 4 * self.hidden_size

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads


# benchmark-standard configs (BASELINE.md configs 2/3/—)
GPT2_124M = dict(hidden_size=768, num_layers=12, num_heads=12)
GPT2_350M = dict(hidden_size=1024, num_layers=24, num_heads=16)
GPT3_1_3B = dict(hidden_size=2048, num_layers=24, num_heads=16)
GPT3_6_7B = dict(hidden_size=4096, num_layers=32, num_heads=32)
GPT3_13B = dict(hidden_size=5120, num_layers=40, num_heads=40)


def gpt_config(name: str, **overrides) -> GPTConfig:
    presets = {"gpt2-124m": GPT2_124M, "gpt2-350m": GPT2_350M,
               "gpt3-1.3b": GPT3_1_3B, "gpt3-6.7b": GPT3_6_7B,
               "gpt3-13b": GPT3_13B}
    cfg = dict(presets[name])
    cfg.update(overrides)
    return GPTConfig(**cfg)


class GPTAttention(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        h = config.hidden_size
        w_init = ParamAttr(initializer=Normal(0.0, config.initializer_range))
        # fused QKV projection: one [h, 3h] matmul keeps the MXU busy
        self.qkv_proj = Linear(h, 3 * h, weight_attr=w_init)
        out_init = ParamAttr(initializer=Normal(
            0.0, config.initializer_range / math.sqrt(2 * config.num_layers)))
        self.out_proj = Linear(h, h, weight_attr=out_init)

    def forward(self, x, cache=None):
        cfg = self.config
        b, s, h = x.shape
        qkv = self.qkv_proj(x)
        qkv = MA.reshape(qkv, [b, s, 3, cfg.num_heads, cfg.head_dim])
        q, k, v = MA.unbind(qkv, axis=2)
        if cache is not None:
            # decode path: static-shape attention against the KV cache
            from ..incubate.nn import functional as IF
            if "page_table" in cache:
                # paged serving cache: K/V live in a shared page pool
                # (plain or int8/fp8-quantized with per-page scales)
                # addressed through this row's page table
                out = IF.paged_cache_attention(q, k, v, cache)
            else:
                out, cache["k"], cache["v"] = IF.masked_multihead_attention(
                    q, k, v, cache["k"], cache["v"], cache["offset"])
        else:
            # head-major [B, H, S, D] into the flash kernels: the
            # relayout fuses into the qkv-projection epilogue instead of
            # standing as bare transposes around the pallas_call
            from ..pallas.flash_attention import flash_attention as _fa
            qh = LA.transpose(q, [0, 2, 1, 3])
            kh = LA.transpose(k, [0, 2, 1, 3])
            vh = LA.transpose(v, [0, 2, 1, 3])
            out = _fa(qh, kh, vh, dropout=cfg.attn_dropout, causal=True,
                      training=self.training, head_major=True)
            out = LA.transpose(out, [0, 2, 1, 3])
        out = MA.reshape(out, [b, s, h])
        return self.out_proj(out)


class GPTMLP(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        h, m = config.hidden_size, config.intermediate_size
        w_init = ParamAttr(initializer=Normal(0.0, config.initializer_range))
        out_init = ParamAttr(initializer=Normal(
            0.0, config.initializer_range / math.sqrt(2 * config.num_layers)))
        self.fc_in = Linear(h, m, weight_attr=w_init)
        self.fc_out = Linear(m, h, weight_attr=out_init)

    def forward(self, x):
        return self.fc_out(F.gelu(self.fc_in(x), approximate=True))


class GPTBlock(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln_1 = LayerNorm(config.hidden_size, epsilon=config.layer_norm_eps)
        self.attn = GPTAttention(config)
        self.ln_2 = LayerNorm(config.hidden_size, epsilon=config.layer_norm_eps)
        self.mlp = GPTMLP(config)
        self.dropout = Dropout(config.dropout)

    def forward(self, x, cache=None):
        # scopes name a compiled program's operations by layer kind in
        # an xprof view (docs/OBSERVABILITY.md, "Names on the device")
        with scope("attn"):
            x = x + self.dropout(self.attn(self.ln_1(x), cache=cache))
        with scope("mlp"):
            x = x + self.dropout(self.mlp(self.ln_2(x)))
        return x


class GPTModel(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        emb_init = ParamAttr(initializer=Normal(0.0, config.initializer_range))
        self.wte = Embedding(config.vocab_size, config.hidden_size,
                             weight_attr=emb_init)
        self.wpe = Embedding(config.max_seq_len, config.hidden_size,
                             weight_attr=emb_init)
        self.drop = Dropout(config.dropout)
        self.h = LayerList([GPTBlock(config)
                            for _ in range(config.num_layers)])
        self.ln_f = LayerNorm(config.hidden_size,
                              epsilon=config.layer_norm_eps)

    def forward(self, input_ids, position_ids=None, caches=None):
        b, s = input_ids.shape
        with scope("embed"):
            if position_ids is None:
                position_ids = creation.arange(s, dtype="int32")
                if caches is not None:
                    off = caches[0]["offset"]
                    if len(getattr(off, "shape", [])) == 1:
                        # per-slot offsets (serving): [B, S] positions so
                        # each row is embedded at its own age
                        position_ids = MA.reshape(off, [b, 1]) + \
                            MA.reshape(position_ids, [1, s])
                    else:
                        position_ids = position_ids + off
            x = self.wte(input_ids) + self.wpe(position_ids)
            x = self.drop(x)
        for i, block in enumerate(self.h):
            if self.config.use_recompute and caches is None \
                    and not x.stop_gradient:
                from ..distributed.fleet.utils import recompute
                x = recompute(block, x)
            else:
                x = block(x, cache=None if caches is None else caches[i])
        return self.ln_f(x)


class GPTForCausalLM(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  bias_attr=False)

    def forward(self, input_ids, labels=None, position_ids=None,
                caches=None):
        hidden = self.gpt(input_ids, position_ids, caches=caches)
        with scope("head"):
            if self.lm_head is not None:
                logits = self.lm_head(hidden)
            else:
                logits = F.linear(hidden, self.gpt.wte.weight.T)
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

    def num_params(self, non_embedding=True):
        n = sum(p.size for p in self.parameters())
        if non_embedding:
            n -= self.gpt.wpe.weight.size
        return n

    def flops_per_token(self, seq_len=None):
        """Approximate train-step FLOPs/token (fwd+bwd), PaLM appendix
        formula: 6N + 12·L·H·Q·T."""
        cfg = self.config
        s = seq_len or cfg.max_seq_len
        n = self.num_params()
        return 6 * n + 12 * cfg.num_layers * cfg.hidden_size * s

    @staticmethod
    def generate_step(model, input_ids, temperature=1.0, top_k=None):
        """Single greedy/sampled decode step (host loop drives generation)."""
        from ..tensor_ops import random as R, search as S
        logits = model(input_ids)
        next_logits = logits[:, -1, :]
        if temperature == 0.0:
            return S.argmax(next_logits, axis=-1)
        next_logits = next_logits / temperature
        if top_k is not None:
            vals, _ = S.topk(next_logits, top_k)
            minv = vals[:, -1:]
            next_logits = MA.masked_fill(next_logits, next_logits < minv,
                                         float("-inf"))
        probs = F.softmax(next_logits, axis=-1)
        return R.multinomial(probs, 1)
