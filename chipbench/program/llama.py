"""Llama-architecture models (Mistral-7B among them) through
``paddle_tpu.models.LlamaForCausalLM``."""
from __future__ import annotations

FIELDS = ("vocab_size", "hidden_size", "num_layers", "num_heads",
          "num_kv_heads", "intermediate_size", "max_seq_len", "rope_theta",
          "rms_norm_eps", "initializer_range", "tie_word_embeddings")


def build(cfg, dtype):
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.models.llama import LlamaConfig
    prev = paddle.get_default_dtype()
    paddle.set_default_dtype(dtype)
    try:
        return LlamaForCausalLM(LlamaConfig(**{k: cfg[k] for k in FIELDS}))
    finally:
        paddle.set_default_dtype(prev)
