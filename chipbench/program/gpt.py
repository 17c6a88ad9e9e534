"""GPT family through ``paddle_tpu.models.GPTForCausalLM``."""
from __future__ import annotations

FIELDS = ("vocab_size", "hidden_size", "num_layers", "num_heads",
          "max_seq_len", "intermediate_size", "layer_norm_eps",
          "initializer_range", "tie_word_embeddings")


def build(cfg, dtype):
    """The model with parameters of ``dtype`` ("float32": O2 training
    keeps float32 parameters and casts per op; "bfloat16": served)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.models.gpt import GPTConfig
    prev = paddle.get_default_dtype()
    paddle.set_default_dtype(dtype)
    try:
        return GPTForCausalLM(GPTConfig(**{k: cfg[k] for k in FIELDS}))
    finally:
        paddle.set_default_dtype(prev)
