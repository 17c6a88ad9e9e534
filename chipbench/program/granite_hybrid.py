"""Granite 4.0-H (Mamba-2 layers beside attention layers) through
``paddle_tpu.models.granite_hybrid.GraniteHybridForCausalLM``."""
from __future__ import annotations

FIELDS = ("vocab_size", "hidden_size", "num_layers", "layer_types",
          "num_heads", "num_kv_heads", "intermediate_size", "max_seq_len",
          "rms_norm_eps", "initializer_range", "embedding_multiplier",
          "logits_scaling", "residual_multiplier", "attention_multiplier",
          "mamba_n_heads", "mamba_d_head", "mamba_d_state",
          "mamba_n_groups", "mamba_d_conv", "mamba_expand",
          "mamba_chunk_size", "tie_word_embeddings")


def build(cfg, dtype):
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models.granite_hybrid import (
        GraniteHybridConfig, GraniteHybridForCausalLM)
    from paddle_tpu.nn.initializer import Normal
    prev, draw = paddle.get_default_dtype(), Normal._init
    paddle.set_default_dtype(dtype)
    # the harness installs its seeded weights next, so this one process
    # does not draw the model's own 3.2 B random normals (~25 s cold)
    Normal._init = lambda self, shape, dtype: jnp.zeros(tuple(shape), dtype)
    try:
        return GraniteHybridForCausalLM(
            GraniteHybridConfig(**{k: cfg[k] for k in FIELDS}))
    finally:
        Normal._init = draw
        paddle.set_default_dtype(prev)
