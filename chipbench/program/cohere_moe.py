"""Cohere ``cohere2_moe`` decoders (Command A+), one chip's share, through
``paddle_tpu.models.cohere_moe.CohereMoeForCausalLM``."""
from __future__ import annotations

FIELDS = ("vocab_size", "hidden_size", "num_layers", "layer_types",
          "num_heads", "num_kv_heads", "head_dim", "sliding_window",
          "rope_theta", "layer_norm_eps", "intermediate_size",
          "num_experts_published", "num_experts_per_tok",
          "num_shared_experts", "logit_scale", "max_seq_len",
          "initializer_range", "tie_word_embeddings")


def build(cfg, dtype):
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models.cohere_moe import (
        CohereMoeConfig, CohereMoeForCausalLM)
    from paddle_tpu.nn.initializer import Normal
    prev, draw = paddle.get_default_dtype(), Normal._init
    paddle.set_default_dtype(dtype)
    # the harness installs its seeded weights next: the model's own 4.7 B
    # random normals are neither drawn nor held
    Normal._init = lambda self, shape, dtype: jnp.zeros((), dtype)
    try:
        return CohereMoeForCausalLM(CohereMoeConfig(
            held_experts=tuple(cfg["held_experts"]),
            **{k: cfg[k] for k in FIELDS}))
    finally:
        Normal._init = draw
        paddle.set_default_dtype(prev)
