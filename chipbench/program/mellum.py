"""Mellum 2 decoders (Mellum2-12B-A2.5B) through
``paddle_tpu.models.mellum.MellumForCausalLM``."""
from __future__ import annotations

FIELDS = ("vocab_size", "hidden_size", "num_layers", "layer_types",
          "num_heads", "num_kv_heads", "head_dim", "sliding_window",
          "rope_parameters", "rms_norm_eps", "intermediate_size",
          "num_experts_published", "num_experts_per_tok", "max_seq_len",
          "initializer_range", "tie_word_embeddings")


def build(cfg, dtype):
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models.mellum import MellumConfig, MellumForCausalLM
    from paddle_tpu.nn.initializer import Normal
    prev, draw = paddle.get_default_dtype(), Normal._init
    paddle.set_default_dtype(dtype)
    # the harness installs its seeded weights next: the model's own 3.8 B
    # random normals are neither drawn nor held
    Normal._init = lambda self, shape, dtype: jnp.zeros((), dtype)
    try:
        return MellumForCausalLM(MellumConfig(
            held_experts=tuple(cfg["held_experts"]),
            **{k: cfg[k] for k in FIELDS}))
    finally:
        Normal._init = draw
        paddle.set_default_dtype(prev)
