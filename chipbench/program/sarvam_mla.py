"""``sarvam_mla`` decoders (Sarvam-105B), one chip's share, through
``paddle_tpu.models.sarvam_mla.SarvamMLAForCausalLM``."""
from __future__ import annotations

FIELDS = ("vocab_size", "hidden_size", "num_layers", "num_heads",
          "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
          "v_head_dim", "first_k_dense_replace", "num_experts_published",
          "num_experts_per_tok", "num_shared_experts",
          "routed_scaling_factor", "rope_theta", "rope_scaling",
          "rms_norm_eps", "max_seq_len", "initializer_range",
          "tie_word_embeddings")


def build(cfg, dtype):
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models.sarvam_mla import (
        SarvamMLAConfig, SarvamMLAForCausalLM)
    from paddle_tpu.nn.initializer import Normal
    prev, draw = paddle.get_default_dtype(), Normal._init
    paddle.set_default_dtype(dtype)
    # the harness installs its seeded weights next: the model's own 2.7 B
    # random normals are neither drawn nor held
    Normal._init = lambda self, shape, dtype: jnp.zeros((), dtype)
    try:
        return SarvamMLAForCausalLM(SarvamMLAConfig(
            held_experts=tuple(cfg["held_experts"]),
            # the benchmark's ``intermediate_size`` is one expert's width
            intermediate_size=cfg["dense_intermediate_size"],
            moe_intermediate_size=cfg["intermediate_size"],
            **{k: cfg[k] for k in FIELDS}))
    finally:
        Normal._init = draw
        paddle.set_default_dtype(prev)
