"""``nemotron_h`` decoders (Nemotron-3-Super), one chip's share, through
``paddle_tpu.models.nemotron_h.NemotronHForCausalLM``."""
from __future__ import annotations

FIELDS = ("vocab_size", "hidden_size", "num_layers", "layer_types",
          "num_heads", "num_kv_heads", "head_dim", "max_seq_len",
          "rms_norm_eps", "initializer_range", "mamba_n_heads",
          "mamba_d_head", "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
          "mamba_chunk_size", "moe_latent_size", "num_experts_published",
          "num_experts_per_tok", "num_shared_experts",
          "routed_scaling_factor", "tie_word_embeddings")


def build(cfg, dtype):
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models.nemotron_h import (
        NemotronHConfig, NemotronHForCausalLM)
    from paddle_tpu.nn.initializer import Normal
    prev, draw = paddle.get_default_dtype(), Normal._init
    paddle.set_default_dtype(dtype)
    # the harness installs its seeded weights next: the model's own 4.6 B
    # random normals are neither drawn nor held
    Normal._init = lambda self, shape, dtype: jnp.zeros((), dtype)
    try:
        return NemotronHForCausalLM(NemotronHConfig(
            held_experts=tuple(cfg["held_experts"]),
            # the benchmark's ``intermediate_size`` is one routed
            # expert's width
            moe_intermediate_size=cfg["intermediate_size"],
            moe_shared_expert_intermediate_size=cfg[
                "shared_intermediate_size"],
            **{k: cfg[k] for k in FIELDS}))
    finally:
        Normal._init = draw
        paddle.set_default_dtype(prev)
