"""Tiny-shape SPMD trial step — profiled confirmation of a parallel plan.

Reference capability: the static auto-parallel tuners validate candidate
plans by running profiled trials instead of trusting the cost model
(reference: distributed/auto_parallel/static/tuner/optimization_tuner.py:194
`_profile_trial`, parallel_tuner.py:36 pp search space).

TPU-native realization: run as
``python -m paddle_tpu.distributed.auto_tuner.spmd_trial`` in a fresh
process (mesh + XLA device count are process-global) with the candidate
in ``PADDLE_AUTO_TUNER_CONFIG``.  Builds a tiny GPT over an n-device
virtual CPU mesh with the candidate's dp/mp/pp/sharding axes — the SAME
fleet machinery a real run uses (single-program SPMD pipeline for pp>1,
Megatron TP for mp>1, ZeRO for sharding>1) — times compiled steps, and
prints ``AUTO_TUNER_METRIC: <tokens_per_sec>`` for the tuner to parse.
Absolute numbers are meaningless on virtual devices; the RELATIVE step
times order candidates by real collective/schedule overhead, which the
roofline can only approximate.
"""
from __future__ import annotations

import os
import time


def main():
    n_devices = int(os.environ.get("PADDLE_TRIAL_DEVICES", "8"))
    import jax

    jax.config.update("jax_platforms", "cpu")
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    os.environ["XLA_FLAGS"] = " ".join(flags)

    from .tuner import current_trial_config
    cand = current_trial_config({}) or {}
    dp = int(cand.get("dp", 1))
    mp = int(cand.get("mp", 1))
    pp = int(cand.get("pp", 1))
    sh = int(cand.get("sharding", 1))
    mb = int(cand.get("micro_batch", 1))
    use_rc = bool(cand.get("use_recompute", False))
    amp = str(cand.get("amp", "O0"))

    hidden = int(os.environ.get("PADDLE_TRIAL_HIDDEN", "64"))
    # depth is FIXED by the caller (divisible by n_devices, hence by any
    # pp candidate) so every candidate times the SAME model
    layers = int(os.environ.get("PADDLE_TRIAL_LAYERS", str(n_devices)))
    seq = int(os.environ.get("PADDLE_TRIAL_SEQ", "64"))
    if layers % pp:
        raise SystemExit(f"trial depth {layers} not divisible by pp={pp}")

    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models.gpt import GPTConfig

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": dp, "mp_degree": mp,
                               "pp_degree": pp, "sharding_degree": sh,
                               "sep_degree": 1}
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=hidden, num_layers=layers,
                    num_heads=4, max_seq_len=seq,
                    use_flash_attention=False, use_recompute=use_rc)
    batch = max(2 * dp * sh, 2 * mb)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32)

    if pp > 1:
        from paddle_tpu.models import GPTForCausalLMPipe
        strategy.pipeline = True
        accum = max(batch // max(mb * dp * sh, 1), 1)
        strategy.pipeline_configs = {"accumulate_steps": accum,
                                     "micro_batch_size": mb}
        fleet.init(strategy=strategy)
        model = fleet.distributed_model(GPTForCausalLMPipe(cfg))
        opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
        x = paddle.to_tensor(ids[:, :-1])
        y = paddle.to_tensor(ids[:, 1:])

        def step():
            with paddle.amp.auto_cast(enable=(amp != "O0"), level=amp,
                                      dtype="bfloat16"):
                return model.train_batch((x, y), opt)
    else:
        from paddle_tpu.models import ParallelGPTForCausalLM
        strategy.sharding = sh > 1
        strategy.sharding_configs = {"stage": 3 if sh > 1 else 1}
        fleet.init(strategy=strategy)
        model = ParallelGPTForCausalLM(cfg, sequence_parallel=False)
        fleet.distributed_model(model)
        opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
        if sh > 1:
            model, opt, _ = fleet.group_sharded_parallel(model, opt,
                                                         level="p_g_os")
        opt = fleet.distributed_optimizer(opt)
        mesh = dist.get_mesh()

        def shard(a):
            return dist.shard_tensor(
                paddle.to_tensor(a), mesh,
                [dist.Shard(0) if n == "dp" else dist.Replicate()
                 for n in mesh.dim_names], stop_gradient=True)

        x, y = shard(ids[:, :-1]), shard(ids[:, 1:])

        @paddle.jit.to_static
        def train_step(x, y):
            with paddle.amp.auto_cast(enable=(amp != "O0"), level=amp,
                                      dtype="bfloat16"):
                _, loss = model(x, labels=y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        def step():
            return train_step(x, y)

    # warmup covers eager + discovery + compile; then time compiled steps
    for _ in range(3):
        loss = step()
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        loss = step()
    _ = float(loss)
    dt = (time.perf_counter() - t0) / reps
    tokens_per_sec = batch * seq / dt
    print(f"AUTO_TUNER_METRIC: {tokens_per_sec:.3f}", flush=True)


if __name__ == "__main__":
    main()
