"""Benchmark harness: GPT-2 124M compiled train step, in one process, on
the device JAX gives it.

Prints ONE JSON line {"metric", "value", "unit", "platform",
"device_kind", "device_count", ...} and a '#' comment line on stderr.
It measures a chip: without ``JAX_PLATFORMS=cpu`` in the environment it
fails unless ``jax.devices()`` is a TPU.  Told ``JAX_PLATFORMS=cpu`` it
runs a 2-layer smoke of the same code path whose metric name says
``cpu_smoke`` and which prints no MFU — a host has no peak to hold one
against (``cost_model.DEVICE_SPECS``).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _log(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def main():
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.core.op_cache import ensure_compile_cache
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.models.gpt import gpt_config

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and os.environ.get("JAX_PLATFORMS", "") != "cpu":
        raise SystemExit(
            f"bench.py measures a TPU and found platform {dev.platform!r} "
            f"({dev.device_kind!r}); for the CPU smoke of the same code "
            "path say so: JAX_PLATFORMS=cpu python bench.py")
    _log(f"platform={dev.platform} device_kind={dev.device_kind!r} "
         f"devices={jax.device_count()} compile cache at "
         f"{ensure_compile_cache()}")

    if on_tpu:
        cfg = gpt_config("gpt2-124m", max_seq_len=1024)
        batch, seq, steps, warmup = 8, 1024, 20, 3
    else:
        cfg = gpt_config("gpt2-124m", num_layers=2, max_seq_len=256)
        batch, seq, steps, warmup = 2, 256, 20, 2

    paddle.seed(0)
    with paddle.amp.auto_cast(enable=on_tpu, level="O2",
                              dtype="bfloat16"):
        model = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters(),
                                 weight_decay=0.01)

    rng = np.random.default_rng(0)
    data = rng.integers(0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32)
    x = paddle.to_tensor(data[:, :-1])
    y = paddle.to_tensor(data[:, 1:])
    # warmup/discovery run at batch 1: the two eager passes to_static needs
    # are memory-hostile at full batch (the eager tape holds every
    # residual); the batch-polymorphic input_spec lets jax.jit re-trace the
    # same bound program for the full batch without another eager pass
    x1 = paddle.to_tensor(data[:1, :-1])
    y1 = paddle.to_tensor(data[:1, 1:])

    amp_level = "O2" if on_tpu else "O0"

    def _forward(x, y):
        with paddle.amp.auto_cast(enable=on_tpu, level=amp_level,
                                  dtype="bfloat16"):
            _, loss = model(x, labels=y)
        return loss

    def _eager_step(x, y, update=True):
        loss = _forward(x, y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    # the framework-owned compiled train step (framework/train_step.py,
    # FLAGS_compiled_train_step, default ON) fuses fwd+bwd+optimizer into
    # one donated-buffer program; BENCH_TO_STATIC=1 pins the legacy
    # to_static lane, and the flag off runs op-by-op eager — the three
    # lanes the ISSUE 8 gate compares
    use_compiled = (paddle.get_flags("FLAGS_compiled_train_step")
                    ["FLAGS_compiled_train_step"]
                    and not os.environ.get("BENCH_TO_STATIC"))
    _cstep = None
    if use_compiled:
        from paddle_tpu.framework.train_step import CompiledTrainStep
        _cstep = CompiledTrainStep(_forward, opt, network=model,
                                   eager_step=_eager_step)

        def train_step(x, y):
            return _cstep(x, y, update=True)
        step_lane = "compiled"
    elif os.environ.get("BENCH_TO_STATIC"):
        @paddle.jit.to_static(input_spec=[
            paddle.jit.InputSpec([None, seq], "int32"),
            paddle.jit.InputSpec([None, seq], "int32")])
        def train_step(x, y):
            loss = _forward(x, y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss
        step_lane = "to_static"
    else:
        train_step = _eager_step
        step_lane = "eager"
    _log(f"train-step lane: {step_lane}")

    # warmup: eager + discovery (batch 1) + ≥2 full-batch compiled calls —
    # the donating jit variant is built after the first compiled call and
    # itself compiles on the second, which must stay out of the timed loop
    t0 = time.perf_counter()
    for _ in range(2):
        loss = train_step(x1, y1)
    for _ in range(max(warmup - 2, 2)):
        loss = train_step(x, y)
    jax.block_until_ready(loss._data_)
    _log(f"warmup done in {time.perf_counter() - t0:.1f}s (compile "
         f"included), loss={float(loss):.4f}")
    if _cstep is not None and not _cstep.compiled:
        raise SystemExit(
            "the compiled lane was asked for and the step is not compiled: "
            f"{_cstep.fallback_reason}")

    # 20-step steady-state window with a trimmed mean: per-step timings
    # with the 2 slowest and 2 fastest dropped average out transient host
    # load (benchmarks/CPU_SMOKE_VARIANCE.md)
    per_step = []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = train_step(x, y)
        jax.block_until_ready(loss._data_)
        per_step.append(time.perf_counter() - t0)
    # force a value read BEFORE reporting: async dispatch errors (e.g.
    # resource exhaustion) must fail the bench, not surface after JSON
    final_loss = float(loss)
    trimmed = sorted(per_step)[2:-2]
    dt = sum(trimmed) / len(trimmed)
    tokens_per_sec = batch * seq / dt

    result = {
        "metric": "gpt2_124m_train_tokens_per_sec"
                  if on_tpu else "gpt2_124m_cpu_smoke_tokens_per_sec",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec/chip",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
        "step_lane": step_lane,
        "step_time_ms_p50": round(float(np.median(per_step)) * 1e3, 3),
        "loss": round(final_loss, 4),
        "batch": batch, "seq": seq, "steps": steps,
    }
    from paddle_tpu.cost_model import device_peak_flops
    peak = device_peak_flops(dev)        # None on a CPU: no MFU there
    if peak is not None:
        # analytic FLOPs from registry metadata: one counted eager
        # forward on the batch-1 slice (FLOPs/token is batch-invariant)
        from paddle_tpu.profiler import count_flops
        with paddle.no_grad():
            _, fc = count_flops(model, x1, labels=y1)
        flops_per_token = fc.train_step_flops / seq
        result["mfu"] = round(tokens_per_sec * flops_per_token / peak, 4)
        result["peak_flops"] = peak
    print(json.dumps(result))
    print("# " + " ".join(f"{k}={v}" for k, v in result.items()
                          if k not in ("metric", "unit")),
          file=sys.stderr)


if __name__ == "__main__":
    main()
