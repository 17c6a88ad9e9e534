#!/usr/bin/env python
"""Benchmark harness for the BASELINE.md driver configs.

Reference capability: SURVEY.md §7 stage 10 — the repo's own benchmark
harness (the reference publishes no in-tree numbers; see BASELINE.md).

Configs:
  1 mnist        MNIST MLP, eager, single chip — trains to accuracy
  2 gpt2-124m    GPT-2 124M, jit/traced, 1 chip — tokens/sec + MFU
  3 gpt3-dp      GPT-3 1.3B-style, data parallel over the mesh
  4 llama-tp-pp  Llama-2 7B-style, TP (x PP-ready) hybrid
  5 moe          MoE expert-parallel hybrid

On hardware each prints one JSON line {"metric","value","unit",...}.
Without a TPU, pass --preset tiny to run the same code paths on the
virtual CPU mesh (numbers are smoke-scale, marked platform=cpu).

Usage:
  python benchmarks/run.py --config 2 [--preset tiny] [--steps 8]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _now():
    return time.perf_counter()


def _emit(metric, value, unit, extra=None):
    rec = {"metric": metric, "value": round(value, 2), "unit": unit}
    rec.update(extra or {})
    print(json.dumps(rec))


def _platform():
    import jax
    return jax.devices()[0].platform


def _serialize_cpu_dispatch():
    """On the virtual CPU mesh, concurrent in-flight SPMD programs can
    deadlock the in-process communicator's rendezvous (few host cores, 8
    virtual devices).  Serializing dispatch removes the race; real TPUs
    are unaffected."""
    import jax
    # must run BEFORE the CPU client is created — the flag is a client
    # construction option, not a runtime toggle
    try:
        jax.config.update("jax_cpu_enable_async_dispatch", False)
    except Exception:
        pass


def _mfu(model, batch, seq, tokens_per_sec):
    """{"mfu": ...} against the chip's row of the peak table — empty on
    a CPU, which has no peak: a host timing is never printed as MFU."""
    from paddle_tpu.cost_model import device_peak_flops
    peak = device_peak_flops()
    if peak is None:
        return {}
    return {"mfu": round(
        tokens_per_sec * model.flops_per_token(seq) / peak, 4)}


def bench_mnist(args):
    """Config 1: trains to an accuracy threshold (reference analog:
    test/book smoke tests)."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn
    paddle.seed(0)
    model = nn.Sequential(nn.Flatten(), nn.Linear(784, 256), nn.ReLU(),
                          nn.Linear(256, 10))
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    rng = np.random.default_rng(0)
    # synthetic separable data stands in when MNIST files are absent
    w_true = rng.standard_normal((784, 10)).astype(np.float32)
    x_np = rng.standard_normal((2048, 784)).astype(np.float32)
    y_np = (x_np @ w_true).argmax(-1).astype(np.int64)
    x, y = paddle.to_tensor(x_np), paddle.to_tensor(y_np)
    t0 = _now()
    # convergence config: needs enough full-batch steps regardless of the
    # throughput-oriented --steps flag
    for epoch in range(max(args.steps, 40)):
        loss = paddle.nn.functional.cross_entropy(model(x), y).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
    acc = float((model(x).argmax(-1) == y).astype("float32").mean()
                .numpy())
    _emit("mnist_mlp_accuracy", acc, "fraction",
          {"seconds": round(_now() - t0, 1), "platform": _platform(),
           "pass": acc > 0.8})
    return acc > 0.8


def _train_loop(model, opt, ids, steps, warmup, use_to_static=True):
    import jax
    import paddle_tpu as paddle

    def step_fn(x, y):
        _, loss = model(x, labels=y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(step_fn) if use_to_static else step_fn
    for _ in range(max(warmup, 1)):   # >=1: compile must not be timed
        loss = step(ids, ids)
    jax.block_until_ready(loss._data_)
    t0 = _now()
    for _ in range(steps):
        loss = step(ids, ids)
    jax.block_until_ready(loss._data_)
    return _now() - t0, float(loss.numpy())


def bench_gpt2(args):
    """Config 2: single-chip GPT-2 124M (the bench.py flagship)."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.models.gpt import gpt_config
    tiny = args.preset == "tiny"
    cfg = gpt_config("gpt2-124m",
                     **({"num_layers": 2, "max_seq_len": 128,
                         "use_flash_attention": False} if tiny else
                        {"max_seq_len": 1024}))
    batch, seq = (2, 128) if tiny else (8, 1024)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())
    ids = paddle.to_tensor(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq)).astype("int32"))
    dt, loss = _train_loop(model, opt, ids, args.steps, args.warmup)
    tps = batch * seq * args.steps / dt
    _emit("gpt2_124m_train_tokens_per_sec", tps, "tokens/sec/chip",
          {"loss": loss, "platform": _platform(),
           **_mfu(model, batch, seq, tps)})


def _fleet_model(kind, tiny, strategy_cfg):
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    s = fleet.DistributedStrategy()
    s.hybrid_configs = strategy_cfg
    shard_deg = strategy_cfg.get("sharding_degree", 1)
    if shard_deg > 1:
        s.sharding = True
        s.sharding_configs = {"stage": 3}
    fleet.init(is_collective=True, strategy=s)
    paddle.seed(0)
    if kind == "gpt-dp":
        from paddle_tpu.models import ParallelGPTForCausalLM
        from paddle_tpu.models.gpt import gpt_config
        cfg = gpt_config("gpt3-1.3b",
                         **({"num_layers": 2, "hidden_size": 256,
                             "num_heads": 4, "vocab_size": 1024,
                             "max_seq_len": 128,
                             "use_flash_attention": False} if tiny else
                            {"max_seq_len": 2048}))
        model = ParallelGPTForCausalLM(cfg)
    elif kind == "llama-tp":
        from paddle_tpu.models import ParallelLlamaForCausalLM, llama_config
        cfg = llama_config("tiny" if tiny else "llama2-7b")
        model = ParallelLlamaForCausalLM(cfg)
    else:  # moe
        from paddle_tpu.models import ParallelGPTForCausalLM
        from paddle_tpu.models.gpt import gpt_config
        cfg = gpt_config("gpt2-124m",
                         **({"num_layers": 2, "hidden_size": 128,
                             "num_heads": 4, "vocab_size": 512,
                             "max_seq_len": 64,
                             "use_flash_attention": False} if tiny else
                            {"max_seq_len": 1024}))
        model = ParallelGPTForCausalLM(cfg, moe_every=2, num_experts=4)
    fleet.distributed_model(model)
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())
    if shard_deg > 1:
        # ZeRO-3: params/grads/opt-state sharded over the sharding axis
        # (the dryrun-proven recipe)
        model, opt, _ = fleet.group_sharded_parallel(model, opt,
                                                     level="p_g_os")
    opt = fleet.distributed_optimizer(opt)
    return model, opt, cfg


def _bench_fleet(kind, metric, args, strategy_cfg):
    import numpy as np
    import jax
    import paddle_tpu as paddle
    _serialize_cpu_dispatch()
    tiny = args.preset == "tiny"
    import paddle_tpu.distributed as dist
    model, opt, cfg = _fleet_model(kind, tiny, strategy_cfg)
    mesh = dist.get_mesh()
    dp = max(mesh.get_dim_size("dp"), 1)
    batch = dp * (2 if tiny else 8)
    seq = min(cfg.max_seq_len, 128 if tiny else 2048)
    # shard the global batch over dp up front (the input contract; a
    # replicated batch would force GSPMD reshards in every eager op)
    ids = dist.shard_tensor(
        paddle.to_tensor(np.random.default_rng(0).integers(
            0, cfg.vocab_size, (batch, seq)).astype("int32")),
        mesh, [dist.Shard(0) if n == "dp" else dist.Replicate()
               for n in mesh.dim_names], stop_gradient=True)

    def step_fn():
        _, loss = model(ids, labels=ids)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    # one compiled module per step: eager per-op dispatch with many
    # in-flight SPMD programs can race the in-process CPU communicator's
    # rendezvous (and on TPU, one fused program is the perf-correct shape)
    step = paddle.jit.to_static(step_fn)
    for _ in range(max(args.warmup, 1)):   # >=1: compile must not be timed
        loss = step()
    jax.block_until_ready(loss._data_)
    if getattr(args, "comm_report", False):
        # per-axis communication budget from the COMPILED step program +
        # roofline projection — multi-chip performance evidence without
        # multi-chip hardware (VERDICT r2 item 7)
        from paddle_tpu.profiler.comm_budget import budget_report
        hlo = step.compiled_hlo()
        report = budget_report(hlo, mesh, device="v5e")
        report.update({"metric": metric + "_comm_budget",
                       "mesh": {n: mesh.get_dim_size(n)
                                for n in mesh.dim_names},
                       "batch": batch, "seq": seq,
                       "platform": _platform()})
        out_path = os.path.join(os.path.dirname(__file__),
                                f"COMM_BUDGET_{kind}.json")
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1)
        print(json.dumps({
            "metric": report["metric"],
            "value": round(report["projected_comm_seconds_per_step"] * 1e3,
                           4),
            "unit": "ms/step (roofline)",
            "collectives": len(report["collectives"]),
            "report": out_path}))
        return
    t0 = _now()
    for _ in range(args.steps):
        loss = step()
    jax.block_until_ready(loss._data_)
    dt = _now() - t0
    n_dev = jax.device_count()
    tps = batch * seq * args.steps / dt
    _emit(metric, tps / n_dev, "tokens/sec/chip",
          {"total_tokens_per_sec": round(tps, 1), "devices": n_dev,
           "loss": float(loss.numpy()), "platform": _platform(),
           **_mfu(model, batch, seq, tps / n_dev)})


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True,
                    choices=["1", "mnist", "2", "gpt2-124m", "3", "gpt3-dp",
                             "4", "llama-tp-pp", "5", "moe"])
    ap.add_argument("--preset", default="auto",
                    choices=["auto", "tiny", "full"],
                    help="auto: full on TPU, tiny on CPU — a default TPU "
                         "run must never record smoke-scale numbers under "
                         "the flagship metric names")
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--comm-report", action="store_true",
                    help="emit the per-axis communication budget of the "
                         "compiled step (configs 3-5) instead of timing")
    args = ap.parse_args()

    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        _serialize_cpu_dispatch()
    if args.preset == "auto":
        args.preset = "full" if _platform() == "tpu" else "tiny"

    c = args.config
    if c in ("1", "mnist"):
        ok = bench_mnist(args)
        sys.exit(0 if ok else 1)
    elif c in ("2", "gpt2-124m"):
        bench_gpt2(args)
    elif c in ("3", "gpt3-dp"):
        # DP-dominant hybrid (dp x ZeRO-3 sharding x mp2) — the recipe the
        # multichip dryrun validates; on the virtual CPU mesh wider pure-dp
        # layouts trip an XLA in-process-communicator rendezvous edge
        _bench_fleet("gpt-dp", "gpt3_1p3b_dp_tokens_per_sec_chip", args,
                     {"dp_degree": -1, "sharding_degree": 2,
                      "mp_degree": 2})
    elif c in ("4", "llama-tp-pp"):
        _bench_fleet("llama-tp", "llama2_7b_tp_tokens_per_sec_chip", args,
                     {"dp_degree": -1, "mp_degree": 2})
    elif c in ("5", "moe"):
        _bench_fleet("moe", "moe_ep_tokens_per_sec_chip", args,
                     {"dp_degree": -1, "mp_degree": 2})


if __name__ == "__main__":
    main()
