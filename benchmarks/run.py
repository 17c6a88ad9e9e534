#!/usr/bin/env python
"""Records the per-axis communication budget of a compiled hybrid step.

Builds one of the BASELINE.md fleet configs, compiles its training step
on the mesh JAX gives it, and writes the bytes each mesh axis moves
(`profiler.comm_budget.budget_report` over the compiled HLO, projected
on the v5e roofline) to `benchmarks/COMM_BUDGET_<kind>.json` — the files
`cost_model.load_comm_budgets` validates and the auto-layout planner
calibrates with.  Byte counts of a compiled program, not timings: it
runs on the virtual CPU mesh (`--preset tiny`) as well as on chips.

Configs:
  3 gpt3-dp      GPT-3 1.3B-style, dp x ZeRO-3 sharding x mp2
  4 llama-tp-pp  Llama-2 7B-style, dp x mp2
  5 moe          MoE expert-parallel hybrid, dp x mp2

Usage:
  python benchmarks/run.py --config 3|4|5 --comm-report [--preset tiny]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

CONFIGS = {
    # DP-dominant hybrid — the recipe the multichip dryrun validates; on
    # the virtual CPU mesh wider pure-dp layouts trip an XLA
    # in-process-communicator rendezvous edge
    "gpt3-dp": ("gpt-dp", "gpt3_1p3b_dp_tokens_per_sec_chip",
                {"dp_degree": -1, "sharding_degree": 2, "mp_degree": 2}),
    "llama-tp-pp": ("llama-tp", "llama2_7b_tp_tokens_per_sec_chip",
                    {"dp_degree": -1, "mp_degree": 2}),
    "moe": ("moe", "moe_ep_tokens_per_sec_chip",
            {"dp_degree": -1, "mp_degree": 2}),
}
ALIASES = {"3": "gpt3-dp", "4": "llama-tp-pp", "5": "moe"}


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


def comm_report(kind, metric, strategy_cfg, tiny, warmup):
    import numpy as np
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.profiler.comm_budget import budget_report
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
    # rendezvous
    step = paddle.jit.to_static(step_fn)
    for _ in range(max(warmup, 1)):
        loss = step()
    jax.block_until_ready(loss._data_)
    report = budget_report(step.compiled_hlo(), mesh, device="v5e")
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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True,
                    choices=sorted(ALIASES) + sorted(CONFIGS))
    ap.add_argument("--preset", default="auto",
                    choices=["auto", "tiny", "full"],
                    help="auto: full on TPU, tiny on CPU")
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--comm-report", action="store_true", required=True,
                    help="write the per-axis communication budget of "
                         "the compiled step")
    args = ap.parse_args()

    _serialize_cpu_dispatch()
    if args.preset == "auto":
        args.preset = "full" if _platform() == "tpu" else "tiny"
    kind, metric, strategy_cfg = CONFIGS[ALIASES.get(args.config,
                                                     args.config)]
    comm_report(kind, metric, strategy_cfg, args.preset == "tiny",
                args.warmup)


if __name__ == "__main__":
    main()
