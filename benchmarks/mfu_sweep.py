"""Layout sweep (ISSUE 12) — a CPU-mesh check of the auto-layout planner.

Measure the compiled train step across every dp×mp factorization of a
virtual CPU world (≥4 devices) for an mp-sharded GPT, compare against
the auto-layout planner's projections (``cost_model.plan_layout``), and
emit ``benchmarks/MFU_SWEEP.json``: per-layout step p50 / tokens-per-sec,
the planner's pick, and projected-vs-measured error.  The smoke config
is parameter-heavy with few tokens — the regime where pure dp genuinely
loses (its gradient all-reduce moves the full model and its optimizer
update is replicated per device, while dp×mp shards both) — so the
≥1.3x hybrid-vs-dp gate in ``tools/check_bench_result.py`` measures the
ordering of layouts, not dispatch noise.  Every number here is a host
timing of virtual devices: it ranks layouts against the planner and is
no statement about a chip, so no MFU is derived from it.

Projection calibration: the analytic roofline carries spec-sheet
constants, so absolute CPU-host times are off by a box-dependent scale
plus a fixed per-step dispatch overhead.  Both are absorbed by an
affine two-anchor fit (the dp-only layout and the measured-best
layout); the HELD-OUT layouts' calibrated error is what the ≤25% gate
checks — the model must get the curvature between layouts right, the
anchors only set units.

The parent process never touches JAX (one process for each device
set): every layout is measured by a child pinned to the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)       # `python benchmarks/mfu_sweep.py`
    # without an exported PYTHONPATH must still find paddle_tpu


def _log(msg):
    print(f"[mfu_sweep] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# layout sweep (virtual CPU world)
# ---------------------------------------------------------------------------

_LAYOUT_WORKER = r"""
import json, os, sys, time
n_dev = int(os.environ["MFU_SWEEP_DEVICES"])
flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
         if "host_platform_device_count" not in f]
flags.append(f"--xla_force_host_platform_device_count={n_dev}")
os.environ["XLA_FLAGS"] = " ".join(flags)
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")

cfg_json = json.loads(os.environ["MFU_SWEEP_CONFIG"])
dp, mp = cfg_json["dp"], cfg_json["mp"]
batch, seq = cfg_json["batch"], cfg_json["seq"]
steps, warmup = cfg_json["steps"], cfg_json["warmup"]

import paddle_tpu as paddle
from paddle_tpu.models import ParallelGPTForCausalLM
from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.distributed.fleet.base import _commit_params
from paddle_tpu.framework.train_step import CompiledTrainStep

cfg = GPTConfig(vocab_size=cfg_json["vocab"], hidden_size=cfg_json["hidden"],
                num_layers=cfg_json["layers"], num_heads=cfg_json["heads"],
                max_seq_len=seq, use_flash_attention=False)
paddle.seed(0)
mesh = mesh_mod.init_mesh([dp, mp], ["dp", "mp"])
if mp > 1:
    # hybrid GSPMD lane: the mesh must be ACTIVE so the TP layers'
    # constraints direct the collectives
    mesh_mod.set_mesh(mesh)
model = ParallelGPTForCausalLM(cfg)
opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters(),
                             weight_decay=0.01)
if mp > 1:
    _commit_params(model, mesh)
n_params = int(sum(p.size for p in model.parameters()))
rng = np.random.default_rng(0)
data = rng.integers(0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32)
x, y = paddle.to_tensor(data[:, :-1]), paddle.to_tensor(data[:, 1:])

def forward(x, y):
    _, loss = model(x, labels=y)
    return loss

# dp-only baselines pass the mesh explicitly WITHOUT activating it:
# the shard_map lane (PR 8) with replicated weights — the exact
# pre-ISSUE-12 best case for this model at this world size
step = CompiledTrainStep(forward, opt, network=model, mesh=mesh)
for _ in range(warmup):
    loss = step(x, y, update=True)
jax.block_until_ready(loss._data_)
ts = []
for _ in range(steps):
    t0 = time.perf_counter()
    jax.block_until_ready(step(x, y, update=True)._data_)
    ts.append(time.perf_counter() - t0)
p50 = float(np.median(ts)) * 1e3
print(json.dumps({
    "dp": dp, "mp": mp, "p50_ms": p50,
    "tokens_per_sec": batch * seq / (p50 / 1e3),
    "compiled": bool(step.compiled),
    "fallback_reason": step.fallback_reason,
    "n_params": n_params,
    "loss": float(np.asarray(loss._data_)),
}))
"""


def _measure_layout(dp, mp, world, cfg, timeout=900):
    env = dict(os.environ)
    env.update({
        "MFU_SWEEP_DEVICES": str(world),
        "MFU_SWEEP_CONFIG": json.dumps(dict(cfg, dp=dp, mp=mp)),
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": os.pathsep.join(
            [os.path.dirname(_HERE)]
            + ([env_p] if (env_p := os.environ.get("PYTHONPATH")) else [])),
    })
    try:
        r = subprocess.run([sys.executable, "-c", _LAYOUT_WORKER],
                           env=env, capture_output=True, text=True,
                           timeout=timeout)
    except subprocess.TimeoutExpired:
        _log(f"layout dp{dp}xmp{mp} TIMED OUT")
        return None
    if r.returncode != 0:
        _log(f"layout dp{dp}xmp{mp} FAILED: {r.stderr[-500:]}")
        return None
    for line in reversed(r.stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except json.JSONDecodeError:
            continue
    return None


def layout_sweep(args):
    from paddle_tpu.cost_model import plan_layout
    from paddle_tpu.cost_model.planner import candidate_step_time

    world = args.world
    if args.smoke:
        cfg = dict(vocab=16384, hidden=256, layers=2, heads=4,
                   batch=4, seq=8, steps=args.steps or 8, warmup=3)
    else:
        cfg = dict(vocab=32768, hidden=512, layers=4, heads=8,
                   batch=8, seq=32, steps=args.steps or 10, warmup=3)

    layouts = [(world // m, m) for m in range(1, world + 1)
               if world % m == 0 and cfg["hidden"] % m == 0]
    _log(f"sweeping {len(layouts)} layouts over a {world}-device "
         f"virtual world: {layouts}")
    measured = {}
    n_params = None
    for dp, mp in layouts:
        rec = _measure_layout(dp, mp, world, cfg)
        if rec is None:
            continue
        measured[f"dp{dp}mp{mp}"] = rec
        n_params = rec["n_params"]
        _log(f"dp{dp}xmp{mp}: p50 {rec['p50_ms']:.1f}ms "
             f"(compiled={rec['compiled']})")
    if len(measured) < 2 or n_params is None:
        _log("not enough successful layout measurements")
        return 1

    # the recorded COMM_BUDGET files must pass their schema gate — a
    # stale budget failing loudly HERE beats it silently skewing a
    # future budget-calibrated plan (BudgetSchemaError propagates)
    from paddle_tpu.cost_model import load_comm_budgets
    budgets = load_comm_budgets(search_dir=_HERE)
    _log(f"validated {len(budgets)} COMM_BUDGET file(s): "
         f"{sorted(budgets)}")

    # planner projections over the SAME grid, from the measured model
    desc = dict(n_params=float(n_params), n_layers=cfg["layers"],
                hidden=cfg["hidden"], global_batch=cfg["batch"],
                seq_len=cfg["seq"], dtype_bytes=4)
    plan = plan_layout(desc, world, device="cpu")
    for name, rec in measured.items():
        step_s, _ = candidate_step_time(desc, rec["dp"], rec["mp"],
                                        device="cpu")
        rec["projected_raw_ms"] = step_s * 1e3

    # affine two-anchor calibration: dp-only + measured-best absorb the
    # host's scale and fixed dispatch overhead; the held-out layouts'
    # error gates the model's between-layout curvature
    dp_name = f"dp{world}mp1"
    best_name = min(measured, key=lambda n: measured[n]["p50_ms"])
    a = measured.get(dp_name, measured[best_name])
    b = measured[best_name]
    if a is b or abs(a["projected_raw_ms"] - b["projected_raw_ms"]) < 1e-9:
        scale, offset = b["p50_ms"] / b["projected_raw_ms"], 0.0
    else:
        scale = (a["p50_ms"] - b["p50_ms"]) / (a["projected_raw_ms"]
                                               - b["projected_raw_ms"])
        offset = a["p50_ms"] - scale * a["projected_raw_ms"]
    errs = {}
    for name, rec in measured.items():
        rec["projected_ms"] = scale * rec["projected_raw_ms"] + offset
        rec["projected_err"] = abs(rec["projected_ms"] - rec["p50_ms"]) \
            / rec["p50_ms"]
        rec["anchor"] = name in (dp_name, best_name)
        if not rec["anchor"]:
            errs[name] = rec["projected_err"]

    pick_name = f"dp{plan.dp}mp{plan.mp}"
    pick = measured.get(pick_name)
    best = measured[best_name]
    dp_only = measured.get(dp_name)
    rec = {
        "metric": "mfu_sweep_layouts",
        "value": round(best["p50_ms"], 3),
        "unit": "ms",
        "world_size": world,
        "model": dict(desc, n_params=int(n_params)),
        "layouts": {k: {kk: (round(vv, 4) if isinstance(vv, float)
                             else vv) for kk, vv in v.items()}
                    for k, v in measured.items()},
        "speedup_hybrid_vs_dp": round(
            dp_only["p50_ms"] / best["p50_ms"], 3) if dp_only else None,
        "planner": {
            "pick": {"dp": plan.dp, "mp": plan.mp},
            "pick_measured": pick is not None,
            "pick_p50_ms": round(pick["p50_ms"], 3) if pick else None,
            "pick_vs_best": round(pick["p50_ms"] / best["p50_ms"], 4)
            if pick else None,
            "max_projected_err": round(max(errs.values()), 4)
            if errs else 0.0,
            "calibration": {"scale": round(scale, 4),
                            "offset_ms": round(offset, 4),
                            "anchors": sorted({dp_name, best_name})},
            "source": plan.source,
            "projected_step_ms": round(plan.projected_step_s * 1e3, 4),
        },
        "steps": cfg["steps"],
        "batch": cfg["batch"],
        "seq": cfg["seq"],
        "smoke": bool(args.smoke),
        "platform": "cpu",      # the children are pinned to it
    }
    out = args.out or os.path.join(_HERE, "MFU_SWEEP.json")
    try:
        with open(out, "w") as f:
            json.dump(rec, f, indent=1)
    except OSError as e:
        _log(f"could not write {out}: {e}")
    print(json.dumps({k: rec[k] for k in
                      ("metric", "value", "unit", "world_size",
                       "speedup_hybrid_vs_dp", "smoke")}
                     | {"planner_pick": rec["planner"]["pick"],
                        "pick_vs_best": rec["planner"]["pick_vs_best"],
                        "max_projected_err":
                            rec["planner"]["max_projected_err"]}))
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small layout-sweep config for CI")
    ap.add_argument("--world", type=int,
                    default=int(os.environ.get("MFU_SWEEP_WORLD", "4")))
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    return layout_sweep(args)


if __name__ == "__main__":
    sys.exit(main())
