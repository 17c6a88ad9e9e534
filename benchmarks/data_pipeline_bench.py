"""Gate for the paddle_tpu.data input pipeline (ISSUE 18).

Three lanes, one JSON:

* **throughput** — an input-heavy ``Model.fit`` (per-sample host work
  calibrated to ~1.2x the train-step time) fed by ``device_prefetch``
  vs the synchronous ``DataLoader(num_workers=0)`` at equal
  model/batch.  CI floor: >= 1.3x steps/sec — enforced only when the
  host has cores to overlap on (``parallel_host``), the same honesty
  rule as the disagg bench; a 1-core box reports ~1.0x and says so.
* **resume** — kill a fit mid-epoch at step k, checkpoint, resume:
  per-step losses must be bit-equal to the uninterrupted run in the
  eager lane and <= 5e-6 in the compiled lane (whole-step jit
  reassociates reductions).
* **resize** — a 4-rank run checkpoints mid-epoch; a 2-rank world
  resumes from the same state: the union of consumed sample ids must
  be a permutation-free continuation — zero lost, zero duplicated.

Also drills ``data_slow`` fault injection and asserts the starvation
counter + input-bound gauge actually move.

Writes benchmarks/DATA_PIPELINE_BENCH.json (or --out) and prints one
JSON line; tools/check_bench_result.py::check_data_bench gates it.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)       # `python benchmarks/data_pipeline_bench.py`

BATCH = 32
FEATURES = 64
N_SAMPLES = BATCH * 40


def _env():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")


class _HeavyDS:
    """CPU-bound sample generation (decode + augment stand-in); cost
    scales with ``reps`` so the bench can calibrate fetch time against
    the measured step time."""

    def __init__(self, reps, n=N_SAMPLES):
        self.reps = reps
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        rng = np.random.default_rng(i)
        x = rng.standard_normal(1024).astype(np.float32)
        for _ in range(self.reps):
            x = np.tanh(x) * 1.0001      # GIL-released numpy work
        feat = x[:FEATURES]
        y = np.float32(feat.sum())
        return feat, y


def _make_model(paddle, nn, lr=0.01):
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(FEATURES, 128), nn.ReLU(),
                        nn.Linear(128, 1))
    m = paddle.hapi.Model(net)
    opt = paddle.optimizer.SGD(learning_rate=lr,
                               parameters=net.parameters())
    m.prepare(opt, nn.MSELoss())
    return m


def _steps_per_sec(paddle, nn, loader_fn, n_steps, warmup=5):
    """Time a fit of ``n_steps`` global iterations, skipping warmup."""
    m = _make_model(paddle, nn)
    ticks = []

    class T(paddle.hapi.callbacks.Callback):
        def on_train_batch_end(self, step, logs=None):
            ticks.append(time.perf_counter())

    m.fit(loader_fn(), epochs=1000, verbose=0, num_iters=n_steps,
          callbacks=[T()], log_freq=10**9)
    timed = ticks[warmup:]
    if len(timed) < 2:
        return 0.0
    return (len(timed) - 1) / (timed[-1] - timed[0])


def _calibrate_reps(paddle, nn):
    """Pick the per-sample work factor so one batch of host fetch costs
    ~1.2x one eager train step — the input-heavy regime where overlap
    matters but is still winnable."""
    m = _make_model(paddle, nn)
    x = paddle.to_tensor(np.zeros((BATCH, FEATURES), np.float32))
    y = paddle.to_tensor(np.zeros((BATCH, 1), np.float32))
    for _ in range(3):
        m.train_batch([x], [y])
    t0 = time.perf_counter()
    for _ in range(5):
        m.train_batch([x], [y])
    step_ms = (time.perf_counter() - t0) / 5 * 1e3

    probe = _HeavyDS(reps=1)
    for _ in range(2):
        probe[0]
    t0 = time.perf_counter()
    for i in range(10):
        probe[i]
    rep1_ms = (time.perf_counter() - t0) / 10 * 1e3 * BATCH
    reps = max(1, int(round(1.2 * step_ms / max(rep1_ms, 1e-3))))
    return reps, step_ms


def _capture_losses(paddle, nn, D, ckpt_dir, seed, epochs, resume=None,
                    num_iters=None, save_mid=False):
    """Run an input-light fit over a pipeline; return per-step losses.
    ``save_mid`` writes a mid-epoch checkpoint at exit (the preemption
    path's save_now)."""
    from paddle_tpu.hapi.callbacks import ModelCheckpoint
    m = _make_model(paddle, nn, lr=0.05)
    pipe = (D.pipeline(_HeavyDS(reps=1, n=BATCH * 8))
            .shard(0, 1).shuffle(seed=seed)
            .batch(BATCH).device_prefetch(2))
    losses = []

    class L(paddle.hapi.callbacks.Callback):
        def on_train_batch_end(self, step, logs=None):
            losses.append(float(logs.get("loss")))

    cbs = [L()]
    ck = None
    if save_mid:
        ck = ModelCheckpoint(save_freq=10**9, save_dir=ckpt_dir)
        cbs.append(ck)
    m.fit(pipe, epochs=epochs, verbose=0, log_freq=1, callbacks=cbs,
          num_iters=num_iters, resume=resume,
          save_dir=None if save_mid else ckpt_dir)
    if save_mid:
        m._sync_compiled_state()
        ck.save_now(next_epoch=pipe.epoch)
        ck.manager.wait()
    return losses


def _resume_drill(paddle, nn, D, compiled, kill_at=5, epochs=2):
    import paddle_tpu.utils.flags as flags
    flags.set_flags({"FLAGS_compiled_train_step": 1 if compiled else 0})
    try:
        ckpt = f"/tmp/data_bench_ckpt_{'c' if compiled else 'e'}"
        shutil.rmtree(ckpt, ignore_errors=True)
        ref = _capture_losses(paddle, nn, D, ckpt, seed=9, epochs=epochs)
        shutil.rmtree(ckpt, ignore_errors=True)
        head = _capture_losses(paddle, nn, D, ckpt, seed=9, epochs=epochs,
                               num_iters=kill_at, save_mid=True)
        tail = _capture_losses(paddle, nn, D, ckpt, seed=9, epochs=epochs,
                               resume=True)
        shutil.rmtree(ckpt, ignore_errors=True)
        got = head + tail
        n = min(len(got), len(ref))
        diffs = [abs(a - b) for a, b in zip(got[:n], ref[:n])]
        return {
            "kill_at_step": kill_at,
            "steps_ref": len(ref),
            "steps_resumed": len(got),
            "bitwise_equal": len(got) == len(ref)
            and all(d == 0.0 for d in diffs),
            "max_abs_diff": max(diffs) if diffs else float("nan"),
        }
    finally:
        flags.set_flags({"FLAGS_compiled_train_step": 1})


def _resize_drill(D, from_deg=4, to_deg=2, per_rank_batches=2, bs=2):
    """4-rank mid-epoch checkpoint -> 2-rank resume; audit sample ids."""
    n = from_deg * to_deg * per_rank_batches * bs * 3

    class IdDS:
        def __len__(self):
            return n

        def __getitem__(self, i):
            return np.int64(i)

    def run(rank, deg, state, nb):
        p = D.pipeline(IdDS()).shard(rank, deg).shuffle(seed=3).batch(bs)
        if state is not None:
            p.load_state_dict(state)
        out, it = [], iter(p)
        for _ in range(nb):
            out.extend(int(v) for v in np.asarray(next(it)._data))
        return out, p.state_dict()

    before, state = [], None
    for r in range(from_deg):
        ids, state = run(r, from_deg, None, per_rank_batches)
        before.extend(ids)
    consumed_global = state["stages"]["shard"]["global_position"]
    remaining = n - consumed_global
    after = []
    for r in range(to_deg):
        ids, _ = run(r, to_deg, state, remaining // (to_deg * bs))
        after.extend(ids)
    union = before + after
    return {
        "from_degree": from_deg, "to_degree": to_deg,
        "checked_samples": len(union),
        "lost": len(set(range(n)) - set(union)),
        "duplicated": len(union) - len(set(union)),
    }


def _goodput_drill(paddle, D):
    """data_slow injection must move the starvation counter and the
    input-bound gauge — proves the goodput layer measures, not decorates."""
    import paddle_tpu.utils.flags as flags
    flags.set_flags(
        {"FLAGS_fault_inject": "data_slow:delay_s=0.002"})
    try:
        pipe = (D.pipeline(_HeavyDS(reps=1, n=BATCH * 6))
                .shard(0, 1).batch(BATCH).device_prefetch(2))
        for b in pipe:
            time.sleep(0.0002)  # consumer far faster than producer
        snap = pipe.goodput.snapshot()
        return {"starved_steps": snap["starved_steps"],
                "input_bound": snap["input_bound"],
                "batches": snap["batches"]}
    finally:
        flags.set_flags({"FLAGS_fault_inject": ""})


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (fewer steps)")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "DATA_PIPELINE_BENCH.json"))
    args = ap.parse_args()
    _env()
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu import data as D
    import paddle_tpu.utils.flags as flags

    n_steps = 40 if args.smoke else 160
    cores = os.cpu_count() or 1
    out = {"metric": "data_pipeline_goodput", "smoke": bool(args.smoke),
           "batch": BATCH, "features": FEATURES, "host_cores": cores,
           "parallel_host": cores >= 2}

    # throughput lane runs eager: the overlap win must come from the
    # pipeline, not from the compiled step hiding host time
    flags.set_flags({"FLAGS_compiled_train_step": 0})
    reps, step_ms = _calibrate_reps(paddle, nn)
    out["calibration"] = {"work_reps": reps,
                          "eager_step_ms": round(step_ms, 3)}

    def sync_loader():
        from paddle_tpu.io import DataLoader
        return DataLoader(_HeavyDS(reps), batch_size=BATCH,
                          shuffle=False, num_workers=0, drop_last=True)

    def prefetch_loader():
        return (D.pipeline(_HeavyDS(reps)).shard(0, 1)
                .batch(BATCH).device_prefetch(2))

    sync_sps = _steps_per_sec(paddle, nn, sync_loader, n_steps)
    pf_sps = _steps_per_sec(paddle, nn, prefetch_loader, n_steps)
    out["throughput"] = {
        "n_steps": n_steps,
        "sync_steps_per_sec": round(sync_sps, 2),
        "prefetch_steps_per_sec": round(pf_sps, 2),
        "speedup": round(pf_sps / max(sync_sps, 1e-9), 3),
    }

    out["resume"] = _resume_drill(paddle, nn, D, compiled=False)
    out["resume_compiled"] = _resume_drill(paddle, nn, D, compiled=True)
    out["resize"] = _resize_drill(D)
    out["goodput_drill"] = _goodput_drill(paddle, D)

    with open(args.out, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps(out, sort_keys=True))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
