"""Gate for hot-spare recovery (framework/hot_spare.py, ISSUE 20).

Three questions, one JSON (benchmarks/RECOVERY_BENCH.json):

* **recovery latency** — the SAME injected failure (hard crash after
  ``CRASH_STEP`` completed steps) recovered two ways.  The peer lane
  pulls the last per-step snapshot from the buddy's RAM over the real
  rpc ``Blob`` path (crc + finiteness validation included) and resumes
  at the crash step — nothing to replay.  The disk lane restores the
  newest ``ckpt-N`` (saved every ``DISK_EVERY`` steps, the cadence disk
  can afford) and must re-train the steps since.  Recovery = restore +
  replay-to-crash-point; that replay term is the dominant MTTR cost the
  hot-spare layer exists to delete.  CI floor: peer ≤ 0.5x disk, and
  peer loses strictly fewer steps.
* **snapshot overhead** — steady-state guarded step p50 (agent armed,
  snapshot every ``SNAP_EVERY`` steps streamed to a live buddy
  receiver) vs the unguarded step p50 at equal model/batch.
  CI ceiling: ≤ 1.05x.
* honesty fields — state size, step times, raw restore times, so a
  regression is attributable instead of a bare ratio moving.

``FLAGS_hot_spare=0`` bitwise identity is gated in
tests/test_hot_spare.py (flag-off fit trajectory), not re-measured here.

Writes RECOVERY_BENCH.json (or --out) and prints one JSON line;
tools/check_bench_result.py::check_recovery_bench gates it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)       # `python benchmarks/recovery_bench.py`

HID = 512
BATCH = 16
BATCH_OVR = 2048     # overhead lane: compute-bound step (same net/state),
                     # so snapshot-bytes per step-ms sits near a real
                     # accelerator step instead of a toy 12ms CPU step
CRASH_STEP = 16      # crash at the worst point of the disk interval:
DISK_EVERY = 8       # ckpts at 0,8 → steps 9..15 exist only in RAM
SNAP_EVERY = 8       # overhead lane uses the FLAGS_hot_spare_every default


def _env():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _build(paddle, nn):
    paddle.seed(11)
    net = nn.Sequential(nn.Linear(HID, HID), nn.Tanh(),
                        nn.Linear(HID, HID), nn.Tanh(),
                        nn.Linear(HID, HID))
    opt = paddle.optimizer.AdamW(1e-3, parameters=net.parameters())
    return net, opt


def _batch(step, batch=BATCH):
    rng = np.random.default_rng(2000 + step)
    x = rng.standard_normal((batch, HID)).astype("float32")
    y = rng.standard_normal((batch, HID)).astype("float32")
    return x, y


def _train_step(paddle, net, opt, step, batch=BATCH):
    x, y = _batch(step, batch)
    loss = ((net(paddle.to_tensor(x)) - paddle.to_tensor(y)) ** 2).mean()
    loss.backward()
    opt.step()
    opt.clear_grad()
    return float(loss.numpy())


def _host_state(net, opt, step):
    return {"model": {k: np.asarray(v._data_) for k, v in
                      net.state_dict().items()},
            "optimizer": opt.state_dict(), "step": int(step)}


def _state_bytes(state):
    from paddle_tpu.framework.hot_spare import pack_state
    return len(pack_state(state))


def _p50(xs):
    return float(np.percentile(np.asarray(xs, dtype=np.float64), 50))


def _overhead_lane(paddle, nn, hot_spare, store, n_steps):
    """Guarded vs unguarded steady-state step p50 at equal model."""
    def run(agent):
        net, opt = _build(paddle, nn)
        times = []
        for step in range(n_steps + 4):
            t0 = time.perf_counter()
            _train_step(paddle, net, opt, step, batch=BATCH_OVR)
            if agent is not None:
                agent.maybe_snapshot(
                    step, lambda: _host_state(net, opt, step),
                    {"it": step + 1, "epoch": 0, "next_step": step + 1})
            dt = (time.perf_counter() - t0) * 1e3
            if step >= 4:                    # drop compile/warmup steps
                times.append(dt)
        if agent is not None:
            agent.wait()
        return times

    unguarded = run(None)
    hot_spare.advertise_buddy_map(store, "rbench", 2)
    receiver = hot_spare.HotSpareAgent("rbench", 1, 2, store=store,
                                       every=SNAP_EVERY)
    sender = hot_spare.HotSpareAgent("rbench", 0, 2, store=store,
                                     every=SNAP_EVERY)
    try:
        guarded = run(sender)
    finally:
        sender.close(park=False)
        receiver.close(park=False)
        hot_spare._STORES.pop("rbench", None)
    return _p50(unguarded), _p50(guarded)


def _failure_lanes(paddle, nn, hot_spare, store, outdir):
    """One crash, two recoveries: buddy RAM vs newest disk ckpt-N."""
    from paddle_tpu.framework.checkpoint_manager import CheckpointManager
    hot_spare.advertise_buddy_map(store, "rfail", 2)
    receiver = hot_spare.HotSpareAgent("rfail", 1, 2, store=store)
    sender = hot_spare.HotSpareAgent("rfail", 0, 2, store=store)
    mgr = CheckpointManager(os.path.join(outdir, "ckpts"), max_to_keep=3)

    net, opt = _build(paddle, nn)
    try:
        for step in range(CRASH_STEP):
            _train_step(paddle, net, opt, step)
            state = _host_state(net, opt, step)
            # per-step peer snapshot (the hot-spare cadence RAM affords)
            sender.snapshot_now(step, state,
                                {"it": step + 1, "epoch": 0,
                                 "next_step": step + 1})
            if step % DISK_EVERY == 0:       # the cadence disk affords
                mgr.save(state, step=step)
        pre_crash = _host_state(net, opt, CRASH_STEP - 1)
        state_bytes = _state_bytes(pre_crash)

        # ---- crash: the training process is gone ----
        del net, opt

        # peer lane: live rpc fetch from the buddy + validate + rebuild
        from paddle_tpu.distributed.rpc.rpc import rpc_sync
        import pickle
        t0 = time.perf_counter()
        raw = rpc_sync(hot_spare.worker_name("rfail", 1),
                       hot_spare._rpc_fetch, ("rfail", 0), timeout=10)
        rec = pickle.loads(bytes(raw))
        peer_state, peer_book = hot_spare.validated_state(rec)
        net_p, opt_p = _build(paddle, nn)
        net_p.set_state_dict(peer_state["model"])
        opt_p.set_state_dict(peer_state["optimizer"])
        peer_restore_ms = (time.perf_counter() - t0) * 1e3
        peer_resume_at = int(peer_state["step"]) + 1
        assert peer_resume_at == CRASH_STEP, peer_resume_at
        for k, v in pre_crash["model"].items():   # lossless replica
            np.testing.assert_array_equal(peer_state["model"][k], v, k)

        # disk lane: newest valid ckpt-N + replay the steps since
        t0 = time.perf_counter()
        disk_state, disk_step = mgr.restore_latest()
        net_d, opt_d = _build(paddle, nn)
        net_d.set_state_dict(disk_state["model"])
        opt_d.set_state_dict(disk_state["optimizer"])
        disk_restore_ms = (time.perf_counter() - t0) * 1e3
        disk_resume_at = int(disk_state["step"]) + 1
        t0 = time.perf_counter()
        for step in range(disk_resume_at, CRASH_STEP):
            _train_step(paddle, net_d, opt_d, step)
        disk_replay_ms = (time.perf_counter() - t0) * 1e3
    finally:
        sender.close(park=False)
        receiver.close(park=False)
        hot_spare._STORES.pop("rfail", None)

    return {
        "crash_step": CRASH_STEP,
        "state_bytes": int(state_bytes),
        "peer_restore_ms": round(peer_restore_ms, 3),
        "peer_steps_lost": CRASH_STEP - peer_resume_at,
        "peer_recovery_ms": round(peer_restore_ms, 3),
        "disk_restore_ms": round(disk_restore_ms, 3),
        "disk_steps_lost": CRASH_STEP - disk_resume_at,
        "disk_replay_ms": round(disk_replay_ms, 3),
        "disk_recovery_ms": round(disk_restore_ms + disk_replay_ms, 3),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (fewer overhead steps)")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "RECOVERY_BENCH.json"))
    args = ap.parse_args()
    _env()
    import tempfile

    import jax
    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed.store import FileKVStore
    from paddle_tpu.framework import hot_spare

    hot_spare.declare_metrics()
    workdir = tempfile.mkdtemp(prefix="recovery_bench_")
    store = FileKVStore(os.path.join(workdir, "kv"))

    n_overhead = 16 if args.smoke else 48
    fail = _failure_lanes(paddle, nn, hot_spare, store, workdir)
    un_p50, gu_p50 = _overhead_lane(paddle, nn, hot_spare, store,
                                    n_overhead)

    cores = os.cpu_count() or 1
    out = {
        "metric": "recovery_ladder",
        "value": fail["peer_recovery_ms"],
        "smoke": bool(args.smoke),
        "platform": jax.devices()[0].platform,
        # the 1.05x overhead gate needs the stream thread to overlap the
        # step — only measurable on a parallel host (data-bench convention)
        "parallel_host": cores >= 2,
        "host_cores": cores,
        "unguarded_step_ms_p50": round(un_p50, 3),
        "guarded_step_ms_p50": round(gu_p50, 3),
        "snapshot_overhead_ratio": round(gu_p50 / max(un_p50, 1e-9), 4),
        "snap_every": SNAP_EVERY,
        "disk_every": DISK_EVERY,
        "latency_ratio": round(
            fail["peer_recovery_ms"] / max(fail["disk_recovery_ms"],
                                           1e-9), 4),
    }
    out.update(fail)

    with open(args.out, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps(out, sort_keys=True))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
