"""Runtime flag system (reference: paddle/phi/core/flags.cc — ~100
PHI_DEFINE_EXPORTED_* flags surfaced via paddle.set_flags).  TPU-native: a
typed registry seeded from environment variables; every flag here has a
reader (docs/KNOBS.md)."""
from __future__ import annotations

import os
from typing import Any


_FLAGS: dict[str, Any] = {
    "FLAGS_check_nan_inf": False,
    "FLAGS_check_nan_inf_level": 0,
    # donate mutated captures (params/opt state) in compiled train steps so
    # XLA updates them in place; disable if user code holds raw jax arrays
    # of parameters across steps, or Tensors that SHARE a parameter's
    # buffer across steps (e.g. a detach()'d view taken before the step) —
    # after donation such holds read a deleted buffer.  Captures aliasing
    # each other within one step are detected and skip donation.
    "FLAGS_jit_donate_buffers": True,
    # tiered executable cache (core/op_cache.py).  Tier 1: jitted eager
    # op dispatch — repeated same-signature eager op calls replay one
    # cached XLA program instead of re-trace/re-dispatch; the LRU is
    # bounded by FLAGS_eager_op_cache_size entries.  Tier 2 (JAX's
    # persistent compilation cache) is always on and has no flag: the
    # JAX_COMPILATION_CACHE_DIR environment variable places it, else it
    # lives in <checkout>/.jax_cache (docs/CACHING.md).
    # hybrid dp×mp compiled train step (framework/train_step.py,
    # docs/TRAIN_STEP.md): a ProcessMesh with an mp axis > 1 compiles
    # the step as ONE GSPMD program over NamedSharding trees derived
    # from the model's declared partition.  Off: mp meshes run the
    # byte-identical eager lane (the pre-ISSUE-12 behavior); pure-dp
    # meshes and single-device steps are unaffected either way.
    "FLAGS_compiled_mp_step": True,
    # compiled serving scheduler tick (serving/compiled_tick.py,
    # docs/SERVING.md): the paged engine's decode iteration — batched
    # decode + vectorized per-slot sampling chain + offset/eos/length
    # bookkeeping — runs as ONE donated-buffer jit program over
    # device-resident scheduler state, with admission/completion as the
    # only host boundary.  Off: the scheduler is byte-identical to the
    # pre-tick engine (per-call dispatch, host sampling).
    "FLAGS_compiled_tick": True,
    # fused per-iteration sampling on the UNCOMPILED serving lane: when
    # every active slot is greedy or seeded, one jitted call samples
    # all slots instead of a host round-trip per non-greedy slot.  Also
    # routes seeded requests' per-row draws through the same key-derived
    # stream the compiled tick uses (lane-independent tokens).  Off:
    # the historical per-row global-RNG path, byte-for-byte.
    "FLAGS_serving_fused_sampling": True,
    "FLAGS_eager_op_cache": True,
    "FLAGS_eager_op_cache_size": 4096,
    # fault-injection spec for robustness drills (utils/fault_injection.py;
    # grammar in docs/FAULT_TOLERANCE.md).  Empty = disabled: the save and
    # step paths then pay a single falsy check, nothing more.
    "FLAGS_fault_inject": "",
    # unified telemetry (paddle_tpu.observability, docs/OBSERVABILITY.md).
    # A non-empty export path arms the background MetricsExporter thread:
    # periodic JSON snapshots of the metrics registry are APPENDED there
    # (one object per line) for dashboards.  Empty = no thread, no I/O.
    "FLAGS_metrics_export_path": "",
    "FLAGS_metrics_export_interval_s": 10.0,
    # peak device FLOP/s for MFU accounting (StepMetrics).  0 = derive
    # from the device generation (profiler/timer.py device_peak_flops).
    "FLAGS_peak_flops": 0.0,
    # flight recorder ring-buffer capacity (events kept for the crash /
    # preemption dump).  0 disables recording AND the dump hooks.
    "FLAGS_flight_recorder_size": 512,
    # where the flight recorder dumps on crash/SIGTERM; empty = a
    # flight_recorder.<pid>.json file under FLAGS_dump_dir.
    "FLAGS_flight_recorder_path": "",
    # default directory (relative to the working dir) for crash/stall
    # dumps whose *_path flag is unset — keeps post-mortem litter out of
    # the repo/cwd root and under one ignorable prefix.
    "FLAGS_dump_dir": ".paddle_tpu_dumps",
    # elastic resharding (distributed/reshard.py): allow fit(resume=...)
    # to reshard a checkpoint whose saved mesh layout differs from the
    # resumed topology (world-size change).  False = any layout change
    # fails loudly with LayoutMismatchError naming both layouts.
    "FLAGS_reshard_on_resume": True,
    # hang guardian (distributed/watchdog.py, docs/RESILIENCE.md).
    # A collective stuck longer than this triggers a stall dump and a
    # CollectiveTimeoutError naming the op, per-group sequence number,
    # and the ranks that never arrived.  0 (default) disables the
    # watchdog entirely — the collective path pays a few dict lookups.
    "FLAGS_collective_timeout_s": 0.0,
    # stall-dump destination (all-thread stacks + last-N collectives +
    # metrics snapshot).  Empty = stall_dump.<pid>.json in the working
    # directory; multi-rank jobs insert ".rank<R>" before the extension.
    "FLAGS_stall_dump_path": "",
    # after the stall dump + async abort, a thread still wedged outside
    # the interpreter (a real cross-process transfer) is hard-exited so
    # the controller can reap the rank.  Tests set this False to keep a
    # deliberately-stalled pytest process alive.
    "FLAGS_collective_hard_abort": True,
    # eager collective backend (distributed/collective.py): "auto" runs
    # the XLA cross-process program and falls back to host-mediated
    # collectives (host_collectives.py, the ProcessGroupGloo analog)
    # when the backend cannot execute multiprocess programs; "xla" and
    # "host" pin a lane.
    "FLAGS_collective_backend": "auto",
    # compiled train step (framework/train_step.py, docs/TRAIN_STEP.md):
    # hapi Model.fit and the train benches execute the WHOLE training
    # step — forward, backward, grad clip/scale, AMP found-inf check,
    # optimizer update — as one donated-buffer jax.jit program (with dp
    # gradient reduction as in-program psum under shard_map when a dp
    # mesh spans >1 local device) instead of op-by-op eager dispatch.
    # Eager stays the fallback: hooks, tracers, custom train_batch
    # overrides, launched multi-process worlds without a global jax
    # mesh, or this flag off all run the byte-identical eager path.
    "FLAGS_compiled_train_step": True,
    # Pallas fused multi-LoRA decode delta (serving/adapters.py,
    # docs/SERVING.md): the per-slot adapter gather-matmul
    # y += gather(B, idx) @ (gather(A, idx) @ x) * scale runs as one
    # scalar-prefetch Pallas kernel on TPU instead of the XLA gather
    # lane.  Off (default): the XLA gather path, which is the
    # bit-equality reference.  Set before the engine starts.
    "FLAGS_pallas_lora": False,
    # Pallas fused-optimizer kernels (pallas/fused.py): run the AdamW/
    # Adam elementwise update as a row-blocked Pallas kernel on TPU
    # (exact — same fp32 arithmetic as the XLA lane, verified bitwise in
    # interpreter mode).  Off, or on shapes/backends the kernel does not
    # support, the jnp update runs unchanged.
    "FLAGS_pallas_fused_optimizer": True,
    # desync detector sampling: every N-th collective per group reads
    # peers' arrival records from the guardian store and raises
    # DesyncError on an op mismatch at the same sequence number.
    # 0 disables the proactive check (arrival records are still written
    # whenever a guardian store is configured — stall blame needs them).
    "FLAGS_desync_check_every": 16,
    # training sentinel (framework/sentinel.py, docs/RESILIENCE.md):
    # anomaly detection (non-finite loss/grads, loss-spike z-score,
    # grad-norm explosion vs EMA), poisoned-step skip via the AMP
    # found-inf machinery, last-known-good anchor rollback with the
    # offending batch window quarantined on replay, and per-rank blame
    # over the guardian store.  Off (default): training is bitwise
    # identical to the sentinel never existing.
    "FLAGS_sentinel": False,
    # rolling window of accepted losses the spike z-score is computed
    # against; also bounds how many device-held health records are
    # fetched per host sync.
    "FLAGS_sentinel_window": 32,
    # a finite loss more than this many stds above the rolling-window
    # mean is an anomaly (the window must be at least 1/4 full first).
    "FLAGS_sentinel_spike_zscore": 6.0,
    # health records (device loss/grad-norm/skip-flag) are fetched and
    # evaluated every N update steps — ONE batched device->host sync per
    # N steps, so the compiled hot path stays sync-free between checks.
    "FLAGS_sentinel_check_every": 8,
    # consecutive in-program skipped (non-finite) steps tolerated before
    # the sentinel escalates to a rollback.
    "FLAGS_sentinel_max_skips": 3,
    # weight-poisoning anomalies (finite spikes / grad explosions that
    # were APPLIED before detection) tolerated before rollback.  1 =
    # any applied anomaly rolls back to the last-known-good anchor.
    "FLAGS_sentinel_rollback_after": 1,
    # minimum update steps between last-known-good anchor saves (anchors
    # are only taken after a fully-healthy check window).
    "FLAGS_sentinel_anchor_every": 32,
    # a finite grad norm more than this multiple of its EMA is a
    # grad-explosion anomaly.  0 disables the grad-norm signal.
    "FLAGS_sentinel_grad_factor": 100.0,
    # rollbacks attempted before the sentinel declares the anomaly
    # persistent: multi-rank jobs publish blame and abort into the
    # controller's quarantine-relaunch path, single-rank jobs disable
    # the sentinel with a loud warning rather than loop forever.
    "FLAGS_sentinel_max_rollbacks": 3,
    # sentinel dump destination (reason "sentinel": signals, escalation
    # action, per-rank health, blamed rank).  Empty = a
    # sentinel_dump.<pid>.json under FLAGS_dump_dir; multi-rank jobs
    # insert .rank<R> before the extension, like stall dumps.
    "FLAGS_sentinel_dump_path": "",
    # distributed request tracing (observability/tracing.py,
    # docs/OBSERVABILITY.md).  A non-empty directory arms per-request
    # TraceContext minting and span recording across router/engine/
    # migration hops; each process spools its spans there as atomic
    # JSONL for the fleet collector to merge.  Empty (default) = no
    # context objects, no spans, no I/O — every hot-path seam pays one
    # falsy flag check / None compare and the serving output is
    # byte-identical to tracing never existing.
    "FLAGS_trace_dir": "",
    # tail-sampling probabilistic floor: fraction of OK-and-fast traces
    # kept anyway (decided by a deterministic hash of the trace id, so
    # reruns keep the same traces).  Errors, deadline evictions and
    # traces slower than FLAGS_trace_latency_threshold_ms are ALWAYS
    # kept regardless of this rate.
    "FLAGS_trace_sample_rate": 0.05,
    # root-request latency above which a trace is always kept (the tail
    # the p99 attribution exists for).  0 keeps every trace.
    "FLAGS_trace_latency_threshold_ms": 250.0,
    # per-process span ring capacity: completed spans beyond this are
    # dropped oldest-first (and counted) rather than growing without
    # bound on a replica the collector never visits.
    "FLAGS_trace_buffer_cap": 4096,
    # serving/stats.py request_observe label-cardinality cap: at most
    # this many request_id-labeled children are kept per metric family
    # (LRU rotation — the oldest request's child is dropped when a new
    # request would exceed the cap), so a long-lived engine's registry
    # converges instead of growing per request.
    "FLAGS_serving_request_label_cap": 1024,
    # hot-spare recovery (framework/hot_spare.py, docs/FAULT_TOLERANCE.md
    # "Recovery ladder"): each rank periodically snapshots its shard
    # state into host RAM and streams it — chunked, crc32-per-chunk,
    # double-buffered — to its ring-buddy rank's RAM over the rpc Blob
    # fast path, so a relaunched incarnation restores from a peer's
    # memory in seconds instead of re-reading disk.  Off (default):
    # training and resume are byte-identical to the module never
    # existing (disk restore_latest stays the only rung).
    "FLAGS_hot_spare": False,
    # update steps between peer snapshots.  Lower = fewer steps lost on
    # a crash, more host-RAM churn and rpc bytes.
    "FLAGS_hot_spare_every": 8,
    # snapshot stream chunk size (KiB): each chunk carries its own
    # crc32 and rides the rpc Blob raw path; the buddy only flips its
    # valid copy at a fully-verified commit.
    "FLAGS_hot_spare_chunk_kb": 1024,
    # per-rpc timeout for snapshot streaming and peer-restore pulls; a
    # buddy slower than this skips the cadence (stream) or fails the
    # ladder rung loudly (restore) rather than wedging the step loop.
    "FLAGS_hot_spare_timeout_s": 10.0,
}


def _coerce(old, new):
    if isinstance(old, bool):
        if isinstance(new, str):
            return new.lower() in ("1", "true", "yes")
        return bool(new)
    if isinstance(old, int) and not isinstance(old, bool):
        return int(new)
    if isinstance(old, float):
        return float(new)
    return new


# environment overrides at import
for _k in list(_FLAGS):
    if _k in os.environ:
        _FLAGS[_k] = _coerce(_FLAGS[_k], os.environ[_k])


def set_flags(flags: dict):
    for k, v in flags.items():
        if k in _FLAGS:
            _FLAGS[k] = _coerce(_FLAGS[k], v)
        else:
            _FLAGS[k] = v


def get_flags(keys=None):
    if keys is None:
        return dict(_FLAGS)
    if isinstance(keys, str):
        return {keys: _FLAGS.get(keys)}
    return {k: _FLAGS.get(k) for k in keys}


def flag(name, default=None):
    return _FLAGS.get(name, default)
