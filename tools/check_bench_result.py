#!/usr/bin/env python
"""Benchmark regression gate.

Reference capability: tools/check_op_benchmark_result.py — CI compares a
run's numbers against recorded baselines and fails on regressions beyond
a threshold.

Usage: python tools/check_bench_result.py BENCH_rN.json [--threshold 0.9]
Compares `value` against the recorded per-platform best in
BENCH_BASELINE.json (written by bench.py).

An `eager_op_dispatch_*` result (benchmarks/eager_overhead.py) is
validated against its JSON schema instead of the throughput baseline —
the microbench's comparison is self-contained (cached vs uncached in
one process).  A `serving_*` result (benchmarks/serving_bench.py) is
likewise schema-validated, plus a floor on its self-contained
continuous-batching speedup vs the sequential baseline.  A
`serving_paged_*` result (--workload prefix) gates the paged KV
cache: >= 2x tokens/sec vs the slot engine at equal cache memory,
prefix-cache hits on every shared-prompt request, and strictly more
concurrent sequences than preallocation would have allowed."""
from __future__ import annotations

import argparse
import json
import os
import sys


_EAGER_SCHEMA = {
    # key -> accepted types; every key is required
    "metric": str,
    "value": (int, float),
    "unit": str,
    "speedup_vs_uncached": (int, float),
    "step_speedup_vs_uncached": (int, float),
    "cached": dict,
    "uncached": dict,
    "loss": (int, float),
    "iters": int,
    "ops_per_fwd": int,
    "smoke": bool,
    "platform": str,
    "tier1": dict,
}
_EAGER_TIER1_KEYS = ("hits", "misses", "evictions", "bypasses",
                     "entries", "bytes")


def check_eager_overhead(run):
    """Schema gate for benchmarks/eager_overhead.py output."""
    errors = []
    for key, types in _EAGER_SCHEMA.items():
        if key not in run:
            errors.append(f"missing key {key!r}")
        elif not isinstance(run[key], types):
            errors.append(f"{key!r} has type {type(run[key]).__name__}, "
                          f"expected {types}")
    if not errors:
        for side in ("cached", "uncached"):
            for k in ("fwd_ops_per_sec", "step_ops_per_sec"):
                v = run[side].get(k)
                if not isinstance(v, (int, float)) or v <= 0:
                    errors.append(f"{side}.{k} must be a positive number, "
                                  f"got {v!r}")
        for k in _EAGER_TIER1_KEYS:
            if not isinstance(run["tier1"].get(k), int):
                errors.append(f"tier1.{k} missing or not an int")
        if not errors:
            if run["value"] <= 0:
                errors.append("value must be positive")
            if run["speedup_vs_uncached"] <= 0:
                errors.append("speedup_vs_uncached must be positive")
            if run["tier1"]["hits"] <= 0:
                errors.append("tier1.hits is zero — the cached pass "
                              "never hit its own cache")
        # sentinel healthy-path gate (ISSUE 10): detection on top of
        # the guarded eager step must cost <= 2% (older recorded
        # baselines predate the section, so it is optional there)
        sen = run.get("sentinel")
        if isinstance(sen, dict):
            ratio = sen.get("overhead_vs_guarded")
            if not isinstance(ratio, (int, float)) or ratio <= 0:
                errors.append("sentinel.overhead_vs_guarded missing or "
                              f"not positive: {ratio!r}")
            elif ratio > _SENTINEL_MAX_OVERHEAD:
                errors.append(
                    f"sentinel eager overhead {ratio:.3f}x > "
                    f"{_SENTINEL_MAX_OVERHEAD}x vs the guarded step")
            if sen.get("anomalies"):
                errors.append("sentinel flagged anomalies on the "
                              "healthy bench workload")
    if errors:
        print("eager_overhead schema check FAILED:")
        for e in errors:
            print(f"  - {e}")
        return 1
    print(f"eager_overhead schema OK: {run['value']:.1f} ops/sec, "
          f"{run['speedup_vs_uncached']:.2f}x vs uncached, "
          f"tier1 hits={run['tier1']['hits']}")
    return 0


_TRAIN_STEP_SCHEMA = {
    # key -> accepted types; every key is required
    "metric": str,
    "value": (int, float),
    "unit": str,
    "speedup_vs_eager": (int, float),
    "eager": dict,
    "compiled": dict,
    "losses_allclose": bool,
    "losses_max_reldiff": (int, float),
    "losses_bitwise_equal": bool,
    "compiled_lane_active": bool,
    "steps": int,
    "batch": int,
    "seq": int,
    "smoke": bool,
    "platform": str,
}

# acceptance floors (ISSUE 8): the one-program donated-buffer train step
# must beat op-by-op eager dispatch by >= 1.5x step-time p50 on the CPU
# smoke config (dispatch-bound; clears ~4x).  The full CPU config is
# dominated by real matmul time — the one-program win there is bounded
# by Amdahl at ~1.4x on a quiet box — so it carries a softer 1.15x
# regression floor rather than the headline gate.
_TRAIN_STEP_MIN_SPEEDUP_SMOKE = 1.5
_TRAIN_STEP_MIN_SPEEDUP_FULL = 1.15

# sentinel healthy-path ceiling (ISSUE 10): the sentinel's detection
# signals (device health vector, cond-sampled grad norm) on top of the
# guarded (found-inf-armed) step, measured interleaved so box drift
# cancels.  The skip machinery itself is the PRE-EXISTING AMP select
# path and is recorded informationally, not gated here.
_SENTINEL_MAX_OVERHEAD = 1.02


def check_train_step_bench(run):
    """Schema + speedup/equality gate for benchmarks/train_step_bench.py."""
    errors = []
    for key, types in _TRAIN_STEP_SCHEMA.items():
        if key not in run:
            errors.append(f"missing key {key!r}")
        elif run[key] is None or not isinstance(run[key], types):
            errors.append(f"{key!r} has type {type(run[key]).__name__}, "
                          f"expected {types}")
    if not errors:
        for side in ("eager", "compiled"):
            for k in ("p50_ms", "p99_ms", "mean_ms", "steps"):
                v = run[side].get(k)
                if not isinstance(v, (int, float)) or v <= 0:
                    errors.append(f"{side}.{k} must be a positive "
                                  f"number, got {v!r}")
        if run["value"] <= 0:
            errors.append("value must be positive")
        if not run["compiled_lane_active"]:
            errors.append("compiled lane fell back to eager — the gate "
                          "measured eager twice")
        floor = (_TRAIN_STEP_MIN_SPEEDUP_SMOKE if run["smoke"]
                 else _TRAIN_STEP_MIN_SPEEDUP_FULL)
        if run["speedup_vs_eager"] < floor:
            errors.append(
                f"speedup_vs_eager {run['speedup_vs_eager']:.2f} < "
                f"required {floor}x")
        if run["platform"] == "cpu" and not run["losses_allclose"]:
            errors.append(
                "compiled fp32 loss trajectory diverged from eager on "
                f"CPU beyond ulp tolerance (max rel diff "
                f"{run.get('losses_max_reldiff')})")
        sen = run.get("sentinel")
        if not isinstance(sen, dict):
            errors.append("missing 'sentinel' overhead section")
        else:
            ratio = sen.get("overhead_vs_guarded")
            if not isinstance(ratio, (int, float)) or ratio <= 0:
                errors.append("sentinel.overhead_vs_guarded missing or "
                              f"not positive: {ratio!r}")
            elif ratio > _SENTINEL_MAX_OVERHEAD:
                errors.append(
                    f"sentinel compiled overhead {ratio:.3f}x > "
                    f"{_SENTINEL_MAX_OVERHEAD}x vs the guarded step")
            if not sen.get("pair_compiled"):
                errors.append("sentinel overhead pair fell back to "
                              "eager — the gate measured nothing")
    if errors:
        print("train_step_bench schema check FAILED:")
        for e in errors:
            print(f"  - {e}")
        return 1
    tag = ("bit-equal" if run["losses_bitwise_equal"]
           else f"ulp-close (max rel {run['losses_max_reldiff']:.1e})")
    print(f"train_step_bench schema OK: p50 {run['value']:.1f}ms "
          f"compiled vs {run['eager']['p50_ms']:.1f}ms eager "
          f"({run['speedup_vs_eager']:.2f}x), trajectories {tag}")
    return 0


_MFU_SWEEP_SCHEMA = {
    # key -> accepted types; every key is required
    "metric": str,
    "value": (int, float),
    "unit": str,
    "world_size": int,
    "model": dict,
    "layouts": dict,
    "speedup_hybrid_vs_dp": (int, float),
    "planner": dict,
    "steps": int,
    "batch": int,
    "seq": int,
    "smoke": bool,
    "platform": str,
}
_MFU_LAYOUT_KEYS = ("dp", "mp", "p50_ms", "tokens_per_sec", "compiled",
                    "projected_ms", "projected_err", "anchor")

# acceptance floors (ISSUE 12): at equal world size the hybrid
# dp×mp compiled step must beat the dp-only compiled step by >= 1.3x
# step-time p50 on the parameter-heavy sweep config (pure dp moves the
# full model per step in its grad all-reduce and replicates the
# optimizer update; smoke clears ~3.5x), the planner's pick must match
# or beat every hand-written layout on the grid (<= 5% of the measured
# best), and the calibrated projection must land within 25% of the
# measured step time on held-out layouts.
_MFU_MIN_HYBRID_SPEEDUP = 1.3
_MFU_MAX_PICK_VS_BEST = 1.05
_MFU_MAX_PROJECTED_ERR = 0.25


def check_mfu_sweep(run):
    """Schema + hybrid-speedup/planner gates for
    benchmarks/mfu_sweep.py (layout sweep, MFU_SWEEP.json)."""
    errors = []
    for key, types in _MFU_SWEEP_SCHEMA.items():
        if key not in run:
            errors.append(f"missing key {key!r}")
        elif run[key] is None or not isinstance(run[key], types):
            errors.append(f"{key!r} has type {type(run[key]).__name__}, "
                          f"expected {types}")
    if not errors:
        if len(run["layouts"]) < 2:
            errors.append("fewer than 2 layouts measured — nothing to "
                          "compare")
        for name, lay in run["layouts"].items():
            for k in _MFU_LAYOUT_KEYS:
                if k not in lay:
                    errors.append(f"layouts.{name} missing {k!r}")
            if not lay.get("compiled"):
                errors.append(f"layouts.{name} fell back to eager "
                              f"({lay.get('fallback_reason')}) — the "
                              "sweep measured the wrong lane")
        losses = {round(lay.get("loss", 0), 4)
                  for lay in run["layouts"].values()}
        if len(losses) != 1:
            errors.append(f"per-layout losses diverged: {sorted(losses)}"
                          " — layouts did not compute the same step")
        if run["speedup_hybrid_vs_dp"] < _MFU_MIN_HYBRID_SPEEDUP:
            errors.append(
                f"speedup_hybrid_vs_dp {run['speedup_hybrid_vs_dp']:.2f}"
                f" < required {_MFU_MIN_HYBRID_SPEEDUP}x at equal world "
                "size")
        planner = run["planner"]
        if not planner.get("pick_measured"):
            errors.append("planner pick was not on the measured grid")
        ratio = planner.get("pick_vs_best")
        if not isinstance(ratio, (int, float)) or \
                ratio > _MFU_MAX_PICK_VS_BEST:
            errors.append(
                f"planner pick is {ratio!r}x the measured-best layout "
                f"(> {_MFU_MAX_PICK_VS_BEST}) — the planner lost to a "
                "hand-written layout")
        err = planner.get("max_projected_err")
        if not isinstance(err, (int, float)) or \
                err > _MFU_MAX_PROJECTED_ERR:
            errors.append(
                f"max projected-vs-measured error {err!r} > "
                f"{_MFU_MAX_PROJECTED_ERR} on held-out layouts")
    if errors:
        print("mfu_sweep schema check FAILED:")
        for e in errors:
            print(f"  - {e}")
        return 1
    print(f"mfu_sweep schema OK: best layout dp{run['planner']['pick']['dp']}"
          f"xmp{run['planner']['pick']['mp']} at {run['value']:.1f}ms, "
          f"{run['speedup_hybrid_vs_dp']:.2f}x vs dp-only, planner err "
          f"{run['planner']['max_projected_err']:.3f}")
    return 0


_SERVING_SCHEMA = {
    # key -> accepted types; every key is required
    "metric": str,
    "value": (int, float),
    "unit": str,
    "speedup_vs_sequential": (int, float),
    "sequential": dict,
    "serving": dict,
    "ttft_ms_avg": (int, float),
    "per_token_ms_avg": (int, float),
    "slot_occupancy": (int, float),
    "num_requests": int,
    "num_slots": int,
    "max_new_tokens": int,
    "greedy_mismatches": int,
    "smoke": bool,
    "platform": str,
}

# acceptance floor: continuous batching must sustain >= 2x the
# sequential per-request generate() throughput at >= 4 concurrent
# requests (ISSUE 3); CPU smoke runs clear ~3x, so 2.0 has margin
# without being noise-sensitive
_SERVING_MIN_SPEEDUP = 2.0


def check_serving_bench(run):
    """Schema + speedup gate for benchmarks/serving_bench.py output."""
    errors = []
    for key, types in _SERVING_SCHEMA.items():
        if key not in run:
            errors.append(f"missing key {key!r}")
        elif run[key] is None or not isinstance(run[key], types):
            errors.append(f"{key!r} has type {type(run[key]).__name__}, "
                          f"expected {types}")
    if not errors:
        for side in ("sequential", "serving"):
            for k in ("tokens_per_sec", "wall_s", "tokens"):
                v = run[side].get(k)
                if not isinstance(v, (int, float)) or v <= 0:
                    errors.append(f"{side}.{k} must be a positive "
                                  f"number, got {v!r}")
        if run["value"] <= 0:
            errors.append("value must be positive")
        if run["greedy_mismatches"] != 0:
            errors.append(f"{run['greedy_mismatches']} serving outputs "
                          "diverged from the sequential greedy baseline")
        if not 0.0 < run["slot_occupancy"] <= 1.0:
            errors.append(f"slot_occupancy {run['slot_occupancy']!r} "
                          "outside (0, 1]")
        if run["num_requests"] >= 4 and \
                run["speedup_vs_sequential"] < _SERVING_MIN_SPEEDUP:
            errors.append(
                f"speedup_vs_sequential {run['speedup_vs_sequential']:.2f}"
                f" < required {_SERVING_MIN_SPEEDUP}x at "
                f"{run['num_requests']} concurrent requests")
    if errors:
        print("serving_bench schema check FAILED:")
        for e in errors:
            print(f"  - {e}")
        return 1
    print(f"serving_bench schema OK: {run['value']:.1f} tokens/sec, "
          f"{run['speedup_vs_sequential']:.2f}x vs sequential, "
          f"occupancy {run['slot_occupancy']:.2f}, "
          f"ttft {run['ttft_ms_avg']:.0f}ms")
    return 0


_PAGED_SCHEMA = {
    # key -> accepted types; every key is required
    "metric": str,
    "value": (int, float),
    "unit": str,
    "speedup_vs_slots": (int, float),
    "slots": dict,
    "paged": dict,
    "prefix_cache_hits": int,
    "prefix_cache_hit_tokens": int,
    "max_concurrent": int,
    "prealloc_capacity": int,
    "pool_pages": int,
    "prefix_len": int,
    "num_requests": int,
    "max_new_tokens": int,
    "greedy_mismatches": int,
    "smoke": bool,
    "platform": str,
}

# acceptance floors (ISSUE 7): on the shared-prefix workload the paged
# engine must sustain >= 2x the slot engine's tokens/sec at EQUAL cache
# memory (smoke clears ~2.3x, full ~2.6x), and must have run strictly
# more concurrent sequences than the same bytes preallocated as
# max_seq_len stripes could
_PAGED_MIN_SPEEDUP = 2.0


def check_paged_bench(run):
    """Schema + speedup/occupancy gates for the shared-prefix lane of
    benchmarks/serving_bench.py (--workload prefix)."""
    errors = []
    for key, types in _PAGED_SCHEMA.items():
        if key not in run:
            errors.append(f"missing key {key!r}")
        elif run[key] is None or not isinstance(run[key], types):
            errors.append(f"{key!r} has type {type(run[key]).__name__}, "
                          f"expected {types}")
    if not errors:
        for side in ("slots", "paged"):
            for k in ("tokens_per_sec", "wall_s", "tokens",
                      "slot_occupancy", "ttft_ms_avg"):
                v = run[side].get(k)
                if not isinstance(v, (int, float)) or v <= 0:
                    errors.append(f"{side}.{k} must be a positive "
                                  f"number, got {v!r}")
        if run["value"] <= 0:
            errors.append("value must be positive")
        if run["greedy_mismatches"] != 0:
            errors.append(f"{run['greedy_mismatches']} paged outputs "
                          "diverged from the sequential greedy baseline")
        if run["num_requests"] >= 4 and \
                run["speedup_vs_slots"] < _PAGED_MIN_SPEEDUP:
            errors.append(
                f"speedup_vs_slots {run['speedup_vs_slots']:.2f} < "
                f"required {_PAGED_MIN_SPEEDUP}x at equal cache memory")
        if run["prefix_cache_hits"] < run["num_requests"]:
            errors.append(
                f"prefix_cache_hits {run['prefix_cache_hits']} < "
                f"{run['num_requests']} — the shared system prompt was "
                "recomputed instead of reused")
        if run["max_concurrent"] <= run["prealloc_capacity"]:
            errors.append(
                f"max_concurrent {run['max_concurrent']} <= "
                f"prealloc_capacity {run['prealloc_capacity']} — paging "
                "admitted no more sequences than slot preallocation")
    if errors:
        print("serving_paged schema check FAILED:")
        for e in errors:
            print(f"  - {e}")
        return 1
    print(f"serving_paged schema OK: {run['value']:.1f} tokens/sec, "
          f"{run['speedup_vs_slots']:.2f}x vs slot engine, "
          f"{run['prefix_cache_hits']} prefix hits, "
          f"{run['max_concurrent']} concurrent vs "
          f"{run['prealloc_capacity']} preallocated")
    return 0


_SPEC_SCHEMA = {
    # key -> accepted types; every key is required
    "metric": str,
    "value": (int, float),
    "unit": str,
    "speedups": dict,
    "speedup_min": (int, float),
    "speculation_k": int,
    "acceptance_rate": (int, float),
    "batches": dict,
    "int8_kv": dict,
    "max_new_tokens": int,
    "greedy_mismatches": int,
    "spec_draft_ms_avg": (int, float),
    "spec_verify_ms_avg": (int, float),
    "spec_rollback_ms_avg": (int, float),
    "smoke": bool,
    "platform": str,
}

# acceptance floors (ISSUE 11): the speculative lane must sustain >= 2x
# the plain paged engine's decode tokens/sec at every measured batch
# size 1..4 (smoke clears ~2.7x with K=8 and a 1-block draft against an
# 8-block target), keep greedy outputs bit-equal to sequential
# generate(), and accept most of what a perfectly-agreeing draft
# proposes (the lane's draft computes the target's function; a low rate
# means the accept machinery itself broke).  The int8-KV section must
# show the pages-in-use peak at equal token load at ~half the fp32
# pool's (quantized pages pack 2x the tokens in half the bytes).
_SPEC_MIN_SPEEDUP = 2.0
_SPEC_MIN_ACCEPTANCE = 0.8
_SPEC_MAX_INT8_PAGES_RATIO = 0.6


_TICK_SCHEMA = {
    # key -> accepted types; every key is required
    "metric": str,
    "value": (int, float),
    "unit": str,
    "speedup_vs_uncompiled": (int, float),
    "uncompiled": dict,
    "compiled": dict,
    "tick_compiled_hits": (int, float),
    "tick_fallbacks": (int, float),
    "slot_occupancy": (int, float),
    "num_slots": int,
    "num_requests": int,
    "max_new_tokens": int,
    "greedy_mismatches": int,
    "sampled_mismatches": int,
    "smoke": bool,
    "platform": str,
}
_TICK_MIN_SPEEDUP = 1.5


def check_tick_bench(run):
    """Schema + speedup/bit-equality gates for the high-occupancy
    compiled-tick lane of benchmarks/serving_bench.py (--workload
    occupancy, ISSUE 13): at 8+ slots of short decodes the ONE-program
    tick must deliver >= 1.5x tokens/sec over the uncompiled scheduler
    with outputs bit-equal (greedy vs the sequential reference, seeded
    sampled across lanes) and zero fallbacks."""
    errors = []
    for key, types in _TICK_SCHEMA.items():
        if key not in run:
            errors.append(f"missing key {key!r}")
        elif run[key] is None or not isinstance(run[key], types):
            errors.append(f"{key!r} has type {type(run[key]).__name__}, "
                          f"expected {types}")
    if not errors:
        for side in ("uncompiled", "compiled"):
            for k in ("tokens_per_sec", "wall_s", "tokens"):
                v = run[side].get(k)
                if not isinstance(v, (int, float)) or v <= 0:
                    errors.append(f"{side}.{k} must be a positive "
                                  f"number, got {v!r}")
        if run["num_slots"] < 8:
            errors.append(f"num_slots {run['num_slots']} < 8 — not a "
                          "high-occupancy lane")
        if run["speedup_vs_uncompiled"] < _TICK_MIN_SPEEDUP:
            errors.append(
                f"speedup_vs_uncompiled {run['speedup_vs_uncompiled']:.2f}"
                f" < required {_TICK_MIN_SPEEDUP}x at "
                f"{run['num_slots']} slots")
        if run["tick_compiled_hits"] <= 0:
            errors.append("tick_compiled_hits is 0 — the compiled lane "
                          "never actually ran the tick program")
        if run["tick_fallbacks"] != 0:
            errors.append(f"{run['tick_fallbacks']} tick fallback(s) on "
                          "an all-hostable workload")
        if run["greedy_mismatches"] != 0:
            errors.append(
                f"{run['greedy_mismatches']} outputs diverged from the "
                "sequential greedy baseline — the compiled tick must be "
                "output-invariant")
        if run["sampled_mismatches"] != 0:
            errors.append(
                f"{run['sampled_mismatches']} seeded-sampled outputs "
                "diverged between the compiled and uncompiled lanes")
    if errors:
        print("serving_tick schema check FAILED:")
        for e in errors:
            print(f"  - {e}")
        return 1
    print(f"serving_tick schema OK: {run['value']:.1f} tokens/sec, "
          f"{run['speedup_vs_uncompiled']:.2f}x vs uncompiled at "
          f"{run['num_slots']} slots, {run['tick_compiled_hits']} "
          "compiled ticks, outputs bit-equal")
    return 0


def check_spec_bench(run):
    """Schema + speedup/acceptance/capacity gates for the speculative
    lane of benchmarks/serving_bench.py (--workload speculative)."""
    errors = []
    for key, types in _SPEC_SCHEMA.items():
        if key not in run:
            errors.append(f"missing key {key!r}")
        elif run[key] is None or not isinstance(run[key], types):
            errors.append(f"{key!r} has type {type(run[key]).__name__}, "
                          f"expected {types}")
    if not errors:
        for name in ("batch_1", "batch_4"):
            side = run["batches"].get(name)
            if not isinstance(side, dict):
                errors.append(f"batches.{name} missing")
                continue
            for k in ("baseline_tokens_per_sec", "spec_tokens_per_sec",
                      "speedup"):
                v = side.get(k)
                if not isinstance(v, (int, float)) or v <= 0:
                    errors.append(f"batches.{name}.{k} must be a "
                                  f"positive number, got {v!r}")
            sp = run["speedups"].get(name)
            if isinstance(sp, (int, float)) and sp < _SPEC_MIN_SPEEDUP:
                errors.append(
                    f"speedups.{name} {sp:.2f} < required "
                    f"{_SPEC_MIN_SPEEDUP}x vs the non-speculative "
                    "paged engine")
        if run["value"] <= 0:
            errors.append("value must be positive")
        if run["greedy_mismatches"] != 0:
            errors.append(
                f"{run['greedy_mismatches']} outputs diverged from the "
                "sequential greedy baseline — speculation must be "
                "output-invariant")
        if run["acceptance_rate"] < _SPEC_MIN_ACCEPTANCE:
            errors.append(
                f"acceptance_rate {run['acceptance_rate']:.2f} < "
                f"{_SPEC_MIN_ACCEPTANCE} with a function-identical "
                "draft — the accept machinery is rejecting good tokens")
        int8 = run["int8_kv"]
        for k in ("pages_peak_float32", "pages_peak_int8", "ratio"):
            if not isinstance(int8.get(k), (int, float)) or \
                    int8[k] <= 0:
                errors.append(f"int8_kv.{k} missing or not positive")
        if not errors and int8["ratio"] > _SPEC_MAX_INT8_PAGES_RATIO:
            errors.append(
                f"int8_kv.ratio {int8['ratio']:.2f} > "
                f"{_SPEC_MAX_INT8_PAGES_RATIO} — quantized KV did not "
                "deliver ~2x effective cache capacity at equal tokens")
    if errors:
        print("serving_speculative schema check FAILED:")
        for e in errors:
            print(f"  - {e}")
        return 1
    print(f"serving_speculative schema OK: {run['value']:.1f} tokens/"
          f"sec, speedups {run['speedups']}, acceptance "
          f"{run['acceptance_rate']:.2f}, int8 pages ratio "
          f"{run['int8_kv']['ratio']:.2f}")
    return 0


_LORA_SCHEMA = {
    # key -> accepted types; every key is required
    "metric": str,
    "value": (int, float),
    "unit": str,
    "speedup_vs_sequential_adapters": (int, float),
    "sequential_adapters": dict,
    "multiplexed": dict,
    "num_adapters": int,
    "adapter_rank": int,
    "max_adapters": int,
    "num_slots": int,
    "requests_per_adapter": int,
    "max_new_tokens": int,
    "adapter_mismatches": int,
    "dropped_requests": int,
    "tick_fallbacks": (int, float),
    "tick_compiled_hits": (int, float),
    "adapters_loaded": (int, float),
    "adapter_evictions": (int, float),
    "adapter_load_ms_avg": (int, float),
    "smoke": bool,
    "platform": str,
}

# acceptance floors (ISSUE 16): multiplexing N adapters through ONE
# batched engine must sustain >= 5x the aggregate tokens/sec of N
# sequential single-adapter engine runs (the CI smoke lane, 4 adapters
# on 4 slots, clears a lower 2x floor), every per-request output must
# be bit-equal to the dedicated-engine reference, adapter hot-swap
# must drop zero requests, and the compiled tick must serve the whole
# mixed-adapter workload without a single fallback.
_LORA_MIN_SPEEDUP = 5.0
_LORA_MIN_SPEEDUP_SMOKE = 2.0


def check_lora_bench(run):
    """Schema + speedup/bit-equality/zero-drop gates for the
    multi-tenant LoRA lane of benchmarks/serving_bench.py (--workload
    multitenant, ISSUE 16)."""
    errors = []
    for key, types in _LORA_SCHEMA.items():
        if key not in run:
            errors.append(f"missing key {key!r}")
        elif run[key] is None or not isinstance(run[key], types):
            errors.append(f"{key!r} has type {type(run[key]).__name__}, "
                          f"expected {types}")
    if not errors:
        for side in ("sequential_adapters", "multiplexed"):
            for k in ("tokens_per_sec", "wall_s", "tokens"):
                v = run[side].get(k)
                if not isinstance(v, (int, float)) or v <= 0:
                    errors.append(f"{side}.{k} must be a positive "
                                  f"number, got {v!r}")
        floor = _LORA_MIN_SPEEDUP_SMOKE if run["smoke"] \
            else _LORA_MIN_SPEEDUP
        if run["speedup_vs_sequential_adapters"] < floor:
            errors.append(
                f"speedup_vs_sequential_adapters "
                f"{run['speedup_vs_sequential_adapters']:.2f} < required "
                f"{floor}x for {run['num_adapters']} adapters")
        if run["adapter_mismatches"] != 0:
            errors.append(
                f"{run['adapter_mismatches']} outputs diverged from the "
                "single-adapter engine reference — per-slot adapter "
                "gather must be output-invariant")
        if run["dropped_requests"] != 0:
            errors.append(f"{run['dropped_requests']} request(s) "
                          "dropped during adapter hot-swap")
        if run["tick_fallbacks"] != 0:
            errors.append(f"{run['tick_fallbacks']} tick fallback(s) on "
                          "a mixed-adapter workload")
        if run["tick_compiled_hits"] <= 0:
            errors.append("tick_compiled_hits is 0 — the compiled tick "
                          "never actually served the multiplexed lane")
        if run["adapters_loaded"] < run["num_adapters"]:
            errors.append(
                f"adapters_loaded {run['adapters_loaded']} < "
                f"num_adapters {run['num_adapters']} — some tenant "
                "never reached a pool slot")
    if errors:
        print("serving_lora schema check FAILED:")
        for e in errors:
            print(f"  - {e}")
        return 1
    print(f"serving_lora schema OK: {run['value']:.1f} tokens/sec, "
          f"{run['speedup_vs_sequential_adapters']:.2f}x vs "
          f"{run['num_adapters']} sequential single-adapter runs, "
          f"{run['adapter_evictions']} eviction(s), outputs bit-equal, "
          "zero drops/fallbacks")
    return 0


_FLEET_SCHEMA = {
    # key -> accepted types; every key is required
    "metric": str,
    "value": (int, float),
    "unit": str,
    "passed": bool,
    "num_replicas": int,
    "num_slots": int,
    "num_requests": int,
    "max_new_tokens": int,
    "drain_deadline_s": (int, float),
    "variants": dict,
    "smoke": bool,
    "platform": str,
}
_FLEET_VARIANT_KEYS = ("lost_requests", "greedy_mismatches",
                       "duplicate_tokens", "recovery_p99_s", "failovers",
                       "resubmissions", "requests_recovered",
                       "leaked_processes")


def check_fleet_bench(run):
    """Schema + zero-loss/recovery gates for
    benchmarks/serving_fleet_bench.py (ISSUE 9): with replicas dying
    mid-load, every request completes bit-equal to the single-model
    greedy reference (zero lost, zero duplicate tokens), p99 recovery
    stays under the drain deadline, the SIGTERM victim exits 0 within
    the deadline, and no replica process leaks."""
    errors = []
    for key, types in _FLEET_SCHEMA.items():
        if key not in run:
            errors.append(f"missing key {key!r}")
        elif run[key] is None or not isinstance(run[key], types):
            errors.append(f"{key!r} has type {type(run[key]).__name__}, "
                          f"expected {types}")
    if not errors:
        if not run["variants"]:
            errors.append("no chaos variants recorded")
        for name, v in run["variants"].items():
            for k in _FLEET_VARIANT_KEYS:
                if k not in v:
                    errors.append(f"variants.{name} missing {k!r}")
            if errors:
                continue
            if v["lost_requests"] != 0:
                errors.append(f"{name}: {v['lost_requests']} requests "
                              "LOST when the replica died")
            if v["greedy_mismatches"] != 0 or v["duplicate_tokens"] != 0:
                errors.append(
                    f"{name}: {v['greedy_mismatches']} outputs diverged "
                    "from the single-model greedy reference (dropped or "
                    "duplicated tokens on failover)")
            if v["recovery_p99_s"] >= run["drain_deadline_s"]:
                errors.append(
                    f"{name}: recovery p99 {v['recovery_p99_s']}s >= "
                    f"drain deadline {run['drain_deadline_s']}s")
            if v["leaked_processes"]:
                errors.append(f"{name}: leaked replica processes "
                              f"{v['leaked_processes']}")
        sigkill = run["variants"].get("sigkill")
        if sigkill is not None and sigkill.get("failovers", 0) < 1:
            errors.append("sigkill variant recorded no failover — the "
                          "kill landed on an idle fleet (not mid-load)")
        sigterm = run["variants"].get("sigterm")
        if sigterm is not None:
            if sigterm.get("drain_exitcode") != 0:
                errors.append(f"sigterm victim exit code "
                              f"{sigterm.get('drain_exitcode')!r} != 0")
            if sigterm.get("drain_exit_s", 1e9) >= \
                    run["drain_deadline_s"] + 10:
                errors.append(
                    f"sigterm victim took {sigterm.get('drain_exit_s')}s "
                    "to exit — past the drain deadline + grace")
    if errors:
        print("serving_fleet schema check FAILED:")
        for e in errors:
            print(f"  - {e}")
        return 1
    worst = max(v["recovery_p99_s"] for v in run["variants"].values())
    print(f"serving_fleet schema OK: {len(run['variants'])} chaos "
          f"variant(s), zero lost requests, recovery p99 {worst:.2f}s "
          f"< {run['drain_deadline_s']}s deadline")
    return 0


_DISAGG_SCHEMA = {
    # key -> accepted types; every key is required
    "metric": str,
    "value": (int, float),
    "unit": str,
    "ttft_p99_improvement": (int, float),
    "decode_p50_improvement": (int, float),
    "symmetric": dict,
    "disagg": dict,
    "flip": dict,
    "greedy_mismatches": int,
    "num_replicas": int,
    "long_prompts": int,
    "chat_prompts": int,
    "parallel_host": bool,
    "host_cores": int,
    "smoke": bool,
    "platform": str,
}
_DISAGG_SIDE_KEYS = ("ttft_p99_ms", "decode_p50_ms", "tokens_per_sec",
                     "wall_s", "requests")
_DISAGG_FLIP_KEYS = ("victim", "new_role", "lost_requests",
                     "greedy_mismatches", "resubmissions", "converged",
                     "gen_bumped")
# acceptance floors (ISSUE 14): at EQUAL chip count on the mixed
# long-prompt/chat workload, the disaggregated fleet must beat the
# symmetric fleet on BOTH tail TTFT (prefill replicas run chunk rounds
# without decode steps in the way) and median inter-token latency (the
# decode replica's hot loop never pays a prefill chunk), migrated
# outputs must be bit-equal to the single-replica greedy reference,
# and a mid-load role flip must lose zero requests.
#
# The improvement floors apply on a `parallel_host` (>= 3 cores or
# TPU): with the two replicas timesliced onto 1 core, total work is
# conserved and wall-clock deltas measure the OS scheduler, not the
# architecture — there the lane still gates bit-equality, actual
# migration, and the lossless role flip, and records latencies
# observationally (benchmarks/README.md: "a regression canary, never
# a hardware claim").
_DISAGG_MIN_IMPROVEMENT = 1.0


def check_disagg_bench(run):
    """Schema + improvement/bit-equality/flip gates for the
    prefill/decode disaggregation lane of
    benchmarks/serving_fleet_bench.py (--workload disagg, ISSUE 14)."""
    errors = []
    for key, types in _DISAGG_SCHEMA.items():
        if key not in run:
            errors.append(f"missing key {key!r}")
        elif run[key] is None or not isinstance(run[key], types):
            errors.append(f"{key!r} has type {type(run[key]).__name__}, "
                          f"expected {types}")
    if not errors:
        for side in ("symmetric", "disagg"):
            for k in _DISAGG_SIDE_KEYS:
                v = run[side].get(k)
                if not isinstance(v, (int, float)) or v <= 0:
                    errors.append(f"{side}.{k} must be a positive "
                                  f"number, got {v!r}")
        for k in _DISAGG_FLIP_KEYS:
            if k not in run["flip"]:
                errors.append(f"flip missing {k!r}")
    if not errors:
        if run.get("parallel_host", True):
            if run["ttft_p99_improvement"] <= _DISAGG_MIN_IMPROVEMENT:
                errors.append(
                    f"ttft_p99_improvement "
                    f"{run['ttft_p99_improvement']:.3f}"
                    f"x <= {_DISAGG_MIN_IMPROVEMENT}x — disaggregation "
                    "did not improve tail TTFT vs the symmetric fleet")
            if run["decode_p50_improvement"] <= _DISAGG_MIN_IMPROVEMENT:
                errors.append(
                    f"decode_p50_improvement "
                    f"{run['decode_p50_improvement']:.3f}x <= "
                    f"{_DISAGG_MIN_IMPROVEMENT}x — disaggregation did "
                    "not improve median inter-token latency")
        if run["greedy_mismatches"] != 0:
            errors.append(
                f"{run['greedy_mismatches']} outputs diverged from the "
                "single-replica greedy reference — migrated KV pages "
                "must be bit-exact")
        if run["disagg"].get("migrated_requests", 0) < 1:
            errors.append("no request actually migrated — the "
                          "disaggregated lane measured nothing")
        flip = run["flip"]
        if flip["lost_requests"] != 0:
            errors.append(f"{flip['lost_requests']} requests LOST "
                          "through the mid-load role flip")
        if flip["greedy_mismatches"] != 0:
            errors.append(f"{flip['greedy_mismatches']} outputs "
                          "diverged across the role flip")
        if not flip["converged"]:
            errors.append("fleet never converged after the role flip "
                          "(victim not back ready under its new role)")
        if not flip["gen_bumped"]:
            errors.append("role flip rejoined WITHOUT a bumped "
                          "generation — the anti-flap protocol was "
                          "bypassed")
    if errors:
        print("serving_disagg schema check FAILED:")
        for e in errors:
            print(f"  - {e}")
        return 1
    gated = "" if run.get("parallel_host", True) else \
        " (observational: timesliced host)"
    print(f"serving_disagg schema OK: ttft p99 "
          f"{run['ttft_p99_improvement']:.2f}x, decode p50 "
          f"{run['decode_p50_improvement']:.2f}x vs symmetric{gated}, "
          f"{run['disagg'].get('migrated_requests')} migrated, "
          "flip lost 0")
    return 0


_DATA_SCHEMA = {
    # key -> accepted types; every key is required
    "metric": str,
    "throughput": dict,
    "resume": dict,
    "resume_compiled": dict,
    "resize": dict,
    "goodput_drill": dict,
    "calibration": dict,
    "parallel_host": bool,
    "host_cores": int,
    "batch": int,
    "smoke": bool,
}

# acceptance floors (ISSUE 18): on an input-heavy fit (per-batch host
# fetch calibrated to ~1.2x the step time), device_prefetch must
# deliver >= 1.3x steps/sec over the synchronous loader at equal
# model/batch — enforced only on a `parallel_host` (>= 2 cores): with
# producer and trainer timesliced onto 1 core total work is conserved
# and the delta measures the OS scheduler, not the overlap (the disagg
# bench convention).  Resume must be BIT-equal in the eager lane; the
# compiled lane tolerates 5e-6 (whole-step jit reassociates
# reductions).  The 4->2 dp resize must lose and duplicate exactly
# zero sample ids.  The data_slow drill must actually move the
# starvation counter and the input-bound gauge.
_DATA_MIN_SPEEDUP = 1.3
_DATA_MAX_COMPILED_DIFF = 5e-6


def check_data_bench(run):
    """Schema + overlap/determinism/resize gates for
    benchmarks/data_pipeline_bench.py (DATA_PIPELINE_BENCH.json)."""
    errors = []
    for key, types in _DATA_SCHEMA.items():
        if key not in run:
            errors.append(f"missing key {key!r}")
        elif run[key] is None or not isinstance(run[key], types):
            errors.append(f"{key!r} has type {type(run[key]).__name__}, "
                          f"expected {types}")
    if not errors:
        thr = run["throughput"]
        for k in ("sync_steps_per_sec", "prefetch_steps_per_sec",
                  "speedup"):
            v = thr.get(k)
            if not isinstance(v, (int, float)) or v <= 0:
                errors.append(f"throughput.{k} must be a positive "
                              f"number, got {v!r}")
        if not errors and run["parallel_host"] and \
                thr["speedup"] < _DATA_MIN_SPEEDUP:
            errors.append(
                f"throughput.speedup {thr['speedup']:.3f} < required "
                f"{_DATA_MIN_SPEEDUP}x on a parallel host "
                f"({run['host_cores']} cores)")
        res = run["resume"]
        if res.get("bitwise_equal") is not True:
            errors.append(
                "resume.bitwise_equal is not True — the eager mid-epoch "
                f"save->restore diverged (max abs diff "
                f"{res.get('max_abs_diff')!r}, "
                f"{res.get('steps_resumed')!r} of "
                f"{res.get('steps_ref')!r} steps)")
        resc = run["resume_compiled"]
        diff = resc.get("max_abs_diff")
        if not isinstance(diff, (int, float)) or \
                diff > _DATA_MAX_COMPILED_DIFF:
            errors.append(
                f"resume_compiled.max_abs_diff {diff!r} > "
                f"{_DATA_MAX_COMPILED_DIFF} tolerance")
        if resc.get("steps_resumed") != resc.get("steps_ref"):
            errors.append(
                f"resume_compiled ran {resc.get('steps_resumed')!r} "
                f"steps vs {resc.get('steps_ref')!r} in the reference")
        rez = run["resize"]
        if rez.get("lost") != 0 or rez.get("duplicated") != 0:
            errors.append(
                f"resize {rez.get('from_degree')}->{rez.get('to_degree')}"
                f" lost {rez.get('lost')!r} and duplicated "
                f"{rez.get('duplicated')!r} sample ids (both must be 0)")
        if not isinstance(rez.get("checked_samples"), int) or \
                rez.get("checked_samples", 0) <= 0:
            errors.append("resize.checked_samples missing or not a "
                          "positive int — the audit checked nothing")
        drill = run["goodput_drill"]
        if not drill.get("starved_steps"):
            errors.append("goodput_drill.starved_steps is 0 under "
                          "data_slow injection — the starvation counter "
                          "never moved")
        ib = drill.get("input_bound")
        if not isinstance(ib, (int, float)) or not 0.0 < ib <= 1.0:
            errors.append(f"goodput_drill.input_bound {ib!r} outside "
                          "(0, 1] under data_slow injection")
    if errors:
        print("data_pipeline schema check FAILED:")
        for e in errors:
            print(f"  - {e}")
        return 1
    gated = "" if run["parallel_host"] else \
        " (observational: timesliced host)"
    print(f"data_pipeline schema OK: prefetch "
          f"{run['throughput']['speedup']:.2f}x vs sync loader{gated}, "
          f"resume bit-equal, compiled diff "
          f"{run['resume_compiled']['max_abs_diff']:.1e}, resize "
          f"{run['resize']['from_degree']}->{run['resize']['to_degree']} "
          "lost 0 / dup 0")
    return 0


_RECOVERY_SCHEMA = {
    # key -> accepted types; every key is required
    "metric": str,
    "value": (int, float),
    "latency_ratio": (int, float),
    "peer_restore_ms": (int, float),
    "peer_recovery_ms": (int, float),
    "peer_steps_lost": int,
    "disk_restore_ms": (int, float),
    "disk_replay_ms": (int, float),
    "disk_recovery_ms": (int, float),
    "disk_steps_lost": int,
    "snapshot_overhead_ratio": (int, float),
    "guarded_step_ms_p50": (int, float),
    "unguarded_step_ms_p50": (int, float),
    "crash_step": int,
    "state_bytes": int,
    "snap_every": int,
    "disk_every": int,
    "smoke": bool,
    "platform": str,
    "parallel_host": bool,
    "host_cores": int,
}

# acceptance floors (ISSUE 20): recovering the SAME injected crash from
# the buddy's RAM snapshot (restore + zero replay) must cost <= 0.5x the
# disk ladder rung (restore newest ckpt-N + re-train the steps since),
# must lose STRICTLY fewer steps, and arming the hot-spare agent must
# keep the steady-state guarded step p50 within 1.05x of unguarded.
# The overhead floor needs the stream thread to actually OVERLAP the
# step, so it is enforced only on a `parallel_host` (>= 2 cores): on a
# 1-core timesliced box total work is conserved and the ratio measures
# the OS scheduler, not the overlap (the data/disagg bench convention) —
# there the overhead is recorded observationally under a loose sanity
# cap.  The latency gate applies everywhere: both recovery lanes are
# serial, so timeslicing is fair to them.
# FLAGS_hot_spare=0 bitwise identity is gated in tests/test_hot_spare.py.
_RECOVERY_MAX_LATENCY_RATIO = 0.5
_RECOVERY_MAX_OVERHEAD = 1.05
_RECOVERY_MAX_OVERHEAD_TIMESLICED = 1.5


def check_recovery_bench(run):
    """Schema + latency/steps-lost/overhead gates for
    benchmarks/recovery_bench.py (RECOVERY_BENCH.json)."""
    errors = []
    for key, types in _RECOVERY_SCHEMA.items():
        if key not in run:
            errors.append(f"missing key {key!r}")
        elif run[key] is None or not isinstance(run[key], types):
            errors.append(f"{key!r} has type {type(run[key]).__name__}, "
                          f"expected {types}")
    if not errors:
        for k in ("peer_restore_ms", "disk_restore_ms",
                  "disk_recovery_ms", "guarded_step_ms_p50",
                  "unguarded_step_ms_p50", "state_bytes"):
            if run[k] <= 0:
                errors.append(f"{k} must be positive, got {run[k]!r}")
        if run["latency_ratio"] > _RECOVERY_MAX_LATENCY_RATIO:
            errors.append(
                f"latency_ratio {run['latency_ratio']:.3f} > "
                f"{_RECOVERY_MAX_LATENCY_RATIO} — peer restore did not "
                "beat the disk rung by 2x on the same failure")
        if run["peer_steps_lost"] >= run["disk_steps_lost"]:
            errors.append(
                f"peer_steps_lost {run['peer_steps_lost']} >= "
                f"disk_steps_lost {run['disk_steps_lost']} — the RAM "
                "replica was no fresher than the newest ckpt-N")
        if run["parallel_host"] and \
                run["snapshot_overhead_ratio"] > _RECOVERY_MAX_OVERHEAD:
            errors.append(
                f"snapshot_overhead_ratio "
                f"{run['snapshot_overhead_ratio']:.3f} > "
                f"{_RECOVERY_MAX_OVERHEAD} on a parallel host "
                f"({run['host_cores']} cores) — arming the agent "
                "slowed the guarded training step")
        if not run["parallel_host"] and \
                run["snapshot_overhead_ratio"] > \
                _RECOVERY_MAX_OVERHEAD_TIMESLICED:
            errors.append(
                f"snapshot_overhead_ratio "
                f"{run['snapshot_overhead_ratio']:.3f} > sanity cap "
                f"{_RECOVERY_MAX_OVERHEAD_TIMESLICED} even for a "
                "timesliced 1-core host — the snapshot path is doing "
                "way too much synchronous work")
    if errors:
        print("recovery_ladder schema check FAILED:")
        for e in errors:
            print(f"  - {e}")
        return 1
    gated = "" if run["parallel_host"] else \
        f" (observational: {run['host_cores']}-core host)"
    print(f"recovery_ladder schema OK: peer {run['peer_recovery_ms']:.0f}ms "
          f"({run['peer_steps_lost']} steps lost) vs disk "
          f"{run['disk_recovery_ms']:.0f}ms ({run['disk_steps_lost']} "
          f"lost), ratio {run['latency_ratio']:.2f}, snapshot overhead "
          f"{run['snapshot_overhead_ratio']:.3f}x{gated}")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("bench_json")
    ap.add_argument("--baseline", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_BASELINE.json"))
    ap.add_argument("--threshold", type=float, default=0.9,
                    help="fail if value < threshold * recorded best")
    args = ap.parse_args()

    with open(args.bench_json) as f:
        run = json.load(f)
    if "parsed" in run:          # driver-recorded BENCH_rN.json wrapper
        run = run["parsed"]
    if str(run.get("metric", "")).startswith("recovery"):
        return check_recovery_bench(run)
    if str(run.get("metric", "")).startswith("data_pipeline"):
        return check_data_bench(run)
    if str(run.get("metric", "")).startswith("eager_op_dispatch"):
        return check_eager_overhead(run)
    if str(run.get("metric", "")).startswith("train_step"):
        return check_train_step_bench(run)
    if str(run.get("metric", "")).startswith("mfu_sweep"):
        return check_mfu_sweep(run)
    if str(run.get("metric", "")).startswith("serving_disagg"):
        return check_disagg_bench(run)
    if str(run.get("metric", "")).startswith("serving_fleet"):
        return check_fleet_bench(run)
    if str(run.get("metric", "")).startswith("serving_lora"):
        return check_lora_bench(run)
    if str(run.get("metric", "")).startswith("serving_tick"):
        return check_tick_bench(run)
    if str(run.get("metric", "")).startswith("serving_speculative"):
        return check_spec_bench(run)
    if str(run.get("metric", "")).startswith("serving_paged"):
        return check_paged_bench(run)
    if str(run.get("metric", "")).startswith("serving_"):
        return check_serving_bench(run)
    value = float(run["value"])
    platform = "cpu" if "cpu" in run.get("metric", "") else "tpu"

    try:
        with open(args.baseline) as f:
            base = json.load(f)
    except OSError:
        print("no baseline recorded — pass (first run)")
        return 0
    entry = base.get(platform) or {}
    best = entry.get("tokens_per_sec")
    if not best:
        print(f"no {platform} baseline recorded — pass")
        return 0
    ratio = value / best
    print(f"{run['metric']}: {value:.1f} vs best {best:.1f} "
          f"(ratio {ratio:.3f}, threshold {args.threshold})")
    if ratio < args.threshold:
        print("benchmark regression gate FAILED")
        return 1
    print("benchmark gate passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
