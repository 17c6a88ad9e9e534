"""Profiler tests (reference: test/legacy_test profiler tests — scheduler
state machine, span capture, chrome export)."""
import json
import os
import types

import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.profiler import (
    Profiler, ProfilerState, ProfilerTarget, RecordEvent, make_scheduler,
)


def test_make_scheduler_states():
    sched = make_scheduler(closed=1, ready=1, record=2, repeat=1)
    states = [sched(i) for i in range(6)]
    assert states[0] == ProfilerState.CLOSED
    assert states[1] == ProfilerState.READY
    assert states[2] == ProfilerState.RECORD
    assert states[3] == ProfilerState.RECORD_AND_RETURN
    assert states[4] == ProfilerState.CLOSED  # repeat exhausted


def test_profiler_records_spans_and_exports(tmp_path):
    done = []
    prof = Profiler(targets=[ProfilerTarget.CPU],
                    scheduler=make_scheduler(closed=0, ready=0, record=2,
                                             repeat=1),
                    on_trace_ready=lambda p: done.append(p),
                    timer_only=True)
    prof.start()
    for step in range(3):
        with RecordEvent("forward"):
            x = paddle.randn([32, 32])
            (x @ x).numpy()
        with RecordEvent("backward"):
            pass
        prof.step()
    prof.stop()
    names = {e["name"] for e in prof.events}
    assert "forward" in names
    assert any(n.startswith("ProfileStep") for n in names)

    out = str(tmp_path / "trace.json")
    prof.export(out)
    data = json.load(open(out))
    assert len(data["traceEvents"]) > 0

    table = prof.summary()
    assert "forward" in table


def test_record_event_outside_profiler_is_noop():
    with RecordEvent("orphan"):
        pass  # must not raise or leak into the next profiler


def test_benchmark_ips():
    bm = profiler.benchmark()
    bm.begin()
    for _ in range(3):
        bm.before_reader()
        bm.after_reader()
        bm.after_step(num_samples=4)
    assert bm.ips > 0
    assert "ips" in bm.step_info()


_V5E = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")


def test_mfu_calculator():
    # mfu = flops/time/peak, the peak from the device's row of the table
    m1 = profiler.mfu(197e12, 1.0, n_devices=1, device=_V5E)
    m2 = profiler.mfu(197e12, 2.0, n_devices=2, device=_V5E)
    assert m1 == pytest.approx(1.0) and m2 == pytest.approx(0.25)
    # the CPU these tests run on has no peak: no MFU from its timings
    assert profiler.mfu(1e12, 1.0) is None


def test_registry_flops_counter_mfu():
    """Registry flops metadata feeds a profiler-computed MFU for any model
    (replaces the per-model hand formula; VERDICT r1 weak #7)."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.profiler import count_flops
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.models.gpt import GPTConfig

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                    num_heads=4, max_seq_len=128, use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    ids = paddle.to_tensor(np.random.default_rng(0).integers(
        0, 512, (2, 128), dtype=np.int32))
    with paddle.no_grad():
        _, fc = count_flops(m, ids, labels=ids)
    # the matmul family must dominate the count
    heavy = sum(v for k, v in fc.by_op.items()
                if k in ("matmul", "linear", "bmm", "flash_attention"))
    assert heavy > 0.5 * fc.forward_flops
    # counted analytic flops within 3x of the PaLM formula (hand method)
    analytic_step = m.flops_per_token(128) * 2 * 128
    ratio = fc.train_step_flops / analytic_step
    assert 1 / 3 < ratio < 3, (ratio, fc.by_op, fc.uncounted)
    # registry-metadata MFU is finite and positive
    val = profiler.mfu(fc.train_step_flops, step_time_s=0.5, device=_V5E)
    assert 0 < val < 100


def test_operator_summary_tables():
    """VERDICT r3 item 10: summary() prints a sorted per-op table (calls,
    host time, device time, FLOPs) from the dispatch-funnel spans."""
    import numpy as np
    from paddle_tpu.profiler import SortedKeys

    prof = Profiler(targets=[ProfilerTarget.CPU, ProfilerTarget.TPU])
    a = paddle.to_tensor(np.random.default_rng(0)
                         .normal(size=(64, 64)).astype(np.float32))
    with prof:
        with RecordEvent("block"):
            for _ in range(3):
                b = paddle.matmul(a, a)
            (b + a).numpy()
    table = prof.summary(sorted_by=SortedKeys.CPUTotal)
    assert "Operator Summary" in table
    assert "Overview Summary" in table
    assert "matmul" in table and "block" in table
    # per-op aggregation: matmul called 3 times, with analytic GFLOPs
    row = next(ln for ln in table.splitlines()
               if ln.startswith("matmul"))
    cols = row.split()
    assert cols[1] == "3"
    gflops = float(cols[-1])
    assert abs(gflops - 3 * 2 * 64**3 / 1e9) / (3 * 2 * 64**3 / 1e9) < 0.5
    # device column populated (TPU target → sync timing)
    assert cols[-3] != "-"
    # events carry Operator category for the chrome trace
    assert any(e.get("cat") == "Operator" for e in prof.events)


def test_op_profiling_off_outside_profiler():
    import numpy as np
    from paddle_tpu.profiler.profiler import op_profiling_active
    assert not op_profiling_active()
    x = paddle.to_tensor(np.ones((4, 4), np.float32))
    paddle.matmul(x, x)  # no profiler: dispatch must not record spans
    prof = Profiler(targets=[ProfilerTarget.CPU], timer_only=True)
    with prof:
        assert not op_profiling_active()   # timer_only skips op spans


def test_merge_chrome_traces_cross_host(tmp_path):
    """CrossStackProfiler analog: per-host traces merge into one
    timeline with disjoint pid bands."""
    import json
    from paddle_tpu.profiler import merge_chrome_traces
    for i in range(2):
        with open(tmp_path / f"host{i}.json", "w") as f:
            json.dump({"traceEvents": [
                {"name": f"op{i}", "ph": "X", "ts": 10 * i, "dur": 5,
                 "pid": 7, "tid": 1}]}, f)
    out = merge_chrome_traces(
        [str(tmp_path / "host0.json"), str(tmp_path / "host1.json")],
        str(tmp_path / "merged.json"))
    merged = json.load(open(out))["traceEvents"]
    evs = [e for e in merged if e.get("ph") == "X"]
    metas = [e for e in merged if e.get("ph") == "M"]
    assert len(evs) == 2 and len(metas) == 2
    assert evs[0]["pid"] != evs[1]["pid"]       # disjoint host bands
    assert any("host1" in m["args"]["name"] for m in metas)


def test_op_spans_carry_cache_hit_annotation():
    """ISSUE 1 tier-3 observability: op spans recorded while the tier-1
    executable cache serves a dispatch are annotated cache_hit=True."""
    import numpy as np
    from paddle_tpu.core import op_cache

    op_cache.clear()
    paddle.set_flags({"FLAGS_eager_op_cache": True})
    a = paddle.to_tensor(np.ones((16, 16), np.float32))
    paddle.matmul(a, a)   # outside the profiler: populates the cache
    prof = Profiler(targets=[ProfilerTarget.CPU])
    with prof:
        for _ in range(2):
            paddle.matmul(a, a)
    spans = [e for e in prof.events
             if e.get("cat") == "Operator" and e.get("name") == "matmul"]
    assert spans, "no matmul op spans recorded"
    assert all(e["args"].get("cache_hit") is True for e in spans)
    # and with the cache off, the annotation reports the bypass honestly
    paddle.set_flags({"FLAGS_eager_op_cache": False})
    prof2 = Profiler(targets=[ProfilerTarget.CPU])
    with prof2:
        paddle.matmul(a, a)
    paddle.set_flags({"FLAGS_eager_op_cache": True})
    spans2 = [e for e in prof2.events
              if e.get("cat") == "Operator" and e.get("name") == "matmul"]
    assert spans2 and all("cache_hit" not in e.get("args", {})
                          for e in spans2)
    op_cache.clear()


def test_make_scheduler_skip_first_and_repeat_edges():
    """ISSUE 4 satellite: skip_first delays the whole cycle; repeat=0
    cycles forever; a single-step window is RECORD_AND_RETURN."""
    sched = make_scheduler(closed=1, ready=0, record=1, repeat=1,
                           skip_first=3)
    assert [sched(i) for i in range(3)] == [ProfilerState.CLOSED] * 3
    assert sched(3) == ProfilerState.CLOSED          # cycle: closed
    assert sched(4) == ProfilerState.RECORD_AND_RETURN
    assert sched(5) == ProfilerState.CLOSED          # repeat exhausted
    assert sched(50) == ProfilerState.CLOSED

    # repeat=0 → cycles forever
    sched = make_scheduler(closed=0, ready=1, record=1, repeat=0)
    for base in (0, 2, 200):
        assert sched(base) == ProfilerState.READY
        assert sched(base + 1) == ProfilerState.RECORD_AND_RETURN

    # single-step window: every step both records and returns
    sched = make_scheduler(closed=0, ready=0, record=1, repeat=0)
    assert sched(0) == ProfilerState.RECORD_AND_RETURN
    assert sched(7) == ProfilerState.RECORD_AND_RETURN


def test_chrome_export_has_process_and_thread_metadata(tmp_path):
    """ISSUE 4 satellite: Perfetto shows bare pids/tids without
    process_name/thread_name metadata rows — the export must emit them
    for every pid/tid its spans reference."""
    prof = Profiler(targets=[ProfilerTarget.CPU], timer_only=True)
    with prof:
        with RecordEvent("meta::span"):
            pass
    out = str(tmp_path / "meta_trace.json")
    prof.export(out)
    events = json.load(open(out))["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X"]
    metas = [e for e in events if e.get("ph") == "M"]
    assert spans, "no spans exported"
    proc_names = {m["pid"] for m in metas if m["name"] == "process_name"}
    thread_names = {(m["pid"], m["tid"]) for m in metas
                    if m["name"] == "thread_name"}
    for e in spans:
        assert e["pid"] in proc_names, e
        assert (e["pid"], e["tid"]) in thread_names, e
    pid_row = [m for m in metas if m["name"] == "process_name"][0]
    assert str(os.getpid()) in pid_row["args"]["name"]


def test_record_event_args_land_in_span():
    prof = Profiler(targets=[ProfilerTarget.CPU], timer_only=True)
    with prof:
        with RecordEvent("tagged", args={"request_id": 11}):
            pass
    span = [e for e in prof.events if e["name"] == "tagged"][0]
    assert span["args"]["request_id"] == 11
