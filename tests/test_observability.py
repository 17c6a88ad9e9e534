"""Unified telemetry tests: typed registry, Prometheus/JSON exposition,
monitor shim compatibility, StepMetrics/MFU, exporter, flight recorder
(reference capability: platform/monitor.{h,cc} stats + the profiler's
chrometracing plane, unified here per docs/OBSERVABILITY.md)."""
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time

import pytest

from paddle_tpu import observability as obs
from paddle_tpu.observability import (
    Counter, Gauge, Histogram, MetricsRegistry, MetricsExporter,
    FlightRecorder, StepMetrics, log_buckets,
)
from paddle_tpu.utils import monitor


# ---------------------------------------------------------------------------
# registry types
# ---------------------------------------------------------------------------

def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("c", "help")
    assert c.inc() == 1
    assert c.inc(4) == 5
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g")
    g.set(7.5)
    assert g.value == 7.5
    g.dec(0.5)
    assert g.value == 7.0
    g.max(3.0)              # watermark never goes down
    assert g.value == 7.0
    g.max(9.0)
    assert g.value == 9.0
    # get-or-create returns the SAME metric; type conflicts raise
    assert reg.counter("c") is c
    with pytest.raises(TypeError):
        reg.gauge("c")


def test_histogram_bucket_boundaries():
    reg = MetricsRegistry()
    h = reg.histogram("h", buckets=(1.0, 10.0, 100.0))
    # le buckets are INCLUSIVE upper bounds (prometheus semantics)
    for v in (0.5, 1.0, 1.5, 10.0, 99.0, 100.5):
        h.observe(v)
    assert h.count == 6
    assert h.sum == pytest.approx(212.5)
    assert h.min == 0.5 and h.max == 100.5
    text = reg.render_prometheus()
    # cumulative counts at each bound: <=1: 2, <=10: 4, <=100: 5, inf: 6
    assert 'h_bucket{le="1"} 2' in text
    assert 'h_bucket{le="10"} 4' in text
    assert 'h_bucket{le="100"} 5' in text
    assert 'h_bucket{le="+Inf"} 6' in text
    assert "h_count 6" in text


def test_histogram_percentiles():
    h = MetricsRegistry().histogram("lat", buckets=log_buckets(0.1, 1e4))
    for v in range(1, 101):            # 1..100 ms uniform
        h.observe(float(v))
    p50 = h.percentile(50)
    p99 = h.percentile(99)
    assert 30 <= p50 <= 70             # bucket-resolution estimate
    assert p99 >= p50
    assert p99 <= 100.0                # clamped to observed max
    assert h.percentile(0) >= h.min
    snap = h.snapshot()
    assert snap["count"] == 100 and snap["p50"] == p50
    with pytest.raises(ValueError):
        h.percentile(101)


def test_histogram_empty_percentile_is_none():
    h = MetricsRegistry().histogram("e")
    assert h.percentile(50) is None
    assert h.snapshot()["p99"] is None
    assert h.avg is None


def test_log_buckets_spacing():
    b = log_buckets(0.001, 1000, per_decade=3)
    assert list(b) == sorted(b)
    assert b[0] <= 0.001 and b[-1] >= 1000
    # ~log-spaced: successive ratio constant-ish
    ratios = [b[i + 1] / b[i] for i in range(len(b) - 1)]
    assert max(ratios) / min(ratios) < 1.01


def test_concurrent_counter_and_histogram():
    reg = MetricsRegistry()
    c = reg.counter("races.c")
    h = reg.histogram("races.h")
    lc = reg.counter("races.l", labelnames=("worker",))
    n_threads, n_iter = 8, 400
    errs = []

    def worker(i):
        try:
            for _ in range(n_iter):
                c.inc()
                h.observe(2.0)
                lc.labels(worker=str(i % 2)).inc()
        except Exception as e:          # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=worker, args=(i,))
          for i in range(n_threads)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert not errs
    assert c.value == n_threads * n_iter
    assert h.count == n_threads * n_iter
    assert h.sum == pytest.approx(2.0 * n_threads * n_iter)
    total = sum(child.value for _, child in lc._samples())
    assert total == n_threads * n_iter


# ---------------------------------------------------------------------------
# Prometheus exposition: strict parse
# ---------------------------------------------------------------------------

_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_SAMPLE = re.compile(
    r"^(?P<name>%s)(?P<labels>\{[^}]*\})? (?P<value>[-+]?[0-9.eE+-]+|NaN)$"
    % _NAME)
_LABEL = re.compile(r'(%s)="((?:[^"\\]|\\.)*)"(,|$)' % _NAME)


def _parse_prometheus(text):
    """Strict text-format-0.0.4 parser: every line must be a HELP/TYPE
    comment or a well-formed sample; returns {name: [(labels, value)]}."""
    series = {}
    typed = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            assert re.match(r"^# HELP %s .*$" % _NAME, line), line
            continue
        if line.startswith("# TYPE "):
            m = re.match(r"^# TYPE (%s) "
                         r"(counter|gauge|histogram|summary|untyped)$"
                         % _NAME, line)
            assert m, line
            typed[m.group(1)] = m.group(2)
            continue
        m = _SAMPLE.match(line)
        assert m, f"unparseable sample line: {line!r}"
        labels = {}
        body = (m.group("labels") or "{}")[1:-1]
        consumed = 0
        for lm in _LABEL.finditer(body):
            labels[lm.group(1)] = lm.group(2)
            consumed = lm.end()
        assert consumed == len(body), f"bad label block: {body!r}"
        series.setdefault(m.group("name"), []).append(
            (labels, m.group("value")))
    return series, typed


def test_render_prometheus_round_trips_strict_parser():
    reg = MetricsRegistry()
    reg.counter("app.requests", "requests served",
                labelnames=("route",)).labels(route="/v1").inc(3)
    reg.gauge("app.depth", "queue depth").set(2)
    h = reg.histogram("app.lat_ms", "latency", buckets=(1, 10))
    h.observe(0.5)
    h.observe(50)
    series, typed = _parse_prometheus(reg.render_prometheus())
    assert typed["app_requests"] == "counter"
    assert typed["app_depth"] == "gauge"
    assert typed["app_lat_ms"] == "histogram"
    assert ({"route": "/v1"}, "3") in series["app_requests"]
    # histogram series complete and cumulative
    buckets = {lb["le"]: float(v) for lb, v in series["app_lat_ms_bucket"]}
    assert buckets["1"] == 1 and buckets["10"] == 1
    assert buckets["+Inf"] == 2
    assert float(series["app_lat_ms_count"][0][1]) == 2


def test_prometheus_label_and_name_escaping():
    reg = MetricsRegistry()
    c = reg.counter("weird.name-with.dots", "multi\nline \\help",
                    labelnames=("path",))
    c.labels(path='a"b\\c\nd').inc()
    text = reg.render_prometheus()
    series, typed = _parse_prometheus(text)       # must stay parseable
    assert "weird_name_with_dots" in typed
    (labels, value), = series["weird_name_with_dots"]
    assert labels["path"] == 'a\\"b\\\\c\\nd'     # escaped forms survive
    assert "multi\nline" not in text              # no raw newline in HELP


def test_full_default_registry_renders_parseable():
    """Whatever the framework has published so far (cache tiers, io,
    train) must come out strictly parseable."""
    monitor.incr("smoke.counter")
    monitor.observe("smoke.lat", 3.0)
    series, typed = _parse_prometheus(obs.render_prometheus())
    assert "smoke_counter" in series
    assert typed["smoke_lat"] == "histogram"


# ---------------------------------------------------------------------------
# monitor shim compatibility
# ---------------------------------------------------------------------------

def test_monitor_reset_clears_derived_keys():
    """Satellite fix: reset(name) used to pop only the exact key, leaving
    observe()'s <name>.sum/<name>.count pair orphaned."""
    monitor.observe("orph.lat", 5.0)
    monitor.observe("orph.lat", 7.0)
    s = monitor.all_stats()
    assert s["orph.lat.sum"] == 12.0 and s["orph.lat.count"] == 2
    monitor.reset("orph.lat")
    s = monitor.all_stats()
    assert s.get("orph.lat.sum", 0) == 0
    assert s.get("orph.lat.count", 0) == 0
    # resetting via a derived key clears the whole observation too
    monitor.observe("orph.lat", 5.0)
    monitor.reset("orph.lat.count")
    assert monitor.get_monitor_value("orph.lat.sum") == 0


def test_monitor_values_are_registry_metrics():
    monitor.reset("shim.c")
    monitor.incr("shim.c", 2)
    m = obs.REGISTRY.get("shim.c")
    assert isinstance(m, Counter) and m.value == 2
    monitor.set_value("shim.g", 4.5)
    assert isinstance(obs.REGISTRY.get("shim.g"), Gauge)
    monitor.observe("shim.h", 1.0)
    assert isinstance(obs.REGISTRY.get("shim.h"), Histogram)
    # and the flat view matches the legacy shapes
    s = monitor.all_stats()
    assert s["shim.c"] == 2 and s["shim.g"] == 4.5
    assert s["shim.h.count"] == 1


def test_cache_stats_backed_by_registry():
    from paddle_tpu.core import op_cache
    from paddle_tpu.utils import cache_stats
    op_cache.clear()
    st = cache_stats()["tier1"]
    assert st["hits"] == 0 and st["misses"] == 0
    assert obs.REGISTRY.get("cache.tier1.hits") is not None
    import numpy as np
    import paddle_tpu as paddle
    x = paddle.to_tensor(np.ones((2, 2), np.float32))
    (x + x).numpy()
    (x + x).numpy()
    st = cache_stats()["tier1"]
    assert st["misses"] >= 1 and st["hits"] >= 1
    assert obs.REGISTRY.get("cache.tier1.misses").value == st["misses"]


def test_serving_request_labeled_series():
    from paddle_tpu.serving import stats as sstats
    sstats.reset_serving_stats()
    sstats.request_observe("request_tokens", 7, 5)
    sstats.request_observe("request_tokens", 8, 3)
    s = monitor.all_stats()
    assert s["serving.request_tokens{request_id=7}"] == 5
    series, _ = _parse_prometheus(obs.render_prometheus())
    assert ({"request_id": "7"}, "5") in series["serving_request_tokens"]
    sstats.reset_serving_stats()
    assert "serving.request_tokens{request_id=7}" not in monitor.all_stats()


def test_serving_request_label_cardinality_converges():
    """A long-lived engine's per-request family is LRU-rotated to
    ``FLAGS_serving_request_label_cap`` children (ISSUE 19): observing
    thousands of distinct request ids converges to the cap with the
    most-recent ids surviving, instead of growing one series per
    request forever."""
    from paddle_tpu.serving import stats as sstats
    from paddle_tpu.utils.flags import set_flags
    sstats.reset_serving_stats()
    set_flags({"FLAGS_serving_request_label_cap": 8})
    try:
        for rid in range(100):
            sstats.request_observe("request_tokens", rid, 1)
        from paddle_tpu.observability import registry
        fam = registry.counter("serving.request_tokens",
                               labelnames=("request_id",))
        kept = {vals[0] for vals, _ in fam._samples()}
        assert len(kept) == 8
        assert kept == {str(r) for r in range(92, 100)}  # MRU survive
        # re-touching an old id re-creates it and evicts the LRU one
        sstats.request_observe("request_tokens", 0, 1)
        kept = {vals[0] for vals, _ in fam._samples()}
        assert "0" in kept and "92" not in kept and len(kept) == 8
        # cap <= 0 disables rotation entirely
        set_flags({"FLAGS_serving_request_label_cap": 0})
        for rid in range(200, 220):
            sstats.request_observe("request_tokens", rid, 1)
        assert len(fam._samples()) == 28
    finally:
        set_flags({"FLAGS_serving_request_label_cap": 1024})
        sstats.reset_serving_stats()


# ---------------------------------------------------------------------------
# StepMetrics
# ---------------------------------------------------------------------------

def test_step_metrics_throughput_and_mfu():
    reg = MetricsRegistry()
    sm = StepMetrics(prefix="t.", registry=reg, peak_flops=1e12,
                     tokens_per_example=16)
    sm.set_flops_per_step(2e9)
    for _ in range(4):
        with sm.step(examples=8):
            time.sleep(0.002)
    snap = sm.snapshot()
    assert snap["steps"] == 4
    assert snap["examples_total"] == 32
    assert snap["tokens_total"] == 32 * 16
    assert snap["step_time_ms"]["count"] == 4
    assert snap["step_time_ms"]["p50"] >= 1.0
    assert snap["step_time_ms"]["p99"] >= snap["step_time_ms"]["p50"]
    assert snap["tokens_per_sec"] > 0
    # mfu = flops / dt / peak; dt ~2ms → ~2e9/0.002/1e12 ≈ 1.0 (loose)
    assert 0 < snap["mfu"] < 100
    assert snap["peak_flops"] == 1e12
    # memory watermark sampled (CPU fallback: host RSS)
    assert snap["memory"], snap
    key = next(iter(snap["memory"]))
    assert "peak" in " ".join(snap["memory"][key].keys()) or \
        "peak_bytes" in snap["memory"][key]


def test_step_metrics_peak_is_per_device_of_the_step(monkeypatch):
    """The peak comes from the one table by device_kind, times the
    devices the step runs on (the active mesh; one without a mesh —
    not every chip the host holds); an unlisted accelerator raises."""
    import types

    import jax
    from paddle_tpu import cost_model
    from paddle_tpu.distributed import mesh as mesh_mod

    def fake(kind, platform="tpu"):
        return [types.SimpleNamespace(platform=platform, device_kind=kind)]

    sm = StepMetrics(prefix="pk.", registry=MetricsRegistry())
    assert sm.peak_flops() is None                     # the CPU under test
    monkeypatch.setattr(jax, "devices", lambda *a: fake("TPU v5 lite"))
    assert cost_model.device_spec().name == "v5e"
    assert cost_model.DEVICE_SPECS["v5e"].hbm_bandwidth == 819e9
    assert sm.peak_flops() == 197e12                   # no mesh: one chip
    monkeypatch.setattr(
        mesh_mod, "get_mesh",
        lambda: types.SimpleNamespace(
            jax_mesh=types.SimpleNamespace(size=4)))
    assert sm.peak_flops() == 4 * 197e12
    monkeypatch.setattr(jax, "devices", lambda *a: fake("TPU v9 mega"))
    with pytest.raises(KeyError, match="TPU v9 mega"):
        sm.peak_flops()


def test_step_metrics_peak_flops_flag():
    import paddle_tpu as paddle
    paddle.set_flags({"FLAGS_peak_flops": 5e11})
    try:
        sm = StepMetrics(prefix="pf.", registry=MetricsRegistry())
        assert sm.peak_flops() == 5e11
    finally:
        paddle.set_flags({"FLAGS_peak_flops": 0.0})


def test_hapi_fit_reports_step_metrics():
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn

    class Data:
        def __len__(self):
            return 32

        def __getitem__(self, i):
            rng = np.random.default_rng(i)
            return (rng.normal(size=(8,)).astype(np.float32),
                    np.array([i % 2], dtype=np.int64))

    net = nn.Sequential(nn.Linear(8, 8), nn.ReLU(), nn.Linear(8, 2))
    model = paddle.Model(net)
    model.prepare(optimizer=paddle.optimizer.SGD(
        learning_rate=0.1, parameters=net.parameters()),
        loss=nn.CrossEntropyLoss())
    # train.steps_total is a process-global counter shared by every Model,
    # so assert the delta this fit contributes, not the absolute value
    from paddle_tpu.observability import registry as _global_registry
    steps_before = _global_registry.counter("train.steps_total").value
    examples_before = _global_registry.counter("train.examples_total").value
    model.fit(Data(), batch_size=8, epochs=1, verbose=0, shuffle=False)
    snap = model.step_metrics.snapshot()
    assert snap["steps"] - steps_before == 4
    assert snap["step_time_ms"]["p50"] is not None
    assert snap["step_time_ms"]["p99"] is not None
    assert snap["examples_per_sec"] > 0
    # float inputs: no token notion, but examples counted
    assert snap["examples_total"] - examples_before == 32
    # linear layers have estimators → analytic flops; the CPU this runs
    # on has no peak, so no MFU is derived from its timings
    assert snap["flops_per_step"] and snap["flops_per_step"] > 0
    assert snap["mfu"] is None and snap["peak_flops"] is None
    assert "train_mfu" not in model.step_metrics.registry.render_prometheus()


# ---------------------------------------------------------------------------
# exporter
# ---------------------------------------------------------------------------

def test_metrics_exporter_appends_snapshots(tmp_path):
    reg = MetricsRegistry()
    reg.counter("exp.ticks").inc(3)
    path = str(tmp_path / "metrics.jsonl")
    ex = MetricsExporter(path, interval_s=0.03, registry=reg).start()
    time.sleep(0.15)
    ex.stop()
    lines = [json.loads(line)
             for line in open(path).read().splitlines() if line]
    assert len(lines) >= 2             # periodic + final
    for rec in lines:
        assert {"schema_version", "ts", "pid", "counters", "gauges",
                "histograms"} <= set(rec)
        # every line self-describes its schema so a consumer pinned to
        # version 1 can fail loudly instead of misparsing (ISSUE 19)
        assert rec["schema_version"] == 1
    assert lines[-1]["counters"]["exp.ticks"] == 3


def test_maybe_start_exporter_flag_gated(tmp_path):
    import paddle_tpu as paddle
    from paddle_tpu.observability import exporter as exp_mod
    assert exp_mod.maybe_start_exporter() is None   # flag empty: no thread
    path = str(tmp_path / "auto.jsonl")
    paddle.set_flags({"FLAGS_metrics_export_path": path,
                      "FLAGS_metrics_export_interval_s": 0.05})
    try:
        ex = exp_mod.maybe_start_exporter()
        assert ex is not None and ex.running
        assert exp_mod.maybe_start_exporter() is ex  # idempotent
    finally:
        paddle.set_flags({"FLAGS_metrics_export_path": "",
                          "FLAGS_metrics_export_interval_s": 10.0})
        exp_mod.stop_exporter()
    assert os.path.exists(path)
    json.loads(open(path).read().splitlines()[-1])


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_recorder_ring_is_bounded(tmp_path):
    fr = FlightRecorder(capacity=4)
    for i in range(10):
        fr.record("span", f"e{i}")
    evs = fr.events()
    assert len(evs) == 4
    assert evs[0]["name"] == "e6" and evs[-1]["name"] == "e9"
    out = fr.dump(path=str(tmp_path / "fr.json"), reason="test")
    data = json.load(open(out))
    assert data["reason"] == "test"
    assert [e["name"] for e in data["events"]] == ["e6", "e7", "e8", "e9"]
    assert "metrics" in data and "counters" in data["metrics"]
    # dual clocks on every event (ISSUE 19): wall time anchors the
    # event against other processes' dumps and trace spans, the
    # monotonic stamp gives drift-free in-process deltas
    for e in data["events"]:
        assert e["ts"] > 0 and e["mono"] > 0


def test_flight_recorder_disabled_is_noop(tmp_path):
    fr = FlightRecorder(capacity=0)
    fr.record("span", "x")
    assert fr.events() == []
    assert fr.dump(path=str(tmp_path / "no.json")) is None
    assert not os.path.exists(tmp_path / "no.json")


def test_record_event_feeds_flight_recorder():
    from paddle_tpu.profiler import RecordEvent
    from paddle_tpu.observability import flight_recorder as frmod
    rec = frmod.get_recorder()
    # a saturated ring (earlier serving tests emit a span per scheduler
    # tick) keeps a constant length as it evicts — assert on content,
    # not growth
    with RecordEvent("obsv::probe", args={"request_id": 42}):
        pass
    evs = rec.events()
    last = [e for e in evs if e["name"] == "obsv::probe"][-1]
    assert last["kind"] == "span" and last["request_id"] == 42


def _run_worker(mode, tmp_path, extra_env=None):
    dump = str(tmp_path / f"fr_{mode}.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               FLAGS_flight_recorder_path=dump,
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    env.update(extra_env or {})
    proc = subprocess.Popen(
        [sys.executable, os.path.join(os.path.dirname(__file__),
                                      "_flightrec_worker.py"), mode],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    return proc, dump


def test_flight_recorder_dumps_on_unhandled_exception(tmp_path):
    proc, dump = _run_worker("crash", tmp_path)
    out, _ = proc.communicate(timeout=120)
    assert proc.returncode != 0        # it really crashed
    assert os.path.exists(dump), out
    data = json.load(open(dump))
    assert data["reason"] == "exception"
    assert data["error"]["type"] == "RuntimeError"
    assert "synthetic training failure" in data["error"]["message"]
    assert any(e["kind"] == "step" for e in data["events"])
    assert data["metrics"]["counters"]


def test_flight_recorder_dumps_on_sigterm(tmp_path):
    proc, dump = _run_worker("sigterm", tmp_path)
    # wait for the worker to announce its loop is running
    line = proc.stdout.readline()
    assert "ready" in line, line
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=120)
    assert os.path.exists(dump), out
    data = json.load(open(dump))
    assert data["reason"] == "sigterm"
    assert any(e["kind"] == "preemption" for e in data["events"])
    assert any(e["kind"] == "step" for e in data["events"])
