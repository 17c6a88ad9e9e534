"""The one span primitive (``observability.tracing.span``) and the seams
that use it: the compiled train step, the scheduler iteration, the
compiled tick, the prefill chunk call, admission and compiles.  What a
span writes with tracing off, what it adds with ``FLAGS_trace_dir`` set,
that the phases reach a ``jax.profiler`` trace under their names, and
that the counters beside them count what PERF.md says they count."""
import glob
import os
import time

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTForCausalLM, gpt_config
from paddle_tpu.observability import flight_recorder, tracing
from paddle_tpu.observability.tracing import span
from paddle_tpu.serving import Engine, ServingConfig
from paddle_tpu.utils import monitor
from paddle_tpu.utils.flags import set_flags


def _delta(before, after):
    return {k: after[k] - before.get(k, 0) for k in after
            if isinstance(after[k], (int, float))}


def _host_event_names(trace_dir):
    """Names of the host-plane events of the one xplane under a
    ``jax.profiler`` trace directory."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return {e.name for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events}


@pytest.fixture()
def trace_dir(tmp_path):
    d = str(tmp_path / "traces")
    tracing.reset()
    set_flags({"FLAGS_trace_dir": d,
               "FLAGS_trace_latency_threshold_ms": 0.0})   # keep all
    yield d
    set_flags({"FLAGS_trace_dir": "",
               "FLAGS_trace_latency_threshold_ms": 250.0})
    tracing.reset()


# ---------------------------------------------------------------- span

def test_span_off_observes_histogram_and_leaves_the_ring_empty():
    tracing.reset()
    before = monitor.all_stats()
    with span("unit.phase") as sp:
        time.sleep(0.002)
    with span("unit.other", hist="unit.kept_name_ms"):
        pass
    d = _delta(before, monitor.all_stats())
    assert d["unit.phase_ms.count"] == 1
    assert d["unit.phase_ms.sum"] == pytest.approx(sp.ms) and sp.ms >= 2.0
    assert d["unit.kept_name_ms.count"] == 1
    assert "unit.other_ms.count" not in d
    assert not tracing._phases and not tracing._buffer


def test_span_on_records_phase_with_parent_and_request_ids(trace_dir):
    with span("unit.outer", request_ids=[3, 5]) as outer:
        with span("unit.inner"):
            pass
    inner_rec, outer_rec = list(tracing._phases)
    assert (inner_rec["name"], outer_rec["name"]) == \
        ("unit.inner", "unit.outer")
    for rec in (inner_rec, outer_rec):
        assert rec["kind"] == "phase" and rec["trace"].endswith("-phases")
        assert rec["t0"] <= rec["t1"]
        assert abs(rec["wall"] - time.time()) < 60
    assert inner_rec["parent"] == outer_rec["span"]
    assert outer_rec["parent"] is None
    assert outer_rec["attrs"]["request_ids"] == [3, 5]
    assert outer.ms >= 0
    # request spans and phases do not share a ring
    assert not tracing._buffer


def test_phases_are_spooled_and_merged_beside_the_traces(trace_dir,
                                                         tmp_path):
    root = tracing.start_span("req")
    with span("unit.tick"):
        pass
    root.end()
    tracing.decide(root.ctx.trace_id, latency_ms=1.0)
    tracing.spool_now(trace_dir)
    merged = tracing.merge_spools(trace_dir)
    (tr,) = merged["traces"]                 # the phase made no trace
    assert [s["name"] for s in tr["spans"]] == ["req"]
    assert [p["name"] for p in merged["phases"]] == ["unit.tick"]
    events, _ = tracing.chrome_events(merged)
    (ph,) = [e for e in events if e["cat"] == "phase"]
    assert ph["name"] == "unit.tick" and ph["tid"] == 2


def test_span_records_the_exception_and_lets_it_through(trace_dir):
    with pytest.raises(KeyError):
        with span("unit.raises"):
            raise KeyError("x")
    (rec,) = tracing._phases
    assert rec["status"] == "KeyError"
    assert tracing._tls.phases == []


def test_record_event_is_a_span(tmp_path):
    """``RecordEvent`` keeps its sinks — flight recorder, a recording
    ``Profiler``'s host buffer — and gains the span's: the histogram and
    the xplane of a running ``jax.profiler`` session."""
    from paddle_tpu.profiler import Profiler, ProfilerTarget, RecordEvent
    before = monitor.all_stats()
    prof = Profiler(targets=[ProfilerTarget.CPU], timer_only=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with prof:
            ev = RecordEvent("unit::user", args={"request_id": 9}).begin()
            ev.end()
            ev.end()                        # a second end is a no-op
    finally:
        jax.profiler.stop_trace()
    (host,) = [e for e in prof.events if e["name"] == "unit::user"]
    assert host["args"]["request_id"] == 9 and host["cat"] == "UserDefined"
    last = [e for e in flight_recorder.get_recorder().events()
            if e["name"] == "unit::user"][-1]
    assert last["kind"] == "span" and last["request_id"] == 9
    assert _delta(before, monitor.all_stats())["unit::user_ms.count"] == 1
    assert "unit::user" in _host_event_names(str(tmp_path))


# ------------------------------------------------------ compiled train step

@pytest.fixture(scope="module")
def train_run(tmp_path_factory):
    """Five calls of a tiny CompiledTrainStep under a jax.profiler trace:
    (host event names, registry delta, the step object)."""
    from paddle_tpu.framework.train_step import CompiledTrainStep
    paddle.seed(0)
    w = paddle.Parameter(np.ones((8,), np.float32))
    opt = paddle.optimizer.AdamW(0.05, parameters=[w])

    def forward(x, y):
        return ((w * x - y) ** 2).mean()

    cs = CompiledTrainStep(forward, opt)
    x = paddle.to_tensor(np.ones(8, np.float32))
    y = paddle.to_tensor(np.zeros(8, np.float32))
    d = str(tmp_path_factory.mktemp("train_trace"))
    before = monitor.all_stats()
    jax.profiler.start_trace(d)
    try:
        for _ in range(5):
            float(np.asarray(cs(x, y)._data_))
    finally:
        jax.profiler.stop_trace()
    return _host_event_names(d), _delta(before, monitor.all_stats()), cs


def test_train_step_phases_reach_the_profiler_trace(train_run):
    names, reg, cs = train_run
    assert cs.compiled
    assert {"train.step", "train.step.gather", "train.step.launch",
            "train.step.adopt"} <= names
    assert reg["train.step_ms.count"] == 5
    # call 1 is the eager warm-up: four compiled calls have children
    for child in ("gather", "launch", "adopt"):
        assert reg[f"train.step.{child}_ms.count"] == 4
    assert reg["train.step.launch_ms.sum"] <= reg["train.step_ms.sum"]


def test_jitted_bodies_carry_their_names(train_run):
    _, _, cs = train_run
    assert cs._jit_full.__name__ == "train_step"
    x = paddle.to_tensor(np.ones(8, np.float32))
    micro = cs._build_jit(False, cs._gather_args(x, x))
    assert micro.__name__ == "train_micro_step"


def test_compile_ms_counts_a_first_call_and_not_a_second():
    from paddle_tpu.core.op_cache import ensure_compile_cache
    ensure_compile_cache()

    @jax.jit
    def unit_compile_probe(a):
        return a * 3 + 1

    key = "jit.compile_ms.count"
    n0 = monitor.all_stats()[key]
    unit_compile_probe(np.ones(3, np.float32)).block_until_ready()
    n1 = monitor.all_stats()[key]
    unit_compile_probe(np.ones(3, np.float32)).block_until_ready()
    n2 = monitor.all_stats()[key]
    assert n1 == n0 + 1 and n2 == n1
    assert monitor.all_stats()["jit.compile_ms.sum"] > 0


def test_dispatch_count_counts_eager_ops():
    from paddle_tpu.core.op_cache import dispatch_count
    a = paddle.to_tensor(np.ones((4, 4), np.float32))
    (a @ a).numpy()                         # warm: a miss or a hit
    n0 = dispatch_count()
    for _ in range(3):
        b = a @ a
    b.numpy()
    assert dispatch_count() - n0 == 3


# ------------------------------------------------------------ serving

@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPTForCausalLM(gpt_config(
        "gpt2-124m", num_layers=2, hidden_size=128, num_heads=4,
        vocab_size=512, max_seq_len=64))
    m.eval()
    return m


def _prompts(lens, seed=0, vocab=512):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (n,)).astype("int32") for n in lens]


@pytest.fixture(scope="module")
def serve_run(model, tmp_path_factory):
    """Two requests (prompts of 5 and 21 tokens, chunk 16) through a
    4-slot paged engine under a jax.profiler trace: (host event names,
    registry delta, the engine's compiled tick)."""
    d = str(tmp_path_factory.mktemp("serve_trace"))
    cfg = ServingConfig(num_slots=4, page_size=4,
                        prefill_chunk_tokens=16)
    with Engine(model, cfg) as eng:         # start() resets serving.*
        before = monitor.all_stats()
        jax.profiler.start_trace(d)
        try:
            futs = [eng.submit(p, max_new_tokens=6)
                    for p in _prompts([5, 21])]
            for f in futs:
                f.result(timeout=300)
        finally:
            jax.profiler.stop_trace()
        reg = _delta(before, monitor.all_stats())
        tick = eng._tick
    return _host_event_names(d), reg, tick


def test_serving_phases_reach_the_profiler_trace(serve_run):
    names, reg, _ = serve_run
    assert {"serving.admit", "serving.iteration", "serving.prefill_round",
            "serving.prefill_chunk", "serving.prefill.view",
            "serving.prefill.model", "serving.prefill.absorb",
            "serving.prefill.collect",
            "serving.tick", "serving.tick.build", "serving.tick.launch",
            "serving.tick.sync", "serving.tick.deliver",
            "serving.tick.rebuild", "serving.publish"} <= names
    # the re-upload of the scheduler state happens at mutations only
    assert 0 < reg["serving.tick.rebuild_ms.count"] \
        < reg["serving.tick.build_ms.count"]
    assert reg["serving.tick.compiled_hits"] > 0
    assert reg["serving.tick.fallbacks"] == 0


def test_tick_host_time_is_the_tick_less_its_sync(model, trace_dir):
    cfg = ServingConfig(num_slots=4, page_size=4,
                        prefill_chunk_tokens=16)
    with Engine(model, cfg) as eng:         # start() resets serving.*
        eng.submit(_prompts([5])[0], max_new_tokens=2).result(timeout=300)
        tracing.reset()                     # the programs are compiled
        before = monitor.all_stats()
        futs = [eng.submit(p, max_new_tokens=n)
                for p, n in zip(_prompts([5, 21]), (24, 12))]
        for f in futs:
            f.result(timeout=300)
        reg = _delta(before, monitor.all_stats())
    n = reg["serving.tick.host_ms.count"]
    assert n == reg["serving.decode_ms.count"] == \
        reg["serving.tick.sync_ms.count"] > 0
    by_name = {}
    for ph in tracing.merge_spools(trace_dir)["phases"]:
        by_name.setdefault(ph["name"], []).append(ph)
    # a tick's host time is its launching call (its ``serving.tick``)
    # less the collection of the tick before it, which that call holds
    # -- the wait for the device and that tick's deliveries -- plus its
    # own deliveries, wherever it is collected.  The phase records say
    # which collections ran under a launch and which were drains.
    tick_ids = {t["span"] for t in by_name["serving.tick"]}
    held = {k: sum((p["t1"] - p["t0"]) * 1e3
                   for p in by_name[f"serving.tick.{k}"]
                   if p["parent"] in tick_ids)
            for k in ("sync", "deliver")}
    host, decode, sync, deliver = (
        reg[f"serving.{k}_ms.sum"] for k in
        ("tick.host", "decode", "tick.sync", "tick.deliver"))
    assert host == pytest.approx(
        decode - held["sync"] - held["deliver"] + deliver, rel=1e-6)
    # and the identity has teeth: most ticks were read under a launch,
    # and a host time that kept their waits would be off by them
    assert reg["serving.tick.overlapped"] >= 0.8 * n
    assert 0 < held["sync"] <= sync
    assert held["sync"] > 1e3 * 1e-6 * host
    # the old histograms keep their names: an iteration holds its tick
    assert reg["serving.tick_ms.sum"] >= reg["serving.decode_ms.sum"]


def test_prefill_token_counters_are_exact_on_a_two_request_plan(serve_run):
    _, reg, _ = serve_run
    chunks = reg["serving.prefill_chunk_ms.count"]
    # both admitted together (a call of 2 rows, then one of 1) or one
    # after the other (three calls of 1 row): 3 rows of 16 tokens either
    # way, one program a call; the prompts' 5 + 21 tokens are all that
    # was new
    assert chunks in (2, 3)
    assert reg["serving.prefill.tokens_computed"] == 3 * 16
    assert reg["serving.prefill.tokens_useful"] == 5 + 21
    assert reg["serving.prefill.launches"] == chunks
    assert reg["serving.prefill.compiled_hits"] == chunks
    assert reg["serving.prefill.fallbacks"] == 0
    assert reg["serving.prefill_ms.count"] == chunks


def test_page_ticks_in_use_never_pass_reserved(serve_run):
    _, reg, _ = serve_run
    assert 0 < reg["serving.kv.page_ticks_in_use"] \
        <= reg["serving.kv.page_ticks_reserved"]


def test_tick_programs_carry_their_names(serve_run):
    _, _, tick = serve_run
    assert {j.__name__ for j in tick._jits.values()} == \
        {"serving_tick_greedy", "serving_prefill_r1", "serving_prefill_r2",
         "serving_prefill_r4"}
    assert tick._build_jit("mixed", False).__name__ == "serving_tick_mixed"


def test_queue_request_ms_is_zero_until_a_request_waits(model):
    cfg = ServingConfig(num_slots=1, page_size=4,
                        prefill_chunk_tokens=16)
    (p,) = _prompts([5])
    with Engine(model, cfg) as eng:
        eng.submit(p, max_new_tokens=4).result(timeout=300)
        alone = monitor.all_stats()
        assert alone["serving.queue.request_ms"] == 0
        assert alone["serving.queue_wait_ms.count"] == 1
        # the one slot is taken: the second request waits in the queue
        futs = [eng.submit(p, max_new_tokens=8) for _ in range(2)]
        for f in futs:
            f.result(timeout=300)
        crowded = monitor.all_stats()
    assert crowded["serving.queue.request_ms"] > 0
    assert crowded["serving.queue_wait_ms.count"] == 3
    # the second waited for the first's eight tokens
    assert crowded["serving.queue_wait_ms.sum"] \
        >= crowded["serving.queue.request_ms"] * 0.5


def test_serving_phase_spans_carry_request_ids_when_armed(model,
                                                          trace_dir):
    cfg = ServingConfig(num_slots=2, page_size=4,
                        prefill_chunk_tokens=16)
    (p,) = _prompts([5])
    with Engine(model, cfg) as eng:
        fut = eng.submit(p, max_new_tokens=3)
        fut.result(timeout=300)
        rid = fut.request_id
    merged = tracing.merge_spools(trace_dir)    # shutdown spooled
    by_name = {}
    for ph in merged["phases"]:
        by_name.setdefault(ph["name"], []).append(ph)
    assert by_name["serving.tick"][0]["attrs"]["request_ids"] == [rid]
    assert by_name["serving.prefill_chunk"][0]["attrs"]["request_ids"] \
        == [rid]
    # two ticks for three tokens: the first is read under the second's
    # launch (its ``serving.tick``), the second is drained, with nothing
    # left to launch, straight under its iteration
    tick_ids = {t["span"] for t in by_name["serving.tick"]}
    iter_ids = {t["span"] for t in by_name["serving.iteration"]}
    parents = [s["parent"] for s in by_name["serving.tick.sync"]]
    assert len(parents) == len(tick_ids) == 2
    assert parents[0] in tick_ids and parents[1] in iter_ids
    # the request's own trace is whole and alone
    (tr,) = merged["traces"]
    assert tr["decision_count"] == 1
