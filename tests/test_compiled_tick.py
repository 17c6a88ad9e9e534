"""Compiled serving tick (ISSUE 13): one donated-buffer jit program per
scheduler iteration over device-resident state — bit-equality vs the
uncompiled scheduler across mixed workloads, flag-off byte-identity,
typed warn-once fallbacks, watchdog/drain semantics, and the shared
capture core factored out of framework/train_step.py."""
import time
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTForCausalLM, gpt_config
from paddle_tpu.serving import (
    DeadlineExceededError, Engine, SamplingParams, SchedulerStallError,
    ServingConfig, serving_stats,
)
from paddle_tpu.serving.compiled_tick import (
    CompiledServingTick, TickFallbackWarning,
)
from paddle_tpu.utils import flags as _flags


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPTForCausalLM(gpt_config(
        "gpt2-124m", num_layers=2, hidden_size=64, num_heads=2,
        vocab_size=256, max_seq_len=64))
    m.eval()
    return m


@pytest.fixture
def tick_flag():
    """Restore the tick/fused-sampling flags after each test."""
    saved = {k: _flags._FLAGS[k] for k in
             ("FLAGS_compiled_tick", "FLAGS_serving_fused_sampling")}
    yield _flags._FLAGS
    _flags._FLAGS.update(saved)


def _prompts(lens, seed=0, vocab=256):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (n,)).astype("int32") for n in lens]


def _ref_greedy(model, prompt, max_new, eos_token_id=None):
    ids = model.generate(paddle.to_tensor(prompt[None, :]),
                         max_new_tokens=max_new, temperature=0.0,
                         eos_token_id=eos_token_id)
    return np.asarray(ids._data_)[0, prompt.size:]


def _serve(model, subs, cfg=None, compiled=True, flags=None):
    """Run the engine with FLAGS_compiled_tick set to `compiled`;
    returns ([RequestOutput], stats snapshot, engine tick object)."""
    fl = flags if flags is not None else _flags._FLAGS
    saved = fl["FLAGS_compiled_tick"]
    fl["FLAGS_compiled_tick"] = compiled
    try:
        eng = Engine(model, cfg or ServingConfig(
            num_slots=2, max_queue=len(subs) + 1)).start()
        try:
            futs = [eng.submit(p, max_new_tokens=mn, sampling=sp,
                               eos_token_id=eos)
                    for p, mn, sp, eos in subs]
            outs = [f.result(timeout=300) for f in futs]
            snap = eng.stats()
            tick = eng._tick
        finally:
            eng.shutdown()
        return outs, snap, tick
    finally:
        fl["FLAGS_compiled_tick"] = saved


def test_mixed_workload_bit_equality(model, tick_flag):
    """Greedy, greedy+eos (slot refilled mid-flight), seeded-sampled,
    and seeded+penalty/top-k/top-p requests through 2 slots: the
    compiled tick's outputs are bit-identical to the uncompiled
    scheduler's, completion reasons included."""
    pa, pb, pc, pd, pe = _prompts([5, 9, 3, 7, 6], seed=7)
    eos = int(_ref_greedy(model, pb, 8)[1])   # pb finishes on eos @2
    subs = [
        (pa, 8, None, None),
        (pb, 8, None, eos),
        (pc, 8, SamplingParams(temperature=0.8, top_k=20, seed=3), None),
        (pd, 8, SamplingParams(temperature=1.0, top_p=0.9,
                               repetition_penalty=1.3, seed=5), None),
        (pe, 8, None, None),
    ]
    ref, snap_u, _ = _serve(model, subs, compiled=False)
    got, snap_c, _ = _serve(model, subs, compiled=True)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(r.output_ids, g.output_ids)
        assert r.finish_reason == g.finish_reason
    assert got[1].finish_reason == "eos"
    assert got[1].output_ids.size < 8         # refilled mid-flight
    assert snap_c["tick_compiled_hits"] > 0
    assert snap_c["tick_fallbacks"] == 0      # every request hostable
    assert snap_u["tick_compiled_hits"] == 0
    np.testing.assert_array_equal(got[0].output_ids,
                                  _ref_greedy(model, pa, 8))


def test_flag_off_is_tickless(model, tick_flag):
    """FLAGS_compiled_tick off: no tick object is built at all — the
    scheduler runs the historical per-call path (and with fused
    sampling off too, unseeded draws consume the global RNG exactly as
    before: same paddle.seed, same stream)."""
    (p,) = _prompts([5])
    tick_flag["FLAGS_serving_fused_sampling"] = False
    subs = [(p, 5, SamplingParams(temperature=0.9), None)]

    def run():
        paddle.seed(123)
        outs, snap, tick = _serve(model, subs, compiled=False)
        return outs[0].output_ids, snap, tick

    toks1, snap, tick = run()
    toks2, _, _ = run()
    assert tick is None
    np.testing.assert_array_equal(toks1, toks2)   # global-RNG stream
    assert snap["tick_compiled_hits"] == 0 and snap["tick_fallbacks"] == 0


def test_unseeded_sampling_typed_warn_once_fallback(model, tick_flag):
    """Non-greedy sampling without a seed cannot ride the vectorized
    in-program chain: the engine warns ONCE with the typed
    TickFallbackWarning and latches the uncompiled iteration."""
    pa, pb = _prompts([4, 6], seed=1)
    sp = SamplingParams(temperature=1.0)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        outs, snap, _ = _serve(
            model, [(pa, 6, sp, None), (pb, 6, sp, None)],
            compiled=True)
    tw = [x for x in w if issubclass(x.category, TickFallbackWarning)]
    assert len(tw) == 1, [str(x.message) for x in tw]
    assert "seed" in str(tw[0].message)
    assert snap["tick_compiled_hits"] == 0
    assert snap["tick_fallbacks"] > 0
    assert all(o.output_ids.size == 6 for o in outs)


def test_speculation_falls_back_typed(model, tick_flag):
    """Speculation-on latches the uncompiled scheduler with the typed
    warning; speculation_k=0 with a draft model configured does NOT
    (the tick runs)."""
    (p,) = _prompts([5])
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        outs, snap, _ = _serve(
            model, [(p, 4, None, None)],
            cfg=ServingConfig(num_slots=1, draft_model=model,
                              speculation_k=2),
            compiled=True)
    assert any(issubclass(x.category, TickFallbackWarning) and
               "speculative" in str(x.message) for x in w)
    np.testing.assert_array_equal(outs[0].output_ids,
                                  _ref_greedy(model, p, 4))

    # K=0: bitwise the plain loop — and the tick hosts it
    outs, snap, _ = _serve(
        model, [(p, 4, None, None)],
        cfg=ServingConfig(num_slots=1, draft_model=model,
                          speculation_k=0),
        compiled=True)
    assert snap["tick_compiled_hits"] > 0
    np.testing.assert_array_equal(outs[0].output_ids,
                                  _ref_greedy(model, p, 4))


def test_seeded_stream_reproducible_and_lane_independent(model,
                                                         tick_flag):
    """A seeded request's sampled stream is identical across engine
    runs AND across lanes (per-row host path, fused call, compiled
    tick); different seeds give different streams."""
    (p,) = _prompts([6], seed=9)
    sp7 = SamplingParams(temperature=0.9, top_k=50, seed=7)
    subs = [(p, 8, sp7, None)]
    a, _, _ = _serve(model, subs, compiled=True)
    b, _, _ = _serve(model, subs, compiled=True)
    c, _, _ = _serve(model, subs, compiled=False)
    np.testing.assert_array_equal(a[0].output_ids, b[0].output_ids)
    np.testing.assert_array_equal(a[0].output_ids, c[0].output_ids)
    d, _, _ = _serve(model, [(p, 8, SamplingParams(
        temperature=0.9, top_k=50, seed=8), None)], compiled=True)
    assert not np.array_equal(a[0].output_ids, d[0].output_ids)


def test_deadline_evict_under_compiled_tick(model, tick_flag):
    """Deadline enforcement keeps its per-token granularity on the
    compiled lane: an expired in-flight request is evicted (typed
    error, slot freed) and the survivor completes bit-equal."""
    pa, pb = _prompts([5, 4], seed=2)
    tick_flag["FLAGS_compiled_tick"] = True
    eng = Engine(model, ServingConfig(num_slots=2, max_queue=4)).start()
    try:
        f_slow = eng.submit(pa, max_new_tokens=50, deadline_s=0.12)
        f_ok = eng.submit(pb, max_new_tokens=5)
        with pytest.raises(DeadlineExceededError):
            f_slow.result(timeout=60)
        out = f_ok.result(timeout=60)
        snap = eng.stats()
    finally:
        eng.shutdown()
    np.testing.assert_array_equal(out.output_ids,
                                  _ref_greedy(model, pb, 5))
    assert snap["requests_evicted_deadline"] >= 1


def test_drain_completes_inflight_under_compiled_tick(model, tick_flag):
    """drain() semantics survive the compiled tick: in-flight slots run
    to completion, queued requests fail, admissions stop."""
    from paddle_tpu.serving import EngineShutdownError
    pa, pb, pc = _prompts([5, 6, 4], seed=4)
    tick_flag["FLAGS_compiled_tick"] = True
    eng = Engine(model, ServingConfig(num_slots=1, max_queue=8)).start()
    inflight = eng.submit(pa, max_new_tokens=30)
    t0 = time.monotonic()
    while serving_stats()["active_slots"] < 1 and \
            time.monotonic() - t0 < 30:
        time.sleep(0.005)
    queued = eng.submit(pb, max_new_tokens=5)
    eng.drain(deadline_s=60)
    out = inflight.result(timeout=5)
    assert out.output_ids.size == 30
    with pytest.raises(EngineShutdownError):
        queued.result(timeout=5)
    with pytest.raises(EngineShutdownError):
        eng.submit(pc)


def test_stall_watchdog_restarts_compiled_tick(model, tick_flag,
                                               monkeypatch):
    """A stalled compiled tick trips the PR 5 scheduler watchdog: the
    outstanding futures fail with SchedulerStallError, the loop
    restarts with a FRESH tick (the donated pools may be torn), and the
    engine serves again — scheduler_restarts/stalls counted."""
    (p,) = _prompts([5], seed=6)
    # warm the persistent compile cache for this tick program first: a
    # cold first compile inside the watchdog's budget would read as a
    # stall of its own and churn the restart budget
    _serve(model, [(p, 2, None, None)],
           cfg=ServingConfig(num_slots=1), compiled=True)
    orig = CompiledServingTick._run
    state = {"calls": 0}

    def stalling_run(self):
        state["calls"] += 1
        if state["calls"] == 2:
            t0 = time.monotonic()
            while time.monotonic() - t0 < 60.0:
                time.sleep(0.01)     # interruptible by async-raise
        return orig(self)

    monkeypatch.setattr(CompiledServingTick, "_run", stalling_run)
    tick_flag["FLAGS_compiled_tick"] = True
    # budget must sit between the rebuilt tick's (cache-served)
    # recompile time and the injected stall
    eng = Engine(model, ServingConfig(
        num_slots=1, step_timeout_s=6.0,
        max_scheduler_restarts=2)).start()
    try:
        tick0 = eng._tick
        f = eng.submit(p, max_new_tokens=4)
        exc = f.exception(timeout=30)
        assert isinstance(exc, SchedulerStallError), exc
        out = eng.generate(p, max_new_tokens=4, timeout=60)
        snap = eng.stats()
        assert eng._tick is not tick0        # rebuilt on restart
    finally:
        eng.shutdown()
    np.testing.assert_array_equal(out.output_ids,
                                  _ref_greedy(model, p, 4))
    assert snap["scheduler_stalls"] >= 1
    assert snap["scheduler_restarts"] >= 1


def test_pool_gauge_throttle_converges(model, tick_flag):
    """The throttled pool-gauge publisher (ISSUE 13 satellite) still
    converges: after the engine quiesces, the gauges reflect the true
    pool state (every page back, peak recorded) even though steady
    ticks skipped the registry lock."""
    prompts = _prompts([5, 7, 4, 6], seed=8)
    tick_flag["FLAGS_compiled_tick"] = True
    eng = Engine(model, ServingConfig(
        num_slots=2, max_queue=5, enable_prefix_cache=False)).start()
    futs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    outs = [f.result(timeout=300) for f in futs]
    eng.shutdown()          # loop exit force-flushes the pool gauges
    snap = serving_stats()
    assert all(o.output_ids.size == 6 for o in outs)
    assert snap["kv_pages_peak"] > 0
    # quiesced engine: every page back in the pool, gauges converged
    # despite steady-state ticks skipping the registry lock
    assert snap["kv_pages_in_use"] == 0
    assert snap["kv_pages_free"] == eng.cache.usable_pages


def test_tick_metrics_in_snapshot_and_prometheus(model, tick_flag):
    """serving.tick_ms / tick.compiled_hits / tick.fallbacks land in
    serving_stats() and the Prometheus exposition (schema the
    check_telemetry --serving-tick gate enforces)."""
    import paddle_tpu.observability as obs
    (p,) = _prompts([5])
    _, snap, _ = _serve(model, [(p, 4, None, None)], compiled=True)
    assert snap["tick_ms_avg"] is not None and snap["tick_ms_avg"] > 0
    assert snap["tick_compiled_hits"] > 0
    assert snap["tick_fallbacks"] == 0
    text = obs.render_prometheus()
    assert "serving_tick_ms_bucket" in text
    assert "serving_tick_compiled_hits" in text
    assert "serving_tick_fallbacks" in text
    from tools.check_telemetry import (check_serving_tick_exposition,
                                       parse_prometheus)
    series, typed, errors = parse_prometheus(text)
    assert not errors
    assert check_serving_tick_exposition(series, typed) == []


def test_capture_core_shared_with_train_step():
    """The two-phase capture/replay machinery is ONE implementation:
    train_step's historical names alias framework/capture.py, and
    run_discovery captures reads + rolls back side effects."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.framework import capture, train_step
    assert train_step._StepBindTracer is capture.BindTracer
    assert train_step._Installed is capture.Installed
    assert train_step.TraceEscape is capture.TraceEscape

    pre = Tensor(np.ones(3, np.float32))
    counter = Tensor(np.zeros((), np.float32))

    def body():
        from paddle_tpu.tensor_ops import math as M
        counter._data = counter._data + 1.0      # write: rolled back
        return M.add(Tensor(np.ones(3, np.float32)), pre)  # read: captured

    disc = capture.run_discovery(body)
    assert any(t is pre for t in disc.capture_list)
    assert not disc.uses_rng
    assert float(np.asarray(counter._data_)) == 0.0   # rollback

    def hostly():
        return float(np.asarray(pre.numpy()).sum())

    with pytest.raises(capture.TraceEscape):
        capture.run_discovery(hostly)


def test_concurrent_engines_share_one_model(model, tick_flag):
    """Thread-mode fleets host several engines over ONE model object:
    while one engine's tick program traces (tracers swapped into the
    shared parameters), the other engines' eager prefills/decodes must
    not observe them — the process-wide capture TRACE_LOCK serializes
    the window.  Both engines' greedy outputs stay bit-equal to the
    sequential reference."""
    prompts = _prompts([5, 7, 4, 6], seed=21)
    refs = [_ref_greedy(model, p, 6) for p in prompts]
    tick_flag["FLAGS_compiled_tick"] = True
    engines = [Engine(model, ServingConfig(num_slots=2,
                                           max_queue=8)).start()
               for _ in range(2)]
    try:
        # submit to BOTH immediately: engine 0's first tick traces
        # while engine 1 is mid-prefill/decode on the same parameters
        futs = [(e, e.submit(p, max_new_tokens=6))
                for p in prompts for e in engines]
        outs = [(e, f.result(timeout=300)) for e, f in futs]
    finally:
        for e in engines:
            e.shutdown()
    for (e, o), ref in zip(outs, [r for r in refs for _ in engines]):
        np.testing.assert_array_equal(o.output_ids, ref)


def test_fused_sampling_flag_off_keeps_per_row_path(model, tick_flag):
    """FLAGS_serving_fused_sampling off: seeded requests go back to the
    historical per-row scheduler-thread RNG draw — the stream ignores
    the request seed (a DIFFERENT request seed gives the same tokens,
    unlike the seeded lane where streams are seed-derived)."""
    (p,) = _prompts([5], seed=12)
    tick_flag["FLAGS_serving_fused_sampling"] = False

    def run(request_seed):
        outs, _, _ = _serve(
            model, [(p, 6, SamplingParams(temperature=0.9,
                                          seed=request_seed), None)],
            compiled=False)
        return outs[0].output_ids

    a, b = run(7), run(8)
    # historical path: the scheduler thread's own RNG drives the draw,
    # so changing the request seed changes nothing...
    np.testing.assert_array_equal(a, b)
    # ...while the fused lane derives the stream from the request seed
    tick_flag["FLAGS_serving_fused_sampling"] = True
    c, d = run(7), run(8)
    assert not np.array_equal(c, d)
    assert not np.array_equal(a, c)


# ---------------------------------------------------------------------------
# one tick in flight (ISSUE 31): tick n+1 is launched before tick n's
# finish codes are read
# ---------------------------------------------------------------------------

def _sampled(seed):
    return SamplingParams(temperature=1.0, seed=seed)


def _fresh_at(stream, lo):
    """(k, token) of the first position >= ``lo`` whose token has not
    occurred before it in ``stream``: as an eos id it ends the request
    exactly there."""
    for k in range(lo, stream.size):
        if stream[k] not in stream[:k]:
            return k, int(stream[k])
    raise AssertionError(f"no fresh token from {lo} on in {stream}")


def _serve_together(model, subs, compiled, num_slots=2):
    """``subs`` submitted under the engine's lock, so that one admission
    round takes the first ``num_slots`` of them together; returns
    ([RequestOutput], stats after the stop, the stopped engine, the pool
    as it was at the start)."""
    saved = _flags._FLAGS["FLAGS_compiled_tick"]
    _flags._FLAGS["FLAGS_compiled_tick"] = compiled
    try:
        eng = Engine(model, ServingConfig(
            num_slots=num_slots, max_queue=len(subs) + 1,
            enable_prefix_cache=False)).start()
        try:
            start = (eng.cache.free_page_count, eng.cache.free_slots,
                     eng.cache.offsets.copy())
            with eng._work:
                futs = [eng.submit(p, max_new_tokens=mn, sampling=sp,
                                   eos_token_id=eos)
                        for p, mn, sp, eos in subs]
            outs = [f.result(timeout=300) for f in futs]
        finally:
            eng.shutdown()          # a clean stop reads the tick in flight
        return outs, serving_stats(), eng, start
    finally:
        _flags._FLAGS["FLAGS_compiled_tick"] = saved


def test_eos_mid_stream_while_other_rows_decode_on(model, tick_flag):
    """A request that ends by eos while its neighbour decodes on: the
    tick after its last was launched before the host knew, and ran its
    row dead.  Tokens, finish reason and token count are the eager
    lane's; no token of the dead tick is delivered or counted."""
    pa, pb = _prompts([6, 9], seed=31)
    free, _, _, _ = _serve_together(
        model, [(pa, 24, _sampled(5), None)], compiled=False)
    k, eos = _fresh_at(free[0].output_ids, 4)
    subs = [(pa, 24, _sampled(5), eos), (pb, 24, _sampled(6), None)]
    ref, snap_u, _, _ = _serve_together(model, subs, compiled=False)
    got, snap_c, eng, _ = _serve_together(model, subs, compiled=True)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(r.output_ids, g.output_ids)
        assert r.finish_reason == g.finish_reason
    assert got[0].finish_reason == "eos"
    assert got[0].output_ids.size == k + 1 < 24 == got[1].output_ids.size
    total = sum(o.output_ids.size for o in got)
    assert snap_c["tokens_generated"] == snap_u["tokens_generated"] == total
    assert snap_c["tick_fallbacks"] == 0 and snap_c["tick_overlapped"] > 0
    assert eng._tick._pending is None


def test_length_and_eos_in_one_tick_then_refills_leave_the_pool_whole(
        model, tick_flag):
    """A length finish (known to the host before the launch: its row is
    left out of growth) and an eos finish (not known: its row rides one
    tick dead, its mirrors one step ahead) in the SAME tick, the slots
    refilled until five requests are done: every output is the eager
    lane's, and pages, slots and the host offset mirror are back where
    they started."""
    pa, pb, pc, pd, pe = _prompts([5, 9, 7, 6, 4], seed=32)
    free, _, _, _ = _serve_together(
        model, [(pb, 24, _sampled(3), None), (pd, 24, _sampled(4), None)],
        compiled=False)
    kb, eos_b = _fresh_at(free[0].output_ids, 3)
    _, eos_d = _fresh_at(free[1].output_ids, 6)
    subs = [(pa, kb + 1, None, None),           # length, in b's eos tick
            (pb, 24, _sampled(3), eos_b),
            (pc, 9, None, None),
            (pd, 24, _sampled(4), eos_d),
            (pe, 5, _sampled(8), None)]
    ref, _, _, _ = _serve_together(model, subs, compiled=False)
    got, snap, eng, start = _serve_together(model, subs, compiled=True)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(r.output_ids, g.output_ids)
        assert r.finish_reason == g.finish_reason
    assert [g.finish_reason for g in got] == \
        ["length", "eos", "length", "eos", "length"]
    assert got[0].output_ids.size == got[1].output_ids.size == kb + 1
    assert snap["tick_fallbacks"] == 0
    free_pages, free_slots, offsets = start
    assert eng.cache.free_page_count == free_pages == eng.cache.usable_pages
    assert eng.cache.available_pages == free_pages   # no reservation left
    assert eng.cache.free_slots == free_slots == 2
    np.testing.assert_array_equal(eng.cache.offsets, offsets)
    assert eng._tick._pending is None


def test_overlap_and_drain_counters(model, tick_flag):
    """Three long answers one after the other through ONE slot, two
    ended by eos and one by length: every tick but a request's first is
    launched over an unread one, and each request's end — the one
    mutation of its life that meets a tick in flight — collects that
    tick with no launch over it, once."""
    (p,) = _prompts([6], seed=33)
    free, _, _, _ = _serve_together(
        model, [(p, 40, _sampled(9), None)], compiled=False, num_slots=1)
    k, eos = _fresh_at(free[0].output_ids, 30)
    tick_flag["FLAGS_compiled_tick"] = True
    eng = Engine(model, ServingConfig(num_slots=1)).start()
    try:
        outs = [eng.generate(p, max_new_tokens=40, sampling=_sampled(9),
                             eos_token_id=e, timeout=300)
                for e in (eos, None, eos)]
    finally:
        eng.shutdown()
    snap = serving_stats()
    assert [o.finish_reason for o in outs] == ["eos", "length", "eos"]
    assert [o.output_ids.size for o in outs] == [k + 1, 40, k + 1]
    # a request of T tokens: T - 1 live ticks, the first launched over
    # nothing; an eos costs one more tick, run dead over the unread last
    assert snap["tick_compiled_hits"] == 2 * (k + 1) + 39
    assert snap["tick_overlapped"] == 2 * k + 38
    assert snap["tick_drains"] == 3
    assert snap["tick_overlap_share"] >= 0.8
    assert snap["tick_fallbacks"] == 0
    assert snap["tokens_generated"] == sum(o.output_ids.size for o in outs)


@pytest.mark.parametrize("sampling", [
    None, SamplingParams(temperature=1.0, top_k=20, seed=5,
                         repetition_penalty=1.3)],
    ids=["greedy", "seeded-penalty"])
def test_every_tick_hands_its_tokens_to_the_host(model, tick_flag, sampling):
    """The tick returns its own tokens beside ``fin`` and the host
    appends them at the tick's delivery, so a live request's token list
    grows tick by tick, not at an admission's or a completion's flush —
    and the served tokens are the uncompiled scheduler's."""
    prompts = _prompts([9, 14], seed=21)
    subs = [(p, 24, sampling, None) for p in prompts]
    plain, _, _ = _serve(model, subs, compiled=False, flags=tick_flag)
    cfg = ServingConfig(num_slots=2, max_queue=4)
    held = []
    tick_flag["FLAGS_compiled_tick"] = True
    eng = Engine(model, cfg).start()
    try:
        deliver = eng._tick._deliver

        def spy(tick, rows, ending):
            held.append(sum(len(r.tokens) for r in rows.values()))
            deliver(tick, rows, ending)

        eng._tick._deliver = spy
        with eng._work:
            futs = [eng.submit(p, max_new_tokens=24, sampling=sampling)
                    for p in prompts]
        outs = [f.result(timeout=300) for f in futs]
        snap = eng.stats()
    finally:
        eng.shutdown()
    for a, b in zip(plain, outs):
        np.testing.assert_array_equal(a.output_ids, b.output_ids)
    assert snap["tick_fallbacks"] == 0
    # both rows decode side by side: each delivered tick found two tokens
    # more on the requests' lists than the one before it
    steps = np.diff(held)
    assert len(held) >= 20 and steps.max() == 2 and (steps > 0).all()


# the families' tick programs, pinned (ISSUE 34) ----------------------------
_PINNED = {
    # family: (greedy tick, one-row prefill member): sha256[:16] of the
    # StableHLO of a tiny engine's programs, as PR 33's tree traced them
    "llama": ("dca425a4f34f1eae", "a0a8ad78c7ff9b02"),
    "cohere_moe": ("fdad0e74642cca58", "3bfb9711f5616c51"),
    "granite_hybrid": ("6e97f6d411d56c2d", "85229164b3cdb108"),
}

_HASH_SCRIPT = r"""
import hashlib, json, sys
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.serving import Engine, ServingConfig

def digest(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16]

def programs(model, vocab):
    model.eval()
    eng = Engine(model, ServingConfig(
        num_slots=2, max_seq_len=64, page_size=4, prefill_chunk_tokens=8,
        enable_prefix_cache=False)).start()
    ids = np.random.default_rng(0).integers(0, vocab, (13,)).astype("int32")
    eng.generate(ids, max_new_tokens=6)
    out = [digest(eng._tick.lowered_text("greedy")),
           digest(eng._tick.lowered_text("prefill_r1"))]
    eng.shutdown()
    return out

paddle.seed(0)
from paddle_tpu.models import LlamaForCausalLM, llama_config
from paddle_tpu.models.cohere_moe import (
    TINY_COHERE_MOE, CohereMoeConfig, CohereMoeForCausalLM)
from paddle_tpu.models.granite_hybrid import (
    TINY_GRANITE_HYBRID, GraniteHybridConfig, GraniteHybridForCausalLM)
print(json.dumps({
    "llama": programs(LlamaForCausalLM(llama_config(
        "tiny", vocab_size=256, max_seq_len=64)), 256),
    "cohere_moe": programs(CohereMoeForCausalLM(
        CohereMoeConfig(**TINY_COHERE_MOE)), 256),
    "granite_hybrid": programs(GraniteHybridForCausalLM(
        GraniteHybridConfig(**TINY_GRANITE_HYBRID)),
        TINY_GRANITE_HYBRID["vocab_size"])}))
"""


@pytest.fixture(scope="module")
def family_program_hashes():
    """The three families' tick and prefill programs, hashed in a fresh
    process (names and counters of this process's earlier traces stay out
    of the text)."""
    import json
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root)
    env.pop("PADDLE_TPU_PALLAS_INTERPRET", None)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", _HASH_SCRIPT], cwd=root,
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("family", sorted(_PINNED))
def test_a_familys_tick_program_is_the_one_pinned(family,
                                                  family_program_hashes):
    """A change to code the families share (the routing call's new
    arguments, the blocked chunk read's shared helper, the cache's
    per-kind page shapes: ISSUE 34) must leave a family that uses none
    of it the program it traced before — or say which changed and why,
    and re-pin."""
    assert tuple(family_program_hashes[family]) == _PINNED[family]
