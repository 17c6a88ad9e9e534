"""Continuous-batching serving engine (paddle_tpu/serving/): slot KV
caches, admission control, deadlines, stats, clean shutdown."""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTForCausalLM, gpt_config
from paddle_tpu.serving import (
    DeadlineExceededError, Engine, EngineShutdownError, QueueFullError,
    SamplingParams, ServingConfig, serving_stats,
)


def _np(t):
    return np.asarray(t._data_)


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


def _ref_greedy(model, prompt, max_new, eos_token_id=None):
    ids = model.generate(paddle.to_tensor(prompt[None, :]),
                         max_new_tokens=max_new, temperature=0.0,
                         eos_token_id=eos_token_id)
    return _np(ids)[0, prompt.size:]


def _wait_active(eng, n, timeout=60.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if serving_stats()["active_slots"] >= n:
            return
        time.sleep(0.01)
    raise AssertionError(f"engine never reached {n} active slot(s)")


def test_mixed_age_slots_match_sequential_greedy(model):
    """Five requests of different prompt lengths through 2 slots: every
    multi-tenant decode result must equal the per-request generate()
    greedy output, and the stats snapshot must be coherent."""
    prompts = _prompts([5, 9, 3, 7, 6])
    with Engine(model, ServingConfig(num_slots=2)) as eng:
        futs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        outs = [f.result(timeout=300) for f in futs]
        snap = eng.stats()
    for p, o in zip(prompts, outs):
        np.testing.assert_array_equal(o.output_ids, _ref_greedy(model, p, 6))
        assert o.finish_reason == "length"
        assert o.ttft_ms > 0 and o.latency_ms >= o.ttft_ms
        np.testing.assert_array_equal(
            o.ids, np.concatenate([p, o.output_ids]))
    assert snap["requests_submitted"] == 5
    assert snap["requests_completed"] == 5
    assert snap["tokens_generated"] == 30
    assert snap["prefill_steps"] == 5
    # 5 requests x 5 post-prefill tokens over 2 slots needs >= 13 steps
    assert snap["decode_steps"] >= 13
    assert 0.0 < snap["slot_occupancy"] <= 1.0
    assert snap["ttft_ms_avg"] > 0 and snap["per_token_ms_avg"] > 0
    assert snap["tokens_per_sec"] > 0


def test_eos_slot_refill_mid_flight(model):
    """A request finishing on EOS frees its slot, which is refilled by a
    queued request WITHOUT draining the still-running batch."""
    pa, pb, pc = _prompts([5, 9, 3], seed=7)
    # eos := the 3rd greedy token of pa, so pa finishes a few steps in
    eos = int(_ref_greedy(model, pa, 3)[-1])
    with Engine(model, ServingConfig(num_slots=2)) as eng:
        fa = eng.submit(pa, max_new_tokens=20, eos_token_id=eos)
        fb = eng.submit(pb, max_new_tokens=12)
        fc = eng.submit(pc, max_new_tokens=6)      # waits for a slot
        oa, ob, oc = (f.result(timeout=300) for f in (fa, fb, fc))
    assert oa.finish_reason == "eos"
    assert oa.output_ids[-1] == eos and oa.output_ids.size <= 3
    np.testing.assert_array_equal(
        oa.output_ids, _ref_greedy(model, pa, 20, eos_token_id=eos))
    # b decoded straight through; c rode the refilled slot
    np.testing.assert_array_equal(ob.output_ids, _ref_greedy(model, pb, 12))
    np.testing.assert_array_equal(oc.output_ids, _ref_greedy(model, pc, 6))


def test_queue_full_rejection(model):
    (p,) = _prompts([5])
    eng = Engine(model, ServingConfig(num_slots=1, max_queue=1)).start()
    try:
        slow = eng.submit(p, max_new_tokens=40)
        _wait_active(eng, 1)                 # the slot is now occupied
        queued = eng.submit(p, max_new_tokens=2)   # fills the queue
        with pytest.raises(QueueFullError, match="queue is full"):
            eng.submit(p, max_new_tokens=2)
        assert serving_stats()["requests_rejected_queue_full"] == 1
        assert slow.result(timeout=300).output_ids.size == 40
        assert queued.result(timeout=300).output_ids.size == 2
    finally:
        eng.shutdown()


def test_deadline_eviction_frees_slot(model):
    (p,) = _prompts([5])
    with Engine(model, ServingConfig(num_slots=1)) as eng:
        doomed = eng.submit(p, max_new_tokens=10000, deadline_s=0.05)
        with pytest.raises(DeadlineExceededError):
            doomed.result(timeout=300)
        assert serving_stats()["requests_evicted_deadline"] == 1
        # the slot came back: a normal request completes
        ok = eng.submit(p, max_new_tokens=4).result(timeout=300)
        np.testing.assert_array_equal(ok.output_ids,
                                      _ref_greedy(model, p, 4))


def test_deadline_policy_ignore(model):
    (p,) = _prompts([5])
    with Engine(model, ServingConfig(num_slots=1,
                                     deadline_policy="ignore")) as eng:
        out = eng.submit(p, max_new_tokens=4,
                         deadline_s=0.0).result(timeout=300)
    assert out.finish_reason == "length"
    assert out.output_ids.size == 4


def test_clean_shutdown_with_inflight_requests(model):
    before = {t.ident for t in threading.enumerate()}
    prompts = _prompts([5, 7, 9])
    eng = Engine(model, ServingConfig(num_slots=1)).start()
    futs = [eng.submit(p, max_new_tokens=50) for p in prompts]
    _wait_active(eng, 1)
    eng.shutdown()
    # every future resolves promptly: completed or EngineShutdownError
    shut = 0
    for f in futs:
        assert f.done()
        if f.exception() is not None:
            assert isinstance(f.exception(), EngineShutdownError)
            shut += 1
    assert shut >= 1                 # 150 tokens >> time before shutdown
    leaked = {t.ident for t in threading.enumerate()} - before
    assert not leaked
    # a dead engine rejects new work instead of hanging clients
    with pytest.raises(EngineShutdownError):
        eng.submit(prompts[0])


def test_per_request_sampling_params(model):
    """Slots apply each request's own processor chain: one greedy + one
    sampled request coexist in the batch."""
    pg, ps = _prompts([5, 6], seed=3)
    with Engine(model, ServingConfig(num_slots=2)) as eng:
        fg = eng.submit(pg, max_new_tokens=5)
        fs = eng.submit(ps, max_new_tokens=5, sampling=SamplingParams(
            temperature=0.8, top_k=20, repetition_penalty=1.3))
        og, os_ = fg.result(timeout=300), fs.result(timeout=300)
    np.testing.assert_array_equal(og.output_ids, _ref_greedy(model, pg, 5))
    assert os_.output_ids.size == 5
    assert (os_.output_ids >= 0).all() and (os_.output_ids < 512).all()


def test_submit_validation_and_capacity(model):
    with Engine(model, ServingConfig(num_slots=1)) as eng:
        with pytest.raises(ValueError, match="empty"):
            eng.submit(np.zeros(0, np.int32))
        with pytest.raises(ValueError, match="no room"):
            eng.submit(np.zeros(64, np.int32))       # == max_seq_len
        with pytest.raises(ValueError, match="top_p"):
            eng.submit(np.zeros(4, np.int32),
                       sampling=SamplingParams(temperature=1.0, top_p=0.0))
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit(np.zeros(4, np.int32), max_new_tokens=0)
        # a prompt that fills all-but-one position finishes by capacity
        (p,) = _prompts([5])
        out = eng.submit(np.zeros(63, np.int32),
                         max_new_tokens=50).result(timeout=300)
        assert out.finish_reason == "length"
        assert out.output_ids.size == 1              # 63 + 1 == capacity
    with pytest.raises(ValueError, match="num_slots"):
        Engine(model, ServingConfig(num_slots=0))
    with pytest.raises(ValueError, match="deadline_policy"):
        Engine(model, ServingConfig(deadline_policy="nope"))


def test_monitor_thread_safety():
    """Satellite: utils.monitor incr/observe/all_stats race-free under
    concurrent writers (the serving scheduler vs stat readers)."""
    from paddle_tpu.utils import monitor
    monitor.reset("t.counter")
    monitor.reset("t.lat.sum")
    monitor.reset("t.lat.count")
    errs = []

    def worker():
        try:
            for _ in range(500):
                monitor.incr("t.counter")
                monitor.observe("t.lat", 2.0)
                monitor.all_stats()
        except Exception as e:          # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert monitor.get_monitor_value("t.counter") == 8 * 500
    assert monitor.get_monitor_value("t.lat.count") == 8 * 500
    assert monitor.get_monitor_value("t.lat.sum") == 8 * 500 * 2.0
    for k in ("t.counter", "t.lat.sum", "t.lat.count"):
        monitor.reset(k)


def test_predictor_pool_and_config_validation(tmp_path):
    """Satellite: PredictorPool.retrieve names the pool size on a bad
    index; Config rejects nonexistent model paths at construction."""
    from paddle_tpu import inference, nn, static

    with pytest.raises(FileNotFoundError, match="does not exist"):
        inference.Config(str(tmp_path / "nope"))
    with pytest.raises(FileNotFoundError, match="nope.onnx"):
        inference.Config(str(tmp_path / "nope.onnx"))

    prefix = str(tmp_path / "m")
    static.save_inference_model(
        prefix, [static.InputSpec([1, 4], "float32", "x")], None,
        layer=nn.Linear(4, 2))
    pool = inference.PredictorPool(inference.Config(prefix), size=2)
    assert pool.retrieve(1) is not None
    with pytest.raises(IndexError, match="holds 2 predictor"):
        pool.retrieve(2)
    with pytest.raises(IndexError, match="0..1"):
        pool.retrieve(-1)


def test_serving_with_llama_gqa():
    """Per-slot offsets through the rope + GQA decode path (llama):
    mixed-age slot decode equals per-request greedy."""
    from paddle_tpu.models import LlamaForCausalLM, llama_config
    paddle.seed(3)
    llama = LlamaForCausalLM(llama_config("tiny", max_seq_len=64))
    llama.eval()
    prompts = _prompts([4, 8, 6], seed=11)
    with Engine(llama, ServingConfig(num_slots=2)) as eng:
        futs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        outs = [f.result(timeout=300) for f in futs]
    for p, o in zip(prompts, outs):
        np.testing.assert_array_equal(o.output_ids,
                                      _ref_greedy(llama, p, 5))


def test_profiler_captures_serving_spans(model):
    """serving.prefill_chunk / serving.tick spans land in profiler
    traces (the scheduler thread is instrumented like any op
    dispatch)."""
    from paddle_tpu.profiler import Profiler, ProfilerTarget
    (p,) = _prompts([5])
    prof = Profiler(targets=[ProfilerTarget.CPU], timer_only=True)
    prof.start()
    try:
        with Engine(model, ServingConfig(num_slots=1)) as eng:
            eng.submit(p, max_new_tokens=4).result(timeout=300)
    finally:
        prof.stop()
    names = {e["name"] for e in prof.events}
    assert "serving.prefill_chunk" in names
    assert "serving.tick" in names
