"""The prefill chunk as a member of the compiled tick's program family
(ISSUE 27): one donated program per chunk call with as many rows as the
smallest bucket that holds the prefilling requests — token-for-token
equality with the eager lane (``FLAGS_compiled_tick`` off), the bucket
chosen and the counters that say so, the shared fallback lattice, no
compile after the first chunk call, donation, and the program's name."""
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import state as _state
from paddle_tpu.models import (GPTForCausalLM, LlamaForCausalLM, gpt_config,
                               llama_config)
from paddle_tpu.serving import Engine, ServingConfig
from paddle_tpu.serving.compiled_tick import TickFallbackWarning
from paddle_tpu.utils import flags as _flags
from paddle_tpu.utils import monitor

CHUNK = 24          # capacity 64: a prompt past 48 left-shifts its last chunk
SLOTS = 8           # buckets 1, 2, 4, 8
VOCAB = 256


def _gpt():
    paddle.seed(0)
    m = GPTForCausalLM(gpt_config(
        "gpt2-124m", num_layers=2, hidden_size=64, num_heads=2,
        vocab_size=VOCAB, max_seq_len=64))
    m.eval()
    return m


def _llama():
    paddle.seed(0)
    m = LlamaForCausalLM(llama_config(
        "tiny", hidden_size=64, num_heads=4, num_kv_heads=2,
        intermediate_size=128, vocab_size=VOCAB, max_seq_len=64))
    m.eval()
    return m


@pytest.fixture(scope="module")
def models():
    return {"gpt": _gpt(), "llama": _llama()}


@pytest.fixture
def tick_flag():
    saved = _flags._FLAGS["FLAGS_compiled_tick"]
    yield _flags._FLAGS
    _flags._FLAGS["FLAGS_compiled_tick"] = saved


def _cfg(**kw):
    base = dict(num_slots=SLOTS, max_queue=64, page_size=8,
                prefill_chunk_tokens=CHUNK)
    base.update(kw)
    return ServingConfig(**base)


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, (n,)).astype("int32") for n in lens]


def _wave(eng, prompts, max_new=4):
    """Submit ``prompts`` under the engine's lock, so one admission pass
    takes them all and they prefill in the same round; returns their
    output ids."""
    with eng._work:
        futs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    return [f.result(timeout=300).output_ids for f in futs]


def _serve(model, waves, compiled, cfg=None, max_new=4):
    """Run ``waves`` (lists of prompts, each wave after the last has
    finished) through a fresh engine; returns ([[ids]], stats, tick)."""
    _flags._FLAGS["FLAGS_compiled_tick"] = compiled
    eng = Engine(model, cfg or _cfg()).start()
    try:
        outs = [_wave(eng, w, max_new) for w in waves]
        return outs, eng.stats(), eng._tick
    finally:
        eng.shutdown()


def _reg(*names):
    s = monitor.all_stats()
    return [s.get(n, 0) for n in names]


# one chunk; two chunks; three, the last left-shifted (58 > 64 - 24); a
# prompt whose first two pages the tree already holds (wave 2)
_SHARED = _prompts([16], seed=3)[0]
_MIXED = [
    [_prompts([9], seed=1)[0], _prompts([40], seed=2)[0],
     _prompts([58], seed=4)[0],
     np.concatenate([_SHARED, _prompts([5], seed=5)[0]])],
    [np.concatenate([_SHARED, _prompts([30], seed=6)[0]]),
     _prompts([24], seed=7)[0]],
]


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_mixed_workload_equals_eager_lane(models, tick_flag, family):
    model = models[family]
    ref, snap_e, _ = _serve(model, _MIXED, compiled=False)
    got, snap_c, _ = _serve(model, _MIXED, compiled=True)
    for wave_r, wave_g in zip(ref, got):
        for r, g in zip(wave_r, wave_g):
            np.testing.assert_array_equal(r, g)
    assert snap_e["prefill_compiled_hits"] == 0
    assert snap_e["prefill_fallbacks"] == 0
    assert snap_c["prefill_compiled_hits"] > 0
    assert snap_c["prefill_fallbacks"] == 0
    assert snap_c["prefix_cache_hits"] >= 1      # the shared_len start ran
    assert snap_c["prefill_chunks"] == snap_e["prefill_chunks"]


@pytest.mark.parametrize("n,rows", [(1, 1), (2, 2), (3, 4), (SLOTS, SLOTS)])
def test_bucket_holds_the_prefilling_requests(models, tick_flag, n, rows):
    """``n`` one-chunk prompts admitted together are ONE chunk call of the
    smallest bucket that holds them: one launch, rows x chunk positions."""
    model = models["gpt"]
    prompts = _prompts([5 + i for i in range(n)], seed=11)
    ref, _, _ = _serve(model, [prompts], compiled=False, max_new=2)
    _flags._FLAGS["FLAGS_compiled_tick"] = True
    eng = Engine(model, _cfg()).start()
    try:
        keys = ("serving.prefill.launches",
                "serving.prefill.tokens_computed",
                "serving.prefill.tokens_useful",
                "serving.prefill.compiled_hits",
                "serving.prefill_chunk_ms.count")
        before = _reg(*keys)
        got = _wave(eng, prompts, 2)
        delta = [b - a for a, b in zip(before, _reg(*keys))]
    finally:
        eng.shutdown()
    assert delta == [1, rows * CHUNK, sum(p.size for p in prompts), 1, 1]
    for r, g in zip(ref[0], got):
        np.testing.assert_array_equal(r, g)


def test_family_buckets_follow_num_slots(models, tick_flag):
    _flags._FLAGS["FLAGS_compiled_tick"] = True
    for slots, want in ((1, [1]), (2, [1, 2]), (6, [1, 2, 4, 6]),
                        (8, [1, 2, 4, 8])):
        eng = Engine(models["gpt"], _cfg(num_slots=slots)).start()
        try:
            assert eng._tick.prefill_buckets() == want
        finally:
            eng.shutdown()


def test_no_bucket_compiles_after_the_first_chunk_call(models, tick_flag):
    """Every member is built before the first chunk call returns: a later
    wave that meets a new row count builds nothing."""
    _flags._FLAGS["FLAGS_compiled_tick"] = True
    eng = Engine(models["gpt"], _cfg()).start()
    try:
        _wave(eng, _prompts([7], seed=21), 3)            # rows 1 + the tick
        (count0,) = _reg("jit.compile_ms.count")
        hits0 = eng.stats()["prefill_compiled_hits"]
        _wave(eng, _prompts([6, 8, 30, 5, 9], seed=22), 3)   # rows 8, then 1
        _wave(eng, _prompts([6, 8, 7], seed=23), 3)          # rows 4
        (count1,) = _reg("jit.compile_ms.count")
        assert eng.stats()["prefill_compiled_hits"] >= hits0 + 3
    finally:
        eng.shutdown()
    assert count1 == count0


def test_hook_falls_back_with_one_typed_warning(models, tick_flag):
    model = models["gpt"]
    prompts = _prompts([30, 9], seed=31)
    ref, _, _ = _serve(model, [prompts], compiled=False)
    handle = model.gpt.register_forward_post_hook(lambda lay, i, o: None)
    try:
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            got, snap, _ = _serve(model, [prompts], compiled=True)
    finally:
        handle.remove()
    tw = [x for x in w if issubclass(x.category, TickFallbackWarning)]
    assert len(tw) == 1, [str(x.message) for x in tw]
    assert "hooks" in str(tw[0].message)
    assert snap["prefill_compiled_hits"] == 0
    assert snap["prefill_fallbacks"] >= 1
    assert snap["tick_compiled_hits"] == 0
    for r, g in zip(ref[0], got[0]):
        np.testing.assert_array_equal(r, g)


def test_active_tracer_falls_back_with_one_typed_warning(models, tick_flag):
    """A framework tracer active on the calling thread: the member is
    refused (the eager lane runs), counted, and warned once."""
    _flags._FLAGS["FLAGS_compiled_tick"] = True
    eng = Engine(models["gpt"], _cfg(num_slots=2)).start()
    try:
        _wave(eng, _prompts([5], seed=41), 2)
        tick = eng._tick
        assert tick.prefill_member(1) is not None
        (before,) = _reg("serving.prefill.fallbacks")
        _state.STATE.tracer = object()
        try:
            with warnings.catch_warnings(record=True) as w:
                warnings.simplefilter("always")
                assert tick.prefill_member(1) is None
                assert tick.prefill_member(2) is None
        finally:
            _state.STATE.tracer = None
        (after,) = _reg("serving.prefill.fallbacks")
    finally:
        eng.shutdown()
    tw = [x for x in w if issubclass(x.category, TickFallbackWarning)]
    assert len(tw) == 1 and "tracer" in str(tw[0].message)
    assert after - before == 2


def test_speculation_keeps_both_chunk_calls_eager(models, tick_flag):
    model = models["gpt"]
    (p,) = _prompts([30], seed=51)
    ref, _, _ = _serve(model, [[p]], compiled=False)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", TickFallbackWarning)
        got, snap, _ = _serve(model, [[p]], compiled=True, cfg=_cfg(
            num_slots=2, draft_model=model, speculation_k=2))
    assert snap["prefill_compiled_hits"] == 0
    assert snap["prefill_fallbacks"] >= 1
    np.testing.assert_array_equal(ref[0][0], got[0][0])


def test_pools_are_donated_to_the_chunk_call(models, tick_flag):
    _flags._FLAGS["FLAGS_compiled_tick"] = True
    eng = Engine(models["gpt"], _cfg(num_slots=2)).start()
    try:
        old = eng.cache.flat_pools()
        _wave(eng, _prompts([7], seed=61), 1)
        new = eng.cache.flat_pools()
        assert eng.stats()["prefill_compiled_hits"] == 1
    finally:
        eng.shutdown()
    assert all(a.is_deleted() for a in old)
    assert not any(a.is_deleted() for a in new)


def test_program_name_is_not_a_tick(models, tick_flag):
    _, _, tick = _serve(models["gpt"], [_prompts([5], seed=71)],
                        compiled=True, cfg=_cfg(num_slots=2))
    for rows in (1, 2):
        text = tick.lowered_text(f"prefill_r{rows}")
        assert f"serving_prefill_r{rows}" in text
        assert "serving_tick" not in text
    assert "serving_tick_greedy" in tick.lowered_text("greedy")


def test_adapter_rows_ride_the_compiled_member(models, tick_flag):
    """The row-ordered adapter index is a program input: an engine with
    an adapter pool prefills compiled, base and adapted rows together,
    and matches the eager lane."""
    from paddle_tpu import nn
    tuned = _gpt()
    nn.attach_lora(tuned, rank=4)
    rng = np.random.default_rng(100)
    for lay in nn.lora_layers(tuned).values():
        for w in (lay.lora_A, lay.lora_B):
            w.set_value(rng.standard_normal(w.shape).astype(np.float32)
                        * 0.5)
    spec = {"t0": nn.adapter_spec(tuned)}
    base = _gpt()
    prompts = _prompts([30, 9], seed=81)

    def run(compiled):
        _flags._FLAGS["FLAGS_compiled_tick"] = compiled
        eng = Engine(base, _cfg(num_slots=2, max_adapters=2,
                                adapter_rank_pool=4, adapters=spec)).start()
        try:
            with eng._work:
                futs = [eng.submit(prompts[0], max_new_tokens=4,
                                   adapter_id="t0"),
                        eng.submit(prompts[1], max_new_tokens=4)]
            return ([f.result(timeout=300).output_ids for f in futs],
                    eng.stats())
        finally:
            eng.shutdown()

    ref, _ = run(False)
    got, snap = run(True)
    assert snap["prefill_compiled_hits"] >= 2
    assert snap["prefill_fallbacks"] == 0
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(r, g)
    # the adapter moved the adapted row, and only it
    plain, _, _ = _serve(base, [prompts], compiled=True,
                         cfg=_cfg(num_slots=2))
    assert not np.array_equal(plain[0][0], got[0])
    np.testing.assert_array_equal(plain[0][1], got[1])
