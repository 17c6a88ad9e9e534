"""The prefill chunk as a member of the compiled tick's program family:
one donated program per chunk call with as many rows as the smallest
bucket that holds the prefilling requests — token-for-token equality
with the eager lane (``FLAGS_compiled_tick`` off), the bucket chosen and
the counters that say so, the shared fallback lattice, no compile after
the first chunk call, donation, and the program's name.  A member with a
compiled tick to follow is read after that tick's launch: the same
tokens, exact counters, a clean stop that reads it once, and a completed
prompt that joins the tick after."""
import threading
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import state as _state
from paddle_tpu.models import (GPTForCausalLM, LlamaForCausalLM, gpt_config,
                               llama_config)
from paddle_tpu.models.cohere_moe import (TINY_COHERE_MOE, CohereMoeConfig,
                                          CohereMoeForCausalLM)
from paddle_tpu.models.mellum import (TINY_MELLUM, MellumConfig,
                                      MellumForCausalLM)
from paddle_tpu.serving import Engine, EngineShutdownError, ServingConfig
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


# ---------------------------------------------------------------------------
# the member read after the next tick's launch
# ---------------------------------------------------------------------------

def _beside(model, cfg, first, then, new, on_step=None, spy=None):
    """``first`` submitted alone, ``then`` once ``first`` decodes: each
    chunk call of ``then`` is a one-row member with a compiled tick to
    follow.  ``spy(eng)`` runs before the first submission,
    ``on_step(eng)`` before every tick's step once ``then`` is in.
    Returns (the started engine, the two futures, the registry when
    ``first`` was submitted)."""
    _flags._FLAGS["FLAGS_compiled_tick"] = True
    eng = Engine(model, cfg).start()
    if spy is not None:
        spy(eng)
    decoding, go = threading.Event(), threading.Event()
    step = eng._tick.step

    def gated():
        if not go.is_set():
            decoding.set()
            go.wait(300)
        elif on_step is not None:
            on_step(eng)
        return step()

    eng._tick.step = gated
    before = monitor.all_stats()
    futs = [eng.submit(first, max_new_tokens=new[0])]
    assert decoding.wait(300)
    futs.append(eng.submit(then, max_new_tokens=new[1]))
    go.set()
    return eng, futs, before


def _alone(model, cfg, prompts, new):
    """The same requests one after the other, each with nothing decoding
    beside it: no compiled tick follows any member, so each is read as
    soon as it is launched.  Returns (output ids, registry delta)."""
    _flags._FLAGS["FLAGS_compiled_tick"] = True
    with Engine(model, cfg) as eng:
        before = monitor.all_stats()
        outs = [eng.submit(p, max_new_tokens=n).result(timeout=300)
                .output_ids for p, n in zip(prompts, new)]
    return outs, _delta(before)


def _delta(before):
    after = monitor.all_stats()
    return {k: v - before.get(k, 0) for k, v in after.items()
            if isinstance(v, (int, float))}


# a neighbour of 9 tokens that decodes 40; a prompt of three chunks (the
# last left-shifted: 50 > 64 - 24) beside it
_NEIGHBOUR, _THREE = _prompts([9, 50], seed=91)


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_member_read_after_the_tick_equals_eager_lane(models, tick_flag,
                                                      family):
    model = models[family]
    eng, futs, _ = _beside(model, _cfg(), _NEIGHBOUR, _THREE, (40, 8))
    try:
        got = [f.result(timeout=300).output_ids for f in futs]
    finally:
        eng.shutdown()
    snap = eng.stats()
    assert snap["prefill_compiled_hits"] == 4
    assert snap["prefill_overlap_share"] > 0
    assert snap["tick_fallbacks"] == snap["prefill_fallbacks"] == 0
    _flags._FLAGS["FLAGS_compiled_tick"] = False
    with Engine(model, _cfg()) as ref_eng:
        want = [ref_eng.submit(p, max_new_tokens=n).result(timeout=300)
                .output_ids for p, n in ((_NEIGHBOUR, 40), (_THREE, 8))]
    for r, g in zip(want, got):
        np.testing.assert_array_equal(r, g)


def _moe_model(family):
    paddle.seed(0)
    if family == "mellum":
        m = MellumForCausalLM(MellumConfig(**TINY_MELLUM))
    else:
        m = CohereMoeForCausalLM(CohereMoeConfig(**TINY_COHERE_MOE))
    m.eval()
    return m


@pytest.fixture(scope="module")
def moe_models():
    return {f: _moe_model(f) for f in ("mellum", "cohere")}


def _moe_cfg():
    return ServingConfig(num_slots=4, max_queue=16, page_size=4,
                         prefill_chunk_tokens=8, enable_prefix_cache=False)


_EXACT = ("serving.moe.prefill.pairs", "serving.moe.prefill.experts_hit",
          "serving.moe.pairs_local", "serving.moe.tokens_routed",
          "serving.prefill.tokens_useful", "serving.prefill.compiled_hits",
          "serving.tokens_generated", "serving.prefill_steps")


@pytest.mark.parametrize("family", ["mellum", "cohere"])
def test_routing_and_prefill_counts_are_exact(moe_models, tick_flag,
                                              family):
    """Members read after the next tick's launch count what members read
    at once count: every routed pair, every expert hit, every prompt
    token (the tiny Command A+ holds 4 of its 16 experts, so its pairs
    follow the router)."""
    model = moe_models[family]
    near, three = _prompts([5, 21], seed=93)
    want, ref = _alone(model, _moe_cfg(), [near, three], (30, 6))
    assert ref.get("serving.prefill.overlapped", 0) == 0
    eng, futs, before = _beside(model, _moe_cfg(), near, three, (30, 6))
    try:
        got = [f.result(timeout=300).output_ids for f in futs]
    finally:
        eng.shutdown()
    reg = _delta(before)
    assert reg["serving.prefill.overlapped"] >= 2
    for r, g in zip(want, got):
        np.testing.assert_array_equal(r, g)
    assert {k: reg[k] for k in _EXACT} == {k: ref[k] for k in _EXACT}
    if family == "mellum":
        # every expert held: k pairs a routed token a layer, and a token
        # routed for each prompt position and each decode step
        layers, k = TINY_MELLUM["num_layers"], TINY_MELLUM[
            "num_experts_per_tok"]
        decoded = reg["serving.tokens_generated"] - 2
        assert reg["serving.moe.prefill.pairs"] == \
            (near.size + three.size) * layers * k
        assert reg["serving.moe.tokens_routed"] == \
            (near.size + three.size + decoded) * layers
        assert reg["serving.moe.pairs_local"] == \
            reg["serving.moe.tokens_routed"] * k
    else:
        assert reg["serving.moe.prefill.pairs"] > 0


def test_clean_stop_reads_an_open_member_once(moe_models, tick_flag):
    """A stop asked for while a member is launched and unread: the
    iteration reads it on its way out — the completed prompt's one token
    is delivered, its routing and prefill counted once."""
    model = moe_models["mellum"]
    near, three = _prompts([5, 21], seed=95)
    want, ref = _alone(model, _moe_cfg(), [near, three], (30, 1))

    def stop_at_last_chunk(eng):
        if eng._prefill_ended:          # the prompt's last chunk is open
            with eng._work:
                eng._running = False

    eng, futs, before = _beside(model, _moe_cfg(), near, three, (30, 1),
                                on_step=stop_at_last_chunk)
    try:
        out = futs[1].result(timeout=300)
        with pytest.raises(EngineShutdownError):
            futs[0].result(timeout=300)
    finally:
        eng.shutdown()
    reg = _delta(before)
    np.testing.assert_array_equal(out.output_ids, want[1])
    assert out.finish_reason == "length" and out.ttft_ms > 0
    assert reg["serving.prefill.overlapped"] >= 1
    once = ("serving.moe.prefill.pairs", "serving.moe.prefill.experts_hit",
            "serving.prefill.tokens_useful",
            "serving.prefill.compiled_hits", "serving.prefill_steps",
            "serving.ttft_ms.count")
    assert {k: reg[k] for k in once} == {k: ref[k] for k in once}
    assert reg["serving.moe.prefill.pairs"] == (near.size + three.size) \
        * TINY_MELLUM["num_layers"] * TINY_MELLUM["num_experts_per_tok"]


def test_completed_prompt_joins_the_tick_after(models, tick_flag):
    """A prompt completed by a member read after the next tick's launch
    keeps its first token and ``ttft_ms``, and decodes from the tick
    launched after that one."""
    model = models["gpt"]
    launches, joined = [], []

    def spy(eng):
        launch, finish = eng._tick._launch, eng._finish_prefill

        def spy_launch(live):
            launches.append(set(live))
            return launch(live)

        def spy_finish(overlapped):
            finish(overlapped)
            for slot, req in eng._active.items():
                if req.prompt.size == _THREE.size and not joined:
                    joined.append((slot, overlapped, len(launches)))

        eng._tick._launch, eng._finish_prefill = spy_launch, spy_finish

    eng, futs, _ = _beside(model, _cfg(), _NEIGHBOUR, _THREE, (40, 8),
                           spy=spy)
    try:
        got = [f.result(timeout=300) for f in futs]
    finally:
        eng.shutdown()
    (slot, overlapped, k), = joined
    assert overlapped
    assert slot not in launches[k - 1]      # launched behind its member
    assert slot in launches[k]              # the tick after
    ref, _, _ = _serve(model, [[_THREE]], compiled=False, max_new=8)
    np.testing.assert_array_equal(got[1].output_ids, ref[0][0])
    assert 0 < got[1].ttft_ms <= got[1].latency_ms
