"""Granite 4.0-H (Mamba-2 layers beside attention layers) against the one
plain reference of ``chipbench/reference/`` on seeded weights, at a tiny
size on the CPU: the whole-sequence forward, the serving engine's eager
and compiled lanes with a recurrent state beside the pages, the state's
reset and isolation, the ``ssm_update`` kernel, the chunked form, and
the typed refusals of what a recurrent state cannot ride yet."""
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import llama_config, LlamaForCausalLM
from paddle_tpu.models import granite_hybrid
from paddle_tpu.models.generation import speculative_generate
from paddle_tpu.models.granite_hybrid import (
    TINY_GRANITE_HYBRID, GraniteHybridConfig, GraniteHybridForCausalLM)
from paddle_tpu.pallas import ssm
from paddle_tpu.serving import (Engine, PagedKVCache, RecurrentStateError,
                                SamplingParams, ServingConfig,
                                serving_stats)
from paddle_tpu.utils import flags as _flags

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "chipbench"))
from reference import common as refc            # noqa: E402
from reference import granite_hybrid as ref     # noqa: E402
from reference import run as refrun             # noqa: E402

# hidden 64, two periods of [mamba, mamba, attention, mamba], H 4, P 16,
# N 16
TINY = dict(TINY_GRANITE_HYBRID, initializer_range=0.1)
VOCAB = TINY["vocab_size"]
CHUNK = 16
MAX_LEN = 96


def _reference_cfg(cfg):
    keys = ("vocab_size", "hidden_size", "num_layers", "layer_types",
            "num_heads", "num_kv_heads", "intermediate_size",
            "rms_norm_eps", "initializer_range", "embedding_multiplier",
            "logits_scaling", "residual_multiplier",
            "attention_multiplier", "mamba_n_heads", "mamba_d_head",
            "mamba_d_state", "mamba_n_groups", "mamba_d_conv")
    return {k: getattr(cfg, k) for k in keys}


@pytest.fixture(scope="module")
def tiny():
    """(model, reference config, weights, reference runner)."""
    cfg = GraniteHybridConfig(**TINY)
    model = GraniteHybridForCausalLM(cfg)
    model.eval()
    rcfg = _reference_cfg(cfg)
    weights = refc.make_weights(ref.weight_spec(rcfg), 2**31 + 5,
                                jnp.float32)
    named = dict(model.named_parameters())
    assert set(named) == set(weights)
    for name, p in named.items():
        assert tuple(p.shape) == tuple(weights[name].shape), name
        p._data_ = weights[name]
    return model, rcfg, weights, refrun.ServeReference("granite_hybrid",
                                                       rcfg)


@pytest.fixture
def tick_flag():
    saved = _flags._FLAGS["FLAGS_compiled_tick"]
    yield _flags._FLAGS
    _flags._FLAGS["FLAGS_compiled_tick"] = saved


def _cfg(**kw):
    base = dict(num_slots=4, max_seq_len=MAX_LEN, page_size=8,
                prefill_chunk_tokens=CHUNK, enable_prefix_cache=False)
    base.update(kw)
    return ServingConfig(**base)


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, (n,)).astype("int32") for n in lens]


def _reference_logits(runner, weights, ids):
    padded = np.zeros(MAX_LEN, np.int32)
    padded[:len(ids)] = ids
    return np.asarray(runner.logits(weights, padded))[:len(ids)]


# (a) ------------------------------------------------------------------
def test_whole_sequence_logits_match_reference(tiny):
    model, _, weights, runner = tiny
    ids = _prompts([37, 37], seed=1)        # 37 tokens: five sub-chunks
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(np.stack(ids)))._data_)
    for row, seq in zip(got, ids):
        np.testing.assert_allclose(
            row, _reference_logits(runner, weights, seq), atol=2e-6)


def test_generate_decodes_against_its_own_caches(tiny):
    """``generate()`` builds the model's own per-layer caches (keys and
    values for the attention layers, a state row for the others): every
    token it emits is the reference's first choice."""
    model, _, weights, runner = tiny
    prompts = np.stack(_prompts([9, 9], seed=2))
    with paddle.no_grad():
        out = np.asarray(model.generate(paddle.to_tensor(prompts),
                                        max_new_tokens=12)._data_)
    for row in out:
        want = _reference_logits(runner, weights, row)
        np.testing.assert_array_equal(row[9:], want[8:-1].argmax(-1))
    assert len(set(out[0, 9:].tolist())) > 3


# (b) ------------------------------------------------------------------
def test_engine_logits_match_reference_step_by_step(tiny):
    """Through ``Engine`` (its eager lane: a forward hook is what shows
    the logits): a 37-token prompt prefilled in chunks of 16, 16 and a
    ragged 5, then 40 decoded tokens — the logits of every call against
    the reference's one full forward."""
    model, _, weights, runner = tiny
    seen = []
    hook = model.register_forward_post_hook(
        lambda layer, inputs, out: seen.append(np.asarray(out._data_)))
    try:
        with pytest.warns(UserWarning, match="hooks"):
            with Engine(model, _cfg()) as eng:
                prompt = _prompts([37], seed=3)[0]
                out = eng.generate(prompt, max_new_tokens=40)
    finally:
        hook.remove()
    ids = np.concatenate([prompt, out.output_ids])
    want = _reference_logits(runner, weights, ids)
    chunks = [c for c in seen if c.shape[1] == CHUNK]
    steps = [c for c in seen if c.shape[1] == 1]
    assert len(chunks) == 3 and len(steps) == 39
    pos = 0
    for c, n in zip(chunks, (16, 16, 5)):
        np.testing.assert_allclose(c[0, :n], want[pos:pos + n], atol=2e-6)
        pos += n
    slot = 0                                # the only request's slot
    for i, c in enumerate(steps):
        np.testing.assert_allclose(c[slot, 0], want[37 + i], atol=2e-6)
    # every served token is the reference's first choice
    np.testing.assert_array_equal(
        out.output_ids, want[36:36 + 40].argmax(-1))


# (c) ------------------------------------------------------------------
def _serve(model, waves, compiled, cfg=None, sampling=None, max_new=24):
    """``waves`` (lists of prompts, each wave submitted under the lock
    so one admission round takes it, after the last has finished)
    through a fresh engine; returns ([[ids]], stats, prefill row counts)."""
    _flags._FLAGS["FLAGS_compiled_tick"] = compiled
    outs, rows = [], []
    with Engine(model, cfg or _cfg()) as eng:
        if eng._tick is not None:
            run = eng._tick.run_prefill

            def spy(member, *a, **k):
                rows.append(member[0])
                return run(member, *a, **k)

            eng._tick.run_prefill = spy
        for wave in waves:
            with eng._work:
                futs = [eng.submit(p, max_new_tokens=max_new,
                                   sampling=sampling) for p in wave]
            outs.append([f.result(timeout=600).output_ids for f in futs])
        stats = serving_stats()
    return outs, stats, rows


@pytest.mark.parametrize("sampling", [
    None, SamplingParams(temperature=1.0, top_k=50, seed=11)],
    ids=["greedy", "seeded"])
def test_compiled_lanes_match_eager_lane(tiny, tick_flag, sampling):
    model = tiny[0]
    waves = [_prompts([37, 21, 16], seed=4), _prompts([50, 9], seed=5)]
    eager, st_e, _ = _serve(model, waves, False, sampling=sampling)
    comp, st_c, rows = _serve(model, waves, True, sampling=sampling)
    for a, b in zip(eager, comp):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
    assert st_e["tick_compiled_hits"] == 0
    assert st_c["tick_compiled_hits"] > 0 and st_c["tick_fallbacks"] == 0
    assert st_c["prefill_compiled_hits"] == len(rows) > 0
    assert st_c["prefill_fallbacks"] == 0
    assert 4 in rows and 2 in rows          # three requests, then two
    assert st_c["state_resets"] == 5
    assert st_c["state_bytes"] > 0
    assert 0 < st_c["state_rows_live_share"] <= 1
    assert st_c["state_reset_ms_avg"] > 0


def test_compiled_tick_runs_the_kernel(tiny, tick_flag, monkeypatch):
    """With Mosaic kernels on (here: the interpreter) the tick's Mamba
    layers go through ``ssm_update``, and serve what the XLA lane
    serves."""
    model = tiny[0]
    waves = [_prompts([21, 16], seed=6)]
    plain, _, _ = _serve(model, waves, True, max_new=8)
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    calls, kernel = [], ssm.ssm_update

    def counted(state, rows, *rest):
        calls.append(state.shape)
        return kernel(state, rows, *rest)

    monkeypatch.setattr(ssm, "ssm_update", counted)
    # the mixer is jitted: which lane it took is part of its trace
    granite_hybrid._mamba2_mix.clear_cache()
    try:
        with_kernel, st, _ = _serve(model, waves, True, max_new=8)
    finally:
        granite_hybrid._mamba2_mix.clear_cache()
    for x, y in zip(plain[0], with_kernel[0]):
        np.testing.assert_array_equal(x, y)
    # the jitted mixer is traced once for all the layers; the state has
    # slots + 1 rows
    assert calls and {c[0] for c in calls} == {5}
    assert st["tick_fallbacks"] == 0


# (d) ------------------------------------------------------------------
def test_reused_slot_starts_from_an_empty_state(tiny, tick_flag):
    model = tiny[0]
    long_one, probe = _prompts([60, 19], seed=7)
    one_slot = _cfg(num_slots=1)
    fresh, _, _ = _serve(model, [[probe]], True, cfg=one_slot)
    reused, st, _ = _serve(model, [[long_one], [probe]], True,
                           cfg=one_slot)
    np.testing.assert_array_equal(fresh[0][0], reused[1][0])
    assert st["state_resets"] == 2


def test_two_rows_of_one_member_keep_their_own_state(tiny, tick_flag):
    model = tiny[0]
    a, b = _prompts([23, 41], seed=8)
    alone_a, _, _ = _serve(model, [[a]], True)
    alone_b, _, _ = _serve(model, [[b]], True)
    both, _, rows = _serve(model, [[a, b]], True)
    # chunks 1 and 2 side by side in the 2-row member, the longer
    # prompt's third chunk alone
    assert rows == [2, 2, 1]
    np.testing.assert_array_equal(both[0][0], alone_a[0][0])
    np.testing.assert_array_equal(both[0][1], alone_b[0][0])


@pytest.mark.parametrize("compiled", [True, False],
                         ids=["compiled", "eager"])
def test_finished_request_leaves_its_state_rows_readable(tiny, tick_flag,
                                                         compiled):
    """``RequestOutput.slot`` names the slot, and until its next
    admission ``read_state`` gives the rows the last tick left: the
    reference's state after the prompt and every token but the last."""
    model, rcfg, weights, _ = tiny
    tick_flag["FLAGS_compiled_tick"] = compiled
    short, long_one = _prompts([13, 37], seed=11)
    with Engine(model, _cfg(num_slots=2)) as eng:
        futs = [eng.submit(p, max_new_tokens=n)
                for p, n in ((short, 5), (long_one, 21))]
        outs = [f.result(timeout=300) for f in futs]
        assert sorted(o.slot for o in outs) == [0, 1]
        rows = [eng.cache.read_state(o.slot) for o in outs]
    mamba = [i for i, kind in enumerate(rcfg["layer_types"])
             if kind == "mamba"]
    for out, row in zip(outs, rows):
        assert sorted(row) == mamba
        ids = np.zeros(MAX_LEN, np.int32)
        n = out.prompt_ids.size + out.output_ids.size - 1
        ids[:n] = out.ids[:n]
        x = ref.embed(weights, jnp.asarray(ids)[None], rcfg)
        for i in range(rcfg["num_layers"]):
            w = refrun._layer_weights(ref, rcfg, weights, i)
            x, want = ref.layer_and_state(x, w, rcfg, refc.mm_f32, n)
            if want is not None:
                np.testing.assert_allclose(
                    row[i]["ssm_state"], np.asarray(want[0]),
                    rtol=2e-4, atol=2e-5)


def test_eos_row_keeps_its_state_through_the_dead_tick(tiny, tick_flag):
    """The compiled tick runs one ahead of the host (ISSUE 31): the
    tick after a row's eos is launched before the host has read it, and
    runs the row dead.  The row's recurrent state is then what its last
    fed token left — the eager lane's, which never ran that tick —
    however long the neighbour decodes on."""
    model = tiny[0]
    short, long_one = _prompts([13, 37], seed=12)
    sp = SamplingParams(temperature=1.0, seed=21)

    def run(compiled, eos):
        tick_flag["FLAGS_compiled_tick"] = compiled
        with Engine(model, _cfg(num_slots=2)) as eng:
            with eng._work:
                futs = [eng.submit(short, max_new_tokens=24, sampling=sp,
                                   eos_token_id=eos),
                        eng.submit(long_one, max_new_tokens=24)]
            outs = [f.result(timeout=300) for f in futs]
            return outs, eng.cache.read_state(outs[0].slot)

    (free, _), _ = run(False, None)
    stream = free.output_ids
    k = next(k for k in range(5, 20) if stream[k] not in stream[:k])
    (e_short, e_long), e_state = run(False, int(stream[k]))
    (c_short, c_long), c_state = run(True, int(stream[k]))
    assert c_short.finish_reason == e_short.finish_reason == "eos"
    np.testing.assert_array_equal(c_short.output_ids, stream[:k + 1])
    np.testing.assert_array_equal(e_short.output_ids, stream[:k + 1])
    np.testing.assert_array_equal(c_long.output_ids, e_long.output_ids)
    assert c_long.output_ids.size == 24 > k + 5
    assert sorted(c_state) == sorted(e_state) and c_state
    for i, arrays in e_state.items():
        for name, want in arrays.items():
            # the lanes' programs round apart in the last bits; one fed
            # token more would shift the whole conv window
            np.testing.assert_allclose(c_state[i][name], want, rtol=2e-4,
                                       atol=2e-5,
                                       err_msg=f"layer {i} {name}")


# (e), (f) -------------------------------------------------------------
def _ssm_inputs(batch, rows_total, seed=0, heads=8, p=16, n=16, g=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    state = jax.random.normal(ks[0], (rows_total, heads, p, n))
    x = jax.random.normal(ks[1], (batch, heads, p))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (batch, heads)))
    a = -jnp.exp(jax.random.normal(ks[3], (heads,)))
    bm = jax.random.normal(ks[4], (batch, g, n))
    cm = jax.random.normal(ks[5], (batch, g, n))
    return state, x, dt, a, bm, cm


@pytest.mark.parametrize("groups", [1, 2])
def test_ssm_update_kernel_matches_xla_lane(monkeypatch, groups):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    state, x, dt, a, bm, cm = _ssm_inputs(4, 7, g=groups)
    dt = dt.at[2].set(0.0)                  # a row that is not decoding
    rows = jnp.asarray([5, 0, 3, 6], jnp.int32)     # 6: the scratch row
    want_s, want_y = ssm.ssm_step_xla(state, rows, x, dt, a, bm, cm)
    got_s, got_y = jax.jit(ssm.ssm_update)(state, rows, x, dt, a, bm, cm)
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got_s, want_s, rtol=1e-5, atol=1e-5)
    # rows the call did not name, and the dt = 0 row, are as they were
    for untouched in (1, 2, 4, 3):
        np.testing.assert_array_equal(got_s[untouched], state[untouched])


@pytest.mark.parametrize("sub_chunk", [64, 5], ids=["whole", "scanned"])
def test_chunked_form_matches_recurrence_across_chunks(sub_chunk):
    heads, p, n, batch = 8, 16, 16, 2
    lens = (16, 16, 7)
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    total = sum(lens)
    x = jax.random.normal(ks[0], (batch, total, heads, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, total, heads)))
    dt = dt.at[1, 30:].set(0.0)             # a row's pad tail
    a = -jnp.exp(jax.random.normal(ks[2], (heads,)) * 2.0)
    bm = jax.random.normal(ks[3], (batch, total, 1, n))
    cm = jax.random.normal(ks[4], (batch, total, 1, n))
    rows = jnp.arange(batch, dtype=jnp.int32)
    s_rec = jnp.zeros((batch, heads, p, n))
    ys = []
    for t in range(total):
        s_rec, y = ssm.ssm_step_xla(s_rec, rows, x[:, t], dt[:, t], a,
                                    bm[:, t], cm[:, t])
        ys.append(y)
    y_rec = jnp.stack(ys, axis=1)
    s, pos, parts = jnp.zeros((batch, heads, p, n)), 0, []
    for length in lens:                     # the state carried across
        sl = slice(pos, pos + length)
        s, y = ssm.ssd_chunked(s, x[:, sl], dt[:, sl], a, bm[:, sl],
                               cm[:, sl], sub_chunk)
        parts.append(y)
        pos += length
    np.testing.assert_allclose(jnp.concatenate(parts, 1), y_rec,
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s, s_rec, rtol=2e-4, atol=2e-4)


# (g) ------------------------------------------------------------------
def _draft():
    m = LlamaForCausalLM(llama_config(
        "tiny", hidden_size=32, num_heads=2, num_kv_heads=2,
        intermediate_size=64, vocab_size=VOCAB, max_seq_len=128,
        num_layers=1))
    m.eval()
    return m


@pytest.mark.parametrize("kwargs, names", [
    (dict(enable_prefix_cache=True), "enable_prefix_cache"),
    (dict(speculation_k=2, draft_model="llama"), "speculation_k"),
    (dict(role="prefill"), "role"),
    (dict(role="decode"), "role"),
], ids=["prefix", "speculation", "prefill-role", "decode-role"])
def test_refused_at_construction(tiny, kwargs, names):
    kwargs = dict(kwargs)
    if kwargs.get("draft_model") == "llama":
        kwargs["draft_model"] = _draft()
    with pytest.raises(RecurrentStateError, match=names):
        Engine(tiny[0], _cfg(**kwargs))


def test_refused_page_export_and_recurrent_draft(tiny):
    model = tiny[0]
    eng = Engine(model, _cfg())
    with pytest.raises(RecurrentStateError, match="migrator"):
        eng.migrator = lambda *a: None
    with pytest.raises(RecurrentStateError, match="submit_resume"):
        eng.submit_resume(np.arange(4), [1], {})
    cache = PagedKVCache(
        2, 2, 32, 2, 16, page_size=8,
        layer_states=GraniteHybridConfig(**TINY).layer_states(
            "float32")[:2])
    slot = cache.allocate(2)
    with pytest.raises(RecurrentStateError, match="export_pages"):
        cache.export_pages(slot)
    with pytest.raises(RecurrentStateError, match="adopt_pages"):
        cache.adopt_pages(1, 0, np.zeros((2, 1, 8, 2, 16)),
                          np.zeros((2, 1, 8, 2, 16)))
    with pytest.raises(RecurrentStateError, match="draft_model"):
        Engine(_draft(), ServingConfig(speculation_k=2, draft_model=model))
    with pytest.raises(NotImplementedError, match="recurrent state"):
        speculative_generate(model, _draft(),
                             paddle.to_tensor(np.zeros((1, 4), "int32")),
                             speculation_k=2)
