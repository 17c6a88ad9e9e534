"""Cohere ``cohere2_moe`` (parallel blocks, window and full attention
layers, routed and shared experts of which a chip holds a run) against
the plain reference of ``chipbench/reference/`` on seeded weights, at a
tiny size on the CPU: the whole-sequence forward, the serving engine's
eager and compiled lanes with page tables by layer kind, the shares of
the expert layer adding up, the grouped expert product's kernel against
its XLA lane, the ring of window pages, and the typed refusals."""
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.cohere_moe import (
    TINY_COHERE_MOE, CohereMoeConfig, CohereMoeForCausalLM, CohereSparseMLP)
from paddle_tpu.pallas import moe
from paddle_tpu.serving import (Engine, PagedKVCache, SamplingParams,
                                ServingConfig, WindowLayerError,
                                serving_stats)
from paddle_tpu.utils import flags as _flags

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "chipbench"))
from reference import common as refc            # noqa: E402
from reference import cohere_moe as ref         # noqa: E402
from reference import run as refrun             # noqa: E402

# hidden 64, two periods of [window, window, window, full], 4 query / 2 KV
# heads of 16, window 8, 16 experts, 4 a token, 2 shared, 4 held
TINY = dict(TINY_COHERE_MOE)
VOCAB = TINY["vocab_size"]
WINDOW = TINY["sliding_window"]
CHUNK = 8
PAGE = 4
MAX_LEN = 64


def _reference_cfg(cfg):
    keys = ("vocab_size", "hidden_size", "num_layers", "layer_types",
            "num_heads", "num_kv_heads", "head_dim", "sliding_window",
            "rope_theta", "layer_norm_eps", "intermediate_size",
            "num_experts_published", "num_experts_per_tok",
            "num_shared_experts", "logit_scale", "initializer_range")
    out = {k: getattr(cfg, k) for k in keys}
    out["held_experts"] = list(cfg.held_experts)
    return out


def _install(model, weights):
    named = dict(model.named_parameters())
    assert set(named) == set(weights)
    for name, p in named.items():
        assert tuple(p.shape) == tuple(weights[name].shape), name
        p._data_ = weights[name]


@pytest.fixture(scope="module")
def tiny():
    """(model, reference config, weights, reference runner)."""
    cfg = CohereMoeConfig(**TINY)
    model = CohereMoeForCausalLM(cfg)
    model.eval()
    rcfg = _reference_cfg(cfg)
    weights = refc.make_weights(ref.weight_spec(rcfg), 2**31 + 9,
                                jnp.float32)
    _install(model, weights)
    return model, rcfg, weights, refrun.ServeReference("cohere_moe", rcfg)


@pytest.fixture
def tick_flag():
    saved = _flags._FLAGS["FLAGS_compiled_tick"]
    yield _flags._FLAGS
    _flags._FLAGS["FLAGS_compiled_tick"] = saved


def _cfg(**kw):
    base = dict(num_slots=4, max_seq_len=MAX_LEN, page_size=PAGE,
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
    ids = _prompts([37, 37], seed=1)        # 37 tokens: 4.6 windows
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(np.stack(ids)))._data_)
    for row, seq in zip(got, ids):
        np.testing.assert_allclose(
            row, _reference_logits(runner, weights, seq), atol=5e-5)


def test_generate_refuses_dense_caches_by_name(tiny):
    model = tiny[0]
    ids = paddle.to_tensor(np.stack(_prompts([5], seed=2)))
    with pytest.raises(NotImplementedError, match="sliding_attention"):
        model.generate(ids, max_new_tokens=3)
    with paddle.no_grad():                  # the cache-free lane works
        out = model.generate(ids, max_new_tokens=3, use_cache=False)
    assert tuple(out.shape) == (1, 8)


# (b) ------------------------------------------------------------------
def test_engine_logits_match_reference_step_by_step(tiny):
    """Through ``Engine`` (its eager lane: a forward hook is what shows
    the logits): a 21-token prompt prefilled in chunks of 8, 8 and a
    ragged 5 — across the window of 8, chunk and page boundaries — then
    30 decoded tokens, each call's logits against the reference's one
    full forward."""
    model, _, weights, runner = tiny
    seen = []
    hook = model.register_forward_post_hook(
        lambda layer, inputs, out: seen.append(np.asarray(out._data_)))
    try:
        with pytest.warns(UserWarning, match="hooks"):
            with Engine(model, _cfg()) as eng:
                prompt = _prompts([21], seed=3)[0]
                out = eng.generate(prompt, max_new_tokens=30)
    finally:
        hook.remove()
    ids = np.concatenate([prompt, out.output_ids])
    want = _reference_logits(runner, weights, ids)
    chunks = [c for c in seen if c.shape[1] == CHUNK]
    steps = [c for c in seen if c.shape[1] == 1]
    assert len(chunks) == 3 and len(steps) == 29
    pos = 0
    for c, n in zip(chunks, (8, 8, 5)):
        np.testing.assert_allclose(c[0, :n], want[pos:pos + n], atol=5e-5)
        pos += n
    for i, c in enumerate(steps):
        np.testing.assert_allclose(c[0, 0], want[21 + i], atol=5e-5)
    np.testing.assert_array_equal(
        out.output_ids, want[20:20 + 30].argmax(-1))


# (c) ------------------------------------------------------------------
def _mlp_share(cfg_kw, weights, layer, held):
    """The program's expert layer holding ``held``, with layer
    ``layer``'s seeded weights (the full stack cut to the run)."""
    cfg = CohereMoeConfig(**dict(cfg_kw, held_experts=held))
    mlp = CohereSparseMLP(cfg)
    p = f"model.layers.{layer}.mlp."
    lo, hi = held[0], held[0] + held[1]
    mlp.gate.weight._data_ = weights[p + "gate.weight"]
    for name in ("gate_proj", "up_proj", "down_proj"):
        getattr(mlp.experts, name)._data_ = \
            weights[p + "experts." + name][lo:hi]
        getattr(mlp.shared_experts, name)._data_ = \
            weights[p + "shared_experts." + name]
    return mlp


def test_the_shares_add_up_to_the_uncut_layer():
    """Over the 4 shares of 4 experts, the layer's partial results with
    the shared term counted once sum to the uncut reference's layer."""
    full = dict(TINY, held_experts=(0, 16))
    rcfg = _reference_cfg(CohereMoeConfig(**full))
    weights = refc.make_weights(ref.weight_spec(rcfg), 77, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 19, 64), jnp.float32)
    w = refrun._layer_weights(ref, rcfg, weights, 1)
    want = np.asarray(ref._ffn(x, w, rcfg, refc.mm_f32))
    shared = np.asarray(ref._ffn(x, w, dict(rcfg, held_experts=[0, 0]),
                                 refc.mm_f32))
    total = np.zeros_like(want)
    with paddle.no_grad():
        for first in (0, 4, 8, 12):
            mlp = _mlp_share(TINY, weights, 1, (first, 4))
            part = np.asarray(mlp(paddle.to_tensor(x))._data_)
            # a share alone is not the layer
            assert np.abs(part - want).max() > 1e-3
            total += part - shared
    np.testing.assert_allclose(total + shared, want, atol=2e-5)


# (d) ------------------------------------------------------------------
def test_every_token_to_one_expert_drops_nothing():
    """No capacity: a router that sends every token to the same expert
    fills one group with all of them and computes every one."""
    kw = dict(TINY, num_experts_per_tok=1)
    rcfg = _reference_cfg(CohereMoeConfig(**kw))
    weights = dict(refc.make_weights(ref.weight_spec(rcfg), 5, jnp.float32))
    name = "model.layers.0.mlp.gate.weight"
    weights[name] = jnp.zeros_like(weights[name]).at[:, 2].set(1.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (3, 33, 64)))
    w = refrun._layer_weights(ref, rcfg, weights, 0)
    want = np.asarray(ref._ffn(x, w, rcfg, refc.mm_f32))
    mlp = _mlp_share(kw, weights, 0, (0, 4))
    cache = {"valid_len": paddle.to_tensor(np.full(3, 33, np.int32))}
    with paddle.no_grad():
        got = np.asarray(mlp(paddle.to_tensor(x), cache=cache)._data_)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(cache["moe_counts"]._data_),
                                  [0, 0, 99, 0])


# the grouped product: kernel (interpreter) against the XLA lane --------
def _pairs(tokens, k, n_experts, held, seed, concentrate=None):
    rng = np.random.default_rng(seed)
    if concentrate is None:
        experts = np.stack([rng.permutation(n_experts)[:k]
                            for _ in range(tokens)])
    else:
        experts = np.tile(np.asarray(concentrate)[None, :k], (tokens, 1))
    gates = rng.random((tokens, k)).astype(np.float32)
    gates /= gates.sum(-1, keepdims=True)
    return jnp.asarray(experts, jnp.int32), jnp.asarray(gates)


def _dense_routed(x, experts, gates, wg, wu, wd, held):
    """The held experts' part, expert by expert, float64 on the host."""
    x = np.asarray(x, np.float64)
    out = np.zeros_like(x)
    for e in range(held[1]):
        g = (np.asarray(gates, np.float64)
             * (np.asarray(experts) == held[0] + e)).sum(-1)
        a = x @ np.asarray(wg[e], np.float64)
        f = (a / (1 + np.exp(-a)) * (x @ np.asarray(wu[e], np.float64))) \
            @ np.asarray(wd[e], np.float64)
        out += g[:, None] * f
    return out


@pytest.mark.parametrize("tokens, concentrate", [
    (8, None),              # a tick's few rows: uneven groups, tile 16
    (8, (9, 1, 5)),         # all to expert 1 of the held: empty groups
    (200, None),            # a chunk: tile 128
    (200, (0, 3, 12)),      # one full group of 200 and one more
    (5, (8, 9, 10)),        # no held expert hit at all
], ids=["tick-uneven", "tick-one-group", "chunk-uneven", "chunk-full-group",
        "none-held"])
def test_expert_gmm_kernel_matches_xla_lane(monkeypatch, tokens,
                                            concentrate):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    h, f, held = 128, 256, (0, 4)
    keys = jax.random.split(jax.random.PRNGKey(tokens), 4)
    x = jax.random.normal(keys[0], (tokens, h), jnp.float32)
    wg = jax.random.normal(keys[1], (4, h, f), jnp.float32) * 0.1
    wu = jax.random.normal(keys[2], (4, h, f), jnp.float32) * 0.1
    wd = jax.random.normal(keys[3], (4, f, h), jnp.float32) * 0.1
    experts, gates = _pairs(tokens, 3, 16, held, tokens, concentrate)
    assert moe.kernel_hosts(x, (wg, wu), moe.row_tile(tokens))
    got_k, counts_k = moe.routed_experts(x, experts, gates, wg, wu, wd,
                                         held, lane="kernel")
    got_x, counts_x = moe.routed_experts(x, experts, gates, wg, wu, wd,
                                         held, lane="xla")
    want = _dense_routed(x, experts, gates, wg, wu, wd, held)
    np.testing.assert_allclose(np.asarray(got_k), want, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got_x), want, atol=2e-4)
    np.testing.assert_array_equal(np.asarray(counts_k),
                                  np.asarray(counts_x))
    in_held = (np.asarray(experts) < 4)
    assert int(np.asarray(counts_k).sum()) == int(in_held.sum())


def test_group_pairs_skips_dead_rows_in_counts_only():
    experts, gates = _pairs(6, 2, 8, (0, 8), 1)
    valid = jnp.asarray([True, False, True, True, False, True])
    g_all = moe.group_pairs(experts, gates, (0, 8), 16)
    g_live = moe.group_pairs(experts, gates, (0, 8), 16, valid)
    assert int(g_all.counts.sum()) == 12 and int(g_live.counts.sum()) == 8
    np.testing.assert_array_equal(np.asarray(g_all.pair_row),
                                  np.asarray(g_live.pair_row))


# (e) ------------------------------------------------------------------
def _serve(model, waves, compiled, cfg=None, sampling=None, max_new=20):
    _flags._FLAGS["FLAGS_compiled_tick"] = compiled
    outs = []
    with Engine(model, cfg or _cfg()) as eng:
        for wave in waves:
            with eng._work:
                futs = [eng.submit(p, max_new_tokens=max_new,
                                   sampling=sampling) for p in wave]
            outs.append([f.result(timeout=600).output_ids for f in futs])
        stats = serving_stats()
    return outs, stats


@pytest.mark.parametrize("sampling", [
    None, SamplingParams(temperature=1.0, top_k=50, seed=11)],
    ids=["greedy", "seeded"])
def test_compiled_lanes_match_eager_lane(tiny, tick_flag, sampling):
    model = tiny[0]
    waves = [_prompts([37, 21, 9], seed=4), _prompts([30, 5], seed=5)]
    eager, st_e = _serve(model, waves, False, sampling=sampling)
    comp, st_c = _serve(model, waves, True, sampling=sampling)
    for a, b in zip(eager, comp):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
    assert st_e["tick_compiled_hits"] == 0
    assert st_c["tick_compiled_hits"] > 0 and st_c["tick_fallbacks"] == 0
    assert st_c["prefill_compiled_hits"] > 0
    assert st_c["prefill_fallbacks"] == 0
    # routing counts left the programs with their outputs: 4 of 16
    # experts held, 4 a token -> about one pair a token
    assert 0.5 < st_c["expert_pairs_per_token"] < 1.5
    # three window layers in four: a ring of 8/4 + 8/4 + 1 = 5 pages
    assert 0 < st_c["window_pages_held_share"] < 1
    assert st_c["window_pages_reclaimed"] > 0


def test_greedy_served_tokens_are_the_references_first_choice(tiny,
                                                              tick_flag):
    model, _, weights, runner = tiny
    prompts = _prompts([37, 12], seed=6)
    outs, _ = _serve(model, [prompts], True, max_new=24)
    for prompt, out in zip(prompts, outs[0]):
        ids = np.concatenate([prompt, out])
        want = _reference_logits(runner, weights, ids)
        n = len(prompt)
        np.testing.assert_array_equal(out, want[n - 1:-1].argmax(-1))


def test_a_chunk_calls_token_budget_bounds_its_rows(tiny, tick_flag,
                                                    monkeypatch):
    from paddle_tpu.serving import engine as engine_mod
    model = tiny[0]
    waves = [_prompts([20, 20, 20, 20], seed=7)]
    free, _ = _serve(model, waves, True)
    rows = []
    _flags._FLAGS["FLAGS_compiled_tick"] = True
    chunk = _cfg().prefill_chunk_tokens
    monkeypatch.setattr(engine_mod, "PREFILL_CALL_TOKENS", 2 * chunk)
    with Engine(model, _cfg()) as eng:
        assert eng._tick.prefill_buckets() == [1, 2]
        run = eng._tick.run_prefill
        eng._tick.run_prefill = lambda m, *a, **k: (
            rows.append(m[0]), run(m, *a, **k))[1]
        with eng._work:
            futs = [eng.submit(p, max_new_tokens=20) for p in waves[0]]
        capped = [f.result(timeout=600).output_ids for f in futs]
    assert rows and max(rows) == 2
    for x, y in zip(free[0], capped):
        np.testing.assert_array_equal(x, y)


def test_compiled_tick_runs_both_kernels(tick_flag, monkeypatch):
    """At shapes the kernels host (8 kv heads of 16 in pages of 8; hidden
    and expert width 128) and with Mosaic kernels on (here: the
    interpreter), the tick's attention goes through ``paged_decode`` with
    a window in the window layers and its experts through ``expert_gmm``
    — and serves what the XLA lanes serve."""
    from paddle_tpu.utils import monitor
    kw = dict(TINY, hidden_size=128, num_heads=16, num_kv_heads=8,
              intermediate_size=128, num_layers=4, sliding_window=16)
    cfg = CohereMoeConfig(**kw)
    model = CohereMoeForCausalLM(cfg)
    model.eval()
    _install(model, refc.make_weights(
        ref.weight_spec(_reference_cfg(cfg)), 3, jnp.float32))
    scfg = _cfg(page_size=8, num_slots=2)
    waves = [_prompts([29, 12], seed=9)]
    plain, _ = _serve(model, waves, True, cfg=scfg, max_new=16)
    before = dict(monitor.all_stats())
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    with_kernels, st = _serve(model, waves, True, cfg=scfg, max_new=16)
    after = monitor.all_stats()
    for x, y in zip(plain[0], with_kernels[0]):
        np.testing.assert_array_equal(x, y)
    for name in ("pallas.paged_decode.kernel", "pallas.expert_gmm.kernel"):
        assert after.get(name, 0) > before.get(name, 0), name
    assert st["tick_fallbacks"] == 0 and st["prefill_fallbacks"] == 0


# (f) ------------------------------------------------------------------
def test_reused_slot_sees_none_of_its_former_tenants_window(tiny,
                                                            tick_flag):
    model = tiny[0]
    long_one, probe = _prompts([50, 13], seed=8)
    one_slot = _cfg(num_slots=1)
    fresh, _ = _serve(model, [[probe]], True, cfg=one_slot)
    reused, _ = _serve(model, [[long_one], [probe]], True, cfg=one_slot)
    np.testing.assert_array_equal(fresh[0][0], reused[1][0])


def _cache(**kw):
    base = dict(num_layers=4, num_slots=3, max_len=64, num_kv_heads=2,
                head_dim=16, page_size=4,
                layer_windows=[8, 8, 8, None], window_slack=8)
    base.update(kw)
    return PagedKVCache(**base)


def test_a_slot_never_holds_more_than_its_ring():
    cache = _cache()
    assert cache.ring_pages == 8 // 4 + 8 // 4 + 1
    assert cache.layers[0]["k_pool"].shape[0] == 3 * cache.ring_pages + 1
    assert cache.layers[3]["k_pool"].shape[0] == 3 * 16 + 1
    a = cache.allocate(16)
    b = cache.allocate(10)
    for pos in range(64):
        cache.ensure_capacity(a, pos)
        assert cache.window_pages_held(a) == min(pos // 4 + 1,
                                                 cache.ring_pages)
    for pos in range(40):
        cache.ensure_capacity(b, pos)
    assert cache.window_pages_held(a) == cache.ring_pages
    assert cache.pages_in_use == 16 + 10
    assert cache.window_pages_in_use == 2 * cache.ring_pages
    # the two slots' rings share no page and never name the scratch page
    ring_a, ring_b = cache.table_w[a], cache.table_w[b]
    assert not set(ring_a) & set(ring_b) and 0 not in ring_a
    # logical page p lives at entry p % ring: the table is not rewritten
    before = cache.table_w.copy()
    cache.ensure_capacity(a, 63)
    np.testing.assert_array_equal(before, cache.table_w)


def test_release_returns_every_page_of_both_kinds():
    cache = _cache()
    free_full, free_w = cache.free_page_count, len(cache._free_w)
    slots = [cache.allocate(16) for _ in range(3)]
    assert cache.allocate(1) is None
    for s in slots:
        for pos in range(0, 64, 4):
            cache.ensure_capacity(s, pos)
    assert cache.free_page_count == 0
    assert cache.window_pages_promised == 3 * cache.ring_pages
    for s in slots:
        cache.release(s)
    assert cache.free_page_count == free_full
    assert len(cache._free_w) == free_w
    assert not cache.table_w.any() and not cache.table.any()
    assert cache.window_pages_promised == 0


def test_one_kind_of_paged_layer_builds_what_it_always_built():
    plain = PagedKVCache(2, 3, 64, 2, 16, page_size=4)
    assert plain.ring_pages == 0 and plain.window is None
    assert plain.window_table_array() is None
    assert plain.prefill_window_table([0], 2) is None
    assert "window" not in plain.layers[0]
    # a window as long as the slot keeps what a full layer keeps: one
    # table, and the mask alone
    wide = _cache(layer_windows=[60, 60, 60, None])
    assert wide.ring_pages == 0 and wide.layers[0]["window"] == 60
    with pytest.raises(ValueError, match="one ring table"):
        _cache(layer_windows=[8, 12, 8, None])


# (g) ------------------------------------------------------------------
def test_typed_refusals_name_the_layer_kind(tiny):
    model = tiny[0]
    with pytest.raises(WindowLayerError, match="sliding_attention"):
        Engine(model, ServingConfig(enable_prefix_cache=True))
    with pytest.raises(WindowLayerError, match="draft_model"):
        Engine(model, _cfg(draft_model=model, speculation_k=2))
    from paddle_tpu.models import LlamaForCausalLM, llama_config
    draft = LlamaForCausalLM(llama_config("tiny", vocab_size=VOCAB,
                                          max_seq_len=MAX_LEN))
    with pytest.raises(WindowLayerError, match="speculation_k"):
        Engine(model, _cfg(draft_model=draft, speculation_k=2))
    with pytest.raises(WindowLayerError, match="role"):
        Engine(model, _cfg(role="prefill"))
    eng = Engine(model, _cfg())
    with pytest.raises(WindowLayerError, match="migrator"):
        eng.migrator = lambda *a: None
    cache = _cache()
    slot = cache.allocate(4)
    for call in (lambda: cache.export_pages(slot),
                 lambda: cache.rollback(slot, 0),
                 lambda: cache.make_shared(slot, 0)):
        with pytest.raises(WindowLayerError, match="ring"):
            call()
