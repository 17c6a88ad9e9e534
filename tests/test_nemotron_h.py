"""``nemotron_h`` (a per-layer stack of Mamba-2 mixers with grouped B/C
and a grouped gated norm, GQA attention without rotation, and latent
relu² expert layers that hold nothing between calls) against the plain
reference of ``chipbench/reference/`` on seeded weights, at a tiny size
on the CPU: the whole-sequence forward, prefill in chunks then decoding
through state rows and pages (XLA lanes, and the relu² kernel with the
state update in the interpreter), the relu² grouped product against its
XLA lane, the grouped norm and the head-to-group map by hand, the
routing with bias and factor, the shares of the latent expert layer
adding up, what the cache keeps of a layer that holds nothing, slot
reuse, and the typed refusals."""
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import granite_hybrid
from paddle_tpu.models import sparse_experts as se
from paddle_tpu.models.nemotron_h import (
    TINY_NEMOTRON_H, NemotronHConfig, NemotronHForCausalLM,
    NemotronLatentMoE)
from paddle_tpu.pallas import moe, ssm
from paddle_tpu.serving import (Engine, PagedKVCache, RecurrentStateError,
                                ServingConfig, serving_stats)
from paddle_tpu.utils import flags as _flags
from paddle_tpu.utils import monitor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "chipbench"))
from reference import common as refc            # noqa: E402
from reference import nemotron_h as ref         # noqa: E402
from reference import run as refrun             # noqa: E402

# hidden 64, [moe, mamba, moe, mamba, attention]; Mamba 8 heads of 16 in
# 2 groups, state 16; attention 4 / 2 heads of 16; 16 experts, 6 a token,
# 8 held, latent 32, width 48, shared 96
TINY = dict(TINY_NEMOTRON_H)
VOCAB = TINY["vocab_size"]
CHUNK = 8
PAGE = 4
MAX_LEN = 64
# the widths the relu² kernel and the state update's kernel take
KERNEL_WIDTHS = dict(moe_latent_size=128, moe_intermediate_size=128)


def _reference_cfg(cfg):
    keys = ("vocab_size", "hidden_size", "num_layers", "layer_types",
            "num_heads", "num_kv_heads", "head_dim", "rms_norm_eps",
            "initializer_range", "mamba_n_heads", "mamba_d_head",
            "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
            "moe_latent_size", "num_experts_published",
            "num_experts_per_tok", "num_shared_experts",
            "routed_scaling_factor")
    out = {k: getattr(cfg, k) for k in keys}
    out["held_experts"] = list(cfg.held_experts)
    out["intermediate_size"] = cfg.moe_intermediate_size
    out["shared_intermediate_size"] = \
        cfg.moe_shared_expert_intermediate_size
    return out


def _install(model, weights):
    named = dict(model.named_parameters())
    assert set(named) == set(weights)
    for name, p in named.items():
        assert tuple(p.shape) == tuple(weights[name].shape), name
        p._data_ = weights[name]


def _build(seed=2**31 + 5, **kw):
    cfg = NemotronHConfig(**dict(TINY, **kw))
    model = NemotronHForCausalLM(cfg)
    model.eval()
    rcfg = _reference_cfg(cfg)
    weights = refc.make_weights(ref.weight_spec(rcfg), seed, jnp.float32)
    _install(model, weights)
    return model, rcfg, weights, refrun.ServeReference("nemotron_h", rcfg)


@pytest.fixture(scope="module")
def tiny():
    """(model, reference config, weights, reference runner)."""
    return _build()


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


def _serve(model, waves, compiled, cfg=None, max_new=20):
    _flags._FLAGS["FLAGS_compiled_tick"] = compiled
    outs = []
    with Engine(model, cfg or _cfg()) as eng:
        for wave in waves:
            with eng._work:
                futs = [eng.submit(p, max_new_tokens=max_new) for p in wave]
            outs.append([f.result(timeout=600).output_ids for f in futs])
        cache = eng.cache
    return outs, serving_stats(), cache


# (a) the model against the reference --------------------------------------
def test_whole_sequence_logits_match_reference(tiny):
    model, _, weights, runner = tiny
    ids = _prompts([37, 37], seed=1)
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(np.stack(ids)))._data_)
    for row, seq in zip(got, ids):
        np.testing.assert_allclose(
            row, _reference_logits(runner, weights, seq), atol=5e-5)


def test_generate_decodes_against_its_own_caches(tiny):
    """``generate`` with the model's own dense caches (keys and values,
    state rows, nothing for an expert layer) gives what the cache-free
    lane gives."""
    model = tiny[0]
    ids = paddle.to_tensor(np.stack(_prompts([13], seed=2)))
    with paddle.no_grad():
        cached = model.generate(ids, max_new_tokens=10).numpy()
        plain = model.generate(ids, max_new_tokens=10,
                               use_cache=False).numpy()
    np.testing.assert_array_equal(cached, plain)


def test_engine_logits_match_reference_step_by_step(tiny, tick_flag):
    """Through ``Engine``'s eager lane (a forward hook is what shows the
    logits): a 21-token prompt prefilled in chunks of 8, 8 and a ragged 5
    through state rows and pages, then 20 decoded tokens, each call's
    logits against the reference's one full forward."""
    model, _, weights, runner = tiny
    _flags._FLAGS["FLAGS_compiled_tick"] = False
    seen = []
    hook = model.register_forward_post_hook(
        lambda layer, inputs, out: seen.append(np.asarray(out._data_)))
    try:
        with Engine(model, _cfg()) as eng:
            prompt = _prompts([21], seed=3)[0]
            out = eng.generate(prompt, max_new_tokens=20)
    finally:
        hook.remove()
    ids = np.concatenate([prompt, out.output_ids])
    want = _reference_logits(runner, weights, ids)
    chunks = [c for c in seen if c.shape[1] == CHUNK]
    steps = [c for c in seen if c.shape[1] == 1]
    assert len(chunks) == 3 and len(steps) == 19
    pos = 0
    for c, n in zip(chunks, (8, 8, 5)):
        np.testing.assert_allclose(c[0, :n], want[pos:pos + n], atol=5e-5)
        pos += n
    for i, c in enumerate(steps):
        np.testing.assert_allclose(c[0, 0], want[21 + i], atol=5e-5)


@pytest.mark.parametrize("lane", ["xla_lane", "kernel"])
def test_greedy_served_tokens_are_the_references_first_choice(
        tick_flag, monkeypatch, lane):
    """Prefill in chunks, then decoding through state rows and pages in
    the compiled tick — by the XLA lanes, and with the relu² experts and
    the state update on their Pallas kernels in the interpreter (latent
    and expert width 128) — serves the reference's argmax."""
    if lane == "kernel":
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    # the mixer is jitted: the lane it takes is part of its trace
    granite_hybrid._mamba2_mix.clear_cache()
    try:
        model, _, weights, runner = _build(**KERNEL_WIDTHS)
        before = monitor.all_stats()
        prompts = _prompts([27, 12], seed=6)
        outs, st, _ = _serve(model, [prompts], True, max_new=10)
    finally:
        granite_hybrid._mamba2_mix.clear_cache()
    after = monitor.all_stats()
    for name in ("pallas.expert_gmm.relu2.", "pallas.ssm_update."):
        assert after.get(name + lane, 0) > before.get(name + lane, 0), name
    assert st["tick_fallbacks"] == 0 and st["prefill_fallbacks"] == 0
    for prompt, out in zip(prompts, outs[0]):
        ids = np.concatenate([prompt, out])
        want = _reference_logits(runner, weights, ids)
        n = len(prompt)
        np.testing.assert_array_equal(out, want[n - 1:-1].argmax(-1))


# (b) the pieces by hand -------------------------------------------------
def test_relu2_expert_gmm_kernel_matches_xla_lane(monkeypatch):
    """The ungated product with the ``relu(.)^2`` epilogue: the kernel
    (interpreter) against ``expert_gmm_xla``, over groups padded to
    whole tiles; and ``routed_experts`` with ``act="relu2"`` against the
    dense sum by hand."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    t, lat, f, held, k = 40, 128, 256, 6, 3
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (t, lat), jnp.float32)
    wu = jax.random.normal(ks[1], (held, lat, f), jnp.float32) * 0.1
    wd = jax.random.normal(ks[2], (held, f, lat), jnp.float32) * 0.1
    experts = jax.random.randint(ks[3], (t, k), 0, 2 * held)
    gates = jax.nn.softmax(jax.random.normal(ks[4], (t, k)), -1)
    tm = moe.row_tile(t)
    groups = moe.group_pairs(experts, gates, (2, held), tm)
    xs = jnp.concatenate([x, jnp.zeros((1, lat))])[groups.row_token]
    got = moe.expert_gmm(xs, (wu,), groups, tm, lane="kernel", act="relu2")
    want = moe.expert_gmm_xla(xs, (wu,), groups.sizes, act="relu2")
    real = np.asarray(groups.row_token) < t
    np.testing.assert_allclose(np.asarray(got)[real],
                               np.asarray(want)[real], rtol=1e-5,
                               atol=1e-5)
    y, counts = moe.routed_experts(x, experts, gates, None, wu, wd,
                                   (2, held), act="relu2")
    dense = np.zeros((t, lat), np.float32)
    for i in range(t):
        for j in range(k):
            e = int(experts[i, j]) - 2
            if 0 <= e < held:
                h = np.maximum(np.asarray(x[i]) @ np.asarray(wu[e]), 0) ** 2
                dense[i] += float(gates[i, j]) * (h @ np.asarray(wd[e]))
    np.testing.assert_allclose(np.asarray(y), dense, rtol=1e-4, atol=1e-4)
    assert int(counts.sum()) == int(((experts >= 2)
                                     & (experts < 2 + held)).sum())
    with pytest.raises(ValueError, match="relu2"):
        moe.routed_experts(x, experts, gates, wu, wu, wd, (2, held),
                           act="relu2")


@pytest.mark.parametrize("act,count", [(None, 2), ("swiglu", 1),
                                       ("relu2", 2)])
def test_expert_gmm_refuses_act_that_disagrees_with_weights(act, count):
    """The activation is said by ``act`` alone: two matrices with
    ``act`` None are refused, not taken for a gated pair."""
    groups = moe.group_pairs(jnp.zeros((4, 1), jnp.int32),
                             jnp.ones((4, 1)), (0, 2), 16)
    w = jnp.zeros((2, 8, 128), jnp.float32)
    with pytest.raises(ValueError, match="weight matrices"):
        moe.expert_gmm(jnp.zeros((32, 8)), (w,) * count, groups, 16,
                       lane="xla", act=act)
    assert set(se.EXPERT_ACTS) == {a for a in moe.ACTS if a}


def _mix(norm_groups, proj, leaves):
    dims = granite_hybrid._MixDims(8, 16, 2, 16, 4, 8, 1e-5, norm_groups)
    return granite_hybrid._mamba2_mix(proj, *leaves, None, None, None,
                                      None, dims=dims)[0]


def _mix_inputs():
    d_inner, conv = 128, 128 + 2 * 2 * 16
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    proj = jax.random.normal(ks[0], (2, 11, d_inner + conv + 8))
    leaves = (jax.random.normal(ks[1], (conv, 4)) * 0.5, jnp.zeros(conv),
              jnp.zeros(8), jnp.linspace(0.0, 2.0, 8), jnp.ones(8),
              1.0 + 0.1 * jax.random.normal(ks[2], (d_inner,)))
    return proj, leaves


def test_grouped_gated_norm_by_hand():
    """With ``norm_groups`` G the gated output is normalised over each of
    G groups of channels on its own; with 1, over all of them (Granite's
    form, whose trace is the one it was: the families' pinned ticks)."""
    proj, leaves = _mix_inputs()
    one, two = _mix(1, proj, leaves), _mix(2, proj, leaves)
    w = leaves[-1]

    def undo(y):          # the norm's scale off: a unit-RMS vector back
        return np.asarray(y / w)

    u1, u2 = undo(one), undo(two)
    # unit RMS but for eps (1e-5 against mean squares of ~0.01)
    np.testing.assert_allclose(np.sqrt(np.mean(u1 ** 2, -1)), 1.0,
                               rtol=2e-3)
    for g in range(2):
        part = u2[..., 64 * g:64 * (g + 1)]
        np.testing.assert_allclose(np.sqrt(np.mean(part ** 2, -1)), 1.0,
                                   rtol=2e-3)
    # the same gated vector, only the norm differs: each group of the
    # grouped output is the whole's scaled by one factor
    ratio = u2[..., :64] / u1[..., :64]
    np.testing.assert_allclose(ratio, ratio[..., :1] * np.ones_like(ratio),
                               rtol=1e-3)
    assert not np.allclose(u1, u2, rtol=1e-3)


def test_heads_read_their_groups_in_runs():
    """Head i reads B and C of group i // (H / G), as the reference's map
    says; not group i mod G."""
    heads, g = 8, 2
    bm = jnp.arange(g, dtype=jnp.float32)[None, :, None] \
        * jnp.ones((1, g, 4))
    got = np.asarray(ssm._heads_of_groups(bm, heads))[0, :, 0]
    np.testing.assert_array_equal(got, np.arange(heads) // (heads // g))
    np.testing.assert_array_equal(got, [0, 0, 0, 0, 1, 1, 1, 1])


def test_routing_with_bias_and_factor_matches_reference(tiny):
    """The layer's own ``route`` (bias in the choice, factor 5 on the
    normalised gates) chooses and weighs as the reference's routed part
    does."""
    model, rcfg, weights, _ = tiny
    mlp = model.backbone.layers[0].mixer
    w = refrun._layer_weights(ref, rcfg, weights, 0)
    y = jax.random.normal(jax.random.PRNGKey(5), (1, 30, 64))
    _, _, want_idx = ref.routed_part(y, w, rcfg)
    router = tuple(t._data_ for t in mlp.router_leaves())
    normed = ref._rms(y, w["norm.weight"], rcfg["rms_norm_eps"])[0]
    idx, gates = mlp.route(normed, *router)
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                  np.sort(np.asarray(want_idx[0]), -1))
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 5.0, rtol=1e-5)
    plain, _ = moe.route_sigmoid_topk(moe.router_logits(normed, router[0]),
                                      rcfg["num_experts_per_tok"])
    assert (np.sort(np.asarray(plain), -1)
            != np.sort(np.asarray(idx), -1)).any()


def _moe_share(weights, layer, held):
    """The program's latent expert layer holding ``held``, with layer
    ``layer``'s seeded weights (the full stack cut to the run)."""
    cfg = NemotronHConfig(**dict(TINY, held_experts=held))
    mlp = NemotronLatentMoE(cfg)
    p = f"backbone.layers.{layer}.mixer."
    lo, hi = held[0], held[0] + held[1]
    for name, leaf in mlp.named_parameters():
        full = weights[p + name]
        leaf._data_ = full[lo:hi] if name.startswith("experts.") else full
    return mlp


def test_the_shares_add_up_to_the_uncut_layer():
    """Over the 4 shares of 4 experts, each through ``W_lat_up``, the
    layer's partial results with the shared expert counted once sum to
    the uncut reference's layer."""
    full = dict(TINY, held_experts=(0, 16))
    rcfg = _reference_cfg(NemotronHConfig(**full))
    weights = refc.make_weights(ref.weight_spec(rcfg), 77, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 19, 64), jnp.float32)
    w = refrun._layer_weights(ref, rcfg, weights, 0)
    want = np.asarray(ref._moe(x, w, rcfg, refc.mm_f32))
    shared = np.asarray(ref._moe(x, w, dict(rcfg, held_experts=[0, 0]),
                                 refc.mm_f32))
    total = np.zeros_like(want)
    with paddle.no_grad():
        for first in (0, 4, 8, 12):
            mlp = _moe_share(weights, 0, (first, 4))
            part = np.asarray(mlp(paddle.to_tensor(x))._data_)
            assert np.abs(part - want).max() > 1e-3
            total += part - shared
    np.testing.assert_allclose(total + shared, want, atol=5e-5)


# (c) what the cache keeps -------------------------------------------------
def test_feed_forward_layers_claim_no_pool_and_no_row(tiny, tick_flag):
    """The two expert layers of the tiny stack hold nothing: the cache
    builds K/V pools for its one attention layer and state rows for its
    two Mamba layers and nothing else; the gauge and ``serving_stats``
    say so, and the expert layers still count their routing."""
    model = tiny[0]
    outs, st, cache = _serve(model, [_prompts([19, 9], seed=7)], True,
                             max_new=6)
    assert cache.layers_without_store == 2
    assert st["kv_layers_without_store"] == 2
    assert cache.pools == 2 and st["kv_pools"] == 2
    kinds = model.config.layer_types
    for i, (kind, lay) in enumerate(zip(kinds, cache.layers)):
        if kind == "moe":
            assert set(lay) == {"valid_len"}, (i, set(lay))
            assert cache._keys[i] == ()
    assert set(cache.read_state(0)) == {1, 3}
    state = NemotronHConfig(**TINY).layer_states("float32")[1]
    per_row = sum(int(np.prod(s)) * np.dtype(d).itemsize
                  for s, d, *_ in state.values())
    assert st["state_bytes"] == 2 * (4 + 1) * per_row
    assert st["expert_pairs_per_token"] > 0


def test_state_rows_are_stored_lane_packed(tiny, tick_flag):
    """8 heads of 16 in 2 groups: 4 heads a row of lanes (a tile never
    spans two groups), each slot's row ``[2, 16, 64]``; ``read_state``
    hands it back ``[8, 16, 16]``, the stored row unpacked."""
    state = NemotronHConfig(**TINY).layer_states("float32")[1]
    assert state["ssm_state"] == ((2, 16, 64), "float32", (8, 16, 16))
    outs, st, cache = _serve(tiny[0], [_prompts([15], seed=9)], True,
                             max_new=4)
    assert st["state_heads_per_tile"] == cache.state_heads_per_tile == 4
    rows = cache.read_state(0)
    assert set(rows) == {1, 3}
    for i, arrays in rows.items():
        stored = np.asarray(cache.layers[i]["ssm_state"]._data_[0])
        assert stored.shape == (2, 16, 64)
        np.testing.assert_array_equal(arrays["ssm_state"],
                                      ssm.unpack_state(stored, 8))
        assert np.abs(stored).max() > 0


def test_reused_slot_starts_from_an_empty_state(tiny, tick_flag):
    """One slot, three layer kinds: the second tenant's tokens are those
    a fresh engine serves it (its state rows reset, its pages its own,
    the expert layers holding nothing to carry over)."""
    model = tiny[0]
    long_one, probe = _prompts([40, 19], seed=8)
    one_slot = _cfg(num_slots=1)
    fresh, _, _ = _serve(model, [[probe]], True, cfg=one_slot)
    reused, st, _ = _serve(model, [[long_one], [probe]], True, cfg=one_slot)
    np.testing.assert_array_equal(fresh[0][0], reused[1][0])
    assert st["state_resets"] == 2


def test_compiled_lanes_match_eager_lane(tiny, tick_flag):
    model = tiny[0]
    waves = [_prompts([30, 21, 16], seed=4), _prompts([41, 9], seed=5)]
    eager, st_e, _ = _serve(model, waves, False)
    comp, st_c, _ = _serve(model, waves, True)
    for a, b in zip(eager, comp):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
    assert st_e["tick_compiled_hits"] == 0
    assert st_c["tick_compiled_hits"] > 0 and st_c["tick_fallbacks"] == 0
    assert st_c["prefill_fallbacks"] == 0


@pytest.mark.parametrize("kwargs, names", [
    (dict(enable_prefix_cache=True), "enable_prefix_cache"),
    (dict(role="prefill"), "role"),
    (dict(role="decode"), "role"),
], ids=["prefix", "prefill-role", "decode-role"])
def test_refused_at_construction(tiny, kwargs, names):
    with pytest.raises(RecurrentStateError, match=names):
        Engine(tiny[0], _cfg(**kwargs))


def test_refused_speculation_and_migration(tiny):
    model = tiny[0]
    draft = NemotronHForCausalLM(NemotronHConfig(**TINY))
    with pytest.raises(RecurrentStateError):
        Engine(model, _cfg(speculation_k=2, draft_model=draft))
    eng = Engine(model, _cfg())
    with pytest.raises(RecurrentStateError, match="migrator"):
        eng.migrator = lambda *a: None


def test_storeless_layer_needs_no_kv_widths():
    """A cache of state layers and layers that hold nothing asks for no
    K/V widths (the storeless kind is a kind of its own, not ``kv``); a
    layer cannot be both storeless and stateful."""
    state = NemotronHConfig(**TINY).layer_states("float32")[1]
    cache = PagedKVCache(3, 2, 32, page_size=4,
                         layer_states=[state, None, state],
                         layer_storeless=[False, True, False])
    assert cache.layers_without_store == 1 and cache.pools == 0
    assert cache.flat_pools() and len(cache.flat_pools()) == 4
    with pytest.raises(ValueError, match="holds nothing"):
        PagedKVCache(1, 2, 32, page_size=4, layer_states=[state],
                     layer_storeless=[True])
    with pytest.raises(ValueError, match="num_kv_heads"):
        PagedKVCache(2, 2, 32, page_size=4, layer_storeless=[False, True])
