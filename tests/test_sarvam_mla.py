"""``sarvam_mla`` (latent attention over a latent page store, a leading
dense layer, routed experts with a selection bias and a scaling factor,
a shared expert) against the plain reference of ``chipbench/reference/``
on seeded weights, at a tiny size on the CPU: the whole-sequence forward,
prefill in chunks then decoding through latent pages (XLA lane and the
kernel in the interpreter), the absorbed form against the up-projected
one, the yarn frequencies and softmax scale by hand, the routing with
bias and factor, the shares of the expert layer adding up, what of the
page machinery a latent store keeps, and the typed refusals."""
import math
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.incubate.nn import functional as IF
from paddle_tpu.models import sarvam_mla as sm
from paddle_tpu.models.sarvam_mla import (
    TINY_SARVAM_MLA, SarvamMLAConfig, SarvamMLAForCausalLM, SarvamSparseMLP)
from paddle_tpu.pallas import mla, moe
from paddle_tpu.serving import (Engine, LatentStoreError, PagedKVCache,
                                SamplingParams, ServingConfig,
                                serving_stats)
from paddle_tpu.utils import flags as _flags
from paddle_tpu.utils import monitor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "chipbench"))
from reference import common as refc            # noqa: E402
from reference import sarvam_mla as ref         # noqa: E402
from reference import run as refrun             # noqa: E402

# hidden 64, one dense layer then two expert layers, 4 heads (16 nope + 8
# rope, values of 16) over a latent of 32, 16 experts, 4 a token, 1
# shared, 4 held; yarn factor 4 over an original context of 16
TINY = dict(TINY_SARVAM_MLA)
VOCAB = TINY["vocab_size"]
CHUNK = 8
PAGE = 4
MAX_LEN = 64


def _reference_cfg(cfg):
    keys = ("vocab_size", "hidden_size", "num_layers", "num_heads",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "first_k_dense_replace", "num_experts_published",
            "num_experts_per_tok", "num_shared_experts",
            "routed_scaling_factor", "rope_theta", "rope_scaling",
            "rms_norm_eps", "initializer_range")
    out = {k: getattr(cfg, k) for k in keys}
    out["held_experts"] = list(cfg.held_experts)
    out["intermediate_size"] = cfg.moe_intermediate_size
    out["dense_intermediate_size"] = cfg.intermediate_size
    return out


def _install(model, weights):
    named = dict(model.named_parameters())
    assert set(named) == set(weights)
    for name, p in named.items():
        assert tuple(p.shape) == tuple(weights[name].shape), name
        p._data_ = weights[name]


def _build(**kw):
    cfg = SarvamMLAConfig(**dict(TINY, **kw))
    model = SarvamMLAForCausalLM(cfg)
    model.eval()
    rcfg = _reference_cfg(cfg)
    weights = refc.make_weights(ref.weight_spec(rcfg), 2**31 + 9,
                                jnp.float32)
    _install(model, weights)
    return model, rcfg, weights, refrun.ServeReference("sarvam_mla", rcfg)


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


# (a) the model against the reference --------------------------------------
def test_whole_sequence_logits_match_reference(tiny):
    model, _, weights, runner = tiny
    ids = _prompts([37, 37], seed=1)
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(np.stack(ids)))._data_)
    for row, seq in zip(got, ids):
        np.testing.assert_allclose(
            row, _reference_logits(runner, weights, seq), atol=5e-5)


def test_generate_refuses_dense_caches_by_name(tiny):
    model = tiny[0]
    ids = paddle.to_tensor(np.stack(_prompts([5], seed=2)))
    with pytest.raises(NotImplementedError, match="latent row"):
        model.generate(ids, max_new_tokens=3)
    with paddle.no_grad():                  # the cache-free lane works
        out = model.generate(ids, max_new_tokens=3, use_cache=False)
    assert tuple(out.shape) == (1, 8)


def test_engine_logits_match_reference_step_by_step(tiny):
    """Through ``Engine`` (its eager lane: a forward hook is what shows
    the logits): a 21-token prompt prefilled in chunks of 8, 8 and a
    ragged 5 (the up-projected form over latent pages), then 30 decoded
    tokens (the absorbed form, its XLA lane; the kernel serves the same
    tokens in ``test_greedy_served_tokens_...[kernel]``: in the eager
    lane the interpreter's callbacks deadlock against the next eager
    op), each call's logits against the reference's one full forward."""
    model, _, weights, runner = tiny
    seen = []
    hook = model.register_forward_post_hook(
        lambda layer, inputs, out: seen.append(np.asarray(out._data_)))
    before = monitor.all_stats().get("pallas.mla_decode.xla_lane", 0)
    try:
        with pytest.warns(UserWarning, match="hooks"):
            with Engine(model, _cfg()) as eng:
                prompt = _prompts([21], seed=3)[0]
                out = eng.generate(prompt, max_new_tokens=30)
    finally:
        hook.remove()
    assert monitor.all_stats().get("pallas.mla_decode.xla_lane", 0) > before
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


# (b) the two forms of one attention ----------------------------------------
def _latent_inputs(b, s_ctx, h=4, rank=32, nope=16, rope=8, v=16, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    rows = jax.random.normal(keys[0], (b, s_ctx, rank + rope), jnp.float32)
    q = jax.random.normal(keys[1], (b, s_ctx, h, nope + rope), jnp.float32)
    w = jax.random.normal(keys[2], (rank, h * (nope + v)), jnp.float32) * 0.2
    return q, rows, w


def _filled_cache(rows, page, slots=None):
    """A one-layer latent cache with a slot a row of ``rows`` [B, S,
    width], each grown to hold S + 1 positions (nothing written yet)."""
    b, s, width = rows.shape
    cache = PagedKVCache(1, slots or b, 64, page_size=page,
                         layer_latents=[width])
    for _ in range(b):
        slot = cache.allocate(64 // page)
        cache.ensure_capacity(slot, s)
    return cache


@pytest.mark.parametrize("lane", ["xla", "kernel"])
def test_absorbed_decode_equals_up_projected_form(lane, monkeypatch):
    """The same queries over the same cached rows: the chunk read
    (up-projected keys and values, blocked online softmax) and the
    single-token read (absorbed form: XLA lane, kernel in the
    interpreter) agree to float32 rounding, and with the dense formula
    computed on the host."""
    if lane == "kernel":
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    b, s, h, rank, nope, rope, v = 3, 21, 4, 32, 16, 8, 16
    page = 8
    q, rows, w = _latent_inputs(b, s)
    scale = 0.37
    # dense formula, float64
    kv = (np.asarray(rows[..., :rank], np.float64)
          @ np.asarray(w, np.float64)).reshape(b, s, h, nope + v)
    k = np.concatenate([kv[..., :nope], np.broadcast_to(
        np.asarray(rows[:, :, None, rank:], np.float64),
        (b, s, h, rope))], -1)
    sc = np.einsum("bqhd,bkhd->bhqk", np.asarray(q, np.float64), k) * scale
    sc = np.where(np.tril(np.ones((s, s), bool)), sc, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("bhqk,bkhd->bqhd", p, kv[..., nope:])

    cache = _filled_cache(rows, page)
    view = cache.layer_caches()[0]
    T = paddle.to_tensor
    with paddle.no_grad():
        # the whole context as one chunk at offset 0
        chunk = IF.paged_latent_attention(T(q), T(rows), T(w), view,
                                          nope_dim=nope, scale=scale)
        np.testing.assert_allclose(np.asarray(chunk._data_), want,
                                   atol=2e-5)
        # the last position again, as a single-token read at offset s - 1
        for slot in range(b):
            cache.set_offset(slot, s - 1)
        view = cache.layer_caches()[0]
        before = monitor.all_stats()
        step = IF.paged_latent_attention(
            T(q[:, -1:]), T(rows[:, -1:]), T(w), view, nope_dim=nope,
            scale=scale)
    after = monitor.all_stats()
    name = "pallas.mla_decode." + ("kernel" if lane == "kernel"
                                   else "xla_lane")
    assert after.get(name, 0) > before.get(name, 0)
    np.testing.assert_allclose(np.asarray(step._data_)[:, 0], want[:, -1],
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(step._data_)[:, 0],
                               np.asarray(chunk._data_)[:, -1], atol=2e-5)


def test_latent_decode_kernel_matches_xla_lane_over_ragged_rows(monkeypatch):
    """Rows of different lengths over one pool, several steps a row: an
    empty slot (offset 0), a row ending mid-page, a row that fills more
    than one step of the kernel."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(mla, "_STEP_BYTES", 2 * 8 * 128 * 4)   # 2 pages
    b, h, lanes, page, n_tab = 4, 4, 128, 8, 8
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    pool = jax.random.normal(keys[0], (b * n_tab + 1, page, lanes),
                             jnp.float32)
    q = jax.random.normal(keys[1], (b, h, lanes), jnp.float32)
    pt = jnp.asarray(np.random.default_rng(0).permutation(b * n_tab)
                     .reshape(b, n_tab) + 1, jnp.int32)
    off = jnp.asarray([0, 13, 37, 63], jnp.int32)
    got = mla.mla_decode(q, pool, pt, off, 96, 0.21, lane="kernel")
    want = mla.mla_decode(q, pool, pt, off, 96, 0.21, lane="xla")
    assert got.shape == (b, h, 96)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # against the dense formula for one row
    rows = np.asarray(pool)[np.asarray(pt)[2]].reshape(-1, lanes)[:38]
    sc = np.asarray(q)[2] @ rows.T * 0.21
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(got)[2], p @ rows[:, :96],
                               atol=2e-5)


def _one_wait_case(dtype, group):
    """(q, pool, page table, offsets) of six rows over one pool whose live
    page counts are 2g, 1, 2g + 1, 2g - 1, g and g - 1: rows that end on
    a full step and on a partial one, an empty slot (offset 0), and a next
    row's prefetch that crosses from full to partial and back."""
    page = 32 // jnp.dtype(dtype).itemsize          # whole sublane tiles
    b, h, lanes, n_tab = 6, 4, 128, 2 * group + 2
    live = [2 * group, 1, 2 * group + 1, 2 * group - 1, group, group - 1]
    rng = np.random.default_rng(42)
    off = jnp.asarray([0 if n == 1 else (n - 1) * page + int(
        rng.integers(0, page)) for n in live], jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(group), 2)
    pool = jax.random.normal(keys[0], (b * n_tab + 1, page, lanes),
                             jnp.float32).astype(dtype)
    q = jax.random.normal(keys[1], (b, h, lanes), jnp.float32).astype(dtype)
    pt = jnp.asarray(rng.permutation(b * n_tab).reshape(b, n_tab) + 1,
                     jnp.int32)
    return q, pool, pt, off


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("group", [2, 4])
def test_latent_decode_one_wait_steps_match_xla_lane(monkeypatch, dtype,
                                                     group):
    """Full steps (one unrolled run of copies, one wait for the slot)
    beside partial ones (a copy and a wait a page) over rows of k·group -
    1, k·group and k·group + 1 live pages: the kernel reads what the XLA
    lane reads, row by row."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    q, pool, pt, off = _one_wait_case(dtype, group)
    page, lanes = pool.shape[1:]
    monkeypatch.setattr(mla, "_STEP_BYTES",
                        group * page * lanes * jnp.dtype(dtype).itemsize)
    assert mla.mla_decode_pages_per_step(page, lanes,
                                         jnp.dtype(dtype).itemsize) == group
    got = mla.mla_decode(q, pool, pt, off, 96, 0.21, lane="kernel")
    want = mla.mla_decode(q, pool, pt, off, 96, 0.21, lane="xla")
    # the dense formula over each row's live rows, in float32
    rows_all = np.asarray(pool.astype(jnp.float32))
    dense = []
    for r in range(q.shape[0]):
        rows = rows_all[np.asarray(pt)[r]].reshape(-1, lanes)[
            :int(off[r]) + 1]
        sc = np.asarray(q[r].astype(jnp.float32)) @ rows.T * 0.21
        p = np.exp(sc - sc.max(-1, keepdims=True))
        dense.append((p / p.sum(-1, keepdims=True)) @ rows[:, :96])
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)),
                               np.asarray(want.astype(jnp.float32)),
                               atol=tol)
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)),
                               np.stack(dense), atol=tol)


def test_pages_per_step_gauge_in_serving_stats(monkeypatch):
    """The kernel's step, set where the kernel lane is traced, is what
    ``serving_stats()`` shows; the XLA lane leaves it alone."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    q, pool, pt, off = _one_wait_case(jnp.float32, 2)
    for group in (4, 2):
        monkeypatch.setattr(mla, "_STEP_BYTES", group * 8 * 128 * 4)
        mla.mla_decode(q, pool, pt, off, 96, 0.21, lane="kernel")
        assert serving_stats()["mla_decode_pages_per_step"] == group
    mla.mla_decode(q, pool, pt, off, 96, 0.21, lane="xla")
    assert serving_stats()["mla_decode_pages_per_step"] == 2


def test_latent_pages_rule():
    assert mla.latent_row_lanes(576) == 640
    assert mla.latent_row_lanes(512) == 512
    assert mla.mla_decode_pages_per_step(16, 640, 2) == 128
    # float32 pages of 4 rows are not whole sublane tiles: the XLA lane
    assert mla.mla_decode_pages_per_step(4, 128, 4) == 0
    assert mla.mla_decode_pages_per_step(8, 128, 4) > 0
    assert mla.mla_decode_pages_per_step(16, 576, 2) == 0


# (c) rotation and scale by hand --------------------------------------------
def test_yarn_frequencies_and_softmax_scale_by_hand():
    cfg = SarvamMLAConfig()
    assert cfg.head_dim == 576 and cfg.q_head_dim == 192
    inv = np.asarray(sm.yarn_inv_freq(64, 10000.0, cfg.rope_scaling))
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64.0)
    # correction dims: 64 ln(4096 / (32 * 2 pi)) / (2 ln 1e4) = 10.47
    # -> 10; 64 ln(4096 / (2 pi)) / (2 ln 1e4) = 22.51 -> 23
    assert math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                      / (2 * math.log(1e4))) == 10
    assert math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(1e4))) == 23
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40.0, rtol=1e-6)
    # pair 15: ramp 5/13 of the way
    np.testing.assert_allclose(
        inv[15], plain[15] * (1 - 5 / 13) + plain[15] / 40 * (5 / 13),
        rtol=1e-6)
    np.testing.assert_allclose(
        cfg.softmax_scale, 192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2,
        rtol=1e-12)
    np.testing.assert_allclose(cfg.softmax_scale, 0.13523, rtol=1e-4)
    assert sm.yarn_cos_sin_scale(cfg.rope_scaling) == 1.0
    # the reference computes both on its own
    rcfg = {"rope_scaling": cfg.rope_scaling, "qk_rope_head_dim": 64,
            "qk_nope_head_dim": 128, "rope_theta": 10000.0}
    np.testing.assert_allclose(np.asarray(ref.yarn_inv_freq(rcfg)), inv,
                               rtol=1e-6)
    np.testing.assert_allclose(ref.softmax_scale(rcfg), cfg.softmax_scale)


def test_rope_pairs_rotates_interleaved_pairs():
    x = jnp.asarray([[[1.0, 0.0, 0.0, 2.0]]])            # [1, 1, 4]
    inv = jnp.asarray([math.pi / 2, math.pi])
    out = np.asarray(sm.rope_pairs(x, jnp.asarray([1]), inv))
    np.testing.assert_allclose(out[0, 0], [0.0, 1.0, 0.0, -2.0], atol=1e-6)


# (d) routing ---------------------------------------------------------------
def test_routing_with_bias_and_factor_matches_reference():
    """The bias moves the CHOICE and not the gates; the factor scales the
    normalised gates; both as the reference's routed part has them."""
    t, e, k = 64, 16, 4
    logits = jax.random.normal(jax.random.PRNGKey(0), (t, e)) * 1.3
    bias = jax.random.normal(jax.random.PRNGKey(1), (e,)) * 0.3
    plain_idx, plain_g = moe.route_sigmoid_topk(logits, k)
    idx, g = moe.route_sigmoid_topk(logits, k, bias=bias, scale=2.5)
    s = np.asarray(jax.nn.sigmoid(logits))
    want_idx = np.argsort(-(s + np.asarray(bias)), axis=-1)[:, :k]
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                  np.sort(want_idx, -1))
    # the bias changed some choices
    assert (np.sort(np.asarray(idx), -1)
            != np.sort(np.asarray(plain_idx), -1)).any()
    top = np.take_along_axis(s, np.asarray(idx), -1)
    np.testing.assert_allclose(np.asarray(g),
                               2.5 * top / top.sum(-1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g).sum(-1), 2.5, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(plain_g).sum(-1), 1.0, rtol=1e-6)


def test_routing_without_bias_and_factor_traces_what_it_traced():
    """Command A+'s call passes neither: its jaxpr is the one of the
    function as it stood before either existed."""
    def before(router_logits, k):
        scores = jax.nn.sigmoid(router_logits.astype(jnp.float32))
        top, idx = jax.lax.top_k(scores, k)
        return idx.astype(jnp.int32), \
            top / jnp.sum(top, axis=-1, keepdims=True)

    x = jnp.zeros((8, 16), jnp.float32)
    now = jax.make_jaxpr(lambda r: moe.route_sigmoid_topk(r, 4))(x)
    was = jax.make_jaxpr(lambda r: before(r, 4))(x)
    assert str(now) == str(was)


def _mlp_share(cfg_kw, weights, layer, held):
    """The program's expert layer holding ``held``, with layer
    ``layer``'s seeded weights (the full stack cut to the run)."""
    cfg = SarvamMLAConfig(**dict(cfg_kw, held_experts=held))
    mlp = SarvamSparseMLP(cfg)
    p = f"model.layers.{layer}.mlp."
    lo, hi = held[0], held[0] + held[1]
    mlp.gate.weight._data_ = weights[p + "gate.weight"]
    mlp.gate.expert_bias._data_ = weights[p + "gate.expert_bias"]
    for name in ("gate_proj", "up_proj", "down_proj"):
        getattr(mlp.experts, name)._data_ = \
            weights[p + "experts." + name][lo:hi]
        getattr(mlp.shared_experts, name)._data_ = \
            weights[p + "shared_experts." + name]
    return mlp


def test_the_shares_add_up_to_the_uncut_layer():
    """Over the 4 shares of 4 experts, the layer's partial results with
    the shared expert counted once sum to the uncut reference's layer."""
    full = dict(TINY, held_experts=(0, 16))
    rcfg = _reference_cfg(SarvamMLAConfig(**full))
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
            assert np.abs(part - want).max() > 1e-3
            total += part - shared
    np.testing.assert_allclose(total + shared, want, atol=5e-5)


# (e) serving lanes ---------------------------------------------------------
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
    # two expert layers of three; 4 of 16 experts held, 4 a token
    assert 0.5 < st_c["expert_pairs_per_token"] < 1.5
    assert st_c["kv_latent_pools"] == 3 and st_c["kv_pools"] == 0
    assert st_c["kv_latent_row_bytes"] == 40 * 4
    # the contexts a latent layer's reads cover are counted
    reg = monitor.all_stats()
    assert reg["serving.kv.context_token_ticks"] > 0
    assert reg["serving.prefill.context_tokens"] > 0


@pytest.mark.parametrize("lane", ["xla_lane", "kernel"])
def test_greedy_served_tokens_are_the_references_first_choice(
        tiny, tick_flag, monkeypatch, lane):
    """Prefill in chunks, then decoding through latent pages in the
    compiled tick — by the XLA lane, and by the Pallas kernel in the
    interpreter (pages of 8 rows) — serves the reference's argmax."""
    model, _, weights, runner = tiny
    cfg = None
    if lane == "kernel":
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
        model, _, weights, runner = _build()
        cfg = _cfg(page_size=8)
    before = monitor.all_stats().get("pallas.mla_decode." + lane, 0)
    prompts = _prompts([37, 12], seed=6)
    outs, _ = _serve(model, [prompts], True, max_new=24, cfg=cfg)
    assert monitor.all_stats().get("pallas.mla_decode." + lane, 0) > before
    for prompt, out in zip(prompts, outs[0]):
        ids = np.concatenate([prompt, out])
        want = _reference_logits(runner, weights, ids)
        n = len(prompt)
        np.testing.assert_array_equal(out, want[n - 1:-1].argmax(-1))


def test_compiled_tick_runs_the_latent_kernel(tick_flag, monkeypatch):
    """At pages the kernel hosts (8 float32 rows) and with Mosaic kernels
    on (here: the interpreter) the tick's latent read goes through
    ``mla_decode`` — and serves what the XLA lane serves."""
    model = _build()[0]
    scfg = _cfg(page_size=8, num_slots=2)
    waves = [_prompts([29, 12], seed=9)]
    plain, _ = _serve(model, waves, True, cfg=scfg, max_new=16)
    before = dict(monitor.all_stats())
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    with_kernel, st = _serve(model, waves, True, cfg=scfg, max_new=16)
    after = monitor.all_stats()
    for x, y in zip(plain[0], with_kernel[0]):
        np.testing.assert_array_equal(x, y)
    assert after.get("pallas.mla_decode.kernel", 0) \
        > before.get("pallas.mla_decode.kernel", 0)
    assert st["tick_fallbacks"] == 0 and st["prefill_fallbacks"] == 0
    assert st["mla_decode_kernel_traces"] > 0
    assert st["mla_decode_pages_per_step"] > 0


def test_reused_slot_sees_none_of_its_former_tenants_rows(tiny, tick_flag):
    model = tiny[0]
    long_one, probe = _prompts([50, 13], seed=8)
    one_slot = _cfg(num_slots=1)
    fresh, _ = _serve(model, [[probe]], True, cfg=one_slot)
    reused, _ = _serve(model, [[long_one], [probe]], True, cfg=one_slot)
    np.testing.assert_array_equal(fresh[0][0], reused[1][0])


# (f) what a latent page keeps of the page machinery ------------------------
def test_prefix_sharing_over_latent_pages_serves_the_same_tokens(tiny,
                                                                 tick_flag):
    """A latent page is a page: the prefix tree shares prompt pages
    through the page table alone, and the suffix's prefill reads them."""
    model = tiny[0]
    rng = np.random.default_rng(10)
    head = rng.integers(0, VOCAB, (24,)).astype("int32")
    prompts = [np.concatenate([head, rng.integers(0, VOCAB, (n,))
                               .astype("int32")]) for n in (5, 9, 2)]
    plain, _ = _serve(model, [[p] for p in prompts], True, max_new=12)
    shared, st = _serve(model, [[p] for p in prompts], True, max_new=12,
                        cfg=_cfg(enable_prefix_cache=True))
    for a, b in zip(plain, shared):
        np.testing.assert_array_equal(a[0], b[0])
    assert st["prefix_cache_hit_tokens"] >= 2 * 20


def test_speculation_over_latent_pages_serves_the_same_tokens(tiny,
                                                              tick_flag):
    """Rollback moves an offset and returns pages: nothing in it knows
    what a page holds."""
    from paddle_tpu.models import LlamaForCausalLM, llama_config
    model = tiny[0]
    paddle.seed(3)
    draft = LlamaForCausalLM(llama_config("tiny", vocab_size=VOCAB,
                                          max_seq_len=MAX_LEN))
    prompts = _prompts([19, 7], seed=12)
    plain, _ = _serve(model, [prompts], True, max_new=14)
    spec, st = _serve(model, [prompts], True, max_new=14,
                      cfg=_cfg(draft_model=draft, speculation_k=3))
    for a, b in zip(plain[0], spec[0]):
        np.testing.assert_array_equal(a, b)
    assert st["spec_windows"] > 0


def test_typed_refusals_name_the_store_kind(tiny):
    model = tiny[0]
    with pytest.raises(LatentStoreError, match="draft_model"):
        Engine(model, _cfg(draft_model=model, speculation_k=2))
    with pytest.raises(LatentStoreError, match="role"):
        Engine(model, _cfg(role="prefill"))
    with pytest.raises(LatentStoreError, match="cache_dtype"):
        Engine(model, _cfg(cache_dtype="int8"))
    eng = Engine(model, _cfg())
    with pytest.raises(LatentStoreError, match="migrator"):
        eng.migrator = lambda *a: None
    with pytest.raises(ValueError, match="latent page store"):
        IF.paged_cache_attention(None, None, None, {"latent_pool": None})
