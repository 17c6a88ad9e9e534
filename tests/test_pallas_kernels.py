"""Pallas kernel numerics in interpreter mode (CPU CI; reference analog:
OpTest numpy-reference checks, test/legacy_test/op_test.py:381)."""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.pallas import flash_attention as fa  # noqa: E402
from paddle_tpu.pallas import fused as pf  # noqa: E402
from paddle_tpu.pallas import autotune  # noqa: E402


@pytest.fixture(autouse=True)
def _interpret_mode():
    prev = os.environ.get("PADDLE_TPU_PALLAS_INTERPRET")
    os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
    yield
    if prev is None:
        os.environ.pop("PADDLE_TPU_PALLAS_INTERPRET", None)
    else:
        os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = prev


def _qkv(b=2, s=256, h=2, d=64, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)  # noqa: E731
    return mk(), mk(), mk()


_PATH_COUNTERS = ("pallas.flash.resident", "pallas.flash.streamed")


def _path_traces():
    from paddle_tpu.utils import monitor
    stats = monitor.all_stats()
    return [stats.get(name, 0) for name in _PATH_COUNTERS]


@pytest.fixture(params=["walk", "streamed"])
def flash_path(request, monkeypatch):
    """Every kernel test runs over BOTH paths.  Which one a call takes
    is a rule on its shapes (``fa._walk_vmem_bytes``), no argument: the
    test forces the rule's answer by taking the walk's VMEM budget
    away, and afterwards holds the two counters to it."""
    if request.param == "streamed":
        monkeypatch.setattr(fa, "_WALK_VMEM_BUDGET", 0)
    before = _path_traces()
    yield request.param
    took = [after - b for after, b in zip(_path_traces(), before)]
    walked, streamed = took
    if request.param == "walk":
        assert walked > 0 and streamed == 0, took
    else:
        assert streamed > 0 and walked == 0, took


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_xla(causal, flash_path):
    q, k, v = _qkv()
    sc = 1.0 / np.sqrt(q.shape[-1])
    out, lse = fa._pallas_flash_fwd(q, k, v, causal=causal, scale=sc,
                                    block_q=128, block_k=128)
    ref = fa._xla_attention(q, k, v, causal=causal, scale=sc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # lse sanity: logsumexp of the scaled logits
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * sc
    if causal:
        mask = jnp.tril(jnp.ones((q.shape[1],) * 2, bool))
        logits = jnp.where(mask, logits, -1e30)
    ref_lse = jax.scipy.special.logsumexp(logits, axis=-1)
    np.testing.assert_allclose(np.asarray(lse[..., 0]), np.asarray(ref_lse),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_xla(causal, flash_path):
    q, k, v = _qkv(seed=1)
    sc = 1.0 / np.sqrt(q.shape[-1])

    def f_pallas(q_, k_, v_):
        return (fa._flash_core(q_, k_, v_, None, None, None, None, causal, sc, 0.0, 128, 128) ** 2).sum()

    def f_ref(q_, k_, v_):
        return (fa._xla_attention(q_, k_, v_, causal=causal,
                                  scale=sc) ** 2).sum()

    g_p = jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for gp, gr in zip(g_p, g_r):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                   atol=5e-5, rtol=5e-5)


def test_flash_mixed_blocks_bf16(flash_path):
    q, k, v = _qkv(b=1, s=384, h=2, d=128, dtype=jnp.bfloat16, seed=2)
    sc = 1.0 / np.sqrt(q.shape[-1])
    out, _ = fa._pallas_flash_fwd(q, k, v, causal=True, scale=sc,
                                  block_q=128, block_k=64)
    ref = fa._xla_attention(q, k, v, causal=True, scale=sc)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=2e-2)


def test_rms_norm_kernel():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 32, 256)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((256,)), jnp.float32)

    def ref(x_, w_):
        ms = jnp.mean(x_ * x_, -1, keepdims=True)
        return x_ * jax.lax.rsqrt(ms + 1e-6) * w_

    y = pf.rms_norm_pallas(x, w, 1e-6)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref(x, w)),
                               atol=1e-5)
    g_p = jax.grad(lambda a, b: (pf.rms_norm_pallas(a, b, 1e-6) ** 2).sum(),
                   argnums=(0, 1))(x, w)
    g_r = jax.grad(lambda a, b: (ref(a, b) ** 2).sum(),
                   argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(g_p[0]), np.asarray(g_r[0]),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(g_p[1]), np.asarray(g_r[1]),
                               atol=1e-3)


def test_rope_kernel():
    rng = np.random.default_rng(0)
    b, s, h, d = 2, 64, 4, 64
    t = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    inv = 1.0 / (10000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)
    emb = jnp.concatenate([freqs, freqs], -1)
    cos, sin = jnp.cos(emb), jnp.sin(emb)

    def ref(t_):
        c = cos[None, :, None, :]
        s_ = sin[None, :, None, :]
        t1, t2 = jnp.split(t_, 2, -1)
        return t_ * c + jnp.concatenate([-t2, t1], -1) * s_

    o = pf.rope_pallas(t, cos, sin)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref(t)), atol=1e-5)
    gp = jax.grad(lambda a: (pf.rope_pallas(a, cos, sin) ** 2).sum())(t)
    gr = jax.grad(lambda a: (ref(a) ** 2).sum())(t)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gr), atol=1e-5)


def test_rope_kernel_is_neox_only():
    # the interleaved style has no kernel that compiles for the TPU: it
    # takes the XLA rope by a static rule, interpreter or not
    assert pf.rope_supported((2, 64, 4, 64), 64, neox=True)
    assert not pf.rope_supported((2, 64, 4, 64), 64, neox=False)


def test_rope_wired_through_incubate():
    import paddle_tpu as paddle
    from paddle_tpu.incubate.nn import functional as IF
    rng = np.random.default_rng(3)
    q = paddle.to_tensor(rng.standard_normal((2, 64, 4, 64)).astype("float32"))
    k = paddle.to_tensor(rng.standard_normal((2, 64, 4, 64)).astype("float32"))
    q.stop_gradient = False
    qo, ko, vo = IF.fused_rotary_position_embedding(q, k)
    assert vo is None and tuple(qo.shape) == tuple(q.shape)
    qo.sum().backward()
    assert q.grad is not None


def test_autotune_sweep_records_in_process_and_hides_no_failure():
    autotune.clear()
    calls = []

    def run(cfg):
        calls.append(cfg)

    best = autotune.sweep("op", (128, 64), [(1,), (2,)], run)
    assert best in [(1,), (2,)]
    assert autotune.lookup("op", (128, 64)) == best
    # second sweep is served from the table — run() not called again
    n = len(calls)
    assert autotune.sweep("op", (128, 64), [(1,), (2,)], run) == best
    assert len(calls) == n
    # a candidate the device refuses is a finding: it raises, it is not
    # skipped in favour of the ones that ran
    def refuse(cfg):
        if cfg == (2,):
            raise NotImplementedError("Mosaic refuses this block shape")

    with pytest.raises(NotImplementedError):
        autotune.sweep("op2", (128, 64), [(1,), (2,)], refuse)
    assert autotune.lookup("op2", (128, 64)) is None
    # nothing outlives the process: no file, no $HOME
    assert not hasattr(autotune, "_cache_path")
    autotune.clear()
    assert autotune.lookup("op", (128, 64)) is None


@pytest.mark.parametrize("bq,bk", [(128, 64), (64, 128)])
def test_flash_backward_mixed_blocks_causal(bq, bk, flash_path):
    """Causal bwd with unequal block sizes exercises the clamped
    dead-block index maps (first-live-q and diagonal-kv math)."""
    q, k, v = _qkv(b=1, s=256, h=2, d=64, seed=4)
    sc = 1.0 / np.sqrt(q.shape[-1])

    def f_pallas(q_, k_, v_):
        return (fa._flash_core(q_, k_, v_, None, None, None, None, True, sc, 0.0, bq, bk) ** 2).sum()

    def f_ref(q_, k_, v_):
        return (fa._xla_attention(q_, k_, v_, causal=True,
                                  scale=sc) ** 2).sum()

    g_p = jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for gp, gr in zip(g_p, g_r):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                   atol=5e-5, rtol=5e-5)


def _dense_dropout_ref(q, k, v, seed, rate, sc, causal=False):
    """Dense attention applying the EXACT kernel keep-mask (the hash is
    position-based, so evaluating it with block = whole matrix reproduces
    the blocked kernel's mask bit-for-bit)."""
    b, s, h, d = q.shape
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * sc
    if causal:
        m = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(m, logits, fa.NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    keeps = []
    for n in range(b * h):
        u = fa._dropout_uniform(jnp.uint32(seed), jnp.int32(n), 0, 0, s, s)
        keeps.append(u >= rate)
    keep = jnp.stack(keeps).reshape(b, h, s, s)
    probs = jnp.where(keep, probs / (1.0 - rate), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_dropout_matches_dense_hash(causal, flash_path):
    q, k, v = _qkv(b=1, s=256, h=2, d=64, seed=3)
    sc = 1.0 / np.sqrt(q.shape[-1])
    seed = jnp.full((1, 1), 1234, jnp.uint32)
    rate = 0.3

    def f_pallas(q_, k_, v_):
        return (fa._flash_core(q_, k_, v_, None, None, None, seed,
                               causal, sc, rate, 128, 128) ** 2).sum()

    def f_ref(q_, k_, v_):
        return (_dense_dropout_ref(q_, k_, v_, 1234, rate, sc,
                                   causal) ** 2).sum()

    out = fa._flash_core(q, k, v, None, None, None, seed, causal, sc,
                         rate, 128, 128)
    ref = _dense_dropout_ref(q, k, v, 1234, rate, sc, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-5, rtol=5e-5)
    g_p = jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for gp, gr in zip(g_p, g_r):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("mask_kind", ["bool_padding", "additive"])
def test_flash_mask_matches_xla(mask_kind):
    b, s, h, d = 2, 256, 2, 64
    q, k, v = _qkv(b=b, s=s, h=h, d=d, seed=4)
    sc = 1.0 / np.sqrt(d)
    rng = np.random.default_rng(7)
    if mask_kind == "bool_padding":
        # padded-batch key mask: [B, 1, S, S] bool, last 64 keys dead
        keep = np.ones((b, 1, s, s), bool)
        keep[:, :, :, s - 64:] = False
        mask = jnp.asarray(keep)
        mask_add = jnp.where(mask, 0.0, fa.NEG_INF).astype(jnp.float32)
    else:
        mask_add = jnp.asarray(
            rng.standard_normal((b, h, s, s)), jnp.float32)
        mask = mask_add

    def f_pallas(q_, k_, v_):
        return (fa._flash_core(q_, k_, v_, mask_add, None, None, None,
                               False, sc, 0.0, 128, 128) ** 2).sum()

    def f_ref(q_, k_, v_):
        return (fa._xla_attention(q_, k_, v_, attn_mask=mask,
                                  scale=sc) ** 2).sum()

    before = _path_traces()
    out = fa._flash_core(q, k, v, mask_add, None, None, None, False, sc,
                         0.0, 128, 128)
    # an [S, S] mask is streamed by blocks: the grid, whatever the shape
    assert [a - b_ for a, b_ in zip(_path_traces(), before)] == [0, 1]
    ref = fa._xla_attention(q, k, v, attn_mask=mask, scale=sc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-5, rtol=5e-5)
    g_p = jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for gp, gr in zip(g_p, g_r):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_segment_ids_varlen(causal, flash_path):
    # packed varlen: two sequences of 160+96 tokens in one row
    b, s, h, d = 2, 256, 2, 64
    q, k, v = _qkv(b=b, s=s, h=h, d=d, seed=5)
    sc = 1.0 / np.sqrt(d)
    seg_np = np.zeros((b, s), np.int32)
    seg_np[:, 160:] = 1
    seg = jnp.asarray(seg_np)
    qseg = seg[:, :, None]
    kseg = seg[:, None, :]

    def f_pallas(q_, k_, v_):
        return (fa._flash_core(q_, k_, v_, None, qseg, kseg, None,
                               causal, sc, 0.0, 128, 64) ** 2).sum()

    def f_ref(q_, k_, v_):
        return (fa._xla_attention(q_, k_, v_, causal=causal, scale=sc,
                                  segment_ids=seg) ** 2).sum()

    out = fa._flash_core(q, k, v, None, qseg, kseg, None, causal, sc,
                         0.0, 128, 64)
    ref = fa._xla_attention(q, k, v, causal=causal, scale=sc,
                            segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-5, rtol=5e-5)
    g_p = jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for gp, gr in zip(g_p, g_r):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gqa_native_kv_heads(causal, flash_path):
    # K/V carry 2 heads, Q carries 4 — kernels must index q_head // n_rep
    # without materializing repeated K/V (VERDICT r2 item 4)
    b, s, h, h_kv, d = 2, 256, 4, 2, 64
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h_kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h_kv, d)), jnp.float32)
    sc = 1.0 / np.sqrt(d)

    def f_pallas(q_, k_, v_):
        return (fa._flash_core(q_, k_, v_, None, None, None, None,
                               causal, sc, 0.0, 128, 128) ** 2).sum()

    def f_ref(q_, k_, v_):
        return (fa._xla_attention(q_, k_, v_, causal=causal,
                                  scale=sc) ** 2).sum()

    out = fa._flash_core(q, k, v, None, None, None, None, causal, sc,
                         0.0, 128, 128)
    ref = fa._xla_attention(q, k, v, causal=causal, scale=sc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-5, rtol=5e-5)
    g_p = jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    assert g_p[1].shape == (b, s, h_kv, d)
    for gp, gr in zip(g_p, g_r):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                   atol=2e-4, rtol=2e-4)


def test_flash_all_features_combined(flash_path):
    # GQA + segment ids + dropout + causal in one call: smoke + shapes +
    # determinism (same seed → same output)
    b, s, h, h_kv, d = 1, 256, 4, 2, 64
    rng = np.random.default_rng(8)
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h_kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h_kv, d)), jnp.float32)
    seg = jnp.asarray(np.repeat([[0, 1]], 128, axis=1).reshape(1, s))
    qseg, kseg = seg[:, :, None], seg[:, None, :]
    seed = jnp.full((1, 1), 42, jnp.uint32)
    sc = 1.0 / np.sqrt(d)

    def run():
        return fa._flash_core(q, k, v, None, qseg, kseg, seed, True, sc,
                              0.2, 128, 128)
    o1, o2 = run(), run()
    assert o1.shape == (b, s, h, d)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    g = jax.grad(lambda q_: (fa._flash_core(
        q_, k, v, None, qseg, kseg, seed, True, sc, 0.2, 128,
        128) ** 2).sum())(q)
    assert np.isfinite(np.asarray(g)).all()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_dropout_gqa_matches_dense_hash(causal, flash_path):
    # pins the fwd/bwd dropout-stream head-id algebra under GQA: the dkv
    # kernel reconstructs head = (n//h_kv)*h + (n%h_kv)*n_rep + r//num_q,
    # which must match the forward's grid index exactly
    b, s, h, h_kv, d = 1, 256, 4, 2, 64
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h_kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h_kv, d)), jnp.float32)
    sc = 1.0 / np.sqrt(d)
    seed = jnp.full((1, 1), 77, jnp.uint32)
    rate = 0.25
    n_rep = h // h_kv

    def dense_ref(q_, k_, v_):
        kr = jnp.repeat(k_, n_rep, axis=2)
        vr = jnp.repeat(v_, n_rep, axis=2)
        return _dense_dropout_ref(q_, kr, vr, 77, rate, sc, causal)

    def f_pallas(q_, k_, v_):
        return (fa._flash_core(q_, k_, v_, None, None, None, seed,
                               causal, sc, rate, 128, 64) ** 2).sum()

    def f_ref(q_, k_, v_):
        return (dense_ref(q_, k_, v_) ** 2).sum()

    out = fa._flash_core(q, k, v, None, None, None, seed, causal, sc,
                         rate, 128, 64)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(dense_ref(q, k, v)),
                               atol=5e-5, rtol=5e-5)
    g_p = jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    assert g_p[1].shape == (b, s, h_kv, d)
    for gp, gr in zip(g_p, g_r):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                   atol=2e-4, rtol=2e-4)


def test_flash_trainable_mask_gets_gradient():
    # a learned additive bias must receive its true gradient (XLA path);
    # the pallas backward produces no mask grad so routing must avoid it
    import os
    import paddle_tpu as paddle
    os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
    rng = np.random.default_rng(10)
    qv = rng.standard_normal((1, 128, 2, 64)).astype("float32")
    bias = paddle.to_tensor(
        np.zeros((1, 2, 128, 128), np.float32), stop_gradient=False)
    q = paddle.to_tensor(qv, stop_gradient=False)
    k, v = paddle.to_tensor(qv), paddle.to_tensor(qv)
    out = fa.flash_attention(q, k, v, attn_mask=bias)
    (out ** 2).sum().backward()
    assert bias.grad is not None
    assert float(np.abs(np.asarray(bias.grad._data_)).max()) > 0


@pytest.mark.parametrize("causal", [False, True])
def test_flash_head_major_matches_default_layout(causal, flash_path):
    # [B, H, S, D] path (free reshape instead of transposes) must be
    # numerically identical to the [B, S, H, D] path, fwd and bwd
    q, k, v = _qkv(b=2, s=256, h=2, d=64, seed=11)
    sc = 1.0 / np.sqrt(q.shape[-1])
    qh, kh, vh = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))

    out_ref = fa._flash_core(q, k, v, None, None, None, None, causal,
                             sc, 0.0, 128, 128)
    out_hm = fa._flash_core(qh, kh, vh, None, None, None, None, causal,
                            sc, 0.0, 128, 128, None, None, True)
    np.testing.assert_allclose(np.asarray(jnp.swapaxes(out_hm, 1, 2)),
                               np.asarray(out_ref), atol=1e-6)

    def f_ref(a, b_, c):
        return (fa._flash_core(a, b_, c, None, None, None, None, causal,
                               sc, 0.0, 128, 128) ** 2).sum()

    def f_hm(a, b_, c):
        return (fa._flash_core(a, b_, c, None, None, None, None, causal,
                               sc, 0.0, 128, 128, None, None, True)
                ** 2).sum()

    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    g_hm = jax.grad(f_hm, argnums=(0, 1, 2))(qh, kh, vh)
    for gr, gh in zip(g_ref, g_hm):
        np.testing.assert_allclose(np.asarray(jnp.swapaxes(gh, 1, 2)),
                                   np.asarray(gr), atol=1e-5)


def test_flash_bwd_blocks_differ_from_fwd(flash_path):
    # split fwd/bwd block choices: passing distinct bwd blocks must give
    # identical numerics (only scheduling differs)
    q, k, v = _qkv(b=1, s=256, h=2, d=64, seed=12)
    sc = 1.0 / np.sqrt(q.shape[-1])

    def f(bqb, bkb):
        def loss(a, b_, c):
            return (fa._flash_core(a, b_, c, None, None, None, None,
                                   True, sc, 0.0, 128, 128, bqb, bkb)
                    ** 2).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    g_same = f(None, None)
    g_diff = f(64, 128)
    for a, b_ in zip(g_same, g_diff):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-5)


def test_flash_bf16_d128_walk_streamed_and_xla_agree():
    """The training cell's types at a small shape: bfloat16 tiles of
    head size 128 go to the matrix unit as they are on both paths.
    Walk, streamed grid and the XLA lane give the same forward and the
    same three gradients to bfloat16's resolution; walk and grid,
    which share every tile's arithmetic, much closer than that."""
    b, s, h, d = 1, 512, 2, 128
    q, k, v = _qkv(b=b, s=s, h=h, d=d, dtype=jnp.bfloat16, seed=13)
    w = _qkv(b=b, s=s, h=h, d=d, dtype=jnp.bfloat16, seed=14)[0]
    sc = 1.0 / np.sqrt(d)

    def run(attn):
        def loss(q_, k_, v_):
            out = attn(q_, k_, v_)
            return jnp.sum(out.astype(jnp.float32)
                           * w.astype(jnp.float32)), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return [np.asarray(x, np.float32) for x in (out, *grads)]

    def kernels(q_, k_, v_):
        return fa._flash_core(q_, k_, v_, None, None, None, None, True,
                              sc, 0.0, 256, 128, 128, 256)

    before = _path_traces()
    walk = run(kernels)
    assert [a - b_ for a, b_ in zip(_path_traces(), before)] == [3, 0]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fa, "_WALK_VMEM_BUDGET", 0)
        grid = run(kernels)
    assert [a - b_ for a, b_ in zip(_path_traces(), before)] == [3, 3]
    xla = run(lambda q_, k_, v_: fa._xla_attention(
        q_, k_, v_, causal=True, scale=sc))
    for name, a, g, x in zip(("out", "dq", "dk", "dv"), walk, grid, xla):
        assert a.dtype == np.float32 and np.isfinite(a).all(), name
        scale = np.abs(x).max()
        # one bfloat16 rounding of the result and of p / ds on the way
        np.testing.assert_allclose(a, x, atol=2e-2 * scale, err_msg=name)
        np.testing.assert_allclose(g, x, atol=2e-2 * scale, err_msg=name)
        np.testing.assert_allclose(a, g, atol=8e-3 * scale, err_msg=name)


def test_flash_walk_rule_and_counters():
    """Which path a call takes is read off its shapes, in one place."""
    budget = fa._WALK_VMEM_BUDGET
    # the training cell: S 2,048, d 128, bfloat16, one q-head a kv head
    cell = fa._walk_vmem_bytes(2048, 128, 2, 1)
    assert 0 < cell <= budget
    # Q, dO (512 KB each) and the lse, delta rows (64 KB each), twice
    assert cell == 2 * (2 * 2048 * 128 * 2 + 2 * 2048 * 32)
    # an [S, S] mask has no side that can be held: the streamed grid
    assert fa._walk_vmem_bytes(2048, 128, 2, 1, has_mask=True) == 0
    # a head over the budget: one of S 65,536, or eight q-heads a kv
    # head (dKV holds the Q and dO of all eight) at S 8,192
    assert fa._walk_vmem_bytes(65536, 128, 2, 1) == 0
    assert fa._walk_vmem_bytes(8192, 128, 2, 8) == 0
    assert fa._walk_vmem_bytes(32768, 128, 2, 1) > 0
    assert fa._walk_vmem_bytes(8192, 128, 2, 4) > 0
    # segment ids ride the walk, and are counted
    assert fa._walk_vmem_bytes(2048, 128, 2, 1, has_seg=True) \
        == cell + 2 * 2048 * 32
    # the blocks: a walk's from the table, a streamed step's as before;
    # every block divides the sequence
    assert fa._pick_blocks(2048, 128, "fwd", True) \
        == fa._WALK_BLOCKS["fwd"]
    assert fa._pick_blocks(2048, 128, "bwd", True) \
        == fa._WALK_BLOCKS["bwd"]
    assert fa._pick_blocks(2048, 128, "fwd", False) == (256, 512)
    for s in range(128, 2048 + 1, 128):
        for which in ("fwd", "bwd"):
            for walk in (False, True):
                bq, bk = fa._pick_blocks(s, 128, which, walk)
                assert s % bq == 0 and s % bk == 0, (s, which, walk)

    # the counters: one a trace of a kernel, by the path it took
    q, k, v = _qkv(b=1, s=256, h=2, d=64, seed=15)
    mask = jnp.zeros((1, 1, 256, 256), jnp.float32)

    def traces(mask_add):
        before = _path_traces()
        jax.grad(lambda q_: fa._flash_core(
            q_, k, v, mask_add, None, None, None, True, 0.125, 0.0, 128,
            128).sum())(q)
        return [a - b_ for a, b_ in zip(_path_traces(), before)]

    assert traces(None) == [3, 0]          # forward, dKV, dQ: the walk
    assert traces(mask) == [0, 3]          # the mask streams, and runs
