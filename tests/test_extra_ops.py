"""Long-tail op pack + inplace variants (reference: the paddle.* symbols
exported by python/paddle/__init__.py __all__; OpTest-style numpy
reference checks per SURVEY §4)."""
import numpy as np
import pytest

import paddle_tpu as paddle


def T(a):
    return paddle.to_tensor(np.asarray(a))


def test_top_level_all_parity(api_spec):
    """Every top-level symbol of the frozen surface exists here."""
    ref_all = api_spec["paddle_tpu"]
    missing = sorted(s for s in ref_all
                     if not hasattr(paddle, s) and s != "DataParallel")
    assert missing == [], f"top-level API gaps: {missing}"
    assert paddle.DataParallel is not None  # lazy __getattr__


def test_math_extras_match_numpy():
    x = np.linspace(0.5, 2.0, 7).astype(np.float32)
    np.testing.assert_allclose(paddle.asinh(T(x)).numpy(), np.arcsinh(x),
                               rtol=1e-6)
    np.testing.assert_allclose(paddle.acosh(T(1 + x)).numpy(),
                               np.arccosh(1 + x), rtol=1e-6)
    np.testing.assert_allclose(paddle.atanh(T(x / 4)).numpy(),
                               np.arctanh(x / 4), rtol=1e-6)
    np.testing.assert_allclose(paddle.logaddexp(T(x), T(2 * x)).numpy(),
                               np.logaddexp(x, 2 * x), rtol=1e-6)
    import scipy.special as sp
    np.testing.assert_allclose(paddle.digamma(T(x)).numpy(), sp.digamma(x),
                               rtol=1e-5)
    np.testing.assert_allclose(paddle.lgamma(T(x)).numpy(), sp.gammaln(x),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(paddle.i0(T(x)).numpy(), sp.i0(x), rtol=1e-5)
    np.testing.assert_allclose(paddle.i1e(T(x)).numpy(), sp.i1e(x),
                               rtol=1e-5)


def test_addmm_and_mm():
    a = np.ones((2, 2), np.float32)
    x = np.arange(4, dtype=np.float32).reshape(2, 2)
    y = np.eye(2, dtype=np.float32)
    out = paddle.addmm(T(a), T(x), T(y), beta=0.5, alpha=2.0)
    np.testing.assert_allclose(out.numpy(), 0.5 * a + 2.0 * x)
    np.testing.assert_allclose(paddle.mm(T(x), T(y)).numpy(), x)


def test_cdist():
    x = np.zeros((3, 4), np.float32)
    y = np.ones((2, 4), np.float32)
    np.testing.assert_allclose(paddle.cdist(T(x), T(y)).numpy(),
                               np.full((3, 2), 2.0), rtol=1e-6)
    np.testing.assert_allclose(
        paddle.cdist(T(x), T(y), p=1.0).numpy(), np.full((3, 2), 4.0),
        rtol=1e-6)


def test_cummin_cummax_indices():
    x = np.array([3.0, 1.0, 2.0, 0.5, 4.0], np.float32)
    v, i = paddle.cummin(T(x))
    np.testing.assert_allclose(v.numpy(), np.minimum.accumulate(x))
    np.testing.assert_array_equal(i.numpy(), [0, 1, 1, 3, 3])
    v, i = paddle.cummax(T(x))
    np.testing.assert_allclose(v.numpy(), np.maximum.accumulate(x))
    np.testing.assert_array_equal(i.numpy(), [0, 0, 0, 0, 4])


def test_logcumsumexp():
    x = np.array([0.1, 0.5, 2.0, -1.0], np.float32)
    ref = np.log(np.cumsum(np.exp(x)))
    np.testing.assert_allclose(paddle.logcumsumexp(T(x)).numpy(), ref,
                               rtol=1e-5)


def test_nan_reductions():
    x = np.array([[1.0, np.nan, 3.0], [np.nan, 5.0, 6.0]], np.float32)
    np.testing.assert_allclose(paddle.nanmedian(T(x)).numpy(),
                               np.nanmedian(x))
    np.testing.assert_allclose(
        paddle.nanquantile(T(x), 0.5, axis=1).numpy(),
        np.nanquantile(x, 0.5, axis=1))


def test_take_flat_semantics():
    x = np.arange(6).reshape(2, 3)
    idx = np.array([[0, 5], [-1, -6]])
    out = paddle.take(T(x), T(idx))
    np.testing.assert_array_equal(out.numpy(), [[0, 5], [5, 0]])
    out = paddle.take(T(x), T(np.array([7, -8])), mode="wrap")
    np.testing.assert_array_equal(out.numpy(), [1, 4])


def test_shape_manip_extras():
    x = np.arange(24).reshape(2, 12).astype(np.float32)
    np.testing.assert_array_equal(
        paddle.unflatten(T(x), 1, [3, 4]).numpy(), x.reshape(2, 3, 4))
    parts = paddle.unstack(T(x), axis=0)
    assert len(parts) == 2
    np.testing.assert_array_equal(parts[1].numpy(), x[1])
    vs = paddle.vsplit(T(x), 2)
    np.testing.assert_array_equal(vs[0].numpy(), x[:1])
    np.testing.assert_array_equal(
        paddle.view(T(x), [4, 6]).numpy(), x.reshape(4, 6))
    np.testing.assert_array_equal(
        paddle.view_as(T(x), T(np.zeros((6, 4)))).numpy(), x.reshape(6, 4))
    np.testing.assert_array_equal(
        paddle.as_strided(T(x.reshape(-1)), [2, 3], [12, 1]).numpy(),
        x.reshape(-1)[np.arange(2)[:, None] * 12 + np.arange(3)])
    np.testing.assert_array_equal(
        paddle.crop(T(x), shape=[1, 3], offsets=[1, 2]).numpy(),
        x[1:2, 2:5])


def test_unique_consecutive():
    x = np.array([1, 1, 2, 2, 2, 3, 1, 1])
    out, inv, counts = paddle.unique_consecutive(
        T(x), return_inverse=True, return_counts=True)
    np.testing.assert_array_equal(out.numpy(), [1, 2, 3, 1])
    np.testing.assert_array_equal(counts.numpy(), [2, 3, 1, 2])
    np.testing.assert_array_equal(inv.numpy(), [0, 0, 1, 1, 1, 2, 3, 3])


def test_trapezoid():
    y = np.array([1.0, 2.0, 3.0], np.float32)
    np.testing.assert_allclose(paddle.trapezoid(T(y)).numpy(), 4.0)
    np.testing.assert_allclose(
        paddle.cumulative_trapezoid(T(y)).numpy(), [1.5, 4.0])


def test_renorm():
    x = np.array([[3.0, 4.0], [0.3, 0.4]], np.float32)
    out = paddle.renorm(T(x), p=2.0, axis=0, max_norm=1.0).numpy()
    np.testing.assert_allclose(np.linalg.norm(out[0]), 1.0, rtol=1e-5)
    np.testing.assert_allclose(out[1], x[1], rtol=1e-6)  # under the cap


def test_shard_index():
    lbl = np.array([0, 5, 9, 13])
    out = paddle.shard_index(T(lbl), index_num=16, nshards=2, shard_id=1)
    np.testing.assert_array_equal(out.numpy(), [-1, -1, 1, 5])


def test_utility_surface():
    x = T(np.ones((2, 3), np.float32))
    assert paddle.is_tensor(x) and not paddle.is_tensor(5)
    assert paddle.is_floating_point(x) and not paddle.is_integer(x)
    assert int(paddle.numel(x)) == 6 and int(paddle.rank(x)) == 2
    np.testing.assert_array_equal(paddle.shape(x).numpy(), [2, 3])
    assert paddle.tolist(x) == [[1.0, 1.0, 1.0], [1.0, 1.0, 1.0]]
    assert paddle.broadcast_shape([2, 1, 3], [4, 3]) == [2, 4, 3]
    p = paddle.create_parameter([3, 4], "float32")
    assert list(p.shape) == [3, 4] and not p.stop_gradient
    st = paddle.get_rng_state()
    paddle.set_rng_state(st)
    with paddle.LazyGuard():
        pass
    repr(paddle.CPUPlace()), repr(paddle.CUDAPlace(0))


def test_inplace_variants_grad_and_leaf_protection():
    t = paddle.to_tensor(np.full(3, 2.0, np.float32))
    t.stop_gradient = False
    y = paddle.tanh_(t * 1.0)   # in-place on an intermediate
    y.sum().backward()
    np.testing.assert_allclose(t.grad.numpy(),
                               1.0 - np.tanh(2.0) ** 2 * np.ones(3),
                               rtol=1e-5)
    with pytest.raises(RuntimeError, match="leaf"):
        paddle.scale_(t, 0.5)
    with paddle.no_grad():
        paddle.scale_(t, 0.5)
    np.testing.assert_allclose(t.numpy(), np.ones(3), rtol=1e-6)


def test_random_fills():
    paddle.seed(123)
    x = paddle.zeros([1000])
    paddle.normal_(x, mean=1.0, std=0.1)
    assert abs(float(x.mean()) - 1.0) < 0.02
    paddle.uniform_(x, min=0.0, max=2.0)
    assert 0.0 <= float(x.min()) and float(x.max()) <= 2.0
    paddle.geometric_(x, probs=0.5)
    assert float(x.min()) > 0.0  # continuous value, support (0, inf)
    paddle.cauchy_(x)
    assert np.isfinite(x.numpy()).all()


def test_summary_and_flops():
    from paddle_tpu import nn
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
    info = paddle.summary(net, (1, 8))
    assert info["total_params"] == 8 * 16 + 16 + 16 * 2 + 2
    fl = paddle.flops(net, (4, 8))
    assert fl >= 2 * 4 * 8 * 16  # at least the first matmul


def test_svd_returns_vh_reference_contract():
    """paddle.linalg.svd returns (U, S, VH) with x == U @ diag(S) @ VH
    (reference tensor/linalg.py: 'VH is the conjugate transpose of V');
    a previous implementation returned V and broke reconstruction."""
    a = np.arange(12, dtype=np.float32).reshape(3, 4)
    u, s, vh = paddle.linalg.svd(paddle.to_tensor(a),
                                 full_matrices=False)
    assert tuple(u.shape) == (3, 3) and tuple(vh.shape) == (3, 4)
    np.testing.assert_allclose(
        u.numpy() @ np.diag(s.numpy()) @ vh.numpy(), a, atol=1e-4)
    nu, ns, nvh = np.linalg.svd(a, full_matrices=False)
    np.testing.assert_allclose(np.abs(s.numpy()), np.abs(ns), rtol=1e-5,
                               atol=1e-5)  # rank-2: s[2] is numeric 0
