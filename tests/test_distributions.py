"""Distribution families, KL registry, transforms (reference:
python/paddle/distribution/ + test/distribution/)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import distribution as D


def _np(t):
    return np.asarray(t._data_)


ALL_FAMILIES = [
    lambda: D.Normal(0., 1.),
    lambda: D.Uniform(0., 1.),
    lambda: D.Bernoulli(0.3),
    lambda: D.Categorical(logits=np.ones(4, np.float32)),
    lambda: D.Beta(2., 3.),
    lambda: D.Exponential(1.5),
    lambda: D.Gamma(2., 3.),
    lambda: D.Chi2(3.),
    lambda: D.Dirichlet(np.ones(3, np.float32)),
    lambda: D.Laplace(0., 1.),
    lambda: D.LogNormal(0., 1.),
    lambda: D.Geometric(0.3),
    lambda: D.Poisson(4.),
    lambda: D.Gumbel(0., 1.),
    lambda: D.Cauchy(0., 1.),
    lambda: D.StudentT(5., 0., 1.),
    lambda: D.Binomial(10., 0.4),
    lambda: D.Multinomial(5, np.ones(3, np.float32) / 3),
    lambda: D.MultivariateNormal(np.zeros(2, np.float32),
                                 covariance_matrix=np.eye(2,
                                                          dtype=np.float32)),
]


@pytest.mark.parametrize("mk", ALL_FAMILIES,
                         ids=lambda mk: type(mk()).__name__)
def test_sample_logprob_finite(mk):
    paddle.seed(0)
    d = mk()
    s = d.sample((5,))
    lp = d.log_prob(s)
    assert np.all(np.isfinite(_np(lp)))


@pytest.mark.parametrize("mk,true_mean", [
    (lambda: D.Gamma(2., 3.), 2 / 3),
    (lambda: D.Exponential(2.), 0.5),
    (lambda: D.Laplace(1., 1.), 1.0),
    (lambda: D.Gumbel(0., 1.), 0.5772),
    (lambda: D.Poisson(4.), 4.0),
    (lambda: D.Geometric(0.5), 1.0),
], ids=["gamma", "exponential", "laplace", "gumbel", "poisson", "geometric"])
def test_sample_mean_converges(mk, true_mean):
    paddle.seed(1)
    d = mk()
    s = _np(d.sample((100000,)))
    assert abs(s.mean() - true_mean) < 0.05 * max(1.0, abs(true_mean))


@pytest.mark.parametrize("make_pq", [
    lambda: (D.Normal(0., 1.), D.Normal(0.5, 1.5)),
    lambda: (D.Gamma(2., 1.), D.Gamma(3., 2.)),
    lambda: (D.Beta(2., 3.), D.Beta(3., 2.)),
    lambda: (D.Exponential(1.), D.Exponential(2.)),
    lambda: (D.Laplace(0., 1.), D.Laplace(0.5, 2.)),
    lambda: (D.Dirichlet(np.array([1., 2., 3.], np.float32)),
             D.Dirichlet(np.array([2., 2., 2.], np.float32))),
], ids=["normal", "gamma", "beta", "exponential", "laplace", "dirichlet"])
def test_kl_matches_monte_carlo(make_pq):
    paddle.seed(2)
    p, q = make_pq()
    s = p.sample((200000,))
    mc = float(np.mean(_np(p.log_prob(s)) - _np(q.log_prob(s))))
    kl = float(_np(D.kl_divergence(p, q)).sum()
               if _np(D.kl_divergence(p, q)).ndim else
               _np(D.kl_divergence(p, q)))
    assert abs(kl - mc) < 0.05 * max(1.0, abs(kl))


def test_register_kl_custom_pair():
    class MyDist(D.Normal):
        pass

    # subclass resolves to the Normal/Normal rule through the MRO
    got = D.kl_divergence(MyDist(0., 1.), D.Normal(0., 1.))
    np.testing.assert_allclose(_np(got), 0.0, atol=1e-6)

    @D.register_kl(MyDist, MyDist)
    def _kl(p, q):
        return np.float32(42.0)

    assert float(_np(D.kl_divergence(MyDist(0., 1.), MyDist(0., 1.)))) == 42.0


def test_kl_unregistered_raises():
    with pytest.raises(NotImplementedError):
        D.kl_divergence(D.Gamma(1., 1.), D.Normal(0., 1.))


def test_transformed_distribution_lognormal():
    paddle.seed(3)
    td = D.TransformedDistribution(D.Normal(0.2, 0.8), [D.ExpTransform()])
    ln = D.LogNormal(0.2, 0.8)
    x = ln.sample((7,))
    np.testing.assert_allclose(_np(td.log_prob(x)), _np(ln.log_prob(x)),
                               atol=1e-5)


@pytest.mark.parametrize("t", [
    D.AffineTransform(1.0, 2.0), D.ExpTransform(), D.SigmoidTransform(),
    D.TanhTransform(), D.PowerTransform(2.0),
], ids=["affine", "exp", "sigmoid", "tanh", "power"])
def test_transform_roundtrip_and_ldj(t):
    x = paddle.to_tensor(np.linspace(0.1, 0.9, 8).astype("float32"))
    y = t.forward(x)
    xr = t.inverse(y)
    np.testing.assert_allclose(_np(xr), _np(x), atol=1e-5)
    # numeric jacobian check
    eps = 1e-3
    num = (np.asarray(t.forward(paddle.to_tensor(_np(x) + eps))._data_)
           - np.asarray(t.forward(paddle.to_tensor(_np(x) - eps))._data_)) \
        / (2 * eps)
    np.testing.assert_allclose(_np(t.forward_log_det_jacobian(x)),
                               np.log(np.abs(num)), atol=1e-3)


def test_stickbreaking_roundtrip():
    t = D.StickBreakingTransform()
    x = paddle.to_tensor(np.random.default_rng(0).standard_normal(5)
                         .astype("float32"))
    y = t.forward(x)
    assert abs(float(_np(y).sum()) - 1.0) < 1e-5
    np.testing.assert_allclose(_np(t.inverse(y)), _np(x), atol=1e-4)


def test_independent_reinterprets_batch():
    base = D.Normal(np.zeros((3, 4), np.float32),
                    np.ones((3, 4), np.float32))
    ind = D.Independent(base, 1)
    assert ind.batch_shape == (3,)
    assert ind.event_shape == (4,)
    lp = ind.log_prob(ind.sample())
    assert tuple(lp.shape) == (3,)


def test_multivariate_normal_batched_values():
    d = D.MultivariateNormal(
        np.zeros(3, np.float32),
        scale_tril=np.diag([1.0, 2.0, 0.5]).astype(np.float32))
    s = d.sample((11,))
    lp = d.log_prob(s)
    assert tuple(lp.shape) == (11,)
    # against the factored normal
    ref = (D.Normal(0., 1.).log_prob(paddle.to_tensor(_np(s)[:, 0])))
    ref2 = D.Normal(0., 2.).log_prob(paddle.to_tensor(_np(s)[:, 1]))
    ref3 = D.Normal(0., 0.5).log_prob(paddle.to_tensor(_np(s)[:, 2]))
    np.testing.assert_allclose(_np(lp), _np(ref) + _np(ref2) + _np(ref3),
                               atol=1e-4)


def test_transform_all_parity_with_reference(api_spec):
    # paddle.distribution.transform __all__ must cover the classes of
    # the frozen surface that the module itself defines (the spec also
    # records what it imports)
    from paddle_tpu.distribution import transform as T
    ref_all = {
        name for name, entry in
        api_spec["paddle_tpu.distribution.transform"].items()
        if entry["kind"] == "class"
        and getattr(T, name).__module__ == T.__name__}
    assert ref_all, "no transform classes in tools/api_spec.json"
    missing = ref_all - set(T.__all__)
    assert not missing, f"missing transforms: {missing}"
    for name in ref_all:
        assert callable(getattr(T, name)), name


def test_stack_transform_matches_reference_example():
    from paddle_tpu import distribution as D
    x = paddle.to_tensor(
        np.stack([[1.0, 2, 3], [1, 2, 3]], 1).astype("float32"))
    t = D.StackTransform(
        (D.ExpTransform(), D.PowerTransform(paddle.to_tensor(2.0))), 1)
    f = t.forward(x)
    np.testing.assert_allclose(np.asarray(f._data_)[:, 0],
                               np.exp([1.0, 2, 3]), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(f._data_)[:, 1],
                               [1.0, 4, 9], rtol=1e-5)
    np.testing.assert_allclose(np.asarray(t.inverse(f)._data_),
                               np.asarray(x._data_), rtol=1e-5)
    ldj = t.forward_log_det_jacobian(x)
    np.testing.assert_allclose(np.asarray(ldj._data_)[:, 0],
                               [1.0, 2, 3], rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ldj._data_)[:, 1],
                               np.log([2.0, 4, 6]), rtol=1e-5)


def test_kl_cauchy_lognormal_expfamily():
    from paddle_tpu import distribution as D
    kl = D.kl_divergence(D.Cauchy(paddle.to_tensor(0.0),
                                  paddle.to_tensor(1.0)),
                         D.Cauchy(paddle.to_tensor(1.0),
                                  paddle.to_tensor(2.0)))
    np.testing.assert_allclose(float(np.asarray(kl._data_)),
                               np.log((9 + 1) / 8), rtol=1e-5)
    kl = D.kl_divergence(D.LogNormal(paddle.to_tensor(0.0),
                                     paddle.to_tensor(1.0)),
                         D.LogNormal(paddle.to_tensor(0.5),
                                     paddle.to_tensor(1.5)))
    expect = np.log(1.5) + (1.0 + 0.25) / (2 * 2.25) - 0.5
    np.testing.assert_allclose(float(np.asarray(kl._data_)), expect,
                               rtol=1e-5)


def test_categorical_rejects_degenerate_weights():
    import pytest
    with pytest.raises(ValueError, match="nonnegative weights"):
        D.Categorical(logits=np.zeros(4, np.float32))
    with pytest.raises(ValueError, match="nonnegative weights"):
        D.Categorical(logits=np.array([0.5, -0.1], np.float32))
