"""nn/functional/optimizer/io long-tail surface (parity with the frozen
surface in tools/api_spec.json + OpTest-style numerics; conv transposes
verified vs torch elsewhere)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
F = nn.functional


def T(a):
    return paddle.to_tensor(np.asarray(a))


def test_subpackage_all_parity(api_spec):
    for mod, name in [(paddle.nn, "nn"),
                      (paddle.nn.functional, "nn.functional"),
                      (paddle.optimizer, "optimizer"),
                      (paddle.io, "io")]:
        missing = sorted(s for s in api_spec[f"paddle_tpu.{name}"]
                         if not hasattr(mod, s))
        assert missing == [], f"{name}: {missing}"


def test_ctc_loss_matches_torch():
    torch = pytest.importorskip("torch")
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(7, 3, 6)).astype(np.float32)
    labels = np.array([[1, 2, 3], [2, 2, 0], [5, 4, 1]], np.int32)
    il, ll = np.array([7, 6, 7]), np.array([3, 2, 3])
    ref = torch.nn.functional.ctc_loss(
        torch.from_numpy(logits).log_softmax(-1),
        torch.from_numpy(labels.astype(np.int64)),
        torch.from_numpy(il), torch.from_numpy(ll),
        blank=0, reduction="none").numpy()
    got = F.ctc_loss(T(logits), T(labels), T(il), T(ll),
                     reduction="none").numpy()
    np.testing.assert_allclose(got, ref, atol=1e-3)


def test_conv_transposes_match_torch():
    torch = pytest.importorskip("torch")
    rng = np.random.default_rng(0)
    x = rng.normal(size=(1, 3, 5, 5)).astype(np.float32)
    w = rng.normal(size=(3, 4, 3, 3)).astype(np.float32)
    for st, p in [(2, 0), (2, 1), (1, 1)]:
        ref = torch.nn.functional.conv_transpose2d(
            torch.from_numpy(x), torch.from_numpy(w), stride=st,
            padding=p).numpy()
        got = F.conv2d_transpose(T(x), T(w), stride=st, padding=p).numpy()
        np.testing.assert_allclose(got, ref, atol=1e-4)
    x1 = rng.normal(size=(2, 3, 10)).astype(np.float32)
    w1 = rng.normal(size=(3, 4, 3)).astype(np.float32)
    ref = torch.nn.functional.conv_transpose1d(
        torch.from_numpy(x1), torch.from_numpy(w1), stride=2,
        padding=1).numpy()
    np.testing.assert_allclose(
        F.conv1d_transpose(T(x1), T(w1), stride=2, padding=1).numpy(),
        ref, atol=1e-4)


def test_unpool_roundtrip_and_fold_inverse():
    rng = np.random.default_rng(0)
    x = T(rng.normal(size=(1, 2, 4, 4)).astype(np.float32))
    pooled, idx = F.max_pool2d(x, 2, return_mask=True)
    rec = F.max_unpool2d(pooled, idx, 2)
    assert rec.shape == [1, 2, 4, 4]
    # every pooled max lands back at its original argmax position
    np.testing.assert_allclose(np.sort(rec.numpy()[rec.numpy() != 0]),
                               np.sort(pooled.numpy().ravel()), rtol=1e-6)
    xi = T(rng.normal(size=(1, 2, 6, 6)).astype(np.float32))
    rec = F.fold(F.unfold(xi, 2, strides=2), (6, 6), 2, strides=2)
    np.testing.assert_allclose(rec.numpy(), xi.numpy(), atol=1e-5)


def test_pool3d_and_adaptive():
    x = T(np.arange(2 * 3 * 8 * 8 * 8, dtype=np.float32)
          .reshape(2, 3, 8, 8, 8))
    assert F.max_pool3d(x, 2).shape == [2, 3, 4, 4, 4]
    assert F.avg_pool3d(x, 2).shape == [2, 3, 4, 4, 4]
    assert nn.AdaptiveAvgPool3D(2)(x).shape == [2, 3, 2, 2, 2]
    x1 = T(np.arange(2 * 3 * 10, dtype=np.float32).reshape(2, 3, 10))
    out = nn.AdaptiveAvgPool1D(5)(x1)
    assert out.shape == [2, 3, 5]
    np.testing.assert_allclose(out.numpy()[0, 0],
                               [0.5, 2.5, 4.5, 6.5, 8.5])


def test_loss_zoo_values():
    x = T(np.array([[2.0, -1.0], [0.5, 0.1]], np.float32))
    y = T(np.array([[1.0, -1.0], [1.0, -1.0]], np.float32))
    sm = F.soft_margin_loss(x, y)
    ref = np.log1p(np.exp(-np.array([[2.0, 1.0], [0.5, -0.1]]))).mean()
    assert float(sm) == pytest.approx(ref, rel=1e-5)
    p = T(np.array([[0.9, 0.1]], np.float32))
    ll = F.log_loss(p, T(np.array([[1.0, 0.0]], np.float32)))
    np.testing.assert_allclose(ll.numpy(), -np.log(np.array([[0.9, 0.9]])),
                               rtol=1e-3)
    probs = T(np.array([[0.8, 0.1, 0.1]], np.float32))
    d = F.dice_loss(probs, T(np.array([[0]], np.int64)))
    assert 0.0 < float(d) < 1.0
    g = F.gaussian_nll_loss(T(np.zeros(4, np.float32)),
                            T(np.zeros(4, np.float32)),
                            T(np.ones(4, np.float32)))
    assert float(g) == pytest.approx(0.0, abs=1e-6)


def test_hsigmoid_and_margin_ce_train():
    paddle.seed(0)
    layer = nn.HSigmoidLoss(8, 10)
    x = T(np.random.default_rng(0).normal(size=(4, 8)).astype(np.float32))
    x.stop_gradient = False
    loss = layer(x, T(np.array([0, 3, 7, 9]))).sum()
    loss.backward()
    assert layer.weight.grad is not None
    logits = T((np.random.default_rng(1).normal(size=(4, 10)) * 0.1)
               .astype(np.float32))
    loss, sm = F.margin_cross_entropy(logits, T(np.array([1, 2, 3, 4])),
                                      return_softmax=True)
    assert np.isfinite(float(loss)) and sm.shape == [4, 10]


def test_new_layers_forward():
    rng = np.random.default_rng(0)
    x = T(rng.normal(size=(2, 4, 8, 8)).astype(np.float32))
    assert nn.InstanceNorm2D(4)(x).shape == [2, 4, 8, 8]
    assert nn.LocalResponseNorm(3)(x).shape == [2, 4, 8, 8]
    assert nn.ChannelShuffle(2)(x).shape == [2, 4, 8, 8]
    assert nn.PixelUnshuffle(2)(x).shape == [2, 16, 4, 4]
    assert nn.UpsamplingNearest2D(scale_factor=2)(x).shape == [2, 4, 16, 16]
    assert nn.ZeroPad2D([1, 1, 2, 2])(x).shape == [2, 4, 12, 10]
    assert nn.Softmax2D()(x).shape == [2, 4, 8, 8]
    assert nn.CosineSimilarity(axis=1)(x, x).shape == [2, 8, 8]
    b = nn.Bilinear(3, 4, 5)
    assert b(T(rng.normal(size=(2, 3)).astype(np.float32)),
             T(rng.normal(size=(2, 4)).astype(np.float32))).shape == [2, 5]
    c3 = nn.Conv3D(2, 3, 2)
    assert c3(T(rng.normal(size=(1, 2, 4, 4, 4)).astype(np.float32))
              ).shape == [1, 3, 3, 3, 3]
    ct = nn.Conv1DTranspose(3, 4, 3, stride=2)
    assert ct(T(rng.normal(size=(2, 3, 5)).astype(np.float32))
              ).shape == [2, 4, 11]
    sn = nn.SpectralNorm((4, 6), power_iters=2)
    w = T(rng.normal(size=(4, 6)).astype(np.float32))
    wn = sn(w)
    # spectral norm of the output ~ 1
    s = np.linalg.svd(wn.numpy(), compute_uv=False)[0]
    assert s == pytest.approx(1.0, rel=0.2)


def test_sync_batchnorm_convert():
    net = nn.Sequential(nn.Conv2D(2, 4, 3), nn.BatchNorm2D(4))
    net2 = nn.SyncBatchNorm.convert_sync_batchnorm(net)
    assert isinstance(net2[1], nn.SyncBatchNorm)
    x = T(np.random.default_rng(0).normal(size=(2, 2, 6, 6))
          .astype(np.float32))
    assert net2(x).shape == [2, 4, 4, 4]


def test_new_optimizers_converge():
    def run(opt_cls, **kw):
        paddle.seed(0)
        lin = nn.Linear(4, 1)
        opt = opt_cls(parameters=lin.parameters(), **kw)
        x = T(np.ones((8, 4), np.float32))
        losses = []
        for _ in range(12):
            loss = ((lin(x) - 1.0) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        return losses

    for cls, kw in [(paddle.optimizer.Adadelta, {"learning_rate": 1.0}),
                    (paddle.optimizer.Adamax, {"learning_rate": 0.1})]:
        losses = run(cls, **kw)
        # Adadelta's self-scaled steps start tiny; Adamax overshoots near
        # the optimum — require clear overall progress either way
        assert losses[-1] < losses[0] * 0.95, (cls.__name__, losses)
        assert min(losses) < losses[0] * 0.5 or \
            all(b < a for a, b in zip(losses, losses[1:])), \
            (cls.__name__, losses)


def test_lbfgs_quadratic():
    paddle.seed(0)
    w = paddle.create_parameter([2], "float32")
    with paddle.no_grad():
        paddle.normal_(w, mean=3.0, std=0.1)
    opt = paddle.optimizer.LBFGS(parameters=[w], max_iter=10,
                                 line_search_fn="strong_wolfe")

    def closure():
        loss = ((w - paddle.to_tensor(np.array([1.0, -2.0], np.float32)))
                ** 2).sum()
        loss.backward()
        return loss

    loss = opt.step(closure)
    assert float(loss) < 1e-3
    np.testing.assert_allclose(w.numpy(), [1.0, -2.0], atol=1e-2)


def test_beam_search_decoder():
    """Beam decode over a deterministic cell: transitions always favor
    token (prev+1) % V, so the best beam counts up from start."""
    V, B, beam = 5, 2, 3
    emb = paddle.to_tensor(np.eye(V, dtype=np.float32))

    class CountCell(nn.Layer):
        def forward(self, inputs, states):
            # inputs: one-hot of last token [N, V]; favor next token
            logits = paddle.concat([inputs[:, -1:], inputs[:, :-1]],
                                   axis=1) * 5.0
            return logits, states

    dec = nn.BeamSearchDecoder(CountCell(), start_token=0, end_token=4,
                               beam_size=beam,
                               embedding_fn=lambda t:
                               paddle.nn.functional.one_hot(t, V))
    init = paddle.zeros([B, 1])
    out, _ = paddle.nn.dynamic_decode(dec, inits=init, max_step_num=6)
    seqs = np.asarray(out.numpy())          # [batch, time, beam]
    assert seqs.shape[0] == B and seqs.shape[2] == beam
    # best beam counts up: 1,2,3,4 then end padding
    np.testing.assert_array_equal(seqs[0, :4, 0], [1, 2, 3, 4])
    # time-major flag transposes the leading dims
    out_tm, _ = paddle.nn.dynamic_decode(dec, inits=init, max_step_num=6,
                                         output_time_major=True)
    assert list(out_tm.shape)[:2] == [seqs.shape[1], B]


def test_io_extras():
    class DS(paddle.io.Dataset):
        def __init__(self, base):
            self.base = base

        def __len__(self):
            return 4

        def __getitem__(self, i):
            return self.base + i

    comp = paddle.io.ComposeDataset([DS(0), DS(10)])
    assert comp[1] == (1, 11)

    class IDS(paddle.io.IterableDataset):
        def __init__(self, vals):
            self.vals = vals

        def __iter__(self):
            return iter(self.vals)

    chain = paddle.io.ChainDataset([IDS([1, 2]), IDS([3])])
    assert list(chain) == [1, 2, 3]
    assert paddle.io.get_worker_info() is None
