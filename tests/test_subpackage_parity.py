"""Subpackage parity with the frozen public surface
(tools/api_spec.json) + functional smoke of the static/sparse/fft compat
surface."""
import importlib

import numpy as np
import pytest

import paddle_tpu as paddle


def _assert_parity(api_spec, names):
    for name in names:
        mod = importlib.import_module(f"paddle_tpu.{name}")
        missing = sorted(s for s in api_spec[f"paddle_tpu.{name}"]
                         if not hasattr(mod, s))
        assert missing == [], f"{name}: {missing}"


def test_all_subpackages_parity(api_spec):
    _assert_parity(api_spec, ["static", "static.nn", "amp", "vision",
                              "fft", "sparse", "distribution"])


def test_sparse_ops():
    sp = paddle.sparse
    x = sp.sparse_coo_tensor([[0, 1], [1, 0]], [2.0, -3.0], [2, 2])
    np.testing.assert_allclose(sp.abs(x).to_dense().numpy(),
                               [[0, 2], [3, 0]])
    np.testing.assert_allclose(
        sp.mv(x, paddle.to_tensor(np.array([1.0, 2.0], np.float32)))
        .numpy(), [4.0, -3.0])
    np.testing.assert_allclose(sp.multiply(x, x).to_dense().numpy(),
                               [[0, 4], [9, 0]])
    np.testing.assert_allclose(
        sp.transpose(x, [1, 0]).to_dense().numpy(), [[0, -3], [2, 0]])
    m = sp.masked_matmul(paddle.ones([2, 3]), paddle.ones([3, 2]), x)
    np.testing.assert_allclose(m.to_dense().numpy(), [[0, 3], [3, 0]])
    assert sp.is_same_shape(x, x)
    assert float(sp.sum(x)) == pytest.approx(-1.0)
    u, s, v = sp.pca_lowrank(x, q=1)
    assert u.shape == [2, 1] and s.shape == [1]


def test_static_nn_fc_trains():
    import paddle_tpu.static as static
    x = paddle.to_tensor(np.ones((3, 4), np.float32))
    out = static.nn.fc(x, 5, activation="relu")
    assert out.shape == [3, 5]
    out2 = static.nn.conv2d(paddle.ones([1, 2, 6, 6]), 3, 3, act="relu")
    assert out2.shape == [1, 3, 4, 4]
    seq = paddle.to_tensor(
        np.arange(12, dtype=np.float32).reshape(2, 3, 2))
    lens = paddle.to_tensor(np.array([2, 3]))
    pooled = static.nn.sequence_pool(seq, "average", lengths=lens)
    np.testing.assert_allclose(pooled.numpy()[0],
                               seq.numpy()[0, :2].mean(0))
    last = static.nn.sequence_last_step(seq, lengths=lens)
    np.testing.assert_allclose(last.numpy()[0], seq.numpy()[0, 1])
    rev = static.nn.sequence_reverse(seq, lengths=lens)
    np.testing.assert_allclose(rev.numpy()[0, 0], seq.numpy()[0, 1])
    np.testing.assert_allclose(rev.numpy()[0, 2], seq.numpy()[0, 2])


def test_static_control_flow_and_metrics():
    import paddle_tpu.static as static
    r = static.nn.cond(paddle.to_tensor(np.array(True)),
                       lambda: paddle.ones([2]),
                       lambda: paddle.zeros([2]))
    np.testing.assert_allclose(r.numpy(), [1, 1])
    i, = static.nn.while_loop(
        lambda i: i < 5,
        lambda i: i + 1,
        [paddle.to_tensor(np.array(0.0, np.float32))])
    assert float(i) == 5.0
    pred = paddle.to_tensor(np.array([[0.1, 0.9], [0.8, 0.2]], np.float32))
    lbl = paddle.to_tensor(np.array([[1], [0]]))
    acc = static.accuracy(pred, lbl)
    assert float(acc) == pytest.approx(1.0)
    a, _, _ = static.auc(pred, lbl)
    assert float(a) == pytest.approx(1.0)


def test_static_ema():
    import paddle_tpu.static as static
    p = paddle.create_parameter([2], "float32")
    with paddle.no_grad():
        paddle.fill_(p, 1.0) if hasattr(paddle, "fill_") else None
        p.set_value(np.ones(2, np.float32))
    ema = static.ExponentialMovingAverage(decay=0.5)
    ema.update([p])
    with paddle.no_grad():
        p.set_value(np.full(2, 3.0, np.float32))
    ema.update([p])
    with ema.apply():
        np.testing.assert_allclose(p.numpy(), [2.0, 2.0])  # 0.5*1+0.5*3
    np.testing.assert_allclose(p.numpy(), [3.0, 3.0])  # restored


def test_deform_conv2d_zero_offset_is_conv():
    from paddle_tpu.vision.ops import deform_conv2d
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.normal(size=(1, 4, 6, 6)).astype(np.float32))
    w = paddle.to_tensor(rng.normal(size=(5, 4, 3, 3)).astype(np.float32))
    off = paddle.zeros([1, 18, 4, 4])
    got = deform_conv2d(x, off, w)
    ref = paddle.nn.functional.conv2d(x, w)
    np.testing.assert_allclose(got.numpy(), ref.numpy(), atol=1e-4)
    m = paddle.ones([1, 9, 4, 4]) * 0.5
    np.testing.assert_allclose(deform_conv2d(x, off, w, mask=m).numpy(),
                               0.5 * ref.numpy(), atol=1e-4)


def test_fft_hermitian_family():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(4, 5)).astype(np.complex64)
    got = paddle.fft.hfft2(paddle.to_tensor(x)).numpy()
    ref = np.fft.hfft(np.fft.fft(x, axis=0), axis=1)
    np.testing.assert_allclose(got, ref, atol=1e-3)
    y = rng.normal(size=(4, 8)).astype(np.float32)
    got = paddle.fft.ihfft2(paddle.to_tensor(y)).numpy()
    ref = np.fft.ifft(np.fft.ihfft(y, axis=1), axis=0)
    np.testing.assert_allclose(got, ref, atol=1e-4)


def test_vision_image_backend():
    paddle.vision.set_image_backend("pil")
    assert paddle.vision.get_image_backend() == "pil"
    with pytest.raises(ValueError):
        paddle.vision.set_image_backend("nope")
    assert paddle.amp.is_bfloat16_supported()


def test_remaining_namespaces_parity(api_spec):
    _assert_parity(api_spec, ["incubate", "text", "device", "jit",
                              "autograd", "hub"])


def test_viterbi_matches_bruteforce():
    import itertools
    rng = np.random.default_rng(0)
    pot = rng.normal(size=(1, 4, 3)).astype(np.float32)
    trans = rng.normal(size=(5, 5)).astype(np.float32)
    sc, path = paddle.text.viterbi_decode(
        paddle.to_tensor(pot), paddle.to_tensor(trans),
        paddle.to_tensor(np.array([4])))
    best, bs = None, -1e9
    for seq in itertools.product(range(3), repeat=4):
        s = trans[-2, seq[0]] + pot[0, 0, seq[0]]
        for t in range(1, 4):
            s += trans[seq[t - 1], seq[t]] + pot[0, t, seq[t]]
        s += trans[seq[-1], -1]
        if s > bs:
            bs, best = s, seq
    assert abs(float(sc) - bs) < 1e-4
    assert tuple(path.numpy()[0]) == best


def test_saved_tensors_hooks_fire():
    events = []
    with paddle.autograd.saved_tensors_hooks(
            lambda t: events.append("pack") or t,
            lambda p: events.append("unpack") or p):
        x = paddle.to_tensor(np.ones(3, np.float32), stop_gradient=False)
        y = (x * 2.0).sum()
    y.backward()
    assert "pack" in events and "unpack" in events
    np.testing.assert_allclose(x.grad.numpy(), np.full(3, 2.0))


def test_hub_local_repo(tmp_path):
    (tmp_path / "hubconf.py").write_text(
        "def toy(scale=2):\n"
        "    'a toy model'\n"
        "    return ('model', scale)\n")
    assert paddle.hub.list(str(tmp_path)) == ["toy"]
    assert "toy model" in paddle.hub.help(str(tmp_path), "toy")
    assert paddle.hub.load(str(tmp_path), "toy", scale=3) == ("model", 3)


def test_deep_namespaces_parity(api_spec):
    _assert_parity(api_spec, [
        "vision.datasets", "incubate.nn", "incubate.nn.functional",
        "incubate.optimizer", "metric", "nn.initializer", "nn.utils",
        "linalg"])


def test_fused_layers_forward_and_train():
    from paddle_tpu.incubate import nn as inn
    paddle.seed(0)
    enc = inn.FusedTransformerEncoderLayer(16, 4, 32, dropout_rate=0.0)
    opt = paddle.optimizer.AdamW(1e-3, parameters=enc.parameters())
    x = paddle.to_tensor(
        np.random.default_rng(0).normal(size=(2, 5, 16)).astype(np.float32))
    losses = []
    for _ in range(4):
        loss = (enc(x) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    moe = inn.FusedEcMoe(16, 32, 4)
    assert moe(x).shape == [2, 5, 16]


def test_nn_utils_weight_norm():
    from paddle_tpu.nn.utils import (weight_norm, remove_weight_norm,
                                     parameters_to_vector,
                                     vector_to_parameters,
                                     clip_grad_norm_, clip_grad_value_)
    lin = paddle.nn.Linear(4, 3)
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    weight_norm(lin)
    o1 = lin(x)
    # g/v reparameterization reproduces the original weight exactly
    remove_weight_norm(lin)
    np.testing.assert_allclose(lin(x).numpy(), o1.numpy(), rtol=1e-5)
    vec = parameters_to_vector(lin.parameters())
    assert vec.shape == [15]
    vector_to_parameters(vec * 0.0, lin.parameters())
    assert float(np.abs(lin(x).numpy()).sum()) == 0.0
    loss = (lin(x) ** 2).sum()
    loss.backward()
    clip_grad_value_(lin.parameters(), 1e-8)
    n = clip_grad_norm_(lin.parameters(), 1.0)
    assert float(n) <= 1e-6


def test_linalg_extras():
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.normal(size=(3, 5)).astype(np.float32))
    np.testing.assert_allclose(paddle.linalg.cov(x).numpy(),
                               np.cov(x.numpy()), rtol=1e-4)
    np.testing.assert_allclose(paddle.linalg.corrcoef(x).numpy(),
                               np.corrcoef(x.numpy()), rtol=1e-4)
    a = rng.normal(size=(4, 4)).astype(np.float32)
    lu, piv = paddle.linalg.lu(paddle.to_tensor(a))
    p, l, u = paddle.linalg.lu_unpack(lu, piv)
    np.testing.assert_allclose(
        (p.numpy() @ l.numpy() @ u.numpy()), a, atol=1e-4)


def test_metric_accuracy_fn():
    pred = paddle.to_tensor(
        np.array([[0.1, 0.9], [0.8, 0.2], [0.3, 0.7]], np.float32))
    lbl = paddle.to_tensor(np.array([1, 0, 0]))
    assert float(paddle.metric.accuracy(pred, lbl)) == pytest.approx(2 / 3)


def test_dataset_folder(tmp_path):
    import numpy as _np
    for cls in ("cat", "dog"):
        d = tmp_path / cls
        d.mkdir()
        for i in range(2):
            _np.save(d / f"{i}.npy", _np.full((2, 2), i, _np.float32))
    ds = paddle.vision.datasets.DatasetFolder(str(tmp_path))
    assert len(ds) == 4
    img, target = ds[0]
    assert target in (0, 1)
    flat = paddle.vision.datasets.ImageFolder(str(tmp_path))
    assert len(flat) == 4


_ZOO_LIGHT = ["alexnet", "squeezenet1_0"]   # fast-lane representatives
_ZOO_HEAVY = ["vgg11", "densenet121", "inception_v3",
              "shufflenet_v2_x1_0", "mobilenet_v2", "mobilenet_v3_small",
              "mobilenet_v3_large", "resnext50_32x4d", "wide_resnet50_2"]


@pytest.mark.parametrize("name", _ZOO_LIGHT + _ZOO_HEAVY)
def test_model_zoo_families_forward(name):
    """Every model family in the reference zoo instantiates and runs a
    forward pass (tiny input).  Heavy families run in the slow lane
    (conftest _SLOW_TESTS); two light ones keep the family smoke fast."""
    from paddle_tpu.vision import models as M
    x = paddle.to_tensor(np.random.default_rng(0)
                         .normal(size=(1, 3, 64, 64)).astype(np.float32))
    paddle.seed(0)
    net = getattr(M, name)(num_classes=7)
    net.eval()
    assert net(x).shape == [1, 7], name


def test_googlenet_aux_heads():
    from paddle_tpu.vision import models as M
    x = paddle.to_tensor(np.random.default_rng(0)
                         .normal(size=(1, 3, 64, 64)).astype(np.float32))
    out, aux1, aux2 = M.googlenet(num_classes=7)(x)
    assert out.shape == [1, 7] and aux1.shape == [1, 7]


def test_hapi_new_callbacks():
    from paddle_tpu.hapi import ReduceLROnPlateau, VisualDL

    class _Opt:
        def __init__(self):
            self.lr = 1.0

        def get_lr(self):
            return self.lr

        def set_lr(self, v):
            self.lr = v

    class _Model:
        _optimizer = _Opt()

    cb = ReduceLROnPlateau(monitor="loss", factor=0.5, patience=1,
                           verbose=0)
    cb.model = _Model()
    cb.on_epoch_end(0, {"loss": 1.0})
    cb.on_epoch_end(1, {"loss": 1.0})  # no improvement → wait=1 ≥ patience
    assert cb.model._optimizer.lr == 0.5

    import tempfile, os, json
    with tempfile.TemporaryDirectory() as d:
        v = VisualDL(log_dir=d)
        v.on_epoch_end(0, {"loss": 0.25})
        line = open(os.path.join(d, "scalars.jsonl")).readline()
        assert json.loads(line)["loss"] == 0.25
