"""Compiled train step (framework/train_step.py, ISSUE 8).

Equality contract (docs/TRAIN_STEP.md): the one-program step performs
the EXACT op sequence of the eager step, but XLA fuses it into one
program whose codegen may contract multiplies into fma and vectorize
scalarizing reductions (the loss value, an ACTIVE global-norm clip)
differently than the standalone per-op programs — those outputs agree
to ~1 ulp.  The parameter-update chain itself is bitwise-stable: when
no active clip rescales by a fused reduction, weights stay BIT-equal
to eager for the whole trajectory, and that is asserted here.  Any
semantic drift (wrong scale, missing bias correction, reordered
update) would diverge far beyond ulp and fail these tests loudly.
"""
import json
import math
import os
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, Model

STEPS = 12
_FLAGS = ("FLAGS_compiled_train_step", "FLAGS_pallas_fused_optimizer")


@pytest.fixture(autouse=True)
def _restore_flags():
    saved = {f: paddle.get_flags(f)[f] for f in _FLAGS}
    yield
    paddle.set_flags(saved)


def _batches(steps=STEPS, batch=4, din=8, dout=4, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.standard_normal((batch, din)).astype("float32"),
             rng.standard_normal((batch, dout)).astype("float32"))
            for _ in range(steps)]


def _mlp_model(clip=None, lr=0.01):
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    opt = paddle.optimizer.AdamW(lr, parameters=net.parameters(),
                                 weight_decay=0.01, grad_clip=clip)
    model = Model(net)
    model.prepare(optimizer=opt, loss=lambda o, y: ((o - y) ** 2).mean())
    return model, net


def _run(compiled, clip=None, accum=1, batches=None, hook=None):
    paddle.set_flags({"FLAGS_compiled_train_step": compiled})
    model, net = _mlp_model(clip=clip)
    if hook:
        hook(model, net)
    model._accum_steps = accum
    losses = []
    for i, (x, y) in enumerate(batches or _batches()):
        update = (i + 1) % accum == 0
        losses.append(np.float32(
            model.train_batch(paddle.to_tensor(x), paddle.to_tensor(y),
                              update=update)[0]))
    weights = [p.numpy().copy() for p in net.parameters()]
    return losses, weights, model


def _assert_ulp_close(a, b, rel=2e-6):
    for la, lb in zip(a, b):
        assert abs(la - lb) <= rel * max(abs(la), 1e-12), (la, lb)


# ---------------------------------------------------------------- core


def test_compiled_engages_and_matches_eager_with_clip():
    """AdamW + weight decay + ACTIVE global-norm clip, 12 steps: losses
    ulp-close, weights tightly close, compiled lane genuinely on."""
    from paddle_tpu.utils import monitor
    clip = nn.ClipGradByGlobalNorm(0.05)   # small norm -> clip active
    le, we, _ = _run(False, clip=clip)
    hits0 = monitor.all_stats().get("jit.compiled_step_hit", 0)
    lc, wc, m = _run(True, clip=clip)
    cs = m._compiled_step
    assert cs and cs is not False and cs.compiled, cs and cs.fallback_reason
    assert monitor.all_stats().get("jit.compiled_step_hit", 0) \
        >= hits0 + STEPS - 1                     # call 1 is eager warmup
    assert len(set(np.float32(le))) > 3          # trajectory moved
    _assert_ulp_close(le, lc)
    for a, b in zip(we, wc):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=1e-7)


def test_weights_bitwise_equal_without_active_clip():
    """No fused-reduction rescale in the update chain -> the parameter
    trajectory is BIT-identical to eager for all 12 steps."""
    le, we, _ = _run(False)
    lc, wc, m = _run(True)
    assert m._compiled_step.compiled
    for a, b in zip(we, wc):
        assert a.tobytes() == b.tobytes()
    _assert_ulp_close(le, lc)


def test_grad_accumulation_matches_eager():
    """accumulate_grad_batches=2: micro-steps compile as the
    backward-only program, the closing step as the full update."""
    clip = nn.ClipGradByGlobalNorm(1.0)
    le, we, _ = _run(False, clip=clip, accum=2)
    lc, wc, m = _run(True, clip=clip, accum=2)
    assert m._compiled_step.compiled
    _assert_ulp_close(le, lc)
    for a, b in zip(we, wc):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=1e-7)
    # accumulation genuinely accumulated: a full-update-every-step run
    # lands elsewhere
    l1, _, _ = _run(True, clip=clip, accum=1)
    assert any(np.float32(a) != np.float32(b) for a, b in zip(l1[1:],
                                                              lc[1:]))


# ----------------------------------------------------------- fallbacks


def test_flag_off_stays_undecided_and_eager():
    paddle.set_flags({"FLAGS_compiled_train_step": False})
    model, net = _mlp_model()
    for x, y in _batches(steps=3):
        model.train_batch(paddle.to_tensor(x), paddle.to_tensor(y))
    # undecided (None), so flipping the flag later can still compile
    assert model._compiled_step is None


def test_layer_hook_falls_back_byte_identical():
    seen = []

    def install(model, net):
        net[0].register_forward_post_hook(
            lambda layer, inp, out: seen.append(1) or out)

    le, we, _ = _run(False, hook=install)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        lc, wc, m = _run(True, hook=install)
    cs = m._compiled_step
    assert cs is not None and cs is not False
    assert "hook" in (cs.fallback_reason or "")
    assert seen                                     # hooks genuinely ran
    assert [np.float32(a) for a in le] == [np.float32(b) for b in lc]
    for a, b in zip(we, wc):
        assert a.tobytes() == b.tobytes()


def test_tensor_grad_hook_falls_back_byte_identical():
    def install(model, net):
        net[2].weight.register_hook(lambda g: g)

    le, we, _ = _run(False, hook=install)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        lc, wc, m = _run(True, hook=install)
    assert "hook" in (m._compiled_step.fallback_reason or "")
    for a, b in zip(we, wc):
        assert a.tobytes() == b.tobytes()


def test_host_read_in_forward_falls_back_not_dies():
    """float()/item() inside the forward escapes tracing: the step must
    warn once, latch eager, and keep training byte-identically."""
    from paddle_tpu.framework.train_step import CompiledTrainStep

    def build(compiled):
        paddle.set_flags({"FLAGS_compiled_train_step": compiled})
        paddle.seed(0)
        w = paddle.Parameter(np.ones((4,), np.float32))
        opt = paddle.optimizer.AdamW(0.05, parameters=[w])

        def forward(x, y):
            h = w * x
            assert float(h.sum()) < 1e9     # host read of a live value
            return ((h - y) ** 2).mean()
        return w, opt, forward

    batches = [(np.float32(np.arange(4) + i), np.zeros(4, np.float32))
               for i in range(5)]

    w_e, opt_e, fwd_e = build(False)
    eager = []
    for x, y in batches:
        loss = fwd_e(paddle.to_tensor(x), paddle.to_tensor(y))
        loss.backward()
        opt_e.step()
        opt_e.clear_grad()
        eager.append(float(np.asarray(loss._data_)))

    w_c, opt_c, fwd_c = build(True)
    cs = CompiledTrainStep(fwd_c, opt_c)
    got = []
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        for x, y in batches:
            got.append(float(np.asarray(
                cs(paddle.to_tensor(x), paddle.to_tensor(y))._data_)))
    assert "host read" in (cs.fallback_reason or "")
    assert any("compiled train step disabled" in str(r.message)
               for r in rec)
    assert got == eager
    assert w_c.numpy().tobytes() == w_e.numpy().tobytes()


def test_donation_alias_tied_buffers_skips_compiled_call():
    """Two parameters backed by ONE device buffer may not both be
    donated; the step must detect the alias per call and run eager."""
    from paddle_tpu.framework.train_step import CompiledTrainStep
    from paddle_tpu.utils import monitor

    paddle.seed(0)
    w1 = paddle.Parameter(np.ones((4,), np.float32))
    w2 = paddle.Parameter(np.ones((4,), np.float32))
    w2._data_ = w1._data_                      # tied: same jax array
    opt = paddle.optimizer.AdamW(0.05, parameters=[w1, w2])

    def forward(x, y):
        return ((w1 * x + w2 * x - y) ** 2).mean()

    cs = CompiledTrainStep(forward, opt)
    before = monitor.all_stats().get("jit.compiled_step_alias_fallback", 0)
    x = paddle.to_tensor(np.ones(4, np.float32))
    y = paddle.to_tensor(np.zeros(4, np.float32))
    for _ in range(3):
        loss = cs(x, y)
        assert np.isfinite(float(np.asarray(loss._data_)))
        # re-tie after each eager update so the alias stays live
        w2._data_ = w1._data_
    assert monitor.all_stats().get(
        "jit.compiled_step_alias_fallback", 0) > before


# ------------------------------------------------------- AMP / scaler


def _scaler_lane(compiled, steps=8):
    from paddle_tpu.framework.train_step import CompiledTrainStep
    from paddle_tpu.amp import GradScaler

    paddle.set_flags({"FLAGS_compiled_train_step": compiled})
    paddle.seed(0)
    w = paddle.Parameter(np.ones((4,), np.float32))
    opt = paddle.optimizer.AdamW(0.05, parameters=[w], weight_decay=0.01)
    sc = GradScaler(init_loss_scaling=8.0, incr_every_n_steps=3,
                    decr_every_n_nan_or_inf=1)

    def forward(x, y):
        return ((w * x - y) ** 2).mean()

    cs = CompiledTrainStep(forward, opt, scaler=sc)
    rng = np.random.default_rng(0)
    losses, snapshots = [], []
    for i in range(steps):
        xv = rng.standard_normal(4).astype("float32")
        if i == 4:
            xv = xv * np.float32(3e38)     # overflow -> found-inf skip
        yv = rng.standard_normal(4).astype("float32")
        loss = cs(paddle.to_tensor(xv), paddle.to_tensor(yv))
        losses.append(float(np.asarray(loss._data_)))
        snapshots.append(w.numpy().copy())
    cs.sync_scaler()
    return losses, snapshots, (sc._scale, sc._good_steps, sc._bad_steps), cs


def test_amp_scaler_trajectory_and_infskip_match_eager():
    le, se, state_e, _ = _scaler_lane(False)
    lc, sc_, state_c, cs = _scaler_lane(True)
    assert cs.compiled, cs.fallback_reason
    _assert_ulp_close(le[:4] + le[5:], lc[:4] + lc[5:])
    assert not np.isfinite(lc[4])              # the poisoned step
    # found-inf skipped the update in BOTH lanes: weights unchanged
    np.testing.assert_array_equal(sc_[4], sc_[3])
    np.testing.assert_array_equal(se[4], se[3])
    # device-held scale/good/bad materialized back identically
    assert state_c == state_e
    for a, b in zip(se, sc_):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=1e-7)


def test_gradscaler_deferred_found_inf_stays_on_device():
    from paddle_tpu.amp import GradScaler
    w = paddle.Parameter(np.ones((4,), np.float32))
    opt = paddle.optimizer.AdamW(0.05, parameters=[w])
    sc = GradScaler(init_loss_scaling=8.0)

    loss = sc.scale((w * w).sum())
    loss.backward()
    w.grad._data = w.grad._data * np.float32("inf")
    sc.unscale_(opt, defer_found_inf=True)
    assert sc._found_inf is False              # decision NOT on host yet
    flag = sc._found_inf_tensor()
    assert float(np.asarray(flag._data_)[0]) == 1.0
    opt.clear_grad()

    sc2 = GradScaler(init_loss_scaling=8.0)
    loss = sc2.scale((w * w).sum())
    loss.backward()
    sc2.unscale_(opt, defer_found_inf=True)
    assert float(np.asarray(sc2._found_inf_tensor()._data_)[0]) == 0.0


# -------------------------------------------------- donation / resume


def test_checkpoint_resume_continues_bit_identical(tmp_path):
    """Donated buffers never leak into checkpoints: save at epoch 2,
    resume, and land bit-identically on the uninterrupted 4-epoch run
    (async_save exercises the pre-donation snapshot path)."""
    from paddle_tpu.hapi.callbacks import ModelCheckpoint
    from paddle_tpu.io import TensorDataset

    rng = np.random.default_rng(0)
    data = TensorDataset([rng.standard_normal((16, 8)).astype("float32"),
                          rng.standard_normal((16, 4)).astype("float32")])

    def fit(epochs, save_dir=None, resume=None, async_save=False):
        model, net = _mlp_model()
        cbs = None
        if save_dir:
            cbs = [ModelCheckpoint(1, save_dir, async_save=async_save)]
        model.fit(data, batch_size=4, epochs=epochs, verbose=0,
                  shuffle=False, log_freq=2, callbacks=cbs,
                  save_dir=None if cbs else save_dir, resume=resume)
        if model._compiled_step not in (None, False):
            assert model._compiled_step.compiled
        return [p.numpy().copy() for p in net.parameters()]

    ref = fit(4)
    save_dir = str(tmp_path / "ck")
    fit(2, save_dir=save_dir, async_save=True)
    resumed = fit(4, save_dir=save_dir, resume=True)
    for a, b in zip(ref, resumed):
        assert a.tobytes() == b.tobytes()


# ------------------------------------------------------ data parallel


def test_dp_mesh_psum_matches_single_device(monkeypatch):
    """PADDLE_COMPILED_DP=2 shards the batch under shard_map: gradient
    pmean over even shards == full-batch mean, so the trajectory must
    match the single-device eager run; odd batches fall back per call
    and the compiled lane resumes after."""
    from paddle_tpu.utils import monitor

    le, we, _ = _run(False)
    monkeypatch.setenv("PADDLE_COMPILED_DP", "2")
    lc, wc, m = _run(True)
    cs = m._compiled_step
    assert cs.compiled and cs._dp == 2, cs.fallback_reason
    _assert_ulp_close(le, lc, rel=5e-6)
    for a, b in zip(we, wc):
        np.testing.assert_allclose(a, b, rtol=5e-6, atol=5e-7)

    # ragged tail: batch 3 cannot shard over dp=2 -> one-off eager step
    ragged = monitor.all_stats().get("jit.compiled_step_ragged_fallback", 0)
    x = np.zeros((3, 8), np.float32)
    y = np.zeros((3, 4), np.float32)
    m.train_batch(paddle.to_tensor(x), paddle.to_tensor(y))
    assert monitor.all_stats().get(
        "jit.compiled_step_ragged_fallback", 0) == ragged + 1
    assert cs.fallback_reason is None          # not latched
    x4, y4 = _batches(steps=1)[0]
    m.train_batch(paddle.to_tensor(x4), paddle.to_tensor(y4))


def test_dp_psum_matches_two_proc_sync_grads_drill(tmp_path):
    """ISSUE 8 drill: 2-process eager dp (per-tensor ``_sync_grads``
    all-reduces, host-collective lane) vs the compiled step's in-program
    pmean on a 2-device mesh — same global batches, same trajectory."""
    from paddle_tpu.distributed.launch.context import Context, parse_args
    from paddle_tpu.distributed.launch.controller import (
        CollectiveController)

    worker = os.path.join(os.path.dirname(__file__),
                          "_train_step_dp_worker.py")
    args = parse_args(["--nproc_per_node", "2", worker, str(tmp_path)])
    code = CollectiveController(Context(args=args)).run()
    assert code == 0
    assert (tmp_path / "ok.0").exists() and (tmp_path / "ok.1").exists()
    ranks = [json.load(open(tmp_path / f"result.{r}.json"))
             for r in (0, 1)]
    # per-step global loss of the eager 2-proc lane = mean of shard means
    eager_losses = [(a + b) / 2.0 for a, b in zip(ranks[0]["losses"],
                                                  ranks[1]["losses"])]

    os.environ["PADDLE_COMPILED_DP"] = "2"
    try:
        lc, wc, m = _run(True, batches=_batches(steps=6))
    finally:
        del os.environ["PADDLE_COMPILED_DP"]
    assert m._compiled_step.compiled and m._compiled_step._dp == 2
    for a, b in zip(eager_losses, lc):
        assert abs(a - b) <= 1e-5 * max(abs(a), 1.0), (a, b)
    for got, ref in zip(wc, ranks[0]["weights"]):
        np.testing.assert_allclose(
            got.ravel(), np.asarray(ref, np.float32), rtol=1e-5,
            atol=1e-6)


# ----------------------------------------------------- pallas fused opt


def test_pallas_adam_kernel_gating_and_closeness(monkeypatch):
    """Shape gating of the row-blocked kernel, and closeness of the raw
    kernel against a hand-computed eager op sequence (1-ulp: the eager
    reference is built from standalone ops whose codegen may not fma,
    while the in-program contract is asserted bitwise below through
    ``optimizer.step`` itself)."""
    import jax.numpy as jnp
    from paddle_tpu.pallas import fused

    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    assert not fused.optimizer_kernels_enabled()    # CPU without interpret
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert fused.optimizer_kernels_enabled()
    paddle.set_flags({"FLAGS_pallas_fused_optimizer": False})
    assert not fused.optimizer_kernels_enabled()    # flag wins
    paddle.set_flags({"FLAGS_pallas_fused_optimizer": True})

    rng = np.random.default_rng(3)
    shape = (8, 128)
    w = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    g = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    m1 = jnp.asarray(rng.standard_normal(shape) * 0.1, jnp.float32)
    m2 = jnp.asarray(rng.random(shape) * 0.01, jnp.float32)
    assert fused.adam_update_supported(w)
    assert fused.adam_update_supported(jnp.zeros((3, 5)))
    assert not fused.adam_update_supported(jnp.zeros((640,)))
    assert not fused.adam_update_supported(jnp.zeros(()))

    for wd, decoupled in ((0.0, False), (0.01, False), (0.01, True)):
        b1, b2, eps, lr = 0.9, 0.999, 1e-8, np.float32(0.003)
        bc1, bc2 = np.float32(1 - b1 ** 3), np.float32(1 - b2 ** 3)
        pw, pm1, pm2 = fused.adam_update_pallas(
            w, g, m1, m2, lr, bc1, bc2, b1=b1, b2=b2, eps=eps, wd=wd,
            decoupled=decoupled)
        gf = g.astype(jnp.float32)
        if wd and not decoupled:
            gf = gf + wd * w
        rm1 = b1 * m1 + (1 - b1) * gf
        rm2 = b2 * m2 + (1 - b2) * jnp.square(gf)
        upd = (rm1 / bc1) / (jnp.sqrt(rm2 / bc2) + eps)
        if wd and decoupled:
            upd = upd + wd * w
        rw = w - lr * upd
        for got, ref in ((pw, rw), (pm1, rm1), (pm2, rm2)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=1e-6, atol=1e-8)


@pytest.mark.parametrize("itemsizes", [(4, 4), (4, 2), (4, 1)])
def test_adam_blocks_are_whole_tiles_within_the_budget(itemsizes):
    """The block rule over every leaf it hosts: a block dimension is the
    view's whole dimension or whole tiles of the narrowest operand (its
    sublane rows, 128 lanes), and a block's padded tiles never exceed
    ``_ADAM_BLOCK_ELEMS``."""
    from paddle_tpu.pallas import fused

    sub = 32 // min(itemsizes)
    for lead in ((), (3,)):
        for r in (1, 7, 8, 13, 16, 100, 1000, 5000):
            for c in (1, 100, 128, 129, 2048, 8192, 8193, 16385, 100000):
                view, block = fused.adam_update_blocks(lead + (r, c),
                                                       itemsizes)
                assert len(view) == len(block) == 2 + len(lead)
                assert all(1 <= b <= v for b, v in zip(block, view))
                assert block[-2] in (view[-2], block[-2] // sub * sub)
                assert block[-1] in (view[-1], block[-1] // 128 * 128)
                tiles = (math.prod(block[:-2]) * -(-block[-2] // sub) * sub
                         * -(-block[-1] // 128) * 128)
                assert tiles <= fused._ADAM_BLOCK_ELEMS, (lead, r, c, block)
    assert fused.adam_update_blocks((2048,), itemsizes) is None


_ADAM_OPTS = [
    dict(cls="AdamW", weight_decay=0.01),
    dict(cls="Adam", weight_decay=0.01),     # L2-coupled lane
    dict(cls="Adam", weight_decay=None),
]
_ADAM_LANES = ("pallas.adamw_update.in_place",
               "pallas.adamw_update.xla_lane")


def _adam_trajectory(opt_kw, shape, enabled):
    """Four ``optimizer.step`` calls on one leaf of ``shape``: the final
    parameter and how many leaves each lane counted meanwhile."""
    from paddle_tpu.utils import monitor

    paddle.set_flags({"FLAGS_pallas_fused_optimizer": enabled})
    paddle.seed(0)
    w = paddle.Parameter(
        np.random.default_rng(1).standard_normal(shape).astype("float32"))
    cls = getattr(paddle.optimizer, opt_kw["cls"])
    kw = ({"weight_decay": opt_kw["weight_decay"]}
          if opt_kw["weight_decay"] is not None else {})
    opt = cls(0.01, parameters=[w], **kw)
    before = [monitor.all_stats().get(k, 0) for k in _ADAM_LANES]
    for i in range(4):
        w.grad = paddle.to_tensor(np.full(shape, 0.1 * (i + 1), np.float32))
        opt.step()
        opt.clear_grad()
    counts = [monitor.all_stats().get(k, 0) - b
              for k, b in zip(_ADAM_LANES, before)]
    return w.numpy(), counts


# leaves of one block (pallas.fused.adam_update_blocks): a whole tile,
# rows that are no multiple of a tile, two rank-3 leaves, and a 1-D
# leaf, which the rule gives the jnp lane
@pytest.mark.parametrize("shape", [(8, 128), (13, 96), (3, 16, 40),
                                   (2, 24, 96), (40,)], ids=str)
@pytest.mark.parametrize("opt_kw", _ADAM_OPTS)
def test_pallas_lane_through_optimizer_step_matches_flag_off(
        monkeypatch, opt_kw, shape):
    """optimizer.step routes every leaf of rank 2 and up through the
    in-place kernel when enabled, and a 1-D leaf through the jnp lane;
    the full trajectory must stay BITWISE equal to the flag-off jnp
    lane — the "exact" contract the flag promises — and each leaf
    traced counts once, under the lane the rule gave it."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    on, on_counts = _adam_trajectory(opt_kw, shape, True)
    off, off_counts = _adam_trajectory(opt_kw, shape, False)
    assert on.tobytes() == off.tobytes()
    assert on_counts == ([1, 0] if len(shape) >= 2 else [0, 1])
    assert off_counts == [0, 1]


# under a block budget of 2,048 elements: a partial last row block, a
# split last dimension with a partial lane block, a rank-3 leaf two
# leading indices a block
@pytest.mark.parametrize("shape", [(20, 96), (4, 300), (6, 2, 40)], ids=str)
@pytest.mark.parametrize("opt_kw", _ADAM_OPTS)
def test_pallas_lane_over_many_blocks_within_rounding(monkeypatch, opt_kw,
                                                     shape):
    """A grid of several steps reaches every element once, partial
    blocks included: a block missed or updated twice is off by a step's
    0.01.  Within rounding, not bitwise: the interpreter runs such a
    grid as a loop whose body the CPU contracts into fmas otherwise
    than the jnp lane's fusion, an ulp of the operands a step (the
    parent's kernel differed so at (2048, 128) too); one block is
    bitwise, above."""
    from paddle_tpu.pallas import fused

    monkeypatch.setattr(fused, "_ADAM_BLOCK_ELEMS", 2048)
    view, block = fused.adam_update_blocks(shape)
    assert math.prod(-(-v // b) for v, b in zip(view, block)) > 1
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    on, on_counts = _adam_trajectory(opt_kw, shape, True)
    off, _ = _adam_trajectory(opt_kw, shape, False)
    np.testing.assert_allclose(on, off, rtol=0, atol=1e-6)
    assert on_counts == [1, 0]


# ------------------------------------------------- hybrid dp x mp lane
#
# ISSUE 12: a ProcessMesh with an mp axis compiles the step as ONE
# GSPMD program over NamedSharding trees derived from the TP layers'
# declared partitions.  Equality contract vs the single-device step:
# ulp-level, NOT bitwise — the row-parallel product is a partial-sum
# all-reduce whose fp32 accumulation order differs from one fused
# matmul's (docs/TRAIN_STEP.md "Hybrid parallel").


@pytest.fixture
def _mesh_guard():
    from paddle_tpu.distributed import mesh as mesh_mod
    yield mesh_mod
    mesh_mod.set_mesh(None)


def _mp_net(clip=None, lr=0.01):
    from paddle_tpu.distributed.fleet.mp_layers import (
        ColumnParallelLinear, RowParallelLinear)
    paddle.seed(0)
    net = nn.Sequential(
        ColumnParallelLinear(8, 16, gather_output=False),
        nn.ReLU(),
        RowParallelLinear(16, 4, input_is_parallel=True))
    opt = paddle.optimizer.AdamW(lr, parameters=net.parameters(),
                                 weight_decay=0.01, grad_clip=clip)
    return net, opt


def _run_mp(mesh, compiled, clip=None, steps=8, accum=1, hook=None,
            batches=None):
    """Train the TP MLP on ``mesh`` (None = single device) through a
    standalone CompiledTrainStep; returns (losses, weights, step)."""
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.fleet.base import _commit_params
    from paddle_tpu.framework.train_step import CompiledTrainStep

    mesh_mod.set_mesh(mesh)
    paddle.set_flags({"FLAGS_compiled_train_step": compiled})
    net, opt = _mp_net(clip=clip)
    if mesh is not None:
        _commit_params(net, mesh)
    if hook:
        hook(net)

    def forward(x, y):
        return ((net(x) - y) ** 2).mean()

    cs = CompiledTrainStep(forward, opt, network=net,
                           accumulate_grad_batches=accum)
    losses = []
    for i, (x, y) in enumerate(batches or _batches(steps=steps)):
        update = (i + 1) % accum == 0
        loss = cs(paddle.to_tensor(x), paddle.to_tensor(y),
                  update=update)
        losses.append(float(np.asarray(loss._data_)))
    weights = [np.asarray(p._data_).copy() for p in net.parameters()]
    grads = [None if p.grad is None else np.asarray(p.grad._data_).copy()
             for p in net.parameters()]
    mesh_mod.set_mesh(None)
    return losses, weights, grads, cs


def test_mp_mesh_matches_single_device(_mesh_guard):
    """mp=2: the GSPMD one-program step trains the TP-sharded MLP to
    the single-device trajectory at ulp tolerance, with the compiled
    lane genuinely on and the mesh recognized as hybrid."""
    le, we, _, _ = _run_mp(None, False)
    mesh = _mesh_guard.init_mesh([1, 2], ["dp", "mp"])
    lc, wc, _, cs = _run_mp(mesh, True)
    assert cs.compiled, cs.fallback_reason
    assert cs._gspmd and cs._mp == 2 and not cs._shard_map
    _assert_ulp_close(le, lc, rel=5e-6)
    for a, b in zip(we, wc):
        np.testing.assert_allclose(a, b, rtol=5e-6, atol=5e-7)


def test_mp_partial_sum_grads_match_single_device(_mesh_guard):
    """Backward-only micro-steps (accum=2): the mp partial-sum grad
    reduction (row-parallel all-reduce inserted by GSPMD) matches the
    single-device gradients tightly, through the compiled micro
    program."""
    batches = _batches(steps=3)
    _, _, ge, _ = _run_mp(None, False, accum=4, batches=batches)
    mesh = _mesh_guard.init_mesh([1, 2], ["dp", "mp"])
    _, _, gc, cs = _run_mp(mesh, True, accum=4, batches=batches)
    assert cs.compiled and cs._jit_micro is not None
    assert ge and all(g is not None for g in ge)
    for a, b in zip(ge, gc):
        np.testing.assert_allclose(a, b, rtol=5e-6, atol=5e-7)


def test_mp_clip_active_matches_single_device(_mesh_guard):
    """ACTIVE global-norm clip over mp-sharded grads: the norm crosses
    the mp axis inside the program; trajectories stay ulp-close."""
    clip = nn.ClipGradByGlobalNorm(0.05)
    le, we, _, _ = _run_mp(None, False, clip=clip)
    mesh = _mesh_guard.init_mesh([1, 2], ["dp", "mp"])
    lc, wc, _, cs = _run_mp(mesh, True, clip=clip)
    assert cs.compiled, cs.fallback_reason
    assert len(set(np.float32(lc))) > 3
    _assert_ulp_close(le, lc, rel=5e-6)
    for a, b in zip(we, wc):
        np.testing.assert_allclose(a, b, rtol=5e-6, atol=5e-7)


def test_dp_mp_2x2_mesh_and_ragged_fallback(_mesh_guard):
    """dp=2 × mp=2: batch shards over dp, params over mp, one program;
    a ragged tail batch runs a one-off eager step (mesh scope lifted —
    the model's own dp constraint cannot shard batch 3) and the
    compiled lane resumes un-latched."""
    from paddle_tpu.utils import monitor

    le, we, _, _ = _run_mp(None, False)
    mesh = _mesh_guard.init_mesh([2, 2], ["dp", "mp"])
    lc, wc, _, cs = _run_mp(mesh, True)
    assert cs.compiled and cs._dp == 2 and cs._mp == 2
    _assert_ulp_close(le, lc, rel=5e-6)
    for a, b in zip(we, wc):
        np.testing.assert_allclose(a, b, rtol=5e-6, atol=5e-7)

    _mesh_guard.set_mesh(mesh)
    ragged = monitor.all_stats().get("jit.compiled_step_ragged_fallback",
                                     0)
    loss = cs(paddle.to_tensor(np.zeros((3, 8), np.float32)),
              paddle.to_tensor(np.zeros((3, 4), np.float32)))
    assert np.isfinite(float(np.asarray(loss._data_)))
    assert monitor.all_stats().get(
        "jit.compiled_step_ragged_fallback", 0) == ragged + 1
    assert cs.fallback_reason is None
    x4, y4 = _batches(steps=1)[0]
    cs(paddle.to_tensor(x4), paddle.to_tensor(y4))
    assert cs.compiled


def test_mp_hook_fallback_byte_identical(_mesh_guard):
    """Layer hooks on an mp-sharded model: the latch falls back to the
    byte-identical eager mp lane (same GSPMD eager ops), exactly like
    the dp-only latch."""
    seen = []

    def install(net):
        net[0].register_forward_post_hook(
            lambda layer, inp, out: seen.append(1) or out)

    mesh = _mesh_guard.init_mesh([1, 2], ["dp", "mp"])
    le, we, _, _ = _run_mp(mesh, False, hook=install)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        lc, wc, _, cs = _run_mp(mesh, True, hook=install)
    assert "hook" in (cs.fallback_reason or "")
    assert seen
    assert [np.float32(a) for a in le] == [np.float32(b) for b in lc]
    for a, b in zip(we, wc):
        assert a.tobytes() == b.tobytes()


def test_unsupported_mesh_axis_warns_typed_once(_mesh_guard):
    """A pp>1 mesh axis forces eager with ONE MeshFallbackWarning
    naming the axis; training continues byte-identically to eager."""
    from paddle_tpu.framework.train_step import MeshFallbackWarning

    mesh = _mesh_guard.init_mesh([1, 2], ["dp", "pp"])
    le, we, _, _ = _run_mp(mesh, False)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        lc, wc, _, cs = _run_mp(mesh, True)
    typed = [r for r in rec
             if issubclass(r.category, MeshFallbackWarning)]
    assert len(typed) == 1, [str(r.message) for r in rec]
    assert "'pp'" in str(typed[0].message)
    assert "'pp'" in (cs.fallback_reason or "")
    assert [np.float32(a) for a in le] == [np.float32(b) for b in lc]
    for a, b in zip(we, wc):
        assert a.tobytes() == b.tobytes()


def test_mp_donation_alias_tied_buffers_skips_compiled_call(_mesh_guard):
    """Two mp-sharded parameters backed by ONE device buffer: the alias
    check must detect it per call and run eager — donating one buffer
    for two outputs is as unsound on a mesh as off it."""
    from paddle_tpu.distributed.placement import Replicate, Shard, \
        commit_param
    from paddle_tpu.framework.train_step import CompiledTrainStep
    from paddle_tpu.utils import monitor

    mesh = _mesh_guard.init_mesh([1, 2], ["dp", "mp"])
    _mesh_guard.set_mesh(mesh)
    paddle.set_flags({"FLAGS_compiled_train_step": True})
    paddle.seed(0)
    w1 = paddle.Parameter(np.ones((4, 8), np.float32))
    w2 = paddle.Parameter(np.ones((4, 8), np.float32))
    commit_param(w1, mesh, [Replicate(), Shard(1)])
    w2._data_ = w1._data_
    w2.placements = list(w1.placements)
    w2.process_mesh = mesh
    opt = paddle.optimizer.AdamW(0.05, parameters=[w1, w2])

    def forward(x, y):
        return (((x @ w1) + (x @ w2) - y) ** 2).mean()

    cs = CompiledTrainStep(forward, opt)
    before = monitor.all_stats().get("jit.compiled_step_alias_fallback",
                                     0)
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    y = paddle.to_tensor(np.zeros((2, 8), np.float32))
    for _ in range(3):
        loss = cs(x, y)
        assert np.isfinite(float(np.asarray(loss._data_)))
        w2._data_ = w1._data_            # re-tie: keep the alias live
    assert monitor.all_stats().get(
        "jit.compiled_step_alias_fallback", 0) > before


# ------------------------------------------------- auto-layout planner


_PLANNER_DESC = dict(n_params=2e9, n_layers=2, hidden=16,
                     global_batch=4, seq_len=32)


def test_planner_deterministic_and_budget_schema_gate(tmp_path,
                                                     monkeypatch):
    """Same inputs -> byte-identical plan (the elastic re-plan must
    agree across processes); a COMM_BUDGET file with a stale
    schema_version fails loudly instead of skewing plans."""
    from paddle_tpu.cost_model import (BudgetSchemaError, plan_layout,
                                       load_comm_budgets)

    p1 = plan_layout(_PLANNER_DESC, 8)
    p2 = plan_layout(_PLANNER_DESC, 8)
    assert p1.to_json() == p2.to_json()
    assert p1.dp * p1.mp * p1.pp == 8
    assert p1.mp > 1            # parameter-heavy desc: mp must win
    spec = p1.mesh_spec()
    assert spec.world == 8

    # the recorded budgets load and validate
    budgets = load_comm_budgets()
    assert {"gpt-dp", "llama-tp", "moe"} <= set(budgets)
    p3 = plan_layout(dict(_PLANNER_DESC, comm_budget="llama-tp"), 8)
    assert p3.source.startswith("roofline+budget:")

    # stale schema_version -> loud BudgetSchemaError naming the file
    bad = tmp_path / "COMM_BUDGET_stale.json"
    bad.write_text(json.dumps({"schema_version": 0, "collectives": [],
                               "mesh": {}}))
    monkeypatch.setenv("PADDLE_COMM_BUDGET_DIR", str(tmp_path))
    with pytest.raises(BudgetSchemaError) as ei:
        load_comm_budgets()
    assert "COMM_BUDGET_stale.json" in str(ei.value)
    # ...and a budget-less file (pre-versioning) is just as loud
    bad.write_text(json.dumps({"collectives": [], "mesh": {}}))
    with pytest.raises(BudgetSchemaError):
        load_comm_budgets()


def test_resume_target_mesh_derives_from_active_plan(_mesh_guard,
                                                     monkeypatch):
    """fit(resume=...)'s reshard target: PADDLE_RESHARD_MESH wins, then
    the ACTIVE hybrid mesh's factorization (the planner's plan needs no
    env override), then pure-dp."""
    from paddle_tpu.distributed.reshard import MeshSpec

    net = nn.Sequential(nn.Linear(4, 4))
    m = Model(net)
    assert m._resume_target_mesh() == MeshSpec(("dp",), (1,))
    mesh = _mesh_guard.init_mesh([1, 2], ["dp", "mp"])
    _mesh_guard.set_mesh(mesh)
    assert m._resume_target_mesh() == MeshSpec(("mp",), (2,))
    monkeypatch.setenv("PADDLE_RESHARD_MESH",
                       json.dumps({"axes": ["dp"], "shape": [4]}))
    assert m._resume_target_mesh() == MeshSpec(("dp",), (4,))


def test_plan_topology_resize_4_to_2_replans_and_roundtrips(
        _mesh_guard, tmp_path):
    """The elastic 4->2 resize drill on planner meshes: train on the
    world-4 plan (mp=4), checkpoint SHARDED per the plan's layout,
    re-plan for world 2 (mp=2), reshard-restore, continue — the resumed
    trajectory matches the uninterrupted run within 5e-4, with a real
    reshard (no fast path) in between."""
    from concurrent.futures import ThreadPoolExecutor
    from paddle_tpu.distributed.fleet.base import _commit_params
    from paddle_tpu.distributed.fleet.elastic import plan_topology
    from paddle_tpu.distributed.reshard import (
        MeshSpec, partition_from_tensor, restore_latest_resharded,
        save_sharded)
    from paddle_tpu.framework.train_step import CompiledTrainStep

    batches = _batches(steps=6)
    ref_losses, ref_w, _, _ = _run_mp(None, False, batches=batches)

    plan4 = plan_topology(4, _PLANNER_DESC)
    plan2 = plan_topology(2, _PLANNER_DESC)
    assert plan4["mp"] > 1 and plan2["mp"] > 1    # genuinely re-planned
    assert plan4["dp"] * plan4["mp"] == 4
    assert plan2["dp"] * plan2["mp"] == 2

    def mesh_for(plan):
        return _mesh_guard.init_mesh([plan["dp"], plan["mp"]],
                                     ["dp", "mp"])

    def spec_for(plan):
        return MeshSpec(("dp", "mp"), (plan["dp"], plan["mp"]))

    # ---- first incarnation: world-4 plan, 3 steps, sharded save ----
    mesh4 = mesh_for(plan4)
    _mesh_guard.set_mesh(mesh4)
    paddle.set_flags({"FLAGS_compiled_train_step": True})
    net, opt = _mp_net()
    _commit_params(net, mesh4)
    cs = CompiledTrainStep(lambda x, y: ((net(x) - y) ** 2).mean(), opt,
                           network=net)
    losses = []
    for x, y in batches[:3]:
        losses.append(float(np.asarray(
            cs(paddle.to_tensor(x), paddle.to_tensor(y))._data_)))
    assert cs.compiled and cs._mp == plan4["mp"], cs.fallback_reason

    spec4 = spec_for(plan4)
    state = {"model": net.state_dict(), "optimizer": opt.state_dict()}
    tensors = {f"model.{k}": v for k, v in state["model"].items()}

    def partition_fn(key, arr):
        t = tensors.get(key)
        if t is None:
            return (None,) * arr.ndim
        return partition_from_tensor(t, spec4)

    assert any(a is not None
               for k in tensors
               for a in partition_fn(k, np.asarray(tensors[k]._data_)))
    ckdir = tmp_path / "ckpt-00000001"
    with ThreadPoolExecutor(max_workers=4) as ex:
        futs = [ex.submit(save_sharded, str(ckdir), state, spec4, r,
                          partition_fn=partition_fn, step=1)
                for r in range(spec4.world)]
        for f in futs:
            f.result(timeout=120)
    _mesh_guard.set_mesh(None)

    # ---- resized incarnation: world-2 plan, reshard-restore ----
    mesh2 = mesh_for(plan2)
    _mesh_guard.set_mesh(mesh2)
    restored = restore_latest_resharded(str(tmp_path), spec_for(plan2),
                                        0)
    assert restored is not None
    state2, _step, report = restored
    assert not report["fast_path"] and report["arrays_resharded"] > 0
    net2, opt2 = _mp_net()
    _commit_params(net2, mesh2)
    net2.set_state_dict(state2["model"])
    opt2.set_state_dict(state2["optimizer"])
    cs2 = CompiledTrainStep(lambda x, y: ((net2(x) - y) ** 2).mean(),
                            opt2, network=net2)
    for x, y in batches[3:]:
        losses.append(float(np.asarray(
            cs2(paddle.to_tensor(x), paddle.to_tensor(y))._data_)))
    assert cs2.compiled and cs2._mp == plan2["mp"], cs2.fallback_reason
    _mesh_guard.set_mesh(None)

    for a, b in zip(ref_losses, losses):
        assert abs(a - b) <= 5e-4 * max(abs(a), 1.0), (a, b)
    final_w = [np.asarray(p._data_) for p in net2.parameters()]
    for a, b in zip(ref_w, final_w):
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-5)


# ------------------------------------------------------- observability


def test_hlo_fingerprint_stable_and_rng_neutral():
    from paddle_tpu.core import state as _state

    lc, _, m = _run(True, batches=_batches(steps=3))
    cs = m._compiled_step
    x, y = _batches(steps=1)[0]
    before = _state.STATE.rng_counter
    fp1 = cs.hlo_fingerprint(paddle.to_tensor(x), paddle.to_tensor(y))
    fp2 = cs.hlo_fingerprint(paddle.to_tensor(x), paddle.to_tensor(y))
    assert _state.STATE.rng_counter == before
    assert fp1 == fp2
    assert isinstance(fp1, str) and len(fp1) == 16
    int(fp1, 16)
