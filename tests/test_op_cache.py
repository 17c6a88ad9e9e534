"""Tiered executable cache tests (core/op_cache.py).

Covers the ISSUE-1 acceptance surface: tier-1 hit/miss counters, the LRU
eviction bound, fallback-path parity (saved-tensor hooks, unhashable
statics, per-call closure impls, flag off), gradient correctness through
the cached jitted vjp, RNG-drawing op opt-out, and the tier-2 persistent
compilation cache round trip."""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import op_cache
from paddle_tpu.utils import cache_stats


@pytest.fixture(autouse=True)
def _fresh_cache():
    op_cache.clear()
    paddle.set_flags({"FLAGS_eager_op_cache": True,
                      "FLAGS_eager_op_cache_size": 4096})
    yield
    op_cache.clear()
    paddle.set_flags({"FLAGS_eager_op_cache": True,
                      "FLAGS_eager_op_cache_size": 4096})


def _t1():
    return cache_stats()["tier1"]


def test_hit_miss_counters():
    x = paddle.to_tensor(np.ones((4, 5), np.float32))
    paddle.nn.functional.relu(x)
    st = _t1()
    assert st["misses"] == 1 and st["hits"] == 0 and st["entries"] == 1
    paddle.nn.functional.relu(x)
    paddle.nn.functional.relu(x)
    st = _t1()
    assert st["misses"] == 1 and st["hits"] == 2
    # a different signature is a separate entry
    y = paddle.to_tensor(np.ones((2, 3), np.float32))
    paddle.nn.functional.relu(y)
    st = _t1()
    assert st["misses"] == 2 and st["entries"] == 2
    assert st["bytes"] > 0


def test_grad_flag_and_static_kwargs_separate_entries():
    x = paddle.to_tensor(np.ones((4, 4), np.float32))
    xg = paddle.to_tensor(np.ones((4, 4), np.float32), stop_gradient=False)
    paddle.nn.functional.softmax(x, axis=0)
    paddle.nn.functional.softmax(x, axis=1)   # static kwarg in the key
    paddle.nn.functional.softmax(xg, axis=0)  # grad flag in the key
    st = _t1()
    assert st["misses"] == 3 and st["entries"] == 3


def test_lru_eviction_bound():
    paddle.set_flags({"FLAGS_eager_op_cache_size": 4})
    for n in range(2, 9):   # 7 distinct signatures
        paddle.nn.functional.relu(
            paddle.to_tensor(np.ones((n,), np.float32)))
    st = _t1()
    assert st["entries"] <= 4
    assert st["evictions"] >= 3
    assert st["misses"] == 7


def test_flag_off_bypasses_and_matches():
    x = paddle.to_tensor(np.random.default_rng(0)
                         .standard_normal((8, 8)).astype(np.float32))
    on = paddle.nn.functional.gelu(x).numpy()
    paddle.set_flags({"FLAGS_eager_op_cache": False})
    off = paddle.nn.functional.gelu(x).numpy()
    st = _t1()
    np.testing.assert_allclose(on, off, rtol=1e-6, atol=1e-6)
    assert st["misses"] == 1 and st["hits"] == 0  # only the flag-on call


def test_grad_correctness_through_cached_vjp():
    rng = np.random.default_rng(1)
    xv = rng.standard_normal((6, 4)).astype(np.float32)
    wv = rng.standard_normal((4, 3)).astype(np.float32)

    def run():
        x = paddle.to_tensor(xv, stop_gradient=False)
        w = paddle.to_tensor(wv, stop_gradient=False)
        y = paddle.nn.functional.relu(paddle.matmul(x, w))
        loss = (y * y).sum()
        loss.backward()
        return float(loss), x.grad.numpy(), w.grad.numpy()

    l1, gx1, gw1 = run()           # populates the cache (misses)
    l2, gx2, gw2 = run()           # replays cached jitted vjp forwards
    st = _t1()
    assert st["hits"] > 0, "second pass should hit the cached executables"
    paddle.set_flags({"FLAGS_eager_op_cache": False})
    l3, gx3, gw3 = run()           # today's uncached path
    assert l1 == l2
    np.testing.assert_allclose(gx2, gx1, rtol=0, atol=0)
    np.testing.assert_allclose(l2, l3, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(gx2, gx3, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gw2, gw3, rtol=1e-5, atol=1e-6)


def test_saved_tensor_hooks_fall_back():
    from paddle_tpu.autograd import saved_tensors_hooks
    packed = []

    def pack(t):
        packed.append(t)
        return t

    def unpack(t):
        return t

    x = paddle.to_tensor(np.ones((3, 3), np.float32), stop_gradient=False)
    with saved_tensors_hooks(pack, unpack):
        y = paddle.matmul(x, x)
        loss = y.sum()
    loss.backward()
    # the hooked ops must NOT be cached (their vjp is deferred to
    # backward re-linearization from the packed values)
    assert packed, "pack hook never fired"
    assert x.grad is not None
    assert all(k[0] != "matmul" for k in list(op_cache._T1)), \
        "op executed under saved_tensors_hooks leaked into the cache"


def test_per_call_closure_impls_bypass():
    # dropout's impl is a per-call closure (closes over the drawn RNG
    # key; not the registry fn): it must bypass the cache, and two calls
    # must keep drawing fresh masks
    x = paddle.to_tensor(np.ones((64, 64), np.float32))
    a = paddle.nn.functional.dropout(x, p=0.5, training=True).numpy()
    b = paddle.nn.functional.dropout(x, p=0.5, training=True).numpy()
    assert all(k[0] != "dropout" for k in list(op_cache._T1)), \
        "per-call closure impl must not be cached"
    assert not np.allclose(a, b), "dropout masks must differ per call"


def test_unhashable_static_bypasses():
    # name=<ndarray> rides through the registered relu's **kwargs: the
    # key cannot hash it, so the call must take the uncached path
    x = paddle.to_tensor(np.ones((3,), np.float32) * -1)
    out = paddle.nn.functional.relu(x, name=np.ones(3, np.float32))
    np.testing.assert_allclose(out.numpy(), np.zeros(3))
    st = _t1()
    assert st["bypasses"] >= 1
    assert st["misses"] == 0 and st["entries"] == 0


def test_rng_drawing_op_opts_out():
    from paddle_tpu.core.dispatch import defop
    import jax

    @defop("_test_rng_draw_op")
    def _test_rng_draw_op(x):
        from paddle_tpu.core import state as _state
        key = _state.next_rng_key()
        return x + jax.random.uniform(key, x.shape)

    x = paddle.to_tensor(np.zeros((16,), np.float32))
    a = _test_rng_draw_op(x).numpy()
    b = _test_rng_draw_op(x).numpy()
    st = _t1()
    assert "_test_rng_draw_op" in st["skipped_ops"]
    assert st["entries"] == 0
    assert not np.allclose(a, b), "RNG op must draw fresh keys per call"


def test_int_vs_float_static_do_not_collide():
    x = paddle.to_tensor(np.full((4,), -2.0, np.float32))
    a = paddle.pow(x, 2).numpy()     # int exponent
    b = paddle.pow(x, 2.0).numpy()   # float exponent: distinct key
    np.testing.assert_allclose(a, b, rtol=1e-6)
    st = _t1()
    assert st["misses"] == 2, "2 and 2.0 must not share a cache key"


def test_eager_train_loss_parity_cache_on_off():
    """The bench-style parity gate: identical losses with the cache on
    and off over a multi-step eager training loop."""

    def train(steps=4):
        paddle.seed(7)
        rng = np.random.default_rng(3)
        x = paddle.to_tensor(rng.standard_normal((8, 16))
                             .astype(np.float32))
        y = paddle.to_tensor(rng.standard_normal((8, 4))
                             .astype(np.float32))
        lin = paddle.nn.Linear(16, 4)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=lin.parameters())
        losses = []
        for _ in range(steps):
            loss = ((lin(x) - y) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        return losses

    on = train()
    paddle.set_flags({"FLAGS_eager_op_cache": False})
    off = train()
    np.testing.assert_allclose(on, off, rtol=1e-5, atol=1e-7)


def test_tier2_persistent_compile_cache_round_trip():
    import jax
    import jax.numpy as jnp

    # conftest armed the cache through the framework's resolver
    d = op_cache.ensure_compile_cache()
    assert d == jax.config.jax_compilation_cache_dir
    assert os.path.isabs(d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        const = float(np.random.default_rng().integers(1 << 30))
        f = jax.jit(lambda a: (a * 3 + const).sum())   # a program no
        f(jnp.ones((32, 32)))                          # earlier run cached
        st = cache_stats()["tier2"]
        assert st["enabled"] and st["dir"] == d
        assert st["entries"] > 0 and st["bytes"] > 0
        # drop the in-memory executable: the recompile must be served
        # from the persistent cache (the cross-process re-run analog)
        jax.clear_caches()
        before = cache_stats()["tier2"]["hits"]
        f2 = jax.jit(lambda a: (a * 3 + const).sum())
        f2(jnp.ones((32, 32)))
        assert cache_stats()["tier2"]["hits"] > before
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.5)


_PLACEMENT_PROBE = """
import os, sys
import jax, jax.numpy as jnp
import paddle_tpu as paddle
from paddle_tpu.core import op_cache
d = op_cache.ensure_compile_cache()
(paddle.to_tensor([1.0, 2.0]) * 3).numpy()      # an eager-op program
def placement_probe(a):
    return (a * 5 + 2).sum()
jax.jit(placement_probe)(jnp.ones((8, 8)))
print(d)
print(len([f for f in os.listdir(d) if not f.endswith("-atime")]))
"""


def test_tier2_cache_placed_by_environment_only(tmp_path):
    """JAX_COMPILATION_CACHE_DIR places the cache and the files appear
    there and nowhere else; unset, the directory is the absolute
    <checkout>/.jax_cache next to the package."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    default = os.path.join(root, ".jax_cache")
    assert op_cache._DEFAULT_CACHE_DIR == default

    placed = str(tmp_path / "placed")

    def probes(d):
        # the probe's own program: other test workers write theirs into
        # the default directory all the while
        return {f for f in (os.listdir(d) if os.path.isdir(d) else ())
                if f.startswith("jit_placement_probe-")}

    before = probes(default)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root,
               JAX_COMPILATION_CACHE_DIR=placed)
    out = subprocess.run([sys.executable, "-c", _PLACEMENT_PROBE], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    got_dir, n_files = out.stdout.strip().splitlines()[-2:]
    assert got_dir == placed and int(n_files) > 0
    assert probes(placed)
    assert probes(default) == before    # nothing leaked into the default dir
