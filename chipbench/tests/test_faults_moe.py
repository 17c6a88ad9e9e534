"""Planted faults of the sparse-expert cell, at the tiny presets on CPUs:
what page tables by layer kind, per-layer rotation and a routed expert
layer can get wrong.  Each has to bring ``served_logit_gap`` or
``routed_gap`` over the cell's limit; the faults of the routed part have
to bring ``routed_gap`` over its own.  One thing is said, not judged: a router computed in
bfloat16 chooses other experts at some positions; how many, and the gap
it costs, are printed.  ``fault_moe.py`` plants the same faults on the
chip at the timed sizes."""
import pytest

from test_faults import tiny_run    # puts the harness on the path
import drive_serve_moe              # noqa: E402

CELL = "command-a-plus-ep8-d4.doc-reason-32"


class _Patched(drive_serve_moe.MoeProgram):
    """A program built, run and compared with attributes of the
    program's modules swapped; ``patches()`` yields (owner, name,
    replacement).  They are lifted after the last comparison."""

    def __init__(self, run):
        self._undo = []
        for owner, name, new in self.patches():
            self._undo.append((owner, name, getattr(owner, name)))
            setattr(owner, name, new)
        try:
            super().__init__(run)
        except BaseException:
            self._restore()
            raise

    def _restore(self):
        for owner, name, old in reversed(self._undo):
            setattr(owner, name, old)
        self._undo = []

    def finish(self):
        self._restore()
        super().finish()


def _after_init(cls, fix):
    """``cls.__init__`` followed by ``fix(self)``."""
    init = cls.__init__

    def patched(self, *a, **k):
        init(self, *a, **k)
        fix(self)
    return cls, "__init__", patched


class WindowIgnored(_Patched):
    """Window layers see every position: no window in the mask, and the
    cache manager is told of none, so nothing is given up either."""

    def patches(self):
        from paddle_tpu.models import cohere_moe as m
        yield (m.CohereMoeConfig, "layer_windows",
               lambda self: [None] * self.num_layers)
        yield _after_init(m.CohereMoeAttention,
                          lambda att: setattr(att, "window", None))


class RotationInFullLayers(_Patched):
    """Full attention layers rotate q and k too."""

    def patches(self):
        from paddle_tpu.models import cohere_moe as m
        yield _after_init(m.CohereMoeAttention,
                          lambda att: setattr(att, "rotary", True))


class RotaryHalves(_Patched):
    """Rotation over the halves (x_m, x_{m + D/2}), as Llama's, for the
    interleaved pairs."""

    def patches(self):
        import jax.numpy as jnp
        from paddle_tpu.models import cohere_moe as m

        def rope_halves(x, pos, theta):
            d = x.shape[-1]
            inv = 1.0 / (theta ** (jnp.arange(0, d, 2,
                                              dtype=jnp.float32) / d))
            ang = pos.astype(jnp.float32)[..., None] * inv
            if ang.ndim == 2:
                ang = ang[None]
            cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None, :]
            sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None, :]
            xf = x.astype(jnp.float32)
            rot = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], -1)
            return (xf * cos + rot * sin).astype(x.dtype)

        yield m, "rope_interleaved", rope_halves


def _route(score, normalise=True):
    def route(router_logits, k):
        import jax
        import jax.numpy as jnp
        top, idx = jax.lax.top_k(score(router_logits.astype(jnp.float32)), k)
        if normalise:
            top = top / jnp.sum(top, -1, keepdims=True)
        return idx.astype(jnp.int32), top
    return route


class ScoresNotNormalised(_Patched):
    """The chosen sigmoid scores weigh the experts as they are."""

    def patches(self):
        import jax
        from paddle_tpu.pallas import moe
        yield moe, "route_sigmoid_topk", _route(jax.nn.sigmoid, False)


class SoftmaxForSigmoid(_Patched):
    """Softmax scores over the experts in place of sigmoid scores."""

    def patches(self):
        import jax
        from paddle_tpu.pallas import moe
        yield (moe, "route_sigmoid_topk",
               _route(lambda r: jax.nn.softmax(r, axis=-1)))


class SharedExpertsSummed(_Patched):
    """The shared experts' outputs summed, not averaged."""

    def patches(self):
        from paddle_tpu.models import cohere_moe as m
        yield _after_init(m.CohereSparseMLP,
                          lambda mlp: setattr(mlp, "shared_scale", 1.0))


class OneHeldExpertSkipped(_Patched):
    """The first held expert's pairs are never computed."""

    def patches(self):
        import jax.numpy as jnp
        from paddle_tpu.pallas import moe
        routed = moe.routed_experts

        def skipping(x, experts, gates, wg, wu, wd, held, *rest, **kw):
            gates = jnp.where(experts == held[0], 0.0, gates)
            return routed(x, experts, gates, wg, wu, wd, held, *rest, **kw)

        yield moe, "routed_experts", skipping


class RouterInBfloat16(_Patched):
    """Router scores from a bfloat16 product: said, not judged."""

    def patches(self):
        import jax.numpy as jnp
        from paddle_tpu.pallas import moe
        yield (moe, "router_logits", lambda x, w: jnp.matmul(
            x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32))


FAULTS = {f.__name__: f for f in (
    WindowIgnored, RotationInFullLayers, RotaryHalves, ScoresNotNormalised,
    SoftmaxForSigmoid, SharedExpertsSummed, OneHeldExpertSkipped)}
#: the faults of the routed part: ``routed_gap`` alone has to refuse them
ROUTED = ("ScoresNotNormalised", "SoftmaxForSigmoid", "OneHeldExpertSkipped")


def test_sound_moe_run_is_correct():
    r = tiny_run(CELL, seconds=3)
    drive_serve_moe.measure(r)
    assert r.correct
    assert set(r.compared) == {"served_logit_gap", "routed_gap"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault):
    r = tiny_run(CELL, seconds=3)
    drive_serve_moe.measure(r, prog_factory=FAULTS[fault])
    assert not r.correct, r.compared
    if fault in ROUTED:
        value, limit = r.compared["routed_gap"]
        assert value > limit, r.compared


def test_moe_controls_are_not_correct():
    """The token the float8 reference puts first lies further below the
    reference's best than the limit allows, and the routed part with
    float8 products in the experts alone lies further from the float32
    one than ITS limit allows."""
    r = tiny_run(CELL, seconds=3)
    r.control = r.control_routed = "fp8"
    drive_serve_moe.measure(r)
    limits = r.cell["limits"]
    assert r.records["control_gap"] > limits["served_logit_gap"]
    assert r.records["control_routed_gap"] > limits["routed_gap"]


def test_router_in_bfloat16_is_said():
    """How many positions choose another set of experts when the router's
    product is in bfloat16, over seeded weights and unit-variance inputs,
    and what the served tokens then read: printed, and not compared with
    the limit (a routing that flips on a tie is a different, equally
    valid, reading of the same scores)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.pallas import moe
    x = jax.random.normal(jax.random.PRNGKey(1), (4096, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (64, 16), jnp.float32) * 0.1
    exact, _ = moe.route_sigmoid_topk(moe.router_logits(x, w), 4)
    low, _ = moe.route_sigmoid_topk(jnp.matmul(
        x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32), 4)
    differ = int((np.sort(np.asarray(exact), -1)
                  != np.sort(np.asarray(low), -1)).any(-1).sum())
    r = tiny_run(CELL, seconds=3)
    drive_serve_moe.measure(r, prog_factory=RouterInBfloat16)
    gap = r.compared["served_logit_gap"][0]
    print(f"router in bfloat16: {differ} of 4096 positions choose another "
          f"set of experts; served_logit_gap {gap:.3g} "
          f"(limit {r.cell['limits']['served_logit_gap']})")
    assert 0 < differ < 4096 and np.isfinite(gap)
