"""Planted faults of the latent-attention cell, at the tiny presets on
CPUs: what the absorbed decode, the up-projected prefill read, yarn's
scale and a router with a selection bias and a scaling factor can get
wrong.  Each has to turn ``correct`` false; the faults of attention have
to bring ``latent_gap`` over its own limit, those of the routed part
``routed_gap`` over its own, and every fault that touches most positions
``served_latent_gap`` — the number read from the engine's own pages —
over its own.  ``fault_mla.py`` plants the same faults on the chip at the
timed sizes."""
import pytest

from test_faults import tiny_run    # puts the harness on the path
import test_faults_moe
import drive_serve_mla              # noqa: E402

CELL = "sarvam-105b-ep8-d5.longdoc-reason-56"


class _Patched(test_faults_moe._Patched, drive_serve_mla.MlaProgram):
    """``test_faults_moe._Patched`` over the latent cell's program."""


class RopeScoreDropped(_Patched):
    """The rope part of the score dropped: neither the query's rope dims
    nor the cached rotary key carry anything."""

    def patches(self):
        import jax.numpy as jnp
        from paddle_tpu.models import sarvam_mla as m
        yield (m, "rope_pairs",
               lambda x, pos, inv_freq, mult=1.0: jnp.zeros_like(x))


class ValuesFromTheWholeRow(_Patched):
    """V taken as the whole 576-wide row in the absorbed decode: the
    weighted sum of the rotary key's lanes leaks into the latent's."""

    def patches(self):
        from paddle_tpu.pallas import mla
        decode = mla.mla_decode

        def leaking(q, pool, page_table, offsets, v_width, scale,
                    lane=None):
            whole = decode(q, pool, page_table, offsets, pool.shape[-1],
                           scale, lane)
            extra = whole[..., v_width:2 * v_width]
            return whole[..., :v_width].at[..., :extra.shape[-1]].add(extra)

        yield mla, "mla_decode", leaking


class ScaleWithoutYarn(_Patched):
    """The softmax scale without yarn's factor: (nope + rope)^-1/2."""

    def patches(self):
        from paddle_tpu.models import sarvam_mla as m
        yield (m, "yarn_softmax_scale",
               lambda q_head_dim, scaling: q_head_dim ** -0.5)


def _route(**forced):
    def route(self, tokens, wr, bias):
        from paddle_tpu.pallas import moe
        cfg = self.config
        kw = dict(bias=bias, scale=cfg.routed_scaling_factor)
        kw.update(forced)
        return moe.route_sigmoid_topk(moe.router_logits(tokens, wr),
                                      cfg.num_experts_per_tok, **kw)
    return route


class BiasLeftOutOfTheSelection(_Patched):
    """The experts chosen by the scores alone."""

    def patches(self):
        from paddle_tpu.models import sarvam_mla as m
        yield m.SarvamSparseMLP, "route", _route(bias=None)


class ScalingFactorLeftOut(_Patched):
    """The gates normalised to sum 1 and not multiplied by 2.5."""

    def patches(self):
        from paddle_tpu.models import sarvam_mla as m
        yield m.SarvamSparseMLP, "route", _route(scale=None)


class OneHeldExpertSkipped(_Patched):
    """The first held expert's pairs are never computed."""
    patches = test_faults_moe.OneHeldExpertSkipped.patches


FAULTS = {f.__name__: f for f in (
    RopeScoreDropped, ValuesFromTheWholeRow, ScaleWithoutYarn,
    BiasLeftOutOfTheSelection, ScalingFactorLeftOut, OneHeldExpertSkipped)}
#: the numbers that each alone have to refuse a fault.  One held expert
#: of four skipped moves the quarter of the positions that chose it: the
#: median position's row does not see it, the routed part does.
HELD_BY = {"RopeScoreDropped": ("served_latent_gap", "latent_gap"),
           "ValuesFromTheWholeRow": ("served_latent_gap", "latent_gap"),
           "ScaleWithoutYarn": ("served_latent_gap", "latent_gap"),
           "BiasLeftOutOfTheSelection": ("served_latent_gap", "routed_gap"),
           "ScalingFactorLeftOut": ("served_latent_gap", "routed_gap"),
           "OneHeldExpertSkipped": ("routed_gap",)}
NUMBERS = {"served_logit_gap", "served_latent_gap", "routed_gap",
           "latent_gap"}


def test_sound_mla_run_is_correct():
    r = tiny_run(CELL, seconds=3)
    drive_serve_mla.measure(r)
    assert r.correct
    assert set(r.compared) == NUMBERS


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault):
    r = tiny_run(CELL, seconds=3)
    drive_serve_mla.measure(r, prog_factory=FAULTS[fault])
    assert not r.correct, r.compared
    for name in HELD_BY[fault]:
        value, limit = r.compared[name]
        assert value > limit, (name, r.compared)


def test_mla_controls_are_not_correct():
    """Each float8 control in the program's place comes out ``correct``
    false by the harness's own comparison: the rows the reference caches
    when it is computed in float8 (whole, the latent rows alone, the
    experts alone) for the engine's rows, the reference's own attention
    with float8 rows or float8 ``W_kvb`` products for ``latent_gap``,
    float8 products in the experts alone for ``routed_gap``."""
    import control_mla
    r = tiny_run(CELL, seconds=3)
    r.control_routed = r.control_latent = r.control_rows = "fp8"
    drive_serve_mla.measure(r)
    assert r.correct
    got = control_mla.verdicts(r)
    assert sorted(got) == sorted(control_mla.CONTROLS)
    for name, (numbers, correct) in got.items():
        assert numbers and not correct, (name, numbers)
    limits = r.cell["limits"]
    assert got["fp8_whole"][0]["served_latent_gap"] > \
        limits["served_latent_gap"]
    assert got["fp8_rows"][0]["latent_gap"] > limits["latent_gap"]
    assert got["fp8_kvb"][0]["latent_gap"] > limits["latent_gap"]
    assert got["fp8_experts"][0]["routed_gap"] > limits["routed_gap"]
    assert r.correct            # the run's own verdict stands
