"""Planted faults of the Mellum2 cell, at the tiny presets on CPUs: what
two rotations by layer kind, a window and a softmax router over all the
experts can get wrong.  Each has to turn ``correct`` false: the faults
of attention by ``attention_gap`` over its own limit, those of the
routed part by ``routed_gap`` over its own.  ``fault_mellum.py`` plants
the same faults on the chip at the timed sizes."""
import pytest

from test_faults import tiny_run    # puts the harness on the path
import test_faults_moe
import drive_serve_window_moe       # noqa: E402

CELL = "mellum2-12b-a2.5b-d8.code-mixed-64"


class _Patched(test_faults_moe._Patched,
               drive_serve_window_moe.WindowMoeProgram):
    """``test_faults_moe._Patched`` over this cell's program."""


class FullLayersAtDefault(_Patched):
    """The full layers rotated at the plain frequencies of their theta,
    not by YaRN (the attention factor kept)."""

    def patches(self):
        from paddle_tpu.models import mellum as m
        from paddle_tpu.models import rotary
        yield (m, "yarn_inv_freq", lambda dim, theta, scaling:
               rotary.default_inv_freq(dim, theta))


class AttentionFactorDropped(_Patched):
    """YaRN's frequencies, but cos and sin not multiplied by the
    attention factor."""

    def patches(self):
        from paddle_tpu.models import mellum as m
        yield m, "yarn_cos_sin_scale", lambda scaling: 1.0


class WindowWidened(_Patched):
    """Window layers see twice the published window, and the cache
    manager sizes their rings for it.  Widened to full, the six window
    layers' pages (1.7 GiB a layer at the timed size) do not fit beside
    the weights on one chip; twice the window does, and at a 12 k
    request most positions still see keys they must not."""

    def patches(self):
        from paddle_tpu.models import mellum as m
        widen = lambda w: None if w is None else 2 * w   # noqa: E731
        windows = m.MellumConfig.layer_windows
        yield (m.MellumConfig, "layer_windows",
               lambda self: [widen(w) for w in windows(self)])
        yield test_faults_moe._after_init(
            m.MellumAttention,
            lambda att: setattr(att, "window", widen(att.window)))


class SigmoidForSoftmax(_Patched):
    """Sigmoid scores, normalised over the chosen, in place of the
    softmax's probabilities: the same experts, other gates."""

    def patches(self):
        import jax
        from paddle_tpu.pallas import moe
        yield (moe, "route_softmax_topk",
               test_faults_moe._route(jax.nn.sigmoid))


class OneExpertSkipped(_Patched):
    """The first of the 64 experts' pairs are never computed."""
    patches = test_faults_moe.OneHeldExpertSkipped.patches


FAULTS = {f.__name__: f for f in (
    FullLayersAtDefault, AttentionFactorDropped, WindowWidened,
    SigmoidForSoftmax, OneExpertSkipped)}
#: the number that alone has to refuse each fault
HELD_BY = {"FullLayersAtDefault": "attention_gap",
           "AttentionFactorDropped": "attention_gap",
           "WindowWidened": "attention_gap",
           "SigmoidForSoftmax": "routed_gap",
           "OneExpertSkipped": "routed_gap"}
NUMBERS = {"served_logit_gap", "routed_gap", "attention_gap"}


def test_sound_run_is_correct():
    r = tiny_run(CELL, seconds=3)
    drive_serve_window_moe.measure(r)
    assert r.correct, r.compared
    assert set(r.compared) == NUMBERS


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault):
    r = tiny_run(CELL, seconds=3)
    drive_serve_window_moe.measure(r, prog_factory=FAULTS[fault])
    assert not r.correct, r.compared
    value, limit = r.compared[HELD_BY[fault]]
    assert value > limit, r.compared


def test_controls_are_not_correct():
    """Each float8 control in the program's place comes out ``correct``
    false by the harness's own comparison: the whole reference in float8
    for ``served_logit_gap``, float8 products in the experts alone for
    ``routed_gap``, float8 keys and values for ``attention_gap``."""
    import control_mellum
    r = tiny_run(CELL, seconds=3)
    r.control_routed = "fp8"
    progs = []
    drive_serve_window_moe.measure(
        r, prog_factory=control_mellum.keeping(progs))
    assert r.correct
    r.records["control_gap"] = control_mellum.served_control(
        r, progs[0], "fp8")
    got = control_mellum.verdicts(r)
    assert sorted(got) == sorted(control_mellum.CONTROLS)
    for name, (numbers, correct) in got.items():
        assert correct is False, (name, numbers, r.cell["limits"])
    assert r.correct
