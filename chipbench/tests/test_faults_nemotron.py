"""Planted faults of the latent-expert hybrid cell, at the tiny presets on
CPUs: what a LatentMoE layer, a grouped Mamba-2 mixer and attention
without rotation can get wrong.  Each has to turn ``correct`` false; the
faults of the routed part have to bring ``routed_gap`` over its own
limit, a rotation in attention ``attention_gap``.  The controls: the
routed part with float8 experts has to fail ``routed_gap``, a bfloat16
state ``state_gap``, float8 keys and values ``attention_gap``.
``fault_nemotron.py`` plants the same faults on the chip at the timed
sizes."""
import numpy as np
import pytest

from test_faults import tiny_run    # puts the harness on the path
import drive_serve_hybrid_moe       # noqa: E402
import drive_serve_state            # noqa: E402

CELL = "nemotron-3-super-ep4-d11.reason-128"


class _Patched(drive_serve_hybrid_moe.HybridMoeProgram):
    """A program built, run and compared with attributes of the
    program's modules swapped; ``patches()`` yields (owner, name,
    replacement).  They are lifted after the last comparison.  The
    Mamba-2 mixer's jit keeps the trace of the functions it first saw,
    so its cache is cleared on both sides."""

    def __init__(self, run):
        self._undo = []
        for owner, name, new in self.patches():
            self._undo.append((owner, name, getattr(owner, name)))
            setattr(owner, name, new)
        _clear_mixer()
        try:
            super().__init__(run)
        except BaseException:
            self._restore()
            raise

    def _restore(self):
        for owner, name, old in reversed(self._undo):
            setattr(owner, name, old)
        self._undo = []
        _clear_mixer()

    def finish(self):
        self._restore()
        super().finish()


def _clear_mixer():
    from paddle_tpu.models import granite_hybrid
    granite_hybrid._mamba2_mix.clear_cache()


def _after_init(cls, fix):
    """``cls.__init__`` followed by ``fix(self)``."""
    init = cls.__init__

    def patched(self, *a, **k):
        init(self, *a, **k)
        fix(self)
    return cls, "__init__", patched


class ReluForRelu2(_Patched):
    """The routed experts' up product takes ``relu``, not ``relu²``."""

    def patches(self):
        import jax
        from paddle_tpu.pallas import moe
        gmm = moe.expert_gmm

        def relu(x, weights, groups, tm, lane=None, act=None):
            if act != "relu2":
                return gmm(x, weights, groups, tm, lane, act)
            return jax.nn.relu(gmm(x, weights, groups, tm, lane))

        yield moe, "expert_gmm", relu


class LatentUpSkipped(_Patched):
    """``W_lat_up`` skipped: the latent sum goes on in the first
    ``moe_latent_size`` channels of the stream, the rest zero."""

    def patches(self):
        import jax.numpy as jnp
        from paddle_tpu.core.dispatch import apply_op
        from paddle_tpu.models import nemotron_h as m

        def skip(mlp):
            pad = mlp.config.hidden_size - mlp.config.moe_latent_size
            mlp.fc2_latent_proj.forward = lambda y: apply_op(
                "latent_up_skipped", lambda a: jnp.pad(
                    a, ((0, 0), (0, 0), (0, pad))), (y,))
        yield _after_init(m.NemotronLatentMoE, skip)


class FactorLeftOut(_Patched):
    """The gates are the normalised scores, not 5 times them."""

    def patches(self):
        from paddle_tpu.models import nemotron_h as m
        yield _after_init(m.NemotronLatentMoE,
                          lambda mlp: setattr(mlp, "gate_scale", None))


class BiasLeftOut(_Patched):
    """The experts are chosen by the scores alone, without the bias."""

    def patches(self):
        from paddle_tpu.models import nemotron_h as m

        def unbiased(mlp):
            route = mlp.route
            mlp.route = lambda tokens, wr, bias=None: route(tokens, wr)
        yield _after_init(m.NemotronLatentMoE, unbiased)


class GatedNormOverAll(_Patched):
    """The gated norm over all ``d_inner`` channels at once, Granite's
    form, not over each group's."""

    def patches(self):
        from paddle_tpu.models import granite_hybrid as g
        yield _after_init(g.GraniteMamba2Mixer,
                          lambda mixer: setattr(mixer, "norm_groups", 1))


class HeadsInterleaved(_Patched):
    """Head ``i`` reads group ``i mod G`` of B and C, not ``i // (H /
    G)``: every lane (the decode kernel, the XLA step, the chunked scan)
    is handed one group a head in that order."""

    def patches(self):
        from paddle_tpu.pallas import ssm

        def interleaved(fn):
            def call(state, rows_or_x, *rest):
                # ssm_step(state, rows, x, dt, a, bm, cm);
                # ssd_chunked(s_in, x, dt, a, bm, cm, chunk)
                args = [state, rows_or_x, *rest]
                x_at = 2 if fn is step else 1
                heads = args[x_at].shape[-2]
                for i in (x_at + 3, x_at + 4):
                    groups = args[i].shape[-2]
                    args[i] = args[i][..., np.arange(heads) % groups, :]
                return fn(*args)
            return call

        step = ssm.ssm_step
        yield ssm, "ssm_step", interleaved(step)
        yield ssm, "ssd_chunked", interleaved(ssm.ssd_chunked)


class RotaryApplied(_Patched):
    """Queries and keys rotated by position, as Llama's are; this
    family's attention carries none."""

    def patches(self):
        from paddle_tpu.incubate.nn import functional as IF
        from paddle_tpu.tensor_ops import creation
        from paddle_tpu.tensor_ops import manipulation as MA
        attend = IF.paged_cache_attention

        def rotated(q, k, v, cache, scale=None):
            b, s = q.shape[0], q.shape[1]
            pos = MA.reshape(cache["offset"], [b, 1]) + MA.reshape(
                creation.arange(s, dtype="int32"), [1, s])
            q, k, _ = IF.fused_rotary_position_embedding(
                q, k, position_ids=pos, rotary_emb_base=10000.0)
            return attend(q, k, v, cache, scale=scale)

        yield IF, "paged_cache_attention", rotated


FAULTS = {f.__name__: f for f in (
    ReluForRelu2, LatentUpSkipped, FactorLeftOut, BiasLeftOut,
    GatedNormOverAll, HeadsInterleaved, RotaryApplied)}
#: the faults of the routed part: ``routed_gap`` alone has to refuse them
ROUTED = ("ReluForRelu2", "LatentUpSkipped", "FactorLeftOut", "BiasLeftOut")
#: and of attention: at the cell's sizes served logits do not see it
ATTENTION = ("RotaryApplied",)


def test_sound_run_is_correct():
    r = tiny_run(CELL, seconds=3)
    drive_serve_hybrid_moe.measure(r)
    assert r.correct and set(r.compared) == {
        "served_logit_gap", "state_gap", "routed_gap",
        "attention_gap"}, r.compared


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault):
    r = tiny_run(CELL, seconds=3)
    drive_serve_hybrid_moe.measure(r, prog_factory=FAULTS[fault])
    assert not r.correct, r.compared
    for name, faults in (("routed_gap", ROUTED),
                         ("attention_gap", ATTENTION)):
        if fault in faults:
            value, limit = r.compared[name]
            assert value > limit, r.compared


def test_controls_are_not_correct():
    """The routed part with float8 experts alone lies further from the
    float32 one than ``routed_gap``'s limit allows; the reference with
    a bfloat16 state, further than ``state_gap``'s; attention over
    float8 keys and values, further than ``attention_gap``'s; the token
    the float8 reference puts first, further than
    ``served_logit_gap``'s."""
    r = tiny_run(CELL, seconds=3)
    r.control, r.control_routed, r.control_state = "fp8", "fp8", "bfloat16"
    drive_serve_hybrid_moe.measure(r)
    limits = r.cell["limits"]
    assert r.correct, r.compared
    assert r.records["control_routed_gap"] > limits["routed_gap"]
    assert r.records["control_state_gap"] > limits["state_gap"]
    assert r.records["control_gap"] > limits["served_logit_gap"]
    assert r.records["control_attention_gap"] > limits["attention_gap"]


def test_bfloat16_program_state_is_not_correct(monkeypatch):
    """The program's state rounded to bfloat16 after every step and
    chunk: ``state_gap`` refuses it."""
    from paddle_tpu.pallas import ssm
    from control_state import round_program_state
    monkeypatch.setattr(ssm, "ssm_step", ssm.ssm_step)
    monkeypatch.setattr(ssm, "ssd_chunked", ssm.ssd_chunked)
    round_program_state(drive_serve_state.KEEP["bfloat16"])
    _clear_mixer()
    r = tiny_run(CELL, seconds=3)
    try:
        drive_serve_hybrid_moe.measure(r)
    finally:
        monkeypatch.undo()
        _clear_mixer()
    gap, limit = r.compared["state_gap"]
    assert gap > limit and not r.correct, r.compared
