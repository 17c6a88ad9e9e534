"""Planted faults of the hybrid cell, at the tiny presets on CPUs: what a
recurrent state beside the pages can get wrong, and what sets this
family's attention apart.  Each has to bring ``served_logit_gap`` over
the cell's limit — the proof that the seeded weights give the
recurrence a memory the check can see (reference/granite_hybrid.py).
One fault no served token shows, a state kept in bfloat16: ``state_gap``
(drive_serve_state.py) has to refuse it."""
import math

import pytest

from test_faults import tiny_run    # puts the harness on the path
import drive_serve                  # noqa: E402
import drive_serve_state            # noqa: E402

CELL = "granite-4.0-h-micro.decode-long-64"


class StateNotReset(drive_serve.ServeProgram):
    """Admission leaves the slot's previous tenant's state in place."""

    def __init__(self, run):
        super().__init__(run)
        self.engine.cache.reset_state = lambda slot: None


class PadsFedToRecurrence(drive_serve.ServeProgram):
    """A prefill chunk's pad positions move the state like real tokens,
    and the convolution's window ends on the chunk, not on the row's
    last real token."""

    def __init__(self, run):
        super().__init__(run)
        cache = self.engine.cache
        views_over = cache.views_over

        def all_real(pools, pt, off, state_rows=None, valid_len=None):
            if state_rows is not None:          # a prefill call
                valid_len = None
            return views_over(pools, pt, off, state_rows, valid_len)

        cache.views_over = all_real


class SqrtScaledAttention(drive_serve.ServeProgram):
    """Scores scaled by 1/sqrt(head) in place of attention_multiplier."""

    def __init__(self, run):
        super().__init__(run)
        cfg = self.model.config
        cfg.attention_multiplier = 1.0 / math.sqrt(cfg.head_dim)


class RotaryApplied(drive_serve.ServeProgram):
    """Queries and keys rotated by position, as Llama's are; this
    family's carry none."""

    def __init__(self, run):
        super().__init__(run)
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

        self._attend = attend
        IF.paged_cache_attention = rotated

    def shutdown(self):
        from paddle_tpu.incubate.nn import functional as IF
        IF.paged_cache_attention = self._attend
        super().shutdown()


def test_sound_hybrid_run_is_correct():
    r = tiny_run(CELL, seconds=3)
    drive_serve_state.measure(r)
    assert r.correct and set(r.compared) == {"served_logit_gap",
                                             "state_gap"}, r.compared


@pytest.mark.parametrize("fault", [
    StateNotReset, PadsFedToRecurrence, SqrtScaledAttention, RotaryApplied])
def test_planted_fault_is_not_correct(fault):
    r = tiny_run(CELL, seconds=3)
    drive_serve.measure(r, prog_factory=fault)
    assert not r.correct, r.compared


def test_hybrid_control_is_not_correct():
    """The token the float8 reference puts first lies further below the
    reference's best than the limit allows."""
    r = tiny_run(CELL, seconds=3)
    r.control = "fp8"
    drive_serve.measure(r)
    assert r.records["control_gap"] > r.cell["limits"]["served_logit_gap"]


def test_bfloat16_state_is_not_correct(monkeypatch):
    """The program's state rounded to bfloat16 after every step and
    chunk: the served tokens pass, the state does not; nor does the
    reference with its state rounded so, the limit's control."""
    from paddle_tpu.models import granite_hybrid
    from paddle_tpu.pallas import ssm
    from control_state import round_program_state
    monkeypatch.setattr(ssm, "ssm_step", ssm.ssm_step)
    monkeypatch.setattr(ssm, "ssd_chunked", ssm.ssd_chunked)
    round_program_state(drive_serve_state.KEEP["bfloat16"])
    # the mixer's jit keeps the trace of the functions it first saw
    granite_hybrid._mamba2_mix.clear_cache()
    r = tiny_run(CELL, seconds=3)
    r.control_state = "bfloat16"
    try:
        drive_serve_state.measure(r)
    finally:
        granite_hybrid._mamba2_mix.clear_cache()
    gap, limit = r.compared["served_logit_gap"]
    assert gap <= limit
    gap, limit = r.compared["state_gap"]
    assert gap > limit and not r.correct
    assert r.records["control_state_gap"] > limit
