"""``correct`` comes out false when the timed path is broken underneath.
Each test skips the harness's look for a chip (a rehearsal ``Run`` at the
tiny presets) and drives the rest of a run through ``measure`` with the
fault planted in the program object the window drives; the lower-
precision control, put in the program's place, has to fail too."""
import argparse
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))

import compare  # noqa: E402
import drive_serve  # noqa: E402
import drive_train  # noqa: E402
import run as harness  # noqa: E402
from reference import run as refrun  # noqa: E402

TRAIN = "gpt3-1.3b-1chip.train-2k"
SERVE = ["mistral-7b-d16.decode-long"]


def tiny_run(cell, seed=2**31 + 5, seconds=1.5):
    r = harness.Run(argparse.Namespace(
        workload=cell, seed=seed, seconds=seconds, trace=0, rehearse=True))
    r.find_device()
    return r


class StateUnchanged(drive_train.TrainProgram):
    """A step that returns its state unchanged: the loss is computed,
    nothing is updated."""

    def step(self, x, y):
        with self.paddle.no_grad():
            return self._forward(x, y)


class HalfBatch(drive_train.TrainProgram):
    """Half of the batch left out, the mean taken over the rest."""

    def feed(self, batch):
        return super().feed(batch[:max(1, batch.shape[0] // 2)])


def test_sound_training_run_is_correct():
    r = tiny_run(TRAIN)
    drive_train.measure(r)
    assert r.correct, r.compared


@pytest.mark.parametrize("broken", [StateUnchanged, HalfBatch])
def test_broken_train_step_is_not_correct(broken):
    r = tiny_run(TRAIN)
    if broken is StateUnchanged:
        # the warm-up insists on a compiled step; the fault is planted
        # after it, where the window's own call is made
        class Planted(drive_train.TrainProgram):
            def reset_to_seed(self):
                super().reset_to_seed()
                self.step = lambda x, y: StateUnchanged.step(self, x, y)
        broken = Planted
    drive_train.measure(r, prog_factory=broken)
    assert not r.correct, r.compared


def test_train_control_is_not_correct():
    """The reference in float8, put in the program's place, fails."""
    r = tiny_run(TRAIN)
    drive_train.measure(r)
    rec = r.records
    low = refrun.TrainReference(
        r.config["reference"], r.model_cfg, r.optimizer, "fp8"
    ).follow(rec["spec"], r.seed, rec["param_dtype"], rec["fed"])
    numbers, _ = compare.train_numbers(low, rec["reference"])
    _, ok, _ = compare.judge(numbers, r.cell["limits"])
    assert not ok, numbers


class TokenAltered(drive_serve.ServeProgram):
    """One served token altered where the client reads it."""

    def submit(self, prompt, max_new):
        fut, live = super().submit(prompt, max_new)
        vocab = self.model.config.vocab_size
        inner = fut.result

        def result(timeout=None):
            out = inner(timeout)
            ids = np.array(out.output_ids)
            ids[len(ids) // 2] = (ids[len(ids) // 2] + 1) % vocab
            out.output_ids = ids
            return out
        fut.result = result
        return fut, live


@pytest.mark.parametrize("cell", SERVE)
def test_sound_serving_run_is_correct(cell):
    r = tiny_run(cell, seconds=3)
    drive_serve.measure(r)
    assert r.correct, r.compared


@pytest.mark.parametrize("cell", SERVE)
def test_altered_token_is_not_correct(cell):
    r = tiny_run(cell, seconds=3)
    drive_serve.measure(r, prog_factory=TokenAltered)
    assert not r.correct, r.compared


@pytest.mark.parametrize("cell", SERVE)
def test_serve_control_is_not_correct(cell):
    """The token the float8 reference puts first lies further below the
    reference's best than the limit allows."""
    r = tiny_run(cell, seconds=3)
    r.control = "fp8"
    drive_serve.measure(r)
    assert r.records["control_gap"] > r.cell["limits"]["served_logit_gap"]


def test_open_loop_runs_and_reports_tails():
    """No cell offers an open loop yet; the generator and the client
    keep the path for the chat cells PERF.md section 7 lists."""
    r = tiny_run(SERVE[0], seconds=3)
    r.mix = dict(r.mix, loop="open", arrivals="poisson", rate_per_s=4.0)
    drive_serve.measure(r)
    assert r.correct, r.compared
    assert r.metrics["ttft_p95_ms"] > 0 and r.metrics["tpot_p95_ms"] > 0
