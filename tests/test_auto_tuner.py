"""Launch-level auto-tuner end-to-end (reference:
python/paddle/distributed/auto_tuner/tuner.py:19 trial loop)."""
import json
import os

from paddle_tpu.distributed.auto_tuner.tuner import (
    AutoTuner, TunerConfig, current_trial_config,
)


def _small_cfg(**kw):
    base = dict(n_devices=8, device="v5e", n_params=1.3e9, n_layers=24,
                hidden=2048, global_batch=64, seq_len=1024)
    base.update(kw)
    return TunerConfig(**base)


def test_candidates_pruned_and_ranked():
    tuner = AutoTuner(_small_cfg())
    cands = list(tuner.candidates())
    assert cands, "search space empty"
    for c in cands:
        assert c["dp"] * c["mp"] * c["pp"] * c["sharding"] == 8
        assert 24 % c["pp"] == 0 and 2048 % c["mp"] == 0
    best = tuner.tune(mode="predict")
    assert best is not None
    # history is fully populated in predict mode
    assert len(tuner.history) == len(cands)


def test_tune_with_trial_fn():
    tuner = AutoTuner(_small_cfg())

    def trial(cand):
        # favor mp=2 artificially
        return 100.0 if cand["mp"] == 2 else 10.0

    best = tuner.tune(trial_fn=trial, max_trials=50)
    assert best["mp"] == 2


def test_tune_by_launch_runs_real_trials(tmp_path):
    script = tmp_path / "trial.py"
    script.write_text(
        "import json, os\n"
        "cfg = json.loads(os.environ['PADDLE_AUTO_TUNER_CONFIG'])\n"
        "# pretend dp-heavy configs are fastest\n"
        "print('AUTO_TUNER_METRIC:', 1000.0 * cfg['dp'] + cfg['micro_batch'])\n")
    tuner = AutoTuner(_small_cfg(
        n_params=0.2e9, mp_candidates=[1, 2], pp_candidates=[1],
        sharding_candidates=[1], micro_batch_candidates=[1, 2]))
    # trial subprocesses re-import jax — force them onto CPU so they
    # don't claim the machine's accelerator
    old = {k: os.environ.get(k) for k in ("JAX_PLATFORMS",)}
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        best = tuner.tune_by_launch(str(script), max_trials=4, timeout=120)
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    assert best is not None
    assert len(tuner.history) == 4
    tputs = [t for _, t in tuner.history]
    assert max(tputs) > 0
    best_cand, best_t = max(tuner.history, key=lambda h: h[1])
    assert best == best_cand


def test_current_trial_config_roundtrip():
    os.environ["PADDLE_AUTO_TUNER_CONFIG"] = json.dumps({"dp": 4, "mp": 2})
    try:
        assert current_trial_config() == {"dp": 4, "mp": 2}
    finally:
        del os.environ["PADDLE_AUTO_TUNER_CONFIG"]
    assert current_trial_config({"dp": 1}) == {"dp": 1}


def test_optimization_dimensions_in_search_space():
    """Optimization-tuner analog (reference: static/tuner/
    optimization_tuner.py — trials toggle recompute/amp): the search
    space carries use_recompute/amp, and recompute shrinks the roofline
    activation estimate so memory-infeasible points become feasible."""
    tuner = AutoTuner(_small_cfg(
        recompute_candidates=[False, True], amp_candidates=["O0", "O2"]))
    cands = list(tuner.candidates())
    assert {c["use_recompute"] for c in cands} == {False, True}
    assert {c["amp"] for c in cands} == {"O0", "O2"}

    from paddle_tpu.cost_model import transformer_step_cost
    plain = transformer_step_cost(1.3e9, 24, 2048, 64, 1024)
    rc = transformer_step_cost(1.3e9, 24, 2048, 64, 1024, recompute=True)
    assert rc.hbm_per_device < plain.hbm_per_device   # fewer acts stored
    assert rc.step_time_s >= plain.step_time_s        # extra forward

    def trial(cand):   # favor the recompute+amp corner artificially
        return 100.0 if cand["use_recompute"] and cand["amp"] == "O2" \
            else 10.0

    best = tuner.tune(trial_fn=trial, max_trials=100)
    assert best["use_recompute"] and best["amp"] == "O2"
