"""A tool beside ``control_nemotron.py``, for a cell of the
``serve_window_moe`` driver: the program's readings and the controls',
for one seed, as one JSON line, and what the harness's own comparison
makes of each control PUT IN THE PROGRAM'S PLACE.  The limits in the
cell's file stand between; PERF.md section 2 lists what was read.

    python3 chipbench/tests/control_mellum.py <cell> <seed> <seconds>

``routed_gap``'s control is the reference's routed part with the
experts' own products in float8 (the router in float32, so the chosen
experts are the reference's); ``attention_gap``'s, read beside every
run, is the reference's attention with its keys and values in float8;
``served_logit_gap``'s is the whole reference in float8, read as
``control.py`` reads it, at the longest finished request: the gap below
the reference's best of the token the float8 reference puts first.
The two references' logits are taken one after the other (each is 5.4
GB at the slot's length, and the chip holds one beside its copy)."""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))

import run as harness  # noqa: E402

#: a control -> {the number it is read as: the record that holds it}
CONTROLS = {
    "fp8_experts": {"routed_gap": "control_routed_gap"},
    "fp8_whole": {"served_logit_gap": "control_gap"},
    "fp8_kv": {"attention_gap": "control_attention_gap"}}


def keeping(progs):
    """A ``prog_factory`` that keeps the program it makes in ``progs``."""
    import drive_serve_window_moe

    def factory(run):
        progs.append(drive_serve_window_moe.WindowMoeProgram(run))
        return progs[-1]
    return factory


def served_control(run, prog, precision):
    """The widest gap below the reference's best, over the longest
    finished request's served positions, of the token the reference
    computed in ``precision`` puts first."""
    import jax.numpy as jnp
    import numpy as np
    from reference import run as refrun
    prompt, output = prog.longest_finished()
    pad = run.cell["engine"]["max_seq_len"]
    n = prompt.size + output.size
    ids = np.zeros(pad, np.int32)
    ids[:n] = np.concatenate([prompt, output])
    name, cfg = run.config["reference"], run.model_cfg
    low = refrun.ServeReference(name, cfg, precision)
    first = jnp.argmax(low.logits(prog.weights, ids), axis=-1) \
        .astype(jnp.int32)
    logits = refrun.ServeReference(name, cfg).logits(prog.weights, ids)
    gaps = np.asarray(refrun._gaps(logits, first))[prompt.size - 1:n - 1]
    return float(gaps.max())


def verdicts(r):
    """{control: [the numbers it replaced, ``correct`` as ``Run.judge``
    decides it]}; the run's own verdict is judged last and stands."""
    program = {k: v[0] for k, v in r.compared.items()}
    out = {}
    for name, reads in CONTROLS.items():
        got = {k: r.records[rec] for k, rec in reads.items()
               if rec in r.records}
        if got:
            r.judge(dict(program, **got))
            out[name] = [got, r.correct]
    r.judge(program)
    return out


def main():
    cell, seed, seconds = sys.argv[1:4]
    flags = set(sys.argv[4:])
    r = harness.Run(argparse.Namespace(
        workload=cell, seed=int(seed), seconds=float(seconds), trace=0,
        rehearse="--rehearse" in flags))
    r.find_device()
    import drive_serve_window_moe
    r.control_routed = "fp8"
    progs = []
    drive_serve_window_moe.measure(r, prog_factory=keeping(progs))
    r.records["control_gap"] = served_control(r, progs[0], "fp8")
    print(json.dumps({
        "cell": cell, "seed": int(seed),
        "program": {k: v[0] for k, v in r.compared.items()},
        "limits": {k: v[1] for k, v in r.compared.items()},
        "controls": verdicts(r),
        "routed_flips": r.records["routed_flips"],
        "correct": r.correct, "failed": r.failed,
        "setup_s": r.setup_s,
        "memory_peak_bytes": r.device.get("memory_peak_bytes"),
        "serve_tokens_per_s": r.metrics["serve_tokens_per_s"]}), flush=True)
    os._exit(0)


if __name__ == "__main__":
    main()
