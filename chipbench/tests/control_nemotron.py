"""A builder's tool beside ``control_moe.py`` and ``control_state.py``, for
a cell of the ``serve_hybrid_moe`` driver: the program's readings and the
controls', for one seed, as one JSON line, and what the harness's own
comparison makes of each control PUT IN THE PROGRAM'S PLACE.  The limits
in the cell's file stand between; PERF.md section 2 lists what was read.

    python3 chipbench/tests/control_nemotron.py <cell> <seed> <seconds> [--fp8]

``routed_gap``'s control is the reference's latent routed part with the
experts' own products in float8 (router and latent projections in
float32, so the chosen experts are the reference's); ``state_gap``'s is
the reference with its Mamba-2 state rounded to bfloat16 after every
token; ``attention_gap``'s, read beside every run, is the reference's
attention with its keys and values in float8.  ``--fp8`` adds ``served_logit_gap``'s control as ``control.py``
reads it: the whole reference in float8."""
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
    "bfloat16_state": {"state_gap": "control_state_gap"},
    "fp8_whole": {"served_logit_gap": "control_gap"},
    "fp8_kv": {"attention_gap": "control_attention_gap"}}


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
    import drive_serve_hybrid_moe
    r.control_routed = "fp8"
    r.control_state = "bfloat16"
    if "--fp8" in flags:
        r.control = "fp8"
    drive_serve_hybrid_moe.measure(r)
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
