"""A builder's tool beside ``control.py``, for a cell of the
``serve_state`` driver: the program's readings and the controls', for
one seed, as one JSON line.  The limits in the cell's file stand
between; PERF.md section 2 lists what was read.

    python3 chipbench/tests/control_state.py <cell> <seed> <seconds> [--fp8] [--program-bfloat16]

``state_gap``'s control is the reference with its recurrent state rounded
to bfloat16 after every token, against the float32 reference, at the
requests the run kept.  ``--fp8`` adds ``served_logit_gap``'s control as
``control.py`` reads it.  ``--program-bfloat16`` rounds the PROGRAM's
state the same way after every decode step and prefill chunk — what a
bfloat16 state array would hold — so that the line's ``program`` is the
reading of that fault, which the limits have to refuse."""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))

import run as harness  # noqa: E402


def round_program_state(keep):
    """Both ways the program moves a state hand it back rounded."""
    from paddle_tpu.pallas import ssm

    def rounded(fn):
        def call(*args):
            state, y = fn(*args)
            return keep(state), y
        return call

    ssm.ssm_step = rounded(ssm.ssm_step)
    ssm.ssd_chunked = rounded(ssm.ssd_chunked)


def main():
    cell, seed, seconds = sys.argv[1:4]
    flags = set(sys.argv[4:])
    r = harness.Run(argparse.Namespace(
        workload=cell, seed=int(seed), seconds=float(seconds), trace=0,
        rehearse="--rehearse" in flags))
    r.find_device()
    import drive_serve_state
    r.control_state = "bfloat16"
    if "--fp8" in flags:
        r.control = "fp8"
    if "--program-bfloat16" in flags:
        round_program_state(drive_serve_state.KEEP["bfloat16"])
    drive_serve_state.measure(r)
    out = {"cell": cell, "seed": int(seed),
           "program_state": "bfloat16" if "--program-bfloat16" in flags
           else "as built",
           "program": {k: v[0] for k, v in r.compared.items()},
           "control": {"state_gap": r.records["control_state_gap"]},
           "correct": r.correct,
           "serve_tokens_per_s": r.metrics["serve_tokens_per_s"]}
    if "--fp8" in flags:
        out["control"]["served_logit_gap"] = r.records["control_gap"]
    print(json.dumps(out), flush=True)
    os._exit(0)


if __name__ == "__main__":
    main()
