"""A builder's tool beside ``control.py``, for a cell of the
``serve_moe`` driver: the program's readings and the controls', for one
seed, as one JSON line.  The limits in the cell's file stand between;
PERF.md section 2 lists what was read.

    python3 chipbench/tests/control_moe.py <cell> <seed> <seconds> [--fp8]

``routed_gap``'s control is the reference's routed part with the
experts' products alone in float8 (the router stays in float32, so the
chosen experts are the reference's), against the float32 reference, at
the request the run compared.  ``--fp8`` adds ``served_logit_gap``'s
control as ``control.py`` reads it: the whole reference in float8."""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))

import run as harness  # noqa: E402


def main():
    cell, seed, seconds = sys.argv[1:4]
    flags = set(sys.argv[4:])
    r = harness.Run(argparse.Namespace(
        workload=cell, seed=int(seed), seconds=float(seconds), trace=0,
        rehearse="--rehearse" in flags))
    r.find_device()
    import drive_serve_moe
    r.control_routed = "fp8"
    if "--fp8" in flags:
        r.control = "fp8"
    drive_serve_moe.measure(r)
    out = {"cell": cell, "seed": int(seed),
           "program": {k: v[0] for k, v in r.compared.items()},
           "control": {"routed_gap": r.records["control_routed_gap"]},
           "routed_flips": r.records["routed_flips"],
           "correct": r.correct,
           "setup_s": r.setup_s,
           "serve_tokens_per_s": r.metrics["serve_tokens_per_s"]}
    if "--fp8" in flags:
        out["control"]["served_logit_gap"] = r.records["control_gap"]
    print(json.dumps(out), flush=True)
    os._exit(0)


if __name__ == "__main__":
    main()
