"""A builder's tool, run on the chip at the cell's own size: one planted
fault of ``test_faults_mla.py`` in the program's place, for one seed, as
one JSON line: the readings, the cell's limits and what the harness's
own comparison made of them.  PERF.md section 2 lists what it read.

    python3 chipbench/tests/fault_mla.py <cell> <seed> <seconds> <fault>

``<fault>`` is a class name of ``test_faults_mla.FAULTS``."""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))
sys.path.insert(2, os.path.dirname(os.path.dirname(HERE)))

import run as harness  # noqa: E402


def main():
    cell, seed, seconds, fault = sys.argv[1:5]
    import drive_serve_mla
    import test_faults_mla as faults
    r = harness.Run(argparse.Namespace(
        workload=cell, seed=int(seed), seconds=float(seconds), trace=0,
        rehearse="--rehearse" in sys.argv))
    r.find_device()
    drive_serve_mla.measure(r, prog_factory=faults.FAULTS[fault])
    print(json.dumps({
        "cell": cell, "seed": int(seed), "fault": fault,
        "correct": r.correct,
        "program": {k: v[0] for k, v in r.compared.items()},
        "limits": {k: v[1] for k, v in r.compared.items()},
        "routed_flips": r.records["routed_flips"],
        "latent_gap_chunk": r.records["latent_gap_chunk"],
        "latent_gap_step": r.records["latent_gap_step"]}), flush=True)
    os._exit(0)


if __name__ == "__main__":
    main()
