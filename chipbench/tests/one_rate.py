"""A builder's tool, not part of a run: one serving cell at an offered
rate other than the cell's, to find the knee once (PERF.md section 4).

    python3 chipbench/tests/one_rate.py <cell> <seed> <seconds> <rate>
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))

import run as harness  # noqa: E402


def main():
    cell, seed, seconds, rate = sys.argv[1:5]
    r = harness.Run(argparse.Namespace(
        workload=cell, seed=int(seed), seconds=float(seconds), trace=0,
        rehearse=False))
    r.mix["rate_per_s"] = float(rate)
    r.find_device()
    import drive_serve
    drive_serve.measure(r)
    print(json.dumps({"rate": float(rate), "metrics": r.metrics,
                      "attempted": r.attempted, "failed": r.failed,
                      "compared": r.compared}), flush=True)
    os._exit(0)


if __name__ == "__main__":
    main()
