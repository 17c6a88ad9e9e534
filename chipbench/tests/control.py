"""A builder's tool, run on the chip at the cell's own size: the
program's reading, the lower-precision control's and (training) the
planted faults', for one seed, as one JSON line.  PERF.md section 2
lists what it read; the limits in ``cells/*.json`` stand between.

    python3 chipbench/tests/control.py <cell> <seed> <seconds> <precision>

Serving: the control is the reference computed in ``precision`` (int8 or
fp8 for a bfloat16 configuration); at every position of the sampled
requests it reads the gap of the token the control puts first.
Training: the control is the reference in ``precision`` put in the
program's place; the fault "half of the batch left out" is planted in
the float32 reference put in the program's place."""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))

import run as harness  # noqa: E402


def main():
    cell, seed, seconds, precision = sys.argv[1:5]
    r = harness.Run(argparse.Namespace(
        workload=cell, seed=int(seed), seconds=float(seconds), trace=0,
        rehearse="--rehearse" in sys.argv))
    r.find_device()
    out = {"cell": cell, "seed": int(seed), "precision": precision}
    if r.cell["driver"] == "serve":
        import drive_serve
        r.control = precision
        drive_serve.measure(r)
        out["program"] = {k: v[0] for k, v in r.compared.items()}
        out["control"] = {"served_logit_gap": r.records["control_gap"]}
    else:
        import compare
        import drive_train
        from reference import run as refrun
        drive_train.measure(r)
        out["program"] = {k: v[0] for k, v in r.compared.items()}
        rec = r.records
        ref, fed = rec["reference"], rec["fed"]
        low = refrun.TrainReference(
            r.config["reference"], r.model_cfg, r.optimizer, precision
        ).follow(rec["spec"], r.seed, rec["param_dtype"], fed)
        out["control"], _ = compare.train_numbers(low, ref)
        half = refrun.TrainReference(
            r.config["reference"], r.model_cfg, r.optimizer
        ).follow(rec["spec"], r.seed, rec["param_dtype"],
                 [b[:b.shape[0] // 2] for b in fed])
        out["half_batch"], _ = compare.train_numbers(half, ref)
    print(json.dumps(out), flush=True)
    os._exit(0)


if __name__ == "__main__":
    main()
