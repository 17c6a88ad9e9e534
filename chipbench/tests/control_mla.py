"""A builder's tool beside ``control_moe.py``, for a cell of the
``serve_mla`` driver: the program's readings and the controls', for one
seed, as one JSON line, and what the harness's own comparison makes of
each control PUT IN THE PROGRAM'S PLACE.  The limits in the cell's file
stand between; PERF.md section 2 lists what was read.

    python3 chipbench/tests/control_mla.py <cell> <seed> <seconds> [--fp8]

``served_latent_gap``'s controls are the rows the reference caches when
it is computed in float8 — every product (``whole``), the latent rows
alone as a float8 cache would hold them (``rows``), the experts'
products alone (``experts``) — in the place of the rows the engine
kept.  ``latent_gap``'s controls are the reference's own attention with
float8 latent rows (``rows``) and with ``W_kvb``'s products in float8
(``kvb``); ``routed_gap``'s is the routed part with the experts'
products alone in float8.  ``--fp8`` adds ``served_logit_gap``'s control
as ``control.py`` reads it — the widest gap, in the float32 reference's
logits, of the token the WHOLE reference in float8 puts first — for the
longest finished request, the two references one after the other: at
20,480 positions and 32,768 rows a reference's logits are 2.7 GB, and
two of them beside the served weights do not fit the chip."""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))

import run as harness  # noqa: E402


def control_served_gap(r, prog):
    """``served_gaps``'s control number for the longest finished
    request, the float8 reference first and gone before the float32 one
    runs."""
    import jax.numpy as jnp
    import numpy as np
    from reference import run as refrun
    prompt, output = prog.longest_finished()
    pad = r.cell["engine"]["max_seq_len"]
    n = prompt.size + output.size
    ids = np.zeros(pad, np.int32)
    ids[:n] = np.concatenate([prompt, output])
    name, cfg = r.config["reference"], r.model_cfg
    first = jnp.argmax(refrun.ServeReference(name, cfg, "fp8").logits(
        prog.weights, ids), axis=-1).astype(jnp.int32)
    gaps = refrun._gaps(refrun.ServeReference(name, cfg).logits(
        prog.weights, ids), first)
    return float(np.asarray(gaps)[prompt.size - 1:n - 1].max())


#: a control -> {the number it is read as: the record that holds it}
CONTROLS = {
    "fp8_whole": {"served_latent_gap": "control_served_latent_gap_whole",
                  "served_logit_gap": "control_served_logit_gap"},
    "fp8_rows": {"served_latent_gap": "control_served_latent_gap_rows",
                 "latent_gap": "control_latent_gap_rows"},
    "fp8_kvb": {"latent_gap": "control_latent_gap_kvb"},
    "fp8_experts": {"served_latent_gap": "control_served_latent_gap_experts",
                    "routed_gap": "control_routed_gap"}}


def verdicts(r):
    """{control: [the numbers it replaced, ``correct`` as ``Run.judge``
    decides it]} with each control's readings in the program's place,
    the other numbers the program's own.  The run's own verdict is
    judged last and stands."""
    program = {k: v[0] for k, v in r.compared.items()}
    out = {}
    for name, reads in CONTROLS.items():
        got = {k: r.records[rec] for k, rec in reads.items()
               if rec in r.records}
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
    import drive_serve_mla
    r.control_routed = r.control_latent = r.control_rows = "fp8"
    progs = []
    drive_serve_mla.measure(
        r, prog_factory=lambda run: progs.append(
            drive_serve_mla.MlaProgram(run)) or progs[0])
    rec = r.records
    if "--fp8" in flags:
        rec["control_served_logit_gap"] = control_served_gap(r, progs[0])
    out = {"cell": cell, "seed": int(seed),
           "program": {k: v[0] for k, v in r.compared.items()},
           "limits": {k: v[1] for k, v in r.compared.items()},
           "latent_gap_chunk": rec["latent_gap_chunk"],
           "latent_gap_step": rec["latent_gap_step"],
           "controls": verdicts(r),
           "routed_flips": rec["routed_flips"],
           "mla_decode_lanes": rec["mla_decode_lanes"],
           "correct": r.correct,
           "setup_s": r.setup_s,
           "memory_peak_bytes": r.device.get("memory_peak_bytes"),
           "serve_tokens_per_s": r.metrics["serve_tokens_per_s"]}
    print(json.dumps(out), flush=True)
    os._exit(0)


if __name__ == "__main__":
    main()
