"""Compile a cell's program at its real sizes for a v5e that is described
and not attached, and print the compiler's memory analysis per device.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse_compile.py \
        --workload gpt3-1.3b-1chip.train-2k [--set n_layers=12]

No chip is used and nothing runs: what the chip's compiler would refuse
(a program that does not fit 16 GB, a kernel Mosaic rejects) it refuses
here, at no chip time.  Its output fixes the depth of
``gpt3-1.3b-1chip`` (PERF.md section 4).  A compile that passes is not
a chip run and is never reported as one.

Training cells: the program's first call (eager + discovery) runs here
on the CPU on one short row, then a fresh jit of the SAME step body is
lowered at the cell's batch against the described device.  Serving
cells hold no program larger than the train step at the same widths;
their bytes are reckoned in PERF.md from the sizes (weights + pages)
and proved by the chip run's ``memory_peak_bytes``."""
from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["TPU_ACCELERATOR_TYPE"] = "v5litepod-4"
os.environ["TPU_WORKER_HOSTNAMES"] = "localhost"

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--set", action="append", default=[],
                    help="override one key of the configuration's file, "
                         "e.g. n_layers=12")
    a = ap.parse_args()

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import run as harness
    import drive_train
    import traffic

    args = argparse.Namespace(workload=a.workload, seed=1, seconds=1,
                              trace=0, rehearse=False)
    run = harness.Run(args)
    for kv in a.set:
        k, v = kv.split("=")
        run.config[k] = type(run.config[k])(v)
    run.model_cfg = {k: run.config[src]
                     for k, src in run.config["fields"].items()}
    if run.cell["driver"] != "train":
        raise SystemExit("only training cells are compiled here; see the "
                         "module's docstring")
    jax.config.update("jax_enable_compilation_cache", False)

    prog = drive_train.TrainProgram(run)
    vocab = run.model_cfg["vocab_size"]
    row = next(traffic.train_batches({"batch": 1, "seq": 128}, 1, vocab))
    for _ in range(2):              # eager + discovery, then bind
        prog.step(*prog.feed(row))
    cs = prog.cstep
    if not cs.compiled:
        raise SystemExit(f"not compiled: {cs.fallback_reason}")

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    from paddle_tpu.pallas import flash_attention as fa
    fa._on_tpu = lambda: True       # the gates decide as on the chip
    os.environ.pop("PADDLE_TPU_PALLAS_INTERPRET", None)

    mix = run.mix
    x, y = prog.feed(np.zeros((mix["batch"], mix["seq"] + 1), np.int32))
    gathered = cs._gather_args(x, y)

    def shape(v):
        if hasattr(v, "shape") and hasattr(v, "dtype"):
            return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one)
        return v
    structs = jax.tree.map(shape, gathered)
    lowered = cs._build_jit(True, gathered).lower(*structs)
    text = lowered.as_text()
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.output_size_in_bytes \
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    print(f"cell {a.workload} {a.set}: layers "
          f"{run.model_cfg['num_layers']}, batch {mix['batch']} x "
          f"{mix['seq']}; {text.count('tpu_custom_call')} Pallas calls")
    print(f"  arguments {m.argument_size_in_bytes / 2**30:.2f} GiB, "
          f"outputs {m.output_size_in_bytes / 2**30:.2f} GiB, aliased "
          f"{m.alias_size_in_bytes / 2**30:.2f} GiB, temporaries "
          f"{m.temp_size_in_bytes / 2**30:.2f} GiB, program "
          f"{m.generated_code_size_in_bytes / 2**20:.1f} MiB")
    print(f"  held while the step runs: {total / 2**30:.2f} GiB of "
          f"15.75 GiB on device {topo.devices[0].device_kind}")


if __name__ == "__main__":
    main()
