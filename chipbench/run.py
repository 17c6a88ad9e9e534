"""The benchmark's one command.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the cell's chips.  It finds the cell in
``BENCHMARK.json``, reads ``cells/<cell>.json`` (the settings a
deployment would set), the configuration's file and the traffic mix,
builds the program with weights from ``--seed``, warms that cell's
shapes, measures for ``--seconds``, decides ``correct`` against the
plain reference, and prints one JSON line last.  ``emit.py`` validates
that line before it is printed; a line that would be refused makes the
run exit non-zero with the reason.

Without a TPU the run fails.  ``--rehearse`` (never given by the driver)
runs the same code on CPUs at the tiny sizes of ``tests/tiny/`` and
prints a last line that names no number."""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)

import emit  # noqa: E402


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def merge(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


class Run:
    """What one run knows: the cell's data, the clock, the trace, and the
    line it builds."""

    def __init__(self, args):
        self.bench = load_json(ROOT, "BENCHMARK.json")
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if args.workload not in cells:
            raise SystemExit(f"no cell {args.workload!r} in BENCHMARK.json;"
                             f" it has {sorted(cells)}")
        self.entry = cells[args.workload]
        self.name = args.workload
        self.chips = self.entry["chips"]
        self.seed = int(args.seed)
        self.trace = bool(args.trace)
        self.rehearsal = bool(args.rehearse)
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config = load_json(ROOT, configs[self.entry["config"]]["file"])
        self.cell = load_json(HERE, "cells", self.name + ".json")
        self.mix = load_json(HERE, "traffic", self.entry["traffic"] + ".json")
        if self.rehearsal:
            tiny = load_json(HERE, "tests", "tiny", self.name + ".json")
            self.config = merge(self.config, tiny.get("config", {}))
            self.cell = merge(self.cell, tiny.get("cell", {}))
            self.mix = merge(self.mix, tiny.get("mix", {}))
        seconds = float(args.seconds)
        if self.trace:
            # a traced run measures a shorter window: traces are large
            # and reading one is part of the run's 360 s
            seconds = min(seconds, self.cell["trace_seconds"])
        self.seconds = seconds
        c = self.config
        self.model_cfg = {k: c[src] for k, src in c["fields"].items()}
        self.model_cfg.update(self.cell.get("model_fields", {}))
        self.optimizer = {
            "learning_rate": c.get("learning_rate"),
            "beta1": c.get("adam_beta1"), "beta2": c.get("adam_beta2"),
            "epsilon": c.get("adam_epsilon"),
            "weight_decay": c.get("weight_decay")}
        self.metrics, self.records = {}, {}
        self.attempted = self.failed = 0
        self.compared, self.correct = {}, False
        self.setup_s = None
        self.peak = None
        self.reduced = None
        self._trace_dir = None

    # -- talking --------------------------------------------------------
    def say(self, msg):
        print(f"[chipbench {self.name}] {msg}", file=sys.stderr, flush=True)

    # -- device ---------------------------------------------------------
    def find_device(self):
        import jax
        devs = jax.devices()
        d = devs[0]
        if d.platform != "tpu" and not self.rehearsal:
            raise SystemExit(
                f"the benchmark needs a TPU and found {d.platform!r}; "
                "--rehearse runs the tiny presets on CPUs and prints no "
                "number")
        if len(devs) < self.chips:
            raise SystemExit(f"cell {self.name} needs {self.chips} chips, "
                             f"JAX sees {len(devs)}")
        self.devices = devs[:self.chips]
        self.device = {"platform": d.platform, "kind": d.device_kind,
                       "count": len(devs)}
        if not self.rehearsal:
            peaks = load_json(HERE, "peaks.json")["devices"]
            if d.device_kind not in peaks:
                raise SystemExit(
                    f"device_kind {d.device_kind!r} is not in "
                    "chipbench/peaks.json: a device without published "
                    "peaks is an error, not a default")
            self.peaks = peaks[d.device_kind]
        else:
            self.peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                          "hbm_bytes": 1 << 34}
        from paddle_tpu.core.op_cache import ensure_compile_cache
        self.say(f"device {self.device}; compile cache "
                 f"{ensure_compile_cache()}")

    def memory_peak(self):
        peak = 0
        for d in self.devices:
            stats = d.memory_stats()
            if stats:
                peak = max(peak, stats.get("peak_bytes_in_use", 0))
        if not peak and self.rehearsal:
            peak = 1                # a CPU reports none
        self.device["memory_peak_bytes"] = peak

    # -- clock and trace ------------------------------------------------
    def end_of_setup(self):
        from paddle_tpu.utils import cache_stats
        t2 = cache_stats()["tier2"]
        self.setup_s = time.perf_counter() - T_PROCESS
        self.say(f"set-up {self.setup_s:.2f}s; cache.tier2 hits="
                 f"{t2['hits']} misses={t2['misses']}")

    def span(self, name):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        """The measured window; traced when the run is."""
        if not self.trace:
            yield
            return
        import jax
        import trace_reduce
        self._trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        jax.profiler.start_trace(self._trace_dir)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                yield
        finally:
            jax.profiler.stop_trace()

    def read_trace(self):
        import trace_reduce
        t = time.perf_counter()
        try:
            path = trace_reduce.find_xplane(self._trace_dir)
            size = os.path.getsize(path)
            keep = os.environ.get("CHIPBENCH_KEEP_TRACE")
            if keep:
                os.makedirs(keep, exist_ok=True)
                shutil.copy(path, os.path.join(
                    keep, f"{self.name}.{self.seed}.xplane.pb"))
            self.reduced = trace_reduce.reduce_trace(path, self.rehearsal)
            dump = os.environ.get("CHIPBENCH_DUMP")
            if dump:                # a builder's look at the event names
                os.makedirs(dump, exist_ok=True)
                with open(os.path.join(
                        dump, f"{self.name}.{self.seed}.ops.json"), "w") as f:
                    json.dump({
                        "lines": trace_reduce.line_names(path),
                        "modules": {k: len(v) for k, v in
                                    self.reduced["modules"].items()},
                        "idle_gaps": self.reduced["idle_gaps"][:40],
                        "ops": sorted(
                            ([k, v["seconds"], v["count"], v["meta"]]
                             for k, v in self.reduced["ops"].items()),
                            key=lambda r: -r[1])[:150]}, f, indent=1)
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
        self.say(f"trace: {size / 1e6:.1f} MB read in "
                 f"{time.perf_counter() - t:.1f}s; window "
                 f"{self.reduced['window_s']:.3f}s, busy "
                 f"{self.reduced['busy_s']:.3f}s over "
                 f"{len(self.reduced['devices'])} device(s)")

    # -- correctness ----------------------------------------------------
    def judge(self, numbers):
        import compare
        self.compared, self.correct, left_out = compare.judge(
            numbers, self.cell["limits"])
        for name in left_out:
            self.say(f"read, not compared: {name} = {numbers[name]:.6g}")

    # -- the line -------------------------------------------------------
    def layer_metrics(self):
        """Every per-layer metric of this cell, each from its own reader
        ``layer_metrics/<name>.py``; a reader with nothing to read leaves
        its metric out, and ``emit`` then refuses the line by name."""
        out = {}
        for name, entry in emit.cell_metrics(self.bench, self.name,
                                             True).items():
            spec = importlib.util.spec_from_file_location(
                "layer_metric_" + name.replace(".", "_"),
                os.path.join(HERE, "layer_metrics", name + ".py"))
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            value = mod.read(self)
            if value is None:
                self.say(f"per-layer metric {name}: nothing to read")
                continue
            out[name] = {"value": value, "unit": entry["unit"]}
        return out

    def line(self):
        units = {m["name"]: m["unit"] for m in self.bench["end_to_end"]}
        line = {"correct": bool(self.correct),
                "attempted": int(self.attempted),
                "failed": int(self.failed)}
        if self.trace:
            self.read_trace()
            line["metrics"] = self.layer_metrics()
            self.device["window_s"] = self.reduced["window_s"]
            self.device["busy_s"] = self.reduced["busy_s"]
        else:
            self.metrics["setup_s"] = self.setup_s
            # a driver measures what its kind of window can; the cell
            # reports what BENCHMARK.json lists for it
            listed = emit.cell_metrics(self.bench, self.name, False)
            line["metrics"] = {k: {"value": v, "unit": units[k]}
                               for k, v in self.metrics.items()
                               if k in listed}
        line["device"] = self.device
        if self.trace:
            import trace_reduce
            line["breakdown"] = {
                "device_ops": trace_reduce.top_ops(self.reduced["ops"]),
                "idle_gaps": [[k, v] for k, v in
                              self.reduced["idle_gaps"][:10]]}
        line["compared"] = self.compared
        return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True, type=int)
    ap.add_argument("--seconds", required=True, type=float)
    ap.add_argument("--trace", required=True, type=int, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the tiny presets of tests/tiny; "
                         "prints no number")
    args = ap.parse_args(argv)
    run = Run(args)
    try:
        import paddle_tpu  # noqa: F401
    except ImportError as e:
        raise SystemExit(f"the program under test is not here: {e}")
    run.find_device()
    driver = importlib.import_module("drive_" + run.cell["driver"])
    driver.measure(run)
    line = run.line()
    for name, (value, limit) in run.compared.items():
        run.say(f"compared {name}: {value:.6g} (limit {limit:.6g})")
    run.say(f"correct: {run.correct}")
    try:
        emit.emit(line, run.bench, run.name, run.trace,
                  None if run.rehearsal else run.chips,
                  redact=run.rehearsal)
    except emit.LineRefused as e:
        run.say(f"the last line was refused and is not printed: {e}")
        return 1
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # nothing may print after the line: leave without running the
    # interpreter's exit hooks of libraries that log on the way out
    os._exit(code)
