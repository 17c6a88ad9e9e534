"""Build, validate and print the run's last line.

The driver reads the last line of standard output as one JSON object.
``validate`` holds it to the benchmark's contract and ``emit`` refuses to
print a line that fails: the run then exits non-zero with the reason on
standard error, which is cheaper than a check that is thrown away."""
from __future__ import annotations

import json
import math
import os
import sys


class LineRefused(Exception):
    """The last line would not meet the contract; the message says why."""


def cell_metrics(bench, cell, trace):
    """The metrics ``BENCHMARK.json`` lists for ``cell`` in this kind of
    run: {name: entry}.  Untraced runs report the end-to-end metrics,
    traced runs the per-layer ones.  A metric with a ``workloads`` key
    belongs to those cells only; without one, to every cell that reports
    the end-to-end metric it moves (every cell, for an end-to-end one)."""
    e2e = {}
    for m in bench["end_to_end"]:
        if "workloads" not in m or cell in m["workloads"]:
            e2e[m["name"]] = m
    if not trace:
        return e2e
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out[m["name"]] = m
        elif m["moves"] in e2e:
            out[m["name"]] = m
    return out


def is_peak_share(name):
    """A kernel's share of its roofline, or a whole step's of the peak
    (``mfu`` as a part of the name of its own)."""
    parts = name.replace(".", "_").split("_")
    return "roofline" in parts or "mfu" in parts


def _finite(x):
    return isinstance(x, (int, float)) and not isinstance(x, bool) \
        and math.isfinite(x)


def validate(line, bench, cell, trace, chips):
    """Raise ``LineRefused`` unless ``line`` is what the driver accepts
    for ``cell`` in this kind of run.  ``chips`` is the cell's count,
    which the device has to report; None (a rehearsal on virtual
    devices) leaves that one check out."""
    need = ["correct", "attempted", "failed", "metrics", "device"]
    for k in need:
        if k not in line:
            raise LineRefused(f"key {k!r} is missing")
    if not isinstance(line["correct"], bool):
        raise LineRefused("'correct' is not true or false")
    for k in ("attempted", "failed"):
        v = line[k]
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            raise LineRefused(f"{k!r} is not a count: {v!r}")
    if line["failed"] > line["attempted"]:
        raise LineRefused("more failed than attempted")

    want = cell_metrics(bench, cell, trace)
    got = line["metrics"]
    if not isinstance(got, dict):
        raise LineRefused("'metrics' is not an object")
    for name, entry in want.items():
        if name not in got:
            raise LineRefused(
                f"metric {name!r} is listed for cell {cell!r} in a "
                f"{'traced' if trace else 'timed'} run and is missing")
        m = got[name]
        if not isinstance(m, dict) or "value" not in m or "unit" not in m:
            raise LineRefused(f"metric {name!r} lacks value or unit")
        if not _finite(m["value"]):
            raise LineRefused(
                f"metric {name!r} is not a finite number: {m['value']!r}")
        if m["unit"] != entry["unit"]:
            raise LineRefused(
                f"metric {name!r} has unit {m['unit']!r}, BENCHMARK.json "
                f"says {entry['unit']!r}")
        if is_peak_share(name) and not 0 < m["value"] <= 105:
            raise LineRefused(
                f"{name!r} = {m['value']} is a share of a peak and lies "
                "outside (0, 105]")
    extra = set(got) - set(want)
    if extra:
        raise LineRefused(f"metrics not listed for this run: {sorted(extra)}")

    dev = line["device"]
    if not isinstance(dev, dict):
        raise LineRefused("'device' is not an object")
    for k in ("platform", "kind", "count", "memory_peak_bytes"):
        if k not in dev:
            raise LineRefused(f"device lacks {k!r}")
    if chips is not None and dev["count"] != chips:
        raise LineRefused(
            f"device count {dev['count']} is not the cell's {chips}")
    if not _finite(dev["memory_peak_bytes"]) or dev["memory_peak_bytes"] <= 0:
        raise LineRefused("device.memory_peak_bytes is not above 0")
    if trace:
        for k in ("window_s", "busy_s"):
            if not _finite(dev.get(k)):
                raise LineRefused(f"device.{k} is missing or not finite")
        if not 0 < dev["busy_s"] <= dev["window_s"]:
            raise LineRefused(
                f"device.busy_s {dev['busy_s']} is not in (0, window_s "
                f"{dev['window_s']}]: busy is the union of device "
                "operations per device, then the mean over the devices, "
                "never a sum")
        bd = line.get("breakdown")
        if bd is not None:
            for k in ("device_ops", "idle_gaps"):
                rows = bd.get(k)
                if not isinstance(rows, list) or len(rows) > 10:
                    raise LineRefused(f"breakdown.{k} is not a list of "
                                      "at most 10 entries")
                for row in rows:
                    if len(row) != 2 or not isinstance(row[0], str) \
                            or not _finite(row[1]):
                        raise LineRefused(
                            f"breakdown.{k} entry {row!r} is not "
                            "[name, seconds]")
    try:
        text = json.dumps(line, allow_nan=False)
    except ValueError as e:
        raise LineRefused(f"not strict JSON: {e}") from e
    if "\n" in text:
        raise LineRefused("the line holds a line break")
    return text


def emit(line, bench, cell, trace, chips, *, redact=False, out=None):
    """Validate, then print ``line`` as the last line of standard output
    and flush.  ``redact`` (CPU rehearsal) validates the real line and
    prints one that names no number: a CPU's numbers never stand under a
    device metric's name."""
    out = out or sys.stdout
    text = validate(line, bench, cell, trace, chips)
    if redact:
        text = json.dumps({
            "rehearsal": True, "validated": True, "cell": cell,
            "trace": bool(trace), "correct": line["correct"],
            "metrics": sorted(line["metrics"]),
            "device": {"platform": line["device"]["platform"],
                       "count": line["device"]["count"]}})
    sys.stderr.flush()
    out.write(text + "\n")
    out.flush()
    if out is sys.stdout:
        # the line is the last thing on standard output, whatever a
        # profiler, a worker thread or an exit hook prints afterwards:
        # from here on descriptor 1 is standard error
        try:
            os.dup2(sys.stderr.fileno(), sys.stdout.fileno())
        except (OSError, ValueError, AttributeError):
            pass                    # a captured stream in a test
    return text
