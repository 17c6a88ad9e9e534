"""The last line's validator: what the driver would refuse, ``emit``
refuses first.  Run by the builder (pytest chipbench/tests), not tier-1."""
import copy
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)

import emit  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]


def good_line(cell, trace, chips=1):
    want = emit.cell_metrics(BENCH, cell, trace)
    line = {"correct": True, "attempted": 10, "failed": 0,
            "metrics": {k: {"value": 12.5, "unit": m["unit"]}
                        for k, m in want.items()},
            "device": {"platform": "tpu", "kind": "TPU v5 lite",
                       "count": chips, "memory_peak_bytes": 1 << 33}}
    if trace:
        line["device"].update(window_s=4.0, busy_s=3.0)
        line["breakdown"] = {"device_ops": [["fusion.1 (fusion)", 1.0]],
                             "idle_gaps": [["chipbench:feed", 0.5]]}
    line["compared"] = {"x": [0.1, 0.2]}
    return line


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_line_passes(cell, trace):
    text = emit.validate(good_line(cell, trace), BENCH, cell, trace, 1)
    assert json.loads(text)["correct"] is True
    assert list(json.loads(text))[-1] == "compared"


def _break(line, what):
    line = copy.deepcopy(line)
    name = sorted(line["metrics"])[0]
    if what == "summed_busy":       # four devices' busy time added up
        line["device"]["busy_s"] = 4 * 3.0
    elif what == "zero_busy":
        line["device"]["busy_s"] = 0.0
    elif what == "missing_metric":
        del line["metrics"][name]
    elif what == "nan":
        line["metrics"][name]["value"] = float("nan")
    elif what == "null":
        line["metrics"][name]["value"] = None
    elif what == "wrong_unit":
        line["metrics"][name]["unit"] = "furlongs"
    elif what == "unlisted_metric":
        line["metrics"]["made_up"] = {"value": 1.0, "unit": "s"}
    elif what == "no_device_count":
        del line["device"]["count"]
    elif what == "wrong_count":
        line["device"]["count"] = 4
    elif what == "long_breakdown":
        line["breakdown"]["device_ops"] = [["op", 0.1]] * 11
    elif what == "failed_over_attempted":
        line["failed"] = 11
    return line


@pytest.mark.parametrize("what", [
    "summed_busy", "zero_busy", "missing_metric", "nan", "null",
    "wrong_unit", "unlisted_metric", "no_device_count", "wrong_count",
    "long_breakdown", "failed_over_attempted"])
@pytest.mark.parametrize("cell", CELLS)
def test_each_fault_is_refused(cell, what):
    with pytest.raises(emit.LineRefused):
        emit.validate(_break(good_line(cell, 1), what), BENCH, cell, 1, 1)


def test_a_share_of_a_peak_over_105_is_refused():
    cell = CELLS[0]
    line = good_line(cell, 1)
    roof = [k for k in line["metrics"] if "roofline" in k or "mfu" in k]
    assert roof
    line["metrics"][roof[0]]["value"] = 106.0
    with pytest.raises(emit.LineRefused):
        emit.validate(line, BENCH, cell, 1, 1)


def test_nothing_prints_after_the_line():
    """A print after ``emit`` (a profiler, a worker thread, an exit
    hook) lands on standard error: the JSON stays the last line."""
    cell = CELLS[0]
    code = (
        "import sys, json; sys.path.insert(0, %r)\n"
        "import emit\n"
        "bench = json.load(open(%r))\n"
        "line = json.loads(%r)\n"
        "emit.emit(line, bench, %r, 0, 1)\n"
        "print('a straggler')\n"
        "sys.stdout.flush()\n"
    ) % (BENCH_DIR, os.path.join(ROOT, "BENCHMARK.json"),
         json.dumps(good_line(cell, 0)), cell)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=60)
    assert p.returncode == 0, p.stderr
    last = p.stdout.strip().splitlines()[-1]
    assert json.loads(last)["correct"] is True
    assert "a straggler" in p.stderr and "straggler" not in p.stdout
