"""CPU rehearsal of every cell through the real harness at the tiny
presets of ``tests/tiny``: untraced and traced, on one device and on
four virtual ones.  The run has to exit 0, ``emit`` has to have accepted
the line, and the line has to be the last line of standard output."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]


def rehearse(cell, trace, devices, seed=2**31 + 77):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PADDLE_TPU_PALLAS_INTERPRET="1")
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, BENCH["command"][1]),
         "--workload", cell, "--seed", str(seed), "--seconds", "3",
         "--trace", str(trace), "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses(cell, trace, devices):
    chips = {w["name"]: w["chips"] for w in BENCH["workloads"]}[cell]
    if devices < chips:
        pytest.skip("the cell needs more devices")
    p = rehearse(cell, trace, devices)
    assert p.returncode == 0, p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["validated"] is True and last["correct"] is True
    assert last["trace"] is bool(trace)
    # a CPU's numbers never stand under a device metric's name
    assert all(isinstance(m, str) for m in last["metrics"])


def test_no_tpu_no_result():
    """Without --rehearse a CPU is refused: no result line at all."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, BENCH["command"][1]),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
