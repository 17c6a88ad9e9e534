"""The reduction from trace to numbers, on one small trace recorded on
the chip (``record_small_trace.py``: five executions of one program, the
host asleep 20 ms between them)."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import trace_reduce  # noqa: E402

TRACE = os.path.join(HERE, "small_tpu.xplane.pb")


def test_union():
    assert trace_reduce.union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    assert trace_reduce.union([]) == []


def test_short_name():
    n = trace_reduce.short_name(
        '%fn.5 = bf16[2]{0} custom-call(s32[2]{0} %a), '
        'custom_call_target="tpu_custom_call"')
    assert n == "fn.5 (pallas)"


@pytest.fixture(scope="module")
def reduced():
    if not os.path.exists(TRACE):
        pytest.skip("no recorded trace beside the test")
    return trace_reduce.reduce_trace(TRACE)


def test_busy_is_a_union_inside_the_window(reduced):
    r = reduced
    assert len(r["devices"]) == 1
    assert 0 < r["busy_s"] < r["window_s"]
    # five naps of 20 ms lie inside the window, idle on the device
    assert r["window_s"] - r["busy_s"] > 5 * 0.02
    assert r["busy_s"] == pytest.approx(4 * 9.02e-5, rel=0.01)
    assert sum(rec["seconds"] for rec in r["ops"].values()) \
        >= r["busy_s"] * 0.999


def test_five_executions_and_named_gaps(reduced):
    r = reduced
    main = max(r["modules"].values(), key=len)
    # the device's tracer starts a moment after the host's: the first of
    # the five executions is not in this recording
    assert len(main) == 4
    gaps = dict(r["idle_gaps"])
    assert abs(sum(gaps.values()) - (r["window_s"] - r["busy_s"])) < 1e-6
    assert gaps.get("chipbench:nap", 0) > 5 * 0.015
