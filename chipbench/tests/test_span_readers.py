"""The ten readers of the program's spans, counters and program names
(PR 26), each on a hand-made run: the number, when it is None, what a
program from before the metric gets (the metric left out, the line still
accepted), and the rehearsal's stand-ins.  And the pattern files against
the operation texts recorded on the chip (``ops_pr26.json``): one
implementation per piece of work."""
import copy
import glob
import importlib.util
import json
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)

import emit  # noqa: E402
import trace_reduce  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
TRAIN, SERVE = "gpt3-1.3b-1chip.train-2k", "mistral-7b-d16.decode-long"

NEW = {
    "step_host_ms.train": TRAIN, "compile_ms_in_window.train": TRAIN,
    "compile_ms_in_window.serve": SERVE, "tick_host_ms.serve": SERVE,
    "tick_gap_ms.serve": SERVE, "queue_depth_mean.serve": SERVE,
    "kv_pages_claimed_share.serve": SERVE,
    "prefill_useful_token_share.serve": SERVE,
    "prefill_launches_per_chunk.serve": SERVE,
    "prefill_device_share.serve": SERVE}

# a 4 s window of the change's program: 100 ticks of 30 ms of which 22
# waiting for the device, 4 prefill chunks, nothing compiled or queued
REGISTRY = {
    "train.step_ms.sum": 140.0, "train.step_ms.count": 28,
    "jit.compile_ms.sum": 0.0, "jit.compile_ms.count": 0,
    "serving.tick.host_ms.sum": 800.0, "serving.tick.host_ms.count": 100,
    "serving.decode_ms.sum": 3000.0, "serving.decode_ms.count": 100,
    "serving.queue.request_ms": 0.0,
    "serving.kv.page_ticks_in_use": 60000,
    "serving.kv.page_ticks_reserved": 100000,
    "serving.prefill.tokens_useful": 384,
    "serving.prefill.tokens_computed": 4 * 32 * 64,
    "serving.prefill.launches": 1200,
    "serving.prefill_chunk_ms.sum": 1000.0,
    "serving.prefill_chunk_ms.count": 4}

# ticks at 0.00, 0.03 and 0.06 s of 20 ms each (gaps of 10 ms), then an
# eager program, then two ticks 4 ms apart
MODULES = {
    "jit_serving_tick_greedy(123)": [(0.00, 0.02), (0.03, 0.05),
                                     (0.06, 0.08), (0.20, 0.22),
                                     (0.224, 0.244)],
    "jit_matmul(7)": [(0.10, 0.13)],
    "jit_add(9)": [(0.14, 0.15)]}


def fake_run(registry=REGISTRY, modules=MODULES, rehearsal=False,
             cell=SERVE):
    said = []
    return SimpleNamespace(
        name=cell, rehearsal=rehearsal, bench=copy.deepcopy(BENCH),
        records={"registry": dict(registry), "seconds": 4.0},
        reduced={"modules": copy.deepcopy(modules), "busy_s": 0.2,
                 "window_s": 4.0},
        say=said.append, said=said)


def read(metric, run):
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + metric.replace(".", "_"),
        os.path.join(BENCH_DIR, "layer_metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def listed(run, metric):
    return metric in emit.cell_metrics(run.bench, run.name, True)


def test_the_ten_are_listed_with_their_cells_and_are_no_peak_shares():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert [m["name"] for m in BENCH["per_layer"][-10:]] == list(NEW)
    for name, cell in NEW.items():
        assert by_name[name]["workloads"] == [cell]
        assert not emit.is_peak_share(name)
        assert os.path.exists(os.path.join(BENCH_DIR, "layer_metrics",
                                           name + ".py"))


@pytest.mark.parametrize("metric,want", [
    ("step_host_ms.train", 5.0),
    ("compile_ms_in_window.train", 0.0),
    ("compile_ms_in_window.serve", 0.0),
    ("tick_host_ms.serve", 8.0),
    ("tick_gap_ms.serve", 10.0),        # gaps 10, 10, 4: the eager
                                        # program's neighbours do not count
    ("queue_depth_mean.serve", 0.0),
    ("kv_pages_claimed_share.serve", 60.0),
    ("prefill_useful_token_share.serve", 100 * 384 / 8192),
    ("prefill_launches_per_chunk.serve", 300.0),
    ("prefill_device_share.serve", 100 * 0.04 / 0.2)])
def test_reader_reads_its_number(metric, want):
    run = fake_run(cell=NEW[metric])
    got = read(metric, run)
    assert got == pytest.approx(want)
    assert isinstance(got, float)
    assert listed(run, metric)


def test_counters_that_moved_are_read_as_moved():
    reg = dict(REGISTRY, **{"jit.compile_ms.sum": 1234.5,
                            "serving.queue.request_ms": 2000.0})
    assert read("compile_ms_in_window.serve", fake_run(reg)) == 1234.5
    assert read("queue_depth_mean.serve", fake_run(reg)) == 0.5


@pytest.mark.parametrize("metric", [
    "prefill_useful_token_share.serve", "prefill_launches_per_chunk.serve",
    "prefill_device_share.serve"])
def test_prefill_readers_are_none_exactly_when_no_chunk_ran(metric):
    empty = dict(REGISTRY, **{
        "serving.prefill_chunk_ms.count": 0,
        "serving.prefill_chunk_ms.sum": 0.0,
        "serving.prefill.tokens_useful": 0,
        "serving.prefill.tokens_computed": 0,
        "serving.prefill.launches": 0})
    run = fake_run(empty)
    assert read(metric, run) is None
    assert read("prefill_wall_share.serve", run) is None
    # nothing to read in a program that has the source: the metric stays
    # listed and emit refuses the line that lacks it
    assert listed(run, metric)
    assert read(metric, fake_run()) is not None
    assert read("prefill_wall_share.serve", fake_run()) is not None


@pytest.mark.parametrize("metric", ["step_host_ms.train",
                                    "tick_host_ms.serve",
                                    "kv_pages_claimed_share.serve"])
def test_an_empty_window_reads_none_and_stays_listed(metric):
    run = fake_run({k: 0 for k in REGISTRY}, cell=NEW[metric])
    assert read(metric, run) is None
    assert listed(run, metric)


def test_tick_gap_is_none_without_two_ticks_in_a_row():
    lone = {"jit_serving_tick_greedy(1)": [(0.0, 0.02), (0.2, 0.22)],
            "jit_add(9)": [(0.1, 0.11)]}
    run = fake_run(modules=lone)
    assert read("tick_gap_ms.serve", run) is None
    assert listed(run, "tick_gap_ms.serve")


@pytest.mark.parametrize("metric", sorted(NEW))
def test_a_program_from_before_the_metric_leaves_it_out(metric):
    """The parent commit: its registry has ``serving.prefill_chunk_ms``
    and ``serving.decode_ms`` and none of the new names, its programs are
    all ``jit_fn``.  Each new reader returns None and does not raise, and
    the line that lacks the metric is one ``emit`` accepts."""
    old_registry = {"serving.prefill_chunk_ms.sum": 1000.0,
                    "serving.prefill_chunk_ms.count": 4,
                    "serving.decode_ms.sum": 3000.0,
                    "serving.decode_ms.count": 100}
    old_modules = {"jit_fn(1)": MODULES["jit_serving_tick_greedy(123)"],
                   "jit_matmul(7)": MODULES["jit_matmul(7)"]}
    cell = NEW[metric]
    run = fake_run(old_registry, old_modules, cell=cell)
    assert read(metric, run) is None
    assert not listed(run, metric)
    assert any(metric in msg and "left out" in msg for msg in run.said)
    want = emit.cell_metrics(run.bench, cell, True)
    line = {"correct": True, "attempted": 1, "failed": 0,
            "metrics": {k: {"value": 1.0, "unit": m["unit"]}
                        for k, m in want.items()},
            "device": {"platform": "tpu", "kind": "TPU v5 lite",
                       "count": 1, "memory_peak_bytes": 1 << 33,
                       "window_s": 4.0, "busy_s": 2.0}}
    emit.validate(line, run.bench, cell, True, 1)
    with pytest.raises(emit.LineRefused):       # the file's own list
        emit.validate(line, BENCH, cell, True, 1)


def test_rehearsal_stand_ins_need_no_xla_modules_line():
    run = fake_run(modules={"chipbench:step": []}, rehearsal=True)
    assert read("tick_gap_ms.serve", run) == pytest.approx(8.0)
    assert read("prefill_device_share.serve", run) == pytest.approx(25.0)
    assert listed(run, "tick_gap_ms.serve")


# ------------------------------------------- pattern files on chip texts

OPS = os.path.join(HERE, "ops_pr26.json")
WORKS = {TRAIN: ("train_attention", "adamw_update"),
         SERVE: ("paged_decode_attention",)}


def _implementations(work, names):
    ops = {n: {"seconds": 1.0, "count": 1, "meta": {}} for n in names}
    seen = []
    for path in sorted(glob.glob(os.path.join(BENCH_DIR, "kernels", work,
                                              "*.json"))):
        with open(path) as f:
            impl = json.load(f)
        if impl.get("rehearsal_only"):
            continue
        _, cnt, matched = trace_reduce.match_ops(ops, impl["patterns"])
        if cnt:
            seen.append((os.path.basename(path), sorted(matched)))
    return seen


@pytest.mark.parametrize("side", ["change", "parent"])
@pytest.mark.parametrize("cell", sorted(WORKS))
def test_one_implementation_per_work_on_the_recorded_texts(cell, side):
    """``metrics_lib.work_seconds`` sums over every pattern file that
    matches, so on the texts the chip gave for this PR's program, and on
    those it gave for the parent's, exactly one file may match each
    piece of work — and every Pallas call of the cell belongs to one."""
    with open(OPS) as f:
        names = json.load(f)[side][cell]
    pallas = {n for n in names if "tpu_custom_call" in n}
    claimed = set()
    for work in WORKS[cell]:
        seen = _implementations(work, names)
        assert len(seen) == 1, (work, [s[0] for s in seen])
        assert set(seen[0][1]) <= pallas
        assert not claimed & set(seen[0][1])
        claimed |= set(seen[0][1])
    assert claimed
    if cell == TRAIN:
        assert claimed == pallas
