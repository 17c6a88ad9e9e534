"""``layer_metrics/scope_lib.py`` and the eight by-scope readers (PR 36)
on a hand-made reduced trace and hand-made tables: the seconds by scope,
a container left out where its body has events of its own, the refusal
when the leaf time does not add up to the busy time, the roofline
readers' need of both directions, and what a program from before
``observability.scopes`` gets (the metric left out, the line still
accepted)."""
import copy
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
sys.path.insert(1, ROOT)

import emit  # noqa: E402
from layer_metrics import scope_lib  # noqa: E402
from paddle_tpu.observability import scopes  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
TRAIN, MLA = "gpt3-1.3b-1chip.train-2k", \
    "sarvam-105b-ep8-d5.longdoc-reason-56"
EIGHT = {
    "scope_named_share.train": TRAIN, "mlp_roofline.train": TRAIN,
    "attn_dense_roofline.train": TRAIN,
    "head_loss_device_share.train": TRAIN,
    "scope_named_share.serve": MLA, "head_device_share.serve": MLA,
    "mla_absorb_device_share.serve": MLA,
    "mla_up_project_device_share.serve": MLA}


def entry(scope, direction="fwd", typ="bf16[8,64]", kind="fusion", **more):
    return dict({"scope": scope, "dir": direction, "type": typ,
                 "kind": kind}, **more)


TABLES = {"jit_train_step": {
    "fusion.1": entry("mlp"), "fusion.2": entry("mlp", "bwd"),
    "fusion.3": entry("attn"), "fusion.4": entry("attn", "bwd"),
    "flash_fwd.5": entry("attn", kind="custom-call"),
    "fusion.6": entry("head"), "fusion.7": entry("loss", "bwd"),
    "fusion.8": entry("embed", "bwd"), "fusion.9": entry("optimizer"),
    "copy.10": entry("", kind="copy"),
    "fusion.11": entry("attn_latent/mla_absorb"),
    "fusion.12": entry("attn_latent/mla_up_project"),
    "fusion.13": entry("sample"),
    "while.14": entry("attn", kind="while", body=["fusion.15"]),
    "fusion.15": entry("attn"),
    "while.16": entry("mlp", kind="while", body=["fusion.17"]),
    "fusion.17": entry("mlp")}}


def event(name, typ="bf16[8,64]{1,0:T(8,128)(2,1)}", kind="fusion"):
    return f"%{name} = {typ} {kind}(bf16[8,64]{{1,0}} %p.1), kind=kLoop"


# 1.0 s of busy time: every instruction above but fusion.17, whose
# loop the device reports as one event
SECONDS = {
    "fusion.1": 0.10, "fusion.2": 0.20, "fusion.3": 0.05,
    "fusion.4": 0.10, "fusion.6": 0.08, "fusion.7": 0.04,
    "fusion.8": 0.02, "fusion.9": 0.07, "copy.10": 0.03,
    "fusion.11": 0.01, "fusion.12": 0.06, "fusion.13": 0.02,
    "while.14": 0.05, "fusion.15": 0.05, "while.16": 0.04}
OPS = {event(n): {"seconds": s, "count": 10, "meta": {}}
       for n, s in SECONDS.items()}
OPS['%flash_fwd.5 = bf16[8,64]{1,0} custom-call(bf16[8,64]{1,0} %q), '
    'custom_call_target="tpu_custom_call"'] = \
    {"seconds": 0.08, "count": 9, "meta": {}}
OPS["%convert.99 = f32[4]{0} convert(bf16[4]{0} %x)"] = \
    {"seconds": 0.05, "count": 3, "meta": {}}           # an eager op's


def fake_run(cell=TRAIN, ops=OPS, busy=1.0, rehearsal=False):
    said = []
    return SimpleNamespace(
        name=cell, seed=1, rehearsal=rehearsal,
        bench=copy.deepcopy(BENCH), say=said.append, said=said,
        records={"steps": 10}, reduced={"ops": copy.deepcopy(ops),
                                        "busy_s": busy},
        model_cfg={"hidden_size": 64, "intermediate_size": 256,
                   "num_heads": 2, "num_layers": 2,
                   "tie_word_embeddings": True},
        mix={"batch": 2, "seq": 16}, cell={"compute_dtype": "bfloat16"},
        peaks={"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9})


def read(metric, run):
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + metric.replace(".", "_"),
        os.path.join(BENCH_DIR, "layer_metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


@pytest.fixture
def tables(monkeypatch):
    monkeypatch.setattr(scopes, "tables", lambda: TABLES)


def test_the_eight_are_listed_for_their_cells():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert [m["name"] for m in BENCH["per_layer"][-8:]] == list(EIGHT)
    for name, cell in EIGHT.items():
        m = by_name[name]
        assert cell in m["workloads"] and m["layer"] == "models"
        assert m["source"] == "device_trace" and m["unit"] == "%"
        assert emit.is_peak_share(name) == name.split(".")[0].endswith(
            "_roofline")
        assert os.path.exists(os.path.join(BENCH_DIR, "layer_metrics",
                                           name + ".py"))


def test_seconds_by_scope_and_the_container_rule(tables):
    run = fake_run()
    got = scope_lib.by_scope(run, "scope_named_share.train")
    by = {k: v[0] for k, v in got["by"].items()}
    assert by[("mlp", "fwd")] == pytest.approx(0.10 + 0.04)
    assert by[("mlp", "bwd")] == pytest.approx(0.20)
    # while.14's body has an event of its own: the loop's is left out;
    # while.16's has none: the loop is the leaf
    assert by[("attn", "fwd")] == pytest.approx(0.05 + 0.08 + 0.05)
    assert got["containers"] == pytest.approx(0.05)
    assert by[(scope_lib.UNSCOPED, "fwd")] == pytest.approx(0.03)
    assert by[(scope_lib.NO_TABLE, "fwd")] == pytest.approx(0.05)
    assert got["leaf"] == pytest.approx(1.0)
    # the table is said, longest first, with each scope's instructions
    table = [s for s in run.said if s.startswith("  ")]
    assert table[0].split()[:2] == ["mlp", "bwd"] and "fusion.2" in table[0]
    # the join is made once a run
    assert scope_lib.by_scope(run, "mlp_roofline.train") is got


@pytest.mark.parametrize("metric,want", [
    ("scope_named_share.train", 100 * (1.0 - 0.03 - 0.05)),
    ("head_loss_device_share.train", 100 * (0.08 + 0.04 + 0.02)),
    ("scope_named_share.serve", 100 * (1.0 - 0.03 - 0.05)),
    ("head_device_share.serve", 100 * (0.08 + 0.02)),
    ("mla_absorb_device_share.serve", 1.0),
    ("mla_up_project_device_share.serve", 6.0)])
def test_share_readers(tables, metric, want):
    assert read(metric, fake_run(EIGHT[metric])) == pytest.approx(want)


def test_roofline_readers(tables):
    run = fake_run()
    tokens, d, ff = 2 * 16, 64, 256
    mlp = 12.0 * d * ff * tokens * 2 * 10 / 1e9
    assert read("mlp_roofline.train", run) == \
        pytest.approx(100 * mlp / 0.34)
    dense = 24.0 * d * d * tokens * 2 * 10 / 1e9
    # the attention kernel's own 0.08 s is taken off the time under attn
    assert read("attn_dense_roofline.train", run) == \
        pytest.approx(100 * dense / (0.18 + 0.10 - 0.08))


def test_a_roofline_needs_both_directions(tables):
    ops = {k: v for k, v in OPS.items() if "%fusion.2 " not in k}
    run = fake_run(ops=ops, busy=0.8)
    assert read("mlp_roofline.train", run) is None
    assert any("one direction is not attributed" in s for s in run.said)
    assert read("attn_dense_roofline.train", run) is not None


def test_nothing_is_read_when_the_leaf_time_does_not_add_up(tables):
    for busy in (0.97, 1.03):
        run = fake_run(busy=busy)
        for metric in EIGHT:
            assert read(metric, run) is None
        assert any("nothing is read" in s for s in run.said)
        # said once, refused by name: the metric stays listed
        assert len(run.bench["per_layer"]) == len(BENCH["per_layer"])
    assert read("scope_named_share.train", fake_run(busy=1.015)) \
        is not None


def test_a_rehearsal_event_is_named_by_the_instruction_alone(tables):
    ops = {n: {"seconds": s, "count": 1, "meta": {}}
           for n, s in SECONDS.items()}
    run = fake_run(ops=ops, busy=0.5, rehearsal=True)
    assert read("scope_named_share.train", run) is not None
    # a CPU faster than the made-up peak does not trip the validator
    run.peaks["bf16_flops_per_s"] = 1e7
    assert read("mlp_roofline.train", run) == 100.0


def test_a_program_without_scopes_leaves_each_metric_out(monkeypatch):
    import paddle_tpu.observability as obs
    monkeypatch.delattr(obs, "scopes")
    monkeypatch.setitem(sys.modules, "paddle_tpu.observability.scopes",
                        None)
    for cell in (TRAIN, MLA):
        run = fake_run(cell)
        mine = [m for m, c in EIGHT.items() if c == cell]
        for metric in mine:
            assert read(metric, run) is None
        listed = {m["name"] for m in run.bench["per_layer"]}
        assert not listed & set(mine)
        assert len(listed) == len(BENCH["per_layer"]) - len(mine)
        assert sum("left out" in s for s in run.said) == len(mine)
        # the line without them is what emit is shown, and accepts
        assert not set(mine) & set(emit.cell_metrics(run.bench, cell, True))
