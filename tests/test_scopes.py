"""Device time by program scope (ISSUE 36): the tape's backward re-enters
its forward's scope, every compiled hot-path program publishes an
instruction -> scope table, nothing of a table is built before
``scopes.tables()`` is asked, and nothing published keeps an engine
alive."""
import contextlib
import gc
import os
import re
import sys
import weakref

import numpy as np
import pytest
import jax

import paddle_tpu as paddle
from paddle_tpu.core import autograd
from paddle_tpu.framework.train_step import CompiledTrainStep
from paddle_tpu.models import GPTForCausalLM, gpt_config
from paddle_tpu.observability import scopes, tracing

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "chipbench"))
from layer_metrics import scope_lib                      # noqa: E402

#: the scopes the models and the step name their work with
#: (docs/OBSERVABILITY.md, "Names on the device")
LAYER_SCOPES = {"embed", "attn", "mamba", "mlp", "head", "loss"}


@pytest.fixture
def fresh_compiles():
    """No persistent compile cache round the test: JAX leaves metadata
    out of the cache key, so an executable another tree wrote comes back
    with that tree's scopes."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()


def _gpt():
    return GPTForCausalLM(gpt_config(
        "gpt2-124m", num_layers=2, hidden_size=32, num_heads=2,
        vocab_size=128, max_seq_len=16)), 128


def _llama():
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64, max_seq_len=16)), 128


def _granite_hybrid():
    from paddle_tpu.models.granite_hybrid import (
        TINY_GRANITE_HYBRID, GraniteHybridConfig, GraniteHybridForCausalLM)
    cfg = dict(TINY_GRANITE_HYBRID, num_layers=4,
               layer_types=["mamba", "attention", "mamba", "mamba"])
    return GraniteHybridForCausalLM(GraniteHybridConfig(**cfg)), 256


def _compiled_step(build):
    paddle.seed(0)
    model, vocab = build()
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())

    def forward(x, y):
        with paddle.amp.auto_cast(enable=True, level="O2",
                                  dtype="bfloat16"):
            return model(x, labels=y)[1]

    step = CompiledTrainStep(forward, opt, network=model)
    ids = paddle.to_tensor(np.random.default_rng(0).integers(
        0, vocab, (2, 16)).astype("int32"))
    for _ in range(2):
        step(ids, ids)
    assert step.compiled, step.fallback_reason
    return step


@pytest.mark.parametrize("build", [_gpt, _llama, _granite_hybrid],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_every_product_of_a_train_step_has_scope_and_direction(
        build, fresh_compiles):
    step = _compiled_step(build)
    (program,) = step._programs.values()
    products = {}
    for line in program.as_text().splitlines():
        got = scopes.split_instruction(line)
        if got is None or got[2] not in ("dot", "convolution"):
            continue
        found = re.search(r'op_name="([^"]*)"', got[3])
        assert found, f"a product without metadata: {line[:200]}"
        scope, direction = scopes.scope_of(found.group(1))
        assert scope.split("/")[0] in LAYER_SCOPES, found.group(1)
        products.setdefault(scope.split("/")[0], set()).add(direction)
    assert {"attn", "mlp", "head"} <= set(products)
    if build is _granite_hybrid:
        assert "mamba" in products
    for scope, directions in products.items():
        assert directions == {"fwd", "bwd"}, (scope, directions)
    # the step's own lines are named too
    table = scopes.tables()["jit_train_step"]
    named = {e["scope"].split("/")[0] for e in table.values()}
    assert {"optimizer", "grad_accum"} <= named
    assert any(e["dir"] == "bwd" and e["kind"] == "fusion"
               for e in table.values())


# ------------------------------------------------------ the table builder

FIXTURE = os.path.join(HERE, "data", "scopes_two_programs.hlo.txt")
NAMES = {"attn", "mlp", "head", "sample", "bwd"}


@pytest.fixture(scope="module")
def fixture_tables():
    with open(FIXTURE) as f:
        texts = f.read().split("// ----\n")
    assert len(texts) == 2
    return {"jit_tick": scopes.build_table(texts[0], NAMES),
            "jit_prefill": scopes.build_table(texts[1], NAMES)}


def test_a_fusion_takes_its_products_scope(fixture_tables):
    e = fixture_tables["jit_tick"]["fusion.1"]
    # three of its four named instructions say attn; the product says mlp
    assert (e["scope"], e["dir"], e["kind"]) == ("mlp", "fwd", "fusion")
    assert e["mixed"] is True
    assert e["type"] == "bf16[8,64]"


def test_a_fusion_without_a_product_takes_the_majority(fixture_tables):
    e = fixture_tables["jit_tick"]["fusion.2"]
    assert (e["scope"], e["dir"]) == ("attn", "bwd")
    assert "mixed" not in e
    mixed = fixture_tables["jit_tick"]["fusion.3"]
    assert mixed["scope"] == "head" and mixed["mixed"] is True


def test_a_while_is_a_container_and_its_body_is_in_the_table(
        fixture_tables):
    t = fixture_tables["jit_tick"]
    assert t["while.1"]["kind"] == "while"
    assert set(t["while.1"]["body"]) == {"fusion.7", "compare.9"}
    assert t["fusion.7"]["scope"] == "attn"
    # a loop the compiler made: named by what it holds
    assert t["while.1"]["scope"] == "attn"
    # plumbing and fused computations are not the device's instructions
    assert "param.1" not in t and "dot.5" not in t and "tuple.3" not in t


def test_programs_sharing_a_name_are_told_apart_by_type_or_ambiguous(
        fixture_tables):
    index = {}
    for table in fixture_tables.values():
        for name, entry in table.items():
            index.setdefault(name, []).append(entry)
    # fusion.1 is mlp [8,64] in the tick and head [2,128] in the member
    assert scope_lib.resolve(index, "fusion.1", "bf16[8,64]")[:2] == \
        ("mlp", "fwd")
    assert scope_lib.resolve(index, "fusion.1", "bf16[2,128]")[:2] == \
        ("head", "fwd")
    # fusion.2 has one type and two scopes
    assert scope_lib.resolve(index, "fusion.2", "f32[8,64]")[0] == \
        scope_lib.AMBIGUOUS
    # copy.4: the same (empty) scope in both
    assert scope_lib.resolve(index, "copy.4", "bf16[64,8]")[0] == \
        scope_lib.UNSCOPED
    assert scope_lib.resolve(index, "fusion.99", "f32[]")[0] == \
        scope_lib.NO_TABLE
    assert scope_lib.resolve(index, "fusion.1", "f32[7]")[0] == \
        scope_lib.NO_TABLE


def test_an_event_text_splits_like_an_instruction():
    got = scopes.split_instruction(
        "%fusion.12 = (bf16[8,64]{1,0:T(8,128)(2,1)}, /*index=1*/f32[8]{0}) "
        "fusion(bf16[8,64]{1,0} %p), kind=kOutput, calls=%fc")
    assert got[:3] == ("fusion.12", "(bf16[8,64],f32[8])", "fusion")
    assert scopes.split_instruction("fusion.12") is None
    assert scopes.scope_of(
        "jit(f)/loss/bwd/transpose(loss)/jvp(jit(_where))/select_n",
        {"loss", "bwd"}) == ("loss", "bwd")
    assert scopes.scope_of("jit(head)/while/body/add", {"head"}) == \
        ("", "fwd")


# ------------------------------------------- an engine publishes its family

def test_an_engine_publishes_lazily_and_is_not_kept_alive(fresh_compiles):
    from paddle_tpu.serving import Engine, ServingConfig
    scopes.clear()
    paddle.seed(0)
    model = GPTForCausalLM(gpt_config(
        "gpt2-124m", num_layers=2, hidden_size=64, num_heads=2,
        vocab_size=256, max_seq_len=64))
    model.eval()
    eng = Engine(model, ServingConfig(num_slots=2, max_queue=4)).start()
    builds = scopes.builds
    try:
        rng = np.random.default_rng(0)
        futs = [eng.submit(rng.integers(0, 256, (n,)).astype("int32"),
                           max_new_tokens=4) for n in (5, 9)]
        for f in futs:
            f.result(timeout=300)
        buckets = eng._tick.prefill_buckets()
    finally:
        eng.shutdown()
    # published, and nothing printed or parsed yet
    assert scopes.builds == builds
    held = dict(scopes._published)
    want = {"jit_serving_tick_greedy"} | {
        f"jit_serving_prefill_r{rows}" for rows in buckets}
    assert want <= set(held)
    assert all(isinstance(v, list) for v in held.values())
    ref = weakref.ref(eng)
    del eng, futs, held
    gc.collect()
    assert ref() is None, "a published program keeps the engine alive"
    tabs = scopes.tables()
    assert scopes.builds == builds + len(tabs)
    tick = tabs["jit_serving_tick_greedy"]
    named = {e["scope"].split("/")[0] for e in tick.values()}
    assert {"attn", "mlp", "head", "sample"} <= named
    assert all(e["dir"] == "fwd" for e in tick.values())
    # a second call builds nothing
    scopes.tables()
    assert scopes.builds == builds + len(tabs)


def test_a_program_published_again_replaces_its_table(fresh_compiles):
    scopes.clear()

    def train_step(x):
        with tracing.scope("mlp"):
            return x @ x

    first = jax.jit(train_step).lower(np.ones((4, 4), np.float32)).compile()
    scopes.publish(first)
    assert any(e["scope"] == "mlp"
               for e in scopes.tables()["jit_train_step"].values())

    def train_step(x):                                  # noqa: F811
        with tracing.scope("attn"):
            return x @ x

    scopes.publish(jax.jit(train_step).lower(
        np.ones((4, 4), np.float32)).compile())
    table = scopes.tables()["jit_train_step"]
    assert {e["scope"] for e in table.values() if e["scope"]} == {"attn"}


# ------------------------------------------------ eager backward, unchanged

def _eager_grads():
    paddle.seed(0)
    model, vocab = _gpt()
    ids = paddle.to_tensor(np.random.default_rng(1).integers(
        0, vocab, (2, 16)).astype("int32"))
    _, loss = model(ids, labels=ids)
    loss.backward()
    return [np.asarray(p.grad._data_) for p in model.parameters()]


def test_eager_backward_is_the_same_numbers_with_the_scope_re_entered(
        monkeypatch):
    entered = []
    real = tracing.backward_of

    @contextlib.contextmanager
    def spy(path):
        entered.append(path)
        with real(path):
            yield

    monkeypatch.setattr(autograd._tracing, "backward_of", spy)
    with_scopes = _eager_grads()
    assert ("attn",) in entered and ("mlp",) in entered \
        and ("loss",) in entered
    assert tracing.scope_path() == ()
    monkeypatch.setattr(autograd._tracing, "backward_of",
                        lambda path: contextlib.nullcontext())
    without = _eager_grads()
    assert len(with_scopes) == len(without) > 0
    for a, b in zip(with_scopes, without):
        np.testing.assert_array_equal(a, b)


def test_the_scope_path_is_per_thread_and_restored():
    import threading
    seen = {}
    with tracing.scope("attn"):
        with tracing.scope("mla_absorb"):
            assert tracing.scope_path() == ("attn", "mla_absorb")
            t = threading.Thread(
                target=lambda: seen.update(other=tracing.scope_path()))
            t.start()
            t.join()
        # re-entering a forward's path under an open prefix adds the rest
        with tracing.backward_of(("attn", "mla_decode")):
            assert tracing.scope_path() == ("attn", "mla_decode", "bwd")
    assert seen["other"] == ()
    assert tracing.scope_path() == ()
    assert {"attn", "mla_absorb", "bwd"} <= tracing.scope_names
