"""Multi-tenant LoRA (ISSUE 16): the training lane — wrap, freeze,
merge/unmerge, adapter-only save/load, compiled-train-step parity —
and the serving lane — batched multi-adapter decode through one
engine with per-slot bit-equality vs dedicated single-adapter
engines, LRU hot-load/eviction under pool pressure, compiled-tick
zero-fallback guarantees, prefix-tree adapter isolation, typed
registry errors, telemetry, and router adapter affinity."""
import os
import time
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import Model, nn
from paddle_tpu.framework.checkpoint_manager import (read_manifest,
                                                     verify_checkpoint)
from paddle_tpu.models import GPTForCausalLM, gpt_config
from paddle_tpu.nn.lora import LoRALinear
from paddle_tpu.serving import (AdapterConfigError, Engine,
                                ReplicaConfig, ReplicaServer,
                                RouterConfig, SamplingParams,
                                ServingConfig, ServingRouter,
                                TickFallbackWarning,
                                UnknownAdapterError, serving_stats)
from paddle_tpu.serving.paged_kv import PrefixTree
from paddle_tpu.utils import flags as _flags


# ------------------------------------------------------------------
# training lane
# ------------------------------------------------------------------

class _MLP(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc_in = nn.Linear(8, 16)
        self.act = nn.ReLU()
        self.fc_out = nn.Linear(16, 4)

    def forward(self, x):
        return self.fc_out(self.act(self.fc_in(x)))


def _mlp(seed=0):
    paddle.seed(seed)
    return _MLP()


def _batches(steps=6, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.standard_normal((4, 8)).astype("float32"),
             rng.standard_normal((4, 4)).astype("float32"))
            for _ in range(steps)]


def test_attach_and_grad_mask():
    """attach_lora wraps the named projections; after
    mark_only_lora_trainable a training run moves ONLY the A/B
    factors — base weight and bias stay bitwise untouched."""
    net = _mlp()
    names = nn.attach_lora(net, rank=4)
    assert names == ["fc_in", "fc_out"]
    assert isinstance(net.fc_in, LoRALinear)
    nn.mark_only_lora_trainable(net)
    trainable = sorted(n for n, p in net.named_parameters()
                       if p.trainable)
    assert trainable == ["fc_in.lora_A", "fc_in.lora_B",
                         "fc_out.lora_A", "fc_out.lora_B"]
    frozen = {n: p.numpy().copy() for n, p in net.named_parameters()
              if not p.trainable}
    before = {n: p.numpy().copy()
              for n, p in net.named_parameters() if p.trainable}
    opt = paddle.optimizer.AdamW(
        0.05, parameters=[p for p in net.parameters() if p.trainable])
    for x, y in _batches():
        loss = ((net(paddle.to_tensor(x)) - paddle.to_tensor(y))
                ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
    for n, p in net.named_parameters():
        if p.trainable:
            assert not np.array_equal(p.numpy(), before[n]), \
                f"{n} never trained"
        else:
            np.testing.assert_array_equal(p.numpy(), frozen[n],
                                          err_msg=n)


def test_merge_unmerge_bitwise():
    """merge() bakes W + A@B*scale into the base weight with the SAME
    expression the unmerged forward computes, so outputs are bitwise
    identical; unmerge() restores the exact pre-merge weight."""
    net = _mlp()
    nn.attach_lora(net, rank=4, alpha=8)
    rng = np.random.default_rng(1)
    for l in nn.lora_layers(net).values():
        l.lora_B.set_value(rng.standard_normal(
            l.lora_B.shape).astype(np.float32) * 0.1)
    x = paddle.to_tensor(
        rng.standard_normal((3, 8)).astype("float32"))
    y0 = net(x).numpy()
    w0 = net.fc_in.weight.numpy().copy()
    for l in nn.lora_layers(net).values():
        l.merge()
        assert l.merged
    np.testing.assert_array_equal(net(x).numpy(), y0)
    assert not np.array_equal(net.fc_in.weight.numpy(), w0)
    for l in nn.lora_layers(net).values():
        l.unmerge()
    np.testing.assert_array_equal(net.fc_in.weight.numpy(), w0)
    np.testing.assert_array_equal(net(x).numpy(), y0)


def test_save_load_adapter_roundtrip(tmp_path):
    """save_adapter writes ONLY the A/B factors (crc-manifested like
    CheckpointManager); load_adapter restores them byte-equal into a
    freshly wrapped model."""
    net = _mlp()
    nn.attach_lora(net, rank=4, alpha=16)
    rng = np.random.default_rng(2)
    for l in nn.lora_layers(net).values():
        l.lora_A.set_value(rng.standard_normal(
            l.lora_A.shape).astype(np.float32))
        l.lora_B.set_value(rng.standard_normal(
            l.lora_B.shape).astype(np.float32))
    d = str(tmp_path / "adapter")
    os.makedirs(d)
    nn.save_adapter(net, d)
    assert verify_checkpoint(d)
    meta = read_manifest(d)["meta"]
    assert meta["format"] == "lora_adapter"
    assert meta["layers"]["fc_in"]["rank"] == 4

    other = _mlp(seed=7)                      # different base weights
    nn.attach_lora(other, rank=4)
    nn.load_adapter(other, d)
    for name, l in nn.lora_layers(net).items():
        l2 = nn.lora_layers(other)[name]
        np.testing.assert_array_equal(l.lora_A.numpy(),
                                      l2.lora_A.numpy())
        np.testing.assert_array_equal(l.lora_B.numpy(),
                                      l2.lora_B.numpy())
        assert l2.alpha == 16 and l2.scaling == l.scaling

    # rank mismatch at load is a typed construction-time error
    third = _mlp()
    nn.attach_lora(third, rank=2)
    with pytest.raises(ValueError, match="rank"):
        nn.load_adapter(third, d)


def test_lora_construction_errors():
    with pytest.raises(TypeError, match="Linear"):
        LoRALinear(nn.LayerNorm(8))
    with pytest.raises(ValueError, match="rank"):
        LoRALinear(nn.Linear(4, 4), rank=0)
    with pytest.raises(ValueError, match="no Linear sublayers"):
        nn.attach_lora(_mlp(), targets=("does_not_exist",))
    with pytest.raises(ValueError, match="no LoRA"):
        nn.mark_only_lora_trainable(_mlp())


def _fit_lora(compiled, steps=6):
    paddle.set_flags({"FLAGS_compiled_train_step": compiled})
    net = _mlp()
    nn.attach_lora(net, rank=4)
    nn.mark_only_lora_trainable(net)
    opt = paddle.optimizer.AdamW(
        0.05, parameters=[p for p in net.parameters() if p.trainable])
    model = Model(net)
    model.prepare(optimizer=opt,
                  loss=lambda o, y: ((o - y) ** 2).mean())
    losses = []
    for x, y in _batches(steps):
        losses.append(np.float32(model.train_batch(
            paddle.to_tensor(x), paddle.to_tensor(y))[0]))
    base = {n: p.numpy().copy() for n, p in net.named_parameters()
            if not p.trainable}
    lora = {n: p.numpy().copy() for n, p in net.named_parameters()
            if p.trainable}
    return losses, base, lora, model


def test_compiled_train_step_frozen_base_matches_eager():
    """A LoRA-wrapped model rides the compiled train step unchanged:
    loss trajectory ulp-close to eager, the frozen base identical on
    both lanes, and only the adapters move."""
    saved = paddle.get_flags("FLAGS_compiled_train_step")
    try:
        le, base_e, lora_e, _ = _fit_lora(False)
        lc, base_c, lora_c, mc = _fit_lora(True)
    finally:
        paddle.set_flags(saved)
    cs = mc._compiled_step
    assert cs and cs is not False and cs.compiled, \
        cs and cs.fallback_reason
    for a, b in zip(le, lc):
        assert abs(a - b) <= 2e-6 * max(abs(a), 1e-12), (a, b)
    for n in base_e:
        np.testing.assert_array_equal(base_e[n], base_c[n], err_msg=n)
    ref = {n: p.numpy() for n, p in _mlp().named_parameters()}
    for n in base_e:                      # base never moved at all
        np.testing.assert_array_equal(base_e[n], ref[n], err_msg=n)
    for n in lora_e:
        np.testing.assert_allclose(lora_e[n], lora_c[n], rtol=1e-5,
                                   atol=1e-6, err_msg=n)


# ------------------------------------------------------------------
# serving lane
# ------------------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPTForCausalLM(gpt_config(
        "gpt2-124m", num_layers=2, hidden_size=64, num_heads=2,
        vocab_size=256, max_seq_len=64))
    m.eval()
    return m


@pytest.fixture(scope="module")
def specs(model):
    """Four heterogeneous adapter state dicts (different seeds) built
    on a throwaway wrapped copy that shares the served model's
    qualified projection names."""
    paddle.seed(0)
    tmp = GPTForCausalLM(gpt_config(
        "gpt2-124m", num_layers=2, hidden_size=64, num_heads=2,
        vocab_size=256, max_seq_len=64))
    tmp.eval()
    nn.attach_lora(tmp, rank=4)
    out = {}
    for i in range(4):
        rng = np.random.default_rng(100 + i)
        for l in nn.lora_layers(tmp).values():
            l.lora_A.set_value(rng.standard_normal(
                l.lora_A.shape).astype(np.float32) * 0.5)
            l.lora_B.set_value(rng.standard_normal(
                l.lora_B.shape).astype(np.float32) * 0.5)
        out[f"t{i}"] = nn.adapter_spec(tmp)
    return out


def _prompts(lens, seed=0, vocab=256):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (n,)).astype("int32") for n in lens]


def test_multi_adapter_bit_equal_vs_single_adapter_engines(model,
                                                           specs):
    """Heterogeneous adapters decoding in the SAME batched step: each
    per-slot output is bitwise identical to a dedicated single-adapter
    engine serving that adapter alone, and a base request riding the
    same program stays pure base — with zero compiled-tick fallbacks
    and no fallback warning."""
    prompts = _prompts([6, 9, 5], seed=3)
    ids = ["t0", "t1", "t2"]

    refs = {}
    for aid, p in zip(ids, prompts):
        eng = Engine(model, ServingConfig(
            num_slots=2, max_queue=4, max_adapters=1,
            adapter_rank_pool=4, adapters={aid: specs[aid]})).start()
        try:
            refs[aid] = eng.submit(
                p, max_new_tokens=5,
                adapter_id=aid).result(timeout=300).output_ids
        finally:
            eng.shutdown()
    base_eng = Engine(model, ServingConfig(
        num_slots=2, max_queue=4)).start()
    try:
        base_ref = base_eng.submit(
            prompts[0], max_new_tokens=5).result(timeout=300).output_ids
    finally:
        base_eng.shutdown()

    eng = Engine(model, ServingConfig(
        num_slots=2, max_queue=8, max_adapters=3, adapter_rank_pool=4,
        adapters=specs)).start()
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("error", TickFallbackWarning)
            futs = [eng.submit(p, max_new_tokens=5, adapter_id=aid)
                    for aid, p in zip(ids, prompts)]
            futs.append(eng.submit(prompts[0], max_new_tokens=5))
            outs = [f.result(timeout=300) for f in futs]
        snap = eng.stats()
    finally:
        eng.shutdown()
    for aid, o in zip(ids, outs):
        np.testing.assert_array_equal(o.output_ids, refs[aid],
                                      err_msg=aid)
    np.testing.assert_array_equal(outs[3].output_ids, base_ref)
    assert snap["tick_fallbacks"] == 0
    assert snap["tick_compiled_hits"] > 0
    assert snap["requests_routed_adapter"] == 3


def test_lru_evict_reload_zero_drops(model, specs):
    """Four tenants through a TWO-slot adapter pool: hot-loads and LRU
    evictions happen mid-run, eviction never touches an in-flight
    request, and every future completes (zero drops).  Re-submitting
    an evicted tenant reloads it bit-identically."""
    prompts = _prompts([5, 7, 6, 8], seed=4)
    eng = Engine(model, ServingConfig(
        num_slots=2, max_queue=16, max_adapters=2, adapter_rank_pool=4,
        adapters=specs)).start()
    try:
        futs = [eng.submit(p, max_new_tokens=4, adapter_id=f"t{i}")
                for i, p in enumerate(prompts)]
        outs = [f.result(timeout=300) for f in futs]
        first = [o.output_ids for o in outs]
        snap = eng.stats()
        assert snap["adapter_evictions"] >= 1
        assert snap["adapters_loaded"] >= 4
        # evicted tenants reload bit-identically
        futs = [eng.submit(p, max_new_tokens=4, adapter_id=f"t{i}")
                for i, p in enumerate(prompts)]
        again = [f.result(timeout=300).output_ids for f in futs]
        snap2 = eng.stats()
    finally:
        eng.shutdown()
    for a, b in zip(first, again):
        np.testing.assert_array_equal(a, b)
    assert all(o.finish_reason == "length" for o in outs)
    assert snap2["requests_completed"] == 8      # zero drops


def test_uncompiled_lane_matches_tick(model, specs):
    """FLAGS_compiled_tick off: the per-call scheduler applies the
    same per-slot delta — outputs bit-equal to the compiled lane."""
    prompts = _prompts([6, 8], seed=5)
    saved = _flags._FLAGS["FLAGS_compiled_tick"]

    def _run():
        eng = Engine(model, ServingConfig(
            num_slots=2, max_queue=4, max_adapters=2,
            adapter_rank_pool=4,
            adapters={k: specs[k] for k in ("t0", "t1")})).start()
        try:
            futs = [eng.submit(p, max_new_tokens=4,
                               adapter_id=aid)
                    for aid, p in zip(("t0", "t1"), prompts)]
            return [f.result(timeout=300).output_ids for f in futs]
        finally:
            eng.shutdown()

    try:
        _flags._FLAGS["FLAGS_compiled_tick"] = True
        compiled = _run()
        _flags._FLAGS["FLAGS_compiled_tick"] = False
        eager = _run()
    finally:
        _flags._FLAGS["FLAGS_compiled_tick"] = saved
    for a, b in zip(compiled, eager):
        np.testing.assert_array_equal(a, b)


def test_prefix_tree_adapter_isolation(model, specs):
    """The SAME prompt under two different adapters must never share
    KV through the prefix tree: scope-keyed entries keep each tenant's
    cache private, and outputs equal each adapter's no-cache
    reference."""
    prompt = _prompts([12], seed=6)[0]
    refs = {}
    for aid in ("t0", "t1"):
        eng = Engine(model, ServingConfig(
            num_slots=2, max_queue=4, max_adapters=1,
            adapter_rank_pool=4, page_size=4,
            enable_prefix_cache=False,
            adapters={aid: specs[aid]})).start()
        try:
            refs[aid] = eng.submit(
                prompt, max_new_tokens=4,
                adapter_id=aid).result(timeout=300).output_ids
        finally:
            eng.shutdown()
    eng = Engine(model, ServingConfig(
        num_slots=2, max_queue=8, max_adapters=2, adapter_rank_pool=4,
        page_size=4, enable_prefix_cache=True,
        adapters={k: specs[k] for k in ("t0", "t1")})).start()
    try:
        # serve t0 twice so its prefix is cached and REUSED, then t1
        # with the identical prompt: a cross-tenant hit would replay
        # t0's adapter KV into t1's decode
        eng.submit(prompt, max_new_tokens=4,
                   adapter_id="t0").result(timeout=300)
        hits0 = eng.stats()["prefix_cache_hits"]
        o0 = eng.submit(prompt, max_new_tokens=4,
                        adapter_id="t0").result(timeout=300)
        assert eng.stats()["prefix_cache_hits"] > hits0
        hits1 = eng.stats()["prefix_cache_hits"]
        o1 = eng.submit(prompt, max_new_tokens=4,
                        adapter_id="t1").result(timeout=300)
        assert eng.stats()["prefix_cache_hits"] == hits1
    finally:
        eng.shutdown()
    np.testing.assert_array_equal(o0.output_ids, refs["t0"])
    np.testing.assert_array_equal(o1.output_ids, refs["t1"])


def test_prefix_tree_scope_api():
    class _FakeCache:
        def make_shared(self, slot, i):
            return 100 + i

    tree = PrefixTree(page_size=4)
    prompt = np.arange(9).astype(np.int32)
    held = []
    assert tree.insert(prompt, _FakeCache(), 0, held, scope="a") == 2
    nodes_a, pages_a = tree.match(prompt, scope="a")
    nodes_b, pages_b = tree.match(prompt, scope="b")
    nodes_0, pages_0 = tree.match(prompt)
    assert pages_a == [100, 101]
    assert not pages_b and not pages_0
    tree.release(nodes_a)
    tree.release(held)


def test_unknown_adapter_fails_future_not_engine(model, specs):
    eng = Engine(model, ServingConfig(
        num_slots=2, max_queue=4, max_adapters=1, adapter_rank_pool=4,
        adapters={"t0": specs["t0"]})).start()
    try:
        p = _prompts([5], seed=8)[0]
        fut = eng.submit(p, max_new_tokens=3, adapter_id="nope")
        with pytest.raises(UnknownAdapterError, match="t0"):
            fut.result(timeout=30)
        # the scheduler survived: both a base and a known-adapter
        # request still complete
        o = eng.submit(p, max_new_tokens=3).result(timeout=300)
        assert o.output_ids.size == 3
        o = eng.submit(p, max_new_tokens=3,
                       adapter_id="t0").result(timeout=300)
        assert o.output_ids.size == 3
    finally:
        eng.shutdown()


def test_adapter_config_errors(model, specs):
    # rank above the preallocated pool rank
    with pytest.raises(AdapterConfigError, match="rank"):
        Engine(model, ServingConfig(
            num_slots=2, max_adapters=1, adapter_rank_pool=2,
            adapters={"t0": specs["t0"]}))
    # width mismatch vs the wrapped projection
    bad = {k: dict(v) for k, v in specs["t0"].items()}
    name = next(iter(bad))
    bad[name] = dict(bad[name], A=np.zeros((3, 4), np.float32))
    with pytest.raises(AdapterConfigError, match=name):
        Engine(model, ServingConfig(
            num_slots=2, max_adapters=1, adapter_rank_pool=4,
            adapters={"t0": bad}))
    # unknown projection name
    with pytest.raises(AdapterConfigError, match="does not have"):
        Engine(model, ServingConfig(
            num_slots=2, max_adapters=1, adapter_rank_pool=4,
            adapters={"t0": {"not.a.layer": specs["t0"][name]}}))
    # ServingConfig-level validation
    with pytest.raises(ValueError, match="max_adapters"):
        ServingConfig(num_slots=2, max_adapters=-1).validate()
    with pytest.raises(ValueError, match="adapters"):
        ServingConfig(num_slots=2,
                      adapters={"t0": specs["t0"]}).validate()


def test_adapter_telemetry_keys_and_exposition(model, specs):
    from tools.check_telemetry import (check_lora_exposition,
                                       parse_prometheus)
    eng = Engine(model, ServingConfig(
        num_slots=2, max_queue=4, max_adapters=1, adapter_rank_pool=4,
        adapters={k: specs[k] for k in ("t0", "t1")})).start()
    try:
        p = _prompts([5], seed=9)[0]
        for aid in ("t0", "t1"):
            eng.submit(p, max_new_tokens=3,
                       adapter_id=aid).result(timeout=300)
        snap = eng.stats()
    finally:
        eng.shutdown()
    assert snap["adapters_loaded"] >= 2
    assert snap["adapter_evictions"] >= 1
    assert snap["requests_routed_adapter"] == 2
    assert snap["adapter_load_ms_avg"] >= 0
    from paddle_tpu import observability as obs
    series, typed, errors = parse_prometheus(obs.render_prometheus())
    assert not errors
    assert check_lora_exposition(series, typed) == []
    assert ('adapter', 't0') in [
        (k, v) for labels, _ in
        series["serving_adapter_requests_routed_adapter"]
        for k, v in labels.items()]


def test_pallas_lora_delta_interpret_matches_xla():
    """The FLAGS_pallas_lora fused gather-matmul lane, run through the
    Pallas interpreter, is bitwise identical to the default XLA gather
    path; pool slot 0 is an exact identity."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.serving import adapters as ad
    rng = np.random.default_rng(0)
    ns, d_in, d_out, P, r = 4, 32, 48, 3, 8
    x = Tensor(rng.standard_normal((ns, 1, d_in)).astype(np.float32))
    y = Tensor(rng.standard_normal((ns, 1, d_out)).astype(np.float32))
    a = Tensor(rng.standard_normal((P, d_in, r)).astype(np.float32))
    b = Tensor(rng.standard_normal((P, r, d_out)).astype(np.float32))
    s = Tensor(np.array([0.0, 1.0, 0.5], np.float32))
    idx = Tensor(np.array([0, 1, 2, 1], np.int32))
    from paddle_tpu.distributed import mesh as mesh_mod
    saved = _flags._FLAGS.get("FLAGS_pallas_lora", False)
    os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
    try:
        _flags._FLAGS["FLAGS_pallas_lora"] = False
        ref = ad.lora_delta(y, x, a, b, s, idx).numpy()
        _flags._FLAGS["FLAGS_pallas_lora"] = True
        # the kernel carries no shard_map: under a multi-device mesh
        # (an earlier test may have left one active) it yields to XLA
        with mesh_mod.suspended():
            assert ad._use_pallas()
            out = ad.lora_delta(y, x, a, b, s, idx).numpy()
            zero = ad.lora_delta(y, x, a, b, s, Tensor(
                np.zeros(ns, np.int32))).numpy()
    finally:
        _flags._FLAGS["FLAGS_pallas_lora"] = saved
        del os.environ["PADDLE_TPU_PALLAS_INTERPRET"]
    np.testing.assert_array_equal(out, ref)
    np.testing.assert_array_equal(zero, y.numpy())


def test_router_adapter_affinity(model, specs):
    """Fleet lane: replicas gossip their hot-adapter set; once a
    tenant is hot on one replica, requests for it stick there even
    when session keys would scatter them across the ring."""
    from paddle_tpu.distributed.store import TCPStore
    scfg = ServingConfig(num_slots=2, max_queue=8, max_adapters=2,
                         adapter_rank_pool=4,
                         adapters={k: specs[k] for k in ("t0", "t1")})
    master = TCPStore(is_master=True)
    rcfg = ReplicaConfig(heartbeat_interval_s=0.15,
                         heartbeat_ttl_s=1.2).validate()
    reps, router = {}, None
    try:
        for name in ("rep-a", "rep-b"):
            reps[name] = ReplicaServer(
                name, model, TCPStore("127.0.0.1", master.port),
                scfg, rcfg)
        router = ServingRouter(
            TCPStore("127.0.0.1", master.port),
            RouterConfig(heartbeat_ttl_s=1.2,
                         poll_interval_s=0.1)).start()
        deadline = time.monotonic() + 30
        while len(router.ring.members) < 2:
            assert time.monotonic() < deadline
            time.sleep(0.05)
        p = _prompts([6], seed=10)[0]
        first = router.submit(p, max_new_tokens=3, adapter_id="t0",
                              session_id="s0").result(timeout=300)
        hot = first.decoded_by
        # wait for the hot replica's gossip to advertise the adapter
        deadline = time.monotonic() + 10
        while True:
            with router._lock:
                view = router._replicas.get(hot)
            if view is not None and "t0" in view.adapters:
                break
            assert time.monotonic() < deadline, "gossip never updated"
            time.sleep(0.1)
        for i in range(3):                 # scattered session keys
            out = router.submit(
                p, max_new_tokens=3, adapter_id="t0",
                session_id=f"scatter-{i}").result(timeout=300)
            assert out.decoded_by == hot
    finally:
        if router is not None:
            router.close()
        for rep in reps.values():
            rep.close()
        master.close()
