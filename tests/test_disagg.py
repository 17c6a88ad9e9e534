"""Prefill/decode disaggregation with live KV-page migration (ISSUE 14):
page export/adopt round-trips (fp32 + int8, attention bit-equal), the
rpc raw-bytes fast path, engine-level handoff/resume/fallback, prefix
-tree copy semantics across replicas, the role-aware router, and the
drain-time migration + role-flip rejoin protocol.  Thread-mode replicas
keep these fast."""
import pickle
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import rpc
from paddle_tpu.distributed.rpc import rpc as rpc_mod
from paddle_tpu.distributed.store import TCPStore
from paddle_tpu.models import GPTForCausalLM, gpt_config
from paddle_tpu.serving import (Engine, PagedKVCache, PageMigrationError,
                                ReplicaConfig, ReplicaServer,
                                RouterConfig, SamplingParams,
                                ServingConfig, ServingRouter,
                                serving_stats)
from paddle_tpu.serving import migration


def _np(t):
    return np.asarray(t._data_)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPTForCausalLM(gpt_config(
        "gpt2-124m", num_layers=2, hidden_size=64, num_heads=4,
        vocab_size=256, max_seq_len=64))
    m.eval()
    return m


def _prompts(lens, seed=0, vocab=256):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (n,)).astype("int32") for n in lens]


def _ref_greedy(model, prompt, max_new):
    ids = model.generate(paddle.to_tensor(prompt[None, :]),
                         max_new_tokens=max_new, temperature=0.0)
    return _np(ids)[0, prompt.size:]


def _fill_cache(cache, rng, dtype):
    """Random recognizable contents in every pool page (and scale)."""
    import jax.numpy as jnp
    from paddle_tpu.core.tensor import Tensor
    for lay in cache.layers:
        shp = lay["k_pool"]._data_.shape
        if dtype == "int8":
            lay["k_pool"] = Tensor(jnp.asarray(
                rng.integers(-127, 127, shp), jnp.int8))
            lay["v_pool"] = Tensor(jnp.asarray(
                rng.integers(-127, 127, shp), jnp.int8))
            sshp = lay["k_scale"]._data_.shape
            lay["k_scale"] = Tensor(jnp.asarray(
                rng.random(sshp), jnp.float32))
            lay["v_scale"] = Tensor(jnp.asarray(
                rng.random(sshp), jnp.float32))
        else:
            lay["k_pool"] = Tensor(jnp.asarray(
                rng.normal(size=shp), jnp.float32))
            lay["v_pool"] = Tensor(jnp.asarray(
                rng.normal(size=shp), jnp.float32))


# ------------------------------------------------------------------
# page serialization round trip
# ------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_page_payload_roundtrip_bitwise(dtype):
    """export_slot -> raw frames -> unpack -> adopt_pages lands every
    page (and per-page scale) bit-exact in the receiving pool."""
    rng = np.random.default_rng(0)
    a = PagedKVCache(2, 2, 64, 2, 4, page_size=16, dtype=dtype)
    slot = a.allocate(4)
    a.ensure_capacity(slot, 47)           # 3 pages assigned
    a.set_offset(slot, 37)
    _fill_cache(a, rng, dtype)
    header, blobs = migration.export_slot(a, slot)
    assert header["num_pages"] == 3 and header["offset"] == 37
    # the frames are Blob-wrapped: pickling them must refuse
    with pytest.raises(TypeError, match="raw-bytes fast path"):
        pickle.dumps(blobs[0])
    pages = migration.unpack(header, *blobs)
    b = PagedKVCache(2, 2, 64, 2, 4, page_size=16, num_pages=8,
                     dtype=dtype)
    s2 = b.adopt_pages(1, pages["offset"], pages["k_pages"],
                       pages["v_pages"], pages["k_scales"],
                       pages["v_scales"])
    assert s2 is not None and int(b.offsets[s2]) == 37
    for li in range(2):
        for kind in ("k_pool", "v_pool"):
            pa = np.asarray(a.layers[li][kind]._data_)
            pb = np.asarray(b.layers[li][kind]._data_)
            for j in range(3):
                np.testing.assert_array_equal(
                    pb[b.table[s2, j]], pa[a.table[slot, j]])
        if dtype == "int8":
            for kind in ("k_scale", "v_scale"):
                sa = np.asarray(a.layers[li][kind]._data_)
                sb = np.asarray(b.layers[li][kind]._data_)
                for j in range(3):
                    np.testing.assert_array_equal(
                        sb[b.table[s2, j]], sa[a.table[slot, j]])
    # adopted pages are slot-private with the growth reservation intact
    assert b._shared[s2] == 0 and len(b._private[s2]) == 3
    assert b._reserved[s2] == 1


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_adopted_pages_attention_bit_equal(dtype):
    """`paged_masked_multihead_attention` over the adopted pool reads
    bit-identically to the source pool — the engine's migrated-output
    bit-equality guarantee reduces to this."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.incubate.nn import functional as IF
    rng = np.random.default_rng(1)
    a = PagedKVCache(1, 2, 64, 2, 4, page_size=16, dtype=dtype)
    slot = a.allocate(4)
    a.ensure_capacity(slot, 40)
    a.set_offset(slot, 41)
    _fill_cache(a, rng, dtype)
    header, blobs = migration.export_slot(a, slot)
    pages = migration.unpack(header, *blobs)
    b = PagedKVCache(1, 2, 64, 2, 4, page_size=16, num_pages=9,
                     dtype=dtype)
    s2 = b.adopt_pages(0, pages["offset"], pages["k_pages"],
                       pages["v_pages"], pages["k_scales"],
                       pages["v_scales"])
    q = rng.normal(size=(2, 1, 4, 4)).astype(np.float32)
    k = rng.normal(size=(2, 1, 2, 4)).astype(np.float32)
    v = rng.normal(size=(2, 1, 2, 4)).astype(np.float32)
    outs = []
    for cache, s in ((a, slot), (b, s2)):
        lay = cache.layer_caches()[0]
        args = [Tensor(q), Tensor(k), Tensor(v), lay["k_pool"],
                lay["v_pool"], lay["page_table"], lay["offset"],
                cache.page_size]
        kw = {}
        if dtype == "int8":
            kw = {"k_scale": lay["k_scale"], "v_scale": lay["v_scale"]}
        res = IF.paged_masked_multihead_attention(*args, **kw)
        outs.append(_np(res[0])[s])
    np.testing.assert_array_equal(outs[0], outs[1])


def test_adopt_pages_backpressure_and_validation():
    rng = np.random.default_rng(2)
    a = PagedKVCache(2, 2, 64, 2, 4, page_size=16)
    slot = a.allocate(4)
    a.ensure_capacity(slot, 47)
    a.set_offset(slot, 40)
    _fill_cache(a, rng, "float32")
    header, blobs = migration.export_slot(a, slot)
    pages = migration.unpack(header, *blobs)
    # pool too small: None (backpressure), never a crash
    tiny = PagedKVCache(2, 1, 64, 2, 4, page_size=16, num_pages=2)
    assert tiny.adopt_pages(0, pages["offset"], pages["k_pages"],
                            pages["v_pages"]) is None
    # wrong layer count
    with pytest.raises(PageMigrationError, match="pool"):
        PagedKVCache(3, 2, 64, 2, 4, page_size=16).adopt_pages(
            0, pages["offset"], pages["k_pages"], pages["v_pages"])
    # wrong page size
    with pytest.raises(PageMigrationError, match="pool"):
        PagedKVCache(2, 2, 64, 2, 4, page_size=8).adopt_pages(
            0, pages["offset"], pages["k_pages"], pages["v_pages"])
    # scales against a float pool
    with pytest.raises(PageMigrationError, match="scales"):
        PagedKVCache(2, 2, 64, 2, 4, page_size=16).adopt_pages(
            0, pages["offset"], pages["k_pages"], pages["v_pages"],
            np.ones((2, 3, 16), np.float32),
            np.ones((2, 3, 16), np.float32))
    # offset past the migrated pages
    with pytest.raises(PageMigrationError, match="offset"):
        PagedKVCache(2, 2, 64, 2, 4, page_size=16).adopt_pages(
            0, 49, pages["k_pages"], pages["v_pages"])
    # wire-version guard
    bad = dict(header, version=99)
    with pytest.raises(PageMigrationError, match="wire version"):
        migration.unpack(bad, *blobs)


def test_prefix_tree_pages_migrate_as_copies():
    """Tree-owned (shared) pages export by value: the receiving slot
    owns plain private copies, and the sender's tree keeps its pages,
    refcounts and free-list accounting untouched."""
    rng = np.random.default_rng(3)
    a = PagedKVCache(1, 2, 64, 2, 4, page_size=16)
    slot = a.allocate(4)
    a.ensure_capacity(slot, 40)
    a.set_offset(slot, 41)
    _fill_cache(a, rng, "float32")
    shared_page = a.make_shared(slot, 0)     # tree takes page 0
    free_before = a.free_page_count
    header, blobs = migration.export_slot(a, slot)
    pages = migration.unpack(header, *blobs)
    b = PagedKVCache(1, 2, 64, 2, 4, page_size=16, num_pages=9)
    s2 = b.adopt_pages(0, pages["offset"], pages["k_pages"],
                       pages["v_pages"])
    # receiver: every adopted page is private, nothing shared
    assert b._shared[s2] == 0 and len(b._private[s2]) == 3
    # sender: the tree page never moved; releasing the slot returns
    # only the private pages, the shared one stays tree-owned
    a.release(slot)
    assert a.free_page_count == free_before + 2
    a.reclaim(shared_page)
    assert a.free_page_count == free_before + 3


# ------------------------------------------------------------------
# rpc raw-bytes fast path
# ------------------------------------------------------------------

def _blob_probe(small, blob, big_bytes):
    """rpc target (top-level: the wire pickles the callable)."""
    assert isinstance(blob, rpc.Blob), type(blob)
    assert isinstance(big_bytes, rpc.Blob), type(big_bytes)
    assert isinstance(small, bytes)
    arr = np.frombuffer(blob.data, np.float32)
    return {"nbytes": len(blob), "sum": float(arr.sum()),
            "big_head": big_bytes.tobytes()[:4], "small": small}


def test_rpc_raw_bytes_fast_path_roundtrip_and_no_copy():
    """bytes in == bytes out over the raw path; the send side passes
    the caller's own buffer (no copy: the sent memoryview wraps the
    original array); large bytes-like args auto-promote past
    RAW_THRESHOLD while small ones keep the pickle path."""
    srv = rpc.RpcServer("blob-probe")
    try:
        arr = np.arange(50000, dtype=np.float32)   # ~200 KB
        big = b"\x01\x02\x03\x04" * (rpc.RAW_THRESHOLD // 4 + 1)
        sent = []
        orig = rpc_mod._send_blob

        def spy(conn, blob):
            sent.append(blob)
            return orig(conn, blob)

        rpc_mod._send_blob = spy
        try:
            out = rpc.rpc_sync("blob-probe", _blob_probe,
                               args=(b"tiny", rpc.Blob(arr), big))
        finally:
            rpc_mod._send_blob = orig
        assert out["nbytes"] == arr.nbytes
        assert out["sum"] == float(arr.sum())
        assert out["big_head"] == b"\x01\x02\x03\x04"
        assert out["small"] == b"tiny"
        # explicit Blob + auto-promoted big bytes rode raw frames...
        assert len(sent) == 2
        # ...and the Blob frame IS the caller's buffer, not a copy
        assert sent[0].data.obj is arr
        # a Blob that leaks into pickle fails loudly, never silently
        with pytest.raises(TypeError, match="raw-bytes fast path"):
            pickle.dumps(rpc.Blob(arr))
        # non-contiguous buffers are refused up front
        with pytest.raises(ValueError, match="contiguous"):
            rpc.Blob(np.ones((8, 8), np.float32)[:, ::2])
    finally:
        srv.close()


# ------------------------------------------------------------------
# engine-level handoff / resume / fallback
# ------------------------------------------------------------------

def _local_migrator(target_engine, name="peer"):
    """Single-phase in-process migrator: unpack + resume on the target
    engine, return the completed payload."""
    def migrate(req, header, blobs, target):
        pages = migration.unpack(header, *blobs)
        fut = target_engine.submit_resume(
            req.prompt, list(req.tokens), pages,
            max_new_tokens=req.max_new_tokens, sampling=req.sampling,
            eos_token_id=req.eos_token_id, ttft_ms=req.ttft_ms)
        out = fut.result(timeout=120)
        return {"request_id": req.id, "replica": name,
                "output_ids": out.output_ids,
                "finish_reason": out.finish_reason}
    return migrate


def test_engine_handoff_bit_equal_greedy_and_seeded(model):
    """A handed-off request's full stream — first token from the
    prefill engine, the rest decoded from adopted pages — is bit-equal
    to a single-engine run, greedy AND seeded-sampled."""
    eng_p = Engine(model, ServingConfig(num_slots=2,
                                        role="prefill")).start()
    eng_d = Engine(model, ServingConfig(num_slots=2,
                                        role="decode")).start()
    eng_c = Engine(model, ServingConfig(num_slots=2)).start()
    try:
        eng_p.migrator = _local_migrator(eng_d)
        p = _prompts([9], seed=4)[0]
        out = eng_p.submit(p, max_new_tokens=8,
                           handoff={"name": "peer"}).result(timeout=180)
        np.testing.assert_array_equal(out.output_ids,
                                      _ref_greedy(model, p, 8))
        assert out.decoded_by == "peer"
        sp = SamplingParams(temperature=0.8, top_k=20, seed=123)
        out_m = eng_p.submit(p, max_new_tokens=8, sampling=sp,
                             handoff={"name": "peer"}).result(timeout=180)
        out_ref = eng_c.generate(p, max_new_tokens=8, sampling=sp,
                                 timeout=180)
        np.testing.assert_array_equal(out_m.output_ids,
                                      out_ref.output_ids)
        snap = serving_stats()
        assert snap["migrations"] >= 2
        assert snap["migration_pages_sent"] >= 2
        assert snap["migration_resumed_requests"] >= 2
    finally:
        eng_p.shutdown()
        eng_d.shutdown()
        eng_c.shutdown()


def test_engine_handoff_fallback_decodes_locally(model):
    """A dead migration target must cost latency, never the request:
    the engine falls back to its own decode batch, bit-equal."""
    eng = Engine(model, ServingConfig(num_slots=2,
                                      role="prefill")).start()
    try:
        def dead(req, header, blobs, target):
            raise ConnectionError("target died mid-transfer")
        eng.migrator = dead
        p = _prompts([7], seed=5)[0]
        out = eng.submit(p, max_new_tokens=6,
                         handoff={"name": "x"}).result(timeout=180)
        np.testing.assert_array_equal(out.output_ids,
                                      _ref_greedy(model, p, 6))
        assert out.decoded_by is None
        assert serving_stats()["migration_fallbacks"] >= 1
        assert eng.cache.pages_in_use == 0        # nothing leaked
    finally:
        eng.shutdown()


def test_submit_resume_validation(model):
    eng = Engine(model, ServingConfig(num_slots=2)).start()
    try:
        pages = {"offset": 5, "k_pages": np.zeros((2, 1, 16, 4, 16),
                                                  np.float32),
                 "v_pages": np.zeros((2, 1, 16, 4, 16), np.float32),
                 "k_scales": None, "v_scales": None}
        p = _prompts([5], seed=6)[0]
        with pytest.raises(ValueError, match="prior token"):
            eng.submit_resume(p, [], pages, max_new_tokens=4)
        with pytest.raises(ValueError, match="exhaust"):
            eng.submit_resume(p, [1, 2, 3, 4], pages, max_new_tokens=4)
        with pytest.raises(PageMigrationError, match="inconsistent"):
            eng.submit_resume(p, [1], dict(pages, offset=9),
                              max_new_tokens=4)
    finally:
        eng.shutdown()


# ------------------------------------------------------------------
# fleet: role-aware routing + migration over the rpc plane
# ------------------------------------------------------------------

_FAST = dict(heartbeat_interval_s=0.15, heartbeat_ttl_s=1.2)


class _RoleFleet:
    """Thread-mode disaggregated fleet: named (role, ServingConfig)
    replicas + a role-aware router on one TCPStore."""

    def __init__(self, model, specs, disaggregation=True):
        self.master = TCPStore(is_master=True)
        rcfg = ReplicaConfig(**_FAST).validate()
        self.reps = {}
        for name, scfg in specs.items():
            self.reps[name] = ReplicaServer(
                name, model, TCPStore("127.0.0.1", self.master.port),
                scfg, rcfg)
        self.router = ServingRouter(
            TCPStore("127.0.0.1", self.master.port),
            RouterConfig(heartbeat_ttl_s=1.2, poll_interval_s=0.1,
                         disaggregation=disaggregation)).start()
        deadline = time.monotonic() + 30
        while len(self.router.ring.members) < len(specs):
            assert time.monotonic() < deadline, \
                f"ring never filled: {self.router.replicas()}"
            time.sleep(0.05)

    def close(self):
        self.router.close()
        for rep in self.reps.values():
            rep.close()
        self.master.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def test_fleet_disagg_routes_prefill_and_migrates(model):
    """Requests land on the prefill replica, their pages migrate, and
    the decode replica finishes them — outputs bit-equal, counters and
    per-role telemetry advancing."""
    specs = {"rep-p": ServingConfig(num_slots=2, role="prefill"),
             "rep-d": ServingConfig(num_slots=4, role="decode")}
    with _RoleFleet(model, specs) as f:
        base = serving_stats()
        prompts = _prompts([5, 9, 6], seed=7)
        futs = [f.router.submit(p, max_new_tokens=5, session_id=i)
                for i, p in enumerate(prompts)]
        outs = [fut.result(timeout=300) for fut in futs]
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(o.output_ids,
                                          _ref_greedy(model, p, 5))
            assert o.decoded_by == "rep-d"
        snap = serving_stats()
        assert snap["migrations"] - base["migrations"] >= 3
        assert snap["migration_pages_sent"] >= 3
        assert snap["migration_resumed_requests"] >= 3
        assert snap["migration_fallbacks"] == base["migration_fallbacks"]
        # both engines returned every page
        assert f.reps["rep-p"].engine.cache.pages_in_use == 0
        assert f.reps["rep-d"].engine.cache.pages_in_use == 0
        # per-role routed series reached the registry
        from paddle_tpu import observability as obs
        prom = obs.render_prometheus()
        assert 'serving_router_requests_routed_role{role="prefill"}' \
            in prom


def test_fleet_disagg_no_decode_replica_degrades_to_local(model):
    """A prefill-only fleet (no decode target in the gossip) decodes
    locally — disaggregation degrades to mixed, never to a failure."""
    specs = {"rep-p": ServingConfig(num_slots=2, role="prefill")}
    with _RoleFleet(model, specs) as f:
        p = _prompts([6], seed=8)[0]
        out = f.router.submit(p, max_new_tokens=4,
                              session_id="solo").result(timeout=300)
        np.testing.assert_array_equal(out.output_ids,
                                      _ref_greedy(model, p, 4))
        assert out.decoded_by == "rep-p"


def test_fleet_disagg_off_ignores_roles(model):
    """RouterConfig.disaggregation=False: roles gossip but routing is
    the PR 9 ring order — no handoff, no migration, byte-identical
    symmetric behavior."""
    specs = {"rep-p": ServingConfig(num_slots=2, role="prefill"),
             "rep-d": ServingConfig(num_slots=2, role="decode")}
    with _RoleFleet(model, specs, disaggregation=False) as f:
        base = serving_stats()
        prompts = _prompts([5, 7, 6, 8], seed=9)
        futs = [f.router.submit(p, max_new_tokens=4, session_id=i)
                for i, p in enumerate(prompts)]
        outs = [fut.result(timeout=300) for fut in futs]
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(o.output_ids,
                                          _ref_greedy(model, p, 4))
            # each request decoded where it was routed: no migration
            assert o.decoded_by in ("rep-p", "rep-d")
        snap = serving_stats()
        assert snap["migrations"] == base["migrations"]
        assert snap["migration_pages_sent"] == \
            base["migration_pages_sent"]


def test_drain_migrates_active_requests_to_survivor(model):
    """Preemption recovery: draining a role-specialized replica streams
    its mid-decode slots to the survivor, which resumes them with KV
    intact — streams complete bit-equal, never recomputing prompts."""
    specs = {"rep-a": ServingConfig(num_slots=2, role="prefill"),
             "rep-b": ServingConfig(num_slots=4, role="decode")}
    with _RoleFleet(model, specs, disaggregation=False) as f:
        base = serving_stats()
        # pin requests to rep-a (disagg off: ring routing by session)
        key = next(f"s{i}" for i in range(1000)
                   if f.router.ring.lookup(f"s{i}") == "rep-a")
        prompts = _prompts([6, 8], seed=10)
        futs = [f.router.submit(p, max_new_tokens=48, session_id=key)
                for p in prompts]
        # drain as soon as BOTH are decoding (don't outwait the decode)
        eng = f.reps["rep-a"].engine
        deadline = time.monotonic() + 60
        while len(eng._active) < 2:
            assert time.monotonic() < deadline, "never started decoding"
            time.sleep(0.02)
        drainer = threading.Thread(
            target=f.reps["rep-a"].drain, kwargs={"deadline_s": 60.0})
        drainer.start()
        outs = [fut.result(timeout=300) for fut in futs]
        drainer.join(120)
        assert not drainer.is_alive()
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(o.output_ids,
                                          _ref_greedy(model, p, 48))
        snap = serving_stats()
        migrated = [o for o in outs if o.decoded_by == "rep-b"]
        assert migrated, "drain never migrated a request"
        assert snap["migration_resumed_requests"] \
            - base["migration_resumed_requests"] >= len(migrated)


def test_role_flip_rejoins_with_bumped_generation(model):
    """A replica leaves as prefill and rejoins as decode under the same
    name: the store generation bumps, the router admits the rejoin
    (anti-flap), and the gossiped role flips.  Requests in flight across
    the flip are neither lost nor doubled: every stream completes
    bit-equal to sequential greedy."""
    specs = {"rep-f": ServingConfig(num_slots=2, role="prefill"),
             "rep-g": ServingConfig(num_slots=2, role="decode")}
    with _RoleFleet(model, specs) as f:
        rep = f.reps["rep-f"]
        gen0 = rep.gen
        inflight = _prompts([5, 7, 6, 8], seed=12)
        futs = [f.router.submit(p, max_new_tokens=12,
                                session_id=f"flip{i}")
                for i, p in enumerate(inflight)]
        rep.drain(deadline_s=30.0)
        deadline = time.monotonic() + 15
        while "rep-f" in f.router.ring.members:
            assert time.monotonic() < deadline
            time.sleep(0.05)
        # settled before the name comes back: a transport error of the
        # old generation is charged to whichever generation the router
        # sees at that moment
        for p, fut in zip(inflight, futs):
            np.testing.assert_array_equal(
                fut.result(timeout=300).output_ids,
                _ref_greedy(model, p, 12))
        flipped = ReplicaServer(
            "rep-f", model, TCPStore("127.0.0.1", f.master.port),
            ServingConfig(num_slots=2, role="decode"),
            ReplicaConfig(**_FAST))
        f.reps["rep-f"] = flipped
        assert flipped.gen > gen0
        deadline = time.monotonic() + 30
        while "rep-f" not in f.router.ring.members:
            assert time.monotonic() < deadline, f.router.replicas()
            time.sleep(0.05)
        with f.router._lock:
            assert f.router._replicas["rep-f"].role == "decode"
        # the flipped replica serves as a migration target now
        p = _prompts([5], seed=11)[0]
        out = f.router.submit(p, max_new_tokens=4,
                              session_id="postflip").result(timeout=300)
        np.testing.assert_array_equal(out.output_ids,
                                      _ref_greedy(model, p, 4))


def test_config_validation():
    with pytest.raises(ValueError, match="role"):
        ServingConfig(role="bogus").validate()
    assert RouterConfig().disaggregation is False
    assert ReplicaConfig().migrate_on_drain is True
    assert ServingConfig().role == "mixed"


# ------------------------------------------------------------------
# deadline propagation across the migration path (ISSUE 17)
# ------------------------------------------------------------------

def test_deadline_propagates_through_migration(model):
    """A client deadline bounds the WHOLE migrated request — prefill,
    transfer, and the resumed decode on the target replica.  A generous
    deadline rides through the handoff untouched; one that expires
    while the (slowed) decode replica holds the request must surface
    `DeadlineExceededError` instead of a late answer."""
    from paddle_tpu.serving import DeadlineExceededError
    from paddle_tpu.utils.flags import set_flags
    specs = {"rep-p": ServingConfig(num_slots=2, role="prefill"),
             "rep-d": ServingConfig(num_slots=4, role="decode")}
    with _RoleFleet(model, specs) as f:
        p = _prompts([6], seed=20)[0]
        # warm both engines so compile time can't eat the deadline
        f.router.submit(p, max_new_tokens=4,
                        session_id="warm").result(timeout=300)
        out = f.router.submit(p, max_new_tokens=5, deadline_s=60.0,
                              session_id="ok").result(timeout=300)
        np.testing.assert_array_equal(out.output_ids,
                                      _ref_greedy(model, p, 5))
        assert out.decoded_by == "rep-d"        # migrated AND bounded
        # now stall the decode replica's scheduler (gray failure: its
        # heartbeats stay healthy) so the resumed decode blows the
        # propagated deadline on the FAR side of the migration
        set_flags({"FLAGS_fault_inject":
                   "engine_slow:to=rep-d,delay_s=0.4,count=200"})
        try:
            with pytest.raises(DeadlineExceededError):
                f.router.submit(
                    p, max_new_tokens=24, deadline_s=1.5,
                    session_id="late").result(timeout=120)
        finally:
            set_flags({"FLAGS_fault_inject": ""})
        # the evicted request released every page on BOTH replicas
        deadline = time.monotonic() + 60
        for name in ("rep-p", "rep-d"):
            eng = f.reps[name].engine
            while eng.cache.pages_in_use or eng._active:
                assert time.monotonic() < deadline, \
                    f"{name} leaked pages after deadline evict"
                time.sleep(0.05)
        assert serving_stats()["requests_evicted_deadline"] >= 1


def test_mid_transfer_deadline_leaves_no_pages_on_either_side(model):
    """The deadline expires DURING the page transfer (the migration rpc
    itself is stalled in-call): wherever the request dies — evicted on
    the target, or fallback-decoded past its deadline at the source —
    it must resolve loudly and strand zero KV pages on either replica."""
    from paddle_tpu.serving import DeadlineExceededError
    from paddle_tpu.utils.flags import set_flags
    specs = {"rep-p": ServingConfig(num_slots=2, role="prefill"),
             "rep-d": ServingConfig(num_slots=4, role="decode")}
    with _RoleFleet(model, specs) as f:
        p = _prompts([7], seed=21)[0]
        f.router.submit(p, max_new_tokens=4,
                        session_id="warm").result(timeout=300)
        # every rpc INTO rep-d now sleeps 2s in-call: the transfer
        # straddles the 1.2s deadline
        set_flags({"FLAGS_fault_inject":
                   "rpc_slow:to=rep-d,delay_s=2.0,count=8"})
        try:
            with pytest.raises(DeadlineExceededError):
                f.router.submit(
                    p, max_new_tokens=16, deadline_s=1.2,
                    session_id="midxfer").result(timeout=120)
        finally:
            set_flags({"FLAGS_fault_inject": ""})
        deadline = time.monotonic() + 60
        for name in ("rep-p", "rep-d"):
            eng = f.reps[name].engine
            while eng.cache.pages_in_use or eng._active:
                assert time.monotonic() < deadline, \
                    f"{name} leaked pages after mid-transfer deadline"
                time.sleep(0.05)
        # the fleet is fully serviceable afterwards
        out = f.router.submit(p, max_new_tokens=4,
                              session_id="after").result(timeout=300)
        np.testing.assert_array_equal(out.output_ids,
                                      _ref_greedy(model, p, 4))
