"""Multi-host serving fleet (paddle_tpu/serving/{router,fleet}.py):
consistent-hash routing, membership + anti-flap reap, load shedding,
failover with idempotent resubmission, drain awareness, and the rpc /
store / engine hardening underneath it.  Thread-mode replicas (several
`ReplicaServer`s in one process, each with its own rpc listener) keep
these fast; one process-mode drill (`ServingFleet`: SIGKILL mid-load,
SIGTERM drain) closes the file."""
import signal
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import rpc
from paddle_tpu.distributed.store import (FileKVStore, TCPElasticStore,
                                          TCPStore)
from paddle_tpu.models import GPTForCausalLM, gpt_config
from paddle_tpu.serving import (Engine, EngineShutdownError, HashRing,
                                QueueFullError, ReplicaConfig,
                                ReplicaServer, RouterConfig,
                                SamplingParams, ServingConfig,
                                ServingFleet, ServingRouter,
                                serving_stats)
from paddle_tpu.utils.flags import set_flags


def _np(t):
    return np.asarray(t._data_)


def _make_model():
    """Top-level, so that a spawned replica process can unpickle it."""
    paddle.seed(0)
    m = GPTForCausalLM(gpt_config(
        "gpt2-124m", num_layers=2, hidden_size=64, num_heads=4,
        vocab_size=256, max_seq_len=64))
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _make_model()


def _prompts(lens, seed=0, vocab=256):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (n,)).astype("int32") for n in lens]


def _ref_greedy(model, prompt, max_new):
    ids = model.generate(paddle.to_tensor(prompt[None, :]),
                         max_new_tokens=max_new, temperature=0.0)
    return _np(ids)[0, prompt.size:]


_FAST = dict(heartbeat_interval_s=0.15, heartbeat_ttl_s=1.2)


class _Fleet:
    """Thread-mode harness: N ReplicaServers + router on one TCPStore."""

    def __init__(self, model, n=2, serving_config=None, replica_config=None,
                 router_config=None):
        self.master = TCPStore(is_master=True)
        scfg = serving_config or ServingConfig(num_slots=2, max_queue=16)
        rcfg = (replica_config or ReplicaConfig(**_FAST)).validate()
        self.reps = {}
        for i in range(n):
            name = f"rep-{i}"
            self.reps[name] = ReplicaServer(
                name, model, TCPStore("127.0.0.1", self.master.port),
                scfg, rcfg)
        self.router = ServingRouter(
            TCPStore("127.0.0.1", self.master.port),
            router_config or RouterConfig(
                heartbeat_ttl_s=rcfg.heartbeat_ttl_s,
                poll_interval_s=0.1)).start()
        deadline = time.monotonic() + 30
        while len(self.router.ring.members) < n:
            assert time.monotonic() < deadline, \
                f"ring never filled: {self.router.replicas()}"
            time.sleep(0.05)

    def kill(self, name):
        """SIGKILL analog for a threaded replica: rpc listener gone,
        heartbeats stop, engine dead — NO deregistration."""
        rep = self.reps[name]
        rep._stop.set()
        rep._beat.join(5.0)
        rep.rpc_server.close()
        rep.engine.shutdown()

    def close(self):
        self.router.close()
        for rep in self.reps.values():
            rep.close()
        self.master.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# ---------------------------------------------------------------- ring
def test_hash_ring_distinct_successors_and_minimal_remap():
    ring = HashRing(virtual_nodes=32)
    ring.rebuild({"a", "b", "c"})
    keys = [f"key-{i}" for i in range(200)]
    for k in keys:
        succ = list(ring.successors(k))
        assert sorted(succ) == ["a", "b", "c"]      # each member once
        assert succ[0] == ring.lookup(k)
    owners = {k: ring.lookup(k) for k in keys}
    # removing one member must not remap keys owned by survivors
    ring.rebuild({"a", "b"})
    for k in keys:
        if owners[k] != "c":
            assert ring.lookup(k) == owners[k]
    # adding it back restores the original ownership exactly
    ring.rebuild({"a", "b", "c"})
    assert {k: ring.lookup(k) for k in keys} == owners


def test_config_validation():
    with pytest.raises(ValueError, match="heartbeat_ttl_s"):
        RouterConfig(heartbeat_ttl_s=0).validate()
    with pytest.raises(ValueError, match="virtual_nodes"):
        RouterConfig(virtual_nodes=0).validate()
    with pytest.raises(ValueError, match="must exceed"):
        ReplicaConfig(heartbeat_interval_s=2.0,
                      heartbeat_ttl_s=1.0).validate()
    with pytest.raises(ValueError, match="tensor_parallel_degree"):
        ReplicaConfig(tensor_parallel_degree=0).validate()


# ------------------------------------------------------------- routing
def test_fleet_greedy_bit_equal_and_affinity(model):
    """Outputs routed through a 2-replica fleet are bit-equal to the
    single-model greedy reference, and same-session requests stick to
    the ring owner."""
    prompts = _prompts([5, 7, 3, 9, 6])
    with _Fleet(model, n=2) as f:
        futs = [f.router.submit(p, max_new_tokens=5, session_id=f"s{i}")
                for i, p in enumerate(prompts)]
        outs = [fut.result(timeout=120) for fut in futs]
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(o.output_ids,
                                          _ref_greedy(model, p, 5))
            assert o.finish_reason == "length"
        # affinity: the ring owner of a session serves every repeat
        owner = f.router.ring.lookup("sticky")
        with f.reps[owner]._dedup_lock:
            before = len(f.reps[owner]._dedup)
        futs = [f.router.submit(prompts[0], max_new_tokens=2,
                                session_id="sticky") for _ in range(3)]
        [fut.result(timeout=120) for fut in futs]
        with f.reps[owner]._dedup_lock:
            assert len(f.reps[owner]._dedup) == before + 3
        snap = serving_stats()
        assert snap["router_requests_routed"] == 8
        assert snap["router_replicas_alive"] == 2
        assert snap["router_route_latency_ms_avg"] > 0


def test_router_load_shedding_fails_fast(model):
    """At >capacity offered load every ready replica sheds; the router
    fails fast with QueueFullError carrying retry_after_s instead of
    queueing unboundedly, and counts the sheds."""
    scfg = ServingConfig(num_slots=1, max_queue=1)
    with _Fleet(model, n=2, serving_config=scfg,
                router_config=RouterConfig(
                    heartbeat_ttl_s=1.2, poll_interval_s=0.1,
                    retry_after_s=0.7)) as f:
        shed_before = serving_stats()["router_requests_shed"]
        prompts = _prompts([6] * 10, seed=3)
        futs = [f.router.submit(p, max_new_tokens=40, session_id=i)
                for i, p in enumerate(prompts)]
        done, shed = 0, 0
        for fut in futs:
            try:
                out = fut.result(timeout=180)
                assert out.finish_reason in ("length", "eos")
                done += 1
            except QueueFullError as e:
                # the hint starts at the knob and scales (up to 8x)
                # with the router's recent shed pressure
                assert 0.7 <= e.retry_after_s <= 0.7 * 8
                shed += 1
        assert done + shed == 10
        assert shed >= 1, "10 requests into 2x(1 slot + 1 queue) must shed"
        assert serving_stats()["router_requests_shed"] - shed_before \
            == shed


def test_failover_replica_death_recovers_request(model):
    """A request routed to a replica that dies mid-fleet is resubmitted
    to a survivor under the same id: the client sees one complete,
    correct stream — never a duplicate, never a hang."""
    with _Fleet(model, n=2) as f:
        owner = f.router.ring.lookup("victim-session")
        f.kill(owner)
        p = _prompts([6], seed=5)[0]
        out = f.router.submit(p, max_new_tokens=5,
                              session_id="victim-session").result(timeout=120)
        np.testing.assert_array_equal(out.output_ids,
                                      _ref_greedy(model, p, 5))
        snap = serving_stats()
        assert snap["router_failovers"] >= 1
        assert snap["router_requests_recovered"] >= 1
        # the dead replica is sticky-dead, not flapping
        deadline = time.monotonic() + 10
        while f.router.replicas().get(owner) != "dead":
            assert time.monotonic() < deadline
            time.sleep(0.05)
        assert f.router.ring.members == {n for n in f.reps if n != owner}


def test_rpc_drop_injection_drills_failover(model):
    """The rpc_drop fault point makes the failover path deterministic:
    no SIGKILL needed — connects to the victim fail, the router marks it
    dead and reroutes."""
    with _Fleet(model, n=2) as f:
        owner = f.router.ring.lookup("drilled")
        try:
            set_flags({"FLAGS_fault_inject": f"rpc_drop:to={owner}"})
            p = _prompts([5], seed=7)[0]
            out = f.router.submit(
                p, max_new_tokens=4,
                session_id="drilled").result(timeout=120)
            np.testing.assert_array_equal(out.output_ids,
                                          _ref_greedy(model, p, 4))
            assert serving_stats()["router_failovers"] >= 1
            assert f.router.replicas()[owner] == "dead"
        finally:
            set_flags({"FLAGS_fault_inject": ""})


def test_rpc_delay_injection_sleeps_connects():
    from paddle_tpu.utils import fault_injection as fi
    try:
        set_flags({"FLAGS_fault_inject":
                   "rpc_delay:to=slowpoke,delay_s=0.2,count=1"})
        t0 = time.monotonic()
        assert fi.check_rpc("rpc_delay", "slowpoke-0") is False
        assert time.monotonic() - t0 >= 0.2
        t0 = time.monotonic()                 # count=1 exhausted
        fi.check_rpc("rpc_delay", "slowpoke-0")
        assert time.monotonic() - t0 < 0.1
        assert fi.check_rpc("rpc_drop", "slowpoke-0") is False
    finally:
        set_flags({"FLAGS_fault_inject": ""})


def test_drain_aware_routing(model):
    """A draining replica leaves the ring within a poll interval and its
    queued requests are resubmitted to survivors — zero lost."""
    with _Fleet(model, n=2) as f:
        owner = f.router.ring.lookup("drainee")
        survivor = next(n for n in f.reps if n != owner)
        # long decodes occupy the owner, then drain it mid-flight
        prompts = _prompts([6] * 4, seed=9)
        futs = [f.router.submit(p, max_new_tokens=30,
                                session_id="drainee") for p in prompts]
        time.sleep(0.3)
        drainer = threading.Thread(
            target=f.reps[owner].drain, kwargs={"deadline_s": 30.0})
        drainer.start()
        outs = [fut.result(timeout=180) for fut in futs]
        drainer.join(60)
        assert not drainer.is_alive()
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(o.output_ids,
                                          _ref_greedy(model, p, 30))
        # the drained replica left the ring; the survivor serves on
        deadline = time.monotonic() + 10
        while f.router.ring.members != {survivor}:
            assert time.monotonic() < deadline
            time.sleep(0.05)
        out = f.router.submit(prompts[0], max_new_tokens=2,
                              session_id="drainee").result(timeout=60)
        assert len(out.output_ids) == 2


def test_replica_reap_and_generation_rejoin(model):
    """Anti-flap end to end: a replica that misses heartbeats goes
    sticky-dead and its lease is reaped; resumed heartbeats re-register
    under a BUMPED generation, which the router accepts as an explicit
    rejoin — membership sees two edges, not an oscillation."""
    with _Fleet(model, n=2) as f:
        victim = sorted(f.reps)[0]
        rep = f.reps[victim]
        gen0 = rep.gen
        rep._stop.set()                      # pause heartbeats
        rep._beat.join(5.0)
        deadline = time.monotonic() + 15
        while f.router.replicas().get(victim) != "dead":
            assert time.monotonic() < deadline, "never marked dead"
            time.sleep(0.05)
        # the router reaped the expired lease (anti-flap)
        deadline = time.monotonic() + 10
        while rep.membership.is_registered(victim):
            assert time.monotonic() < deadline, "lease never reaped"
            time.sleep(0.05)
        # resume heartbeats: the loop notices the reap and re-registers
        # with a bumped generation
        rep._stop = threading.Event()
        rep._beat = threading.Thread(target=rep._beat_loop, daemon=True)
        rep._beat.start()
        deadline = time.monotonic() + 15
        while victim not in f.router.ring.members:
            assert time.monotonic() < deadline, "never rejoined"
            time.sleep(0.05)
        assert rep.gen > gen0


# ------------------------------------------------- store / rpc hardening
@pytest.mark.parametrize("kind", ["tcp", "file"])
def test_elastic_store_expiry_reap_reregister(kind, tmp_path):
    master = None
    if kind == "tcp":
        master = TCPStore(is_master=True)
        store = TCPStore("127.0.0.1", master.port)
    else:
        store = FileKVStore(str(tmp_path))   # no stamp/server_now: falls
        #                                      back to writer wall clock
    try:
        es = TCPElasticStore(store, ttl=0.4)
        es.register("n1")
        es.register("n2")
        assert es.alive_nodes() == ["n1", "n2"]
        assert es.expired_nodes() == []
        time.sleep(0.6)
        es.heartbeat("n2")                   # n1 flaps, n2 stays fresh
        assert es.alive_nodes() == ["n2"]
        assert es.expired_nodes() == ["n1"]
        assert es.is_registered("n1")        # key lingers until reaped
        assert es.reap() == ["n1"]
        assert es.is_registered("n1") is False
        assert es.expired_nodes() == []
        es.register("n1")                    # explicit rejoin
        assert es.alive_nodes() == ["n1", "n2"]
    finally:
        if master is not None:
            store.close()
            master.close()


def test_rpc_shutdown_idempotent_and_connect_retry():
    rpc.shutdown()                           # never initialized: no-op
    rpc.shutdown()
    # connect to a port nothing listens on: retried, then a loud
    # ConnectionError naming the worker — never a hang
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    rpc.connect_worker("ghost", "127.0.0.1", port)
    try:
        t0 = time.monotonic()
        with pytest.raises(ConnectionError, match="ghost"):
            rpc.rpc_sync("ghost", sorted, args=([3, 1],))
        assert time.monotonic() - t0 < 10
    finally:
        rpc.forget_worker(name="ghost")
    with pytest.raises(ValueError, match="unknown worker"):
        rpc.rpc_sync("ghost", sorted, args=([],))
    # a peer that accepts and dies before the handshake ends (a replica
    # killed while it was being dialled) is the same connect failure,
    # not an EOFError the router would take for an application error
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    s.listen()

    def accept_and_die():
        for _ in range(3):                   # one per connect retry
            s.accept()[0].close()

    dier = threading.Thread(target=accept_and_die, daemon=True)
    dier.start()
    rpc.connect_worker("dying", "127.0.0.1", s.getsockname()[1])
    try:
        with pytest.raises(ConnectionError, match="dying"):
            rpc.rpc_sync("dying", sorted, args=([3, 1],))
    finally:
        rpc.forget_worker(name="dying")
        dier.join(5)
        s.close()


def test_rpc_server_close_releases_port():
    """close() must wake the accept loop so the kernel releases the
    socket — a dangling accept would keep 'serving' a dead replica."""
    srv = rpc.RpcServer("porttest")
    port = srv.info.port
    srv.close()
    srv.close()                              # idempotent
    import socket
    deadline = time.monotonic() + 5
    while True:
        try:
            s = socket.socket()
            s.bind(("127.0.0.1", port))
            s.close()
            break
        except OSError:
            assert time.monotonic() < deadline, "port never released"
            time.sleep(0.1)


# -------------------------------------------------- engine under drain
def test_submit_drain_race_never_strands_a_future(model):
    """Hammer submit() from several threads while drain() runs: every
    future resolves (result or EngineShutdownError) and every late
    submit raises — no client ever hangs."""
    eng = Engine(model, ServingConfig(num_slots=2, max_queue=64)).start()
    prompts = _prompts([5], seed=11)
    futures, rejected = [], []
    flock = threading.Lock()
    stop = threading.Event()

    def _hammer():
        while not stop.is_set():
            try:
                fut = eng.submit(prompts[0], max_new_tokens=3)
                with flock:
                    futures.append(fut)
            except (EngineShutdownError, QueueFullError) as e:
                with flock:
                    rejected.append(e)
                if isinstance(e, EngineShutdownError):
                    return
            time.sleep(0.002)

    threads = [threading.Thread(target=_hammer) for _ in range(6)]
    for t in threads:
        t.start()
    time.sleep(0.4)
    eng.drain(deadline_s=60.0)
    stop.set()
    for t in threads:
        t.join(30)
        assert not t.is_alive()
    assert futures, "hammer never got a request in"
    assert any(isinstance(e, EngineShutdownError) for e in rejected), \
        "drain must reject late submits loudly"
    resolved = 0
    for fut in futures:
        try:
            out = fut.result(timeout=30)     # must already be done
            assert out.finish_reason in ("length", "eos")
            resolved += 1
        except (EngineShutdownError, Exception):
            assert fut.done()
    assert eng._pending == {}, "audit registry must drain"
    assert resolved >= 1


def test_replica_handle_submit_idempotent(model):
    """A resubmitted request id re-awaits the SAME engine future: the
    engine decodes once, both calls return identical payloads."""
    master = TCPStore(is_master=True)
    rep = ReplicaServer("solo", model,
                        TCPStore("127.0.0.1", master.port),
                        ServingConfig(num_slots=2, max_queue=8),
                        ReplicaConfig(**_FAST))
    try:
        p = _prompts([6], seed=13)[0]
        sampling = {"temperature": 0.0}
        a = rep.handle_submit("rid-1", p, 4, sampling, None, None)
        before = serving_stats()["requests_submitted"]
        b = rep.handle_submit("rid-1", p, 4, sampling, None, None)
        assert serving_stats()["requests_submitted"] == before, \
            "resubmit must not re-decode"
        np.testing.assert_array_equal(a["output_ids"], b["output_ids"])
        assert a["finish_reason"] == b["finish_reason"]
        # sampled requests stay idempotent too (same future, same draw)
        c = rep.handle_submit("rid-2", p, 4,
                              {"temperature": 0.8, "top_k": 8}, None,
                              None)
        d = rep.handle_submit("rid-2", p, 4,
                              {"temperature": 0.8, "top_k": 8}, None,
                              None)
        np.testing.assert_array_equal(c["output_ids"], d["output_ids"])
    finally:
        rep.close()
        master.close()


def test_router_submit_validation(model):
    with _Fleet(model, n=1) as f:
        with pytest.raises(ValueError, match="empty prompt"):
            f.router.submit(np.zeros((0,), np.int32))
        with pytest.raises(ValueError):
            f.router.submit(_prompts([4])[0],
                            sampling=SamplingParams(temperature=-1))
    with pytest.raises(EngineShutdownError):
        f.router.submit(_prompts([4])[0])


# ------------------------------------------------------- process mode
def test_process_fleet_sigkill_midload_then_sigterm_drain(model):
    """Replica PROCESSES behind the router: SIGKILL one under load and
    every request still resolves once, bit-equal to sequential greedy
    (none lost, no token doubled); a request of a session the victim
    owned fails over; SIGTERM makes the survivor drain and exit 0; no
    process outlives the fleet."""
    prompts = _prompts([6, 9, 4, 11, 7, 5], seed=13)
    fleet = ServingFleet(
        _make_model, num_replicas=2,
        serving_config=ServingConfig(num_slots=2, max_queue=16),
        replica_config=ReplicaConfig(drain_deadline_s=20.0, **_FAST),
        router_config=RouterConfig(heartbeat_ttl_s=1.2,
                                   poll_interval_s=0.1),
        warmup_prompt=_prompts([4], seed=1)[0])
    with fleet:
        procs = dict(fleet._procs)
        victim, survivor = sorted(procs)
        key = next(f"s{i}" for i in range(1000)
                   if fleet.router.ring.lookup(f"s{i}") == victim)
        failovers = fleet.stats()["router_failovers"]
        futs = [fleet.submit(p, max_new_tokens=24, session_id=i)
                for i, p in enumerate(prompts)]
        fleet.kill_replica(victim, sig=signal.SIGKILL)
        futs.append(fleet.submit(prompts[0], max_new_tokens=24,
                                 session_id=key))
        for p, fut in zip(prompts + prompts[:1], futs):
            np.testing.assert_array_equal(
                fut.result(timeout=300).output_ids,
                _ref_greedy(model, p, 24))
        procs[victim].join(30)
        assert procs[victim].exitcode == -signal.SIGKILL
        assert fleet.stats()["router_failovers"] > failovers
        fleet.drain_replica(survivor)               # SIGTERM
        procs[survivor].join(60)
        assert procs[survivor].exitcode == 0
    assert [n for n, p in procs.items() if p.is_alive()] == []

