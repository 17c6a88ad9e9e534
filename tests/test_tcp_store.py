"""Native TCP store: KV, blocking wait, counters, rendezvous, elastic
adapter (reference: phi/core/distributed/store/tcp_store.h:120 +
launch/controllers/master.py ETCDMaster)."""
import multiprocessing as mp
import threading
import time

import pytest

from paddle_tpu.distributed.store import (
    TCPStore, TCPElasticStore, Master,
)


@pytest.fixture()
def store():
    s = TCPStore(is_master=True)
    yield s
    s.close()


def test_set_get_delete(store):
    assert store.get("missing") is None
    store.set("k", b"hello")
    assert store.get("k") == b"hello"
    store.set("k", "world")
    assert store.get("k") == b"world"
    store.delete_key("k")
    assert store.get("k") is None


def test_add_counter(store):
    assert store.add("ctr", 1) == 1
    assert store.add("ctr", 5) == 6
    assert store.add("ctr", 0) == 6


def test_wait_blocks_until_set(store):
    got = {}

    def setter():
        time.sleep(0.3)
        s2 = TCPStore(port=store.port)
        s2.set("later", b"v")
        s2.close()

    t = threading.Thread(target=setter)
    t.start()
    t0 = time.time()
    got["v"] = store.wait("later", timeout=10)
    t.join()
    assert got["v"] == b"v"
    assert time.time() - t0 >= 0.2


def test_wait_timeout(store):
    with pytest.raises(TimeoutError):
        store.wait("never", timeout=0.3)


def test_list_prefix_and_large_values(store):
    store.set("a/1", b"x" * 100_000)
    store.set("a/2", b"y")
    store.set("b/1", b"z")
    out = store.list_prefix("a/")
    assert set(out) == {"a/1", "a/2"}
    assert out["a/1"] == b"x" * 100_000


def test_get_value_larger_than_buffer_and_growth(store):
    # get() must loop until its buffer fits: a value can exceed the
    # initial 64 KiB buffer — and GROW again between the size probe and
    # the refetch (simulated by growing it right before each get)
    store.set("big", b"x" * 100_000)
    assert store.get("big") == b"x" * 100_000
    store.set("big", b"y" * 300_000)
    assert store.get("big") == b"y" * 300_000


def test_second_client_sees_writes(store):
    c2 = TCPStore(port=store.port)
    store.set("shared", b"1")
    assert c2.get("shared") == b"1"
    c2.close()


def _node_main(endpoint, rank, nnodes, q):
    m = Master(endpoint, rank, nnodes, timeout=30)
    eps = m.sync_endpoints(f"10.0.0.{rank}:900{rank}")
    q.put((rank, eps))
    m.close()


def test_master_rendezvous_across_processes():
    import os
    from paddle_tpu.distributed.launch.context import free_port
    port = free_port()
    endpoint = f"127.0.0.1:{port}"
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [ctx.Process(target=_node_main, args=(endpoint, r, 3, q))
             for r in range(3)]
    # spawned children re-import jax at interpreter start — force them
    # onto CPU (they inherit os.environ; without this they'd claim
    # the machine's accelerator)
    old = {k: os.environ.get(k) for k in ("JAX_PLATFORMS",)}
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        for p in procs:
            p.start()
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    results = [q.get(timeout=60) for _ in range(3)]
    for p in procs:
        p.join(timeout=30)
    expect = [f"10.0.0.{r}:900{r}" for r in range(3)]
    for rank, eps in results:
        assert eps == expect


def test_elastic_adapter_liveness(store):
    es = TCPElasticStore(store, ttl=1)
    es.register("n0")
    es.register("n1")
    assert es.alive_nodes() == ["n0", "n1"]
    es.deregister("n1")
    assert es.alive_nodes() == ["n0"]
    time.sleep(1.2)          # ttl expiry without heartbeat
    assert es.alive_nodes() == []
    es.heartbeat("n0")
    assert es.alive_nodes() == ["n0"]
