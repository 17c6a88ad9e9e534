"""RPC agent (reference: python/paddle/distributed/rpc/rpc.py +
paddle/fluid/distributed/rpc/rpc_agent.cc; VERDICT: the path had no
coverage)."""
import multiprocessing as mp
import os

import pytest


def _worker_main(master, q):
    os.environ["JAX_PLATFORMS"] = "cpu"
    from paddle_tpu.distributed import rpc
    rpc.init_rpc("worker1", rank=1, world_size=2, master_endpoint=master)
    # wait until master calls us, then exit on its signal
    q.get(timeout=60)
    rpc.shutdown()


def _double(x):
    return 2 * x


def _boom():
    raise ValueError("remote failure")


def test_rpc_cross_process():
    from paddle_tpu.distributed import rpc
    from paddle_tpu.distributed.launch.context import free_port
    master = f"127.0.0.1:{free_port()}"
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    old = {k: os.environ.get(k) for k in ("JAX_PLATFORMS",)}
    os.environ["JAX_PLATFORMS"] = "cpu"
    proc = ctx.Process(target=_worker_main, args=(master, q))
    try:
        proc.start()
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    try:
        rpc.init_rpc("master", rank=0, world_size=2,
                     master_endpoint=master)
        # wait for the worker to register
        import time
        for _ in range(100):
            if "worker1" in {w.name for w in rpc.get_all_worker_infos()}:
                break
            time.sleep(0.2)
        infos = {w.name for w in rpc.get_all_worker_infos()}
        assert {"master", "worker1"} <= infos

        assert rpc.rpc_sync("worker1", _double, args=(21,)) == 42
        fut = rpc.rpc_async("worker1", _double, args=(5,))
        assert fut.result(timeout=30) == 10
        with pytest.raises(ValueError, match="remote failure"):
            rpc.rpc_sync("worker1", _boom)
        assert rpc.get_worker_info("worker1").rank == 1
        assert rpc.get_current_worker_info().name == "master"
    finally:
        q.put("done")
        proc.join(timeout=30)
        rpc.shutdown()
    assert proc.exitcode == 0
