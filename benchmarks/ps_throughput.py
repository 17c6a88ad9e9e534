"""Measure the PS transport: sparse pull/push rows/sec over real
processes.

docs/PARITY.md calls the multiprocessing.connection transport "a
throughput ceiling, not a capability gap" — this records the ceiling
(VERDICT r03 weak #8).  The server runs in its own process, so every
request crosses a real authenticated TCP connection like a deployment
would; nothing is measured in-process.

Writes benchmarks/PS_THROUGHPUT.json and prints one JSON line.
Reference analog: brpc_ps_client throughput (ps/service/brpc_ps_client).
"""
from __future__ import annotations

import json
import multiprocessing as mp
import os
import time

import numpy as np


DIM = 64
BATCH = 4096
LOOPS = 20


def _server_main(q):
    os.environ["JAX_PLATFORMS"] = "cpu"
    from paddle_tpu.distributed.ps import PSServer
    srv = PSServer()
    srv.add_sparse_table(0, DIM, lr=0.1)
    srv.start()
    q.put(srv.address)
    srv.run()


def bench_tables():
    """Storage-tier capacity benchmark (VERDICT r04 item 7): RAM
    SparseTable vs SSDSparseTable (4096-row hot cache + WAL + record log)
    at working sets far beyond the cache — rows/sec for pull and push,
    plus the on-disk footprint.  Reference analog: memory_sparse_table
    vs ssd_sparse_table capacity trade (ps/table/ssd_sparse_table.h)."""
    import tempfile
    from paddle_tpu.distributed.ps import SparseTable, SSDSparseTable

    rng = np.random.default_rng(1)
    out = {}
    for n_rows in (50_000, 200_000):
        for kind in ("ram", "ssd"):
            if kind == "ram":
                t = SparseTable(DIM, lr=0.1)
            else:
                d = tempfile.mkdtemp(prefix="ps_tier_bench_")
                t = SSDSparseTable(DIM, lr=0.1, cache_rows=4096,
                                   path=os.path.join(d, "t.bin"))
            # populate the working set (off the clock)
            for lo in range(0, n_rows, BATCH):
                t.pull(list(range(lo, min(lo + BATCH, n_rows))))
            loops = 6
            batches = [rng.integers(0, n_rows, BATCH).tolist()
                       for _ in range(loops)]
            grads = rng.standard_normal((BATCH, DIM)).astype(np.float32)
            t0 = time.perf_counter()
            for ids in batches:
                t.pull(ids)
            pull_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            for ids in batches:
                t.push(ids, grads)
            push_s = time.perf_counter() - t0
            rec = {
                "pull_rows_per_sec": round(BATCH * loops / pull_s),
                "push_rows_per_sec": round(BATCH * loops / push_s),
            }
            if kind == "ssd":
                t.flush()
                rec["log_bytes"] = os.path.getsize(t.path)
                rec["cache_rows"] = t.cache_rows
                rec["cold_rows"] = t.num_cold_rows
                t.close()
            out[f"{kind}_{n_rows}"] = rec
    return out


def main():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from paddle_tpu.distributed.ps import PSClient

    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    proc = ctx.Process(target=_server_main, args=(q,), daemon=True)
    proc.start()
    addr = q.get(timeout=60)
    client = PSClient(addr)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, 1_000_000, BATCH).tolist()
    grads = rng.standard_normal((BATCH, DIM)).astype(np.float32)

    client.pull_sparse(0, ids)          # warm: row creation off the clock
    t0 = time.perf_counter()
    for _ in range(LOOPS):
        client.pull_sparse(0, ids)
    pull_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(LOOPS):
        client.push_sparse(0, ids, grads)
    push_s = time.perf_counter() - t0

    client.stop_server()
    client.close()
    proc.join(timeout=10)

    rec = {
        "transport": "multiprocessing.connection (authenticated TCP)",
        "dim": DIM, "batch": BATCH, "loops": LOOPS,
        "pull_rows_per_sec": round(BATCH * LOOPS / pull_s),
        "push_rows_per_sec": round(BATCH * LOOPS / push_s),
        "pull_MBps": round(BATCH * LOOPS * DIM * 4 / pull_s / 1e6, 1),
        "push_MBps": round(BATCH * LOOPS * DIM * 4 / push_s / 1e6, 1),
        "tiers": bench_tables(),
    }
    out = os.path.join(os.path.dirname(__file__), "PS_THROUGHPUT.json")
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
