"""Parameter-server stack + ONNX export surface (reference:
paddle/fluid/distributed/ps/ + python/paddle/distributed/ps/the_one_ps.py
+ python/paddle/onnx/export.py)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.ps import (
    DenseTable, SparseTable, PSServer, PSClient, TheOnePSRuntime,
    PSEmbedding,
)


def test_tables_local():
    d = DenseTable((4,), lr=0.5)
    np.testing.assert_allclose(d.pull(), 0.0)
    d.push(np.ones(4, np.float32))
    np.testing.assert_allclose(d.pull(), -0.5)
    s = SparseTable(3, lr=1.0)
    rows = s.pull([7, 9])
    assert rows.shape == (2, 3)
    s.push([7], np.ones((1, 3), np.float32))
    np.testing.assert_allclose(s.pull([7]), rows[0:1] - 1.0)
    # untouched row unchanged
    np.testing.assert_allclose(s.pull([9]), rows[1:2])


@pytest.fixture()
def runtime():
    cfg = {"tables": {0: {"type": "sparse", "dim": 4, "lr": 0.1},
                      1: {"type": "dense", "shape": [3], "lr": 0.1}}}
    server_rt = TheOnePSRuntime("server", cfg)
    server_rt.init_server()
    worker_rt = TheOnePSRuntime("worker", cfg,
                                server_address=server_rt.server_address)
    client = worker_rt.init_worker()
    yield server_rt, worker_rt, client
    worker_rt.stop()


def test_server_client_pull_push(runtime):
    _, _, client = runtime
    v = client.pull_dense(1)
    np.testing.assert_allclose(v, 0.0)
    client.push_dense(1, np.ones(3, np.float32))
    np.testing.assert_allclose(client.pull_dense(1), -0.1, atol=1e-6)

    rows = client.pull_sparse(0, [1, 2, 3])
    assert rows.shape == (3, 4)
    client.push_sparse(0, [2], np.ones((1, 4), np.float32))
    after = client.pull_sparse(0, [2])
    np.testing.assert_allclose(after, rows[1:2] - 0.1, atol=1e-6)
    # state save round-trips through the wire
    state = client.save()
    assert 0 in state and 2 in state[0]


def test_two_clients_share_state(runtime):
    srv, _, c1 = runtime
    c2 = PSClient(srv.server_address)
    c1.push_dense(1, np.full(3, 10.0, np.float32))
    np.testing.assert_allclose(c2.pull_dense(1), -1.0, atol=1e-6)
    c2.close()


def test_ps_embedding_trains(runtime):
    """Sparse-embedding regression: pull on forward, push on backward —
    loss must drop (the DistributedLookupTable flow)."""
    _, _, client = runtime
    emb = PSEmbedding(client, table_id=0, dim=4)
    w = paddle.to_tensor(np.ones(4, np.float32))
    target = 3.0
    ids = np.array([5, 6], np.int64)
    losses = []
    for _ in range(30):
        e, leaf = emb(paddle.to_tensor(ids))
        pred = (e * w).sum(-1)
        loss = ((pred - target) ** 2).mean()
        loss.backward()
        losses.append(float(loss.numpy()))
    assert losses[-1] < 0.05 * losses[0]


def test_onnx_export_stablehlo(tmp_path):
    from paddle_tpu import nn
    from paddle_tpu.static import InputSpec
    layer = nn.Linear(4, 2)
    prefix = str(tmp_path / "model")
    paddle.onnx.export(layer, prefix,
                       input_spec=[InputSpec([1, 4], "float32", "x")])
    import os
    assert os.path.exists(prefix + ".pdmodel")
    from paddle_tpu.inference import Predictor, Config
    pred = Predictor(Config(prefix))
    x = np.ones((1, 4), np.float32)
    out = pred.run([x])[0]
    ref = layer(paddle.to_tensor(x))
    np.testing.assert_allclose(out, np.asarray(ref._data_), atol=1e-5)


def test_onnx_suffix_emits_real_protobuf(tmp_path):
    """.onnx paths now produce ACTUAL ONNX protobuf via the native
    emitter (tests/test_onnx_export.py covers numerics)."""
    from paddle_tpu import nn
    p = paddle.onnx.export(
        nn.Linear(2, 2), str(tmp_path / "m.onnx"),
        input_spec=[paddle.jit.InputSpec([1, 2], "float32", name="x")])
    from paddle_tpu.onnx import onnx_subset_pb2 as pb
    m = pb.ModelProto()
    m.ParseFromString(open(p, "rb").read())
    assert m.graph.node and m.graph.initializer


@pytest.fixture()
def two_servers():
    from paddle_tpu.distributed.ps import ShardedPSClient
    cfg = {"tables": {0: {"type": "sparse", "dim": 4, "lr": 1.0},
                      1: {"type": "dense", "shape": [3], "lr": 1.0}}}
    rts = []
    for _ in range(2):
        rt = TheOnePSRuntime("server", cfg)
        rt.init_server()
        rts.append(rt)
    client = ShardedPSClient([rt.server_address for rt in rts])
    yield rts, client
    client.stop_server()
    client.close()
    for rt in rts:
        rt.stop()


def test_sharded_client_two_servers(two_servers):
    rts, client = two_servers
    assert client.num_shards == 2
    ids = [0, 1, 2, 3, 10, 11]
    rows = client.pull_sparse(0, ids)
    assert rows.shape == (6, 4)
    # push a distinct gradient per id and verify SGD applied shard-wise
    grads = np.arange(24, dtype=np.float32).reshape(6, 4)
    client.push_sparse(0, ids, grads)
    after = client.pull_sparse(0, ids)
    np.testing.assert_allclose(after, rows - grads, rtol=1e-6)
    # rows physically live on the id%2 server — even ids only on shard 0
    direct0 = PSClient(rts[0].server_address)
    even_rows = direct0.pull_sparse(0, [0, 2, 10])
    np.testing.assert_allclose(np.asarray(even_rows),
                               after[[0, 2, 4]], rtol=1e-6)
    direct0.close()
    # dense routes by table_id
    d = client.pull_dense(1)
    client.push_dense(1, np.ones(3, np.float32))
    np.testing.assert_allclose(client.pull_dense(1), np.asarray(d) - 1.0)


def test_async_communicator_overlap_and_flush(two_servers):
    from paddle_tpu.distributed.ps import Communicator
    _rts, client = two_servers
    comm = Communicator(client)
    base = client.pull_sparse(0, [5, 6])
    for _ in range(10):
        comm.push_sparse_async(0, [5, 6], np.ones((2, 4), np.float32))
    comm.flush()  # barrier: every queued push applied
    after = client.pull_sparse(0, [5, 6])
    np.testing.assert_allclose(after, np.asarray(base) - 10.0, rtol=1e-6)
    comm.stop()


def test_async_ps_embedding_trains():
    from paddle_tpu.distributed.ps import AsyncPSEmbedding, ShardedPSClient
    cfg = {"tables": {0: {"type": "sparse", "dim": 4, "lr": 0.1}}}
    rts = []
    for _ in range(2):
        rt = TheOnePSRuntime("server", cfg)
        rt.init_server()
        rts.append(rt)
    client = ShardedPSClient([rt.server_address for rt in rts])
    emb = AsyncPSEmbedding(client, 0, 4)
    paddle.seed(0)
    w = paddle.to_tensor(np.ones(4, np.float32))
    ids = np.array([1, 2, 3], np.int64)
    target = paddle.to_tensor(np.zeros(3, np.float32))
    losses = []
    for step in range(30):
        emb.prefetch(paddle.to_tensor(ids))
        e = emb(paddle.to_tensor(ids))
        pred = (e * w).sum(-1)
        loss = ((pred - target) ** 2).mean()
        loss.backward()
        emb.comm.flush()  # sync point before the next pull
        losses.append(float(loss.numpy()))
    assert losses[-1] < 0.05 * losses[0]
    emb.comm.stop()
    client.stop_server()
    client.close()
    for rt in rts:
        rt.stop()


# ------------------------------------------------------------------
# SSD tier + geo-SGD (reference: ps/table/ssd_sparse_table.{h,cc},
# framework/fleet/ps_gpu_wrapper.h:114, the_one_ps.py geo strategy)
# ------------------------------------------------------------------

def test_ssd_table_spills_and_rereads(tmp_path):
    from paddle_tpu.distributed.ps import SSDSparseTable
    t = SSDSparseTable(4, lr=1.0, cache_rows=8,
                       path=str(tmp_path / "cold.bin"))
    ids = list(range(32))
    first = t.pull(ids)           # 32 rows through an 8-row cache
    assert len(t.rows) <= 8 and t.num_cold_rows >= 24
    again = t.pull(ids)           # cold rows page back in unchanged
    np.testing.assert_allclose(again, first)
    t.push(ids, np.ones((32, 4), np.float32))
    np.testing.assert_allclose(t.pull(ids), first - 1.0, rtol=1e-6)
    state = t.all_rows()
    assert len(state) == 32
    np.testing.assert_allclose(state[0], first[0] - 1.0, rtol=1e-6)
    t.close()


def test_ssd_table_adagrad_accumulator_survives_eviction(tmp_path):
    from paddle_tpu.distributed.ps import SSDSparseTable, SparseTable
    ssd = SSDSparseTable(3, lr=0.5, optimizer="adagrad", cache_rows=2,
                         path=str(tmp_path / "cold.bin"), seed=7)
    ram = SparseTable(3, lr=0.5, optimizer="adagrad", seed=7)
    ids = [1, 2, 3, 4, 5]
    # seed both tables with identical initial rows
    ram_rows = ram.pull(ids)
    for k, r in zip(ids, ssd.pull(ids)):
        ram.rows[k] = np.array(ram.rows[k])
    np.testing.assert_allclose(ssd.pull(ids), ram_rows)
    rng = np.random.default_rng(0)
    for _ in range(5):            # repeated pushes evict + reload accums
        g = rng.standard_normal((5, 3)).astype(np.float32)
        ssd.push(ids, g)
        ram.push(ids, g)
    np.testing.assert_allclose(ssd.pull(ids), ram.pull(ids), rtol=1e-5)
    ssd.close()


def test_ssd_table_compaction_preserves_state(tmp_path):
    from paddle_tpu.distributed.ps import SSDSparseTable
    t = SSDSparseTable(4, lr=1.0, cache_rows=4,
                       path=str(tmp_path / "cold.bin"))
    ids = list(range(16))
    base = t.pull(ids)
    for _ in range(6):            # churn: many abandoned records
        t.push(ids, np.ones((16, 4), np.float32))
    t.compact()
    from paddle_tpu.distributed.ps import _SB
    assert t._dead_bytes == 0 and \
        t._end == _SB.size + len(t._index) * t._rec_total
    np.testing.assert_allclose(t.pull(ids), base - 6.0, rtol=1e-6)
    t.close()


def test_ssd_table_over_the_wire(tmp_path):
    cfg = {"tables": {0: {"type": "ssd_sparse", "dim": 4, "lr": 1.0,
                          "cache_rows": 4,
                          "path": str(tmp_path / "srv_cold.bin")}}}
    rt = TheOnePSRuntime("server", cfg)
    rt.init_server()
    client = PSClient(rt.server_address)
    ids = list(range(12))
    rows = client.pull_sparse(0, ids)
    client.push_sparse(0, ids, np.ones((12, 4), np.float32))
    np.testing.assert_allclose(client.pull_sparse(0, ids), rows - 1.0,
                               rtol=1e-6)
    state = client.save()
    assert len(state[0]) == 12    # save sees cold rows too
    client.stop_server()
    client.close()
    rt.stop()


def test_geo_sgd_two_workers_merge_deltas():
    from paddle_tpu.distributed.ps import GeoSGDCommunicator
    cfg = {"tables": {0: {"type": "sparse", "dim": 2, "lr": 1.0}}}
    rt = TheOnePSRuntime("server", cfg)
    rt.init_server()
    c1, c2 = PSClient(rt.server_address), PSClient(rt.server_address)
    g1 = GeoSGDCommunicator(c1, 0, 2, lr=1.0, geo_step=3)
    g2 = GeoSGDCommunicator(c2, 0, 2, lr=1.0, geo_step=3)
    base = g1.pull([7])
    _ = g2.pull([7])              # both workers share the server row
    for _ in range(3):            # 3 pushes → one sync each
        g1.push([7], np.full((1, 2), 1.0, np.float32))
        g2.push([7], np.full((1, 2), 2.0, np.float32))
    # between-sync pushes were local-only; after both synced, the server
    # row carries BOTH workers' movement: -3*1 + -3*2 = -9
    probe = PSClient(rt.server_address)
    np.testing.assert_allclose(probe.pull_sparse(0, [7]), base - 9.0,
                               rtol=1e-6)
    # a fresh sync folds the other worker's delta into each local copy
    g1.sync(); g2.sync()
    g1._dirty.add(7); g1.sync()
    np.testing.assert_allclose(g1.pull([7]), base - 9.0, rtol=1e-6)
    for c in (probe, c2):
        c.close()
    c1.stop_server()
    c1.close()
    rt.stop()


def test_geo_sgd_local_pushes_cost_zero_rpcs():
    from paddle_tpu.distributed.ps import GeoSGDCommunicator
    cfg = {"tables": {0: {"type": "sparse", "dim": 2, "lr": 1.0}}}
    rt = TheOnePSRuntime("server", cfg)
    rt.init_server()
    client = PSClient(rt.server_address)
    geo = GeoSGDCommunicator(client, 0, 2, lr=1.0, geo_step=100)
    geo.pull([1])
    calls = {"n": 0}
    orig = client._call
    client._call = lambda **kw: (calls.__setitem__("n", calls["n"] + 1),
                                 orig(**kw))[1]
    origb = client._call_binary
    client._call_binary = lambda *a, **kw: (
        calls.__setitem__("n", calls["n"] + 1), origb(*a, **kw))[1]
    for _ in range(10):           # all below geo_step: purely local
        geo.push([1], np.ones((1, 2), np.float32))
        geo.pull([1])
    assert calls["n"] == 0
    geo.sync()
    assert calls["n"] == 2        # one delta push + one refresh pull
    client._call = orig
    client.stop_server()
    client.close()
    rt.stop()


def test_ssd_table_default_path_and_clean_eviction(tmp_path):
    from paddle_tpu.distributed.ps import SSDSparseTable
    # default path=None must yield a live, usable temp-backed table
    t = SSDSparseTable(4, lr=1.0, cache_rows=4)
    first = t.pull(list(range(12)))
    np.testing.assert_allclose(t.pull(list(range(12))), first)
    # read-mostly workload: clean evictions re-use the existing cold
    # record — the file must NOT grow across repeated pulls
    end_before = t._end
    for _ in range(5):
        t.pull(list(range(12)))
    assert t._end == end_before
    import os
    t.close()
    os.unlink(t.path)


def test_ssd_table_reopen_rebuilds_index(tmp_path):
    """The cold log is self-describing ([magic,key,crc] headers): a fresh
    process reopening the path rebuilds the {id -> offset} index by
    scanning, later records winning (reference: rocksdb recovery in
    ssd_sparse_table.cc)."""
    from paddle_tpu.distributed.ps import SSDSparseTable
    path = str(tmp_path / "t.bin")
    t = SSDSparseTable(4, lr=1.0, cache_rows=4, path=path,
                       initializer=lambda: np.zeros(4, np.float32))
    ids = list(range(12))
    t.pull(ids)
    t.push(ids, np.ones((12, 4), np.float32))     # rows -> -1
    t.flush()
    t.close()

    t2 = SSDSparseTable(4, lr=1.0, cache_rows=4, path=path,
                        initializer=lambda: np.zeros(4, np.float32))
    np.testing.assert_allclose(t2.pull(ids), -np.ones((12, 4)))
    t2.close()


def test_ssd_table_truncates_torn_tail(tmp_path):
    """A crash mid-record-write leaves a torn tail; recovery must stop at
    the first bad magic/crc and truncate, keeping every complete
    record."""
    from paddle_tpu.distributed.ps import SSDSparseTable
    path = str(tmp_path / "t.bin")
    t = SSDSparseTable(4, lr=1.0, cache_rows=2, path=path, wal=False,
                       initializer=lambda: np.zeros(4, np.float32))
    ids = list(range(6))
    t.pull(ids)
    t.push(ids, np.ones((6, 4), np.float32))
    t.flush()
    t.close()
    # simulate the torn write: append half a record of garbage
    with open(path, "ab") as f:
        f.write(b"PTS2" + b"\x00" * 10)

    t2 = SSDSparseTable(4, lr=1.0, cache_rows=2, path=path, wal=False,
                        initializer=lambda: np.zeros(4, np.float32))
    np.testing.assert_allclose(t2.pull(ids), -np.ones((6, 4)))
    from paddle_tpu.distributed.ps import _SB
    assert (t2._end - _SB.size) % t2._rec_total == 0
    t2.close()


def test_ssd_table_kill_during_push_recovers_acked(tmp_path):
    """VERDICT r04 item 7: SIGKILL a worker mid-push-storm; every push it
    ACKNOWLEDGED (reported on stdout) must survive via WAL replay.  Row k
    is pushed +1 per acknowledged round with lr=1, so after recovery
    row k == -(acked rounds)."""
    import signal
    import subprocess
    import sys
    import time

    path = str(tmp_path / "t.bin")
    code = f"""
import sys
import numpy as np
from paddle_tpu.distributed.ps import SSDSparseTable
t = SSDSparseTable(4, lr=1.0, cache_rows=8, path={path!r},
                   initializer=lambda: np.zeros(4, np.float32))
ids = list(range(32))
t.pull(ids)
for round_i in range(10000):
    t.push(ids, np.ones((32, 4), np.float32))
    print(round_i + 1, flush=True)     # ack AFTER the push returned
"""
    env = dict(__import__("os").environ,
               JAX_PLATFORMS="cpu")
    p = subprocess.Popen([sys.executable, "-c", code],
                         stdout=subprocess.PIPE, env=env, text=True)
    acked = 0
    deadline = time.time() + 120
    while acked < 25 and time.time() < deadline:
        line = p.stdout.readline()
        if line.strip().isdigit():
            acked = int(line.strip())
    p.send_signal(signal.SIGKILL)
    p.wait()
    # drain anything acked between the last read and the kill
    for line in p.stdout.read().splitlines():
        if line.strip().isdigit():
            acked = max(acked, int(line.strip()))
    assert acked >= 25

    from paddle_tpu.distributed.ps import SSDSparseTable
    t = SSDSparseTable(4, lr=1.0, cache_rows=8, path=path,
                       initializer=lambda: np.zeros(4, np.float32))
    rows = t.pull(list(range(32)))
    # every acknowledged round recovered; at most one un-acked round
    # (in flight at the kill) beyond
    assert np.all(rows <= -acked + 1e-5), rows.max()
    assert np.all(rows >= -(acked + 1) - 1e-5), rows.min()
    t.close()


def test_ssd_table_geometry_mismatch_errors(tmp_path):
    """Reopening with a different dim/optimizer must ERROR (superblock
    guard), not silently truncate the log to zero."""
    import pytest
    from paddle_tpu.distributed.ps import SSDSparseTable
    path = str(tmp_path / "t.bin")
    t = SSDSparseTable(4, lr=1.0, cache_rows=2, path=path)
    t.pull([1, 2, 3])
    t.flush()
    t.close()
    with pytest.raises(ValueError, match="geometry mismatch"):
        SSDSparseTable(8, lr=1.0, cache_rows=2, path=path)
    with pytest.raises(ValueError, match="geometry mismatch"):
        SSDSparseTable(4, lr=1.0, optimizer="adagrad", cache_rows=2,
                       path=path)


def test_ssd_table_wal_false_with_pending_wal_errors(tmp_path):
    """wal=False on a path whose WAL holds unflushed acknowledged updates
    must refuse: skipping replay would drop them now and replay stale
    entries over newer state later."""
    import pytest
    from paddle_tpu.distributed.ps import SSDSparseTable
    path = str(tmp_path / "t.bin")
    t = SSDSparseTable(4, lr=1.0, cache_rows=8, path=path,
                       initializer=lambda: np.zeros(4, np.float32))
    t.pull([1, 2])
    t.push([1, 2], np.ones((2, 4), np.float32))
    # simulate crash: close file handles WITHOUT flush
    t._file.close()
    t._wal.close()
    with pytest.raises(ValueError, match="write-ahead log"):
        SSDSparseTable(4, lr=1.0, cache_rows=8, path=path, wal=False)
    # wal=True recovers it
    t2 = SSDSparseTable(4, lr=1.0, cache_rows=8, path=path,
                        initializer=lambda: np.zeros(4, np.float32))
    np.testing.assert_allclose(t2.pull([1, 2]), -np.ones((2, 4)))
    t2.close()
