"""paddle_tpu.data pipeline (ISSUE 18): stage state round-trips,
mid-epoch bit-exact fit resume, dp-resize continuation, prefetch
bit-identity, packing correctness against a per-document forward,
corrupt-record policy, goodput telemetry, and the DataLoader
satellites (streaming threaded lane, timeout, warn-once, set_epoch)."""
import threading
import time
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import data as D
from paddle_tpu import nn
from paddle_tpu.data import CorruptRecordError, PipelineConfigError
from paddle_tpu.data.pipeline import PipelineStateError
from paddle_tpu.io import (DataLoader, DataLoaderTimeoutError,
                           DataLoaderWarning)
from paddle_tpu.io.sampler import BatchSampler, DistributedBatchSampler
from paddle_tpu.utils import flags


class _IdDataset:
    def __init__(self, n):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return np.int64(i)


def _drain_ids(pipe, batches=None):
    out = []
    it = iter(pipe)
    while batches is None or len(out) < batches:
        try:
            b = next(it)
        except StopIteration:
            break
        out.append([int(v) for v in np.asarray(b._data)])
    return out


# ---------------------------------------------------------------------------
# pipeline core: determinism, state, resize
# ---------------------------------------------------------------------------


def test_pipeline_epoch_is_seeded_permutation_and_reseeds():
    mk = lambda: (D.pipeline(_IdDataset(24)).shard(0, 1)  # noqa: E731
                  .shuffle(seed=7).batch(4))
    a = sum(_drain_ids(mk()), [])
    b = sum(_drain_ids(mk()), [])
    assert a == b                               # same seed, same order
    assert sorted(a) == list(range(24))         # a permutation
    assert a != list(range(24))                 # actually shuffled
    p = mk()
    e0 = sum(_drain_ids(p), [])
    e1 = sum(_drain_ids(p), [])                 # second epoch reseeds
    assert sorted(e1) == list(range(24)) and e1 != e0


def test_pipeline_state_roundtrip_mid_epoch():
    mk = lambda: (D.pipeline(_IdDataset(32)).shard(0, 1)  # noqa: E731
                  .shuffle(seed=3).batch(4))
    ref = _drain_ids(mk())
    p1 = mk()
    head = _drain_ids(p1, batches=3)
    sd = p1.state_dict()
    assert sd["version"] == 1
    assert sd["stages"]["shard"]["global_position"] == 12
    # state is tiny and derivational: seeds + counters, no buffers
    assert not any(isinstance(v, (list, np.ndarray))
                   for v in sd["stages"]["shard"].values())
    p2 = mk().load_state_dict(sd)
    tail = _drain_ids(p2)
    assert head + tail == ref


def test_pipeline_state_rejects_bad_payloads():
    p = D.pipeline(_IdDataset(8)).shard(0, 1).shuffle(seed=1).batch(2)
    with pytest.raises(PipelineStateError):
        p.load_state_dict({"version": 99, "stages": {}})
    with pytest.raises(PipelineStateError):
        p.load_state_dict({"version": 1, "stages": {
            "shuffle": {"seed": 2}}})     # seed mismatch refuses loudly
    with pytest.raises(PipelineStateError):
        p.load_state_dict({"version": 1, "stages": {
            "shard": {"epoch": -1, "global_position": 0}}})


def test_pipeline_stage_order_enforced():
    with pytest.raises(PipelineConfigError):
        D.pipeline(_IdDataset(8)).batch(2).shuffle(seed=0)
    with pytest.raises(PipelineConfigError):
        D.pipeline(_IdDataset(8)).device_prefetch(2)
    with pytest.raises(PipelineConfigError):
        D.pipeline(_IdDataset(8)).shard(3, 2)
    with pytest.raises(TypeError):
        len(D.pipeline(_IdDataset(8)).pack(4))


def test_resize_4_to_2_no_lost_no_duplicated_ids():
    n = 48
    mk = lambda r, d: (D.pipeline(_IdDataset(n))  # noqa: E731
                       .shard(r, d).shuffle(seed=5).batch(2))
    consumed, state = [], None
    for r in range(4):                        # 4-rank world, 3 batches each
        p = mk(r, 4)
        consumed += sum(_drain_ids(p, batches=3), [])
        state = p.state_dict()
    assert state["stages"]["shard"]["global_position"] == 24
    for r in range(2):                        # resumed 2-rank world drains
        p = mk(r, 2).load_state_dict(state)
        consumed += sum(_drain_ids(p), [])
    assert sorted(consumed) == list(range(n))  # zero lost, zero duplicated


def test_prefetch_yields_bit_identical_batches():
    sync = (D.pipeline(_IdDataset(40)).shard(0, 1).shuffle(seed=2)
            .batch(5))
    pf = (D.pipeline(_IdDataset(40)).shard(0, 1).shuffle(seed=2)
          .batch(5).device_prefetch(3))
    a = [np.asarray(b._data) for b in sync]
    b = [np.asarray(x._data) for x in pf]
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert pf.goodput.snapshot()["batches"] == len(b)


# ---------------------------------------------------------------------------
# mid-epoch fit resume (bit-exact, eager)
# ---------------------------------------------------------------------------


class _RegressionDS:
    def __len__(self):
        return 64

    def __getitem__(self, i):
        rng = np.random.default_rng(i)
        x = rng.standard_normal(8).astype(np.float32)
        return x, np.float32(x.sum())


def _fit_losses(ckpt_dir, resume=None, num_iters=None, save_mid=False):
    from paddle_tpu.hapi.callbacks import ModelCheckpoint
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
    m = paddle.Model(net)
    m.prepare(paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=net.parameters()),
              nn.MSELoss())
    pipe = (D.pipeline(_RegressionDS()).shard(0, 1).shuffle(seed=11)
            .batch(8).device_prefetch(2))
    losses = []

    class L(paddle.hapi.callbacks.Callback):
        def on_train_batch_end(self, step, logs=None):
            losses.append(float(logs.get("loss")))

    cbs = [L()]
    ck = None
    if save_mid:
        ck = ModelCheckpoint(save_freq=10**9, save_dir=ckpt_dir)
        cbs.append(ck)
    m.fit(pipe, epochs=2, verbose=0, log_freq=1, callbacks=cbs,
          num_iters=num_iters, resume=resume,
          save_dir=None if save_mid else str(ckpt_dir))
    if save_mid:
        m._sync_compiled_state()
        ck.save_now(next_epoch=pipe.epoch)
        ck.manager.wait()
    return losses


@pytest.mark.parametrize("compiled", [0, 1], ids=["eager", "compiled"])
def test_fit_resumes_mid_epoch_bit_exact(tmp_path, compiled):
    flags.set_flags({"FLAGS_compiled_train_step": compiled})
    try:
        ref = _fit_losses(tmp_path / "ref")
        head = _fit_losses(tmp_path / "ck", num_iters=5, save_mid=True)
        tail = _fit_losses(tmp_path / "ck", resume=True)
        assert len(head) == 5 and len(head + tail) == len(ref)
        if compiled:
            # the compiled step reassociates reductions (docs/DATA.md)
            np.testing.assert_allclose(head + tail, ref, rtol=0,
                                       atol=5e-6)
        else:
            assert head + tail == ref  # float equality == bitwise here
    finally:
        flags.set_flags({"FLAGS_compiled_train_step": 1})


# ---------------------------------------------------------------------------
# packing: segment-masked attention == per-document forward
# ---------------------------------------------------------------------------


def _masked_attention(emb, segments):
    """Single-head causal attention restricted to same-segment pairs."""
    S = emb.shape[0]
    scores = emb @ emb.T / np.sqrt(emb.shape[1])
    q = np.arange(S)
    mask = ((segments[:, None] == segments[None, :])
            & (segments[:, None] > 0)
            & (q[:, None] >= q[None, :]))
    scores = np.where(mask, scores, -1e30)
    w = np.exp(scores - scores.max(axis=1, keepdims=True))
    w = w / w.sum(axis=1, keepdims=True)
    return w @ emb


def test_pack_rows_and_segment_masked_attention_match_per_doc():
    rng = np.random.default_rng(0)
    docs = [rng.integers(1, 50, (ln,)).astype(np.int64)
            for ln in (3, 5, 2, 6, 4, 1, 7, 2)]

    class Docs:
        def __len__(self):
            return len(docs)

        def __getitem__(self, i):
            return docs[i]

    S = 8
    pipe = D.pipeline(Docs()).shard(0, 1).pack(S).batch(1)
    rows = []
    for b in pipe:
        rows.append({k: np.asarray(v._data)[0] for k, v in b.items()})
    placed = 0
    table = rng.standard_normal((50, 4)).astype(np.float64)
    for row in rows:
        toks, segs, poss = (row["tokens"], row["segment_ids"],
                            row["positions"])
        assert toks.shape == (S,) and segs.shape == (S,)
        emb = table[toks] + 0.1 * poss[:, None]
        packed_out = _masked_attention(emb, segs)
        for seg in sorted(set(segs[segs > 0])):
            idx = np.where(segs == seg)[0]
            # positions reset per document
            np.testing.assert_array_equal(poss[idx],
                                          np.arange(len(idx)))
            doc_emb = table[toks[idx]] + 0.1 * np.arange(
                len(idx))[:, None]
            solo = _masked_attention(doc_emb,
                                     np.ones(len(idx), dtype=np.int64))
            np.testing.assert_allclose(packed_out[idx], solo,
                                       rtol=1e-12, atol=1e-12)
            placed += 1
    # every token of every doc was packed exactly once (none dropped)
    packed_tokens = sorted(t for row in rows
                           for t, s in zip(row["tokens"],
                                           row["segment_ids"]) if s > 0)
    assert packed_tokens == sorted(
        int(t) for d in docs for t in d)


def test_pack_carry_checkpoints_as_pointer_and_resumes():
    rng = np.random.default_rng(1)
    docs = [rng.integers(1, 9, (ln,)).astype(np.int64)
            for ln in (3, 5, 4, 6, 2, 5, 3, 4)]

    class Docs:
        def __len__(self):
            return len(docs)

        def __getitem__(self, i):
            return docs[i]

    mk = lambda: (D.pipeline(Docs()).shard(0, 1)  # noqa: E731
                  .shuffle(seed=4).pack(6).batch(1))
    ref = [np.asarray(b["tokens"]._data) for b in mk()]
    p1 = mk()
    it = iter(p1)
    head = [np.asarray(next(it)["tokens"]._data) for _ in range(2)]
    sd = p1.state_dict()
    carry = sd["stages"]["pack"]["carry"]
    if carry is not None:                     # pointer, never tokens
        assert len(carry) == 2 and all(isinstance(c, int) for c in carry)
    tail = [np.asarray(b["tokens"]._data)
            for b in mk().load_state_dict(sd)]
    got = head + tail
    assert len(got) == len(ref)
    for x, y in zip(got, ref):
        np.testing.assert_array_equal(x, y)


# ---------------------------------------------------------------------------
# corrupt records + goodput fault drills
# ---------------------------------------------------------------------------


def test_corrupt_records_skipped_then_typed_error_past_threshold():
    flags.set_flags({"FLAGS_fault_inject": "data_corrupt:at_sample=3"})
    try:
        pipe = D.pipeline(_IdDataset(16), corrupt_threshold=4) \
            .shard(0, 1).batch(4)
        ids = sum(_drain_ids(pipe), [])
        assert 3 not in ids and len(ids) == 12  # skipped + drop_last
        assert pipe.records_skipped == 1
    finally:
        flags.set_flags({"FLAGS_fault_inject": ""})
    flags.set_flags({"FLAGS_fault_inject": "data_corrupt:every=2"})
    try:
        pipe = D.pipeline(_IdDataset(64), corrupt_threshold=4) \
            .shard(0, 1).batch(4)
        with pytest.raises(CorruptRecordError) as ei:
            _drain_ids(pipe)
        assert ei.value.skipped == 5 and ei.value.threshold == 4
        assert "corrupt" in str(ei.value)
    finally:
        flags.set_flags({"FLAGS_fault_inject": ""})


def test_data_slow_injection_moves_starvation_telemetry():
    flags.set_flags({"FLAGS_fault_inject": "data_slow:delay_s=0.003"})
    try:
        pipe = (D.pipeline(_IdDataset(48)).shard(0, 1).batch(8)
                .device_prefetch(2))
        for _ in pipe:
            pass
        snap = pipe.goodput.snapshot()
        assert snap["starved_steps"] > 0
        assert 0.0 < snap["input_bound"] <= 1.0
        assert snap["batches"] == 6
    finally:
        flags.set_flags({"FLAGS_fault_inject": ""})


def test_step_metrics_snapshot_carries_goodput(tmp_path):
    flags.set_flags({"FLAGS_compiled_train_step": 0})
    try:
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 4), nn.ReLU(), nn.Linear(4, 1))
        m = paddle.Model(net)
        m.prepare(paddle.optimizer.SGD(learning_rate=0.1,
                                       parameters=net.parameters()),
                  nn.MSELoss())
        pipe = (D.pipeline(_RegressionDS()).shard(0, 1).batch(16)
                .device_prefetch(2))
        m.fit(pipe, epochs=1, verbose=0)
        snap = m.step_metrics.snapshot()
        assert "data" in snap
        assert snap["data"]["batches"] == 4
        assert 0.0 <= snap["data"]["input_bound"] <= 1.0
    finally:
        flags.set_flags({"FLAGS_compiled_train_step": 1})


# ---------------------------------------------------------------------------
# DataLoader satellites
# ---------------------------------------------------------------------------


class _CountingDS:
    """Counts __getitem__ calls; optionally raises at one index or
    sleeps past one index."""

    def __init__(self, n, raise_at=None, sleep_from=None, sleep_s=0.0):
        self.n = n
        self.raise_at = raise_at
        self.sleep_from = sleep_from
        self.sleep_s = sleep_s
        self.calls = 0
        self._lock = threading.Lock()

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        with self._lock:
            self.calls += 1
        if self.raise_at is not None and i == self.raise_at:
            raise ValueError(f"poisoned sample {i}")
        if self.sleep_from is not None and i >= self.sleep_from:
            time.sleep(self.sleep_s)
        return np.float32(i)


def test_threaded_loader_streams_lazily_and_in_order():
    ds = _CountingDS(256)
    dl = DataLoader(ds, batch_size=4, shuffle=False, num_workers=2,
                    use_shared_memory=False, prefetch_factor=2)
    it = iter(dl)
    first = np.asarray(next(it)._data)
    np.testing.assert_array_equal(first, [0, 1, 2, 3])
    # bounded prefetch: far fewer than the whole epoch materialized
    assert ds.calls < 256 // 2
    rest = [np.asarray(b._data) for b in it]
    got = np.concatenate([first] + rest)
    np.testing.assert_array_equal(got, np.arange(256))  # in-order


def test_threaded_loader_propagates_worker_exception_at_position():
    ds = _CountingDS(64, raise_at=21)          # poisons batch 5
    dl = DataLoader(ds, batch_size=4, shuffle=False, num_workers=2,
                    use_shared_memory=False)
    seen = []
    with pytest.raises(ValueError, match="poisoned sample 21"):
        for b in dl:
            seen.append(np.asarray(b._data))
    assert len(seen) == 5                      # batches 0..4 delivered


def test_multiprocess_loader_propagates_worker_crash():
    ds = _CountingDS(16, raise_at=5)
    dl = DataLoader(ds, batch_size=4, shuffle=False, num_workers=2,
                    use_shared_memory=True)
    # shm lane wraps the failure in RuntimeError; the threaded fallback
    # (no g++ on the box) re-raises the original ValueError
    with pytest.raises((RuntimeError, ValueError)):
        list(dl)


def test_loader_timeout_is_typed_and_names_the_batch():
    ds = _CountingDS(16, sleep_from=4, sleep_s=5.0)
    dl = DataLoader(ds, batch_size=4, shuffle=False, num_workers=1,
                    use_shared_memory=False, timeout=0.4)
    it = iter(dl)
    next(it)                                   # batch 0 arrives fast
    with pytest.raises(DataLoaderTimeoutError) as ei:
        next(it)
    assert ei.value.batch_index == 1
    assert "batch 1" in str(ei.value)
    with pytest.raises(ValueError):
        DataLoader(ds, timeout=-1)


def test_unsupported_loader_args_warn_once_typed():
    from paddle_tpu.io import dataloader as dl_mod
    dl_mod._WARNED_ARGS.discard("persistent_workers")
    ds = _CountingDS(8)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        DataLoader(ds, persistent_workers=True)
        DataLoader(ds, persistent_workers=True)
    typed = [x for x in w if issubclass(x.category, DataLoaderWarning)]
    assert len(typed) == 1
    assert "persistent_workers" in str(typed[0].message)


def test_batch_sampler_set_epoch_folds_seed():
    mk = lambda: BatchSampler(_IdDataset(32), shuffle=True,  # noqa: E731
                              batch_size=4, seed=13)
    a, b = mk(), mk()
    a.set_epoch(2)
    b.set_epoch(2)
    assert list(a) == list(b)                  # same epoch, same order
    b.set_epoch(3)
    assert list(a) != list(b)                  # reseeds per epoch
    dbs = DistributedBatchSampler(_IdDataset(32), batch_size=4,
                                  num_replicas=1, rank=0, shuffle=True,
                                  seed=7)
    dbs.set_epoch(5)
    want = np.random.RandomState(7 + 5).permutation(32).tolist()
    got = [i for batch in dbs for i in batch]
    assert got == want


def test_fit_calls_set_epoch_on_batch_sampler(tmp_path):
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 1))
    m = paddle.Model(net)
    m.prepare(paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=net.parameters()),
              nn.MSELoss())
    seen = []

    class Spy(BatchSampler):
        def set_epoch(self, epoch):
            seen.append(epoch)
            super().set_epoch(epoch)

    dl = DataLoader(_RegressionDS(),
                    batch_sampler=Spy(_RegressionDS(), shuffle=True,
                                      batch_size=16, seed=3))
    m.fit(dl, epochs=3, verbose=0)
    assert seen == [0, 1, 2]
