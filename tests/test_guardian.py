"""Hang & failure guardian (ISSUE 5): collective watchdog, cross-rank
error trap, desync detector, host-collective fallback, serving drain and
scheduler watchdog, rpc/ps timeout satellites.  Subprocess drills ride
tests/_guardian_worker.py and tests/_serving_drain_worker.py."""
import json
import os
import subprocess
import sys
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

import paddle_tpu as paddle  # noqa: F401  (backend init)
from paddle_tpu.utils.flags import get_flags, set_flags
from paddle_tpu.distributed import watchdog as wd
from paddle_tpu.distributed.store import FileKVStore

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GUARDIAN_WORKER = os.path.join(REPO, "tests", "_guardian_worker.py")
DRAIN_WORKER = os.path.join(REPO, "tests", "_serving_drain_worker.py")

_GUARDIAN_FLAGS = (
    "FLAGS_collective_timeout_s", "FLAGS_collective_hard_abort",
    "FLAGS_stall_dump_path", "FLAGS_desync_check_every",
    "FLAGS_fault_inject")


@pytest.fixture(autouse=True)
def _dumps_into_tmp(tmp_path):
    """Crash-hook and stall dumps land in tmp, not the repo root (every
    deliberately-crashed scheduler thread in this file would otherwise
    litter the working directory with flight_recorder.<pid>.json)."""
    saved = get_flags(["FLAGS_flight_recorder_path",
                       "FLAGS_stall_dump_path"])
    set_flags({
        "FLAGS_flight_recorder_path": str(tmp_path / "flightrec.json"),
        "FLAGS_stall_dump_path": str(tmp_path / "stall.json"),
    })
    yield
    set_flags(saved)


@pytest.fixture
def guardian():
    """Clean watchdog state + flag restoration around each test."""
    saved = get_flags(list(_GUARDIAN_FLAGS))
    wd.reset()
    yield wd
    wd.reset()
    set_flags(saved)


class _FakeGroup:
    def __init__(self, gid=0, ranks=(0, 1)):
        self.id = gid
        self.ranks = list(ranks)
        self.nranks = len(self.ranks)


# ---------------------------------------------------------------------------
# fault-injection grammar
# ---------------------------------------------------------------------------


def test_collective_fault_points_parse_and_validate():
    from paddle_tpu.utils import fault_injection as fi
    spec = fi.parse("collective_delay:op=all_reduce,at_seq=6,"
                    "delay_s=1.5,rank=1;rank_crash:at_seq=3,rank=0,"
                    "once_file=/tmp/x")
    assert spec["collective_delay"]["delay_s"] == 1.5
    assert spec["collective_delay"]["op"] == "all_reduce"
    assert spec["rank_crash"]["once_file"] == "/tmp/x"
    for bad in ("collective_delay:nope=1", "rank_crash:at_seq=xyz"):
        with pytest.raises(fi.FaultSpecError):
            fi.parse(bad)


# ---------------------------------------------------------------------------
# FileKVStore + ErrorTrap
# ---------------------------------------------------------------------------


def test_file_kv_store_roundtrip(tmp_path):
    st = FileKVStore(str(tmp_path))
    st.set("job/error/0", b"payload")
    assert st.get("job/error/0") == b"payload"
    assert st.get("missing", b"d") == b"d"
    assert st.add("cnt", 2) == 2 and st.add("cnt", 3) == 5
    assert st.list_prefix("job/error/") == {"job/error/0": b"payload"}
    st.delete_key("job/error/0")
    assert st.list_prefix("job/error/") == {}


def test_error_trap_report_peers_clear(tmp_path):
    st = FileKVStore(str(tmp_path))
    t0 = wd.ErrorTrap(st, job="j", rank=0)
    t1 = wd.ErrorTrap(st, job="j", rank=1)
    try:
        raise ValueError("boom at step 3")
    except ValueError as e:
        t1.report(e, op="all_reduce", seq=7)
    assert t1.peers() == []          # own record is not a peer error
    (rec,) = t0.peers()
    assert rec["rank"] == 1 and rec["type"] == "ValueError"
    assert rec["op"] == "all_reduce" and rec["seq"] == 7
    assert "boom at step 3" in rec["traceback"]
    t0.record_arrival(0, 5, "all_reduce")
    assert t1.arrivals(0) == {0: (5, "all_reduce")}
    t0.clear()
    assert t0.peers() == [] and t1.arrivals(0) == {}


# ---------------------------------------------------------------------------
# collective watchdog
# ---------------------------------------------------------------------------


def test_watchdog_zero_overhead_when_off(guardian):
    set_flags({"FLAGS_collective_timeout_s": 0.0,
               "FLAGS_fault_inject": ""})
    assert wd.begin("all_reduce", _FakeGroup()) is None
    wd.end(None)                     # no-ops must accept the None token
    wd.preflight(None)
    assert wd.translate(None, KeyError("x")).args == ("x",)


def test_watchdog_times_out_blocked_collective(guardian, tmp_path):
    stall_path = str(tmp_path / "stall.json")
    set_flags({"FLAGS_collective_timeout_s": 0.3,
               "FLAGS_collective_hard_abort": False,
               "FLAGS_stall_dump_path": stall_path})
    store = FileKVStore(str(tmp_path / "kv"))
    wd.configure(store=store, job="j", rank=0)
    caught = {}

    def blocked():
        tok = wd.begin("all_reduce", _FakeGroup(gid=3))
        try:
            wd.preflight(tok)
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                time.sleep(0.01)
        except BaseException as e:
            caught["exc"] = wd.translate(tok, e)
        finally:
            wd.end(tok)

    t = threading.Thread(target=blocked)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive(), "watchdog never aborted the stalled thread"
    exc = caught["exc"]
    assert isinstance(exc, wd.CollectiveTimeoutError)
    assert exc.op == "all_reduce" and exc.seq == 0
    assert exc.missing_ranks == [1]      # rank 1 never wrote an arrival
    assert exc.waited_s >= 0.3
    # the stall dump passes the CI schema gate
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        from check_telemetry import check_stall_dump
    finally:
        sys.path.pop(0)
    dump_path = wd.stall_dump_path()
    assert dump_path.endswith(".rank0.json")
    assert check_stall_dump(dump_path) == []
    data = json.load(open(dump_path))
    assert data["stall"]["missing_ranks"] == [1]
    assert any("blocked" in "".join(th["stack"])
               for th in data["stall"]["threads"])


def test_watchdog_peer_error_aborts_before_timeout(guardian, tmp_path):
    set_flags({"FLAGS_collective_timeout_s": 30.0,
               "FLAGS_collective_hard_abort": False})
    store = FileKVStore(str(tmp_path))
    wd.configure(store=store, job="j", rank=0)
    wd.ErrorTrap(store, job="j", rank=1).report(
        RuntimeError("rank 1 exploded"), op="all_gather", seq=4)
    tok = wd.begin("all_reduce", _FakeGroup())
    with pytest.raises(wd.PeerFailureError) as ei:
        wd.preflight(tok)            # fail-fast, no timeout wait
    wd.end(tok)
    assert ei.value.rank == 1
    assert ei.value.original_type == "RuntimeError"
    assert "rank 1 exploded" in str(ei.value)


def test_desync_detector_blames_mismatched_op(guardian, tmp_path):
    set_flags({"FLAGS_collective_timeout_s": 0.0,
               "FLAGS_desync_check_every": 1})
    store = FileKVStore(str(tmp_path))
    wd.configure(store=store, job="j", rank=0)
    # rank 1 already recorded a DIFFERENT op at the same (group, seq)
    wd.ErrorTrap(store, job="j", rank=1).record_arrival(5, 0, "all_gather")
    tok = wd.begin("all_reduce", _FakeGroup(gid=5))
    with pytest.raises(wd.DesyncError, match="all_gather"):
        wd.preflight(tok)
    wd.end(tok)


def test_watchdog_hard_aborts_c_blocked_thread(tmp_path):
    """A thread wedged outside the interpreter can't take the async
    exception — the watchdog must hard-exit with its abort code instead
    of letting the process hang."""
    code = (
        "import threading, time\n"
        "import paddle_tpu\n"
        "from paddle_tpu.distributed import watchdog as wd\n"
        "class G:\n"
        "    id = 0\n"
        "    ranks = [0, 1]\n"
        "def blocked():\n"
        "    tok = wd.begin('all_reduce', G)\n"
        "    try:\n"
        "        wd.preflight(tok)\n"
        "        time.sleep(120)   # ONE C call: async-raise can't land\n"
        "    finally:\n"
        "        wd.end(tok)\n"
        "t = threading.Thread(target=blocked)\n"
        "t.start()\n"
        "t.join()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""),
               FLAGS_collective_timeout_s="0.5",
               FLAGS_stall_dump_path=str(tmp_path / "stall.json"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == wd.GUARDIAN_ABORT_EXIT_CODE, r.stderr[-2000:]
    assert "hard-aborting" in r.stderr
    assert os.path.exists(str(tmp_path / "stall.rank0.json"))


# ---------------------------------------------------------------------------
# host-collective fallback store
# ---------------------------------------------------------------------------


def test_host_gather_stacks_in_group_order(tmp_path):
    from paddle_tpu.distributed.host_collectives import HostCollectives
    store = FileKVStore(str(tmp_path))
    hc = HostCollectives(store, job="j")
    group = _FakeGroup(gid=0, ranks=(0,))   # single member: no peer wait
    out = hc.gather(group, np.array([1.0, 2.0], np.float32))
    np.testing.assert_array_equal(out, [[1.0, 2.0]])
    # sequence numbers advance per group
    out = hc.gather(group, np.array([3.0], np.float32))
    np.testing.assert_array_equal(out, [[3.0]])
    assert hc._seq[0] == 2


def test_np_reduce_matches_xla_dtype_semantics():
    from paddle_tpu.distributed.collective import ReduceOp, _np_reduce
    st = np.array([[1, 2], [3, 4]], np.int32)
    assert _np_reduce(ReduceOp.SUM, st).dtype == np.int32
    np.testing.assert_array_equal(_np_reduce(ReduceOp.SUM, st), [4, 6])
    assert _np_reduce(ReduceOp.AVG, st).dtype == np.float32
    f = np.array([[1.0, 2.0], [3.0, 5.0]], np.float32)
    np.testing.assert_allclose(_np_reduce(ReduceOp.AVG, f), [2.0, 3.5])
    np.testing.assert_array_equal(_np_reduce(ReduceOp.MAX, f), [3.0, 5.0])


# ---------------------------------------------------------------------------
# rpc timeout satellite
# ---------------------------------------------------------------------------


def _sleepy(seconds):
    time.sleep(seconds)
    return "done"


def test_rpc_timeout_names_worker():
    from paddle_tpu.distributed import rpc
    from paddle_tpu.distributed.launch.context import free_port
    master = f"127.0.0.1:{free_port()}"
    rpc.init_rpc("guardian_w0", rank=0, world_size=1,
                 master_endpoint=master)
    try:
        with pytest.raises(TimeoutError, match="guardian_w0"):
            rpc.rpc_sync("guardian_w0", _sleepy, args=(30,), timeout=0.4)
        fut = rpc.rpc_async("guardian_w0", _sleepy, args=(30,),
                            timeout=0.4)
        with pytest.raises(TimeoutError):
            fut.result(timeout=30)
        # a fast call under the same timeout still succeeds
        assert rpc.rpc_sync("guardian_w0", _sleepy, args=(0.01,),
                            timeout=10) == "done"
    finally:
        rpc.shutdown()


# ---------------------------------------------------------------------------
# ps flush satellite
# ---------------------------------------------------------------------------


class _WedgedClient:
    def __init__(self):
        self.release = threading.Event()

    def push_sparse(self, table_id, ids, grad):
        self.release.wait(60)

    def push_dense(self, table_id, grad):
        pass


def test_ps_flush_timeout_raises_instead_of_fake_barrier():
    from paddle_tpu.distributed.ps import Communicator, PSFlushTimeoutError
    from paddle_tpu.utils import monitor
    before = monitor.all_stats().get("ps.flush_timeouts", 0)
    cli = _WedgedClient()
    comm = Communicator(cli)
    comm.push_sparse_async(0, [1], np.zeros((1, 2), np.float32))
    with pytest.raises(PSFlushTimeoutError, match="timed out"):
        comm.flush(timeout=0.3)
    with pytest.raises(PSFlushTimeoutError, match="failed to stop"):
        comm.stop(timeout=0.3)
    assert monitor.all_stats().get("ps.flush_timeouts", 0) >= before + 2
    cli.release.set()               # let the daemon thread drain out
    comm.flush(timeout=10)          # barrier completes once unwedged


# ---------------------------------------------------------------------------
# serving: drain, pending-futures audit, scheduler watchdog
# ---------------------------------------------------------------------------

VOCAB = 32


class _FakeModel:
    """Deterministic next-token=(last+1)%VOCAB with programmable
    failure/stall on selected call numbers (1-based, prefill+decode
    calls alike)."""

    def __init__(self, fail_calls=(), slow_calls=(), slow_s=5.0,
                 step_sleep=0.0):
        self.config = SimpleNamespace(
            num_layers=1, num_heads=1, num_kv_heads=1, head_dim=4,
            max_seq_len=128, vocab_size=VOCAB)
        self.calls = 0
        self.fail_calls = set(fail_calls)
        self.slow_calls = set(slow_calls)
        self.slow_s = slow_s
        self.step_sleep = step_sleep

    def eval(self):
        return self

    def __call__(self, tokens, caches=None):
        from paddle_tpu.core.tensor import Tensor
        self.calls += 1
        if self.calls in self.fail_calls:
            raise RuntimeError("injected model failure")
        if self.calls in self.slow_calls:
            t0 = time.monotonic()
            while time.monotonic() - t0 < self.slow_s:
                time.sleep(0.01)
        if self.step_sleep:
            time.sleep(self.step_sleep)
        tok = np.asarray(tokens._data_)
        batch, seqlen = tok.shape
        # causal next-token head at EVERY position (the paged engine's
        # chunked prefill samples at the last REAL prompt position, not
        # the last padded one)
        logits = np.zeros((batch, seqlen, VOCAB), np.float32)
        logits[np.arange(batch)[:, None], np.arange(seqlen)[None],
               (tok + 1) % VOCAB] = 10.0
        return Tensor(logits)


_PROMPT = np.array([1, 2, 3], np.int32)


def test_engine_drain_completes_inflight_fails_queued():
    from paddle_tpu.serving import (Engine, EngineShutdownError,
                                    ServingConfig, serving_stats)
    eng = Engine(_FakeModel(step_sleep=0.02), ServingConfig(
        num_slots=2, max_queue=8, default_max_new_tokens=25)).start()
    inflight = [eng.submit(_PROMPT, max_new_tokens=25) for _ in range(2)]
    t0 = time.monotonic()
    while serving_stats()["active_slots"] < 2 and \
            time.monotonic() - t0 < 30:
        time.sleep(0.005)
    queued = [eng.submit(_PROMPT, max_new_tokens=25) for _ in range(3)]
    eng.drain(deadline_s=60)
    for f in inflight:
        out = f.result(timeout=1)
        assert out.finish_reason == "length"
        assert out.output_ids.size == 25
    for f in queued:
        with pytest.raises(EngineShutdownError, match="draining"):
            f.result(timeout=1)
    with pytest.raises(EngineShutdownError):
        eng.submit(_PROMPT)


def test_scheduler_crash_fails_every_outstanding_future():
    """A prefill crash must fail queued AND mid-admission futures (the
    satellite audit), then the bounded restart brings the engine back."""
    from paddle_tpu.serving import Engine, ServingConfig, serving_stats
    model = _FakeModel(fail_calls={1})      # first prefill raises
    eng = Engine(model, ServingConfig(
        num_slots=2, max_queue=8, max_scheduler_restarts=1)).start()
    futs = [eng.submit(_PROMPT, max_new_tokens=3) for _ in range(3)]
    for f in futs:
        exc = f.exception(timeout=30)
        assert isinstance(exc, RuntimeError), exc
        assert "injected model failure" in str(exc)
    # the loop restarted with a fresh slot cache: new work succeeds
    out = eng.generate(_PROMPT, max_new_tokens=2, timeout=60)
    np.testing.assert_array_equal(out.output_ids, [4, 5])
    assert serving_stats()["scheduler_restarts"] == 1
    eng.shutdown()


def test_scheduler_stall_watchdog_fails_futures_and_restarts():
    from paddle_tpu.serving import (Engine, SchedulerStallError,
                                    ServingConfig, serving_stats)
    model = _FakeModel(slow_calls={1}, slow_s=15.0)
    eng = Engine(model, ServingConfig(
        num_slots=1, step_timeout_s=0.3,
        max_scheduler_restarts=2)).start()
    f = eng.submit(_PROMPT, max_new_tokens=2)
    exc = f.exception(timeout=10)   # well before the 15s stall ends
    assert isinstance(exc, SchedulerStallError), exc
    # after the stalled iteration unwinds, the engine must serve again
    out = eng.generate(_PROMPT, max_new_tokens=2, timeout=60)
    assert out.output_ids.size == 2
    snap = serving_stats()
    assert snap["scheduler_stalls"] >= 1
    assert snap["scheduler_restarts"] >= 1
    eng.shutdown()


def test_serving_drain_on_sigterm_subprocess(tmp_path):
    """End-to-end SIGTERM drill: PreemptionHandler-wired drain finishes
    in-flight requests, fails the queue, rejects new admissions."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""),
               FLAGS_flight_recorder_path=str(tmp_path / "fr.json"))
    r = subprocess.run([sys.executable, DRAIN_WORKER, str(tmp_path)],
                       env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, f"stdout:{r.stdout}\nstderr:{r.stderr}"
    data = json.load(open(tmp_path / "drain.json"))
    assert data["completed"] == 2, data
    assert data["tokens"] == [30, 30], data       # ran to completion
    assert data["queued_failed"] == 3, data
    assert data["rejected_after_drain"] == 1, data
    assert data["inflight_errors"] == [] and data["queued_errors"] == []


# ---------------------------------------------------------------------------
# subprocess drills: the 2-process hang + crash-resume acceptance runs
# ---------------------------------------------------------------------------


def _run_controller(tmp_path, sub, max_restart, env_extra,
                    monkeypatch):
    from paddle_tpu.distributed.launch.context import Context, parse_args
    from paddle_tpu.distributed.launch.controller import (
        CollectiveController)
    out = tmp_path / sub
    out.mkdir()
    logs = tmp_path / f"{sub}_logs"
    # workers inherit os.environ: keep their crash/stall dumps in tmp
    monkeypatch.setenv("FLAGS_flight_recorder_path",
                       str(out / "flightrec.json"))
    monkeypatch.setenv("FLAGS_stall_dump_path",
                       str(out / "stall.json"))
    for key, val in env_extra.items():
        monkeypatch.setenv(key, val)
    args = parse_args(["--nproc_per_node", "2",
                       "--max_restart", str(max_restart),
                       "--log_dir", str(logs),
                       GUARDIAN_WORKER, str(out)])
    code = CollectiveController(Context(args=args)).run()
    return code, out, logs


def test_collective_delay_stall_dump(tmp_path, monkeypatch):
    """Acceptance: a stalled collective terminates the job with the
    blamed op/rank in < 2x the timeout, with a schema-valid stall dump
    containing all-thread stacks."""
    stall = tmp_path / "stall.json"
    code, out, logs = _run_controller(
        tmp_path, "delay", 0, {
            "FLAGS_collective_timeout_s": "3",
            "FLAGS_stall_dump_path": str(stall),
            "FLAGS_fault_inject":
                "collective_delay:op=all_reduce,at_seq=6,"
                "delay_s=120,rank=1",
            "PADDLE_GUARDIAN_TERM_GRACE_S": "5",
        }, monkeypatch)
    assert code != 0
    dump = tmp_path / "stall.rank0.json"
    assert dump.exists()
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        from check_telemetry import check_stall_dump
    finally:
        sys.path.pop(0)
    assert check_stall_dump(str(dump)) == []
    data = json.load(open(dump))
    assert data["stall"]["op"] == "all_reduce"
    assert data["stall"]["seq"] == 6
    assert data["stall"]["missing_ranks"] == [1]
    assert data["stall"]["waited_s"] < 2 * data["stall"]["timeout_s"]
    text = "".join(open(logs / f"worker.{r}.log").read()
                   for r in (0, 1))
    assert "CollectiveTimeoutError" in text
    assert "all_reduce" in text


def test_rank_crash_relaunch_resume_matches_uninterrupted(
        tmp_path, monkeypatch):
    """Acceptance: rank 1 crashes mid-step; rank 0 aborts its blocked
    collective with rank 1's ORIGINAL error and exits for relaunch; the
    controller restarts the job, it auto-resumes from the checkpoint,
    and the loss trajectory is byte-equal to an uninterrupted run."""
    code, clean_out, _ = _run_controller(
        tmp_path, "clean", 0, {"FLAGS_fault_inject": ""}, monkeypatch)
    assert code == 0
    code, out, logs = _run_controller(
        tmp_path, "crash", 2, {
            "FLAGS_collective_timeout_s": "3",
            "FLAGS_fault_inject":
                f"rank_crash:at_seq=18,rank=1,"
                f"once_file={tmp_path}/crashed_once",
            "PADDLE_GUARDIAN_TERM_GRACE_S": "5",
            "PADDLE_GUARDIAN_PEER_GRACE_S": "20",
        }, monkeypatch)
    assert code == 0
    assert (tmp_path / "crashed_once").exists()
    for rank in (0, 1):
        clean = json.load(open(clean_out / f"losses.{rank}.json"))
        crashed = json.load(open(out / f"losses.{rank}.json"))
        assert crashed == clean
        assert len(crashed) == 6
    # two incarnations: started at step 0, resumed at step 3
    starts = [int(x) for x in
              open(out / "incarnations.0.log").read().split()]
    assert starts == [0, 3]
    # the healthy rank saw the ORIGINAL error, not a generic timeout
    log0 = open(logs / "worker.0.log").read()
    assert "PeerFailureError" in log0
    assert "InjectedFault" in log0
