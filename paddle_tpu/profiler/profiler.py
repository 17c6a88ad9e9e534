"""Profiler: host spans + device (XLA/TPU) tracing.

Reference capability: `paddle.profiler.Profiler` (reference:
python/paddle/profiler/profiler.py:346 — `start` :558, scheduler states
:79, chrome-trace export via profiler/utils.py:215 and C++
chrometracing_logger.cc; host tracer host_tracer.cc records RecordEvent
spans; cuda_tracer.cc records CUPTI GPU activity).

TPU-native realization: two planes, mirroring the reference's host/device
split —
- host plane: `RecordEvent` spans and the framework's own phases
  (`observability.tracing.span`) recorded in-process and exported as
  Chrome trace JSON (chrome://tracing / Perfetto-loadable); the same
  spans are `TraceAnnotation`s in the device plane's xplane;
- device plane: `jax.profiler` xplane capture (TensorBoard/xprof-loadable),
  started/stopped with the same scheduler — XLA's profiler is the CUPTI
  analog on TPU.
"""
from __future__ import annotations

import json
import os
import threading
import time
from enum import Enum


class ProfilerState(Enum):
    """reference: profiler.py:79 scheduler states."""
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1      # accepted for parity; maps to the device plane
    TPU = 2
    CUSTOM_DEVICE = 3


def make_scheduler(*, closed, ready, record, repeat=0, skip_first=0):
    """reference: profiler.py make_scheduler — step-phase state machine."""
    total = closed + ready + record

    def scheduler(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat > 0 and s >= repeat * total:
            return ProfilerState.CLOSED
        pos = s % total
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == total - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


class _HostEventBuffer:
    """The host_tracer analog: thread-safe span buffer."""

    def __init__(self):
        self._events = []
        self._lock = threading.Lock()

    def add(self, name, ts_us, dur_us, tid, event_type, args=None):
        ev = {"name": name, "ph": "X", "ts": ts_us, "dur": dur_us,
              "pid": os.getpid(), "tid": tid, "cat": event_type}
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    def drain(self):
        with self._lock:
            ev, self._events = self._events, []
        return ev


_HOST_BUFFER = _HostEventBuffer()
_ACTIVE = []


def op_profiling_active():
    """True while a (non-timer-only) profiler records — the dispatch
    funnel then times each eager op (the host_tracer per-op
    instrumentation analog, reference: RecordEvent in the generated
    ad_funcs)."""
    return any(not p.timer_only for p in _ACTIVE)


def record_op_span(name, t0_ns, t1_ns, outs, shapes, static,
                   cache_hit=None):
    """Record one eager op dispatch: host span + analytic FLOPs, and —
    when a device target is being profiled — the device-complete time
    measured by blocking on the op's outputs (the CUPTI/gpu_timer
    analog: per-op device durations, at the cost of breaking async
    dispatch while profiling)."""
    import jax

    if outs and isinstance(outs[0], jax.core.Tracer):
        return                        # symbolic: timing is meaningless
    sync = any(not p.timer_only and (
        ProfilerTarget.TPU in p.targets or ProfilerTarget.GPU in p.targets)
        for p in _ACTIVE)
    dev_dur_us = None
    if sync:
        try:
            jax.block_until_ready(outs)
            dev_dur_us = (time.perf_counter_ns() - t0_ns) / 1e3
        except Exception:
            dev_dur_us = None
    from ..ops.flops import flops_of
    f = flops_of(name, shapes, static)
    args = {}
    if f is not None:
        args["flops"] = f
    if dev_dur_us is not None:
        args["device_dur"] = dev_dur_us
    if cache_hit is not None:
        # tier-1 op-cache annotation (core/op_cache.py): True = this
        # dispatch replayed a cached jitted executable
        args["cache_hit"] = bool(cache_hit)
    _HOST_BUFFER.add(name, t0_ns / 1e3, (t1_ns - t0_ns) / 1e3,
                     threading.get_ident() % 2 ** 31, "Operator",
                     args=args)


class RecordEvent:
    """User-scope span (reference: profiler/utils.py RecordEvent over C++
    event_tracing.h).  Usable as context manager or begin()/end().

    A thin wrapper over ``observability.tracing.span``, so a finished
    span lands where the framework's own phases do: in the xplane of a
    running ``jax.profiler`` session, in the ``<name>_ms`` histogram,
    in the observability flight recorder — a bounded ring that
    survives crashes — whether or not a profiler is attached, and in a
    recording ``Profiler``'s host buffer.  ``args`` lands in the
    chrome-trace event's ``args`` field (e.g. a ``request_id`` so a
    trace span can be joined against the request's metrics)."""

    def __init__(self, name, event_type="UserDefined", args=None):
        self.name = name
        self.event_type = event_type
        self.args = args
        self._span = None

    def begin(self):
        from ..observability.tracing import span
        self._span = span(self.name, cat=self.event_type,
                          **(self.args or {}))
        self._span.__enter__()
        return self

    def end(self):
        if self._span is None:
            return
        span, self._span = self._span, None
        span.__exit__(None, None, None)

    __enter__ = begin

    def __exit__(self, *exc):
        self.end()


class Profiler:
    """reference: profiler.py:346.

    targets    — [ProfilerTarget.CPU, ProfilerTarget.TPU]
    scheduler  — (start, end) tuple or a make_scheduler callable
    on_trace_ready — callback(prof) at RECORD_AND_RETURN steps
    """

    def __init__(self, *, targets=None, scheduler=None, on_trace_ready=None,
                 record_shapes=False, profile_memory=False, timer_only=False,
                 emit_nvtx=False, custom_device_types=None):
        self.targets = targets or [ProfilerTarget.CPU, ProfilerTarget.TPU]
        if isinstance(scheduler, tuple):
            start, end = scheduler
            self.scheduler = make_scheduler(
                closed=max(start, 0), ready=0, record=end - start, repeat=1)
        elif scheduler is None:
            self.scheduler = lambda step: ProfilerState.RECORD
        else:
            self.scheduler = scheduler
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.step_num = 0
        self.state = ProfilerState.CLOSED
        self._events = []
        self._device_dir = None
        self._device_active = False
        self._step_spans = []
        self._step_t0 = None

    # ---- lifecycle (reference: start :558 / stop / step) ----
    def start(self):
        self.state = self.scheduler(self.step_num)
        self._transition(ProfilerState.CLOSED, self.state)
        self._step_t0 = time.perf_counter_ns()
        return self

    def stop(self):
        self._transition(self.state, ProfilerState.CLOSED)
        self.state = ProfilerState.CLOSED
        if self.on_trace_ready:
            self.on_trace_ready(self)

    def step(self, num_samples=None):
        if self._step_t0 is not None:
            t1 = time.perf_counter_ns()
            self._step_spans.append(
                {"name": f"ProfileStep#{self.step_num}", "ph": "X",
                 "ts": self._step_t0 / 1e3,
                 "dur": (t1 - self._step_t0) / 1e3,
                 "pid": os.getpid(), "tid": 0, "cat": "ProfileStep",
                 "args": ({"num_samples": num_samples}
                          if num_samples else {})})
        old = self.state
        self.step_num += 1
        self.state = self.scheduler(self.step_num)
        self._transition(old, self.state)
        if old == ProfilerState.RECORD_AND_RETURN and self.on_trace_ready:
            self.on_trace_ready(self)
        self._step_t0 = time.perf_counter_ns()

    def _transition(self, old, new):
        recording = (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)
        if old not in recording and new in recording:
            _ACTIVE.append(self)
            if not self.timer_only:
                self._start_device_trace()
        elif old in recording and new not in recording:
            if self in _ACTIVE:
                _ACTIVE.remove(self)
            self._events.extend(_HOST_BUFFER.drain())
            self._stop_device_trace()

    # ---- device plane (xplane via jax.profiler) ----
    def _start_device_trace(self):
        if ProfilerTarget.TPU not in self.targets and \
                ProfilerTarget.GPU not in self.targets:
            return
        import tempfile
        import jax
        self._device_dir = tempfile.mkdtemp(prefix="pt_xplane_")
        try:
            jax.profiler.start_trace(self._device_dir)
            self._device_active = True
        except Exception:
            self._device_active = False

    def _stop_device_trace(self):
        if self._device_active:
            import jax
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._device_active = False

    # ---- export ----
    def export(self, path, format="json"):  # noqa: A002
        if format in ("json", "chrometracing"):
            export_chrome_tracing_data(self, path)
        else:
            export_protobuf(self, path)

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms", views=None):
        from .profiler_statistic import summary as _summary
        return _summary(self, time_unit=time_unit, sorted_by=sorted_by,
                        op_detail=op_detail)

    @property
    def events(self):
        return self._events + self._step_spans

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()


def _metadata_rows(events, proc_names=None):
    """process_name/thread_name metadata events ("ph": "M") for every
    pid/tid a span references, so Perfetto/chrome://tracing shows
    labeled rows instead of bare numbers (the same labeling
    merge_chrome_traces applies to its per-host bands).  ``proc_names``
    optionally maps pid -> label (the tracing exporter labels rows with
    replica names instead of raw pids)."""
    pids, tids = set(), set()
    for e in events:
        if e.get("ph") == "M":
            continue
        pids.add(e.get("pid", 0))
        tids.add((e.get("pid", 0), e.get("tid", 0)))
    rows = []
    main_tid = threading.main_thread().ident
    main_tid = main_tid % 2 ** 31 if main_tid is not None else None
    proc_names = proc_names or {}
    for pid in sorted(pids):
        label = proc_names.get(pid, f"paddle_tpu host (pid {pid})")
        rows.append({"name": "process_name", "ph": "M", "pid": pid,
                     "args": {"name": label}})
    for pid, tid in sorted(tids):
        label = "main thread" if tid in (0, main_tid) else f"thread {tid}"
        rows.append({"name": "thread_name", "ph": "M", "pid": pid,
                     "tid": tid, "args": {"name": label}})
    return rows


def write_chrome_trace(events, path, metadata=None, proc_names=None):
    """Write a chrome://tracing / Perfetto-loadable trace file: the
    shared writer behind both the profiler export and the distributed-
    tracing export (observability/tracing.py).  Prepends process/thread
    metadata rows for every pid/tid the events reference."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    trace = {"traceEvents": _metadata_rows(events, proc_names) + events,
             "displayTimeUnit": "ms"}
    if metadata is not None:
        trace["metadata"] = metadata
    with open(path, "w") as f:
        json.dump(trace, f)
    return path


def export_chrome_tracing_data(prof: Profiler, path):
    return write_chrome_trace(prof.events, path,
                              metadata={"xplane_dir": prof._device_dir})


def export_chrome_tracing(dir_name, worker_name=None):
    """on_trace_ready factory (reference: profiler/utils.py:215)."""
    os.makedirs(dir_name, exist_ok=True)

    def handler(prof):
        name = worker_name or f"host_{os.getpid()}"
        export_chrome_tracing_data(
            prof, os.path.join(dir_name,
                               f"{name}_{int(time.time() * 1000)}.json"))

    return handler


def export_protobuf(prof_or_dir, path=None):
    """Parity entry point: the device plane is already a protobuf xplane
    dump under prof._device_dir (jax.profiler); link it."""
    if path is None:
        return prof_or_dir
    prof = prof_or_dir
    with open(path, "w") as f:
        json.dump({"xplane_dir": prof._device_dir,
                   "host_events": prof.events}, f)
    return path


def load_profiler_result(path):
    with open(path) as f:
        return json.load(f)


def merge_chrome_traces(paths, out_path):
    """Merge per-host chrome traces into one timeline (reference
    capability: tools/CrossStackProfiler/ multi-node trace merge).

    Each input's pids are offset into a disjoint host band (host i →
    pid + (i+1)*1_000_000) and a process_name metadata row labels the
    band with the source file, so rows from different hosts never
    collide in chrome://tracing / Perfetto."""
    merged = []
    band_width = 1 << 23      # > kernel.pid_max default (4194304)
    for i, p in enumerate(paths):
        with open(p) as f:
            trace = json.load(f)
        events = trace if isinstance(trace, list) else \
            trace.get("traceEvents", []) or []
        band = (i + 1) * band_width
        seen_pids = set()
        for e in events:
            e = dict(e)
            pid = e.get("pid", 0)
            e["pid"] = band + (pid % band_width
                               if isinstance(pid, int) else 0)
            seen_pids.add(e["pid"])
            merged.append(e)
        for pid in sorted(seen_pids):
            merged.append({"name": "process_name", "ph": "M", "pid": pid,
                           "args": {"name": f"host{i}:"
                                            f"{os.path.basename(p)}"}})
    d = os.path.dirname(out_path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(out_path, "w") as f:
        json.dump({"traceEvents": merged, "displayTimeUnit": "ms"}, f)
    return out_path
