"""Fleet-wide distributed request tracing with tail-based sampling.

The serving fleet routes one request through up to five processes —
router, prefill replica, migration transfer, decode replica, hedge
loser — and aggregate histograms cannot answer "why was THIS p99
request slow?".  This module is the Dapper-style answer, sized for the
repo's serving stack:

- A :class:`TraceContext` (trace_id, span_id, parent_span_id, sampled)
  is minted at ``ServingRouter.submit`` / ``Engine.submit`` and
  propagated through the rpc plane as an optional envelope slot
  (distributed/rpc/rpc.py), carried across the ``Blob`` raw-bytes fast
  path inside the migration meta dict, and preserved under the SAME
  trace for hedged / resubmitted / migrated attempts — exactly-once
  delivery shows up as exactly-one winning span plus explicitly
  cancelled losers.
- Each hop records :class:`Span` objects into a bounded per-process
  ring (``FLAGS_trace_buffer_cap``); every span carries BOTH clocks
  (``time.time()`` wall at start, ``time.monotonic()`` t0/t1) so
  cross-process dumps can be aligned.
- **Tail-based sampling**: the keep/drop decision is made ONCE, at
  request completion on the root (:func:`decide`).  Every error /
  evicted / deadline trace is kept, any trace slower than
  ``FLAGS_trace_latency_threshold_ms`` is kept, and a deterministic
  hash of the trace id keeps a ``FLAGS_trace_sample_rate`` floor of
  the fast+healthy rest — so a given trace id's fate never depends on
  RNG state.
- Child buffers are **spooled** per process as atomic JSONL
  (tmp+``os.replace``, the flight-recorder discipline) under
  ``FLAGS_trace_dir`` and merged by a collector
  (:func:`merge_spools`); :func:`chrome_events` turns a merged trace
  set into Perfetto-loadable chrome-trace events with cross-process
  flow arrows, written through the profiler's shared
  ``write_chrome_trace`` writer.

Zero overhead off (the default): with ``FLAGS_trace_dir`` empty no
context objects, spans, or I/O exist — every instrumented seam pays a
single falsy flag check or ``is None`` compare, and serving output is
byte-identical to this module never existing (the
``FLAGS_fault_inject`` / flight-recorder ``capacity <= 0`` precedent).

Phases that start and end on one thread (the train step, the scheduler
tick, a prefill chunk, admission) use :func:`span` instead: one context
manager whose sinks are the profiler's own trace (a
``jax.profiler.TraceAnnotation``, so the phase sits on the device
trace's clock), the ``<name>_ms`` histogram, the flight recorder and a
recording ``Profiler`` — all with tracing off — and, with
``FLAGS_trace_dir`` set, a ``kind: "phase"`` record in a ring of its
own beside the request spans, spooled and merged with them.
"""
from __future__ import annotations

import atexit
import contextlib
import hashlib
import itertools
import json
import os
import threading
import time
from collections import deque

import jax
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from ..utils.flags import flag as _flag
from . import flight_recorder as _flight_recorder

SCHEMA_VERSION = 1

# spool a process's ring after this many local tail-sampling decisions
# (crash robustness between explicit collector visits)
_SPOOL_EVERY = 64

_lock = threading.Lock()
_tls = threading.local()
_ids = itertools.count(1)
_buffer: deque = deque()          # completed span/decision records
_spooled: list = []               # drained records awaiting/already on disk
# phase records (:func:`span`) ride a ring and a spool list of their
# own, same bounds: a busy scheduler writes several a tick and must not
# evict a request's spans
_phases: deque = deque()
_phases_spooled: list = []
_decided: dict = {}               # trace_id -> decision record (first wins)
_proc_name: str | None = None
_decisions_since_spool = 0


def enabled():
    """Tracing is armed iff ``FLAGS_trace_dir`` names a directory."""
    return bool(_flag("FLAGS_trace_dir"))


def set_process_name(name, default=False):
    """Stamp this process's row label for spans/spools (the replica
    name; the ``engine.fault_name`` precedent).  ``default=True`` only
    sets an unset label — the router claims its host process that way
    without clobbering a replica label when both share one process
    (thread-mode chaos fleets)."""
    global _proc_name
    if default and _proc_name is not None:
        return
    _proc_name = str(name) if name else None


def _proc():
    return _proc_name or f"pid{os.getpid()}"


def _incr(name, value=1):
    from ..utils import monitor
    monitor.incr("serving.trace." + name, value)


class TraceContext:
    """The propagated identity of one request's trace: which trace the
    next span belongs to and which span is its parent.  ``sampled`` is
    the tail-sampling decision once known (None until the root
    decides); it rides the wire form so late hops of an already-decided
    trace could skip recording (currently informational)."""

    __slots__ = ("trace_id", "span_id", "parent_span_id", "sampled")

    def __init__(self, trace_id, span_id, parent_span_id=None,
                 sampled=None):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_span_id = parent_span_id
        self.sampled = sampled

    def wire(self):
        """Compact tuple for the rpc envelope slot / migration meta."""
        return (self.trace_id, self.span_id, self.parent_span_id,
                self.sampled)

    @staticmethod
    def from_wire(w):
        if w is None:
            return None
        return TraceContext(w[0], w[1], w[2] if len(w) > 2 else None,
                            w[3] if len(w) > 3 else None)

    def __repr__(self):     # pragma: no cover - debugging aid
        return (f"TraceContext(trace={self.trace_id!r}, "
                f"span={self.span_id!r})")


class Span:
    """One timed hop of a trace.  Created by :func:`start_span`; call
    :meth:`event` for point annotations (breaker skips, shed/hedge
    decisions, prefill chunks) and :meth:`end` exactly once — ending
    pushes the record into the process ring.  Both clocks are captured:
    ``wall`` (epoch seconds at start) anchors cross-process alignment,
    ``t0``/``t1`` (monotonic) give drift-free durations."""

    __slots__ = ("ctx", "name", "wall", "t0", "t1", "status", "winner",
                 "attrs", "events", "kind", "_ended")

    def __init__(self, name, trace_id, parent_span_id, attrs,
                 kind="span"):
        sid = f"{os.getpid():x}.{next(_ids):x}"
        self.ctx = TraceContext(trace_id, sid, parent_span_id)
        self.name = name
        self.kind = kind
        self.wall = time.time()
        self.t0 = time.monotonic()
        self.t1 = None
        self.status = "ok"
        self.winner = False
        self.attrs = dict(attrs) if attrs else {}
        self.events = []
        self._ended = False

    def event(self, name, **attrs):
        """Append one point annotation at the current time."""
        ev = {"name": name,
              "t_ms": round((time.monotonic() - self.t0) * 1e3, 3)}
        if attrs:
            ev.update(attrs)
        self.events.append(ev)
        return self

    def set(self, **attrs):
        self.attrs.update(attrs)
        return self

    def end(self, status="ok", winner=None, *, t1=None, **attrs):
        """Close the span and push its record into the process ring.
        Idempotent: a second end is ignored (the first outcome wins —
        the same discipline as first-answer-wins futures).  ``t1`` is
        the closing reading where the caller already took one."""
        if self._ended:
            return self
        self._ended = True
        self.t1 = time.monotonic() if t1 is None else t1
        self.status = status
        if winner is not None:
            self.winner = bool(winner)
        if attrs:
            self.attrs.update(attrs)
        rec = {"kind": self.kind, "trace": self.ctx.trace_id,
               "span": self.ctx.span_id,
               "parent": self.ctx.parent_span_id,
               "name": self.name, "proc": _proc(), "pid": os.getpid(),
               "wall": self.wall, "t0": self.t0, "t1": self.t1,
               "status": self.status}
        if self.winner:
            rec["winner"] = True
        if self.attrs:
            rec["attrs"] = self.attrs
        if self.events:
            rec["events"] = self.events
        if self.kind == "phase":
            _record(rec, _phases)
        else:
            _record(rec)
            _incr("spans")
        return self


def _record(rec, ring=_buffer):
    cap = int(_flag("FLAGS_trace_buffer_cap", 4096) or 0)
    with _lock:
        while cap > 0 and len(ring) >= cap:
            ring.popleft()
            _incr("spans_dropped")
        ring.append(rec)


def start_span(name, parent=None, **attrs):
    """Open one span, or return None with tracing off (callers guard
    every later touch with ``span is not None``).  ``parent`` is a
    :class:`Span`, a :class:`TraceContext`, or None — None falls back
    to the thread-bound context (:func:`current`), and with no context
    anywhere a fresh root trace is minted."""
    if not enabled():
        return None
    if isinstance(parent, Span):
        parent = parent.ctx
    if parent is None:
        parent = current()
    if parent is not None:
        return Span(name, parent.trace_id, parent.span_id, attrs)
    trace_id = f"{_proc()}-{os.getpid():x}-{next(_ids):x}"
    return Span(name, trace_id, None, attrs)


# ---------------- phase spans: one primitive, one clock ----------------
_monitor = None
_profiler = None
_exit_spool_armed = False


def _late_imports():
    # utils.monitor and profiler.profiler import this package
    global _monitor, _profiler
    from ..profiler import profiler as prof
    from ..utils import monitor
    _monitor, _profiler = monitor, prof


class span:  # noqa: N801 - used as ``with span(...)``
    """``with span(name, **attrs):`` — one phase on one thread.

    Sinks, in the order they are touched:

    1. a ``jax.profiler.TraceAnnotation(name)``
       (``StepTraceAnnotation(name, step_num=...)`` when ``step_num`` is
       given): one atomic check with no profiler session; with one, the
       phase is an event on the xplane's host plane, on the device
       trace's clock;
    2. the histogram ``hist`` (default ``<name>_ms``) in the registry;
    3. the flight recorder's ring, and the host buffer of a recording
       ``Profiler`` (``cat`` is its event category);
    4. only with ``FLAGS_trace_dir`` set: a ``kind: "phase"`` record in
       the phase ring — name, ``wall``, ``t0``/``t1``, trace id, the
       span id of ``parent`` (default: the phase open on this thread)
       and ``attrs``.

    ``attrs`` go to sinks 3 and 4; build costly ones (sorted request
    ids) only ``if enabled()``.  After the block ``.ms`` holds the
    duration."""

    __slots__ = ("name", "hist", "cat", "attrs", "parent", "ms", "_ann",
                 "_t0", "_rec")

    def __init__(self, name, parent=None, *, hist=None, step_num=None,
                 cat="UserDefined", **attrs):
        self.name = name
        self.hist = hist or name + "_ms"
        self.cat = cat
        self.attrs = attrs
        self.parent = parent
        self.ms = None
        self._rec = None
        self._ann = TraceAnnotation(name) if step_num is None else \
            StepTraceAnnotation(name, step_num=step_num)

    def __enter__(self):
        self._ann.__enter__()
        # both clocks next to the annotation's own reading, so that a
        # phase record's ``wall`` is its xplane twin's start
        wall, self._t0 = time.time(), time.monotonic()
        if enabled():
            global _exit_spool_armed
            if not _exit_spool_armed:
                # a training process has no shutdown to spool from
                _exit_spool_armed = True
                atexit.register(spool_now)
            stack = getattr(_tls, "phases", None)
            if stack is None:
                stack = _tls.phases = []
            parent = self.parent or (stack[-1] if stack else None)
            rec = self._rec = Span(
                self.name, f"{_proc()}-{os.getpid():x}-phases",
                parent.ctx.span_id if parent is not None else None,
                self.attrs, kind="phase")
            rec.wall, rec.t0 = wall, self._t0
            stack.append(rec)
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.monotonic()
        self._ann.__exit__(exc_type, exc, tb)
        self.ms = (t1 - self._t0) * 1e3
        if _monitor is None:
            _late_imports()
        _monitor.observe(self.hist, self.ms)
        if _profiler._ACTIVE:
            _profiler._HOST_BUFFER.add(
                self.name, self._t0 * 1e6, self.ms * 1e3,
                threading.get_ident() % 2 ** 31, self.cat,
                args=self.attrs or None)
        _flight_recorder.record("span", self.name,
                                dur_ms=round(self.ms, 3), **self.attrs)
        rec = self._rec
        if rec is not None:
            _tls.phases.remove(rec)
            # the record closes on the reading the histogram took
            rec.end(status="ok" if exc_type is None
                    else exc_type.__name__, t1=t1)
        return False


# ---------------- program scopes (names on the device) ----------------
#: the marker the tape's backward adds to the scope it re-enters
BACKWARD = "bwd"
#: every name a ``scope`` was entered under in this process: what
#: ``observability.scopes`` looks for in a compiled instruction's
#: ``op_name`` (JAX's own path elements — ``while``, ``body``,
#: ``jit(f)`` — are not scopes)
scope_names: set = {BACKWARD}


@contextlib.contextmanager
def scope(name):
    """``with scope("attn"):`` — a ``jax.named_scope`` the autograd tape
    remembers.  Every operation traced inside carries ``name`` in its
    ``op_name`` metadata; a ``GradNode`` made inside keeps
    :func:`scope_path`, and ``run_backward`` re-enters that path plus
    :data:`BACKWARD` round the node's VJP, so a compiled program's
    backward is named by layer kind as its forward is
    (docs/OBSERVABILITY.md, "Names on the device").  Metadata only: the
    compiled bytes and the compile-cache key do not change."""
    prev = getattr(_tls, "scopes", ())
    _tls.scopes = prev + (name,)
    scope_names.add(name)
    try:
        with jax.named_scope(name):
            yield
    finally:
        _tls.scopes = prev


def scope_path():
    """The names of the scopes open on this thread, outermost first."""
    return getattr(_tls, "scopes", ())


class backward_of:  # noqa: N801 - used as ``with backward_of(...)``
    """Re-enter ``path`` (a :func:`scope_path` kept from the forward),
    less what of it is open already, and the :data:`BACKWARD` marker
    under it.  One ``jax.named_scope`` of the joined names: the tape
    enters this once a node, eagerly too."""

    __slots__ = ("_path", "_prev", "_scope")

    def __init__(self, path):
        self._path = path

    def __enter__(self):
        prev = self._prev = getattr(_tls, "scopes", ())
        path, shared = self._path, 0
        while shared < min(len(prev), len(path)) \
                and prev[shared] == path[shared]:
            shared += 1
        rest = path[shared:] + (BACKWARD,)
        _tls.scopes = prev + rest
        self._scope = jax.named_scope("/".join(rest))
        self._scope.__enter__()

    def __exit__(self, *exc):
        _tls.scopes = self._prev
        return self._scope.__exit__(*exc)


# ---------------- thread-bound context (rpc propagation) ----------------
def current():
    """The context bound to this thread (rpc handlers run under
    :func:`bind`), or None."""
    return getattr(_tls, "ctx", None)


@contextlib.contextmanager
def bind(ctx):
    """Bind ``ctx`` (a TraceContext / Span / None) as this thread's
    current context for the duration of the with-block."""
    if isinstance(ctx, Span):
        ctx = ctx.ctx
    prev = getattr(_tls, "ctx", None)
    _tls.ctx = ctx
    try:
        yield ctx
    finally:
        _tls.ctx = prev


def current_wire():
    """The current thread context's wire form, or None — what the rpc
    client attaches to the call envelope (one attribute read when
    tracing is off)."""
    ctx = getattr(_tls, "ctx", None)
    return ctx.wire() if ctx is not None else None


def bind_wire(w):
    """with-block binding a wire-form context (the rpc server side);
    a no-op null context when ``w`` is None."""
    if w is None:
        return contextlib.nullcontext()
    return bind(TraceContext.from_wire(w))


# ---------------- tail-based sampling ----------------
def _hash_floor(trace_id):
    h = hashlib.sha256(trace_id.encode()).hexdigest()[:8]
    return int(h, 16) / float(1 << 32)


def decide(trace_id, status="ok", latency_ms=0.0):
    """The tail-sampling decision, made ONCE at root-request completion
    by whoever owns the root span.  Keeps: every non-ok trace (error /
    evicted / deadline / cancelled), every trace slower than
    ``FLAGS_trace_latency_threshold_ms`` (0 keeps all), and a
    deterministic-hash floor of ``FLAGS_trace_sample_rate``.  Returns
    the keep decision (bool), or None with tracing off.  A second
    decision for the same trace is ignored (first wins) — the merged
    output and the chaos gate both assert exactly one per trace."""
    global _decisions_since_spool
    if not enabled():
        return None
    with _lock:
        prev = _decided.get(trace_id)
    if prev is not None:
        return bool(prev["keep"])
    thr = float(_flag("FLAGS_trace_latency_threshold_ms", 250.0) or 0.0)
    rate = float(_flag("FLAGS_trace_sample_rate", 0.05) or 0.0)
    if status != "ok":
        keep, reason = True, f"status:{status}"
    elif thr <= 0 or latency_ms >= thr:
        keep, reason = True, "latency"
    elif rate > 0 and _hash_floor(trace_id) < rate:
        keep, reason = True, "floor"
    else:
        keep, reason = False, "sampled_out"
    rec = {"kind": "decision", "trace": trace_id, "keep": keep,
           "reason": reason, "status": status,
           "latency_ms": round(float(latency_ms), 3),
           "proc": _proc(), "pid": os.getpid(),
           "wall": time.time(), "mono": time.monotonic()}
    spool = False
    with _lock:
        if trace_id in _decided:        # lost the race: first wins
            return bool(_decided[trace_id]["keep"])
        _decided[trace_id] = rec
        _decisions_since_spool += 1
        if _decisions_since_spool >= _SPOOL_EVERY:
            _decisions_since_spool = 0
            spool = True
    _record(rec)
    _incr("decisions")
    if keep:
        _incr("decisions_kept")
    if spool:
        spool_now()
    return keep


# ---------------- spool / collect ----------------
def spool_path(trace_dir=None):
    d = str(trace_dir or _flag("FLAGS_trace_dir") or "")
    safe = "".join(c if c.isalnum() or c in "-_." else "_"
                   for c in _proc())
    return os.path.join(d, f"spool-{safe}-{os.getpid()}.jsonl")


def spool_now(trace_dir=None):
    """Atomically (re)write this process's spool file with every record
    seen so far (ring drained into the spooled accumulator, itself
    bounded at 8x the ring cap).  tmp+``os.replace`` — a crash mid-
    write never leaves a torn file, and the collector always reads a
    consistent JSONL.  Returns the path, or None when disabled/empty;
    never raises (telemetry must not take the serving path down)."""
    if not enabled() and trace_dir is None:
        return None
    with _lock:
        cap = int(_flag("FLAGS_trace_buffer_cap", 4096) or 0)
        bound = max(cap * 8, 1024)
        for ring, kept in ((_buffer, _spooled), (_phases, _phases_spooled)):
            while ring:
                kept.append(ring.popleft())
            while len(kept) > bound:
                kept.pop(0)
                _incr("spans_dropped")
        records = _spooled + _phases_spooled
    if not records:
        return None
    path = spool_path(trace_dir)
    try:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            for rec in records:
                f.write(json.dumps(rec, default=str) + "\n")
        os.replace(tmp, path)
    except OSError:
        return None
    _incr("spools")
    return path


def reset():
    """Drop every buffered/spooled record and decision in THIS process
    (tests; fresh campaigns).  On-disk spool files are untouched."""
    global _decisions_since_spool
    with _lock:
        for kept in (_buffer, _spooled, _phases, _phases_spooled):
            kept.clear()
        _decided.clear()
        _decisions_since_spool = 0
    _tls.ctx = None
    _tls.phases = []


def merge_spools(trace_dir=None):
    """Collector: read every ``spool-*.jsonl`` under ``trace_dir``
    (default ``FLAGS_trace_dir``), group spans by trace id, attach each
    trace's tail-sampling decision, and return the merged document::

        {"schema_version": 1,
         "traces": [{"trace_id", "sampled", "decision", "decision_count",
                     "span_count", "spans": [...]}, ...],
         "phases": [...]}

    ``phases`` holds every process's :func:`span` records by start
    time; they belong to no request's trace and are never sampled.

    Spans of explicitly dropped traces (decision keep=False) are
    elided (the span_count remains) — that IS the sampling.  Undecided
    traces (a request lost mid-flight) keep their spans for
    post-mortem.  Torn/alien lines are skipped, never fatal."""
    d = str(trace_dir or _flag("FLAGS_trace_dir") or "")
    spans: dict = {}          # trace_id -> {span_id: record}
    decisions: dict = {}      # trace_id -> [records]
    phases: dict = {}         # span_id -> record
    if d and os.path.isdir(d):
        for fn in sorted(os.listdir(d)):
            if not (fn.startswith("spool-") and fn.endswith(".jsonl")):
                continue
            try:
                with open(os.path.join(d, fn)) as f:
                    lines = f.readlines()
            except OSError:
                continue
            for line in lines:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                tid = rec.get("trace")
                if not tid:
                    continue
                if rec.get("kind") == "span" and rec.get("span"):
                    spans.setdefault(tid, {})[rec["span"]] = rec
                elif rec.get("kind") == "decision":
                    decisions.setdefault(tid, []).append(rec)
                elif rec.get("kind") == "phase" and rec.get("span"):
                    phases[rec["span"]] = rec
    traces = []
    for tid in sorted(set(spans) | set(decisions)):
        ds = decisions.get(tid, [])
        ss = spans.get(tid, {})
        decision = ds[0] if ds else None
        sampled = bool(decision["keep"]) if decision is not None else None
        entry = {"trace_id": tid, "sampled": sampled,
                 "decision": decision, "decision_count": len(ds),
                 "span_count": len(ss)}
        if sampled is not False:
            entry["spans"] = sorted(
                ss.values(), key=lambda r: (r.get("wall", 0.0),
                                            r.get("span", "")))
        traces.append(entry)
    return {"schema_version": SCHEMA_VERSION,
            "generator": "paddle_tpu.observability.tracing",
            "traces": traces,
            "phases": sorted(phases.values(),
                             key=lambda r: (r.get("wall", 0.0),
                                            r.get("span", "")))}


def write_merged(merged, path):
    """Atomic JSON dump of a :func:`merge_spools` document."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(merged, f, indent=1, default=str)
    os.replace(tmp, path)
    return path


def load_merged(path):
    with open(path) as f:
        return json.load(f)


# ---------------- chrome-trace export ----------------
def chrome_events(merged):
    """Merged traces -> (chrome-trace events, proc_names): one "X"
    duration event per span and, on a second row of its process, per
    phase (wall-clock microseconds — the per-span
    wall anchor aligns processes; durations come from the monotonic
    pair) plus "s"/"f" flow events for every parent->child edge that
    crosses a process, so Perfetto draws the request's hop arrows
    router -> prefill -> transfer -> decode."""
    events = []
    proc_ids: dict = {}       # (proc, pid) -> row id
    proc_names: dict = {}
    span_index: dict = {}     # span_id -> record

    def row(rec):
        key = (rec.get("proc", "?"), rec.get("pid", 0))
        if key not in proc_ids:
            proc_ids[key] = len(proc_ids) + 1
            proc_names[proc_ids[key]] = f"{key[0]} (pid {key[1]})"
        return proc_ids[key]

    for tr in merged.get("traces", []):
        for rec in tr.get("spans", []) or []:
            span_index[rec["span"]] = rec
    flow = itertools.count(1)
    for tr in merged.get("traces", []):
        for rec in tr.get("spans", []) or []:
            dur_us = max((rec.get("t1", 0.0) - rec.get("t0", 0.0))
                         * 1e6, 1.0)
            args = {"trace_id": rec["trace"], "span_id": rec["span"],
                    "parent": rec.get("parent"),
                    "status": rec.get("status", "ok")}
            if rec.get("winner"):
                args["winner"] = True
            if rec.get("attrs"):
                args.update(rec["attrs"])
            if rec.get("events"):
                args["events"] = rec["events"]
            events.append({"name": rec["name"], "cat": "trace",
                           "ph": "X",
                           "ts": rec.get("wall", 0.0) * 1e6,
                           "dur": dur_us, "pid": row(rec), "tid": 1,
                           "args": args})
            parent = span_index.get(rec.get("parent"))
            if parent is not None and \
                    (parent.get("proc"), parent.get("pid")) != \
                    (rec.get("proc"), rec.get("pid")):
                fid = next(flow)
                events.append({"name": "hop", "cat": "trace",
                               "ph": "s", "id": fid,
                               "ts": parent.get("wall", 0.0) * 1e6,
                               "pid": row(parent), "tid": 1})
                events.append({"name": "hop", "cat": "trace",
                               "ph": "f", "bp": "e", "id": fid,
                               "ts": rec.get("wall", 0.0) * 1e6,
                               "pid": row(rec), "tid": 1})
    for rec in merged.get("phases", []) or []:
        events.append({"name": rec["name"], "cat": "phase", "ph": "X",
                       "ts": rec.get("wall", 0.0) * 1e6,
                       "dur": max((rec.get("t1", 0.0)
                                   - rec.get("t0", 0.0)) * 1e6, 1.0),
                       "pid": row(rec), "tid": 2,
                       "args": dict(rec.get("attrs") or {},
                                    span_id=rec["span"],
                                    parent=rec.get("parent"))})
    return events, proc_names


def export_chrome(merged, path):
    """Write a merged trace set as Perfetto-loadable chrome-trace JSON
    through the profiler's shared writer (cross-process flow events
    included)."""
    from ..profiler import write_chrome_trace
    events, proc_names = chrome_events(merged)
    return write_chrome_trace(
        events, path,
        metadata={"trace_schema_version": SCHEMA_VERSION,
                  "traces": len(merged.get("traces", []))},
        proc_names=proc_names)
