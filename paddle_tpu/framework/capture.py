"""Shared two-phase capture/replay core for whole-program compilation.

PR 8 proved the pattern for training: run the user's code once eagerly
under a *discovery* tracer that records every pre-existing tensor it
reads (parameters, buffers, masks) while rolling back its side effects,
then *bind* JAX tracers into those tensors' data slots and replay the
body under ``jax.jit`` so the whole step becomes ONE donated-buffer XLA
program.  ISSUE 13 gives the serving scheduler the same treatment (one
program per scheduler tick), so the machinery that was private to
``framework/train_step.py`` lives here now, consumed by both:

- :class:`~paddle_tpu.framework.train_step.CompiledTrainStep` — the
  training step (forward + backward + AMP + clip + dp reduction + fused
  optimizer update);
- :class:`~paddle_tpu.serving.compiled_tick.CompiledServingTick` — the
  serving scheduler tick (batched decode + vectorized sampling chain +
  offset/bookkeeping updates over device-resident scheduler state).

The contract both rely on:

1. **Discovery** (:func:`run_discovery`): execute a thunk eagerly under
   a :class:`~paddle_tpu.jit.tracer._DiscoveryTracer` whose read/write
   hooks snapshot pre-existing tensors, so every side effect (RNG
   counter, buffer writes) is rolled back afterwards; any host read
   raises :class:`TraceEscape` — the compiled program supports no guard
   re-specialization, such bodies simply stay on their eager lane.
2. **Bind + replay**: while ``jax.jit`` traces the program body, a
   :class:`BindTracer` is installed as the framework tracer and the
   captured tensors' ``_data_`` slots hold tracer arrays (swapped
   exception-safely by :class:`Installed`).  Reads of tensors discovery
   did not see, host reads, and unexpected host-scalar providers all
   raise :class:`TraceEscape` so the caller can latch its byte-identical
   eager fallback instead of silently baking stale state into the
   program as a constant.  :data:`USER_TRACE_ERRORS` is the whole set
   that may do so; any other failure of the trace or the compile
   propagates to the caller.
"""
from __future__ import annotations

import threading

import jax

from ..core import state as _state


#: Process-wide guard for the bind-trace window.  While a captured body
#: is being traced, :class:`Installed` has swapped TRACER arrays into
#: the captured tensors' ``_data_`` slots — Tensor objects that may be
#: SHARED with other threads (thread-mode serving fleets host several
#: engines over one model).  A concurrent eager forward on another
#: thread would read those tracers and either crash with an
#: UnexpectedTracerError or silently bake a leaked tracer into its own
#: program.  Holders: any capture consumer around its trace/first-call
#: window, and any runtime that invokes a possibly-shared model outside
#: a trace (the serving engine wraps its prefill/decode/spec model
#: calls).  Re-entrant, so a traced body that nests is fine;
#: uncontended acquisition is nanoseconds.
TRACE_LOCK = threading.RLock()


class TraceEscape(Exception):
    """Raised when a captured body performs a host interaction the
    compiled program cannot replay; the caller falls back to its eager
    lane permanently."""

    category = UserWarning


#: What may latch a capture consumer's eager/uncompiled lane: a property
#: of the USER's body — a typed :class:`TraceEscape`, or JAX refusing a
#: host use of a traced value the framework's own hooks did not see
#: (numpy conversion, ``int()``/``bool()``, a tracer leaked through a
#: side channel).  Everything else raised while lowering or compiling
#: (a Pallas ``NotImplementedError``/``ValueError``, ``XlaRuntimeError``,
#: out of memory) is a defect of the framework or the device and
#: propagates: the other lane would call the same kernel anyway, and a
#: silent switch hides that the compiled program never ran.
USER_TRACE_ERRORS = (
    TraceEscape,
    jax.errors.ConcretizationTypeError,    # incl. TracerBoolConversionError
    jax.errors.TracerArrayConversionError,
    jax.errors.TracerIntegerConversionError,
    jax.errors.UnexpectedTracerError,
)


def describe_escape(e):
    """The fallback reason a caught :data:`USER_TRACE_ERRORS` member
    latches: a ``TraceEscape`` speaks for itself, a JAX error is named."""
    return str(e) if isinstance(e, TraceEscape) \
        else f"{type(e).__name__}: {e}"


class Installed:
    """Exception-safe swap of tensors' device-array slots.  Uses the
    raw ``_data_`` slot so installs/restores never fire tracer hooks."""

    def __init__(self, pairs):
        self._saved = [(t, t._data_) for t, _ in pairs]
        self._new = [a for _, a in pairs]

    def __enter__(self):
        for (t, _), a in zip(self._saved, self._new):
            t._data_ = a
        return self

    def __exit__(self, *exc):
        for t, orig in self._saved:
            t._data_ = orig
        return False


class BindTracer:
    """Minimal tracer active while ``jax.jit`` traces a captured body.

    Compared to ``jit/tracer._BindTracer`` it is stricter: any host read
    of a traced value (``float()`` / ``item()`` / ``bool()`` branch)
    raises :class:`TraceEscape` — captured programs support no guard
    re-specialization; such bodies simply run eagerly.

    ``host_scalars`` feeds the legitimate host-scalar providers the body
    is allowed to consume, in call order (the train step's learning
    rate); any provider past the list raises.  ``rng_key`` of ``None``
    forbids framework RNG draws inside the body (the serving tick:
    sampling randomness enters through explicit per-slot keys, never the
    global stream).
    """

    __slots__ = ("created", "mutated", "mutated_list", "rng_counter",
                 "_rng_key", "_scalars", "_scalar_idx")

    def __init__(self, rng_key=None, host_scalars=()):
        self.created = set()
        self.mutated = {}             # id(Tensor) -> pre-write concrete data
        self.mutated_list = []
        self.rng_counter = 0
        self._rng_key = rng_key
        self._scalars = tuple(host_scalars)
        self._scalar_idx = 0

    def on_create(self, t):
        self.created.add(id(t))

    def on_read(self, t):
        # a concrete read of a tensor discovery did not capture would be
        # silently baked into the program as a constant — a stale-state
        # bug.  (Captured tensors hold tracers by now, so they never
        # reach this branch.)
        if (id(t) not in self.created and id(t) not in self.mutated
                and not isinstance(t._data_, jax.core.Tracer)):
            raise TraceEscape(
                "step body read a tensor the discovery pass did not see "
                f"(shape {tuple(t._data_.shape)}, name={t.name!r}) — "
                "control flow diverged between calls")

    def on_write(self, t):
        i = id(t)
        if i not in self.created and i not in self.mutated:
            self.mutated[i] = t._data_
            self.mutated_list.append(t)

    def host_read(self, t, bool_read=False):
        raise TraceEscape(
            "host read of a traced value (float()/item()/bool()) inside "
            "the captured body — the value escapes into python, which "
            "one compiled program cannot replay")

    def host_input(self, provider):
        if self._scalar_idx < len(self._scalars):
            val = self._scalars[self._scalar_idx]
            self._scalar_idx += 1
            return val
        raise TraceEscape("unexpected host-scalar provider in step body")

    def rng_base(self):
        if self._rng_key is None:
            raise TraceEscape(
                "framework RNG draw inside a captured body that feeds "
                "randomness through explicit keys")
        return self._rng_key

    def rollback_mutations(self):
        """Restore any captured tensors still holding tracers after the
        trace to their pre-write concrete values (forward-mutated
        buffers whose updates the program returns as outputs)."""
        for t in self.mutated_list:
            if isinstance(t._data_, jax.core.Tracer):
                orig = self.mutated.get(id(t))
                if orig is not None and not isinstance(
                        orig, jax.core.Tracer):
                    t._data_ = orig


class Discovery:
    """What :func:`run_discovery` hands back: the ordered pre-existing
    tensors the body read (``capture_list``) and whether it drew
    framework RNG (``uses_rng``)."""

    __slots__ = ("capture_list", "uses_rng")

    def __init__(self, capture_list, uses_rng):
        self.capture_list = capture_list
        self.uses_rng = uses_rng


def run_discovery(thunk, *, no_grad=True):
    """Run ``thunk`` once eagerly under a discovery tracer and return a
    :class:`Discovery`.

    Every pre-existing tensor the body reads is captured in read order;
    values at first read/write are snapshotted so the discovery pass's
    side effects (batchnorm running stats, write-only counters, the RNG
    counter) are rolled back to the pre-call state.  Host reads raise
    :class:`TraceEscape` (a ``bool()`` branch gets the specific
    data-dependent-control-flow message) — the caller latches its eager
    fallback.
    """
    from ..jit.tracer import _DiscoveryTracer
    from ..core.state import no_grad as _no_grad

    tr = _DiscoveryTracer()
    read_snap = {}
    write_snap = {}

    def on_read(t):
        if id(t) not in tr.created and id(t) not in read_snap:
            read_snap[id(t)] = (t, t._data_)
        i = id(t)
        if i not in tr.created and i not in tr.captured:
            tr.captured[i] = t
            tr.capture_list.append(t)

    def on_write(t):
        if id(t) not in tr.created and id(t) not in write_snap:
            write_snap[id(t)] = (t, t._data_)

    tr.on_read, tr.on_write = on_read, on_write
    saved_rng = (_state.STATE.rng_key, _state.STATE.rng_counter)
    _state.STATE.tracer = tr
    try:
        if no_grad:
            with _no_grad():
                thunk()
        else:
            thunk()
    finally:
        _state.STATE.tracer = None
        _state.STATE.rng_key, _state.STATE.rng_counter = saved_rng
        for t, arr in write_snap.values():
            t._data_ = arr
        for t, arr in read_snap.values():
            t._data_ = arr
    if any(rec[0] for rec in tr.host_reads):
        raise TraceEscape(
            "data-dependent python branch (bool(tensor)) in the "
            "forward — guard re-specialization is to_static's job")
    if tr.host_reads:
        raise TraceEscape(
            "host read (float()/item()/numpy()) in the forward")
    return Discovery(list(tr.capture_list), tr.rng_counter > 0)
