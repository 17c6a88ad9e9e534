"""Tiered executable cache: the never-recompile-on-the-hot-path subsystem.

Reference capability: the reference framework never re-selects or
re-compiles a kernel on the hot path — eager ad_funcs hit a cached
kernel-selection result (reference: phi/core/kernel_factory.cc
`KernelFactory::SelectKernelOrThrowError` memoized per signature) and
static-graph runs hit an executor cache (reference:
new_executor/interpretercore.cc).  TPU-native realization, three tiers:

- **Tier 1** (this module + core/dispatch.py): an in-process LRU of
  jitted per-op executables keyed by ``(op name, input avals incl.
  weak_type/sharding, frozen non-tensor args + static kwargs, amp level,
  grad flag)``.  Repeated eager calls of the same op signature skip JAX's
  per-primitive eager dispatch and — for grad-requiring ops — the fresh
  ``jax.vjp`` re-trace, executing one cached XLA program instead
  (forward-only ops via cached ``jax.jit(pure)``; grad ops via a cached
  jitted ``jax.vjp`` forward whose vjp closure round-trips through jit as
  a ``jax.tree_util.Partial`` pytree carrying the residuals).
- **Tier 2** (`ensure_compile_cache`): JAX's persistent XLA compilation
  cache, on by default and applied uniformly wherever this framework
  builds executables (jit/tracer.py, static/__init__.py, jit/sot.py,
  onnx/load.py, the compiled train step and serving tick, tier-1
  misses), so re-runs skip XLA recompiles across processes.  One way to
  place it: ``JAX_COMPILATION_CACHE_DIR`` (JAX reads it itself); unset,
  the cache lives in ``<checkout>/.jax_cache``.
- **Tier 3**: observability — hit/miss/evict/bytes counters per tier,
  surfaced through ``paddle_tpu.utils.cache_stats()`` and as
  ``cache_hit`` annotations on profiler op spans.

Fallbacks are byte-for-byte today's path: unhashable statics,
saved-tensor-hooks, tracer inputs, non-registry op impls (per-call
closures), and ``FLAGS_eager_op_cache=False`` all bypass tier 1.  An op
impl observed drawing framework RNG during its compile trace (the key
would be baked into the executable) is permanently opted out.
"""
from __future__ import annotations

import os
import threading
from collections import OrderedDict

import jax

from . import state as _state
from ..observability import registry as _metrics
from ..utils.flags import flag as _flag


_LOCK = threading.RLock()

_UNHASHABLE = object()

# ---------------------------------------------------------------------------
# tier 1: jitted eager-op executable LRU
# ---------------------------------------------------------------------------


class _Entry:
    __slots__ = ("fn", "jitted", "need_grad", "aval_bytes")

    def __init__(self, fn, jitted, need_grad, aval_bytes):
        self.fn = fn                  # strong ref: a hit requires identity,
        self.jitted = jitted          # so a GC'd id can never alias a key
        self.need_grad = need_grad
        self.aval_bytes = aval_bytes


_T1: "OrderedDict[tuple, _Entry]" = OrderedDict()
# tier counters live in the observability registry so cache behavior is
# visible in render_prometheus()/dump_json() alongside everything else;
# cache_stats() below keeps its historical dict shape as a view of them
_T1_STATS = {
    k: _metrics.counter(f"cache.tier1.{k}", f"tier-1 op-cache {k}")
    for k in ("hits", "misses", "evictions", "bypasses")
}
# the HIT path is the per-op hot path (every cached eager op lands here):
# registry Counter.inc takes the metric family's RLock, a second lock
# acquisition per op on top of _LOCK.  Hits are batched in a plain int
# under _LOCK and flushed to the registry counter every _T1_FLUSH_EVERY
# hits and on every slow-path event (miss, cache_stats(), clear()), so
# exposition lags by at most _T1_FLUSH_EVERY - 1 op hits.
_T1_HOT_HITS = [0]
_T1_FLUSH_EVERY = 256


def _flush_hot_hits():
    """Publish batched hit counts into the registry.  Caller holds
    _LOCK."""
    n = _T1_HOT_HITS[0]
    if n:
        _T1_HOT_HITS[0] = 0
        _T1_STATS["hits"].inc(n)


def dispatch_count():
    """Eager ops the funnel has dispatched so far: tier-1 hits, misses
    and bypasses, the hot-hit batch flushed first.  A caller reads it
    before and after a stretch of eager code to count its launches at
    no per-op cost."""
    with _LOCK:
        _flush_hot_hits()
    return sum(_T1_STATS[k].value for k in ("hits", "misses", "bypasses"))


_T1_BYTES = _metrics.gauge("cache.tier1.bytes",
                           "summed input-aval bytes of cached signatures")
# op names permanently opted out: impls that draw framework RNG inside
# (caching would bake the first call's key) or fail to jit-trace
_SKIP_OPS: set = set()

_T2_STATS = {
    k: _metrics.counter(f"cache.tier2.{k}",
                        f"persistent XLA compile cache {k}")
    for k in ("hits", "misses")
}
_T2_DIR = None            # the resolved cache dir, once armed
_COMPILE_MS = _metrics.histogram(
    "jit.compile_ms", "wall time to build one program: an XLA compile "
    "or a load from the persistent compile cache (ms)")


def _freeze(v):
    """Hashable, type-tagged snapshot of a non-tensor op argument.

    Numeric scalars are tagged with their python type so ``2`` and
    ``2.0`` (equal, same hash) cannot share a cache key — the baked
    constant's dtype differs.  Returns _UNHASHABLE when any part cannot
    be hashed (numpy arrays, mutable containers as dict keys, ...)."""
    if isinstance(v, (bool, int, float, complex)):
        return (type(v).__name__, v)
    if isinstance(v, (list, tuple)):
        out = []
        for e in v:
            f = _freeze(e)
            if f is _UNHASHABLE:
                return _UNHASHABLE
            out.append(f)
        return (type(v).__name__, tuple(out))
    if isinstance(v, dict):
        items = []
        try:
            keys = sorted(v)
        except TypeError:
            return _UNHASHABLE
        for k in keys:
            f = _freeze(v[k])
            if f is _UNHASHABLE:
                return _UNHASHABLE
            items.append((k, f))
        return ("dict", tuple(items))
    try:
        hash(v)
    except TypeError:
        return _UNHASHABLE
    return v


def _tier1_key(name, arrays, template, static, need_grad):
    try:
        # ShapedArray avals are hashable and carry shape/dtype/weak_type
        # in one object; sharding keeps multi-device arrays distinct
        avals = tuple((a.aval, a.sharding) for a in arrays)
    except Exception:
        return None
    ft = _freeze(template)
    if ft is _UNHASHABLE:
        return None
    fs = _freeze(static) if static else ()
    if fs is _UNHASHABLE:
        return None
    # amp level is in the key: the cast already happened upstream so avals
    # capture the dtype, but a level flip mid-run must never serve an
    # executable recorded under the other mode
    return (name, need_grad, _state.STATE.amp_level, avals, ft, fs)


def _registered_fn(name):
    from ..ops.registry import get_op
    od = get_op(name)
    return od.fn if od is not None else None


def tier1_execute(name, fn, pure, arrays, template, static, need_grad):
    """Execute the op through the tier-1 cache when eligible.

    Returns ``(out, vjp_fn, hit)`` — or None, in which case the caller
    MUST run the uncached path (byte-for-byte fallback)."""
    if not _flag("FLAGS_eager_op_cache", True) or name in _SKIP_OPS:
        return None
    # only the registry-registered impl is cacheable: per-call closures
    # (dropout's rate-closing fn, _symbolic_vjp's grad_fn) capture state
    # the key cannot see, and keying by id() would alias after GC
    if _registered_fn(name) is not fn:
        return None
    for a in arrays:
        if isinstance(a, jax.core.Tracer):
            return None               # to_static bind trace / nested vjp
    key = _tier1_key(name, arrays, template, static, need_grad)
    if key is None:
        _T1_STATS["bypasses"].inc()
        return None

    with _LOCK:
        entry = _T1.get(key)
        if entry is not None:
            _T1.move_to_end(key)
            _T1_HOT_HITS[0] += 1
            if _T1_HOT_HITS[0] >= _T1_FLUSH_EVERY:
                _flush_hot_hits()
    if entry is not None:
        if entry.fn is not fn:
            return None               # op re-registered since caching
        if entry.need_grad:
            out, vjp_fn = entry.jitted(*arrays)
        else:
            out, vjp_fn = entry.jitted(*arrays), None
        return out, vjp_fn, True

    # ---- miss: build + trace the per-signature executable ----
    ensure_compile_cache()            # tier 2 catches the XLA compile
    if need_grad:
        # jax.vjp's closure is a jax.tree_util.Partial — a pytree whose
        # leaves are the residuals — so it round-trips through jit: the
        # cached executable computes forward + residuals in one XLA
        # program and the vjp closure is rebuilt from them on return
        jitted = jax.jit(lambda *xs: jax.vjp(pure, *xs))
    else:
        jitted = jax.jit(pure)
    tr = _state.STATE.tracer
    rng0 = _state.STATE.rng_counter + (getattr(tr, "rng_counter", 0)
                                       if tr is not None else 0)
    try:
        if need_grad:
            out, vjp_fn = jitted(*arrays)
        else:
            out, vjp_fn = jitted(*arrays), None
    except Exception:
        # impl does something jit can't trace (host reads, numpy
        # round-trips): permanently opt out and re-run uncached.  A
        # partial trace has no visible side effects to undo — op impls
        # are pure JAX by contract, and an RNG draw mid-trace just
        # advances the counter (the uncached re-run takes the next key).
        with _LOCK:
            _SKIP_OPS.add(name)
        _T1_STATS["bypasses"].inc()
        return None
    rng1 = _state.STATE.rng_counter + (getattr(tr, "rng_counter", 0)
                                       if tr is not None else 0)
    if rng1 != rng0:
        # the impl drew framework RNG during the trace: the key is baked
        # into this executable.  THIS call's result is correct (the trace
        # ran with a genuinely fresh key); never serve it again.
        with _LOCK:
            _SKIP_OPS.add(name)
        return out, vjp_fn, False

    aval_bytes = sum(int(getattr(a, "nbytes", 0) or 0) for a in arrays)
    with _LOCK:
        _flush_hot_hits()
        _T1_STATS["misses"].inc()
        _T1[key] = _Entry(fn, jitted, need_grad, aval_bytes)
        _T1_BYTES.inc(aval_bytes)
        cap = int(_flag("FLAGS_eager_op_cache_size", 4096) or 4096)
        while len(_T1) > cap:
            _, old = _T1.popitem(last=False)
            _T1_STATS["evictions"].inc()
            _T1_BYTES.dec(old.aval_bytes)
    return out, vjp_fn, False


def clear():
    """Drop every tier-1 entry and reset counters (tests/benchmarks)."""
    with _LOCK:
        _T1.clear()
        _SKIP_OPS.clear()
        _T1_HOT_HITS[0] = 0
        for c in _T1_STATS.values():
            c.reset()
        _T1_BYTES.reset()
        for c in _T2_STATS.values():
            c.reset()


# ---------------------------------------------------------------------------
# tier 2: persistent XLA compilation cache
# ---------------------------------------------------------------------------


def _t2_listener(event, **kwargs):
    if not isinstance(event, str):
        return
    if event.endswith("/compilation_cache/cache_hits"):
        _T2_STATS["hits"].inc()
    elif event.endswith("/compilation_cache/cache_misses"):
        _T2_STATS["misses"].inc()


def _compile_listener(event, duration_secs, **kwargs):
    # jax times ``compile_or_get_cached`` as one backend-compile event,
    # whether XLA compiled the program or the persistent cache gave it
    # back: every program built or loaded, once, with its seconds
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_MS.observe(duration_secs * 1e3)


#: where the cache lives when the environment does not place it: next
#: to the package, absolute and the same in every process of a checkout
#: (never a temp name, a pid or a time — a directory that moves never
#: hits).  ``.gitignore`` lists it.
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def ensure_compile_cache():
    """Arm JAX's persistent compilation cache and return its directory.
    Idempotent and cheap once armed — every executable-building seam
    calls it right before compiling.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX's own reading of it stands
    and nothing here touches the directory option.  Unset: the cache is
    placed in ``<checkout>/.jax_cache``."""
    global _T2_DIR
    if _T2_DIR is not None:
        return _T2_DIR
    with _LOCK:
        if _T2_DIR is not None:
            return _T2_DIR
        d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if not d:
            d = _DEFAULT_CACHE_DIR
            jax.config.update("jax_compilation_cache_dir", d)
            # jax latches its cache object (or its absence) at the FIRST
            # compile: any compile before this point — framework import
            # triggers several — froze the disabled state, and the dir
            # update alone is ignored until the latch is reset
            from jax.experimental.compilation_cache import \
                compilation_cache as _cc
            _cc.reset_cache()
        # cache everything: the defaults skip sub-second compiles, which
        # is every compile in the CPU test mesh and most eager-op
        # programs (hundreds of them on a cold chip run)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.monitoring.register_event_listener(_t2_listener)
        jax.monitoring.register_event_duration_secs_listener(
            _compile_listener)
        _T2_DIR = d
    return d


# ---------------------------------------------------------------------------
# tier 3: observability
# ---------------------------------------------------------------------------


def cache_stats():
    """Per-tier counters (the `paddle_tpu.utils.cache_stats()` payload).

    tier1.bytes is the summed input-aval bytes of cached signatures — a
    proxy for the residual footprint the cached vjp executables touch,
    not XLA code size (which jax does not expose per jit wrapper).
    tier2 entries/bytes are measured from the cache directory."""
    with _LOCK:
        _flush_hot_hits()
        t1 = {k: c.value for k, c in _T1_STATS.items()}
        t1["bytes"] = _T1_BYTES.value
        t1["entries"] = len(_T1)
        t1["capacity"] = int(_flag("FLAGS_eager_op_cache_size", 4096)
                             or 4096)
        t1["skipped_ops"] = sorted(_SKIP_OPS)
        t2 = {k: c.value for k, c in _T2_STATS.items()}
    d = _T2_DIR
    t2["enabled"] = d is not None
    t2["dir"] = d
    entries = 0
    nbytes = 0
    if d and os.path.isdir(d):
        try:
            for fe in os.scandir(d):
                if not fe.is_file():
                    continue
                if not fe.name.endswith("-atime"):
                    entries += 1
                try:
                    nbytes += fe.stat().st_size
                except OSError:
                    pass
        except OSError:
            pass
    t2["entries"] = entries
    t2["bytes"] = nbytes
    return {"tier1": t1, "tier2": t2}
