"""Op dispatch: the single funnel every framework op goes through.

Reference capability: the generated `*_ad_func` eager forwards (reference:
paddle/fluid/eager/auto_code_generator/generator/eager_gen.py:243) — AMP
auto-cast hook, grad-requirement check, grad-node construction, kernel call.
TPU-native realization: the "kernel" is a pure JAX function; when gradients are
required we run it through `jax.vjp`, which computes the forward and returns
the VJP closure in one pass (forward cost identical, residuals saved by JAX —
the analogue of the reference's TensorWrapper saved tensors).
"""
from __future__ import annotations

import jax

from . import state as _state
from .tensor import Tensor
from .autograd import GradNode
from ..observability.tracing import scope as _scope


_DECOMP = None
_PROF = None
_OPC = None

# Structural ops whose inputs are loop/branch state plus hoisted captures —
# AMP casting them at the boundary would silently down/up-cast parameters
# and integer loop state; the ops INSIDE the loop body do their own AMP
# casting when traced (tensor_ops/control.py).
_AMP_SKIP = frozenset({"while_loop", "cond"})


def _amp_cast(name, arrays):
    """bf16 autocast hook (reference: eager_amp_auto_cast.h insertion point)."""
    from ..amp.amp_lists import WHITE_LIST, BLACK_LIST
    st = _state.STATE
    if st.amp_level not in ("O1", "O2"):
        return arrays
    white = (name in WHITE_LIST or name in st.amp_custom_white_list)
    black = (name in BLACK_LIST or name in st.amp_custom_black_list)
    if st.amp_level == "O2":
        # O2: everything except the black list runs in amp dtype
        white = not black
    if white and not black:
        target = st.amp_dtype
    elif black:
        target = jax.numpy.float32
    else:
        return arrays
    out = []
    # a scope of its own under the layer's: a cast XLA does not fuse
    # into its consumer is told from the layer's products by name
    with _scope("amp_cast"):
        for a in arrays:
            if hasattr(a, "dtype") and a.dtype in (jax.numpy.float32,
                                                   jax.numpy.float16,
                                                   jax.numpy.bfloat16):
                out.append(a.astype(target))
            else:
                out.append(a)
    return out


def apply_op(name, fn, args, static=None, nondiff=False):
    """Execute op `fn` over `args` (mix of Tensors and python values).

    fn receives raw arrays in place of Tensors, followed by **static kwargs.
    Returns Tensor or tuple of Tensors; records a GradNode when needed.
    """
    static = static or {}
    # prim mode: substitute the registered primitive decomposition
    # (reference: decomposition/decomp.py applied via _set_prim_all_enabled)
    # — module ref bound once lazily; the off path is one flag check
    global _DECOMP
    if _DECOMP is None:
        from .. import decomposition as _DECOMP_mod
        _DECOMP = _DECOMP_mod
    if _DECOMP._ENABLED:
        fn = _DECOMP.maybe_decompose(name, fn)
    if static and any(isinstance(v, Tensor) for v in static.values()):
        # Tensors passed by keyword must flow through the vjp path, not be
        # silently captured as constants — rebind them positionally.
        import inspect
        sig = inspect.signature(fn)
        bound = sig.bind(*args, **static)
        bound.apply_defaults()
        args = tuple(bound.arguments.values())
        static = {}
    # Tensors may sit at a top-level position or inside a list/tuple arg
    # (concat/stack-style ops) — both must flow through the vjp path, not
    # be captured as constants.  Only promote a sequence when every
    # element is a Tensor AND at least one is floating/complex: shape-like
    # lists (reshape's [n, -1], all-int scalars) must stay concrete so the
    # op impl can call int() on them, and int tensors carry no gradient.
    def _floaty(t):
        return jax.numpy.issubdtype(t._data.dtype, jax.numpy.floating) or \
            jax.numpy.issubdtype(t._data.dtype, jax.numpy.complexfloating)

    tensor_paths = []
    for i, a in enumerate(args):
        if isinstance(a, Tensor):
            tensor_paths.append((i, None))
        elif isinstance(a, (list, tuple)) and a and \
                all(isinstance(b, Tensor) for b in a) and \
                any(_floaty(b) for b in a):
            for j in range(len(a)):
                tensor_paths.append((i, j))
    tensors = tuple(args[i] if j is None else args[i][j]
                    for i, j in tensor_paths)
    arrays = [t._data for t in tensors]

    if _state.STATE.amp_level in ("O1", "O2") and name not in _AMP_SKIP:
        arrays = _amp_cast(name, arrays)

    # per-op profiling spans (reference: RecordEvent instrumentation in
    # the generated ad_funcs + CUPTI kernel timing) — lazily bound, one
    # cheap check when no profiler records
    global _PROF
    if _PROF is None:
        from ..profiler import profiler as _PROF
    prof_on = _PROF.op_profiling_active()
    if prof_on:
        import time as _time
        _t0 = _time.perf_counter_ns()

    # `pure` must not close over the input Tensors (or their arrays): under
    # saved_tensors_hooks the node keeps `pure` for backward re-linearization,
    # and a closure pinning the original device arrays would defeat offload
    # hooks.  Blank the tensor slots out of the captured template.
    template = [list(a) if isinstance(a, (list, tuple)) else a for a in args]
    for (i, j) in tensor_paths:
        if j is None:
            template[i] = None
        else:
            template[i][j] = None

    def pure(*xs):
        full = [list(a) if isinstance(a, list) else a for a in template]
        for (i, j), x in zip(tensor_paths, xs):
            if j is None:
                full[i] = x
            else:
                full[i][j] = x
        return fn(*full, **static)

    need_grad = (_state.STATE.grad_enabled and not nondiff
                 and any(not t.stop_gradient for t in tensors))
    hooks = getattr(_state.STATE, "saved_tensor_hooks", None) \
        if need_grad else None

    # tiered executable cache (core/op_cache.py): repeated eager calls of
    # the same op signature execute one cached jitted program instead of
    # re-tracing/re-dispatching — the analogue of the reference's memoized
    # KernelFactory::SelectKernelOrThrowError result.  cache_hit stays
    # None on every bypass path (byte-for-byte today's behavior).
    global _OPC
    if _OPC is None:
        from . import op_cache as _OPC
    cache_hit = None
    cached = None
    if hooks is None:
        cached = _OPC.tier1_execute(name, fn, pure, arrays, template,
                                    static, need_grad)
    if cached is not None:
        out, vjp_fn, cache_hit = cached
    elif hooks is not None:
        # saved_tensors_hooks active: do NOT linearize now — jax.vjp's
        # closure would pin every residual, defeating offload/quantize
        # hooks.  pack() the op inputs (as the op sees them, i.e. after
        # AMP cast) instead; backward unpacks and re-linearizes from the
        # packed values, so pack's result REPLACES the saved tensors and
        # unpack's return is what backward consumes (reference contract:
        # python/paddle/autograd/saved_tensors_hooks.py).
        out = pure(*arrays)
        vjp_fn = None
    elif need_grad:
        out, vjp_fn = jax.vjp(pure, *arrays)
    else:
        out = pure(*arrays)
        vjp_fn = None

    single = not isinstance(out, (tuple, list))
    outs = (out,) if single else tuple(out)

    if prof_on:
        _PROF.record_op_span(
            name, _t0, _time.perf_counter_ns(), outs,
            tuple(tuple(getattr(a, "shape", ())) for a in arrays), static,
            cache_hit=cache_hit)

    fc = _state.STATE.flops_counter
    if fc is not None:
        fc.add(name,
               tuple(tuple(getattr(a, "shape", ())) for a in arrays),
               static)
    osc = getattr(_state.STATE, "op_stats_collector", None)
    if osc is not None:   # amp.debugging collect_operator_stats context
        osc._record(name, outs)

    # NaN/Inf scanning of every op output when FLAGS_check_nan_inf is set
    # (reference: eager nan_inf_utils.h:38 + FLAGS_check_nan_inf,
    # phi/core/flags.cc:74).  Only active eagerly — tracers are symbolic.
    from ..utils.flags import flag as _flag
    if _flag("FLAGS_check_nan_inf"):
        _check_nan_inf(name, outs)
    out_tensors = []
    node = None
    if need_grad:
        out_avals = [(o.shape, o.dtype) for o in outs]
        if hooks is not None:
            from .autograd import _EdgeRef
            pack, _ = hooks
            # pack the arrays the op actually consumed (post-AMP-cast), so
            # backward's re-linearization reproduces the forward exactly
            packed = [pack(t if a is t._data else
                           Tensor(a, stop_gradient=True))
                      for t, a in zip(tensors, arrays)]
            # keep only the autograd edge for intermediates — holding the
            # Tensor itself would pin the activation pack() just offloaded
            edges = tuple(_EdgeRef(t) if t._grad_node is not None else t
                          for t in tensors)
            node = GradNode(name, None, edges, out_avals, single, pure=pure)
            node.packed_saved = packed
            node.saved_hooks = hooks
        else:
            node = GradNode(name, vjp_fn, tensors, out_avals, single,
                            pure=pure)
    for i, o in enumerate(outs):
        t = Tensor(o, stop_gradient=not need_grad)
        if node is not None:
            t._grad_node = node
            t._out_index = i
        out_tensors.append(t)
    return out_tensors[0] if single else tuple(out_tensors)


def _check_nan_inf(name, outs):
    import numpy as np
    from ..utils.flags import flag as _flag
    for i, o in enumerate(outs):
        if isinstance(o, jax.core.Tracer) or not hasattr(o, "dtype"):
            continue
        if not jax.numpy.issubdtype(o.dtype, jax.numpy.floating):
            continue
        bad = ~jax.numpy.isfinite(o)
        if bool(bad.any()):
            n_nan = int(jax.numpy.isnan(o).sum())
            n_inf = int(jax.numpy.isinf(o).sum())
            msg = (f"op '{name}' output {i} contains {n_nan} NaN / "
                   f"{n_inf} Inf values (shape {tuple(o.shape)})")
            level = int(_flag("FLAGS_check_nan_inf_level", 0))
            if level >= 3:
                print(f"[check_nan_inf] WARNING: {msg}")
            else:
                raise FloatingPointError(msg)


def defop(name, nondiff=False):
    """Decorator registering a pure-JAX implementation as a framework op.

    The wrapped function's public signature takes Tensors; internally it is
    called with raw arrays.  Also records the op in the registry (the
    reference's ops.yaml analogue) for introspection/SPMD-rule attachment.
    """
    from ..ops.registry import register_op

    def deco(fn):
        register_op(name, fn, nondiff=nondiff)

        def wrapper(*args, **kwargs):
            return apply_op(name, fn, args, static=kwargs, nondiff=nondiff)
        wrapper.__name__ = name
        wrapper.__doc__ = fn.__doc__
        wrapper.__wrapped__ = fn
        return wrapper
    return deco
