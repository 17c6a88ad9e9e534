"""Tape-based autograd engine.

Reference capability: the eager autograd engine (reference:
paddle/fluid/eager/backward.cc:104 `RunBackward`, grad_node_info.h:182
`GradNodeBase`).  TPU-native realization: each differentiable op call records a
`GradNode` holding the VJP closure produced by `jax.vjp` — JAX computes the
forward *and* linearizes in one pass, so residuals live in the closure exactly
like the reference's `TensorWrapper` saved tensors.  `run_backward` is a
reverse-topological traversal with cotangent accumulation, mirroring the
reference's ready-queue traversal.

The same engine works under tracing: inside `paddle_tpu.jit.to_static` all
arrays are JAX tracers, so `loss.backward()` composes into the single XLA
program being traced.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from ..observability import tracing as _tracing


class GradNode:
    """One autograd graph node = one recorded op."""

    __slots__ = ("name", "vjp_fn", "inputs", "out_avals", "single_output",
                 "pure", "packed_saved", "saved_hooks", "scopes",
                 "__weakref__")

    def __init__(self, name, vjp_fn, inputs, out_avals, single_output,
                 pure=None):
        self.name = name
        self.vjp_fn = vjp_fn          # cotangents -> per-tensor-input cotangents
        self.inputs = inputs          # tuple[Tensor] aligned with vjp_fn result
        self.out_avals = out_avals    # [(shape, dtype), ...]
        self.single_output = single_output
        self.pure = pure              # primal fn, kept for create_graph replay
        self.packed_saved = None      # saved_tensors_hooks pack() results
        self.saved_hooks = None
        # the program scopes open where the op ran: backward re-enters
        # them, so its operations are named by layer kind too
        self.scopes = _tracing.scope_path()

    def __repr__(self):
        return f"<GradNode {self.name}>"


class _EdgeRef:
    """Topology-only stand-in for an intermediate input tensor when
    saved_tensors_hooks are active: keeps the autograd edge (producer
    node, output index, registered hooks) WITHOUT pinning the tensor's
    device array, so pack() genuinely controls what stays resident
    between forward and backward (reference: TensorWrapper's
    unpack_hook-backed storage, paddle/fluid/eager/tensor_wrapper.h)."""

    __slots__ = ("_grad_node", "_out_index", "stop_gradient", "_hooks")

    def __init__(self, t):
        self._grad_node = t._grad_node
        self._out_index = t._out_index
        self.stop_gradient = t.stop_gradient
        self._hooks = t._hooks


def _accumulate(prev, g):
    """Two cotangents of one value, summed: the tape's own work."""
    with _tracing.scope("grad_accum"):
        return prev + g


def _is_float0(g):
    return g is None or getattr(g, "dtype", None) == jax.dtypes.float0


def _topo_order(roots):
    """Post-order DFS over grad nodes (iterative; graphs can be deep)."""
    order, visited = [], set()
    for root in roots:
        if root is None or id(root) in visited:
            continue
        stack = [(root, False)]
        while stack:
            node, processed = stack.pop()
            if processed:
                order.append(node)
                continue
            if id(node) in visited:
                continue
            visited.add(id(node))
            stack.append((node, True))
            for t in node.inputs:
                n = t._grad_node
                if n is not None and id(n) not in visited and not t.stop_gradient:
                    stack.append((n, False))
    order.reverse()  # consumers before producers
    return order


def _symbolic_vjp(node, cots, prims=None):
    """Compute input cotangents as recorded tape ops (differentiable).

    `prims` overrides the primal tensors read for linearization (used by
    saved_tensors_hooks so unpack's returns are what backward consumes);
    defaults to node.inputs."""
    from .tensor import Tensor
    from .dispatch import apply_op
    n_out = len(cots)
    single = node.single_output
    cot_tensors = tuple(c if isinstance(c, Tensor) else Tensor(c)
                        for c in cots)

    def grad_fn(*all_args):
        cs = all_args[:n_out]
        prim_arrays = all_args[n_out:]
        _, vjp = jax.vjp(node.pure, *prim_arrays)
        out = vjp(cs[0] if single else tuple(cs))
        return tuple(out)

    res = apply_op(node.name + "_grad", grad_fn,
                   cot_tensors + tuple(prims if prims is not None
                                       else node.inputs))
    if not isinstance(res, tuple):
        res = (res,)
    return res


def run_backward(tensors, grad_tensors=None, retain_graph=False,
                 create_graph=False, inputs: Optional[Sequence] = None,
                 allow_unused=False):
    """Reverse-mode traversal.

    With ``inputs=None`` accumulates into leaf ``.grad`` (reference
    `RunBackward`); with ``inputs`` given, returns their gradients without
    touching ``.grad`` (reference `GeneralGrad` / paddle.grad).
    """
    from .tensor import Tensor  # local import to avoid cycle

    tensors = list(tensors)
    if grad_tensors is None:
        grad_tensors = [None] * len(tensors)
    grad_tensors = [g._data if isinstance(g, Tensor) else g for g in grad_tensors]

    # cotangent store: (id(node), out_idx) -> array ; leaves: id(tensor) -> array
    node_cots = {}
    leaf_grads = {}
    id_to_node = {}

    def _add_cot(tensor, g):
        if tensor.stop_gradient or _is_float0(g):
            return
        for hook in tensor._hooks:
            out = hook(Tensor(g) if not isinstance(g, Tensor) else g)
            if out is not None:
                g = out._data if isinstance(out, Tensor) else out
        node = tensor._grad_node
        if node is not None:
            key = (id(node), tensor._out_index)
            id_to_node[id(node)] = node
            prev = node_cots.get(key)
            node_cots[key] = g if prev is None else _accumulate(prev, g)
        else:
            prev = leaf_grads.get(id(tensor))
            leaf_grads[id(tensor)] = g if prev is None \
                else _accumulate(prev, g)

    for t, g in zip(tensors, grad_tensors):
        if g is None:
            if t.size != 1:
                raise RuntimeError(
                    "grad can be implicitly created only for scalar outputs; "
                    f"got shape {t.shape}")
            g = jnp.ones(t._data.shape, t._data.dtype)
        _add_cot(t, g)

    roots = [t._grad_node for t in tensors if t._grad_node is not None
             and not t.stop_gradient]
    order = _topo_order(roots)

    for node in order:
        cots = []
        any_live = False
        for i, (shape, dtype) in enumerate(node.out_avals):
            g = node_cots.pop((id(node), i), None)
            if g is None:
                g = jnp.zeros(shape, dtype)
            else:
                any_live = True
                # accumulated cotangents can be wider than the primal output
                # (e.g. an fp32 loss vjp feeding bf16 logits under AMP O2);
                # jax.vjp requires an exact dtype match
                if g.dtype != dtype:
                    with _tracing.backward_of(node.scopes):
                        g = g.astype(dtype)
            cots.append(g)
        if not any_live:
            continue
        if node.packed_saved is not None:
            # saved_tensors_hooks: pack() REPLACED the saved tensors at
            # forward time (no vjp closure was kept), so backward must
            # unpack and re-linearize the op from unpack's returns — the
            # values backward consumes ARE what unpack produced.  Under
            # retain_graph/create_graph the packed values are kept so the
            # hooks fire again on every backward pass.
            _, _unpack = node.saved_hooks
            unpacked = [_unpack(p) for p in node.packed_saved]
            arrs = [u._data if isinstance(u, Tensor) else jnp.asarray(u)
                    for u in unpacked]
            if create_graph:
                # the symbolic-replay path must linearize at unpack's
                # returns: build per-PASS substitute tensors carrying the
                # unpacked values with the original autograd edges, and
                # transiently swap leaf data so identity-keyed .grad
                # routing still lands on the user's tensors.  node.inputs
                # is never overwritten — every later pass re-unpacks.
                hook_prims, hook_swaps = [], []
                for e, a in zip(node.inputs, arrs):
                    if isinstance(e, Tensor):
                        hook_swaps.append((e, e._data_))
                        e._data_ = a
                        hook_prims.append(e)
                        continue
                    t = Tensor(a, stop_gradient=e.stop_gradient)
                    t._grad_node = e._grad_node
                    t._out_index = e._out_index
                    t._hooks = e._hooks
                    hook_prims.append(t)
            else:
                with _tracing.backward_of(node.scopes):
                    _, node.vjp_fn = jax.vjp(node.pure, *arrs)
            if not (retain_graph or create_graph):
                node.packed_saved = None
        else:
            hook_prims, hook_swaps = None, ()
        if create_graph and node.pure is not None:
            # Higher-order mode: re-derive the VJP as a *recorded op* over
            # (cotangents, primal inputs) so the gradient computation itself
            # is differentiable (reference: GeneralGrad create_graph,
            # paddle/fluid/eager/backward.cc:102).
            try:
                with _tracing.backward_of(node.scopes):
                    in_grads = _symbolic_vjp(node, cots, prims=hook_prims)
            finally:
                # reverse: a tensor appearing twice in node.inputs (x*x)
                # records the already-swapped value as its second "orig"
                for t, orig in reversed(hook_swaps):
                    t._data_ = orig
        else:
            seed = cots[0] if node.single_output else tuple(cots)
            if node.vjp_fn is None:
                raise RuntimeError(
                    f"Trying to backward through {node.name} a second time "
                    "(use retain_graph=True)")
            with _tracing.backward_of(node.scopes):
                in_grads = node.vjp_fn(seed)
        for t, g in zip(node.inputs, in_grads):
            _add_cot(t, g)
        if not retain_graph and not create_graph:
            node.vjp_fn = None  # free residuals eagerly

    if inputs is not None:
        results = []
        for t in inputs:
            g = leaf_grads.get(id(t))
            if g is None and t._grad_node is not None:
                # non-leaf input: its cotangent was folded into its node slot
                g = node_cots.get((id(t._grad_node), t._out_index))
            if g is None and not allow_unused:
                raise RuntimeError(
                    "One of the differentiated tensors appears to not have "
                    "been used in the graph (allow_unused=False)")
            if g is None:
                results.append(None)
            elif isinstance(g, Tensor):
                results.append(g)
            else:
                results.append(Tensor(g, stop_gradient=not create_graph))
        return results

    # accumulate into leaf .grad
    seen = set()
    stack = list(tensors)
    while stack:
        t = stack.pop()
        if id(t) in seen:
            continue
        seen.add(id(t))
        g = leaf_grads.pop(id(t), None)
        if g is not None:
            g_t = g if isinstance(g, Tensor) else Tensor(g)
            if t.grad is None:
                # rewrap unless differentiable (create_graph): .grad must
                # own its buffer slot — a caller-visible cotangent stored
                # directly would be mutated by later in-place
                # accumulation/zeroing
                t.grad = g_t if not g_t.stop_gradient \
                    else Tensor(g_t._data_)
            elif not g_t.stop_gradient or not t.grad.stop_gradient:
                # keep the accumulation differentiable / don't mutate a
                # grad a retained higher-order graph may reference
                t.grad = t.grad + g_t
            else:
                # in-place accumulate (reference eager accumulation node):
                # the grad object's identity stays stable across steps,
                # which compiled segments rely on for capture-by-identity
                t.grad._data = _accumulate(t.grad._data, g_t._data_)
        if t._grad_node is not None:
            stack.extend(t._grad_node.inputs)
    return None
