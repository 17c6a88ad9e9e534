"""Single-program SPMD pipeline schedule: collective-permute pipelining.

Reference capability: the 1F1B schedule (reference:
fleet/meta_parallel/pipeline_parallel.py:397-603) and the interleaved
virtual pipeline (`PipelineParallelWithInterleave`, :832) with batched p2p
activation exchange (pp_utils/p2p_communication.py:302).

TPU-native realization: instead of a host-driven issue order over per-stage
programs, the WHOLE schedule is one compiled XLA program — `shard_map` over
the `pp` mesh axis, `lax.scan` over schedule ticks, one cyclic
`lax.ppermute` per tick for the stage-boundary activation hand-off (the
compiled p2p).  Every pp rank executes the same instruction stream on its
own stage's weights, so stage compute for different micro-batches overlaps
by construction — the property the reference's 1F1B issue order exists to
create.

Schedule (circular wavefront): with S stages, C chunks per stage (virtual
pipeline), micro-batch m = g*S + mig (group g, offset mig < S) is processed
by rank r with chunk c at tick

    t = r + c*S + g*S*C + mig

This is a valid schedule: each (tick, rank) pair decodes to at most one
(micro, chunk) via u = t - r; the producer of every activation ran at tick
t-1 one rank earlier (cyclically — the S-1 → 0 wrap is exactly the chunk
c → c+1 hand-off), so ONE cyclic ppermute per tick moves every in-flight
activation where it needs to be.  C=1 degenerates to the classic GPipe
wavefront (T = M + S - 1 ticks); C>1 shrinks the pipeline bubble by 1/C at
the cost of one extra ring pass — the same trade as Megatron's interleaved
1F1B (reference pipeline_parallel.py:832).

Backward is `jax.vjp` through the scan: XLA transposes the ppermute into
the reverse hand-off, giving the backward pipeline for free.  Per-tick
rematerialisation (`jax.checkpoint` around the stage body) keeps live
activation memory at O(carry) per tick instead of O(full residuals) — the
memory property 1F1B exists to create.
"""
from __future__ import annotations

import numpy as np

from ....core import state as _state
from ....core.state import no_grad
from ....core.tensor import Tensor
from ....nn.layer import Layer
from ...placement import Replicate, Shard


class NotHomogeneous(ValueError):
    """Stage parts cannot be stacked (heterogeneous structure)."""


def _part_items(part):
    return [(item, fwd) for item, fwd, _shared in part]


def _item_params(item):
    return list(item.parameters()) if isinstance(item, Layer) else []


def _items_params(items):
    out = []
    for item, _fwd in items:
        out.extend(_item_params(item))
    return out


def _sig(items):
    """Stackability signature: per-item structural identity (layer class /
    callable name, forward-func name) plus per-param (shape, dtype).
    Structure matters, not just parameters — stages with identical params
    but different param-free ops (ReLU vs Tanh) must NOT stack, because
    every stacked part executes the template part's ops."""
    out = []
    for item, fwd in items:
        if isinstance(item, Layer):
            ident = type(item).__name__
        else:
            ident = getattr(item, "__qualname__", type(item).__name__)
        fident = (getattr(fwd, "__qualname__", repr(fwd))
                  if fwd is not None else None)
        psig = tuple((tuple(p._data_.shape), str(p._data_.dtype))
                     for p in _item_params(item))
        out.append((ident, fident, psig))
    return tuple(out)


def homogenize(parts):
    """Split execution-ordered parts into (pre_items, body_parts,
    post_items): strip leading items of the first part / trailing items of
    the last part until every part has the same param signature.  Raises
    NotHomogeneous when no such split exists (e.g. unequal blocks per
    stage)."""
    parts = [_part_items(p) for p in parts]
    if len(parts) < 2:
        raise NotHomogeneous("pipelining needs >= 2 parts")
    mid = [_sig(p) for p in parts[1:-1]]
    if mid and any(s != mid[0] for s in mid):
        raise NotHomogeneous(f"middle stage parts differ: {set(mid)}")
    target = mid[0] if mid else None

    first, last = list(parts[0]), list(parts[-1])
    pre, post = [], []
    if target is None:
        # two parts: strip first down until its sig matches last's remainder
        for cut in range(len(first) + 1):
            for rcut in range(len(last) + 1):
                body_f = first[cut:]
                body_l = last[:len(last) - rcut]
                if _sig(body_f) == _sig(body_l) and _sig(body_f):
                    return (first[:cut],
                            [body_f] + [body_l],
                            last[len(last) - rcut:])
        raise NotHomogeneous("no common stage structure between the 2 parts")
    while first and _sig(first) != target:
        pre.append(first.pop(0))
    while last and _sig(last) != target:
        post.insert(0, last.pop())
    if _sig(first) != target or _sig(last) != target or not target:
        raise NotHomogeneous(
            f"first/last stage parts irreducible to middle signature "
            f"(first={_sig(first)}, mid={target}, last={_sig(last)})")
    return pre, [first] + parts[1:-1] + [last], post


def _run_items(items, x):
    for item, fwd in items:
        x = fwd(item, x) if fwd is not None else item(x)
    return x


class SPMDPipeline:
    """Compiled pipeline runner for a homogeneous-body PipelineLayer.

    Owns the STACKED body parameters ([S, C, *shape], axis 0 sharded over
    pp) — these are the authoritative, optimizer-visible tensors; the
    original per-part layer params become a template through which the
    stage body is traced.  `write_back()` unstacks into the per-part params
    (for state_dict/checkpoint parity with the host-scheduled path).
    """

    def __init__(self, pipeline_layer, n_micro, remat=True):
        import jax

        self._pl = pipeline_layer
        self._mesh = pipeline_layer._mesh
        self._S = pipeline_layer._num_stages
        self._C = pipeline_layer._num_chunks
        self._n_micro = n_micro
        self._remat = remat
        self._loss_fn = pipeline_layer._loss_fn
        if self._mesh is None or "pp" not in self._mesh.dim_names \
                or self._mesh.get_dim_size("pp") != self._S:
            raise NotHomogeneous("mesh pp axis does not match num_stages")

        self._jitted = None
        self.pre, body_parts, self.post = homogenize(pipeline_layer._parts)
        # schedule depth: last micro's exit tick + 1.  The whole point:
        # M+S-1 wavefront ticks (C=1) instead of M*S serialized stage
        # applications — each tick runs ONE stage application on EVERY
        # pp rank concurrently.
        M, S, C = n_micro, self._S, self._C
        self.num_ticks = ((M - 1) // S) * S * C + (M - 1) % S + S * C
        # template = the first body part's layer objects; all stacked
        # chunks are traced through it
        self._template = body_parts[0]
        self._body_params = _items_params(self._template)
        if not self._body_params:
            raise NotHomogeneous("stage body has no parameters")
        self._body_parts = body_parts

        # unique pre/post params, re-committed onto the FULL mesh
        # (replicated over pp; TP placements kept) so the single compiled
        # program sees one device assignment
        seen, self._edge_params = set(), []
        for p in _items_params(self.pre) + _items_params(self.post):
            if id(p) not in seen:
                seen.add(id(p))
                self._edge_params.append(p)
        from ...placement import commit_param
        for p in self._edge_params:
            placements = [Replicate() for _ in self._mesh.dim_names]
            ann = getattr(p, "mp_placement", None)
            if ann is not None and ann[0] in self._mesh.dim_names:
                placements[self._mesh.dim_names.index(ann[0])] = ann[1]
            commit_param(p, self._mesh, placements)

        self._stack_params()

    # ---------------- stacked parameter management ----------------
    def _stacked_sharding(self, param):
        """NamedSharding for a stacked [S, C, *shape] param: axis 0 over
        pp, original TP placement shifted by the two leading axes."""
        from jax.sharding import NamedSharding, PartitionSpec
        entries = [None] * (param._data_.ndim + 2)
        entries[0] = "pp"
        ann = getattr(param, "mp_placement", None)
        if ann is not None and ann[0] in self._mesh.dim_names \
                and isinstance(ann[1], Shard):
            entries[2 + ann[1].dim] = ann[0]
        while entries and entries[-1] is None:
            entries.pop()
        return NamedSharding(self._mesh.jax_mesh, PartitionSpec(*entries))

    def _stack_params(self):
        """Build (or refresh) stacked Tensors from the per-part params
        (S*C parts, execution order part p = c*S + s → stacked[s, c]).
        Refreshing updates the EXISTING Tensor objects in place — an
        optimizer holds references to them, so replacing the objects
        would silently orphan its parameter list (checkpoint resume)."""
        import jax
        import jax.numpy as jnp

        S, C = self._S, self._C
        per_part = [_items_params(p) for p in self._body_parts]
        n = len(self._body_params)
        if any(len(pp) != n for pp in per_part):
            raise NotHomogeneous("inconsistent param counts across parts")
        fresh = not getattr(self, "stacked", None)
        if fresh:
            self.stacked = []
        for j in range(n):
            # [S, C, *shape]
            arr = jnp.stack([
                jnp.stack([np.asarray(per_part[c * S + s][j]._data_)
                           for c in range(C)])
                for s in range(S)])
            arr = jax.device_put(arr,
                                 self._stacked_sharding(self._body_params[j]))
            if fresh:
                t = Tensor(arr, stop_gradient=False)
                t.name = f"pipeline_stacked_{j}_" \
                         f"{getattr(self._body_params[j], 'name', j)}"
                self.stacked.append(t)
            else:
                self.stacked[j]._data_ = arr
        self._dirty = False

    def write_back(self):
        """Unstack the authoritative stacked params into the per-part layer
        params (state_dict/checkpoint path).  No-op while clean — run()
        marks the runner dirty, so eval loops don't re-unstack per batch."""
        import jax
        if not getattr(self, "_dirty", True):
            return
        S = self._S
        per_part = [_items_params(p) for p in self._body_parts]
        for j, t in enumerate(self.stacked):
            for p_idx, part in enumerate(self._body_parts):
                s, c = p_idx % S, p_idx // S
                target = per_part[p_idx][j]
                sl = t._data_[s, c]
                if getattr(target, "process_mesh", None) is not None:
                    from ...placement import named_sharding
                    sl = jax.device_put(sl, named_sharding(
                        target.process_mesh,
                        target.placements or
                        [Replicate()
                         for _ in target.process_mesh.dim_names],
                        sl.ndim))
                target._data_ = sl
        self._dirty = False

    def read_from_layers(self):
        """Re-stack from the per-part params (set_state_dict path)."""
        self._stack_params()

    def parameters(self):
        return list(self.stacked) + list(self._edge_params)

    # ---------------- the compiled schedule ----------------
    def _stage_apply(self, chunk_arrays, x_arr, rng_key):
        """One stage body application, traced through the template part."""
        saved = [(p, p._data_) for p in self._body_params]
        saved_rng = _state.STATE.rng_key, _state.STATE.rng_counter
        _state.STATE.rng_key = rng_key
        _state.STATE.rng_counter = 0
        try:
            for p, a in zip(self._body_params, chunk_arrays):
                p._data_ = a
            t = Tensor(x_arr, stop_gradient=True)
            out = _run_items(self._template, t)
            return out._data_
        finally:
            for p, a in saved:
                p._data_ = a
            _state.STATE.rng_key, _state.STATE.rng_counter = saved_rng

    def _pipeline_fn(self, x_arr, y_arr, base_key, edge_arrays,
                     stacked_arrays):
        """Pure: (micro-batched inputs, labels, params) → mean loss.

        Always executed under jax.jit (see run()): the partial-manual
        shard_map inside must go through the abstract tracing path — its
        eager impl re-shards concrete operands with internal specs that
        refer to auto axes and rejects them."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        S, C, M = self._S, self._C, self._n_micro
        SC = S * C
        T = self.num_ticks

        with no_grad():
            # ---- pre (embedding etc.) on the full mesh ----
            saved = [(p, p._data_) for p in self._edge_params]
            try:
                for p, a in zip(self._edge_params, edge_arrays):
                    p._data_ = a
                h = _run_items(self.pre, Tensor(x_arr, stop_gradient=True))
                h = h._data_
                mb = h.shape[0] // M
                micros = h.reshape((M, mb) + h.shape[1:])

                stage = self._stage_apply
                if self._remat:
                    stage = jax.checkpoint(stage)

                def tick_loop(stacked_local, micros_rep):
                    # stacked_local leaves: [1, C, *shape] → [C, *shape]
                    local = [a[0] for a in stacked_local]
                    r = lax.axis_index("pp")
                    zero = jnp.zeros(micros_rep.shape[1:],
                                     micros_rep.dtype)

                    def body(carry, t):
                        recv = carry
                        u = t - r
                        g = jnp.maximum(u, 0) // SC
                        span = jnp.maximum(u, 0) % SC
                        c = span // S
                        mig = span % S
                        m = g * S + mig
                        valid = (u >= 0) & (m < M)
                        inject = valid & (r == 0) & (c == 0)
                        m_c = jnp.clip(m, 0, M - 1)
                        x_in = jnp.where(
                            inject,
                            lax.dynamic_index_in_dim(micros_rep, m_c, 0,
                                                     keepdims=False),
                            recv)
                        if C == 1:
                            chunk = [a[0] for a in local]
                        else:
                            c_c = jnp.clip(c, 0, C - 1)
                            chunk = [lax.dynamic_index_in_dim(
                                a, c_c, 0, keepdims=False) for a in local]
                        key = jax.random.fold_in(base_key, t)
                        y = stage(chunk, x_in, key)
                        y = jnp.where(valid, y, zero)
                        emit = valid & (r == S - 1) & (c == C - 1)
                        out = jnp.where(emit, y, zero)
                        send = lax.ppermute(
                            y, "pp", [(i, (i + 1) % S) for i in range(S)])
                        return send, out

                    _, ys = lax.scan(body, zero, jnp.arange(T))
                    return ys[None]  # [1, T, mb, ...]

                # manual over pp only: the other axes stay auto, so
                # committed specs naming them pass through (ZeRO-stacked
                # pp × sep/mp combinations)
                pipelined = jax.shard_map(
                    tick_loop,
                    mesh=self._mesh.jax_mesh,
                    in_specs=([P("pp")] * len(stacked_arrays), P()),
                    out_specs=P("pp"),
                    axis_names={"pp"}, check_vma=False)
                ys = pipelined(list(stacked_arrays), micros)  # [S, T, ...]

                # collect each micro's exit tick from the last rank
                t_end = np.array([(m // S) * SC + m % S + SC - 1
                                  for m in range(M)])
                body_out = jnp.take(ys[S - 1], jnp.asarray(t_end), axis=0)
                h_out = body_out.reshape((M * mb,) + body_out.shape[2:])

                # ---- post (final norm / head) + loss on the full batch ----
                out = _run_items(self.post,
                                 Tensor(h_out, stop_gradient=True))
                if self._loss_fn is not None and y_arr is not None:
                    loss = self._loss_fn(out,
                                         Tensor(y_arr, stop_gradient=True))
                else:
                    loss = out
                return loss._data_ if isinstance(loss, Tensor) else loss
            finally:
                for p, a in saved:
                    p._data_ = a

    def run(self, inputs, labels):
        """One pipelined forward+loss with gradients to all params via the
        framework tape (backward() then accumulates into .grad)."""
        from ....core.dispatch import apply_op
        from ....core.state import next_rng_key

        x = inputs if isinstance(inputs, Tensor) else Tensor(inputs)
        y = labels if isinstance(labels, Tensor) or labels is None \
            else Tensor(labels)
        if x.shape[0] % self._n_micro:
            raise ValueError(
                f"batch {x.shape[0]} not divisible by accumulate_steps "
                f"{self._n_micro}")
        base_key = next_rng_key()
        n_edge = len(self._edge_params)
        if self._jitted is None:
            import jax
            self._jitted = jax.jit(self._pipeline_fn)

        def fn(x_arr, y_arr, *param_arrays):
            return self._jitted(x_arr, y_arr, base_key,
                                list(param_arrays[:n_edge]),
                                list(param_arrays[n_edge:]))

        args = (x, y, *self._edge_params, *self.stacked)
        return apply_op("pipeline_spmd", fn, args)
