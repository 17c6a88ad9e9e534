"""Compiled train step: the whole optimizer step as ONE XLA program.

Reference capability: the reference's static-graph train executor runs a
whole step (forward, backward, gradient communication, optimizer update)
as one `InterpreterCore` program (reference:
python/paddle/distributed/passes/auto_parallel_gradient_merge.py +
new_executor/interpretercore.cc), which is how it reaches its published
MFU numbers; op-by-op eager dispatch cannot overlap collectives or fuse
the update.

TPU-native realization (docs/TRAIN_STEP.md): :class:`CompiledTrainStep`
extracts the parameter / optimizer-state / gradient pytrees from a live
eager model, lowers the step body — forward via the op-dispatch funnel,
tape backward, AMP unscale + in-program found-inf reduction, global-norm
clip, the optimizer's ``_fused_update`` — as a pure function of those
pytrees, and compiles it with ``jax.jit`` donating the parameter,
gradient and optimizer-state buffers so XLA updates them in place.  When
a PURE data-parallel mesh spans more than one local device the body runs
under ``shard_map`` over the ``NamedSharding`` mesh
(``distributed/mesh.py``): the batch is sharded over ``dp`` and gradient
reduction happens as an in-program ``psum``/``pmean`` that XLA can
overlap with the rest of the backward, instead of the eager path's
post-hoc per-tensor host collectives (``hapi.Model._sync_grads``).

Hybrid dp×mp meshes (ISSUE 12) compile as ONE GSPMD program instead:
``jax.jit`` over per-leaf ``NamedSharding`` trees derived from each
parameter's declared partition (the ``mp_placement`` annotations the TP
layers carry, committed by ``fleet.distributed_model``), gradients and
optimizer moments mirroring their parameter's sharding, and the batch
sharded over ``dp``.  The model's own ``shard_constraint`` calls then
direct XLA to insert the exact mp collectives (row-parallel partial-sum
all-reduce, vocab-parallel softmax reductions), while the dp gradient
all-reduce falls out of differentiating the global-batch loss — all
inside one program, so XLA's scheduler overlaps the dp grad reduction
with mp compute instead of serializing them at a host boundary.  Mesh
axes the one-program step cannot host (``pp`` — the 1F1B schedule is a
python micro-batch loop; ``sharding`` — ZeRO accumulators rebind per
step; ``sep``) fall back to eager with a :class:`MeshFallbackWarning`
naming the axis.

Lifecycle (two-phase, mirroring ``jit/tracer.py``):

1. **Call 1 — eager + discovery.**  The step runs through the caller's
   byte-identical eager path (a REAL step, so lazily-initialized
   optimizer state and gradients exist), then one no-grad forward under
   a discovery tracer records every pre-existing tensor the forward
   reads (parameters, buffers, masks); its side effects (RNG counter,
   buffer writes) are rolled back.
2. **Call 2 — bind + compile.**  A pure wrapper installs JAX tracers
   into the captured tensors' data slots, replays the step body, and
   collects loss + every mutated value as program outputs; ``jax.jit``
   compiles it with ``donate_argnums`` over params/grads/state.  All
   later calls execute the one cached executable per input signature.

Eager stays the fallback and is byte-for-byte today's path: flag off
(``FLAGS_compiled_train_step``), layer/tensor hooks installed, active
tracers or ``saved_tensors_hooks``, data-dependent host reads in the
forward, optimizers without a fused update (LBFGS), ZeRO-sharded
accumulators, or a launched multi-process world whose backend cannot
run cross-process XLA programs.  What the USER's forward does that one
program cannot replay (``capture.USER_TRACE_ERRORS``: the typed
``TraceEscape`` family and JAX's tracer-conversion errors) warns once
and permanently falls back.  Any other failure of lowering, compiling
or running the program is the framework's or the device's and
propagates — the eager lane would call the same kernels, and a silent
switch would hide that the compiled step never ran.
"""
from __future__ import annotations

import warnings

import numpy as np
import jax
import jax.numpy as jnp

from ..core import state as _state
from ..core.tensor import Tensor
from ..observability import scopes as _scopes
from ..observability.tracing import scope, span as _span
from ..utils.flags import flag as _flag
from .capture import (USER_TRACE_ERRORS, BindTracer, Installed,
                      TraceEscape, describe_escape, run_discovery)


_DONATED_FAILURE_MSG = (
    "compiled train step failed after buffer donation; parameters/"
    "optimizer state backing this step are invalid — reload them from a "
    "checkpoint, or set FLAGS_jit_donate_buffers=False to trade memory "
    "for failure recovery")


class MeshFallbackWarning(UserWarning):
    """Warned once when the active ``ProcessMesh`` carries an axis the
    one-program train step cannot host (pipeline, ZeRO sharding,
    context parallel); the message names the axis that forced the
    eager fallback."""


class _MeshEscape(TraceEscape):
    """A mesh axis forced the eager fallback — warn with the typed
    :class:`MeshFallbackWarning` so callers can filter on it."""

    category = MeshFallbackWarning


# the two-phase capture/replay machinery lived here through PR 12; it is
# shared with the serving scheduler's compiled tick now (ISSUE 13) and
# moved to framework/capture.py — these aliases keep the historical
# import surface intact
_StepBindTracer = BindTracer
_Installed = Installed


def _resolve_mesh(mesh=None):
    """``(mesh, blocked_axis)`` — the mesh this step compiles over, or
    the axis name that forces the eager fallback.

    Precedence: explicit argument > the framework's active/default
    ``ProcessMesh`` (``distributed.mesh``) > the ``PADDLE_COMPILED_DP``
    env var (dp over the first N local devices).  There is deliberately
    NO implicit all-local-devices default: silently resharding the
    batch would change trajectories whenever CI forces a multi-device
    host platform.

    A pure-dp mesh runs under ``shard_map`` (bit-identical to the PR 8
    lane); a mesh with an ``mp`` axis > 1 runs as one GSPMD program
    over NamedSharding trees.  Any other axis of size > 1 (``pp``: the
    1F1B schedule is a python micro-batch loop, not one program;
    ``sharding``: ZeRO accumulators rebind per step; ``sep``) blocks
    compilation — ``blocked_axis`` names it for the typed warning."""
    import os
    from ..distributed import mesh as _mesh_mod
    if mesh is None:
        mesh = _mesh_mod.get_mesh()
    if mesh is None:
        n = int(os.environ.get("PADDLE_COMPILED_DP", "0") or 0)
        if n > 1:
            mesh = _mesh_mod.init_mesh([n], ["dp"])
    if mesh is None:
        return None, None
    for name in mesh.dim_names:
        if name not in ("dp", "mp") and mesh.get_dim_size(name) != 1:
            return None, name
    dp = mesh.get_dim_size("dp") if "dp" in mesh.dim_names else 1
    mp = mesh.get_dim_size("mp") if "mp" in mesh.dim_names else 1
    if dp <= 1 and mp <= 1:
        return None, None
    if mp > 1 and not _flag("FLAGS_compiled_mp_step", True):
        return None, "mp"
    return mesh, None


class CompiledTrainStep:
    """One donated-buffer XLA program per (input signature, phase).

    ``forward_fn(x, y) -> loss Tensor`` is the only user code replayed
    inside the program (wrap autocast inside it); everything after the
    loss — backward, loss scaling, found-inf, dp reduction, clip, the
    fused optimizer update — is the framework-owned step tail.

    ``eager_step(x, y, update) -> loss Tensor`` supplies the exact eager
    semantics used for the warmup call and every fallback
    (``update=False`` marks a gradient-accumulation micro-step: backward
    only, no optimizer update / clear).  hapi passes its historical
    ``Model._train_step`` so fallbacks stay byte-identical; standalone
    callers get a default with the same structure.
    """

    def __init__(self, forward_fn, optimizer, *, scaler=None, network=None,
                 accumulate_grad_batches=1, mesh=None, eager_step=None,
                 sentinel=False):
        self._forward = forward_fn
        self._opt = optimizer
        self._scaler = scaler
        self._network = network
        self._accum = max(int(accumulate_grad_batches or 1), 1)
        self._mesh_arg = mesh
        self._eager = eager_step or self._default_eager_step
        # training-sentinel mode (framework/sentinel.py): the full-step
        # program additionally emits a [grad_norm_sq, skipped] health
        # vector as a device output — detection signals ride the
        # program, the hot path gains NO host syncs.  Off: the program
        # is bit-identical to a sentinel-less build.
        self._sentinel = bool(sentinel)
        self._health_every = max(
            int(_flag("FLAGS_sentinel_check_every", 8) or 1), 1)
        self.last_health = None
        self._micro = 0               # position within the accum window
        self._calls = 0
        self._fallback_reason = None
        self._warned = False
        # build products (populated by discovery / first bind)
        self._built = False
        self._mesh = None
        self._dp = 1
        self._mp = 1
        self._shard_map = False     # pure-dp shard_map lane (PR 8)
        self._gspmd = False         # hybrid dp×mp GSPMD lane (ISSUE 12)
        self._psh = None            # per-param NamedSharding tree
        self._csh = None            # per-capture NamedSharding tree
        self._rep = None            # replicated NamedSharding on the mesh
        self._caps = []               # non-param captured tensors
        self._params = []             # params receiving grads (update set)
        self._idxs = []               # their positions in the optimizer list
        self._lr_scales = ()
        self._wd_mask = ()
        self._state_names = ()
        self._mut_caps = []           # forward-mutated captures (buffers)
        self._jit_full = None
        self._jit_micro = None
        self._programs = {}           # (update, batch signature) -> Compiled
        self._donating = None
        self._scaler_vec = None       # device [scale, good, bad] fp32
        self.check_static_eligibility()

    # ------------------------------------------------------------------
    # public surface
    # ------------------------------------------------------------------

    @property
    def compiled(self):
        return self._built and self._fallback_reason is None

    @property
    def fallback_reason(self):
        return self._fallback_reason

    def __call__(self, x, y=None, update=None):
        if update is None:
            # standalone callers: position within the accumulation window
            update = (self._micro + 1) >= self._accum
        self._calls += 1
        from ..utils import monitor as _monitor
        # host time of the call; the device runs on after it returns
        with _span("train.step", step_num=self._calls):
            if self._fallback_reason is not None \
                    or not self._eligible_now():
                _monitor.incr("jit.compiled_step_fallback")
                loss = self._run_eager(x, y, update)
            elif self._calls == 1:
                loss = self._run_eager(x, y, update)   # real warmup step
                try:
                    self._discover(x, y)
                except USER_TRACE_ERRORS as e:
                    self._latch_user_escape(e)
            else:
                try:
                    loss = self._run_compiled(x, y, update)
                    _monitor.incr("jit.compiled_step_hit")
                except USER_TRACE_ERRORS as e:
                    # raised while tracing, so before any buffer was
                    # donated
                    self._latch_user_escape(e)
                    loss = self._run_eager(x, y, update)
                except Exception as e:
                    # a lowering, compile or device failure is not the
                    # user's and has no other lane: it propagates, with
                    # the state of the donated buffers said when they
                    # are gone
                    if self._donation_burned():
                        raise RuntimeError(_DONATED_FAILURE_MSG) from e
                    raise
        self._micro = 0 if update else self._micro + 1
        return loss

    step = __call__

    def lowered_text(self, x, y=None):
        """StableHLO text of the full-update program for this batch
        signature — what a reader checks to see which kernels are in
        the program (Pallas kernels appear as ``tpu_custom_call``).
        None until compiled."""
        if self._jit_full is None:
            return None
        try:
            return self._jit_full.lower(*self._gather_args(x, y)).as_text()
        finally:
            # _gather_args advanced the RNG counter; reading the program
            # must not perturb the training stream
            _state.STATE.rng_counter -= 1

    def hlo_fingerprint(self, x, y=None):
        """sha256 (first 16 hex) of :meth:`lowered_text` — the auditable
        program identity benchmark records carry.  None until
        compiled."""
        import hashlib
        text = self.lowered_text(x, y)
        if text is None:
            return None
        return hashlib.sha256(text.encode()).hexdigest()[:16]

    def sync_scaler(self):
        """Materialize the device-held loss-scaling state back into the
        python ``GradScaler`` (scale / good / bad counters)."""
        if self._scaler is None or self._scaler_vec is None:
            return
        vec = np.asarray(self._scaler_vec)
        self._scaler._scale = float(vec[0])
        self._scaler._good_steps = int(vec[1])
        self._scaler._bad_steps = int(vec[2])

    # ------------------------------------------------------------------
    # eligibility & fallback
    # ------------------------------------------------------------------

    def _latch_user_escape(self, e):
        self._set_fallback(describe_escape(e),
                           category=getattr(e, "category", UserWarning))

    def _set_fallback(self, reason, category=UserWarning):
        self.sync_scaler()
        self._scaler_vec = None
        self._fallback_reason = reason
        if not self._warned:
            self._warned = True
            warnings.warn(
                f"compiled train step disabled ({reason}); running the "
                "eager step for this model", category)

    def check_static_eligibility(self):
        """One-time structural checks; returns None when eligible, else
        the (latched) fallback reason."""
        opt = self._opt
        from ..optimizer.optimizer import Optimizer
        if opt is None:
            self._fallback_reason = "no optimizer"
        elif type(opt).step is not Optimizer.step:
            self._set_fallback(
                f"{type(opt).__name__}.step is overridden (closure-style "
                "optimizers run eagerly)")
        elif type(opt)._fused_update is Optimizer._fused_update:
            self._set_fallback(f"{type(opt).__name__} has no fused update")
        elif getattr(opt, "_accumulator_commit_hook", None) is not None:
            self._set_fallback("ZeRO-sharded accumulators (fleet.sharding)")
        else:
            world = self._world_blocker()
            if world:
                self._set_fallback(world)
        return self._fallback_reason

    def _world_blocker(self):
        """Launched multi-process worlds ride eager unless the backend
        can genuinely run one cross-process XLA program (TPU pods with a
        global mesh); the CPU host-collective lane cannot."""
        nprocs = jax.process_count()
        if nprocs <= 1:
            return None
        plat = jax.devices()[0].platform
        if plat != "tpu":
            return (f"{nprocs}-process world on {plat!r}: backend cannot "
                    "run cross-process XLA programs (host-collective "
                    "eager lane)")
        return None

    def _eligible_now(self):
        """Cheap per-call checks for state that may change mid-run."""
        if not _flag("FLAGS_compiled_train_step", True):
            return False
        if _state.STATE.tracer is not None:
            return False     # someone is tracing us: compose eagerly
        if getattr(_state.STATE, "saved_tensor_hooks", None) is not None:
            return False
        if self._network is not None:
            for layer in self._network.sublayers(include_self=True):
                if layer._forward_pre_hooks or layer._forward_post_hooks:
                    self._set_fallback("layer forward hooks installed")
                    return False
        for p in self._opt._parameter_list:
            if p._hooks:
                self._set_fallback("tensor gradient hooks installed")
                return False
        return True

    def _donation_burned(self):
        for p in self._params:
            if getattr(p._data_, "is_deleted", lambda: False)():
                return True
        return False

    # ------------------------------------------------------------------
    # eager lane
    # ------------------------------------------------------------------

    def _run_eager(self, x, y, update):
        # a mid-run fallback (ragged batch, flag flip) must not read a
        # stale host scaler: pull the device-held state down first
        if self._scaler_vec is not None:
            self.sync_scaler()
            self._scaler_vec = None
        self.last_health = None   # stale compiled health must not be
        return self._eager(x, y, update)  # mistaken for this step's

    def _default_eager_step(self, x, y, update):
        """Standalone eager semantics (scaler/clip-aware, single rank)."""
        loss = self._forward(x, y)
        bwd = loss
        if self._scaler is not None:
            bwd = self._scaler.scale(bwd)
        if self._accum > 1:
            bwd = bwd * (1.0 / self._accum)
        bwd.backward()
        if update:
            if self._scaler is not None:
                self._scaler.step(self._opt)   # unscale→found-inf→update
            else:
                self._opt.step()
            self._opt.clear_grad()
        return loss

    # ------------------------------------------------------------------
    # phase 1: discovery (side-effect-free capture of forward reads)
    # ------------------------------------------------------------------

    def _discover(self, x, y):
        opt = self._opt
        opt._ensure_state()
        # the shared capture core runs the forward once eagerly under a
        # discovery tracer (side effects — batchnorm running stats,
        # write-only counters, the RNG counter — rolled back to the
        # post-warmup state) and raises TraceEscape on any host read
        disc = run_discovery(lambda: self._forward(x, y))

        # classify captures: the optimizer's update set vs const captures
        grads_present = {id(p) for p in opt._parameter_list
                         if p.grad is not None and not p.stop_gradient}
        self._idxs = [i for i, p in enumerate(opt._parameter_list)
                      if id(p) in grads_present]
        self._params = [opt._parameter_list[i] for i in self._idxs]
        if not self._params:
            raise TraceEscape("no trainable parameters received gradients")
        # the batch tensors are per-call program INPUTS, not captures —
        # holding them in _caps would feed call 1's batch forever
        batch_ids = {id(t) for t in (x, y) if isinstance(t, Tensor)}
        param_ids = {id(p) for p in self._params}
        self._caps = [t for t in disc.capture_list
                      if id(t) not in param_ids and id(t) not in batch_ids]
        # whether the forward draws framework RNG (dropout): only then is
        # a fresh key fed per call — feeding one unconditionally would
        # advance the global RNG counter the eager lane does not touch,
        # desynchronizing everything else that draws from it (shuffling)
        self._uses_rng = disc.uses_rng
        self._lr_scales = tuple(
            p.optimize_attr.get("learning_rate", 1.0) for p in self._params)
        self._wd_mask = tuple(opt._wd_applies(p) for p in self._params)
        self._state_names = tuple(opt._state)
        self._mesh, blocked = _resolve_mesh(self._mesh_arg)
        if blocked == "mp":      # only blocked when the flag is off
            raise _MeshEscape("mesh axis 'mp' present but "
                              "FLAGS_compiled_mp_step is off")
        if blocked is not None:
            raise _MeshEscape(
                f"mesh axis '{blocked}' cannot run inside one compiled "
                "program (pipeline schedules, ZeRO resharding and "
                "context parallel keep their own lanes)")
        names = self._mesh.dim_names if self._mesh is not None else ()
        self._dp = self._mesh.get_dim_size("dp") if "dp" in names else 1
        self._mp = self._mesh.get_dim_size("mp") if "mp" in names else 1
        self._shard_map = self._mesh is not None and self._mp == 1
        self._gspmd = self._mesh is not None and self._mp > 1
        if self._gspmd:
            self._build_sharding_trees()
        self._built = True

    # ------------------------------------------------------------------
    # hybrid dp×mp: NamedSharding trees + state realignment
    # ------------------------------------------------------------------

    def _derived_sharding(self, t):
        """The NamedSharding a captured tensor carries in the hybrid
        program: its committed placements when they were declared on a
        mesh with this step's axes (the TP layers' ``mp_placement``
        annotations committed by ``fleet.distributed_model``), else its
        current NamedSharding when already on this mesh, else
        replicated."""
        from jax.sharding import NamedSharding
        from ..distributed.placement import named_sharding
        arr = t._data_
        placements = getattr(t, "placements", None)
        pmesh = getattr(t, "process_mesh", None)
        if placements and pmesh is not None and \
                tuple(pmesh.dim_names) == tuple(self._mesh.dim_names):
            return named_sharding(self._mesh, placements,
                                  len(arr.shape))
        sh = getattr(arr, "sharding", None)
        if isinstance(sh, NamedSharding) and sh.mesh == self._mesh.jax_mesh:
            return sh
        return self._rep

    def _build_sharding_trees(self):
        """Per-axis NamedSharding trees for params / grads / optimizer
        state / captures, derived once from the model's declared
        partition.  Gradients and moments mirror their parameter's
        sharding (``zeros_like`` inheritance made explicit)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        jm = self._mesh.jax_mesh
        self._rep = NamedSharding(jm, P())
        self._psh = tuple(self._derived_sharding(p) for p in self._params)
        self._csh = tuple(self._derived_sharding(t) for t in self._caps)

    def _align_hybrid(self):
        """Realign committed state onto the derived sharding tree.  The
        warmup eager step leaves gradients / moments / buffers committed
        with whatever sharding GSPMD propagation gave them; ``jax.jit``
        raises on committed inputs whose sharding differs from
        ``in_shardings`` (and donation would be unusable).  After the
        first compiled call the program outputs already carry these
        shardings, so this degenerates to one sharding compare per
        leaf."""
        opt = self._opt
        for k, p in enumerate(self._params):
            want = self._psh[k]
            for t in (p, p.grad):
                if t is not None and t._data_.sharding != want:
                    t._data_ = jax.device_put(t._data_, want)
            for name in self._state_names:
                v = opt._state[name][self._idxs[k]]
                if v is None:
                    continue
                w = want if v._data_.shape == p._data_.shape else self._rep
                if v._data_.sharding != w:
                    v._data_ = jax.device_put(v._data_, w)
        for t, w in zip(self._caps, self._csh):
            if t._data_.sharding != w:
                t._data_ = jax.device_put(t._data_, w)
        st = opt._step_tensor
        if st._data_.sharding != self._rep:
            st._data_ = jax.device_put(st._data_, self._rep)

    def _hybrid_shardings(self, args):
        """The full in_shardings pytree mirroring ``_gather_args``'s
        ``(x, y, params, grads, caps, states, step, svec, lr, key,
        hmark)`` — batch over dp, params/grads/moments per the derived
        trees, scalars replicated."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        rep = self._rep
        bsh = NamedSharding(self._mesh.jax_mesh, P("dp")) \
            if self._dp > 1 else rep
        _xa, ya, _params, _grads, _caps, states, _step, svec, _lr, \
            _key, _hmark = args
        ssh = {name: [None if a is None else
                      (self._psh[k] if getattr(a, "shape", None)
                       == self._params[k]._data_.shape else rep)
                      for k, a in enumerate(vals)]
               for name, vals in states.items()}
        return (bsh, None if ya is None else bsh, self._psh, self._psh,
                self._csh, ssh, rep, None if svec is None else rep, rep,
                rep, rep)

    # ------------------------------------------------------------------
    # phase 2: the pure step body (replayed under jax.jit tracing)
    # ------------------------------------------------------------------

    def _traced_body(self, update, x, y, param_arrs, grad_arrs, cap_arrs,
                     states, step_arr, svec, lr, key, hmark=None):
        """Replay the step over tracer arrays; returns array pytrees.
        Runs only while jax traces — per-step python cost is zero after
        compilation."""
        from ..core.state import no_grad

        tracer = BindTracer(key, host_scalars=(lr,))
        installs = (list(zip(self._params, param_arrs))
                    + list(zip(self._caps, cap_arrs)))
        grad_seed = [(p.grad, g) for p, g in zip(self._params, grad_arrs)]
        _state.STATE.tracer = tracer
        try:
            with Installed(installs), Installed(grad_seed):
                # the forward expects framework Tensors; wrap the traced
                # batch arrays (created under the tracer, so on_read never
                # mistakes them for uncaptured state)
                x_t = Tensor(x)
                y_t = Tensor(y) if y is not None else None
                loss_t = self._forward(x_t, y_t)
                bwd_t = loss_t
                # the step's own lines carry scopes of their own kind
                # (docs/OBSERVABILITY.md, "Names on the device"): no
                # scope on a device operation means nobody named it
                if svec is not None:
                    # scale is device state: multiply by the traced value
                    with scope("loss_scale"):
                        bwd_t = bwd_t * Tensor(
                            svec[0].astype(loss_t._data_.dtype))
                if self._accum > 1:
                    with scope("grad_accum"):
                        bwd_t = bwd_t * (1.0 / self._accum)
                bwd_t.backward()
                loss = loss_t._data_
                grads = [p.grad._data_ for p in self._params]
                grad_ids = {id(p.grad) for p in self._params}
                mut_caps = [t for t in tracer.mutated_list
                            if id(t) not in grad_ids]
                if mut_caps and self._shard_map:
                    # the GSPMD lane computes mutated state over the
                    # GLOBAL batch (single-device semantics); only the
                    # per-shard shard_map lane cannot represent it
                    raise TraceEscape(
                        "forward mutates non-parameter state (running "
                        "stats?) — per-shard divergence under dp is not "
                        "representable; run eager or dp=1")
                self._mut_caps = mut_caps
                mut_vals = tuple(t._data_ for t in mut_caps)
                if not update:
                    return loss, tuple(grads), mut_vals
                with no_grad():
                    tail = self._update_tail(grads, param_arrs, states,
                                             step_arr, svec, lr,
                                             hmark=hmark)
                (new_params, new_states, new_step, new_svec, zeroed,
                 health) = tail
                return (loss, tuple(new_params), tuple(zeroed), new_states,
                        new_step, new_svec, mut_vals, health)
        finally:
            _state.STATE.tracer = None
            # roll back any forward-mutated captures still holding
            # tracers to their pre-write concrete values
            tracer.rollback_mutations()

    def _update_tail(self, grads, param_arrs, states, step_arr, svec, lr,
                     hmark=None):
        """Unscale → dp pmean → found-inf → clip → fused update → select.
        Pure array math mirroring the eager sequence op-for-op."""
        opt = self._opt
        scaler_on = svec is not None
        if scaler_on:
            with scope("loss_scale"):
                inv = 1.0 / svec[0]
                grads = [g * inv.astype(g.dtype) for g in grads]
        if self._dp > 1 and self._shard_map:
            # the in-program analogue of _sync_grads' per-tensor
            # all_reduce + divide: one psum/pmean per gradient that XLA
            # schedules/overlaps inside the step program.  (The GSPMD
            # hybrid lane needs no explicit pmean: differentiating the
            # global-batch loss already yields globally-reduced
            # gradients — XLA inserts and overlaps the dp all-reduce.)
            with scope("grad_sync"):
                grads = [jax.lax.pmean(g, "dp") for g in grads]

        def _found_inf():
            with scope("found_inf"):
                flags = [~jnp.isfinite(jnp.sum(g)) for g in grads]
                found = jnp.any(jnp.stack(flags))
                if self._dp > 1 and self._shard_map:
                    # global decision — a scalar psum, not a host
                    # round-trip
                    found = jax.lax.pmax(found.astype(jnp.int32),
                                         "dp").astype(jnp.bool_)
            return found

        found = None
        if scaler_on:
            found = _found_inf()
            # eager parity: the check is armed only while scaling is
            # active (GradScaler.unscale_ skips it at scale == 1.0) —
            # unless the scaler always checks (the sentinel's unit-scale
            # wrapper generalizing the skip machinery to non-AMP runs)
            if not getattr(self._scaler, "_always_check", False):
                found = jnp.logical_and(found, svec[0] != 1.0)

        health = None
        if self._sentinel:
            if found is None:
                # scaler-less runs: the sentinel arms the same
                # found-inf check the AMP machinery uses, so non-finite
                # steps are skipped in-program here too
                found = _found_inf()
            # device-resident health vector [grad_norm_sq, skipped]:
            # the sentinel fetches a window of these in one batched
            # transfer at its check cadence — zero per-step host syncs.
            # The squared-norm pass costs a full read of every gradient,
            # so it runs under lax.cond only on the calls hmark flags
            # (the sentinel check cadence); other steps carry -1.0
            # ("not sampled").  The found-inf flag stays per-step — it
            # is what the skip select consumes.
            def _gnorm_sq():
                sq = [jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in grads]
                return jnp.sum(jnp.stack(sq)) if sq \
                    else jnp.asarray(0.0, jnp.float32)

            with scope("grad_norm"):
                gnorm_sq = jax.lax.cond(
                    hmark > 0.5, _gnorm_sq,
                    lambda: jnp.asarray(-1.0, jnp.float32))
                health = jnp.stack([gnorm_sq, found.astype(jnp.float32)])

        if opt._grad_clip is not None:
            with scope("grad_clip"):
                pairs = opt._grad_clip(
                    [(p, Tensor(g)) for p, g in zip(self._params, grads)])
                grads = [g._data_ for _, g in pairs]

        new_step = step_arr + 1.0
        with scope("optimizer"):
            new_params, new_states = type(opt)._fused_update(
                opt, lr, new_step, list(param_arrs), grads, states,
                lr_scales=self._lr_scales, wd_mask=self._wd_mask)

        # skip decision: the scaler's found-inf flag when one is
        # installed (bitwise-identical to the pre-sentinel program), or
        # the sentinel's own non-finite check for scaler-less runs
        skip = found
        new_svec = svec
        if skip is not None:
            with scope("skip_select"):
                take = ~skip
                new_params = [jnp.where(take, n, o)
                              for n, o in zip(new_params, param_arrs)]
                new_states = {
                    name: [None if n is None else jnp.where(take, n, o)
                           for n, o in zip(vals, states[name])]
                    for name, vals in new_states.items()}
                new_step = jnp.where(take, new_step, step_arr)
        if scaler_on:
            with scope("loss_scale"):
                new_svec = self._scaler_update(svec, found)
        with scope("grad_accum"):
            zeroed = [jnp.zeros_like(g) for g in grads]
        return new_params, new_states, new_step, new_svec, zeroed, health

    def _scaler_update(self, svec, found):
        """``GradScaler.update`` as pure in-program math."""
        sc = self._scaler
        scale, good, bad = svec[0], svec[1], svec[2]
        active = jnp.logical_and(
            jnp.asarray(bool(sc._enable and sc._dynamic)), scale != 1.0)
        bad_n = jnp.where(found, bad + 1.0, 0.0)
        good_n = jnp.where(found, 0.0, good + 1.0)
        dec = jnp.logical_and(found, bad_n >= sc._decr_every)
        inc = jnp.logical_and(~found, good_n >= sc._incr_every)
        scale_n = jnp.where(
            dec, jnp.maximum(scale * sc._decr_ratio,
                             getattr(sc, "_min_scale", 1.0)),
            jnp.where(inc, scale * sc._incr_ratio, scale))
        bad_n = jnp.where(dec, 0.0, bad_n)
        good_n = jnp.where(inc, 0.0, good_n)
        out = jnp.stack([scale_n, good_n, bad_n])
        return jnp.where(active, out, svec)

    # ------------------------------------------------------------------
    # compile + execute
    # ------------------------------------------------------------------

    def _build_jit(self, update, args):
        from ..core.op_cache import ensure_compile_cache
        ensure_compile_cache()     # tier-2 persistent XLA compile cache
        mesh = self._mesh

        def train_step(x, y, params, grads, caps, states, step_arr, svec,
                       lr, key, hmark):
            if self._shard_map:
                from jax.sharding import PartitionSpec as P

                def body(x, y, params, grads, caps, states, step_arr,
                         svec, lr, key, hmark):
                    # decorrelate per-shard RNG like per-rank eager dp
                    key_s = jax.random.fold_in(
                        key, jax.lax.axis_index("dp"))
                    out = self._traced_body(update, x, y, params, grads,
                                            caps, states, step_arr,
                                            svec, lr, key_s,
                                            hmark=hmark)
                    loss = jax.lax.pmean(out[0], "dp")
                    return (loss,) + tuple(out[1:])
                rep = P()
                in_specs = (P("dp"), P("dp"), rep, rep, rep, rep, rep,
                            rep, rep, rep, rep)
                return jax.shard_map(body, mesh=mesh.jax_mesh,
                                     in_specs=in_specs, out_specs=rep,
                                     check_vma=False)(
                    x, y, params, grads, caps, states, step_arr, svec,
                    lr, key, hmark)
            # single-device AND the hybrid dp×mp GSPMD lane: one global
            # program — the mesh (when present) enters through the
            # in_shardings trees and the model's own shard_constraints,
            # and the traced math is exactly the single-device step
            return self._traced_body(update, x, y, params, grads, caps,
                                     states, step_arr, svec, lr, key,
                                     hmark=hmark)

        self._donating = bool(_flag("FLAGS_jit_donate_buffers", True))
        donate = ()
        if self._donating:
            # params, grads, opt state, step counter, scaler vec — the
            # buffers the program replaces in place
            donate = (2, 3, 5, 6, 7) if update else (3,)
        kwargs = {}
        if self._shard_map:
            from jax.sharding import NamedSharding, PartitionSpec as P
            kwargs["out_shardings"] = NamedSharding(self._mesh.jax_mesh,
                                                    P())
        elif self._gspmd:
            # pin every input leaf to its derived sharding; output
            # shardings are inferred by GSPMD propagation (the update
            # chain is elementwise, so outputs land on the input
            # shardings and donation stays usable)
            kwargs["in_shardings"] = self._hybrid_shardings(args)
        # the program's name on the trace's ``XLA Modules`` line
        train_step.__name__ = train_step.__qualname__ = \
            "train_step" if update else "train_micro_step"
        return jax.jit(train_step, donate_argnums=donate, **kwargs)

    def _gather_args(self, x, y):
        opt = self._opt
        if self._gspmd:
            self._align_hybrid()
        xa = x._data_ if isinstance(x, Tensor) else jnp.asarray(x)
        ya = y._data_ if isinstance(y, Tensor) else (
            None if y is None else jnp.asarray(y))
        params = tuple(p._data_ for p in self._params)
        grads = tuple(p.grad._data_ for p in self._params)
        caps = tuple(t._data_ for t in self._caps)
        states = {name: [None if opt._state[name][i] is None
                         else opt._state[name][i]._data_
                         for i in self._idxs]
                  for name in self._state_names}
        step_arr = opt._step_tensor._data_
        svec = None
        if self._scaler is not None and self._scaler._enable:
            if self._scaler_vec is None:
                sc = self._scaler
                self._scaler_vec = jnp.asarray(
                    [sc._scale, float(sc._good_steps),
                     float(sc._bad_steps)], jnp.float32)
            svec = self._scaler_vec
        lr = np.float32(opt.get_lr())
        key = jax.random.fold_in(_state.STATE.rng_key,
                                 _state.STATE.rng_counter)
        _state.STATE.rng_counter += 1
        # hmark: sample the expensive in-program grad-norm pass only on
        # the sentinel's check cadence (lax.cond skips it otherwise)
        hmark = np.float32(
            1.0 if self._sentinel
            and self._calls % self._health_every == 1 else 0.0)
        return (xa, ya, params, grads, caps, states, step_arr, svec, lr,
                key, hmark)

    def _run_compiled(self, x, y, update):
        from ..utils import monitor as _monitor
        opt = self._opt
        with _span("train.step.gather"):
            args = self._gather_args(x, y)
        if self._dp > 1 and (args[0].shape[0] % self._dp):
            # ragged tail batch cannot shard evenly: one-off eager step
            _monitor.incr("jit.compiled_step_ragged_fallback")
            if self._gspmd:
                # the model's own dp activation constraints cannot
                # shard a ragged batch either — lift the mesh scope for
                # this one step (sharded params compute the same values
                # through GSPMD eager propagation)
                from ..distributed import mesh as _mesh_mod
                with _mesh_mod.suspended():
                    return self._run_eager(x, y, update)
            return self._run_eager(x, y, update)
        if self._donating is not None and self._donating != bool(
                _flag("FLAGS_jit_donate_buffers", True)):
            self._jit_full = self._jit_micro = None   # flag flipped
            self._programs.clear()
        jit = self._jit_full if update else self._jit_micro
        if jit is None:
            jit = self._build_jit(update, args)
            if update:
                self._jit_full = jit
            else:
                self._jit_micro = jit
        if self._donating and self._aliased(args, update):
            _monitor.incr("jit.compiled_step_alias_fallback")
            return self._run_eager(x, y, update)

        with _span("train.step.launch"):
            out = self._program(jit, update, args)(*args)
        with _span("train.step.adopt"):
            if update:
                (loss, new_params, zeroed, new_states, new_step, new_svec,
                 mut_vals, health) = out
                self.last_health = health    # device [gnorm_sq, skipped]
                for p, arr in zip(self._params, new_params):
                    p._data_ = arr
                for name in self._state_names:
                    vals = opt._state[name]
                    for k, i in enumerate(self._idxs):
                        nv = new_states[name][k]
                        if nv is None:
                            continue
                        if vals[i] is None:
                            vals[i] = Tensor(nv)
                        else:
                            vals[i]._data_ = nv
                opt._step_tensor._data_ = new_step
                opt._step_count += 1
                if new_svec is not None:
                    self._scaler_vec = new_svec
                for p, g in zip(self._params, zeroed):
                    p.grad._data_ = g
            else:
                loss, new_grads, mut_vals = out
                for p, g in zip(self._params, new_grads):
                    p.grad._data_ = g
            for t, arr in zip(self._mut_caps, mut_vals):
                t._data_ = arr
        return Tensor(loss)

    def _program(self, jit, update, args):
        """The executable of ``jit`` for this batch signature, built the
        first time the signature is met (trace, lower, compile or cache
        load: what the jit's own first call would do) and called from
        then on.  The step holds its executables so that each can hand
        its HLO to ``observability.scopes``: device time by scope needs
        no second compile."""
        xa, ya, svec = args[0], args[1], args[7]
        key = (update, xa.shape, xa.dtype,
               None if ya is None else (ya.shape, ya.dtype), svec is None)
        program = self._programs.get(key)
        if program is None:
            program = self._programs[key] = jit.lower(*args).compile()
            _scopes.publish(program)
        return program

    def _aliased(self, args, update):
        """Donation is unsound when one device buffer backs two donated
        leaves (tied weights sharing an array): skip this call."""
        if update:
            donated = list(args[2]) + list(args[3]) + [args[6]]
            for vals in args[5].values():
                donated.extend(a for a in vals if a is not None)
            if args[7] is not None:
                donated.append(args[7])
        else:
            donated = list(args[3])
        seen = set()
        for a in donated:
            if id(a) in seen:
                return True
            seen.add(id(a))
        return False
