"""Static-graph compatibility API.

Reference capability: `paddle.static` (reference: python/paddle/static/ —
Program/Executor wrappers over ProgramDesc + StandaloneExecutor,
save/load_inference_model via static/io.py).

TPU-native realization: a "Program" is a traced XLA computation, not a
protobuf op list — the role the reference's ProgramDesc+InterpreterCore
pipeline plays is played by jax.jit tracing + the XLA executable cache
(SURVEY §7: StandaloneExecutor → PJRT executable launcher).  The API here
keeps the reference's shape: build a Program from a callable (or a
to_static-decorated layer), run it through an Executor, and
save/load_inference_model serializes the program as portable StableHLO
(jax.export) + a params file — the pdmodel/pdiparams split.
"""
from __future__ import annotations

import os
import pickle

import numpy as np

from ..core.tensor import Tensor
from ..core import state as _state
from ..jit import InputSpec  # noqa: F401 (re-export, reference parity)

_static_mode = [False]


def enable_static():
    """reference: paddle.enable_static — here a mode flag: under static
    mode, Program.build traces immediately instead of lazily."""
    _static_mode[0] = True


def disable_static():
    _static_mode[0] = False


def in_static_mode():
    return _static_mode[0]


def data(name, shape, dtype="float32", lod_level=0):
    """Placeholder declaration (reference: static.data)."""
    return InputSpec(shape=shape, dtype=dtype, name=name)


class Program:
    """A traced computation (reference: static.Program over ProgramDesc).

    Wraps `fn(*inputs) -> outputs`; tracing/compilation happen on first
    run per input signature (the _ExecutorCache analog is jax.jit's own
    executable cache)."""

    def __init__(self, fn=None, input_specs=None):
        self._fn = fn
        self._input_specs = input_specs or []
        self._exported = None   # jax.export.Exported for deserialized progs
        self._params = {}
        self._param_scales = None  # per-param int8 scales (sorted order)
        self._qrun = None          # jitted dequant-fused caller
        self._name_uid = {}     # auto-name counters for static.nn params
        self._jaxpr = None      # built IR (ClosedJaxpr) — see build()
        self._out_tree = None
        self._compiled = None   # jitted executable over _jaxpr
        self._use_compiled = False  # build() opts Executor.run into it
        self._train = None      # _TrainExecutor after build(for_training=True)

    def clone(self, for_test=False):
        p = Program(self._fn, list(self._input_specs))
        p._exported = self._exported
        p._params = dict(self._params)
        p._param_scales = self._param_scales
        p._jaxpr = self._jaxpr
        p._out_tree = self._out_tree
        p._compiled = self._compiled
        p._use_compiled = self._use_compiled
        # a training-built program clones as one (fresh executor, phases
        # restart); for_test=True strips the training build (reference:
        # clone(for_test=True) prunes backward/optimizer ops)
        if self._train is not None:
            if for_test:
                # the fwd+bwd+opt IR phase 1 wrote into _jaxpr must not
                # masquerade as a compiled-inference program on the clone
                p._jaxpr = None
                p._compiled = None
                p._use_compiled = False
            else:
                p.build(for_training=True)
        return p

    # ---- program IR (reference: ProgramDesc blocks/ops; here the IR is
    # a jaxpr — SURVEY §7: PIR's role is played by jaxpr/StableHLO) ----

    def build(self, for_training=False):
        """Trace the callable into the program IR (a ClosedJaxpr).

        The reference builds ProgramDesc incrementally under
        program_guard; here the whole callable traces in one pass (the
        two-phase tracer handles the dynamic path — this is the static
        path for introspection, pruning, and the compiled Executor).

        Inference build (default): parameters the callable closes over
        become jaxpr CONSTANTS — frozen.  `for_training=True` instead
        captures forward+backward+optimizer as ONE jaxpr whose params and
        optimizer state are donated INVARS, executed by a single cached
        executable with in-place write-back — the StandaloneExecutor-for-
        training analog (reference: new_executor/standalone_executor.cc:160
        runs forward+backward+optimizer jobs).  The training IR
        materializes at the second Executor.run (step 1 runs eagerly so
        lazy optimizer state exists before capture).

        Requires fully-static input_specs: a dynamic dim would bake the
        trace shape into reductions/normalizations and return silently
        wrong numbers for other batch sizes."""
        if for_training:
            if self._fn is None:
                raise ValueError("Program has no function bound")
            # clear a prior inference build: its params-frozen jaxpr and
            # compiled-path opt-in must not survive into (or be cloned
            # out of) the training build — phase 1 rebuilds _jaxpr as the
            # fwd+bwd+opt training IR
            self._use_compiled = False
            self._jaxpr = None
            self._compiled = None
            self._train = _TrainExecutor(self)
            return self
        # (re)build for inference: a previous training build no longer
        # owns execution, and its fwd+bwd+opt IR must not masquerade as
        # the inference program
        if self._train is not None:
            self._train = None
            self._jaxpr = None
        self._ensure_ir()
        self._use_compiled = True
        return self

    def _ensure_ir(self):
        if self._jaxpr is not None:
            return
        if self._fn is None:
            raise ValueError("Program has no function bound")
        if not self._input_specs:
            raise ValueError("build() needs input_specs (static.data)")
        for s in self._input_specs:
            if any(d is None or d < 0 for d in (s.shape or [])):
                raise ValueError(
                    f"build() needs concrete shapes; input {s.name!r} has "
                    f"dynamic dims {list(s.shape)} — give static.data a "
                    "full shape, or use the dynamic path (to_static / "
                    "eager Executor.run)")
        import jax
        import jax.numpy as jnp
        from ..core.dtype import convert_dtype
        jnp_asarray = jnp.asarray

        def as_arrays(*arrays):
            args = [Tensor(a) for a in arrays]
            self._reset_uids()
            with program_guard(self), _state.no_grad():
                outs = self._fn(*args)
            if isinstance(outs, Tensor):
                outs = (outs,)
            return tuple(o._data_ if isinstance(o, Tensor)
                         else jnp_asarray(o) for o in outs)

        avals = [jax.ShapeDtypeStruct(tuple(s.shape),
                                      convert_dtype(s.dtype))
                 for s in self._input_specs]
        self._jaxpr = jax.make_jaxpr(as_arrays)(*avals)
        self._compiled = None

    def global_block(self):
        """The single block of ops (reference: Program.global_block —
        framework.Block with .ops).  Traces the IR if needed but does
        NOT switch execution onto the compiled path — inspection must
        not change run semantics; call build() for that."""
        self._ensure_ir()
        return Block(self._jaxpr.jaxpr)

    def block(self, idx):
        if idx != 0:
            raise IndexError("single-block program (jaxpr IR)")
        return self.global_block()

    def _prune(self, fetch_indices):
        """Dead-code-eliminate to the given output subset (reference:
        Program._prune_with_input used by save_inference_model).
        Returns a NEW built program computing only those outputs."""
        self._ensure_ir()
        from jax.interpreters import partial_eval as pe
        n_out = len(self._jaxpr.jaxpr.outvars)
        used = [i in set(fetch_indices) for i in range(n_out)]
        new_jaxpr, used_consts, used_ins = pe.dce_jaxpr_consts(
            self._jaxpr.jaxpr, used, instantiate=True)
        from jax.extend.core import ClosedJaxpr
        consts = [c for c, u in zip(self._jaxpr.consts, used_consts) if u]
        pruned = Program(None, list(self._input_specs))
        pruned._jaxpr = ClosedJaxpr(new_jaxpr, consts)
        pruned._use_compiled = True   # no callable: IR is all it has
        pruned._params = dict(self._params)
        return pruned

    def _jaxpr_call(self, args):
        """Execute the built IR through ONE cached compiled executable —
        the StandaloneExecutor/PJRT-launcher analog (reference:
        new executor InterpreterCore caching per program)."""
        import jax
        if self._compiled is None:
            from ..core.op_cache import ensure_compile_cache
            ensure_compile_cache()   # tier-2 persistent compilation cache
            closed = self._jaxpr

            def run(*xs):
                return jax.core.eval_jaxpr(closed.jaxpr, closed.consts,
                                           *xs)

            self._compiled = jax.jit(run)
        return self._compiled(*args)

    def _exported_call(self, params, args):
        """Run the deserialized program.  `params` is the list aligned
        with sorted(self._params).  For an int8 bundle the dequant is
        jit-fused into the program, so weights stay int8 in memory and
        on the wire (the TPU analog of the reference's int8 predict —
        analysis_predictor.h:94)."""
        if not self._param_scales:
            return self._exported.call(params, *args)
        if self._qrun is None:
            import jax
            from ..core.op_cache import ensure_compile_cache
            ensure_compile_cache()
            from ..quantization import dequantize
            exp = self._exported
            scales = list(self._param_scales)

            def run(qparams, *a):
                dq = [p if s is None else dequantize(p, s)
                      for p, s in zip(qparams, scales)]
                return exp.call(dq, *a)

            self._qrun = jax.jit(run)
        return self._qrun(params, *args)

    def _reset_uids(self):
        """Restart auto-name sequencing so a re-run of the same
        construction code resolves to the SAME cached parameters
        (reference: params persist in the startup program scope)."""
        self._name_uid.clear()

    def ir_text(self):
        """The program's IR as text (reference: Program.to_string /
        debug dumps): StableHLO MLIR for exported programs; a
        structural summary for callables not yet traced."""
        if self._exported is not None:
            try:
                return str(self._exported.mlir_module())
            except Exception as e:  # jax.export internals may change
                return f"<stablehlo unavailable: {type(e).__name__}: {e}>"
        if self._jaxpr is not None:
            return self._jaxpr.pretty_print()
        specs = ", ".join(f"{s.name}:{s.dtype}{list(s.shape)}"
                          for s in self._input_specs)
        return (f"program(fn={getattr(self._fn, '__name__', self._fn)!r}, "
                f"inputs=[{specs}], params={sorted(self._params)})\n"
                f"# IR materializes at first jit trace; save with "
                f"save_inference_model for the StableHLO dump\n")

    @property
    def num_blocks(self):
        return 1

    def __repr__(self):
        src = "exported-stablehlo" if self._exported is not None else \
            getattr(self._fn, "__name__", None)
        return f"Program({src})"


class OpDesc:
    """One op of a built program (reference: framework.OpDesc views over
    ProgramDesc protos; here a read-only view over a jaxpr eqn)."""

    def __init__(self, eqn, names):
        self._eqn = eqn
        self._names = names

    @property
    def type(self):
        return self._eqn.primitive.name

    def _name(self, v):
        if hasattr(v, "val"):          # Literal
            return repr(v.val)
        return self._names.get(id(v), "?")

    def input_arg_names(self):
        return [self._name(v) for v in self._eqn.invars]

    def output_arg_names(self):
        return [self._name(v) for v in self._eqn.outvars]

    def attrs(self):
        return dict(self._eqn.params)

    def __repr__(self):
        return (f"{self.type}({', '.join(self.input_arg_names())}) -> "
                f"{', '.join(self.output_arg_names())}")


def _var_seq_name(i):
    name = ""
    while True:
        name = chr(ord("a") + i % 26) + name
        i = i // 26 - 1
        if i < 0:
            return name


class Block:
    """The op list + var table of a built program (reference:
    framework.Block).  Vars get stable sequential names (a, b, ...,
    matching jaxpr pretty-print style) keyed by first appearance."""

    def __init__(self, jaxpr):
        self._jaxpr = jaxpr
        self._names = {}
        order = list(jaxpr.constvars) + list(jaxpr.invars)
        for e in jaxpr.eqns:
            order.extend(v for v in e.outvars)
        for v in order:
            if id(v) not in self._names:
                self._names[id(v)] = _var_seq_name(len(self._names))

    @property
    def ops(self):
        return [OpDesc(e, self._names) for e in self._jaxpr.eqns]

    def var_names(self):
        return list(self._names.values())

    def __repr__(self):
        return f"Block({len(self._jaxpr.eqns)} ops)"


class _TrainExecutor:
    """Static-graph TRAINING through the built IR — the StandaloneExecutor
    analog for training (reference:
    fluid/framework/new_executor/standalone_executor.cc:160 runs
    forward+backward+optimizer jobs from one built program).

    Unlike the inference build (params frozen as jaxpr constants), the
    whole train step — forward, tape backward, optimizer update — is
    captured as ONE jaxpr whose parameters/optimizer state are INVARS.
    Every subsequent step executes that jaxpr through a single cached
    compiled executable, with the mutated buffers donated to XLA (in-place
    update, no old+new copies) and written back into the live tensors.

    Step protocol mirrors the dynamic tracer's phases: step 1 runs eagerly
    (lazy optimizer state materializes before capture), step 2 runs
    eagerly under discovery and builds the IR, step 3+ execute the IR."""

    def __init__(self, program):
        self._program = program
        self._phase = 0
        self._entry = None
        self._arg_struct = None
        self._arg_sig = None
        self._jitted = None
        self._flat_tree = None   # structure of the jaxpr's flat outputs
        self._donate = ()

    def _feed_tensors(self, feed):
        return tuple(Tensor(np.asarray(feed[s.name]))
                     for s in self._program._input_specs)

    def _run_eager(self, args):
        program = self._program
        program._reset_uids()
        with program_guard(program):
            return program._fn(*args)

    def step(self, feed):
        import jax
        import warnings
        from ..jit import tracer as _tracer

        program = self._program
        args = self._feed_tensors(feed)
        if self._phase == -1:        # unbuildable (host reads): eager
            return self._run_eager(args)
        if self._phase == 0:
            self._phase = 1
            return self._run_eager(args)
        if self._phase == 1:
            # discovery: run eagerly once more, recording captures
            # (params, moments), mutations, and escaped grads
            sf = _tracer.StaticFunction(program._fn)
            key = sf._canon_key(args, {})
            sf._cache[key] = _tracer._WARMUP   # phase 0 was the warm-up
            program._reset_uids()
            with program_guard(program):
                out = sf._discover(key, args, {})
            entry = sf._cache[key].last
            arg_arrays, arg_struct = _tracer._flatten_args(args, {})
            cap_arrays = [t._data_ for t in entry.captures]
            host_vals = [p() for p in entry.providers]

            def as_arrays(a, c, h):
                return entry.pure(a, c, h, arg_struct)

            try:
                with program_guard(program):   # static.nn params scope
                    program._reset_uids()
                    closed, out_shape = jax.make_jaxpr(
                        as_arrays, return_shape=True)(
                            arg_arrays, cap_arrays, host_vals)
            except _tracer.GraphBreak as e:
                # a host interaction (print(float(loss)) etc.) the built
                # program cannot replay: stay eager permanently — the
                # dynamic path (jit.to_static) offers piecewise
                # compilation for such steps
                self._phase = -1
                warnings.warn(
                    f"static train program cannot be built ({e}); running "
                    "every step eagerly — use jit.to_static for piecewise "
                    "compilation of steps with host reads")
                return out
            program._jaxpr = closed        # the inspectable training IR
            program._compiled = None
            self._flat_tree = jax.tree.structure(out_shape)

            # donate the mutated captures (params/moments/grads) unless a
            # data-dependent guard exists (a mismatched step must keep its
            # inputs) or a to-be-donated buffer is aliased by another
            # capture (double-donate / read-after-free)
            mut_ids = {id(t) for t in entry.mut_targets}
            mut_idx = [i for i, t in enumerate(entry.captures)
                       if id(t) in mut_ids]
            n_args = len(arg_arrays)
            if not entry.guard_bools and \
                    not _tracer._donation_unsafe(cap_arrays, mut_idx):
                self._donate = tuple(n_args + i for i in mut_idx)

            def run(*xs):
                return jax.core.eval_jaxpr(closed.jaxpr, closed.consts,
                                           *xs)

            from ..core.op_cache import ensure_compile_cache
            ensure_compile_cache()   # tier-2 persistent compilation cache
            self._jitted = jax.jit(run, donate_argnums=self._donate)
            self._entry = entry
            self._arg_struct = arg_struct
            self._arg_sig = _tracer._signature(args, {})
            self._phase = 2
            return out
        # phase 2+: run the built executable
        entry = self._entry
        arg_arrays, arg_struct = _tracer._flatten_args(args, {})
        if _tracer._signature(args, {}) != self._arg_sig:
            raise ValueError(
                "static training program was built for a different input "
                "signature; feed the shapes/dtypes it was built with, or "
                "use the dynamic path (jit.to_static) for multi-signature "
                "training")
        cap_arrays = [t._data_ for t in entry.captures]
        host_vals = [p() for p in entry.providers]
        try:
            flat = self._jitted(*arg_arrays, *cap_arrays, *host_vals)
        except Exception as e:
            # the donated param/moment buffers may already be gone —
            # same failure contract as the dynamic donating path
            if self._donate and any(
                    getattr(a, "is_deleted", lambda: False)()
                    for a in cap_arrays):
                raise RuntimeError(_tracer._DONATED_FAILURE_MSG) from e
            raise
        out_arrays, mut_arrays, grad_arrays, guard_arrays = \
            jax.tree.unflatten(self._flat_tree, flat)
        # guard check BEFORE applying mutations (mirrors the dynamic
        # tracer): a mismatch means the program followed the wrong branch
        actual = tuple(bool(np.asarray(g)) for g in guard_arrays)
        if actual != entry.guard_bools:
            warnings.warn(
                "static train program followed a different data-dependent "
                "branch this step; re-running the step eagerly")
            return self._run_eager(args)
        return _tracer._apply_entry_results(entry, out_arrays, mut_arrays,
                                            grad_arrays)


_default_program = Program()


def default_main_program():
    return _default_program


def default_startup_program():
    return _default_program


class program_guard:
    def __init__(self, main_program, startup_program=None):
        self.main = main_program

    def __enter__(self):
        global _default_program
        self._old = _default_program
        _default_program = self.main
        return self.main

    def __exit__(self, *exc):
        global _default_program
        _default_program = self._old


class CompiledProgram:
    """reference: static.CompiledProgram — compilation is implicit (XLA),
    kept for API parity.  BuildStrategy.debug_graphviz_path is honored:
    when set, the program's IR is dumped there at wrap time (StableHLO
    MLIR text for exported/deserialized programs; the callable +
    input-spec summary for not-yet-traced ones, whose IR only exists
    after jit tracing on first run)."""

    def __init__(self, program, build_strategy=None):
        self.program = program
        self.build_strategy = build_strategy
        path = getattr(build_strategy, "debug_graphviz_path", "")
        if path:
            with open(path, "w") as f:
                f.write(program.ir_text())


class _Var:
    """Scope-held value (reference: Variable/LoDTensor holder)."""

    def __init__(self, value=None):
        self._value = value

    def get_tensor(self):
        return self._value

    def set(self, value):
        self._value = value


class Scope:
    """reference: paddle.static.global_scope() — name → variable holder;
    Executor.run records fetched outputs here."""

    def __init__(self):
        self._vars = {}

    def var(self, name):
        return self._vars.setdefault(name, _Var())

    def find_var(self, name):
        return self._vars.get(name)

    def set(self, name, value):
        self.var(name).set(value)


_global_scope = Scope()


def global_scope():
    return _global_scope


def scope_guard(scope):
    import contextlib

    @contextlib.contextmanager
    def _guard():
        global _global_scope
        prev = _global_scope
        _global_scope = scope
        try:
            yield scope
        finally:
            _global_scope = prev
    return _guard()


class Executor:
    """reference: static.Executor (base/executor.py:1030) — run a Program
    with a feed dict, fetch outputs."""

    def __init__(self, place=None):
        self.place = place

    def run(self, program=None, feed=None, fetch_list=None,
            return_numpy=True, **kwargs):
        program = program or _default_program
        if isinstance(program, CompiledProgram):
            program = program.program
        # reference accepts a per-device list of feed dicts whose slices
        # CONCATENATE into the global batch (update() would silently drop
        # every device but the last)
        if isinstance(feed, (list, tuple)):
            merged = {}
            for d in feed:
                for k, v in d.items():
                    merged.setdefault(k, []).append(np.asarray(v))
            feed = {k: (vs[0] if len(vs) == 1
                        else np.concatenate(vs, axis=0))
                    for k, vs in merged.items()}
        feed = feed or {}
        if program._input_specs:
            missing = [s.name for s in program._input_specs
                       if s.name not in feed]
            if missing:
                raise ValueError(
                    f"feed is missing inputs {missing}; required: "
                    f"{[s.name for s in program._input_specs]}")
        if program._exported is not None:
            args = [np.asarray(feed[s.name]) for s in
                    program._input_specs]
            params = [program._params[k] for k in
                      sorted(program._params)]
            outs = program._exported_call(params, args)
        elif program._train is not None:
            # build(for_training=True): forward+backward+optimizer as one
            # built jaxpr with donated param invars (_TrainExecutor)
            outs = program._train.step(feed)
        elif program._use_compiled and program._jaxpr is not None:
            # explicitly-BUILT program: ONE compiled executable, params
            # baked as constants (inference semantics).  Training-style
            # programs whose params mutate between runs stay on the
            # eager path below — build() is opt-in; inspection via
            # global_block() alone never flips this switch.
            args = [np.asarray(feed[s.name]) for s in
                    program._input_specs]
            outs = program._jaxpr_call(args)
        else:
            if program._fn is None:
                raise ValueError("Program has no function bound; build it "
                                 "from a callable or load_inference_model")
            args = [Tensor(np.asarray(feed[s.name]))
                    for s in program._input_specs] if \
                program._input_specs else \
                [Tensor(np.asarray(v)) for v in feed.values()]
            # the running program is the default while its fn executes, so
            # static.nn parameter creation scopes to THIS program and
            # re-runs resolve to the same cached weights
            program._reset_uids()
            with program_guard(program), _state.no_grad():
                outs = program._fn(*args)
        if isinstance(outs, Tensor):
            outs = [outs]
        elif not isinstance(outs, (list, tuple)):
            outs = [outs]
        outs = list(outs)
        named = getattr(program, "_output_names", None) or []
        # scope records ALL outputs under their canonical names BEFORE any
        # fetch selection, so names stay positionally correct
        scope = global_scope()
        for i, o in enumerate(outs):
            val = np.asarray(o._data_) if isinstance(o, Tensor) \
                else np.asarray(o)
            scope.set(named[i] if i < len(named) else f"fetch_{i}", val)
        # fetch selection: indices, or names recorded on the program
        if fetch_list:
            sel = []
            for f in fetch_list:
                if isinstance(f, int):
                    sel.append(outs[f])
                elif isinstance(f, str) and f in named:
                    sel.append(outs[named.index(f)])
                else:
                    sel = outs
                    break
            outs = sel
        if return_numpy:
            return [np.asarray(o._data_) if isinstance(o, Tensor)
                    else np.asarray(o) for o in outs]
        return outs


# ---------------------------------------------------------------------------
# inference model save/load (reference: static/io.py)
# ---------------------------------------------------------------------------

def _export_layer(layer_or_fn, input_specs):
    """Trace to a params-separated StableHLO export."""
    import jax
    import jax.numpy as jnp
    from jax import export as jexport

    if hasattr(layer_or_fn, "state_dict"):
        layer = layer_or_fn
        layer.eval()
        named = sorted(layer.state_dict().items())
        param_tensors = [t for _, t in named]
        param_arrays = [t._data_ for t in param_tensors]

        def pure(params, *xs):
            saved = [t._data_ for t in param_tensors]
            for t, a in zip(param_tensors, params):
                t._data_ = a
            try:
                with _state.no_grad():
                    out = layer(*[Tensor(x) for x in xs])
            finally:
                for t, a in zip(param_tensors, saved):
                    t._data_ = a
            return tuple(o._data_ for o in
                         (out if isinstance(out, (tuple, list)) else
                          (out,)))

        params_np = {k: np.asarray(t._data_) for k, t in named}
    else:
        def pure(params, *xs):
            with _state.no_grad():
                out = layer_or_fn(*[Tensor(x) for x in xs])
            return tuple(o._data_ for o in
                         (out if isinstance(out, (tuple, list)) else
                          (out,)))

        param_arrays = []
        params_np = {}

    # None/-1 dims become jax.export symbolic dimensions, so one exported
    # program serves every batch size (reference: InputSpec dynamic dims).
    # ONE scope shared by every input — per-spec scopes cannot mix.
    import itertools
    dyn_names = (f"_d{i}" for i in itertools.count())
    scope = jexport.SymbolicScope()

    def _shape(spec):
        dims = []
        for axis, d in enumerate(tuple(spec.shape)):
            if d is None or (isinstance(d, int) and d < 0):
                # dynamic axis-0 dims share ONE symbol across inputs (the
                # common "same batch for every input" contract — distinct
                # symbols could never broadcast together); other axes get
                # fresh symbols
                dims.append("_b" if axis == 0 else next(dyn_names))
            else:
                dims.append(str(d))
        if any(d.startswith("_") for d in dims):
            return jexport.symbolic_shape(",".join(dims), scope=scope)
        return tuple(int(d) for d in dims)

    x_structs = [jax.ShapeDtypeStruct(_shape(s), jnp.dtype(s.dtype))
                 for s in input_specs]
    p_structs = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                 for a in param_arrays]
    exp = jexport.export(jax.jit(pure))(p_structs, *x_structs)
    return exp, params_np


def save_inference_model(path_prefix, feed_vars, fetch_vars, executor=None,
                         program=None, layer=None, quantize=None, **kwargs):
    """Serialize <prefix>.pdmodel (StableHLO) + <prefix>.pdiparams
    (reference: static/io.py save_inference_model).

    quantize="int8": bake weights (float arrays, ndim≥2) into the bundle
    as per-channel symmetric int8 + scales — a 4× smaller artifact whose
    dequant is jit-fused back into the program at load (the TPU analog
    of the reference's int8 predict path, analysis_predictor.h:94).  For
    a PTQ-converted model (quantization.PTQ) whose weights already sit
    on the int8 grid, the bake is a near-exact round-trip."""
    target = layer or program
    if target is None:
        raise ValueError("pass layer= (a Layer/callable) to export")
    specs = [v if isinstance(v, InputSpec) else
             InputSpec(shape=v.shape, dtype=str(v.dtype), name=f"x{i}")
             for i, v in enumerate(feed_vars)]
    exp, params_np = _export_layer(target, specs)
    quantized = {}
    if quantize == "int8":
        from ..quantization import bake_int8
        quantized = bake_int8(params_np)
    elif quantize is not None:
        raise ValueError(f"unsupported quantize={quantize!r} "
                         "(only 'int8')")
    d = os.path.dirname(path_prefix)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path_prefix + ".pdmodel", "wb") as f:
        f.write(exp.serialize())
    with open(path_prefix + ".pdiparams", "wb") as f:
        pickle.dump({"params": params_np,
                     "quantized": quantized,
                     "input_specs": [(s.name, list(s.shape or []),
                                      str(s.dtype)) for s in specs]}, f)
    return path_prefix


def load_inference_model(path_prefix, executor=None, **kwargs):
    """Returns (program, feed_names, fetch_names)
    (reference: static/io.py load_inference_model)."""
    from jax import export as jexport
    with open(path_prefix + ".pdmodel", "rb") as f:
        exp = jexport.deserialize(f.read())
    with open(path_prefix + ".pdiparams", "rb") as f:
        meta = pickle.load(f)
    prog = Program()
    prog._exported = exp
    prog._params = {k: v for k, v in sorted(meta["params"].items())}
    quantized = meta.get("quantized") or {}
    if quantized:
        prog._param_scales = [quantized.get(k)
                              for k in sorted(prog._params)]
    prog._input_specs = [InputSpec(shape=shape, dtype=dt, name=name)
                         for name, shape, dt in meta["input_specs"]]
    feed_names = [s.name for s in prog._input_specs]
    n_out = len(exp.out_avals)
    fetch_names = [f"fetch_{i}" for i in range(n_out)]
    prog._output_names = fetch_names
    return prog, feed_names, fetch_names


# reference-parity aliases
save = save_inference_model
load = load_inference_model


# ---- compat surface (reference: static/__init__.py __all__) ----
from .compat import (  # noqa: F401,E402
    Variable, BuildStrategy, ExecutionStrategy, WeightNormParamAttr,
    IpuStrategy, IpuCompiledProgram, ipu_shard_guard, set_ipu_shard,
    name_scope, device_guard, cpu_places, cuda_places, xpu_places,
    create_parameter, create_global_var, append_backward, gradients,
    py_func, Print, accuracy, auc, ctr_metric_bundle,
    ExponentialMovingAverage, serialize_program, deserialize_program,
    serialize_persistables, deserialize_persistables, save_to_file,
    load_from_file, load_program_state, set_program_state,
    normalize_program,
)
from . import nn  # noqa: F401,E402
# paddle.static.create_parameter persists in the program scope like the
# reference's startup-program parameters (overrides the raw compat one)
from .nn import create_parameter  # noqa: F401,E402,F811
