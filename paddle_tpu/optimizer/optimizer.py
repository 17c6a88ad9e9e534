"""Optimizers (reference: python/paddle/optimizer/optimizer.py:93 — step at
:1684, _apply_optimize at :1373; fused adamw PHI kernels).

TPU-native realization: each optimizer owns one jitted fused-update XLA
executable over the whole parameter pytree — the analogue of the reference's
multi-tensor fused kernels, but compiler-generated.  State (moments, master
weights) are jax.Arrays living on device; bf16 params automatically get f32
master weights.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, Parameter
from ..core import dtype as _dtype
from ..core import state as _state
from .lr import LRScheduler


class L2Decay:
    def __init__(self, coeff=0.0):
        self.coeff = float(coeff)


class L1Decay:
    def __init__(self, coeff=0.0):
        self.coeff = float(coeff)


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None,
                 weight_decay=None, grad_clip=None, name=None,
                 multi_precision=True):
        if parameters is None:
            raise ValueError("parameters must be provided (dygraph mode)")
        self._parameter_list = list(parameters)
        self._learning_rate = learning_rate
        self._grad_clip = grad_clip
        self._use_master_weights = multi_precision
        if isinstance(weight_decay, float):
            self._weight_decay = L2Decay(weight_decay)
        else:
            self._weight_decay = weight_decay
        # per-param state: dict name -> list of Tensors aligned with
        # _parameter_list (Tensors so the jit tracer can capture them)
        self._state = {}
        self._step_count = 0
        self._step_tensor = None
        self._update_jit = None

    # ---------------- lr ----------------
    def get_lr(self):
        if isinstance(self._learning_rate, LRScheduler):
            return self._learning_rate.last_lr
        return float(self._learning_rate)

    def set_lr(self, value):
        self._learning_rate = float(value)

    def set_lr_scheduler(self, scheduler):
        self._learning_rate = scheduler

    # ---------------- state helpers ----------------
    def _all_params(self):
        return self._parameter_list

    def _ensure_state(self):
        if self._step_tensor is None:
            self._step_tensor = Tensor(jnp.zeros((), jnp.float32))
        if self._state:
            return
        # ZeRO-1: fleet.sharding installs a commit hook so accumulators are
        # born sharded over the sharding axis (reference analog:
        # dygraph_sharding_optimizer.py:39 rank-bucketed moment ownership)
        commit = getattr(self, "_accumulator_commit_hook", None)
        for name, init in self._state_spec():
            self._state[name] = []
            for p in self._parameter_list:
                v = init(p)
                if v is not None and commit is not None:
                    v = commit(v)
                self._state[name].append(None if v is None else Tensor(v))

    def _master_weight_needed(self, p):
        return (self._use_master_weights and
                p.dtype in (jnp.bfloat16, jnp.float16))

    def _state_spec(self):
        """Subclass returns [(name, init_fn(param)->array)]."""
        return []

    # ---------------- core step ----------------
    def step(self):
        from ..jit.tracer import host_scalar
        self._ensure_state()
        self._step_count += 1
        params_grads = [(p, p.grad) for p in self._parameter_list
                        if p.grad is not None and not p.stop_gradient]
        if not params_grads:
            return
        if self._grad_clip is not None:
            params_grads = self._grad_clip(params_grads)
        pg_map = {id(p): g for p, g in params_grads}

        idxs = [i for i, p in enumerate(self._parameter_list)
                if id(p) in pg_map]
        params = [self._parameter_list[i]._data for i in idxs]
        grads = [pg_map[id(self._parameter_list[i])]._data for i in idxs]
        states = {name: [None if vals[i] is None else vals[i]._data
                         for i in idxs]
                  for name, vals in self._state.items()}
        # lr is host-computed (scheduler) → traced input so compiled steps
        # see the fresh value each call
        lr = jnp.asarray(
            host_scalar(lambda: np.float32(self.get_lr())), jnp.float32)
        new_step = self._step_tensor._data + 1.0
        self._step_tensor._data = new_step
        lr_scales = tuple(
            self._parameter_list[i].optimize_attr.get("learning_rate", 1.0)
            for i in idxs)
        wd_mask = tuple(self._wd_applies(self._parameter_list[i])
                        for i in idxs)

        if self._update_jit is None:
            self._update_jit = jax.jit(
                functools.partial(type(self)._fused_update, self),
                static_argnames=("lr_scales", "wd_mask"))

        # one jitted program cannot mix device sets — pipeline stages place
        # params on disjoint sub-meshes, so run the fused update per
        # device-set group (still one compiled program per stage)
        def _devset(j):
            arr = params[j]
            sh = getattr(arr, "sharding", None)
            if sh is None:
                return ()
            return tuple(sorted(d.id for d in sh.device_set))

        groups = {}
        for j in range(len(idxs)):
            groups.setdefault(_devset(j), []).append(j)

        for sel in groups.values():
            g_states = {name: [vals[j] for j in sel]
                        for name, vals in states.items()}
            new_params, new_states = self._update_jit(
                lr, new_step,
                [params[j] for j in sel], [grads[j] for j in sel],
                g_states,
                lr_scales=tuple(lr_scales[j] for j in sel),
                wd_mask=tuple(wd_mask[j] for j in sel))
            for k, j in enumerate(sel):
                i = idxs[j]
                self._parameter_list[i]._data = new_params[k]
                for name in self._state:
                    vals = self._state[name]
                    nv = new_states[name][k]
                    if nv is None:
                        continue
                    if vals[i] is None:
                        vals[i] = Tensor(nv)
                    else:
                        vals[i]._data = nv

    def _wd_applies(self, p):
        """Whether decoupled/coupled weight decay applies to this param."""
        if getattr(p, "regularizer", None) is not None:
            return True
        if self._weight_decay is None:
            return False
        apply_fn = getattr(self, "_apply_decay_param_fun", None)
        if apply_fn is not None:
            return bool(apply_fn(p.name))
        return True

    def _fused_update(self, lr, step_t, params, grads, states, lr_scales,
                      wd_mask):
        raise NotImplementedError

    def clear_grad(self, set_to_zero=True):
        for p in self._parameter_list:
            p.clear_grad(set_to_zero)

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        loss.backward()
        self.step()
        self.clear_grad()

    # ---------------- checkpoint ----------------
    def state_dict(self):
        self._ensure_state()
        sd = {"step_count": self._step_count,
              "step_tensor": Tensor(self._step_tensor._data_)}
        for name, vals in self._state.items():
            for i, v in enumerate(vals):
                if v is not None:
                    sd[f"{name}.{i}"] = v
        if isinstance(self._learning_rate, LRScheduler):
            sd["LR_Scheduler"] = self._learning_rate.state_dict()
        return sd

    def set_state_dict(self, state):
        self._ensure_state()
        self._step_count = int(state.get("step_count", 0))
        if "step_tensor" in state:
            self._step_tensor = Tensor(state["step_tensor"]._data_)
        for name, vals in self._state.items():
            for i in range(len(vals)):
                key = f"{name}.{i}"
                if key in state:
                    v = state[key]
                    arr = v._data_ if isinstance(v, Tensor) else v
                    # copy on adoption: donating compiled steps rewrite
                    # accumulators in place — the caller's checkpoint
                    # dict must stay restorable (same contract as
                    # Layer.set_state_dict)
                    if hasattr(arr, "copy"):
                        arr = arr.copy()
                    vals[i] = Tensor(arr)
        if "LR_Scheduler" in state and isinstance(self._learning_rate,
                                                  LRScheduler):
            self._learning_rate.set_state_dict(state["LR_Scheduler"])

    load_state_dict = set_state_dict


def _wd_coeff(wd):
    if wd is None:
        return 0.0
    if isinstance(wd, (L1Decay, L2Decay)):
        return wd.coeff
    return float(wd)


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None,
                 weight_decay=None, grad_clip=None, name=None,
                 multi_precision=True):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)

    def _state_spec(self):
        spec = []
        if self._use_master_weights:
            spec.append(("master", lambda p: (
                p._data.astype(jnp.float32)
                if self._master_weight_needed(p) else None)))
        return spec

    def _fused_update(self, lr, step_t, params, grads, states, lr_scales,
                      wd_mask):
        wd = _wd_coeff(self._weight_decay)
        new_params, new_master = [], []
        masters = states.get("master", [None] * len(params))
        for p, g, m, s, use_wd in zip(params, grads, masters, lr_scales,
                                      wd_mask):
            w = m if m is not None else p.astype(jnp.float32)
            gf = g.astype(jnp.float32)
            if wd and use_wd:
                gf = gf + wd * w
            w = w - lr * s * gf
            new_params.append(w.astype(p.dtype))
            new_master.append(w if m is not None else None)
        out_states = {}
        if "master" in states:
            out_states["master"] = new_master
        return new_params, out_states


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 name=None, multi_precision=True):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _state_spec(self):
        return [
            ("velocity", lambda p: jnp.zeros_like(p._data, dtype=jnp.float32)),
            ("master", lambda p: (p._data.astype(jnp.float32)
                                  if self._master_weight_needed(p) else None)),
        ]

    def _fused_update(self, lr, step_t, params, grads, states, lr_scales,
                      wd_mask):
        mu = self._momentum
        wd = _wd_coeff(self._weight_decay)
        new_p, new_v, new_m = [], [], []
        for p, g, v, m, s, use_wd in zip(params, grads, states["velocity"],
                                         states["master"], lr_scales, wd_mask):
            w = m if m is not None else p.astype(jnp.float32)
            gf = g.astype(jnp.float32)
            if wd and use_wd:
                gf = gf + wd * w
            v = mu * v + gf
            upd = gf + mu * v if self._nesterov else v
            w = w - lr * s * upd
            new_p.append(w.astype(p.dtype))
            new_v.append(v)
            new_m.append(w if m is not None else None)
        return new_p, {"velocity": new_v, "master": new_m}


class Adam(Optimizer):
    _decoupled = False

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=True,
                 name=None, apply_decay_param_fun=None, **kwargs):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        self._apply_decay_param_fun = apply_decay_param_fun

    def _state_spec(self):
        return [
            ("moment1", lambda p: jnp.zeros_like(p._data, dtype=jnp.float32)),
            ("moment2", lambda p: jnp.zeros_like(p._data, dtype=jnp.float32)),
            ("master", lambda p: (p._data.astype(jnp.float32)
                                  if self._master_weight_needed(p) else None)),
        ]

    def _fused_update(self, lr, step_t, params, grads, states, lr_scales,
                      wd_mask):
        b1, b2, eps = self._beta1, self._beta2, self._epsilon
        wd = _wd_coeff(self._weight_decay)
        bc1 = 1.0 - b1 ** step_t
        bc2 = 1.0 - b2 ** step_t
        # Pallas fused-update lane (pallas/fused.py): TPU/interpret only,
        # per-parameter shape-gated, in place, bitwise-equal to the jnp
        # sequence below (FLAGS_pallas_fused_optimizer; docs/TRAIN_STEP.md);
        # a leaf traced counts under the lane it took
        from ..pallas import fused as _pf
        from ..utils import monitor
        pallas_on = _pf.optimizer_kernels_enabled()
        new_p, new_m1, new_m2, new_mw = [], [], [], []
        for p, g, m1, m2, mw, s, use_wd in zip(
                params, grads, states["moment1"], states["moment2"],
                states["master"], lr_scales, wd_mask):
            w = mw if mw is not None else p.astype(jnp.float32)
            in_place = pallas_on and _pf.adam_update_supported(w)
            monitor.incr("pallas.adamw_update.in_place" if in_place
                         else "pallas.adamw_update.xla_lane")
            if in_place:
                w, m1, m2 = _pf.adam_update_pallas(
                    w, g, m1, m2, lr * s, bc1, bc2, b1=b1, b2=b2, eps=eps,
                    wd=(wd if use_wd else 0.0), decoupled=self._decoupled)
            else:
                gf = g.astype(jnp.float32)
                if wd and use_wd and not self._decoupled:
                    gf = gf + wd * w  # L2-coupled (Adam semantics)
                m1 = b1 * m1 + (1 - b1) * gf
                m2 = b2 * m2 + (1 - b2) * jnp.square(gf)
                m1_hat = m1 / bc1
                m2_hat = m2 / bc2
                upd = m1_hat / (jnp.sqrt(m2_hat) + eps)
                if wd and use_wd and self._decoupled:
                    upd = upd + wd * w  # decoupled (AdamW semantics)
                w = w - lr * s * upd
            new_p.append(w.astype(p.dtype))
            new_m1.append(m1)
            new_m2.append(m2)
            new_mw.append(w if mw is not None else None)
        return new_p, {"moment1": new_m1, "moment2": new_m2, "master": new_mw}


class AdamW(Adam):
    """Decoupled weight decay (reference: python/paddle/optimizer/adamw.py)."""
    _decoupled = True

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=True, name=None, **kwargs):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, lazy_mode, multi_precision,
                         name, apply_decay_param_fun)


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, name=None,
                 initial_accumulator_value=0.0, multi_precision=True):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def _state_spec(self):
        return [
            ("moment", lambda p: jnp.full_like(
                p._data, self._init_acc, dtype=jnp.float32)),
            ("master", lambda p: (p._data.astype(jnp.float32)
                                  if self._master_weight_needed(p) else None)),
        ]

    def _fused_update(self, lr, step_t, params, grads, states, lr_scales,
                      wd_mask):
        eps = self._epsilon
        wd = _wd_coeff(self._weight_decay)
        new_p, new_m, new_mw = [], [], []
        for p, g, m, mw, s, use_wd in zip(params, grads, states["moment"],
                                          states["master"], lr_scales, wd_mask):
            w = mw if mw is not None else p.astype(jnp.float32)
            gf = g.astype(jnp.float32)
            if wd and use_wd:
                gf = gf + wd * w
            m = m + jnp.square(gf)
            w = w - lr * s * gf / (jnp.sqrt(m) + eps)
            new_p.append(w.astype(p.dtype))
            new_m.append(m)
            new_mw.append(w if mw is not None else None)
        return new_p, {"moment": new_m, "master": new_mw}


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, multi_precision=True):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._rho = rho
        self._epsilon = epsilon
        self._momentum = momentum
        self._centered = centered

    def _state_spec(self):
        return [
            ("mean_square", lambda p: jnp.zeros_like(p._data, jnp.float32)),
            ("mean_grad", lambda p: jnp.zeros_like(p._data, jnp.float32)),
            ("velocity", lambda p: jnp.zeros_like(p._data, jnp.float32)),
            ("master", lambda p: (p._data.astype(jnp.float32)
                                  if self._master_weight_needed(p) else None)),
        ]

    def _fused_update(self, lr, step_t, params, grads, states, lr_scales,
                      wd_mask):
        rho, eps, mu = self._rho, self._epsilon, self._momentum
        wd = _wd_coeff(self._weight_decay)
        new_p, new_ms, new_mg, new_v, new_mw = [], [], [], [], []
        for p, g, ms, mg, v, mw, s, use_wd in zip(
                params, grads, states["mean_square"], states["mean_grad"],
                states["velocity"], states["master"], lr_scales, wd_mask):
            w = mw if mw is not None else p.astype(jnp.float32)
            gf = g.astype(jnp.float32)
            if wd and use_wd:
                gf = gf + wd * w
            ms = rho * ms + (1 - rho) * jnp.square(gf)
            if self._centered:
                mg = rho * mg + (1 - rho) * gf
                denom = jnp.sqrt(ms - jnp.square(mg) + eps)
            else:
                denom = jnp.sqrt(ms + eps)
            v = mu * v + lr * s * gf / denom
            w = w - v
            new_p.append(w.astype(p.dtype))
            new_ms.append(ms)
            new_mg.append(mg)
            new_v.append(v)
            new_mw.append(w if mw is not None else None)
        return new_p, {"mean_square": new_ms, "mean_grad": new_mg,
                       "velocity": new_v, "master": new_mw}


class Lamb(Optimizer):
    """reference: python/paddle/optimizer/lamb.py (distributed fused LAMB)."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01,
                 beta1=0.9, beta2=0.999, epsilon=1e-6, parameters=None,
                 grad_clip=None, exclude_from_weight_decay_fn=None,
                 name=None, multi_precision=True):
        super().__init__(learning_rate, parameters, lamb_weight_decay,
                         grad_clip, name, multi_precision)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._exclude_fn = exclude_from_weight_decay_fn

    def _state_spec(self):
        return [
            ("moment1", lambda p: jnp.zeros_like(p._data, jnp.float32)),
            ("moment2", lambda p: jnp.zeros_like(p._data, jnp.float32)),
            ("master", lambda p: (p._data.astype(jnp.float32)
                                  if self._master_weight_needed(p) else None)),
        ]

    def _wd_applies(self, p):
        if self._exclude_fn is not None and self._exclude_fn(p):
            return False
        return True

    def _fused_update(self, lr, step_t, params, grads, states, lr_scales,
                      wd_mask):
        b1, b2, eps = self._beta1, self._beta2, self._epsilon
        wd = _wd_coeff(self._weight_decay)
        bc1 = 1.0 - b1 ** step_t
        bc2 = 1.0 - b2 ** step_t
        new_p, new_m1, new_m2, new_mw = [], [], [], []
        for p, g, m1, m2, mw, s, use_wd in zip(
                params, grads, states["moment1"], states["moment2"],
                states["master"], lr_scales, wd_mask):
            w = mw if mw is not None else p.astype(jnp.float32)
            gf = g.astype(jnp.float32)
            m1 = b1 * m1 + (1 - b1) * gf
            m2 = b2 * m2 + (1 - b2) * jnp.square(gf)
            r = (m1 / bc1) / (jnp.sqrt(m2 / bc2) + eps)
            if wd and use_wd:
                r = r + wd * w
            w_norm = jnp.linalg.norm(w)
            r_norm = jnp.linalg.norm(r)
            trust = jnp.where((w_norm > 0) & (r_norm > 0),
                              w_norm / r_norm, 1.0)
            w = w - lr * s * trust * r
            new_p.append(w.astype(p.dtype))
            new_m1.append(m1)
            new_m2.append(m2)
            new_mw.append(w if mw is not None else None)
        return new_p, {"moment1": new_m1, "moment2": new_m2, "master": new_mw}


class Adadelta(Optimizer):
    """reference: python/paddle/optimizer/adadelta.py — accumulates E[g²]
    and E[Δx²], step size adapts without an explicit learning-rate decay."""

    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None,
                 name=None, multi_precision=True):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._epsilon = epsilon
        self._rho = rho

    def _state_spec(self):
        return [
            ("avg_sq_grad", lambda p: jnp.zeros_like(p._data,
                                                     dtype=jnp.float32)),
            ("avg_sq_update", lambda p: jnp.zeros_like(p._data,
                                                       dtype=jnp.float32)),
            ("master", lambda p: (p._data.astype(jnp.float32)
                                  if self._master_weight_needed(p)
                                  else None)),
        ]

    def _fused_update(self, lr, step_t, params, grads, states, lr_scales,
                      wd_mask):
        rho, eps = self._rho, self._epsilon
        wd = _wd_coeff(self._weight_decay)
        new_p, new_g2, new_u2, new_mw = [], [], [], []
        for p, g, g2, u2, mw, s, use_wd in zip(
                params, grads, states["avg_sq_grad"],
                states["avg_sq_update"], states["master"], lr_scales,
                wd_mask):
            w = mw if mw is not None else p.astype(jnp.float32)
            gf = g.astype(jnp.float32)
            if wd and use_wd:
                gf = gf + wd * w
            g2 = rho * g2 + (1 - rho) * jnp.square(gf)
            upd = jnp.sqrt(u2 + eps) / jnp.sqrt(g2 + eps) * gf
            u2 = rho * u2 + (1 - rho) * jnp.square(upd)
            w = w - lr * s * upd
            new_p.append(w.astype(p.dtype))
            new_g2.append(g2)
            new_u2.append(u2)
            new_mw.append(w if mw is not None else None)
        return new_p, {"avg_sq_grad": new_g2, "avg_sq_update": new_u2,
                       "master": new_mw}


class Adamax(Optimizer):
    """reference: python/paddle/optimizer/adamax.py — Adam with an
    infinity-norm second moment."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, multi_precision=True):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _state_spec(self):
        return [
            ("moment", lambda p: jnp.zeros_like(p._data,
                                                dtype=jnp.float32)),
            ("inf_norm", lambda p: jnp.zeros_like(p._data,
                                                  dtype=jnp.float32)),
            ("master", lambda p: (p._data.astype(jnp.float32)
                                  if self._master_weight_needed(p)
                                  else None)),
        ]

    def _fused_update(self, lr, step_t, params, grads, states, lr_scales,
                      wd_mask):
        b1, b2, eps = self._beta1, self._beta2, self._epsilon
        wd = _wd_coeff(self._weight_decay)
        bc1 = 1.0 - b1 ** step_t
        new_p, new_m, new_u, new_mw = [], [], [], []
        for p, g, m, u, mw, s, use_wd in zip(
                params, grads, states["moment"], states["inf_norm"],
                states["master"], lr_scales, wd_mask):
            w = mw if mw is not None else p.astype(jnp.float32)
            gf = g.astype(jnp.float32)
            if wd and use_wd:
                gf = gf + wd * w
            m = b1 * m + (1 - b1) * gf
            u = jnp.maximum(b2 * u, jnp.abs(gf))
            w = w - lr * s / bc1 * m / (u + eps)
            new_p.append(w.astype(p.dtype))
            new_m.append(m)
            new_u.append(u)
            new_mw.append(w if mw is not None else None)
        return new_p, {"moment": new_m, "inf_norm": new_u,
                       "master": new_mw}


class LBFGS(Optimizer):
    """reference: python/paddle/optimizer/lbfgs.py — limited-memory BFGS
    with a step(closure) interface.  Two-loop recursion over a bounded
    (s, y) history; `line_search_fn='strong_wolfe'` uses a backtracking
    Armijo search (the Wolfe curvature check is approximated by history
    curvature filtering, the standard practical simplification)."""

    def __init__(self, learning_rate=1.0, max_iter=20, max_eval=None,
                 tolerance_grad=1e-7, tolerance_change=1e-9,
                 history_size=100, line_search_fn=None, parameters=None,
                 weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision=False)
        self._max_iter = max_iter
        self._tol_grad = tolerance_grad
        self._tol_change = tolerance_change
        self._hist = history_size
        self._line_search = line_search_fn
        self._s, self._y = [], []
        self._prev_flat_g = None
        self._prev_flat_w = None

    def _flatten(self, arrs):
        return jnp.concatenate([a.reshape(-1).astype(jnp.float32)
                                for a in arrs])

    def _flat_grads(self):
        # params the closure didn't touch contribute zero gradient
        return self._flatten([
            p.grad._data_ if p.grad is not None
            else jnp.zeros(tuple(p.shape), jnp.float32)
            for p in self._parameter_list])

    def _unflatten_to_params(self, flat):
        off = 0
        for p in self._parameter_list:
            n = int(np.prod(p.shape)) if p.ndim else 1
            p._data_ = flat[off:off + n].reshape(tuple(p.shape)).astype(
                p._data_.dtype)
            off += n

    def _direction(self, g):
        q = g
        alphas = []
        for s, y in zip(reversed(self._s), reversed(self._y)):
            rho = 1.0 / jnp.maximum(jnp.dot(y, s), 1e-10)
            a = rho * jnp.dot(s, q)
            q = q - a * y
            alphas.append((rho, a, s, y))
        if self._y:
            y_last, s_last = self._y[-1], self._s[-1]
            gamma = jnp.dot(s_last, y_last) / jnp.maximum(
                jnp.dot(y_last, y_last), 1e-10)
            q = gamma * q
        for rho, a, s, y in reversed(alphas):
            b = rho * jnp.dot(y, q)
            q = q + s * (a - b)
        return -q

    def step(self, closure=None):
        if closure is None:
            raise RuntimeError("LBFGS.step requires a closure that "
                               "re-evaluates the loss")
        from ..core.state import no_grad

        loss = closure()
        flat_g = self._flat_grads()
        flat_w = self._flatten([p._data_ for p in self._parameter_list])
        for _ in range(self._max_iter):
            if float(jnp.max(jnp.abs(flat_g))) <= self._tol_grad:
                break
            if self._prev_flat_g is not None:
                s = flat_w - self._prev_flat_w
                y = flat_g - self._prev_flat_g
                if float(jnp.dot(s, y)) > 1e-10:   # curvature condition
                    self._s.append(s)
                    self._y.append(y)
                    if len(self._s) > self._hist:
                        self._s.pop(0)
                        self._y.pop(0)
            d = self._direction(flat_g)
            self._prev_flat_w, self._prev_flat_g = flat_w, flat_g
            t = float(self._current_lr())
            g_dot_d = float(jnp.dot(flat_g, d))
            f0 = float(loss)
            for _ls in range(20 if self._line_search else 1):
                new_w = flat_w + t * d
                with no_grad():
                    self._unflatten_to_params(new_w)
                for p in self._parameter_list:
                    p.clear_grad()
                loss = closure()
                if not self._line_search or \
                        float(loss) <= f0 + 1e-4 * t * g_dot_d:
                    break
                t *= 0.5
            flat_w = self._flatten([p._data_ for p in
                                    self._parameter_list])
            flat_g = self._flat_grads()
            if float(jnp.max(jnp.abs(t * d))) <= self._tol_change:
                break
        return loss

    def _current_lr(self):
        lr = self._learning_rate
        try:
            from .lr import LRScheduler
            if isinstance(lr, LRScheduler):
                return lr()
        except Exception:
            pass
        return lr
