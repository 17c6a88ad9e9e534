"""Automatic mixed precision (reference: python/paddle/amp/ —
auto_cast.py:696, grad_scaler.py:578).

TPU-native: bf16 is the native compute type; `auto_cast` flips the dispatch
hook to cast white-listed op inputs (O1) or everything non-black (O2) to
bf16.  GradScaler keeps the reference API; with bf16 no loss scaling is
numerically required (scale stays 1 and never updates), while fp16 uses real
dynamic loss scaling.
"""
from __future__ import annotations

import contextlib

from ..core import state as _state
from ..core.tensor import Tensor
from ..core import dtype as _dtype
from . import amp_lists  # noqa: F401


@contextlib.contextmanager
def auto_cast(enable=True, custom_white_list=None, custom_black_list=None,
              level="O1", dtype="bfloat16", use_promote=True):
    st = _state.STATE
    prev = (st.amp_level, st.amp_dtype, st.amp_custom_white_list,
            st.amp_custom_black_list)
    if enable:
        st.amp_level = level
        st.amp_dtype = _dtype.convert_dtype(dtype)
        st.amp_custom_white_list = set(custom_white_list or ())
        st.amp_custom_black_list = set(custom_black_list or ())
    try:
        yield
    finally:
        (st.amp_level, st.amp_dtype, st.amp_custom_white_list,
         st.amp_custom_black_list) = prev


amp_guard = auto_cast


def decorate(models, optimizers=None, level="O2", dtype="bfloat16",
             master_weight=None, save_dtype=None):
    """Cast model params to amp dtype (O2); optimizer keeps fp32 master
    weights automatically (reference: amp.decorate master weights)."""
    target = _dtype.convert_dtype(dtype)
    single = not isinstance(models, (list, tuple))
    model_list = [models] if single else list(models)
    if level == "O2":
        for m in model_list:
            for p in m.parameters():
                if _dtype.is_floating_point(p.dtype) and p.dtype != target:
                    p._data = p._data.astype(target)
    if optimizers is None:
        return models if single else model_list
    for opt in ([optimizers] if not isinstance(optimizers, (list, tuple))
                else optimizers):
        opt._use_master_weights = (master_weight is not False)
    return (models if single else model_list), optimizers


class GradScaler:
    """Dynamic loss scaling (reference: python/paddle/amp/grad_scaler.py:578).

    bf16 training does not need scaling — with init_loss_scaling=1.0 this is
    a transparent pass-through, keeping train-loop code portable.
    """

    def __init__(self, enable=True, init_loss_scaling=2.0 ** 15,
                 incr_ratio=2.0, decr_ratio=0.5, incr_every_n_steps=2000,
                 decr_every_n_nan_or_inf=1, use_dynamic_loss_scaling=True,
                 min_loss_scale=1.0, always_check_found_inf=False):
        self._enable = enable
        self._scale = float(init_loss_scaling) if enable else 1.0
        self._incr_ratio = incr_ratio
        self._decr_ratio = decr_ratio
        self._incr_every = incr_every_n_steps
        self._decr_every = decr_every_n_nan_or_inf
        self._dynamic = use_dynamic_loss_scaling
        # decay floor: repeated found-inf streaks used to be able to
        # drive the scale toward the hard 1.0 minimum silently; a higher
        # floor keeps fp16 gradients representable AND the streak metric
        # below makes the pathology visible to the training sentinel
        self._min_scale = max(float(min_loss_scale), 1.0)
        # run the found-inf check even at scale == 1.0: the training
        # sentinel wraps non-AMP runs in a unit-scale GradScaler so the
        # existing skip machinery guards them against non-finite steps
        self._always_check = bool(always_check_found_inf)
        self._good_steps = 0
        self._bad_steps = 0
        self._found_inf = False
        self._found_inf_streak = 0
        self._unscaled = False
        # a caller that already reduced the gradients (the training
        # sentinel's fused health pass) can plant its device-side
        # found-inf flag here; the next unscale_ consumes it instead of
        # paying a second reduction over every gradient
        self._planted_found_inf = None

    def scale(self, loss):
        if not self._enable or self._scale == 1.0:
            return loss
        return loss * self._scale

    def unscale_(self, optimizer, defer_found_inf=False):
        # once-per-step guard: an explicit unscale_ (e.g. before a
        # cross-rank grad sync or clipping) must not re-divide in step()
        if not self._enable or self._unscaled:
            return
        self._unscaled = True
        import jax.numpy as jnp
        inv = 1.0 / self._scale
        self._found_inf_dev = None
        found_inf = False
        for p in optimizer._all_params():
            if p.grad is not None:
                g = p.grad._data
                if self._scale != 1.0:
                    g = g * jnp.asarray(inv, g.dtype)
                    p.grad._data = g
        # NaN/Inf check — only when scaling is active.  ONE stacked
        # device reduction over all per-grad sums, then a single host
        # read (the old per-grad fetch loop was one device→host sync per
        # parameter).  With defer_found_inf the flag STAYS on device so
        # the caller can batch it into its gradient all_reduce and read
        # it once after the reduction (Model._sync_grads).
        if self._scale != 1.0 or self._always_check:
            bad = self._planted_found_inf
            self._planted_found_inf = None
            if bad is None:
                sums = [jnp.sum(p.grad._data)
                        for p in optimizer._all_params()
                        if p.grad is not None]
                if sums:
                    bad = ~jnp.isfinite(jnp.stack(sums)).all()
            if bad is not None:
                if defer_found_inf:
                    self._found_inf_dev = bad
                else:
                    import numpy as np
                    found_inf = bool(np.asarray(bad))
        self._found_inf = found_inf

    def _found_inf_tensor(self):
        """The deferred found-inf decision as a [1] float Tensor ready to
        ride a gradient all_reduce (0.0 = all finite)."""
        import jax.numpy as jnp
        bad = getattr(self, "_found_inf_dev", None)
        if bad is None:
            bad = jnp.asarray(self._found_inf)
        self._found_inf_dev = None
        return Tensor(jnp.reshape(bad, (1,)).astype(jnp.float32))

    def step(self, optimizer):
        if not self._enable:
            optimizer.step()
            return
        self.unscale_(optimizer)
        if not self._found_inf:
            optimizer.step()
        self.update()
        self._unscaled = False

    def minimize(self, optimizer, scaled_loss):
        self.step(optimizer)

    def update(self):
        if not self._enable:
            return
        # consecutive-found-inf accounting runs for EVERY enabled scaler
        # (the unit-scale sentinel wrapper included): a growing streak is
        # itself an anomaly — repeated infs silently decaying the scale
        # toward its floor — and the amp.found_inf_streak gauge is how
        # the sentinel and dashboards see it.  Healthy steps with an
        # already-zero streak pay no registry traffic.
        from ..utils import monitor as _monitor
        if self._found_inf:
            self._found_inf_streak += 1
            _monitor.incr("amp.found_inf_total")
            _monitor.set_value("amp.found_inf_streak",
                               self._found_inf_streak)
        elif self._found_inf_streak:
            self._found_inf_streak = 0
            _monitor.set_value("amp.found_inf_streak", 0)
        if not self._dynamic or self._scale == 1.0:
            return
        if self._found_inf:
            self._bad_steps += 1
            self._good_steps = 0
            if self._bad_steps >= self._decr_every:
                self._scale = max(self._scale * self._decr_ratio,
                                  self._min_scale)
                self._bad_steps = 0
        else:
            self._good_steps += 1
            self._bad_steps = 0
            if self._good_steps >= self._incr_every:
                self._scale *= self._incr_ratio
                self._good_steps = 0

    @property
    def found_inf_streak(self):
        """Consecutive steps whose update was skipped for non-finite
        gradients (reset by the first healthy step)."""
        return self._found_inf_streak

    def is_enable(self):
        return self._enable

    def is_use_dynamic_loss_scaling(self):
        return self._dynamic

    def get_loss_scaling(self):
        return self._scale

    def state_dict(self):
        return {"scale": self._scale, "good_steps": self._good_steps,
                "bad_steps": self._bad_steps}

    def load_state_dict(self, state):
        self._scale = state["scale"]
        self._good_steps = state["good_steps"]
        self._bad_steps = state["bad_steps"]

from . import debugging  # noqa: F401


def is_bfloat16_supported(device=None):
    """bf16 is native on TPU (MXU) and emulated losslessly on CPU XLA."""
    return True


def is_float16_supported(device=None):
    import jax
    return jax.devices()[0].platform in ("tpu", "gpu")
