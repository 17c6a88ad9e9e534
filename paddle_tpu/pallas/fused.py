"""Fused Pallas kernels: RMS norm, rotary embedding (rope), and the
Adam/AdamW optimizer update.

Reference capability: the CUDA fusion pack —
paddle/phi/kernels/gpu/rms_norm_kernel.cu (+ its grad in
rms_norm_grad_kernel), paddle/phi/kernels/fusion/gpu/fused_rope_kernel.cu,
and the multi-tensor fused adam/adamw kernels
(paddle/phi/kernels/gpu/adamw_kernel.cu).
TPU-native realization: row-blocked Pallas kernels with fp32 accumulation.
RMS norm saves the per-row reciprocal-RMS as a residual so backward never
re-reduces x², and accumulates the weight gradient across the sequential
TPU grid in VMEM scratch (one kernel, no second pass).  Rope (neox style) has as its backward
the forward kernel with negated sin (the rotation adjoint), so one kernel
serves both directions.  The Adam update kernel streams (w, g, m1, m2)
through VMEM blocks of each leaf's own layout, writes w, m1 and m2 in
place, and performs the EXACT elementwise fp32 sequence of
``optimizer.Adam._fused_update`` — same ops, same order — so the Pallas
lane is bitwise-equal to the jnp lane in interpreter mode
(tests/test_train_step.py; on the chip Mosaic and XLA round it an ulp
apart, PERF.md §6 PR 37); it is gated by
``FLAGS_pallas_fused_optimizer`` and used only on TPU (or under
interpret mode), for leaves of rank 2 and up.

All kernels run in interpreter mode on CPU for CI (see
flash_attention._interpret).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .flash_attention import _interpret
from .flash_attention import _unsharded_kernels_on as _kernels_on


def _pick_block_rows(n_rows, n_cols, budget=1 << 21):
    """Rows per grid step: the largest 8·2^k divisor of n_rows that keeps
    x/g/out blocks within ~2MB of VMEM each (Mosaic needs the sublane dim
    to be a multiple of 8; callers guarantee n_rows % 8 == 0)."""
    cap = max(8, min(budget // max(n_cols * 4, 1), n_rows, 1024))
    if n_rows <= cap:
        return n_rows  # single block (callers guarantee n_rows % 8 == 0)
    rows = 8
    while rows * 2 <= cap and n_rows % (rows * 2) == 0:
        rows *= 2
    return rows


# ------------------------------------------------------------------
# RMS norm
# ------------------------------------------------------------------

def _rms_fwd_kernel(x_ref, w_ref, y_ref, r_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    y_ref[:] = (x * r * w_ref[:].astype(jnp.float32)).astype(y_ref.dtype)
    r_ref[:] = r


def _rms_bwd_kernel(x_ref, w_ref, r_ref, g_ref, dx_ref, dw_ref, dw_scr,
                    *, n_cols):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    num = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        dw_scr[:] = jnp.zeros_like(dw_scr)

    x = x_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    r = r_ref[:]
    g = g_ref[:].astype(jnp.float32)
    xhat = x * r
    gw = g * w
    # dx = r * (gw - xhat * mean(gw * xhat))
    m = jnp.mean(gw * xhat, axis=-1, keepdims=True)
    dx_ref[:] = (r * (gw - xhat * m)).astype(dx_ref.dtype)
    dw_scr[:] += jnp.sum(g * xhat, axis=0, keepdims=True)

    @pl.when(i == num - 1)
    def _finalize():
        dw_ref[:] = dw_scr[:].astype(dw_ref.dtype)


def _rms_pallas_fwd(x2d, w, eps, block_rows):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, n = x2d.shape
    grid = (rows // block_rows,)
    y, r = pl.pallas_call(
        functools.partial(_rms_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
                  pl.BlockSpec((1, n), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, n), x2d.dtype),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)],
        interpret=_interpret(),
        name="rms_norm_fwd",
    )(x2d, w.reshape(1, n))
    return y, r


def _rms_pallas_bwd(x2d, w, r, g2d, block_rows):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, n = x2d.shape
    grid = (rows // block_rows,)
    dx, dw = pl.pallas_call(
        functools.partial(_rms_bwd_kernel, n_cols=n),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
                  pl.BlockSpec((1, n), lambda i: (0, 0)),
                  pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, n), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
                   pl.BlockSpec((1, n), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, n), x2d.dtype),
                   jax.ShapeDtypeStruct((1, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, n), jnp.float32)],
        interpret=_interpret(),
        name="rms_norm_bwd",
    )(x2d, w.reshape(1, n), r, g2d)
    return dx, dw.reshape(w.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm_pallas(x, w, eps):
    """x: [..., N], w: [N] → x / rms(x) * w (fp32 accumulation)."""
    y, _ = _rms_fwd_core(x, w, eps)
    return y


def _rms_fwd_core(x, w, eps):
    n = x.shape[-1]
    x2d = x.reshape(-1, n)
    block = _pick_block_rows(x2d.shape[0], n)
    y, r = _rms_pallas_fwd(x2d, w, eps, block)
    return y.reshape(x.shape), (x2d, r, block)


def _rms_vjp_fwd(x, w, eps):
    y, (x2d, r, block) = _rms_fwd_core(x, w, eps)
    return y, (x2d, w, r, block, x.shape)


def _rms_vjp_bwd(eps, res, g):
    x2d, w, r, block, shape = res
    dx, dw = _rms_pallas_bwd(x2d, w, r, g.reshape(x2d.shape), block)
    return dx.reshape(shape), dw.astype(w.dtype)


rms_norm_pallas.defvjp(_rms_vjp_fwd, _rms_vjp_bwd)


def rms_norm_supported(x, w):
    if not _kernels_on():
        return False
    if w is None or x.shape[-1] != w.shape[-1] or w.ndim != 1:
        return False
    n = x.shape[-1]
    rows = 1
    for dim in x.shape[:-1]:
        rows *= dim
    return n % 128 == 0 and rows % 8 == 0


# ------------------------------------------------------------------
# Rope (rotary position embedding)
# ------------------------------------------------------------------

def _rope_kernel(t_ref, cos_ref, sin_ref, o_ref):
    t = t_ref[:].astype(jnp.float32)         # [block_s, H, D]
    cos = cos_ref[:].astype(jnp.float32)[:, None, :]   # [block_s, 1, D]
    sin = sin_ref[:].astype(jnp.float32)[:, None, :]
    d = t.shape[-1]
    rot = jnp.concatenate([-t[..., d // 2:], t[..., :d // 2]], axis=-1)
    o_ref[:] = (t * cos + rot * sin).astype(o_ref.dtype)


def _rope_call(t, cos, sin):
    """t: [B, S, H, D]; cos/sin: [S, D]."""
    from jax.experimental import pallas as pl

    b, s, h, d = t.shape
    block_s = s
    while block_s * h * d * 4 > (1 << 21) and block_s % 2 == 0:
        block_s //= 2
    grid = (b, s // block_s)
    return pl.pallas_call(
        _rope_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((None, block_s, h, d),
                               lambda i, j: (i, j, 0, 0)),
                  pl.BlockSpec((block_s, d), lambda i, j: (j, 0)),
                  pl.BlockSpec((block_s, d), lambda i, j: (j, 0))],
        out_specs=pl.BlockSpec((None, block_s, h, d),
                               lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(t.shape, t.dtype),
        interpret=_interpret(),
        name="rope",
    )(t, cos, sin)


@jax.custom_vjp
def rope_pallas(t, cos, sin):
    """Rotary embedding, neox (rotate-half) style: [B, S, H, D] with
    [S, D] tables."""
    return _rope_call(t, cos, sin)


def _rope_vjp_fwd(t, cos, sin):
    return _rope_call(t, cos, sin), (cos, sin)


def _rope_vjp_bwd(res, g):
    cos, sin = res
    # adjoint of the rotation = forward with sin negated; the sin/cos
    # tables are position constants, not parameters — zero cotangent
    return (_rope_call(g, cos, -sin),
            jnp.zeros_like(cos), jnp.zeros_like(sin))


rope_pallas.defvjp(_rope_vjp_fwd, _rope_vjp_bwd)


def rope_supported(t_shape, d, neox=True):
    """The kernel serves the neox (rotate-half) style only: an
    interleaved form's pair reshape lowers to a gather Mosaic refuses
    ("Only 2D gather is supported"), so the XLA rope is the one
    interleaved path."""
    if not neox or not _kernels_on():
        return False
    return d % 2 == 0 and d <= 512 and t_shape[1] % 8 == 0


# ------------------------------------------------------------------
# Adam / AdamW fused update
# ------------------------------------------------------------------

def _adam_kernel(scal_ref, w_ref, g_ref, m1_ref, m2_ref,
                 w_out, m1_out, m2_out, *, b1, b2, eps, wd, decoupled):
    """One row block of the Adam/AdamW elementwise update.

    The op sequence MUST mirror ``optimizer.Adam._fused_update`` exactly
    (same fp32 ops, same order) so this lane is bitwise-equal to the jnp
    lane — that is the "exact" contract FLAGS_pallas_fused_optimizer
    promises.  scal_ref holds the three runtime scalars
    [lr*lr_scale, bias_corr1, bias_corr2]."""
    lr_s = scal_ref[0, 0]
    bc1 = scal_ref[0, 1]
    bc2 = scal_ref[0, 2]
    w = w_ref[:]
    gf = g_ref[:].astype(jnp.float32)
    m1 = m1_ref[:]
    m2 = m2_ref[:]
    if wd and not decoupled:
        gf = gf + wd * w              # L2-coupled (Adam semantics)
    m1 = b1 * m1 + (1 - b1) * gf
    m2 = b2 * m2 + (1 - b2) * jnp.square(gf)
    m1_hat = m1 / bc1
    m2_hat = m2 / bc2
    upd = m1_hat / (jnp.sqrt(m2_hat) + eps)
    if wd and decoupled:
        upd = upd + wd * w            # decoupled (AdamW semantics)
    w_out[:] = w - lr_s * upd
    m1_out[:] = m1
    m2_out[:] = m2


# Elements of one operand's block: the [1024, 128] blocks the kernel
# streamed before PR 37, so the double-buffered working set (four
# operands in, three out) stays where it was, ~7 MB of VMEM.
_ADAM_BLOCK_ELEMS = 1024 * 128
_LANES = 128


def adam_update_blocks(shape, itemsizes=(4,)):
    """The kernel's view of a leaf and its block, by one rule on the
    leaf's shape and its operands' item sizes: ``(view, block)``, both
    ``(rows, cols)`` for a matrix and ``(leading, rows, cols)`` above.

    The view merges only the dimensions before the last two, which
    leaves the tiled layout as it is: a bitcast, never a copy.  A block
    spans the whole last dimension where ``_ADAM_BLOCK_ELEMS`` holds as
    many rows as the narrowest operand's sublane tile (16 for a
    bfloat16 gradient), else a lane-aligned share of it; rows come in
    whole tiles, or all of them; where all rows fit, a block takes as
    many leading indices as fit.  The grid is ``cdiv`` of view by block:
    the update is elementwise, so a partial last block is harmless.
    None where the kernel does not host the leaf (a scalar, an empty or
    one-dimensional leaf: those take the jnp lane)."""
    if len(shape) < 2 or math.prod(shape) == 0:
        return None
    *lead, r, c = (int(d) for d in shape)
    view = (math.prod(lead), r, c)
    sub = 32 // min(itemsizes)
    cols = c if sub * c <= _ADAM_BLOCK_ELEMS else \
        _ADAM_BLOCK_ELEMS // sub // _LANES * _LANES
    lanes = -(-cols // _LANES) * _LANES
    rows = _ADAM_BLOCK_ELEMS // lanes // sub * sub
    if r > rows:
        block = (1, rows, cols)
    else:
        held = -(-r // sub) * sub * lanes
        block = (min(view[0], _ADAM_BLOCK_ELEMS // held), r, cols)
    k = 3 if lead else 2
    return view[-k:], block[-k:]


def adam_update_supported(w):
    """Whether the fused kernel hosts this leaf (:func:`adam_update_blocks`)."""
    return adam_update_blocks(w.shape) is not None


def optimizer_kernels_enabled():
    from ..utils.flags import flag as _flag
    return bool(_flag("FLAGS_pallas_fused_optimizer", True)) and \
        _kernels_on()


def adam_update_pallas(w, g, m1, m2, lr_s, bc1, bc2, *, b1, b2, eps, wd,
                       decoupled):
    """Fused Adam/AdamW step for one parameter, in place.

    w/m1/m2: fp32 working value and moments (a shape
    :func:`adam_update_supported` admits); g: gradient (cast to fp32
    inside the kernel); lr_s/bc1/bc2: runtime scalars (traced).  Each
    leaf is read and written in the layout it lives in, and the results
    alias w, m1 and m2: where the caller donates them XLA copies
    nothing, else it copies each aliased input once.  Returns
    (new_w, new_m1, new_m2) with w's shape/dtype."""
    from jax.experimental import pallas as pl

    shape = w.shape
    view, block = adam_update_blocks(
        shape, (w.dtype.itemsize, g.dtype.itemsize))
    grid = tuple(-(-v // b) for v, b in zip(view, block))
    scal = jnp.stack([jnp.asarray(lr_s, jnp.float32),
                      jnp.asarray(bc1, jnp.float32),
                      jnp.asarray(bc2, jnp.float32)]).reshape(1, 3)
    spec = pl.BlockSpec(block, lambda *ij: ij)
    out = pl.pallas_call(
        functools.partial(_adam_kernel, b1=b1, b2=b2, eps=eps, wd=wd,
                          decoupled=decoupled),
        grid=grid,
        in_specs=[pl.BlockSpec((1, 3), lambda *ij: (0, 0)),
                  spec, spec, spec, spec],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct(view, jnp.float32)] * 3,
        input_output_aliases={1: 0, 3: 1, 4: 2},
        interpret=_interpret(),
        name="adamw_update",
    )(scal, w.reshape(view), g.reshape(view), m1.reshape(view),
      m2.reshape(view))
    return tuple(o.reshape(shape) for o in out)
