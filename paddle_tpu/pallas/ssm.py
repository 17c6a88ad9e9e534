"""Mamba-2 (SSD) state-space mixing: the one-token recurrent step, its
Pallas kernel ``ssm_update``, and the chunked form a prefill chunk uses.

With ``H`` heads of ``P`` channels, state size ``N`` and ``G`` groups of
``B``/``C`` (Dao & Gu 2024, arXiv:2405.21060), per head

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        S: [P, N]
    y_t = S_t C_t + D x_t

Decoding runs the recurrence as written, one token a call
(``ssm_step``): on a TPU through the kernel, which reads and writes each
slot's state in place — the state's bytes are all the work there is —
and elsewhere through the same arithmetic in XLA (``ssm_step_xla``),
which is also the kernel's reference.  A chunk of ``L`` tokens uses the
SSD identity (``ssd_chunked``): inside the chunk ``Y = ((C B^T) o M)
(dt X) + (C S_in) decay`` with ``M_ij = exp(sum_{j<k<=i} dt_k A)``,
``S_out = decay_L S_in + sum_j decay_{j->L} dt_j x_j (x) B_j`` — plain
matrix products, so the matrix unit does it.  A position with ``dt = 0``
leaves the state as it was: that is how pad tokens and rows that are not
decoding ride a static batch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import _interpret, _unsharded_kernels_on

_HI = jax.lax.Precision.HIGHEST


def _heads_of_groups(m, heads):
    """[..., G, N] -> [..., H, N]: each group's B or C for its heads."""
    g = m.shape[-2]
    return m if g == heads else jnp.repeat(m, heads // g, axis=-2)


def ssm_step_xla(state, rows, x, dt, a, bm, cm):
    """The recurrence for one token, in XLA.  ``state`` [R, H, P, N]
    float32; ``rows`` int32 [B], the state row of each batch row; ``x``
    [B, H, P], ``dt`` [B, H] (0 leaves a row's state as it was), ``a``
    [H] (negative), ``bm``/``cm`` [B, G, N], all float32.  Returns
    (state', y [B, H, P]) with ``y = S' C`` (the caller adds ``D x``)."""
    h = x.shape[1]
    s = state[rows]
    bh, ch = _heads_of_groups(bm, h), _heads_of_groups(cm, h)
    da = jnp.exp(dt * a[None, :])
    s = s * da[:, :, None, None] + \
        (dt[:, :, None] * x)[..., None] * bh[:, :, None, :]
    y = jnp.sum(s * ch[:, :, None, :], axis=-1)
    return state.at[rows].set(s), y


def _ssm_update_kernel(rows_ref, s_ref, da_ref, xdt_ref, b_ref, c_ref,
                       s_out, y_out, *, heads, per_group):
    del rows_ref                    # consumed by the index maps
    cols = []
    for h in range(heads):
        g = h // per_group
        bn = b_ref[g:g + 1, :]                       # [1, N]
        cn = c_ref[g:g + 1, :]
        # heads sit on the lanes of ``xdt``/``da`` (channels on the
        # sublanes), so a head's column broadcasts along the state's N
        s = s_ref[h] * da_ref[:, h:h + 1] + xdt_ref[:, h:h + 1] * bn
        s_out[h] = s
        cols.append(jnp.sum(s * cn, axis=-1, keepdims=True))
    y_out[...] = jnp.concatenate(cols, axis=-1)      # [P, H]


def ssm_update(state, rows, x, dt, a, bm, cm):
    """``ssm_step_xla`` as ONE Pallas call: grid over the batch rows,
    each step moving one slot's whole [H, P, N] state through VMEM, the
    state aliased in and out so that rows the grid does not name are
    left untouched.  ``rows`` rides scalar prefetch and picks each
    step's state row, like the page table picks pages."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, p = x.shape
    n = state.shape[-1]
    g = bm.shape[1]
    # what the kernel reads per head as a column: channels on sublanes,
    # heads on lanes
    xdt = jnp.swapaxes(x * dt[:, :, None], 1, 2)             # [B, P, H]
    da = jnp.broadcast_to(jnp.exp(dt * a[None, :])[:, None, :], (b, p, h))

    def row_state(i, rows):
        return (rows[i], 0, 0, 0)

    def row(i, rows):
        return (i, 0, 0)

    kernel = functools.partial(_ssm_update_kernel, heads=h,
                               per_group=h // g)
    block = h * p * n * 4
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[pl.BlockSpec((None, h, p, n), row_state),
                  pl.BlockSpec((None, p, h), row),
                  pl.BlockSpec((None, p, h), row),
                  pl.BlockSpec((None, g, n), row),
                  pl.BlockSpec((None, g, n), row)],
        out_specs=[pl.BlockSpec((None, h, p, n), row_state),
                   pl.BlockSpec((None, p, h), row)])
    new_state, y = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((b, p, h), jnp.float32)],
        # operand 0 is ``rows``; the state is operand 1
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(32 << 20, 6 * block)),
        interpret=_interpret(),
        name="ssm_update",
    )(rows.astype(jnp.int32), state, da, xdt, bm, cm)
    return new_state, jnp.swapaxes(y, 1, 2)


def ssm_step(state, rows, x, dt, a, bm, cm):
    """One token of the recurrence: the kernel where Mosaic kernels run
    (a TPU, or the interpreter in CPU tests), XLA elsewhere."""
    if _unsharded_kernels_on():
        return ssm_update(state, rows, x, dt, a, bm, cm)
    return ssm_step_xla(state, rows, x, dt, a, bm, cm)


def _ssd_chunk(s_in, x, dt, a, bm, cm):
    """One chunk of the SSD identity.  ``s_in`` [B, H, P, N]; ``x`` [B,
    L, H, P]; ``dt`` [B, L, H]; ``bm``/``cm`` [B, L, H, N]; float32.
    Returns (s_out, y [B, L, H, P])."""
    length = x.shape[1]
    cum = jnp.cumsum(dt * a[None, None, :], axis=1)          # [B, L, H]
    # M_ij = exp(cum_i - cum_j) for j <= i: masked before the exp, where
    # the upper triangle would overflow
    seg = cum[:, :, None, :] - cum[:, None, :, :]            # [B, i, j, H]
    tri = jnp.tril(jnp.ones((length, length), bool))[None, :, :, None]
    m = jnp.exp(jnp.where(tri, seg, -jnp.inf))
    xdt = x * dt[..., None]
    scores = jnp.einsum("bihn,bjhn->bijh", cm, bm, precision=_HI)
    y = jnp.einsum("bijh,bjhp->bihp", scores * m, xdt, precision=_HI)
    y = y + jnp.einsum("bihn,bhpn->bihp", cm, s_in, precision=_HI) * \
        jnp.exp(cum)[..., None]
    to_end = jnp.exp(cum[:, -1:, :] - cum)                   # [B, L, H]
    s_out = s_in * jnp.exp(cum[:, -1, :])[:, :, None, None] + jnp.einsum(
        "bjhp,bjhn->bhpn", xdt * to_end[..., None], bm, precision=_HI)
    return s_out, y


def ssd_chunked(s_in, x, dt, a, bm, cm, chunk):
    """The chunked form over ``L`` tokens with the state carried in and
    out, in sub-chunks of ``chunk`` where ``L`` is longer.  ``bm``/``cm``
    are [B, L, G, N].  Returns (s_out, y [B, L, H, P]) with ``y = S C``
    at every position (the caller adds ``D x``)."""
    b, length, h, p = x.shape
    bm, cm = _heads_of_groups(bm, h), _heads_of_groups(cm, h)
    if length <= chunk:
        return _ssd_chunk(s_in, x, dt, a, bm, cm)
    pad = -length % chunk
    if pad:                          # dt = 0: the state passes through
        x, dt, bm, cm = (jnp.pad(t, [(0, 0), (0, pad)]
                                 + [(0, 0)] * (t.ndim - 2))
                         for t in (x, dt, bm, cm))
    parts = [jnp.moveaxis(t.reshape((b, -1, chunk) + t.shape[2:]), 1, 0)
             for t in (x, dt, bm, cm)]

    def body(s, part):
        return _ssd_chunk(s, part[0], part[1], a, part[2], part[3])

    s_out, ys = jax.lax.scan(body, s_in, tuple(parts))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, -1, h, p)
    return s_out, y[:, :length]
