"""Mamba-2 (SSD) state-space mixing: the one-token recurrent step, its
Pallas kernel ``ssm_update``, and the chunked form a prefill chunk uses.

With ``H`` heads of ``P`` channels, state size ``N`` and ``G`` groups of
``B``/``C`` (Dao & Gu 2024, arXiv:2405.21060), per head

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        S: [P, N]
    y_t = S_t C_t + D x_t

**The stored state is lane-packed**: a sequence's row is ``[H/k, N, k
P]`` (``state_row_shape``) — the state index on the sublanes and the
``P`` channels of ``k`` heads side by side on the lanes, ``k`` by one
rule on the shape (``heads_per_tile``).  Every function here takes and
returns the state in that layout; ``unpack_state`` gives ``[H, P, N]``
back.  So a head's decay and input are a ROW of lanes, a group's B and
C a COLUMN of sublanes, and a head's output is a sum over the sublanes:
the step moves its bytes with no work across lanes.

Decoding runs the recurrence as written, one token a call
(``ssm_step``): on a TPU through the kernel, which reads and writes each
slot's state in place — the state's bytes are all the work there is —
and elsewhere through the same arithmetic in XLA (``ssm_step_xla``),
which is also the kernel's reference; the lane taken is counted where
it is traced (``pallas.ssm_update.kernel`` / ``.xla_lane``).  A chunk of
``L`` tokens uses the SSD identity (``ssd_chunked``): inside the chunk
``Y = ((C B^T) o M) (dt X) + (C S_in) decay`` with ``M_ij =
exp(sum_{j<k<=i} dt_k A)``, ``S_out = decay_L S_in + sum_j decay_{j->L}
dt_j x_j (x) B_j`` — plain matrix products, so the matrix unit does it.
A position with ``dt = 0`` leaves the state as it was: that is how pad
tokens and rows that are not decoding ride a static batch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import _interpret, _unsharded_kernels_on

_HI = jax.lax.Precision.HIGHEST
_LANES = 128


def heads_per_tile(heads, head_dim, groups):
    """``k``, the heads whose channels share one row of lanes: the
    largest divisor of the heads a group has with ``k * head_dim <=
    128`` (1 where a head fills the lanes alone).  A tile's heads then
    read one group's B and C."""
    per_group = heads // groups
    return max((k for k in range(1, per_group + 1)
                if per_group % k == 0 and k * head_dim <= _LANES),
               default=1)


def state_row_shape(heads, head_dim, state, groups):
    """A sequence's stored SSM state: ``[H/k, N, k P]``."""
    k = heads_per_tile(heads, head_dim, groups)
    return (heads // k, state, k * head_dim)


def state_spec(heads, head_dim, state, groups):
    """What a cache manager keeps of the state a sequence (``layer_states``
    of the Mamba-2 models): (the stored row's shape, its dtype, the ``[H,
    P, N]`` a row is read back in: ``unpack_state``).  Float32: a
    recurrence of 1,000+ steps rounds at every one of them."""
    return (state_row_shape(heads, head_dim, state, groups), "float32",
            (heads, head_dim, state))


def pack_state(s, per_tile):
    """``[..., H, P, N]`` -> the stored ``[..., H/k, N, k P]``."""
    *lead, h, p, n = s.shape
    s = s.reshape(*lead, h // per_tile, per_tile, p, n)
    return s.swapaxes(-1, -2).swapaxes(-2, -3).reshape(
        *lead, h // per_tile, n, per_tile * p)


def unpack_state(s, heads):
    """The stored ``[..., H/k, N, k P]`` -> ``[..., H, P, N]`` (numpy or
    jax arrays alike)."""
    *lead, tiles, n, lanes = s.shape
    k = heads // tiles
    s = s.reshape(*lead, tiles, n, k, lanes // k)
    return s.swapaxes(-3, -2).swapaxes(-2, -1).reshape(
        *lead, heads, lanes // k, n)


def _heads_of_groups(m, heads):
    """[..., G, N] -> [..., H, N]: each group's B or C for its heads."""
    g = m.shape[-2]
    return m if g == heads else jnp.repeat(m, heads // g, axis=-2)


def _as_tiles(m, tiles):
    """[..., H, N] -> [..., T, N, k, 1]: a head's B or C as a column
    beside its tile's other heads."""
    *lead, h, n = m.shape
    return m.reshape(*lead, tiles, h // tiles, n).swapaxes(-1, -2)[..., None]


def ssm_step_xla(state, rows, x, dt, a, bm, cm):
    """The recurrence for one token, in XLA.  ``state`` [R, H/k, N, k P]
    float32; ``rows`` int32 [B], the state row of each batch row; ``x``
    [B, H, P], ``dt`` [B, H] (0 leaves a row's state as it was), ``a``
    [H] (negative), ``bm``/``cm`` [B, G, N], all float32.  Returns
    (state', y [B, H, P]) with ``y = S' C`` (the caller adds ``D x``)."""
    b, h, p = x.shape
    tiles, n, lanes = state.shape[1:]
    k = h // tiles
    s = state[rows].reshape(b, tiles, n, k, p)
    bh, ch = (_as_tiles(_heads_of_groups(m, h), tiles) for m in (bm, cm))
    da = jnp.exp(dt * a[None, :]).reshape(b, tiles, 1, k, 1)
    xdt = (dt[:, :, None] * x).reshape(b, tiles, 1, k, p)
    s = s * da + xdt * bh
    y = jnp.sum(s * ch, axis=2).reshape(b, h, p)
    return state.at[rows].set(s.reshape(b, tiles, n, lanes)), y


def _ssm_update_kernel(rows_ref, s_ref, da_ref, xdt_ref, b_ref, c_ref,
                       s_out, y_out, *, tile_groups, head_dim):
    """One slot.  ``s_ref`` [T, N, L]; ``da_ref``, ``xdt_ref``, ``y_out``
    [T, L] (a tile's heads' values, each over its ``P`` lanes);
    ``b_ref``, ``c_ref`` [G, N]; ``tile_groups[t]``: the group of each
    of tile t's heads."""
    del rows_ref                    # consumed by the index maps
    n, lanes = s_ref.shape[1:]

    def columns(ref, groups):
        # [N, L]: the group's B or C down the sublanes, the same on every
        # lane of its heads — a row spread over the lanes' rows and
        # transposed, once a group
        col = {g: jnp.broadcast_to(ref[g:g + 1, :], (lanes, n)).T
               for g in set(groups)}
        out = col[groups[0]]
        if len(col) > 1:            # a tile whose heads read other groups
            head = jax.lax.broadcasted_iota(jnp.int32, (n, lanes), 1) \
                // head_dim
            for j, g in enumerate(groups[1:], 1):
                out = jnp.where(head == j, col[g], out)
        return out

    held = None
    for t, groups in enumerate(tile_groups):
        if groups != held:
            bn, cn = columns(b_ref, groups), columns(c_ref, groups)
            held = groups
        s = s_ref[t] * da_ref[t:t + 1, :] + xdt_ref[t:t + 1, :] * bn
        s_out[t] = s
        y_out[t:t + 1, :] = jnp.sum(s * cn, axis=0, keepdims=True)


def ssm_update(state, rows, x, dt, a, bm, cm):
    """``ssm_step_xla`` as ONE Pallas call: grid over the batch rows,
    each step moving one slot's whole [H/k, N, k P] state through VMEM,
    the state aliased in and out so that rows the grid does not name are
    left untouched.  ``rows`` rides scalar prefetch and picks each
    step's state row, like the page table picks pages."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, p = x.shape
    tiles, n, lanes = state.shape[1:]
    k = h // tiles
    g = bm.shape[1]
    # a head's decay and input over its channels' lanes: [B, T, k P]
    da = jnp.repeat(jnp.exp(dt * a[None, :]), p, axis=-1) \
        .reshape(b, tiles, lanes)
    xdt = (x * dt[:, :, None]).reshape(b, tiles, lanes)

    def row_state(i, rows):
        return (rows[i], 0, 0, 0)

    def row(i, rows):
        return (i, 0, 0)

    kernel = functools.partial(
        _ssm_update_kernel, head_dim=p,
        tile_groups=tuple(tuple((t * k + j) // (h // g) for j in range(k))
                          for t in range(tiles)))
    block = tiles * n * lanes * 4
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[pl.BlockSpec((None, tiles, n, lanes), row_state),
                  pl.BlockSpec((None, tiles, lanes), row),
                  pl.BlockSpec((None, tiles, lanes), row),
                  pl.BlockSpec((None, g, n), row),
                  pl.BlockSpec((None, g, n), row)],
        out_specs=[pl.BlockSpec((None, tiles, n, lanes), row_state),
                   pl.BlockSpec((None, tiles, lanes), row)])
    new_state, y = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((b, tiles, lanes), jnp.float32)],
        # operand 0 is ``rows``; the state is operand 1
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(32 << 20, 6 * block)),
        interpret=_interpret(),
        name="ssm_update",
    )(rows.astype(jnp.int32), state, da, xdt, bm, cm)
    return new_state, y.reshape(b, h, p)


def ssm_step(state, rows, x, dt, a, bm, cm):
    """One token of the recurrence: the kernel where Mosaic kernels run
    (a TPU, or the interpreter in CPU tests), XLA elsewhere; the lane is
    counted where it is traced."""
    from ..utils import monitor
    use_kernel = _unsharded_kernels_on()
    monitor.incr("pallas.ssm_update.kernel" if use_kernel
                 else "pallas.ssm_update.xla_lane")
    if use_kernel:
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
    out in its stored layout (``[B, H/k, N, k P]``: unpacked once on the
    way in and packed once on the way out, the sub-chunks between in
    ``[B, H, P, N]``), in sub-chunks of ``chunk`` where ``L`` is longer.
    ``bm``/``cm`` are [B, L, G, N].  Returns (s_out, y [B, L, H, P]) with
    ``y = S C`` at every position (the caller adds ``D x``)."""
    b, length, h, p = x.shape
    per_tile = h // s_in.shape[1]
    s_in = unpack_state(s_in, h)
    bm, cm = _heads_of_groups(bm, h), _heads_of_groups(cm, h)
    if length <= chunk:
        s_out, y = _ssd_chunk(s_in, x, dt, a, bm, cm)
        return pack_state(s_out, per_tile), y
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
    return pack_state(s_out, per_tile), y[:, :length]
