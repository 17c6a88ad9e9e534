"""Latent (low-rank) attention over pages: the paged latent decode kernel
and its XLA lane.

A latent layer caches ONE row a token, ``[c, k_r]``: the normed latent
``c`` (``kv_lora_rank`` wide) and the rotated key ``k_r`` all heads share
(``qk_rope_head_dim`` wide).  There are no kv heads and no V: a head's
key is ``[W_uk,h c, k_r]`` and its value ``W_uv,h c``.  Decoding uses
the ABSORBED form, in which the per-head matrices move onto the query
and the output and every head reads the same row:

    score_h,t = (q~_h . c_t + q_r,h . k_r,t) * scale,  q~_h = W_uk,h^T q_nope,h
    o~_h = sum_t p_h,t c_t,                            o_h = W_uv,h o~_h

so a token's row is at once the key of every head (all of it) and the
value of every head (its first ``v_width`` lanes).

**The page.**  ``latent_row_lanes(width)``: a row lives padded to whole
128-lane tiles (576 -> 640, zeros in the last lanes), so a page is a
``[page_size, lanes]`` matrix the kernel's DMA moves as it stands and the
value is a lane-aligned slice of the key.  The query is padded alike
(zeros meet zeros).

**The kernel** (``mla_decode_attention``) is ``_paged_decode_kernel``'s
walk with one pool: grid over rows, the pool stays in HBM, a step copies
``group`` of the row's live pages into one of two VMEM buffers through
the scalar-prefetched page table while the step before computes; the
loop runs ``ceil(live / group)`` times.  A step's scores are ``[H, c] =
q [H, lanes] . rows[c, lanes]^T`` — every head against every row, one
MXU product, K and V read from the same buffer once.

The XLA lane (``mla_decode_xla``) is the same absorbed arithmetic
through the blocked online softmax of every XLA paged read: the kernel's
reference, the lane of CPUs and partitioned programs.  Which lane a
read took is decided where the op is traced and counted there
(``pallas.mla_decode.kernel`` / ``pallas.mla_decode.xla_lane``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import (NEG_INF, _interpret, _mxu_f32,
                              _unsharded_kernels_on)

#: bytes of latent rows one kernel step streams.  A step costs ~0.4 us
#: whatever it holds and computes its whole buffer whatever part of it is
#: live.  Measured on the chip at rows of 640 bfloat16 lanes
#: (docs/perf/ubench_mla_decode.pr34.log): 8, 16 and 32 pages a step at
#: contexts of 1 k, 8 k and 16 k, each faster than the one before; 64,
#: 128 and 256 at 1 k ALONE, where 64 pages are one step and the fastest;
#: end to end, the cell served 13.5 % more tokens at 64 than at 16.  The
#: sweep past 32 at 8 k and 16 k is owed (ROADMAP 2a) before this is
#: called settled.
_STEP_BYTES = 2 << 20


def latent_row_lanes(width):
    """Lanes a cached latent row of ``width`` values occupies: whole
    128-lane tiles."""
    return -(-int(width) // 128) * 128


def mla_decode_pages_per_step(page_size, lanes, itemsize):
    """How many pages one step of the latent decode kernel streams, or 0
    where it cannot host the pool (rows that are not whole lane tiles,
    pages that are not whole sublane tiles of the pool's type) and the
    XLA lane reads it.  A rule on what the call can see."""
    if lanes % 128 or page_size % (32 // itemsize):
        return 0
    g = max(1, _STEP_BYTES // (page_size * lanes * itemsize))
    return 1 << (g.bit_length() - 1)


def _mla_decode_kernel(pt_ref, off_ref, q_ref, pool_hbm, o_ref, buf, sem,
                       slot_ref, *, scale, page_size, group, v_width):
    """One row of a single-token latent decode: the row's LIVE pages,
    ``group`` of them a step, every head at once against the same rows
    (see the module's text; the copies' choreography is
    ``_paged_decode_kernel``'s)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    rows = pl.num_programs(0)
    h = q_ref.shape[0]
    cols = group * page_size

    def live_pages(row):
        return off_ref[row] // page_size + 1

    def copies(row, g, slot, act):
        first = g * group

        def one(j, carry):
            page = pt_ref[row, first + j]
            at = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            act(pltpu.make_async_copy(pool_hbm.at[page], buf.at[slot, at],
                                      sem.at[slot]))
            return carry
        jax.lax.fori_loop(0, jnp.minimum(live_pages(row) - first, group),
                          one, 0)

    @pl.when(b == 0)
    def _first_row():
        # what a buffer holds past a step's live pages meets a
        # probability of exactly 0: it has to be finite
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        copies(0, 0, 0, lambda c: c.start())

    off = off_ref[b]
    n_groups = (live_pages(b) + group - 1) // group
    slot0 = slot_ref[0]
    q = q_ref[...]
    tok = jax.lax.broadcasted_iota(jnp.int32, (h, cols), 1)

    def step(g, carry):
        m_prev, l_prev, acc = carry
        slot = (slot0 + g) % 2
        last = g == n_groups - 1
        nxt_row = jnp.where(last, b + 1, b)

        @pl.when(nxt_row < rows)
        def _prefetch():
            copies(jnp.minimum(nxt_row, rows - 1),
                   jnp.where(last, 0, g + 1), 1 - slot,
                   lambda c: c.start())

        copies(b, g, slot, lambda c: c.wait())
        kv = buf[slot]
        s = _mxu_f32(q, kv, 1) * scale
        s = jnp.where(g * cols + tok <= off, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = _mxu_f32(p.astype(kv.dtype), kv[:, :v_width], 0)
        return m_new, l_new, alpha * acc + pv

    _, l, acc = jax.lax.fori_loop(
        0, n_groups, step,
        (jnp.full((h, 1), NEG_INF, jnp.float32),
         jnp.zeros((h, 1), jnp.float32),
         jnp.zeros((h, v_width), jnp.float32)))
    slot_ref[0] = (slot0 + n_groups) % 2
    o_ref[...] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "group", "v_width",
                                             "interpret"))
def _mla_decode_call(q, pool, page_table, offsets, *, scale, group, v_width,
                     interpret):
    """``mla_decode_attention`` at a fixed step size: a program of its
    own inside the caller's, so the layers of a model lower ONE kernel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, lanes = q.shape
    psz = pool.shape[1]
    h_pad = -(-h // 16) * 16
    q = jnp.pad(q, ((0, 0), (0, h_pad - h), (0, 0)))
    kernel = functools.partial(_mla_decode_kernel, scale=scale,
                               page_size=psz, group=group, v_width=v_width)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[pl.BlockSpec((None, h_pad, lanes),
                               lambda bi, pt, off: (bi, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, h_pad, v_width),
                               lambda bi, pt, off: (bi, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, group * psz, lanes), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)])
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h_pad, v_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="mla_decode",
    )(page_table.astype(jnp.int32), offsets.astype(jnp.int32), q, pool)
    return out[:, :h]


def mla_decode_attention(q, pool, page_table, offsets, v_width, scale):
    """Single-token absorbed latent attention over a paged latent pool.

    q: [B, H, lanes] this step's absorbed queries ``[q~_h, q_r,h, 0]`` in
    the pool's type; pool: [P, page_size, lanes] latent rows ``[c, k_r,
    0]``; page_table: int32 [B, N]; offsets: int32 [B] — row b attends
    positions <= offsets[b] (its freshly written row included).
    Returns ``o~`` [B, H, v_width]: each head's probability-weighted sum
    of the rows' first ``v_width`` lanes.  The caller asks
    ``mla_decode_pages_per_step`` first."""
    group = mla_decode_pages_per_step(pool.shape[1], pool.shape[2],
                                      pool.dtype.itemsize)
    while group > page_table.shape[1]:
        group //= 2
    if not group:
        raise ValueError(
            f"latent decode kernel: pages {tuple(pool.shape[1:])} of "
            f"{pool.dtype} are not whole tiles; the caller reads such a "
            "pool by the XLA lane")
    return _mla_decode_call(q, pool, page_table, offsets,
                            scale=float(scale), group=group,
                            v_width=int(v_width), interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("v_width", "scale"))
def mla_decode_xla(q, pool, page_table, offsets, *, v_width, scale):
    """The XLA lane of ``mla_decode_attention``: the same absorbed
    arithmetic as the blocked online softmax every XLA paged read is
    (``incubate.nn.functional._blocked_attend``) — one shared "kv head"
    whose key is a gathered row and whose value the row's first
    ``v_width`` lanes, every query head a member of its group."""
    from ..incubate.nn.functional import _block_pages, _blocked_attend
    b, h, lanes = q.shape
    psz = pool.shape[1]
    kb, one_block = _block_pages(b, h, 1, page_table.shape[1], psz)

    def gather(phys):
        rows = pool[phys].reshape(b, kb * psz, 1, lanes)
        return rows, rows[..., :v_width]

    cdt = jnp.promote_types(q.dtype, pool.dtype)
    return _blocked_attend(
        q.astype(cdt).reshape(b, 1, 1, h, lanes), offsets.astype(jnp.int32),
        page_table, gather, s=1, d_v=v_width, psz=psz, kb=kb,
        one_block=one_block, window=None, sc=scale, cdt=cdt,
        out_dtype=q.dtype)[:, 0]


def mla_decode(q, pool, page_table, offsets, v_width, scale, lane=None):
    """``o~`` of a single-token latent read by the kernel where it hosts
    the pool here, else by the XLA lane; counted where it is traced.
    ``lane`` forces ``"kernel"`` or ``"xla"``."""
    from ..utils import monitor
    use_kernel = lane == "kernel" if lane is not None else (
        _unsharded_kernels_on() and q.dtype == pool.dtype
        and mla_decode_pages_per_step(pool.shape[1], pool.shape[2],
                                      pool.dtype.itemsize) > 0)
    monitor.incr("pallas.mla_decode.kernel" if use_kernel
                 else "pallas.mla_decode.xla_lane")
    if use_kernel:
        return mla_decode_attention(q, pool, page_table, offsets, v_width,
                                    scale)
    return mla_decode_xla(q, pool, page_table, offsets,
                          v_width=int(v_width), scale=float(scale))
