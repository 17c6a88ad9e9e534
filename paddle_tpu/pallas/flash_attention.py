"""Flash attention for TPU.

Reference capability: FlashAttention-2 via dynloaded CUDA lib (reference:
paddle/phi/kernels/gpu/flash_attn_kernel.cu:203 → phi::dynload::flash_attn_fwd,
backward at paddle/phi/kernels/gpu/flash_attn_grad_kernel.cu; dropout args at
flash_attn_kernel.cu:203; varlen variant at incubate/nn/functional/
variable_length_memory_efficient_attention.py).  TPU-native realization:
three Pallas kernels — forward, dK/dV, dQ — over (q tile, key tile)
products with an online softmax in fp32 scratch accumulators; the
backward recomputes the probabilities per tile from the saved logsumexp,
never an O(S^2) materialization.  A tile's products take their operands
in the type they arrive in and accumulate in float32 (``_mxu``).

How a tile's operands reach VMEM is a rule on the call's shapes
(``_walk_vmem_bytes``), not an argument:

- **the walk**: a grid step is one (head, q block) — for dK/dV one (kv
  head, key block) — and holds the OTHER side of the products in VMEM for
  the whole head (K and V; for dK/dV the Q, dO, lse and delta of the
  q-heads that share the kv head), fetched once a head.  A loop inside
  the kernel counts that side's tiles from the first to the last live
  one: under a causal mask a tile past the diagonal costs neither a step
  nor a fetch, and only the tiles that straddle the diagonal build the
  position mask.  Every call whose head fits the VMEM budget walks.
- **the streamed grid**: a grid step is one (head, q block, key block),
  the key blocks (dK/dV: the q blocks) stream through the innermost grid
  axis, double-buffered by the Mosaic pipeline.  What the walk cannot
  host takes it: an ``[S, S]`` mask, which is streamed by blocks, and a
  head too long for the budget.

Feature coverage (all composable, fwd AND bwd):

- **causal** masking: the walk's loop ends at the diagonal; the streamed
  grid skips dead blocks (clamped index maps dedupe the skipped fetches).
- **attention dropout** on the probabilities via a counter-based in-kernel
  PRNG (position+seed hash) — the identical keep-mask is regenerated in the
  backward kernels, so no O(S^2) mask is ever materialized.
- **additive/boolean masks** of shape [B|1, H|1, S, S], streamed block-wise
  through the grid (the analog of the reference's attn_mask path; such a
  call never walks).
- **segment ids** [B, S]: packed-varlen attention — tokens attend only
  within their segment (the TPU-native replacement for the reference's
  cu_seqlens varlen kernels; padding is just a dedicated segment id).
- **grouped-query attention**: K/V carry num_kv_heads < num_heads and the
  kernels index the shared K/V head directly (q_head // n_rep) in the
  BlockSpecs — K/V HBM traffic stays at num_kv_heads scale, never
  materializing repeated heads (reference keeps kv heads distinct in
  fusion/gpu/masked_multihead_attention.cu).

Layout: the public op takes [batch, seq, heads, head_dim] (the reference's
flash-attn layout); internally the kernels run on [batch*heads, seq, d] so
the block's trailing two dims are (seq_block, d) — Mosaic requires the last
two block dims to be (8k, 128k) or equal to the array dims, which a
squeezed head dim in second-to-last position violates.  From [B, S, H, D]
that is one XLA transpose each way around every call; ``head_major=True``
([B, H, S, D], what the models hand over) makes it a free reshape.

Falls back to a fused XLA attention for shapes that don't tile (seq not a
multiple of 128, head_dim > 256, mask shapes outside [B|1, H|1, S, S]).
On CPU the Pallas path can be exercised in interpreter mode (set
``PADDLE_TPU_PALLAS_INTERPRET=1``) — that is how CI tests the kernels
without a TPU.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dispatch import apply_op
from ..core.tensor import Tensor
from ..core import state as _state

NEG_INF = -1e30


def _on_tpu():
    return jax.devices()[0].platform == "tpu"


def _interpret():
    """``PADDLE_TPU_PALLAS_INTERPRET=1`` runs the kernels in the Pallas
    interpreter — how the CPU tests exercise them.  On a TPU it would
    replace every Mosaic kernel by its emulation while the program
    still reports the chip, so there it is an error."""
    on = os.environ.get("PADDLE_TPU_PALLAS_INTERPRET", "") == "1"
    if on and _on_tpu():
        raise RuntimeError(
            "PADDLE_TPU_PALLAS_INTERPRET=1 on a TPU: the Pallas kernels "
            "would run in the interpreter instead of on the chip; unset "
            "it (it is for CPU tests)")
    return on


def _unsharded_kernels_on():
    """Gate for the kernels that carry no ``shard_map`` of their own
    (paged decode here; RMS norm, rope, Adam in ``fused.py``): on a TPU
    (or in the interpreter, for CPU tests) and not inside a program
    GSPMD partitions — Mosaic kernels cannot be partitioned
    automatically, so under a multi-device mesh the XLA forms run."""
    from ..distributed.mesh import gspmd_mesh
    return (_on_tpu() or _interpret()) and gspmd_mesh() is None


# ------------------------------------------------------------------
# XLA fallback (fused by XLA; used on CPU, for odd shapes)
# ------------------------------------------------------------------

def _xla_attention(q, k, v, attn_mask=None, causal=False, scale=None,
                   dropout=0.0, dropout_key=None, segment_ids=None,
                   head_major=False):
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    h_axis = 1 if head_major else 2
    if k.shape[h_axis] != q.shape[h_axis]:   # GQA: broadcast kv heads
        n_rep = q.shape[h_axis] // k.shape[h_axis]
        k = jnp.repeat(k, n_rep, axis=h_axis)
        v = jnp.repeat(v, n_rep, axis=h_axis)
    eq = "bhqd,bhkd->bhqk" if head_major else "bqhd,bkhd->bhqk"
    logits = jnp.einsum(eq, q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((s_q, s_k), jnp.bool_), k=s_k - s_q)
        logits = jnp.where(mask, logits, NEG_INF)
    if segment_ids is not None:
        seg = segment_ids.astype(jnp.int32)
        same = seg[:, None, :, None] == seg[:, None, None, :]
        logits = jnp.where(same, logits, NEG_INF)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            logits = jnp.where(attn_mask, logits, NEG_INF)
        else:
            logits = logits + attn_mask.astype(logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout), 0.0)
    eq_out = "bhqk,bhkd->bhqd" if head_major else "bhqk,bkhd->bqhd"
    return jnp.einsum(eq_out, probs.astype(v.dtype), v)


# ------------------------------------------------------------------
# shared kernel helpers
# ------------------------------------------------------------------

def _to_bh(x, head_major=False):
    """→ [B*H, S, D] (head-major for Mosaic-legal tiling).  From the
    [B, H, S, D] layout this is a FREE reshape; from [B, S, H, D] it is
    one XLA transpose each way — models keep attention activations
    head-major so the relayout fuses into the surrounding projection
    matmuls instead of standing alone around the pallas_call."""
    if head_major:
        b, h, s, d = x.shape
        return x.reshape(b * h, s, d)
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_bh(y, b, h, head_major=False):
    """[B*H, S, D] → [B, S, H, D] (or [B, H, S, D] when head_major)."""
    _, s, d = y.shape
    if head_major:
        return y.reshape(b, h, s, d)
    return y.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _apply_masks(s, *, causal, q_start, k_start, qseg=None, kseg=None,
                 mask=None, transposed=False):
    """Score masking shared by all three kernels: causal position mask,
    same-segment mask (varlen packing), additive attention mask.  ``s``
    is a tile ``[block_q, block_k]``, or ``[block_k, block_q]`` when
    ``transposed`` (the dKV kernel's)."""
    q_axis = 1 if transposed else 0
    if causal:
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                   q_axis)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                   1 - q_axis)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    if qseg is not None:
        # a column (block, 1) against a row (1, block) — no relayout
        s = jnp.where(qseg == kseg, s, NEG_INF)
    if mask is not None:
        s = s + (mask.T if transposed else mask)
    return s


def _dropout_uniform(seed, head, q_start, k_start, block_q, block_k,
                     transposed=False):
    """Counter-based stateless uniform(0,1) per (head, q_pos, k_pos):
    a murmur-style integer hash, regenerated identically in forward and
    backward so the same probabilities drop — no mask is materialized.
    ``[block_q, block_k]``, or ``[block_k, block_q]`` when transposed."""
    shape = (block_k, block_q) if transposed else (block_q, block_k)
    q_axis = 1 if transposed else 0
    qp = (q_start + jax.lax.broadcasted_iota(
        jnp.int32, shape, q_axis)).astype(jnp.uint32)
    kp = (k_start + jax.lax.broadcasted_iota(
        jnp.int32, shape, 1 - q_axis)).astype(jnp.uint32)
    x = qp * jnp.uint32(0x9E3779B1) + kp * jnp.uint32(0x85EBCA77)
    x = x ^ (seed.astype(jnp.uint32)
             + head.astype(jnp.uint32) * jnp.uint32(0x27D4EB2F))
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x2C1B3C6D)
    x = x ^ (x >> 12)
    x = x * jnp.uint32(0x297A2D39)
    x = x ^ (x >> 15)
    # via int32: Mosaic has no uint32 -> float32 cast, and the value
    # is < 2**24 so the signed reinterpretation is exact
    return (x >> 8).astype(jnp.int32).astype(jnp.float32) \
        * (1.0 / (1 << 24))


def _unpack_rest(rest, *, dropout, has_mask, has_seg):
    """Positional ref unpacking for the optional feature inputs."""
    idx = 0
    seed_ref = mask_ref = qseg_ref = kseg_ref = None
    if dropout > 0.0:
        seed_ref = rest[idx]
        idx += 1
    if has_mask:
        mask_ref = rest[idx]
        idx += 1
    if has_seg:
        qseg_ref, kseg_ref = rest[idx], rest[idx + 1]
        idx += 2
    return (seed_ref, mask_ref, qseg_ref, kseg_ref) + tuple(rest[idx:])


# ------------------------------------------------------------------
# One (q tile, key tile) of each of the three kernels.  The walk and
# the streamed grid differ in how a tile's operands reach VMEM and in
# who counts the tiles (a loop in the kernel, or the grid); what a tile
# computes is this, for both.
# ------------------------------------------------------------------

_NT = ((1,), (1,))       # a [m, d] · b [n, d]ᵀ
_NN = ((1,), (0,))       # a [m, n] · b [n, d]


def _mxu(a, b, contract):
    """One product on the matrix unit, accumulated in float32.  The
    operands go in the type they arrive in: two equal 16-bit tiles
    multiply exactly in one pass — which is all Mosaic's default made
    of float32 tiles too, so casting bfloat16 tiles up bought converts
    and VMEM, no precision.  float32 operands take the full-precision
    passes, said here and not left to a default."""
    if a.dtype != b.dtype:
        wide = jnp.promote_types(a.dtype, b.dtype)
        a, b = a.astype(wide), b.astype(wide)
    precision = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _tile_scores(q, k, *, scale, causal, q_start, k_start, qseg, kseg,
                 mask, transposed=False):
    """The tile's masked scores: ``[q rows, k rows]``, or the other way
    round when ``transposed``."""
    s = (_mxu(k, q, _NT) if transposed else _mxu(q, k, _NT)) * scale
    return _apply_masks(s, causal=causal, q_start=q_start, k_start=k_start,
                        qseg=qseg, kseg=kseg, mask=mask,
                        transposed=transposed)


def _tile_keep(seed_ref, head, dropout, where, q_rows, k_rows):
    """The tile's dropout keep-mask (None without dropout): the same
    hash of (head, q position, key position) in all three kernels."""
    if dropout <= 0.0:
        return None
    u = _dropout_uniform(seed_ref[0, 0], head, where["q_start"],
                         where["k_start"], q_rows, k_rows,
                         where.get("transposed", False))
    return u >= dropout


def _fwd_tile(q, k, v, m_scr, l_scr, acc_scr, *, seed_ref, head, dropout,
              **where):
    """The online softmax's update by one tile of keys: scratch (m, l,
    acc) carries the running max / normalizer / weighted sum of a q
    block across its tiles."""
    s = _tile_scores(q, k, **where)
    m_prev = m_scr[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    if where["mask"] is not None or where["qseg"] is not None:
        # fully-masked rows: m_new == NEG_INF makes exp(s-m) == 1 —
        # zero them so such rows emit 0, not garbage
        p = jnp.where(s > NEG_INF * 0.5, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    m_scr[:] = m_new
    l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
    keep = _tile_keep(seed_ref, head, dropout, where, *s.shape)
    if keep is not None:
        # softmax normalizes over the UNdropped probabilities; dropout
        # applies to what multiplies V
        p = jnp.where(keep, p, 0.0) / (1.0 - dropout)
    acc_scr[:] = alpha * acc_scr[:] + _mxu(p.astype(v.dtype), v, _NN)


def _bwd_tile_p_dp(q, k, v, do, lse, *, seed_ref, head, dropout, **where):
    """What both backward kernels recompute of a tile from the saved
    lse: p, p̃ (p after dropout: what multiplied V) and dp (already
    through the keep-mask) — ``[q rows, k rows]`` with ``lse`` a
    column, or transposed with ``lse`` a row."""
    transposed = where.get("transposed", False)
    s = _tile_scores(q, k, **where)
    p = jnp.exp(s - lse)
    if where["mask"] is not None or where["qseg"] is not None:
        # fully-masked rows: lse == NEG_INF would give exp(0) == 1
        p = jnp.where(s > NEG_INF * 0.5, p, 0.0)
    dp = _mxu(v, do, _NT) if transposed else _mxu(do, v, _NT)
    keep = _tile_keep(seed_ref, head, dropout, where, q.shape[0],
                      k.shape[0])
    if keep is None:
        return p, p, dp
    return (p, jnp.where(keep, p, 0.0) / (1.0 - dropout),
            jnp.where(keep, dp, 0.0) / (1.0 - dropout))


def _dkv_tile(q, k, v, do, lse, delta, dk_scr, dv_scr, **kw):
    """dKV works on the TRANSPOSED tile, ``[k rows, q rows]``: pᵀ and
    dsᵀ are what its two accumulating products contract, so every
    product is a plain one (no tile is transposed on the way to the
    matrix unit), and lse and delta are lane-dense rows ``[1, q rows]``."""
    p, p_v, dp = _bwd_tile_p_dp(q, k, v, do, lse, transposed=True, **kw)
    # dv += p̃ᵀ do;  dsᵀ = pᵀ * (dpᵀ - delta) * scale;  dk += dsᵀ q
    dv_scr[:] += _mxu(p_v.astype(do.dtype), do, _NN)
    ds = p * (dp - delta) * kw["scale"]
    dk_scr[:] += _mxu(ds.astype(q.dtype), q, _NN)


def _dq_tile(q, k, v, do, lse, delta, dq_scr, **kw):
    p, _, dp = _bwd_tile_p_dp(q, k, v, do, lse, **kw)
    ds = p * (dp - delta) * kw["scale"]
    dq_scr[:] += _mxu(ds.astype(k.dtype), k, _NN)


def _walk_tiles(step, first, clear_from, clear_to, last):
    """``step(t, diag)`` for the tiles ``first <= t < last`` of a walk:
    those in ``[clear_from, clear_to)`` lie wholly under the causal
    diagonal and skip the position mask (``diag`` False), the ones that
    straddle it build it."""
    def run(lo, hi, diag):
        def body(t, carry):
            step(t, diag)
            return carry
        jax.lax.fori_loop(lo, hi, body, 0)
    run(first, clear_from, True)
    run(clear_from, clear_to, False)
    run(clear_to, last, True)


def _key_walk(step, *, causal, q_start, block_q, block_k, seq):
    """The key tiles a q block sees (forward, dQ): all of them, or under
    a causal mask those up to its diagonal — first the ones wholly
    under it, then the straddling ones.  A tile past the diagonal costs
    neither a step nor a fetch."""
    if not causal:
        return _walk_tiles(step, 0, 0, seq // block_k, seq // block_k)
    clear = (q_start + 1) // block_k
    _walk_tiles(step, 0, 0, clear, (q_start + block_q - 1) // block_k + 1)


def _query_walk(step, *, causal, k_start, block_q, block_k, seq):
    """The q tiles that see a key block (dKV): from the first that
    reaches its diagonal; wholly under it from ``clear`` on."""
    n_q = seq // block_q
    if not causal:
        return _walk_tiles(step, 0, 0, n_q, n_q)
    clear = jnp.minimum((k_start + block_k + block_q - 2) // block_q, n_q)
    _walk_tiles(step, k_start // block_q, clear, n_q, n_q)


# ------------------------------------------------------------------
# Pallas forward.  Walk: grid (B*H, num_q), the head's K/V resident,
# key tiles counted by a loop in the kernel.  Streamed: grid (B*H,
# num_q, num_kv), a key tile a grid step.
# ------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, block_q, block_k,
                dropout, has_mask, has_seg, walk):
    from jax.experimental import pallas as pl

    (seed_ref, mask_ref, qseg_ref, kseg_ref,
     o_ref, lse_ref, m_scr, l_scr, acc_scr) = _unpack_rest(
        rest, dropout=dropout, has_mask=has_mask, has_seg=has_seg)

    n = pl.program_id(0)
    q_start = pl.program_id(1) * block_q

    def init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def finalize():
        l = jnp.maximum(l_scr[:], 1e-30)  # noqa: E741
        o_ref[:] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[:] = (m_scr[:] + jnp.log(l)).astype(lse_ref.dtype)

    def tile(k, v, k_start, diag, kseg, mask):
        _fwd_tile(q_ref[:], k, v, m_scr, l_scr, acc_scr,
                  seed_ref=seed_ref, head=n, dropout=dropout, scale=scale,
                  causal=diag, q_start=q_start, k_start=k_start,
                  qseg=qseg_ref[:] if has_seg else None, kseg=kseg,
                  mask=mask)

    if walk:
        def step(t, diag):
            at = pl.ds(pl.multiple_of(t * block_k, block_k), block_k)
            tile(k_ref[at, :], v_ref[at, :], t * block_k, diag,
                 kseg_ref[:, at] if has_seg else None, None)
        init()
        _key_walk(step, causal=causal, q_start=q_start, block_q=block_q,
                  block_k=block_k, seq=k_ref.shape[0])
        finalize()
        return

    j = pl.program_id(2)
    k_start = j * block_k
    pl.when(j == 0)(init)
    # Entire block above the causal diagonal contributes nothing: skip
    # the products (the clamped index map already spared the fetch)
    live = (q_start + block_q - 1 >= k_start) if causal else True

    @pl.when(live)
    def _compute():
        tile(k_ref[:], v_ref[:], k_start, causal,
             kseg_ref[:] if has_seg else None,
             mask_ref[:].astype(jnp.float32) if has_mask else None)

    pl.when(j == pl.num_programs(2) - 1)(finalize)


def _feature_specs(*, b, s, h, h_kv, block_q, block_k, dropout, mask, qseg,
                   kseg, q_axis, kv_axis, head_of, batch_of, causal,
                   grid_qi=None):
    """(in_specs, inputs) for the optional seed/mask/segment inputs of a
    streamed grid, shared by the three kernels.  head_of/batch_of map
    grid indices to the global q-head / batch; grid_qi (the dKV call's)
    maps grid indices to the (clamped) q block, and says that the
    kernel's tiles are transposed: its q side's segment ids are the
    row, its key side's the column."""
    from jax.experimental import pallas as pl

    specs, inputs = [], []
    if dropout > 0.0:
        specs.append(pl.BlockSpec((1, 1), lambda *g: (0, 0)))
        inputs.append(None)   # seed filled by caller
    if mask is not None:
        mb, mh = mask.shape[0], mask.shape[1]

        def mask_index(*g):
            bi = batch_of(*g) if mb > 1 else 0
            hi = head_of(*g) if mh > 1 else 0
            qi = grid_qi(*g) if grid_qi is not None else g[q_axis]
            j = g[kv_axis]
            if causal and grid_qi is None:
                j = jnp.minimum(j, (qi * block_q + block_q - 1) // block_k)
            return (bi, hi, qi, j)
        specs.append(pl.BlockSpec((None, None, block_q, block_k),
                                  mask_index))
        inputs.append(mask)
    if qseg is not None:
        def qseg_index(*g):
            qi = grid_qi(*g) if grid_qi is not None else g[q_axis]
            return (batch_of(*g), qi, 0)

        def kseg_index(*g):
            j = g[kv_axis]
            if causal and grid_qi is None:
                qi = g[q_axis]
                j = jnp.minimum(j, (qi * block_q + block_q - 1) // block_k)
            return (batch_of(*g), 0, j)
        if grid_qi is None:
            specs.append(pl.BlockSpec((None, block_q, 1), qseg_index))
            specs.append(pl.BlockSpec((None, 1, block_k), kseg_index))
            inputs.extend([qseg, kseg])
        else:
            # the same ids: kseg [B, 1, S] is the row, qseg the column
            def swapped(index):
                def at(*g):
                    bi, x, y = index(*g)
                    return (bi, y, x)
                return at
            specs.append(pl.BlockSpec((None, 1, block_q),
                                      swapped(qseg_index)))
            specs.append(pl.BlockSpec((None, block_k, 1),
                                      swapped(kseg_index)))
            inputs.extend([kseg, qseg])
    return specs, inputs


def _causal_kv_spec(block_q, block_k, d, q_axis, kv_axis, causal,
                    kv_row):
    """kv BlockSpec for a (bh, …) grid: on causal, beyond-diagonal kv
    fetches clamp to the diagonal block (Mosaic dedupes the repeated
    index, so the pl.when-skipped steps cost no HBM traffic).
    kv_row maps the leading grid index to the K/V head row (GQA)."""
    from jax.experimental import pallas as pl

    def index(*g):
        j = g[kv_axis]
        if causal:
            i = g[q_axis]
            j = jnp.minimum(j, (i * block_q + block_q - 1) // block_k)
        return (kv_row(g[0]), j, 0)
    return pl.BlockSpec((None, block_k, d), index)


def _seed_and_segment_specs(dropout, seed, qseg, kseg, qseg_spec, kseg_spec):
    """(in_specs, inputs) of the optional inputs a walk hosts."""
    from jax.experimental import pallas as pl

    specs, inputs = [], []
    if dropout > 0.0:
        specs.append(pl.BlockSpec((1, 1), lambda *g: (0, 0)))
        inputs.append(seed)
    if qseg is not None:
        specs += [qseg_spec, kseg_spec]
        inputs += [qseg, kseg]
    return specs, inputs


def _q_block_specs(b, h, h_kv, s, d, block_q, block_k, *, walk, causal,
                   dropout, mask, qseg, kseg, seed):
    """What the forward and the dQ call share: a grid step is a q block
    of one head.  → (grid, q/o spec, lse/delta spec, k/v spec, the
    optional inputs' specs, those inputs).  Walk: grid (B*H, num_q),
    the head's K/V resident; streamed: grid (B*H, num_q, num_kv)."""
    from jax.experimental import pallas as pl

    n_rep = h // h_kv

    def kv_row(n):
        return (n // h) * h_kv + (n % h) // n_rep
    qo_spec = pl.BlockSpec((None, block_q, d), lambda n, i, *_: (n, i, 0))
    lse_spec = pl.BlockSpec((None, block_q, 1), lambda n, i, *_: (n, i, 0))
    if walk:
        grid = (b * h, s // block_q)
        kv_spec = pl.BlockSpec((None, s, d), lambda n, i: (kv_row(n), 0, 0))
        feat_specs, feat_inputs = _seed_and_segment_specs(
            dropout, seed, qseg, kseg,
            pl.BlockSpec((None, block_q, 1), lambda n, i: (n // h, i, 0)),
            pl.BlockSpec((None, 1, s), lambda n, i: (n // h, 0, 0)))
    else:
        grid = (b * h, s // block_q, s // block_k)
        kv_spec = _causal_kv_spec(block_q, block_k, d, q_axis=1, kv_axis=2,
                                  causal=causal, kv_row=kv_row)
        feat_specs, feat_inputs = _feature_specs(
            b=b, s=s, h=h, h_kv=h_kv, block_q=block_q, block_k=block_k,
            dropout=dropout, mask=mask, qseg=qseg, kseg=kseg,
            q_axis=1, kv_axis=2, head_of=lambda *g: g[0] % h,
            batch_of=lambda *g: g[0] // h, causal=causal)
        if dropout > 0.0:
            feat_inputs[0] = seed
    return grid, qo_spec, lse_spec, kv_spec, feat_specs, feat_inputs


def _call_walk_bytes(q, k, head_major, has_mask, has_seg):
    """``_walk_vmem_bytes`` of a call, from its operands."""
    _, h, h_kv, s, d = _dims(q, k, head_major)
    return _walk_vmem_bytes(s, d, max(q.dtype.itemsize, k.dtype.itemsize),
                            h // h_kv, has_mask, has_seg)


def _dims(q, k, head_major):
    """(batch, heads, kv heads, seq, head size) of a call's q and k."""
    if head_major:
        b, h, s, d = q.shape
        return b, h, k.shape[1], s, d
    b, s, h, d = q.shape
    return b, h, k.shape[2], s, d


def _pallas_flash_fwd(q, k, v, mask=None, qseg=None, kseg=None, seed=None,
                      *, causal, scale, block_q, block_k, dropout=0.0,
                      head_major=False):
    """q: [B, S, H, D] (or [B, H, S, D] when head_major), k/v likewise
    with H_kv heads → (out in q's layout, lse [B, H, S, 1] fp32).
    mask: [B|1, H|1, S, S] additive fp32; qseg/kseg: [B, S, 1]/[B, 1, S]
    int32; seed: [1,1] uint32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, h_kv, s, d = _dims(q, k, head_major)
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    has_mask, has_seg = mask is not None, qseg is not None
    walk = _call_walk_bytes(q, k, head_major, has_mask, has_seg)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               dropout=dropout, has_mask=has_mask,
                               has_seg=has_seg, walk=bool(walk))
    grid, qo_spec, lse_spec, kv_spec, feat_specs, feat_inputs = \
        _q_block_specs(b, h, h_kv, s, d, block_q, block_k, walk=walk,
                       causal=causal, dropout=dropout, mask=mask,
                       qseg=qseg, kseg=kseg, seed=seed)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[qo_spec, kv_spec, kv_spec] + feat_specs,
        out_specs=[qo_spec, lse_spec],
        out_shape=[jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
                   jax.ShapeDtypeStruct((b * h, s, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=_interpret(),
        name="flash_fwd",
        **_traced(walk, block_q, block_k),
    )(_to_bh(q, head_major), _to_bh(k, head_major),
      _to_bh(v, head_major), *feat_inputs)
    return _from_bh(out, b, h, head_major), lse.reshape(b, h, s, 1)


# ------------------------------------------------------------------
# Pallas backward: a dK/dV kernel (a key block against the q tiles
# that see it) and a dQ kernel (a q block against its key tiles).
# Walk: the q side (dKV: Q, dO, lse, delta of the heads that share the
# kv head) or the key side (dQ: K, V) is resident and a loop counts the
# tiles; streamed: they are the grid's innermost axis.
# ------------------------------------------------------------------

def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    *rest, scale, causal, block_q, block_k, dropout,
                    has_mask, has_seg, h, h_kv, num_q, walk):
    """p is recomputed per tile from the saved lse; GQA heads sharing
    this kv head accumulate into the same scratch."""
    from jax.experimental import pallas as pl

    (seed_ref, mask_ref, qseg_ref, kseg_ref,
     dk_ref, dv_ref, dk_scr, dv_scr) = _unpack_rest(
        rest, dropout=dropout, has_mask=has_mask, has_seg=has_seg)

    n = pl.program_id(0)   # b * h_kv + kv_head
    k_start = pl.program_id(1) * block_k
    n_rep = h // h_kv

    def init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def finalize():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)

    def tile(q, do, lse, delta, head, q_start, diag, qseg, mask):
        _dkv_tile(q, k_ref[:], v_ref[:], do, lse, delta, dk_scr, dv_scr,
                  seed_ref=seed_ref, head=head, dropout=dropout,
                  scale=scale, causal=diag, q_start=q_start,
                  k_start=k_start, qseg=qseg,
                  kseg=kseg_ref[:] if has_seg else None, mask=mask)

    if walk:
        def head_walk(rep, carry):
            def step(t, diag):
                at = pl.ds(pl.multiple_of(t * block_q, block_q), block_q)
                tile(q_ref[rep, at, :], do_ref[rep, at, :],
                     lse_ref[rep, :, at], delta_ref[rep, :, at],
                     n * n_rep + rep, t * block_q, diag,
                     qseg_ref[:, at] if has_seg else None, None)
            _query_walk(step, causal=causal, k_start=k_start,
                        block_q=block_q, block_k=block_k,
                        seq=q_ref.shape[1])
            return carry
        init()
        jax.lax.fori_loop(0, n_rep, head_walk, 0)
        finalize()
        return

    r = pl.program_id(2)   # rep * num_q + q block (innermost)
    q_start = (r % num_q) * block_q
    # global q-head id (matches the forward's grid index 0) for dropout
    head = (n // h_kv) * h + (n % h_kv) * n_rep + r // num_q
    pl.when(r == 0)(init)
    live = (q_start + block_q - 1 >= k_start) if causal else True

    @pl.when(live)
    def _compute():
        tile(q_ref[:], do_ref[:], lse_ref[:], delta_ref[:], head, q_start,
             causal, qseg_ref[:] if has_seg else None,
             mask_ref[:].astype(jnp.float32) if has_mask else None)

    pl.when(r == pl.num_programs(2) - 1)(finalize)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   *rest, scale, causal, block_q, block_k, dropout,
                   has_mask, has_seg, walk):
    from jax.experimental import pallas as pl

    (seed_ref, mask_ref, qseg_ref, kseg_ref,
     dq_ref, dq_scr) = _unpack_rest(
        rest, dropout=dropout, has_mask=has_mask, has_seg=has_seg)

    n = pl.program_id(0)
    q_start = pl.program_id(1) * block_q

    def init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def finalize():
        dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)

    def tile(k, v, k_start, diag, kseg, mask):
        _dq_tile(q_ref[:], k, v, do_ref[:], lse_ref[:], delta_ref[:],
                 dq_scr, seed_ref=seed_ref, head=n, dropout=dropout,
                 scale=scale, causal=diag, q_start=q_start,
                 k_start=k_start, qseg=qseg_ref[:] if has_seg else None,
                 kseg=kseg, mask=mask)

    if walk:
        def step(t, diag):
            at = pl.ds(pl.multiple_of(t * block_k, block_k), block_k)
            tile(k_ref[at, :], v_ref[at, :], t * block_k, diag,
                 kseg_ref[:, at] if has_seg else None, None)
        init()
        _key_walk(step, causal=causal, q_start=q_start, block_q=block_q,
                  block_k=block_k, seq=k_ref.shape[0])
        finalize()
        return

    j = pl.program_id(2)   # kv block (innermost)
    k_start = j * block_k
    pl.when(j == 0)(init)
    live = (q_start + block_q - 1 >= k_start) if causal else True

    @pl.when(live)
    def _compute():
        tile(k_ref[:], v_ref[:], k_start, causal,
             kseg_ref[:] if has_seg else None,
             mask_ref[:].astype(jnp.float32) if has_mask else None)

    pl.when(j == pl.num_programs(2) - 1)(finalize)


def _pallas_flash_bwd(q, k, v, out, lse, dout, mask=None, qseg=None,
                      kseg=None, seed=None, *, causal, scale, block_q,
                      block_k, dropout=0.0, head_major=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, h_kv, s, d = _dims(q, k, head_major)
    n_rep = h // h_kv
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    has_mask, has_seg = mask is not None, qseg is not None
    walk = _call_walk_bytes(q, k, head_major, has_mask, has_seg)
    # delta_i = rowsum(dO_i * O_i): cheap elementwise+reduce, XLA fuses it
    eq = "bhsd,bhsd->bhs" if head_major else "bshd,bshd->bhs"
    delta = jnp.einsum(eq, dout.astype(jnp.float32),
                       out.astype(jnp.float32)).reshape(b * h, s, 1)
    q3, do3 = _to_bh(q, head_major), _to_bh(dout, head_major)
    k3, v3 = _to_bh(k, head_major), _to_bh(v, head_major)
    lse3 = lse.reshape(b * h, s, 1)
    # the dKV kernel's tiles are transposed: lse and delta are rows there
    lse_row, delta_row = lse.reshape(b * h, 1, s), delta.reshape(b * h, 1, s)
    num_q = s // block_q
    common = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, dropout=dropout, has_mask=has_mask,
                  has_seg=has_seg, walk=bool(walk))

    # ---- dK/dV.  Walk: grid (b*h_kv, num_kv), the n_rep q-heads that
    # share the kv head resident.  Streamed: grid (b*h_kv, num_kv,
    # num_q*n_rep), those heads' q blocks through the innermost axis.
    kv_spec_q = pl.BlockSpec((None, block_k, d), lambda n, j, *_: (n, j, 0))
    if walk:
        grid_q = (b * h_kv, s // block_k)
        # rows n*n_rep … of [B*H, S, ·]: (b, kv head)'s q-heads
        qo_spec_q = pl.BlockSpec((n_rep, s, d), lambda n, j: (n, 0, 0))
        lse_spec_q = pl.BlockSpec((n_rep, 1, s), lambda n, j: (n, 0, 0))
        # the same ids: kseg [B, 1, S] is the q side's row, qseg
        # [B, S, 1] the key block's column
        feat_specs_q, feat_inputs_q = _seed_and_segment_specs(
            dropout, seed, kseg, qseg,
            pl.BlockSpec((None, 1, s), lambda n, j: (n // h_kv, 0, 0)),
            pl.BlockSpec((None, block_k, 1),
                         lambda n, j: (n // h_kv, j, 0)))
    else:
        grid_q = (b * h_kv, s // block_k, num_q * n_rep)

        def q_row(n, j, r):
            return (n // h_kv) * h + (n % h_kv) * n_rep + r // num_q

        def qi_clamped(n, j, r):
            i = r % num_q
            if causal:
                i = jnp.maximum(i, (j * block_k) // block_q)
            return i

        qo_spec_q = pl.BlockSpec(
            (None, block_q, d), lambda n, j, r: (q_row(n, j, r),
                                                 qi_clamped(n, j, r), 0))
        lse_spec_q = pl.BlockSpec(
            (None, 1, block_q), lambda n, j, r: (q_row(n, j, r), 0,
                                                 qi_clamped(n, j, r)))
        feat_specs_q, feat_inputs_q = _feature_specs(
            b=b, s=s, h=h, h_kv=h_kv, block_q=block_q, block_k=block_k,
            dropout=dropout, mask=mask, qseg=qseg, kseg=kseg,
            q_axis=2, kv_axis=1,
            head_of=lambda n, j, r: (n % h_kv) * n_rep + r // num_q,
            batch_of=lambda n, j, r: n // h_kv, causal=causal,
            grid_qi=qi_clamped)
        if dropout > 0.0:
            feat_inputs_q[0] = seed
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, h=h, h_kv=h_kv, num_q=num_q,
                          **common),
        grid=grid_q,
        in_specs=[qo_spec_q, kv_spec_q, kv_spec_q, qo_spec_q,
                  lse_spec_q, lse_spec_q] + feat_specs_q,
        out_specs=[kv_spec_q, kv_spec_q],
        out_shape=[jax.ShapeDtypeStruct((b * h_kv, s, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h_kv, s, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=_interpret(),
        name="flash_bwd_dkv",
        **_traced(walk, block_q, block_k),
    )(q3, k3, v3, do3, lse_row, delta_row, *feat_inputs_q)

    # ---- dQ: a q block against its key tiles
    grid, qo_spec, lse_spec, kv_spec, feat_specs, feat_inputs = \
        _q_block_specs(b, h, h_kv, s, d, block_q, block_k, walk=walk,
                       causal=causal, dropout=dropout, mask=mask,
                       qseg=qseg, kseg=kseg, seed=seed)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=grid,
        in_specs=[qo_spec, kv_spec, kv_spec, qo_spec, lse_spec, lse_spec]
        + feat_specs,
        out_specs=qo_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=_interpret(),
        name="flash_bwd_dq",
        **_traced(walk, block_q, block_k),
    )(q3, k3, v3, do3, lse3, delta, *feat_inputs)
    return (_from_bh(dq, b, h, head_major),
            _from_bh(dk, b, h_kv, head_major),
            _from_bh(dv, b, h_kv, head_major))


# ------------------------------------------------------------------
# custom VJP wiring
# ------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(7, 8, 9, 10, 11, 12, 13, 14))
def _flash_core(q, k, v, mask, qseg, kseg, seed, causal, scale, dropout,
                block_q, block_k, block_q_bwd=None, block_k_bwd=None,
                head_major=False):
    out, _ = _pallas_flash_fwd(q, k, v, mask, qseg, kseg, seed,
                               causal=causal, scale=scale, dropout=dropout,
                               block_q=block_q, block_k=block_k,
                               head_major=head_major)
    return out


def _flash_fwd_rule(q, k, v, mask, qseg, kseg, seed, causal, scale, dropout,
                    block_q, block_k, block_q_bwd=None, block_k_bwd=None,
                    head_major=False):
    out, lse = _pallas_flash_fwd(q, k, v, mask, qseg, kseg, seed,
                                 causal=causal, scale=scale,
                                 dropout=dropout, block_q=block_q,
                                 block_k=block_k, head_major=head_major)
    return out, (q, k, v, mask, qseg, kseg, seed, out, lse)


def _flash_bwd_rule(causal, scale, dropout, block_q, block_k,
                    block_q_bwd, block_k_bwd, head_major, res, dout):
    q, k, v, mask, qseg, kseg, seed, out, lse = res
    # the dkv/dq kernels prefer different block shapes than the forward
    # (autotuned separately under flash_attention.bwd)
    bq = block_q_bwd if block_q_bwd is not None else block_q
    bk = block_k_bwd if block_k_bwd is not None else block_k
    dq, dk, dv = _pallas_flash_bwd(
        q, k, v, out, lse, dout, mask, qseg, kseg, seed, causal=causal,
        scale=scale, dropout=dropout, block_q=bq, block_k=bk,
        head_major=head_major)
    # the mask gradient is NOT computed in-kernel; the public op only
    # routes non-trainable (stop_gradient) masks here — a learned additive
    # bias takes the XLA path, which differentiates it exactly
    dmask = jnp.zeros_like(mask) if mask is not None else None
    f0 = jax.dtypes.float0
    dqseg = np.zeros(qseg.shape, f0) if qseg is not None else None
    dkseg = np.zeros(kseg.shape, f0) if kseg is not None else None
    dseed = np.zeros(seed.shape, f0) if seed is not None else None
    return dq, dk, dv, dmask, dqseg, dkseg, dseed


_flash_core.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ------------------------------------------------------------------
# The rule: which calls walk, and with which tiles.  The only place a
# shape turns into a path or a block size.
# ------------------------------------------------------------------

# What a walk's resident operands may take of a v5e core's 128 MiB of
# VMEM, and what its tiles and temporaries get beside them.
_WALK_VMEM_BUDGET = 64 << 20
_WALK_WORK_BYTES = 16 << 20


def _walk_vmem_bytes(s, d, itemsize, n_rep, has_mask=False, has_seg=False):
    """VMEM the resident operands of a call's walk take, or 0 where the
    call takes the streamed grid.  A rule on what the call can see.

    The walk holds one side of the products in VMEM for a whole head
    and counts the other side's live tiles in a loop.  The largest
    resident set of the three kernels is dKV's: Q and dO (``[s, d]``)
    and lse and delta (float32 rows ``[1, s]``, which a tile of 8
    sublanes holds: 32 bytes a position) of the ``n_rep`` q-heads that
    share a kv head, and the q side's segment ids, such a row too,
    where there are any; the pipeline keeps two buffers of each.  A
    head that does not fit streams, and so does an ``[S, S]`` mask: no
    side of it can be held."""
    if has_mask:
        return 0
    row = s * 32
    held = n_rep * (2 * s * d * itemsize + 2 * row) \
        + (row if has_seg else 0)
    return 2 * held if 2 * held <= _WALK_VMEM_BUDGET else 0


def _traced(walk, block_q, block_k):
    """Counts one trace of a kernel by its path (``pallas.flash.resident``
    / ``pallas.flash.streamed``) and gives the ``pallas_call`` what the
    path needs beside the grid: a walk's VMEM limit — its resident
    bytes, and room for a dozen float32 tiles."""
    from jax.experimental.pallas import tpu as pltpu
    from ..utils import monitor

    monitor.incr("pallas.flash.resident" if walk
                 else "pallas.flash.streamed")
    if not walk:
        return {}
    work = max(_WALK_WORK_BYTES, 12 * block_q * block_k * 4)
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=walk + work)}


# (q block, key tile) a direction, from ``docs/perf/
# ubench_flash_train.pr35.log``: the cell's call (S 2,048, d 128,
# bfloat16) and the same tokens at S 4,096 and 8,192.
_WALK_BLOCKS = {"fwd": (1024, 1024), "bwd": (512, 512)}


def _fit(target, s):
    """``target`` (a power of two from 128 up) halved until it divides
    ``s`` (a multiple of 128: ``_supports_pallas``)."""
    block = target
    while s % block:
        block //= 2
    return block


def _pick_blocks(s, d, which="fwd", walk=False):
    """(q block, key block) of a tile for a direction: a sweep this
    process ran first (validated — a non-dividing entry would truncate
    the grid and leave rows unwritten), then the table.  The backward's
    two kernels prefer other shapes than the forward, so the two
    directions are swept and recorded separately; a walk's tiles are
    larger than a streamed step's, which a grid step's fetch bounds."""
    from .autotune import lookup
    cached = lookup(f"flash_attention.{which}", (s, d))
    if cached is not None and len(cached) == 2:
        bq, bk = int(cached[0]), int(cached[1])
        if 0 < bq <= s and 0 < bk <= s and s % bq == 0 and s % bk == 0:
            return bq, bk
    if walk:
        bq, bk = _WALK_BLOCKS[which]
        return _fit(bq, s), _fit(bk, s)
    block_q = 256 if s % 256 == 0 else 128
    block_k = 512 if s % 512 == 0 else block_q
    return min(block_q, s), min(block_k, s)


def _block_candidates(s):
    """The (q block, key block) pairs ``autotune_blocks`` times at
    sequence length ``s``: every pair of dividing powers of two from
    128 to 1,024 whose float32 score tile stays within 2 MiB."""
    sizes = [x for x in (128, 256, 512, 1024) if x <= s and s % x == 0]
    return [(bq, bk) for bq in sizes for bk in sizes
            if bq * bk * 4 <= 2 << 20]


def autotune_blocks(s, d, dtype=jnp.bfloat16, batch=1, heads=1):
    """Timed sweeps over ``_block_candidates`` for (seq, head_dim);
    records the winners for this process (reference: phi/kernels/
    autotune switch_autotune.h).  Forward and backward are swept
    SEPARATELY — the dkv/dq kernels prefer different shapes than the
    forward, and each direction's choice feeds its own key.  The calls
    take the path the rule gives them (a causal call of one head group:
    the walk wherever the head fits).  A candidate the chip refuses
    raises."""
    from . import autotune as at

    cands = _block_candidates(s)
    if not cands:
        return _pick_blocks(s, d)
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (batch, s, heads, d), dtype)
    sc = 1.0 / math.sqrt(d)

    def run_fwd(cfg):
        out = _flash_core(q, q, q, None, None, None, None, True, sc,
                          0.0, cfg[0], cfg[1], None, None, False)
        jax.block_until_ready(out)

    def run_bwd(cfg):
        # time the whole vjp with the FWD pinned to its chosen blocks;
        # cfg drives only the backward kernels
        def f(q_, k_, v_):
            return jnp.sum(_flash_core(
                q_, k_, v_, None, None, None, None, True, sc, 0.0,
                fwd_blocks[0], fwd_blocks[1], cfg[0], cfg[1],
                False).astype(jnp.float32))
        grads = jax.grad(f, argnums=(0, 1, 2))(q, q, q)
        jax.block_until_ready(grads)

    fwd_blocks = at.sweep("flash_attention.fwd", (s, d), cands, run_fwd)
    bwd_blocks = at.sweep("flash_attention.bwd", (s, d), cands, run_bwd)
    return fwd_blocks, bwd_blocks


# ------------------------------------------------------------------
# Paged decode attention: page-table-aware gather/masking for the
# serving engine's paged KV cache (serving/paged_kv.py)
# ------------------------------------------------------------------

_PAGED_STEP_BYTES = 512 * 1024


def paged_decode_pages_per_step(page_size, h_kv, d, itemsize):
    """How many pages one step of the paged decode kernel streams, or 0
    where the kernel cannot host the pool and the XLA gather lane reads
    it.  A rule on what the call can see, nothing else.

    The kernel reads a page as a lane-dense matrix ``[rows, 128]``: a
    row holds ``128 // d`` kv heads of one token side by side.  So ``d``
    divides the 128 lanes, the kv heads fill whole rows, and a page is
    whole sublane tiles (8 rows, in every pool type on the chip) — or
    the answer is 0.  A step moves about ``_PAGED_STEP_BYTES`` of K and
    as much of V, in whole pages, at least one, a power of two of them.
    """
    heads_a_row = 128 // d if d and 128 % d == 0 else 0
    if not heads_a_row or h_kv % heads_a_row \
            or (page_size * h_kv // heads_a_row) % 8:
        return 0
    g = max(1, _PAGED_STEP_BYTES // (page_size * h_kv * d * itemsize))
    return 1 << (g.bit_length() - 1)


def paged_pool_page_shape(page_size, h_kv, d, itemsize):
    """The shape a page has while its pool LIVES on the device
    (serving/paged_kv.py): the kernel's lane-dense ``(rows, 128)``, or
    ``(page_size, h_kv, d)`` as it is written.  The same kind of rule,
    on the same four numbers.

    A pool the kernel hosts whose heads are narrower than the 128 lanes
    lives as the kernel reads it: XLA keeps a 4-D array of such a head
    size in another layout than the kernel's view, and relays the whole
    pool on every way between the two.  At ``d = 128`` the 4-D array
    already is that view tile for tile (the reshape is a bitcast), and
    its page write, a scatter of whole ``[h_kv, 128]`` tiles, is the
    cheaper one: it stays.  A pool the kernel refuses stays too."""
    if d >= 128 or not paged_decode_pages_per_step(page_size, h_kv, d,
                                                   itemsize):
        return (page_size, h_kv, d)
    return (page_size * h_kv * d // 128, 128)


def _mxu_f32(lhs, rhs, rhs_contracts):
    """``lhs [m, k]`` times ``rhs`` (contracting its dim ``rhs_contracts``)
    accumulated in float32 with no bit of an operand dropped, whatever
    the process's default matmul precision.  Equal 16-bit types
    multiply exactly in one MXU pass; a float32 ``rhs`` takes the
    full-precision passes; a float32 ``lhs`` against a bfloat16 ``rhs``
    is the sum of its three bfloat16 terms, the rows of ONE pass."""
    def dot(a, precision):
        return jax.lax.dot_general(
            a, rhs, (((1,), (rhs_contracts,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
    if rhs.dtype == jnp.float32:
        return dot(lhs.astype(jnp.float32), jax.lax.Precision.HIGHEST)
    if lhs.dtype == rhs.dtype:
        return dot(lhs, jax.lax.Precision.DEFAULT)
    terms, rest = [], lhs.astype(jnp.float32)
    for _ in range(3):
        terms.append(rest.astype(rhs.dtype))
        rest = rest - terms[-1].astype(jnp.float32)
    out, m = dot(jnp.concatenate(terms, axis=0),
                 jax.lax.Precision.DEFAULT), lhs.shape[0]
    return out[:m] + out[m:2 * m] + out[2 * m:]


def _paged_decode_kernel(pt_ref, off_ref, q_ref, k_hbm, v_hbm, *rest,
                         scale, page_size, group, token_rows,
                         q_heads_a_row, quant, window=None):
    """One row of a single-token decode: the row's LIVE pages, ``group``
    of them a step, every kv head at once.

    The pools stay in HBM, a page a lane-dense matrix ``[page_size *
    token_rows, 128]``.  A step's pages are copied into one of two VMEM
    buffers by the kernel's own DMAs, one a page, addressed through the
    scalar-prefetched page table; while a step computes, the next one's
    copies are in flight — the row's next group or, after its last, the
    first group of the next row.  The loop runs ``ceil(live / group)``
    times with ``live = offset // page_size + 1``: a page past the row's
    offset costs neither a step nor a copy, an empty slot (offset 0)
    streams one page.

    A step's keys are one matrix ``[c, 128]``, ``c = group * page_size *
    token_rows`` rows ordered (token, row of the token), so the whole
    query group rides the MXU: scores ``[h, c] = q [h, 128] · kᵀ`` for
    every query head against every row, of which a mask keeps the row
    that holds the head's own kv head (row ``h // q_heads_a_row`` of the
    token; the caller put the head's query on that kv head's lanes,
    zeros beside it) and the positions up to the offset.  Probabilities
    of the rest are exactly 0, so ``p [h, c] · v [c, 128]`` holds the
    row's output on the same lanes.  Scores and the online softmax's
    statistics are float32, probabilities take the pool's type, the sum
    is float32 (``_mxu_f32``).  Quantized pools: a token's scale
    multiplies its columns of the scores (K) and of the probabilities
    (V); ``ks_ref`` / ``vs_ref`` hold the row's scales already laid out
    a column each.

    ``window`` (static; None: no lower bound, the program is what it was
    before windows existed) bounds the row's loop from below as well: it
    starts at the row's first in-window page, ``max(offset + 1 - window,
    0) // page_size``, the partial first page is masked by position, and
    the page table is read as a ring (logical page ``p`` at entry ``p %
    N``).  A page that fell out of the window costs neither a step nor a
    copy."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if quant:
        ks_ref, vs_ref, o_ref, kbuf, vbuf, sem, slot_ref = rest
    else:
        o_ref, kbuf, vbuf, sem, slot_ref = rest
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    h, lanes = q_ref.shape
    tokens = group * page_size
    page_rows = page_size * token_rows
    cols = group * page_rows

    def live_pages(row):
        return off_ref[row] // page_size + 1

    def first_page(row):
        return jnp.maximum(off_ref[row] + 1 - window, 0) // page_size

    def pages_to_read(row):
        # no window: the expression (and so the program) of before
        return live_pages(row) if window is None else \
            live_pages(row) - first_page(row)

    def wide(x):
        """int8 / fp8 values as bfloat16, which holds each of them."""
        return x.astype(jnp.bfloat16) if x.dtype.itemsize == 1 else x

    def copies(row, g, slot, act):
        """``act`` on the page copies of group ``g`` of ``row``."""
        first = g * group if window is None else \
            first_page(row) + g * group

        def one(j, carry):
            page = pt_ref[row, first + j] if window is None else \
                pt_ref[row, (first + j) % pt_ref.shape[1]]
            at = pl.ds(pl.multiple_of(j * page_rows, page_rows), page_rows)
            act(pltpu.make_async_copy(k_hbm.at[page], kbuf.at[slot, at],
                                      sem.at[0, slot]))
            act(pltpu.make_async_copy(v_hbm.at[page], vbuf.at[slot, at],
                                      sem.at[1, slot]))
            return carry
        jax.lax.fori_loop(0, jnp.minimum(live_pages(row) - first, group),
                          one, 0)

    @pl.when(b == 0)
    def _first_row():
        # whatever a buffer holds past a step's live pages meets a
        # probability of exactly 0: it has to be finite
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        slot_ref[0] = 0
        copies(0, 0, 0, lambda c: c.start())

    off = off_ref[b]
    n_groups = (pages_to_read(b) + group - 1) // group
    slot0 = slot_ref[0]
    q = q_ref[...]
    col = jax.lax.broadcasted_iota(jnp.int32, (h, cols), 1)
    own = (col % token_rows) == jax.lax.broadcasted_iota(
        jnp.int32, (h, cols), 0) // q_heads_a_row
    tok = col // token_rows

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
        s = _mxu_f32(q, wide(kbuf[slot]), 1) * scale
        if quant:
            at = pl.ds(pl.multiple_of(g * cols, cols), cols)
            s = s * ks_ref[:, at]
        if window is None:
            seen = g * tokens + tok <= off
        else:
            pos = first_page(b) * page_size + g * tokens + tok
            seen = (pos <= off) & (pos > off - window)
        s = jnp.where(own & seen, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        v = wide(vbuf[slot])
        p = p * vs_ref[:, at] if quant else p.astype(v.dtype)
        return m_new, l_new, alpha * acc + _mxu_f32(p, v, 0)

    _, l, acc = jax.lax.fori_loop(
        0, n_groups, step,
        (jnp.full((h, 1), NEG_INF, jnp.float32),
         jnp.zeros((h, 1), jnp.float32),
         jnp.zeros((h, lanes), jnp.float32)))
    slot_ref[0] = (slot0 + n_groups) % 2
    o_ref[...] = (acc / l).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, page_table, offsets,
                           scale=None, k_scale=None, v_scale=None,
                           window=None, h_kv=None):
    """Single-token decode attention over a paged KV cache.

    q: [B, H, D] this step's queries; k_pool/v_pool: [P, page_size,
    H_kv, D] physical page pools, or lane-dense [P, rows, 128] as the
    kernel sees them (``h_kv`` then says how many kv heads a token has:
    the shape no longer does); page_table: int32 [B, N] logical →
    physical page map; offsets: int32 [B] — row b attends positions
    <= offsets[b] (its freshly written token included).  With
    ``k_scale``/``v_scale`` ([P, page_size] float32) the pools hold
    int8/fp8 values, cross HBM at that width, and are dequantized by
    their scales inside the kernel.

    The grid is over rows; the pools are never blocked (``pl.ANY``: they
    stay in HBM) and never gathered into a contiguous copy.  Each row
    streams its live pages alone, ``paged_decode_pages_per_step`` of
    them a step, by double-buffered DMAs through the scalar-prefetched
    page table (``_paged_decode_kernel``): the work follows the
    contexts, not the slots' capacity.  The kernel sees a pool as
    ``[P, rows, 128]`` and a query head on the lanes of its kv head: a
    pool that lives in that shape (serving/paged_kv.py) goes to the
    kernel as it is; a 4-D pool is reshaped here, which is the same
    bytes where D is 128 and one relayout of the pool by XLA where D is
    less.  The caller asks
    ``paged_decode_pages_per_step`` first; a pool it answers 0 for is
    read by the XLA gather lane.

    ``window``: row b attends positions ``offsets[b] - window <  t <=
    offsets[b]`` through a ring page table (``_paged_decode_kernel``);
    not with quantized pools.
    """
    if window is not None and k_scale is not None:
        raise ValueError("paged decode kernel: quantized pools have no "
                         "window lane; the caller reads them by XLA")
    d = q.shape[-1]
    if k_pool.ndim == 4:
        psz, h_kv = k_pool.shape[1:3]
    elif h_kv is None:
        raise ValueError("paged decode kernel: a lane-dense pool "
                         f"{k_pool.shape} does not say its kv heads; "
                         "pass h_kv")
    else:
        psz = k_pool.shape[1] * 128 // (h_kv * d)
    group = paged_decode_pages_per_step(psz, h_kv, d,
                                        k_pool.dtype.itemsize)
    while group > page_table.shape[1]:
        group //= 2
    if not group:
        raise ValueError(
            f"paged decode kernel: pages of {h_kv} kv heads of {d} are "
            "not whole 128-lane rows and 8-row tiles; the caller reads "
            "such a pool by the XLA gather lane")
    return _paged_decode_call(
        q, k_pool, v_pool, page_table, offsets, k_scale, v_scale,
        scale=float(scale) if scale is not None else 1.0 / math.sqrt(d),
        group=group, interpret=_interpret(),
        window=None if window is None else int(window), h_kv=int(h_kv))


@functools.partial(jax.jit, static_argnames=("scale", "group", "interpret",
                                             "window", "h_kv"))
def _paged_decode_call(q, k_pool, v_pool, page_table, offsets, k_scale,
                       v_scale, *, scale, group, interpret, window=None,
                       h_kv):
    """``paged_decode_attention`` at a fixed step size.  A program of its
    own inside the caller's: the layers of a model trace and lower ONE
    kernel between them (16 of them cost Mistral's tick 4 s of set-up
    when each call site traced its own).  A lane-dense pool is the
    kernel's operand as it stands; a 4-D one is reshaped to it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    n_pages = page_table.shape[1]
    quant = k_scale is not None
    page_table = page_table.astype(jnp.int32)
    n_rep, heads_a_row = h // h_kv, 128 // d
    token_rows = h_kv // heads_a_row

    # a query head on the lanes of its kv head, zeros beside it; heads
    # padded to whole tiles of the matmul's left side
    lane_seg = (jnp.arange(h) // n_rep) % heads_a_row
    seg = jax.nn.one_hot(lane_seg, heads_a_row, dtype=q.dtype)
    h_pad = -(-h // 16) * 16
    qk = (q[:, :, None, :] * seg[None, :, :, None]).reshape(b, h, 128)
    qk = jnp.pad(qk, ((0, 0), (0, h_pad - h), (0, 0)))

    row_spec = pl.BlockSpec((None, h_pad, 128),
                            lambda bi, pt, off: (bi, 0, 0))
    hbm_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [row_spec, hbm_spec, hbm_spec]
    if k_pool.ndim == 4:
        n_pool, psz = k_pool.shape[:2]
        page_rows = psz * token_rows
        k_pool = k_pool.reshape(n_pool, page_rows, 128)
        v_pool = v_pool.reshape(n_pool, page_rows, 128)
    else:
        page_rows = k_pool.shape[1]
        psz = page_rows // token_rows
    operands = [qk, k_pool, v_pool]
    if quant:
        # a row's scales, a column of the kernel's score matrix each
        # ((token, row of the token) order), padded to whole steps: a
        # gather of 4 bytes a token beside the pools' h_kv * d
        width = -(-n_pages // group) * group * page_rows

        def columns(scales):
            c = jnp.repeat(scales[page_table].reshape(b, -1), token_rows,
                           axis=1)
            return jnp.pad(c, ((0, 0), (0, width - c.shape[1])))[:, None]
        sc_spec = pl.BlockSpec((None, 1, width),
                               lambda bi, pt, off: (bi, 0, 0))
        in_specs += [sc_spec, sc_spec]
        operands += [columns(k_scale), columns(v_scale)]
    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, page_size=psz, group=group,
        token_rows=token_rows, q_heads_a_row=n_rep * heads_a_row,
        quant=quant, **({} if window is None else {"window": window}))
    buf = pltpu.VMEM((2, group * page_rows, 128), k_pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((1,), jnp.int32)])
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h_pad, 128), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_decode",
    )(page_table, offsets.astype(jnp.int32), *operands)
    # a head's output lies on its kv head's lanes
    out = out[:, :h].reshape(b, h, heads_a_row, d)
    return jnp.take_along_axis(
        out, lane_seg[None, :, None, None], axis=2)[:, :, 0]


def _supports_pallas(q, k, v, attn_mask, segment_ids):
    if not (_on_tpu() or _interpret()):
        return False
    b, s, h, d = q.shape
    if s < 128 or s % 128 != 0:
        return False
    if d > 256:
        return False
    if v.shape != k.shape:
        return False
    if (k.shape[0], k.shape[1], k.shape[3]) != (b, s, d):
        return False
    if h % k.shape[2] != 0:   # GQA: kv heads must divide q heads
        return False
    if attn_mask is not None:
        am = attn_mask
        if am.ndim != 4 or am.shape[2] != s or am.shape[3] != s:
            return False
        if am.shape[0] not in (1, b) or am.shape[1] not in (1, h):
            return False
    if segment_ids is not None:
        if tuple(segment_ids.shape) != (b, s):
            return False
    return True


def _shard_mapped(core, mesh, q, k, mask, head_major):
    """``core`` as a ``shard_map`` over ``mesh``: batch over ``dp`` and
    heads over ``mp`` — attention is independent per (batch, head), so
    each device runs the kernels on its own block and no collective is
    needed.  Mosaic kernels cannot be partitioned by GSPMD; this is how
    the one-program dp×mp step hosts them.  An axis that does not
    divide its dim (or a kv-head count ``mp`` does not divide) stays
    unsharded: that work is then replicated, which is correct."""
    from jax.sharding import PartitionSpec as P

    h_axis = 1 if head_major else 2

    def axis_for(name, *dims):
        if name not in mesh.dim_names:
            return None
        n = mesh.get_dim_size(name)
        return name if n > 1 and all(d % n == 0 for d in dims) else None

    dp = axis_for("dp", q.shape[0])
    mp = axis_for("mp", q.shape[h_axis], k.shape[h_axis])
    qkv = P(dp, mp, None, None) if head_major else P(dp, None, mp, None)
    mask_spec = None if mask is None else P(
        dp if mask.shape[0] > 1 else None,
        mp if mask.shape[1] > 1 else None, None, None)
    seg = P(dp, None, None)

    def body(q, k, v, mask, qseg, kseg, seed):
        if seed is not None:
            # the in-kernel hash counts (batch, head) from 0 in every
            # shard: fold the shard's position in so shards do not
            # repeat one another's keep-mask
            shard = jnp.uint32(0)
            for name in (dp, mp):
                if name is not None:
                    shard = shard * jnp.uint32(mesh.get_dim_size(name)) \
                        + jax.lax.axis_index(name).astype(jnp.uint32)
            seed = seed + shard * jnp.uint32(0x9E3779B1)
        return core(q, k, v, mask, qseg, kseg, seed)

    return jax.shard_map(
        body, mesh=mesh.jax_mesh,
        in_specs=(qkv, qkv, qkv, mask_spec, seg, seg, P()),
        out_specs=qkv, check_vma=False)


def flash_attention(query, key, value, attn_mask=None, dropout=0.0,
                    causal=False, training=True, scale=None,
                    segment_ids=None, head_major=False, name=None):
    """Public op: Tensor-level flash attention, [B, S, H, D].

    K/V may carry fewer heads than Q (GQA) — the Pallas kernels index the
    shared kv head directly.  ``segment_ids`` [B, S] enables packed-varlen
    attention (tokens attend only within their segment).  Dropout and
    additive/boolean masks run inside the kernels; no O(S^2) fallback."""
    dropout = dropout if training else 0.0
    dropout_key = _state.next_rng_key() if dropout > 0.0 else None
    # a TRAINABLE additive bias (learned relative-position bias / ALiBi)
    # must take the XLA path: the Pallas backward does not produce a mask
    # gradient, and fabricating zeros would silently freeze the bias
    mask_trainable = (isinstance(attn_mask, Tensor)
                      and not attn_mask.stop_gradient)

    def fn(q, k, v, m, seg):
        sc = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
        if head_major:
            b_, h_, s_, d_ = q.shape
            shaped_ok = _supports_pallas(
                jax.ShapeDtypeStruct((b_, s_, h_, d_), q.dtype),
                jax.ShapeDtypeStruct((b_, s_, k.shape[1], d_), k.dtype),
                jax.ShapeDtypeStruct((b_, s_, v.shape[1], d_), v.dtype),
                m, seg)
        else:
            shaped_ok = _supports_pallas(q, k, v, m, seg)
        if shaped_ok and not mask_trainable:
            *_, seq_len, d_ = _dims(q, k, head_major)
            walk = bool(_call_walk_bytes(q, k, head_major, m is not None,
                                         seg is not None))
            block_q, block_k = _pick_blocks(seq_len, d_, "fwd", walk)
            block_qb, block_kb = _pick_blocks(seq_len, d_, "bwd", walk)
            mask_add = None
            if m is not None:
                mask_add = (jnp.where(m, 0.0, NEG_INF).astype(jnp.float32)
                            if m.dtype == jnp.bool_
                            else m.astype(jnp.float32))
            qseg = kseg = None
            if seg is not None:
                seg32 = seg.astype(jnp.int32)
                qseg = seg32[:, :, None]
                kseg = seg32[:, None, :]
            seed = (jax.random.bits(dropout_key, (1, 1), jnp.uint32)
                    if dropout > 0.0 else None)

            def core(q, k, v, mask_add, qseg, kseg, seed):
                return _flash_core(q, k, v, mask_add, qseg, kseg, seed,
                                   causal, sc, float(dropout), block_q,
                                   block_k, block_qb, block_kb, head_major)

            from ..distributed.mesh import gspmd_mesh
            mesh = gspmd_mesh()
            if mesh is not None:
                core = _shard_mapped(core, mesh, q, k, mask_add,
                                     head_major)
            return core(q, k, v, mask_add, qseg, kseg, seed)
        return _xla_attention(q, k, v, attn_mask=m, causal=causal,
                              scale=sc, dropout=dropout,
                              dropout_key=dropout_key, segment_ids=seg,
                              head_major=head_major)

    mask_t = attn_mask if isinstance(attn_mask, Tensor) else None
    if attn_mask is not None and mask_t is None:
        attn_mask = Tensor(jnp.asarray(attn_mask))
        mask_t = attn_mask
    seg_t = segment_ids if isinstance(segment_ids, Tensor) else None
    if segment_ids is not None and seg_t is None:
        seg_t = Tensor(jnp.asarray(segment_ids))
    args = (query, key, value, mask_t, seg_t)
    return apply_op("flash_attention", fn, args)
