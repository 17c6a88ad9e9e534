"""Flash attention for TPU.

Reference capability: FlashAttention-2 via dynloaded CUDA lib (reference:
paddle/phi/kernels/gpu/flash_attn_kernel.cu:203 → phi::dynload::flash_attn_fwd,
backward at paddle/phi/kernels/gpu/flash_attn_grad_kernel.cu; dropout args at
flash_attn_kernel.cu:203; varlen variant at incubate/nn/functional/
variable_length_memory_efficient_attention.py).  TPU-native realization:
Pallas kernels that tile Q into VMEM blocks and stream K/V blocks **via the
grid** (one K/V block resident at a time, double-buffered by the Mosaic
pipeline), with online softmax in fp32 scratch accumulators.  Backward is the
flash-attention backward: probabilities are recomputed per block from the
saved logsumexp — never an O(S^2) materialization — with a dK/dV kernel
(streaming Q innermost) and a dQ kernel (streaming K/V innermost).

Feature coverage (all composable, fwd AND bwd):

- **causal** masking with dead-block skipping (clamped index maps dedupe the
  skipped fetches).
- **attention dropout** on the probabilities via a counter-based in-kernel
  PRNG (position+seed hash) — the identical keep-mask is regenerated in the
  backward kernels, so no O(S^2) mask is ever materialized.
- **additive/boolean masks** of shape [B|1, H|1, S, S], streamed block-wise
  through the grid (the analog of the reference's attn_mask path).
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
squeezed head dim in second-to-last position violates.  The relayout is one
XLA transpose each way, negligible next to the attention itself.

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


def _apply_masks(s, *, causal, q_start, k_start, block_q, block_k,
                 qseg=None, kseg=None, mask=None):
    """Score masking shared by all three kernels: causal position mask,
    same-segment mask (varlen packing), additive attention mask."""
    if causal:
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    if qseg is not None:
        # qseg (block_q, 1) vs kseg (1, block_k) broadcast — no relayout
        s = jnp.where(qseg == kseg, s, NEG_INF)
    if mask is not None:
        s = s + mask
    return s


def _dropout_uniform(seed, head, q_start, k_start, block_q, block_k):
    """Counter-based stateless uniform(0,1) per (head, q_pos, k_pos):
    a murmur-style integer hash, regenerated identically in forward and
    backward so the same probabilities drop — no mask is materialized."""
    qp = (q_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)).astype(jnp.uint32)
    kp = (k_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)).astype(jnp.uint32)
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
# Pallas forward: grid (B*H, num_q, num_kv), K/V streamed by the grid
# ------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, block_q, block_k,
                dropout, has_mask, has_seg):
    """One (bh, q_block, kv_block) step of the online softmax.

    The kv grid axis is innermost: scratch (m, l, acc) carries the running
    max / normalizer / weighted sum across kv steps for a fixed q block.
    """
    from jax.experimental import pallas as pl

    (seed_ref, mask_ref, qseg_ref, kseg_ref,
     o_ref, lse_ref, m_scr, l_scr, acc_scr) = _unpack_rest(
        rest, dropout=dropout, has_mask=has_mask, has_seg=has_seg)

    n = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    num_kv = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = i * block_q
    k_start = j * block_k
    # Entire block above the causal diagonal contributes nothing: skip the
    # matmuls (the DMA already happened; autotune trades block_k against
    # the wasted fetches).
    live = (q_start + block_q - 1 >= k_start) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[:].astype(jnp.float32)
        k = k_ref[:].astype(jnp.float32)
        v = v_ref[:].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _apply_masks(
            s, causal=causal, q_start=q_start, k_start=k_start,
            block_q=block_q, block_k=block_k,
            qseg=qseg_ref[:] if has_seg else None,
            kseg=kseg_ref[:] if has_seg else None,
            mask=mask_ref[:].astype(jnp.float32) if has_mask else None)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if has_mask or has_seg:
            # fully-masked rows: m_new == NEG_INF makes exp(s-m) == 1 —
            # zero them so such rows emit 0, not garbage
            p = jnp.where(s > NEG_INF * 0.5, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
        if dropout > 0.0:
            # softmax normalizes over the UNdropped probabilities; dropout
            # applies to what multiplies V
            u = _dropout_uniform(seed_ref[0, 0], n, q_start, k_start,
                                 block_q, block_k)
            p = jnp.where(u >= dropout, p, 0.0) / (1.0 - dropout)
        acc_scr[:] = alpha * acc_scr[:] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(j == num_kv - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:], 1e-30)  # noqa: E741
        o_ref[:] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[:] = (m_scr[:] + jnp.log(l)).astype(lse_ref.dtype)


def _feature_specs(*, b, s, h, h_kv, block_q, block_k, dropout, mask, qseg,
                   kseg, q_axis, kv_axis, head_of, batch_of, causal,
                   grid_qi=None):
    """(in_specs, inputs) for the optional seed/mask/segment inputs, shared
    by the three kernels.  head_of/batch_of map grid indices to the global
    q-head / batch; grid_qi maps grid indices to the (clamped) q block."""
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
        specs.append(pl.BlockSpec((None, block_q, 1), qseg_index))
        specs.append(pl.BlockSpec((None, 1, block_k), kseg_index))
        inputs.extend([qseg, kseg])
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


def _pallas_flash_fwd(q, k, v, mask=None, qseg=None, kseg=None, seed=None,
                      *, causal, scale, block_q, block_k, dropout=0.0,
                      head_major=False):
    """q: [B, S, H, D] (or [B, H, S, D] when head_major), k/v likewise
    with H_kv heads → (out in q's layout, lse [B, H, S, 1] fp32).
    mask: [B|1, H|1, S, S] additive fp32; qseg/kseg: [B, S, 1]/[B, 1, S]
    int32; seed: [1,1] uint32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if head_major:
        b, h, s, d = q.shape
        h_kv = k.shape[1]
    else:
        b, s, h, d = q.shape
        h_kv = k.shape[2]
    n_rep = h // h_kv
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    grid = (b * h, s // block_q, s // block_k)
    has_mask, has_seg = mask is not None, qseg is not None
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               dropout=dropout, has_mask=has_mask,
                               has_seg=has_seg)
    qo_spec = pl.BlockSpec((None, block_q, d), lambda n, i, j: (n, i, 0))
    kv_spec = _causal_kv_spec(block_q, block_k, d, q_axis=1, kv_axis=2,
                              causal=causal,
                              kv_row=lambda n: (n // h) * h_kv
                              + (n % h) // n_rep)
    lse_spec = pl.BlockSpec((None, block_q, 1), lambda n, i, j: (n, i, 0))
    feat_specs, feat_inputs = _feature_specs(
        b=b, s=s, h=h, h_kv=h_kv, block_q=block_q, block_k=block_k,
        dropout=dropout, mask=mask, qseg=qseg, kseg=kseg,
        q_axis=1, kv_axis=2, head_of=lambda *g: g[0] % h,
        batch_of=lambda *g: g[0] // h, causal=causal)
    if dropout > 0.0:
        feat_inputs[0] = seed
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
    )(_to_bh(q, head_major), _to_bh(k, head_major),
      _to_bh(v, head_major), *feat_inputs)
    return _from_bh(out, b, h, head_major), lse.reshape(b, h, s, 1)


# ------------------------------------------------------------------
# Pallas backward: dK/dV kernel (Q innermost) + dQ kernel (K/V innermost)
# ------------------------------------------------------------------

def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    *rest, scale, causal, block_q, block_k, dropout,
                    has_mask, has_seg, h, h_kv, num_q):
    """grid (B*H_kv, num_kv, num_q*n_rep): accumulate dK/dV for one kv
    block while streaming (q_head_rep, q_block) innermost — GQA heads
    sharing this kv head accumulate into the same scratch.  p is
    recomputed per block from the saved lse."""
    from jax.experimental import pallas as pl

    (seed_ref, mask_ref, qseg_ref, kseg_ref,
     dk_ref, dv_ref, dk_scr, dv_scr) = _unpack_rest(
        rest, dropout=dropout, has_mask=has_mask, has_seg=has_seg)

    n = pl.program_id(0)   # b * h_kv + kv_head
    j = pl.program_id(1)   # kv block
    r = pl.program_id(2)   # rep * num_q + q block (innermost)
    num_r = pl.num_programs(2)
    i = r % num_q
    n_rep = h // h_kv
    # global q-head id (matches the forward's grid index 0) for dropout
    head = (n // h_kv) * h + (n % h_kv) * n_rep + r // num_q

    @pl.when(r == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_start = i * block_q
    k_start = j * block_k
    live = (q_start + block_q - 1 >= k_start) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[:].astype(jnp.float32)
        k = k_ref[:].astype(jnp.float32)
        v = v_ref[:].astype(jnp.float32)
        do = do_ref[:].astype(jnp.float32)
        lse = lse_ref[:]          # [block_q, 1]
        delta = delta_ref[:]      # [block_q, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _apply_masks(
            s, causal=causal, q_start=q_start, k_start=k_start,
            block_q=block_q, block_k=block_k,
            qseg=qseg_ref[:] if has_seg else None,
            kseg=kseg_ref[:] if has_seg else None,
            mask=mask_ref[:].astype(jnp.float32) if has_mask else None)
        p = jnp.exp(s - lse)                       # [block_q, block_k]
        if has_mask or has_seg:
            # fully-masked rows: lse == NEG_INF would give exp(0) == 1
            p = jnp.where(s > NEG_INF * 0.5, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout > 0.0:
            u = _dropout_uniform(seed_ref[0, 0], head, q_start, k_start,
                                 block_q, block_k)
            keep = u >= dropout
            p_v = jnp.where(keep, p, 0.0) / (1.0 - dropout)
            dp = jnp.where(keep, dp, 0.0) / (1.0 - dropout)
        else:
            p_v = p
        # dv += p̃^T do
        dv_scr[:] += jax.lax.dot_general(
            p_v, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # ds = p * (dp - delta) * scale;  dk += ds^T q
        ds = p * (dp - delta) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(r == num_r - 1)
    def _finalize():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   *rest, scale, causal, block_q, block_k, dropout,
                   has_mask, has_seg):
    """grid (B*H, num_q, num_kv): accumulate dQ for one q block while
    streaming kv blocks."""
    from jax.experimental import pallas as pl

    (seed_ref, mask_ref, qseg_ref, kseg_ref,
     dq_ref, dq_scr) = _unpack_rest(
        rest, dropout=dropout, has_mask=has_mask, has_seg=has_seg)

    n = pl.program_id(0)
    i = pl.program_id(1)   # q block
    j = pl.program_id(2)   # kv block (innermost)
    num_kv = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_start = i * block_q
    k_start = j * block_k
    live = (q_start + block_q - 1 >= k_start) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[:].astype(jnp.float32)
        k = k_ref[:].astype(jnp.float32)
        v = v_ref[:].astype(jnp.float32)
        do = do_ref[:].astype(jnp.float32)
        lse = lse_ref[:]
        delta = delta_ref[:]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _apply_masks(
            s, causal=causal, q_start=q_start, k_start=k_start,
            block_q=block_q, block_k=block_k,
            qseg=qseg_ref[:] if has_seg else None,
            kseg=kseg_ref[:] if has_seg else None,
            mask=mask_ref[:].astype(jnp.float32) if has_mask else None)
        p = jnp.exp(s - lse)
        if has_mask or has_seg:
            p = jnp.where(s > NEG_INF * 0.5, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout > 0.0:
            u = _dropout_uniform(seed_ref[0, 0], n, q_start, k_start,
                                 block_q, block_k)
            dp = jnp.where(u >= dropout, dp, 0.0) / (1.0 - dropout)
        ds = p * (dp - delta) * scale
        dq_scr[:] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(j == num_kv - 1)
    def _finalize():
        dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)


def _pallas_flash_bwd(q, k, v, out, lse, dout, mask=None, qseg=None,
                      kseg=None, seed=None, *, causal, scale, block_q,
                      block_k, dropout=0.0, head_major=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if head_major:
        b, h, s, d = q.shape
        h_kv = k.shape[1]
    else:
        b, s, h, d = q.shape
        h_kv = k.shape[2]
    n_rep = h // h_kv
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    has_mask, has_seg = mask is not None, qseg is not None
    # delta_i = rowsum(dO_i * O_i): cheap elementwise+reduce, XLA fuses it
    eq = "bhsd,bhsd->bhs" if head_major else "bshd,bshd->bhs"
    delta = jnp.einsum(eq, dout.astype(jnp.float32),
                       out.astype(jnp.float32)).reshape(b * h, s, 1)
    q3, do3 = _to_bh(q, head_major), _to_bh(dout, head_major)
    k3, v3 = _to_bh(k, head_major), _to_bh(v, head_major)
    lse3 = lse.reshape(b * h, s, 1)
    num_q = s // block_q

    # ---- dK/dV: grid (b*h_kv, num_kv, num_q*n_rep) — GQA q-heads that
    # share a kv head stream through the innermost axis and accumulate
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
        (None, block_q, 1), lambda n, j, r: (q_row(n, j, r),
                                             qi_clamped(n, j, r), 0))
    kv_spec_q = pl.BlockSpec((None, block_k, d), lambda n, j, r: (n, j, 0))
    feat_specs_q, feat_inputs_q = _feature_specs(
        b=b, s=s, h=h, h_kv=h_kv, block_q=block_q, block_k=block_k,
        dropout=dropout, mask=mask, qseg=qseg, kseg=kseg,
        q_axis=2, kv_axis=1,
        head_of=lambda n, j, r: (n % h_kv) * n_rep + r // num_q,
        batch_of=lambda n, j, r: n // h_kv, causal=causal,
        grid_qi=qi_clamped)
    if dropout > 0.0:
        feat_inputs_q[0] = seed
    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, dropout=dropout, has_mask=has_mask,
        has_seg=has_seg, h=h, h_kv=h_kv, num_q=num_q)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b * h_kv, s // block_k, num_q * n_rep),
        in_specs=[qo_spec_q, kv_spec_q, kv_spec_q, qo_spec_q,
                  lse_spec_q, lse_spec_q] + feat_specs_q,
        out_specs=[kv_spec_q, kv_spec_q],
        out_shape=[jax.ShapeDtypeStruct((b * h_kv, s, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h_kv, s, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(q3, k3, v3, do3, lse3, delta, *feat_inputs_q)

    # ---- dQ: grid (b*h, num_q, num_kv)
    kv_row = lambda n: (n // h) * h_kv + (n % h) // n_rep  # noqa: E731
    qo_spec = pl.BlockSpec((None, block_q, d), lambda n, i, j: (n, i, 0))
    kv_spec = _causal_kv_spec(block_q, block_k, d, q_axis=1, kv_axis=2,
                              causal=causal, kv_row=kv_row)
    lse_spec = pl.BlockSpec((None, block_q, 1), lambda n, i, j: (n, i, 0))
    feat_specs, feat_inputs = _feature_specs(
        b=b, s=s, h=h, h_kv=h_kv, block_q=block_q, block_k=block_k,
        dropout=dropout, mask=mask, qseg=qseg, kseg=kseg,
        q_axis=1, kv_axis=2, head_of=lambda *g: g[0] % h,
        batch_of=lambda *g: g[0] // h, causal=causal)
    if dropout > 0.0:
        feat_inputs[0] = seed
    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, dropout=dropout, has_mask=has_mask,
        has_seg=has_seg)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b * h, num_q, s // block_k),
        in_specs=[qo_spec, kv_spec, kv_spec, qo_spec, lse_spec, lse_spec]
        + feat_specs,
        out_specs=qo_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=_interpret(),
        name="flash_bwd_dq",
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


def _pick_blocks(s, d, which="fwd"):
    """Block sizes: a sweep this process ran first (validated — a
    non-dividing entry would truncate the grid and leave rows
    unwritten), then shape heuristics.  `which` selects the direction:
    the dkv/dq kernels prefer different shapes than the forward, so fwd
    and bwd are swept and recorded separately."""
    from .autotune import lookup
    cached = lookup(f"flash_attention.{which}", (s, d))
    if cached is not None and len(cached) == 2:
        bq, bk = int(cached[0]), int(cached[1])
        if 0 < bq <= s and 0 < bk <= s and s % bq == 0 and s % bk == 0:
            return bq, bk
    block_q = 256 if s % 256 == 0 else 128
    block_k = 512 if s % 512 == 0 else block_q
    return min(block_q, s), min(block_k, s)


def autotune_blocks(s, d, dtype=jnp.bfloat16, batch=1, heads=1):
    """Timed sweeps over divisor block sizes for (seq, head_dim); records
    the winners for this process (reference: phi/kernels/autotune
    switch_autotune.h).  Forward and backward are swept SEPARATELY —
    the dkv/dq kernels prefer different shapes than the forward, and
    each direction's choice feeds its own key.  A candidate the chip
    refuses raises."""
    from . import autotune as at

    cands = [(bq, bk)
             for bq in (128, 256, 512) for bk in (128, 256, 512)
             if bq <= s and bk <= s and s % bq == 0 and s % bk == 0]
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
            seq_len = q.shape[2] if head_major else q.shape[1]
            block_q, block_k = _pick_blocks(seq_len, q.shape[-1])
            block_qb, block_kb = _pick_blocks(seq_len, q.shape[-1],
                                              which="bwd")
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
