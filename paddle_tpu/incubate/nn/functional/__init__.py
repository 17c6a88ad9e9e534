"""Fused-op APIs (reference capability: python/paddle/incubate/nn/
functional/ — fused_rotary_position_embedding.py, fused_rms_norm.py,
fused_layer_norm.py, fused_matmul_bias.py, and the attention variants).

TPU-native realization: "fused" is XLA's default — these entry points keep
the reference's API surface while lowering to ops XLA fuses into single
kernels (rope/rms/ln are bandwidth-bound elementwise+reduce chains that XLA
fuses into neighbors; flash attention uses the Pallas kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ....core.dispatch import apply_op
from ....core.tensor import Tensor
from ....nn import functional as F
from ....observability.tracing import scope


def fused_rms_norm(x, norm_weight=None, norm_bias=None, epsilon=1e-6,
                   begin_norm_axis=-1, **kwargs):
    """reference: incubate/nn/functional/fused_rms_norm.py (kernel:
    phi/kernels/gpu/rms_norm_kernel.cu)."""
    out = F.rms_norm(x, weight=norm_weight, epsilon=epsilon)
    if norm_bias is not None:
        out = out + norm_bias
    return out


def fused_layer_norm(x, norm_weight=None, norm_bias=None, epsilon=1e-5,
                     begin_norm_axis=-1, **kwargs):
    """reference: incubate/nn/functional/fused_layer_norm.py (kernel:
    fusion/gpu/fused_layernorm_kernel.cu)."""
    return F.layer_norm(x, weight=norm_weight, bias=norm_bias,
                        epsilon=epsilon)


def fused_matmul_bias(x, y, bias=None, transpose_x=False, transpose_y=False,
                      name=None):
    """reference: incubate/nn/functional/fused_matmul_bias.py — epilogue
    fusion is automatic under XLA."""
    from ....tensor_ops import linalg as LA
    out = LA.matmul(x, y, transpose_x=transpose_x, transpose_y=transpose_y)
    if bias is not None:
        out = out + bias
    return out


def fused_bias_act(x, bias=None, dequant_scales=None, shift=None,
                   smooth=None, act_method="gelu", quant_scale=-1,
                   **kwargs):
    """reference: incubate/nn/functional/fused_bias_act (kernel:
    fusion/gpu/fused_bias_act_kernel.cu).  bias-add + activation
    (gelu/relu/silu/geglu/swiglu) — XLA fuses the epilogue chain into the
    producing matmul, so this is the API surface over that fusion.  The
    reference's int8 dequant/quant path is not implemented — passing those
    args raises instead of silently returning un-dequantized values."""
    if dequant_scales is not None or shift is not None or \
            smooth is not None or quant_scale != -1:
        raise NotImplementedError(
            "fused_bias_act quant path (dequant_scales/shift/smooth/"
            "quant_scale) is not implemented; use the quantization "
            "package for QAT/PTQ")
    def fn(xv, bv):
        y = xv if bv is None else xv + bv
        if act_method in ("geglu", "swiglu"):
            a, b = jnp.split(y, 2, axis=-1)
            act = jax.nn.gelu if act_method == "geglu" else jax.nn.silu
            return act(a) * b
        act = {"gelu": jax.nn.gelu, "relu": jax.nn.relu,
               "silu": jax.nn.silu, "swish": jax.nn.silu}[act_method]
        return act(y)
    return apply_op("fused_bias_act", fn, (x, bias))


def _rope_rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def _apply_rope(q, k, v, cos, sin, use_neox):
    def rot(t):
        if t is None:
            return None
        if use_neox:
            return t * cos + _rope_rotate_half(t) * sin
        # interleaved (GPT-J) layout
        t1 = t[..., 0::2]
        t2 = t[..., 1::2]
        c = cos[..., 0::2]
        s = sin[..., 0::2]
        ro = jnp.stack([t1 * c - t2 * s, t2 * c + t1 * s], axis=-1)
        return ro.reshape(t.shape)
    return tuple(r for r in (rot(q), rot(k), rot(v)) if r is not None)


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None, use_neox_rotary_style=True,
                                    time_major=False, rotary_emb_base=10000.0):
    """reference: incubate/nn/functional/fused_rotary_position_embedding.py
    (kernel: fusion/gpu/fused_rope_kernel.cu).  [batch, seq, heads, dim]
    layout; sin/cos default to the standard rope table."""
    qa = q._data if isinstance(q, Tensor) else jnp.asarray(q)
    b, s, h, d = qa.shape
    cos2d = sin2d = None     # [s, d] tables usable by the Pallas kernel
    if sin is None or cos is None:
        inv = 1.0 / (rotary_emb_base ** (jnp.arange(0, d, 2,
                                                    dtype=jnp.float32) / d))
        pos = (position_ids._data if isinstance(position_ids, Tensor)
               else jnp.arange(s, dtype=jnp.float32))
        if pos.ndim == 2:
            # [B, S] per-row positions (serving slot caches: every row
            # decodes at its own age) — tables broadcast per row
            freqs = pos[..., None].astype(jnp.float32) * inv  # [B,S,d/2]
            emb = jnp.concatenate([freqs, freqs], axis=-1)    # [B,S,d]
            cos_a = jnp.cos(emb)[:, :, None, :]
            sin_a = jnp.sin(emb)[:, :, None, :]
        else:
            freqs = jnp.outer(pos, inv)                       # [s, d/2]
            emb = jnp.concatenate([freqs, freqs], axis=-1)    # [s, d]
            if pos.ndim == 1 and emb.shape[0] == s:
                cos2d, sin2d = jnp.cos(emb), jnp.sin(emb)
            cos_a = jnp.cos(emb)[None, :, None, :]
            sin_a = jnp.sin(emb)[None, :, None, :]
    else:
        cos_a = cos._data if isinstance(cos, Tensor) else jnp.asarray(cos)
        sin_a = sin._data if isinstance(sin, Tensor) else jnp.asarray(sin)
        if cos_a.ndim == 2:
            if cos_a.shape == (s, d):
                cos2d, sin2d = cos_a, sin_a
            cos_a = cos_a[None, :, None, :]
            sin_a = sin_a[None, :, None, :]

    args = [t for t in (q, k, v) if t is not None]

    from ....pallas import fused as _pf

    def fn(*ts):
        qq = ts[0]
        kk = ts[1] if k is not None else None
        vv = ts[2] if (v is not None and k is not None) else \
            (ts[1] if v is not None and k is None else None)
        if cos2d is not None and _pf.rope_supported(
                qq.shape, d, use_neox_rotary_style):
            c32 = cos2d.astype(jnp.float32)
            s32 = sin2d.astype(jnp.float32)
            outs = tuple(
                _pf.rope_pallas(t, c32, s32)
                for t in (qq, kk, vv) if t is not None)
        else:
            outs = _apply_rope(qq, kk, vv, cos_a.astype(qq.dtype),
                               sin_a.astype(qq.dtype), use_neox_rotary_style)
        return outs if len(outs) > 1 else outs[0]

    out = apply_op("fused_rope", fn, tuple(args))
    if not isinstance(out, tuple):
        out = (out,)
    result = []
    i = 0
    for t in (q, k, v):
        if t is None:
            result.append(None)
        else:
            result.append(out[i])
            i += 1
    return tuple(result)


def variable_length_memory_efficient_attention(query, key, value, seq_lens=None,
                                               kv_seq_lens=None, mask=None,
                                               scale=None, causal=False):
    """reference: incubate/nn/functional/
    variable_length_memory_efficient_attention.py — maps to the flash
    attention path with an additive mask built from the lengths."""
    from ....pallas.flash_attention import flash_attention
    return flash_attention(query, key, value, attn_mask=mask, causal=causal,
                           scale=scale)


def masked_multihead_attention(q, k, v, cache_k, cache_v, offset,
                               scale=None, name=None):
    """Decode-time attention against a fixed-size KV cache (reference:
    incubate/nn/functional/masked_multihead_attention.py over
    fusion/gpu/masked_multihead_attention.cu).

    q/k/v: [B, S, H, D] new tokens (S=1 in steady-state decode, larger at
    prefill); cache_k/cache_v: [B, S_max, H, D]; offset: int32 scalar —
    tokens already in the cache — or an int32 [B] vector of PER-ROW
    offsets (the serving engine's slot-based caches, where sequences of
    different ages share one decode step).  Writes the new K/V at
    offset..offset+S per row, attends causally over positions
    <= offset+i, and returns (out, cache_k', cache_v').  Static shapes
    throughout: one compiled program serves every decode step (the TPU
    analog of the reference's persistent decode kernel).

    GQA is native: when K/V carry fewer heads than Q (cache holds
    num_kv_heads — never the repeated copies), Q's heads are grouped onto
    the KV heads inside the einsum, so cache HBM and attention FLOPs stay
    at the kv-head count.
    """
    import math as _math

    # eager bounds check: dynamic_update_slice CLAMPS an out-of-range
    # start, which would silently overwrite earlier cache positions while
    # the causal mask still used the unclamped offset
    s_new = (q.shape[1] if hasattr(q, "shape") else 0)
    s_cap = cache_k.shape[1]
    off_concrete = None
    try:
        import numpy as _np
        raw = offset._data_ if isinstance(offset, Tensor) else offset
        if not isinstance(raw, jax.core.Tracer):
            off_concrete = _np.asarray(raw)
    except Exception:
        pass   # traced offset: caller owns the bound
    if off_concrete is not None and (off_concrete + s_new > s_cap).any():
        raise ValueError(
            f"KV cache overflow: offset {off_concrete} + {s_new} new "
            f"tokens > cache capacity {s_cap}")

    def fn(qa, ka, va, ck, cv, off):
        off = off.astype(jnp.int32) if hasattr(off, "astype") else \
            jnp.int32(off)
        if off.ndim == 1:
            # per-row offsets: each slot writes its new K/V at its own
            # age and masks its own causal horizon (serving slot caches)
            upd = jax.vmap(lambda c, u, o: jax.lax.dynamic_update_slice(
                c, u, (o, 0, 0)))
            ck = upd(ck, ka.astype(ck.dtype), off)
            cv = upd(cv, va.astype(cv.dtype), off)
        else:
            ck = jax.lax.dynamic_update_slice(ck, ka.astype(ck.dtype),
                                              (0, off, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, va.astype(cv.dtype),
                                              (0, off, 0, 0))
        out = _cache_attend(qa, ck, cv, off, scale)
        return out, ck, cv

    return apply_op("masked_multihead_attention", fn,
                    (q, k, v, cache_k, cache_v, offset))


def _cache_attend(qa, ck, cv, off, scale):
    """Causal attention of `qa` [B, S, Hq, D] against a full cache
    view `ck`/`cv` [B, S_max, Hkv, D] at per-row ([B]) or scalar
    offsets — the computation shared by the dense slot cache and the
    paged cache, so identical cache contents give bitwise-identical
    outputs regardless of the storage layout (masked positions
    contribute exactly 0 after softmax underflow, so even different
    S_max capacities agree).  GQA groups Q heads onto the kv heads
    inside the einsum."""
    import math as _math

    b, s, h_q, d = qa.shape
    s_max, h_kv = ck.shape[1], ck.shape[2]
    sc = scale if scale is not None else 1.0 / _math.sqrt(d)
    if off.ndim == 1:
        q_pos = off[:, None, None] + jnp.arange(s)[None, :, None]
        k_pos = jnp.arange(s_max)[None, None, :]
        mask = k_pos <= q_pos                     # [b, s, s_max]
    else:
        q_pos = off + jnp.arange(s)[:, None]      # [s, 1]
        k_pos = jnp.arange(s_max)[None, :]        # [1, s_max]
        mask = (k_pos <= q_pos)[None]             # [1, s, s_max]
    qf = qa.astype(jnp.float32)
    kf = ck.astype(jnp.float32)
    if h_q == h_kv:
        logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * sc
        logits = jnp.where(mask[:, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(cv.dtype), cv)
    else:                                         # grouped-query
        rep = h_q // h_kv
        qg = qf.reshape(b, s, h_kv, rep, d)
        logits = jnp.einsum("bqhrd,bkhd->bhrqk", qg, kf) * sc
        logits = jnp.where(mask[:, None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhrqk,bkhd->bqhrd", probs.astype(cv.dtype),
                         cv).reshape(b, s, h_q, d)
    return out.astype(qa.dtype)


#: keys one step of the blocked chunk read gathers and scores at once
_CHUNK_KEY_BLOCK = 512
#: a read whose float32 scores over the table's whole capacity are no
#: larger than this is one block and no loop
_ONE_BLOCK_SCORE_BYTES = 32 << 20


def _blocked_attend(qg, off, pt, gather_kv, *, s, d_v, psz, kb, one_block,
                    window, sc, cdt, out_dtype):
    """The blocked online softmax of a paged read: ``qg`` [B, S, H_kv,
    rep, D_k] at positions ``off .. off + S - 1`` against each row's
    live pages (with ``window``: its in-window pages alone, the table a
    ring), ``kb`` pages a block.  ``gather_kv(phys)`` turns a block's
    physical page ids [B, kb] into its keys [B, kb * psz, H_kv, D_k] and
    values [B, kb * psz, H_kv, D_v] — what a page holds is the store
    kind's to say.  Scores in ``cdt`` with float32 accumulation,
    statistics and sums in float32; as many blocks as the longest row
    needs (``one_block``: one, no loop).  Returns [B, S, H_kv * rep,
    D_v]."""
    b, _, h_kv, rep, _ = qg.shape
    n_tab = pt.shape[1]
    blk = kb * psz
    q_pos = off[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]  # [b,s]
    first_tok = jnp.zeros_like(off) if window is None else \
        jnp.maximum(off - (window - 1), 0)
    first_page = first_tok // psz
    last_page = (off + (s - 1)) // psz
    n_blocks = jnp.max(-(-(last_page - first_page + 1) // kb))
    pt = pt.astype(jnp.int32)

    def body(j, carry):
        m_prev, l_prev, acc = carry
        lp = first_page[:, None] + j * kb + \
            jnp.arange(kb, dtype=jnp.int32)[None, :]             # [b, kb]
        idx = lp % n_tab if window is not None else \
            jnp.minimum(lp, n_tab - 1)
        phys = jnp.take_along_axis(pt, idx, axis=1)
        kblk, vblk = gather_kv(phys)
        k_pos = (lp[:, :, None] * psz
                 + jnp.arange(psz, dtype=jnp.int32)).reshape(b, blk)
        sco = jnp.einsum("bqhrd,bkhd->bhrqk", qg, kblk.astype(cdt),
                         preferred_element_type=jnp.float32) * sc
        mask = k_pos[:, None, :] <= q_pos[:, :, None]            # [b,s,blk]
        if window is not None:
            mask &= k_pos[:, None, :] > q_pos[:, :, None] - window
        sco = jnp.where(mask[:, None, None], sco, -1e30)
        m_new = jnp.maximum(m_prev, jnp.max(sco, axis=-1, keepdims=True))
        p = jnp.where(mask[:, None, None], jnp.exp(sco - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bhrqk,bkhd->bhrqd", p.astype(vblk.dtype), vblk,
                        preferred_element_type=jnp.float32)
        return m_new, l_new, alpha * acc + pv

    init = (jnp.full((b, h_kv, rep, s, 1), -1e30, jnp.float32),
            jnp.zeros((b, h_kv, rep, s, 1), jnp.float32),
            jnp.zeros((b, h_kv, rep, s, d_v), jnp.float32))
    _, l, acc = body(0, init) if one_block else \
        jax.lax.fori_loop(0, n_blocks, body, init)
    out = acc / jnp.maximum(l, 1e-30)
    return jnp.transpose(out, (0, 3, 1, 2, 4)) \
        .reshape(b, s, h_kv * rep, d_v).astype(out_dtype)


def _block_pages(b, h_q, s, n_tab, psz):
    """(pages a block of the blocked read gathers, whether the whole
    table is one block)."""
    one_block = b * h_q * s * n_tab * psz * 4 <= _ONE_BLOCK_SCORE_BYTES
    return (n_tab if one_block
            else max(1, min(n_tab, _CHUNK_KEY_BLOCK // psz))), one_block


@functools.partial(jax.jit,
                   static_argnames=("psz", "h_kv", "scale", "window"))
def _paged_block_attend(qa, kp, vp, pt, off, ks=None, vs=None, *, psz,
                        h_kv, scale, window):
    """Attention of ``qa`` [B, S, Hq, D] at positions ``off .. off + S -
    1`` over each row's LIVE pages — and, with ``window``, over its
    in-window pages alone — in blocks of ``_CHUNK_KEY_BLOCK`` keys:
    a block's pages are gathered through the page table, scored in the
    operands' type with float32 accumulation, and folded into a float32
    online softmax (``_blocked_attend``), so nothing of size ``[Hq, S,
    capacity]`` ever exists.  The loop runs as many blocks as the
    longest row needs; a read small enough over the whole table
    (``_ONE_BLOCK_SCORE_BYTES``: a single-token read, a short chunk of a
    few rows over a short slot) is one block without a loop.
    With ``window`` the table is a ring (logical page ``p`` at entry
    ``p % N``; the same entry where the table spans the slot).  The
    pools are ``[P, psz, h_kv, D]`` or lane-dense ``[P, rows, 128]``:
    what is reshaped to ``[.., psz, h_kv, D]`` is the GATHERED block,
    the live pages of one step, never a pool.  A ``jax.jit`` of its own
    inside the caller's program: the layers of a model trace it once
    between them, not once each."""
    import math as _math
    from ....quantization import dequantize_kv
    b, s, h_q, d = qa.shape
    n_tab = pt.shape[1]
    rep = h_q // h_kv
    sc = scale if scale is not None else 1.0 / _math.sqrt(d)
    kb, one_block = _block_pages(b, h_q, s, n_tab, psz)
    blk = kb * psz
    quant = ks is not None
    kdt = jnp.float32 if quant else kp.dtype
    cdt = jnp.promote_types(qa.dtype, kdt)
    qg = qa.astype(cdt).reshape(b, s, h_kv, rep, d)

    def gather(pool, scales, phys):
        pages = pool[phys].reshape(b, kb, psz, h_kv, d)
        if quant:
            pages = dequantize_kv(pages, scales[phys])
        return pages.reshape(b, blk, h_kv, d)

    return _blocked_attend(
        qg, off, pt,
        lambda phys: (gather(kp, ks, phys), gather(vp, vs, phys)),
        s=s, d_v=d, psz=psz, kb=kb, one_block=one_block, window=window,
        sc=sc, cdt=cdt, out_dtype=qa.dtype)


@functools.partial(jax.jit,
                   static_argnames=("psz", "nope", "width", "scale"))
def _latent_block_attend(qa, pool, pt, off, w_kvb, *, psz, nope, width,
                         scale):
    """A prefill chunk's read of a LATENT store: ``qa`` [B, S, H, nope +
    rope] (rotated) at positions ``off .. off + S - 1`` over each row's
    live latent pages ``[P, psz, lanes]`` in the blocks of
    ``_paged_block_attend``.  A gathered block's rows ``[c, k_r]`` are
    UP-PROJECTED once (``w_kvb`` [rank, H * (nope + v)]: a head's key is
    ``[W_uk,h c, k_r]``, its value ``W_uv,h c``) and attended with
    ``nope + rope``-wide keys and ``v``-wide values through the same
    blocked online softmax — at chunk lengths the absorbed form would
    cost about twice the FLOPs.  ``width`` = rank + rope: the row's
    values among the pool's lanes."""
    b, s, h, d_k = qa.shape
    rank = w_kvb.shape[0]
    d_v = w_kvb.shape[1] // h - nope
    n_tab = pt.shape[1]
    kb, one_block = _block_pages(b, h, s, n_tab, psz)
    blk = kb * psz
    cdt = jnp.promote_types(qa.dtype, pool.dtype)
    qg = qa.astype(cdt).reshape(b, s, h, 1, d_k)

    def gather(phys):
        rows = pool[phys].reshape(b, blk, pool.shape[-1])
        with scope("mla_up_project"):
            kv = jnp.einsum("bkc,cn->bkn", rows[..., :rank], w_kvb,
                            preferred_element_type=jnp.float32) \
                .astype(cdt).reshape(b, blk, h, nope + d_v)
        k_r = jnp.broadcast_to(rows[:, :, None, rank:width].astype(cdt),
                               (b, blk, h, width - rank))
        return (jnp.concatenate([kv[..., :nope], k_r], axis=-1),
                kv[..., nope:])

    return _blocked_attend(
        qg, off, pt, gather, s=s, d_v=d_v, psz=psz, kb=kb,
        one_block=one_block, window=None, sc=scale, cdt=cdt,
        out_dtype=qa.dtype)


def paged_masked_multihead_attention(q, k, v, k_pool, v_pool, page_table,
                                     offset, page_size, scale=None,
                                     k_scale=None, v_scale=None,
                                     window=None, name=None):
    """Decode/chunked-prefill attention against a PAGED KV cache
    (serving/paged_kv.py — the vLLM PagedAttention layout kept
    static-shape for TPU).

    q/k/v: [B, S, H, D] new tokens; k_pool/v_pool: [P, page_size, Hkv,
    D] fixed page pools shared by every sequence, or the same bytes
    lane-dense, [P, page_size * Hkv * D / 128, 128], as `PagedKVCache`
    stores a pool of narrow heads the decode kernel can host (told
    apart by ``ndim``; Hkv is ``k``'s: such a pool is written, read and
    handed back in that shape, and no program reshapes a whole pool);
    page_table: int32 [B, N] mapping each row's logical pages to
    physical pool pages; offset: int32 [B] tokens already cached per
    row.  Writes the new
    K/V through the page table at offset..offset+S per row (rows whose
    table entries are 0 scatter into the reserved scratch page — how
    free/ungrown slots ride the static batch harmlessly), then reads
    each row's live pages in blocks of keys and attends causally with
    `masked_multihead_attention`'s math in an online softmax
    (`_paged_block_attend`) — paged and dense caches holding the same
    values agree to float32 rounding.

    Quantized KV storage: when ``k_scale``/``v_scale`` ([P, page_size]
    float32 per-page scale arrays) are passed, the pools hold int8 (or
    fp8) values.  The write quantizes each new token's [Hkv, D] row
    with its own scale (`paddle_tpu.quantization.quantize_kv_rows`) and
    scatters value + scale through the same page table; the read
    dequantizes fused into the gather (scale × int8 feeds the attention
    matmul directly).
    Returns (out, k_pool', v_pool', k_scale', v_scale') in this mode.

    On a TPU (and in Pallas interpret mode) the single-token decode
    read runs the Pallas kernel
    (`pallas.flash_attention.paged_decode_attention`): each row streams
    its LIVE pages alone, several a step, from the pools in HBM through
    the scalar-prefetched page table, and the query heads that share a
    kv head are the rows of one MXU product — its time follows the
    contexts, not the slots' capacity.  Its online softmax is
    numerically (not bitwise) equivalent to the XLA blocked read, which
    serves single-token reads everywhere else: CPUs, programs
    partitioned over a mesh (the kernel carries no ``shard_map``), and
    pools whose pages the kernel cannot view as whole 128-lane rows
    (`paged_decode_pages_per_step` answers 0: a rule on ``page_size``,
    kv heads, head size).  Which lane a single-token read took is
    decided when the op is traced and counted there, once a trace:
    ``pallas.paged_decode.kernel`` / ``pallas.paged_decode.xla_lane``
    (`serving_stats()` shows both).

    Every other read — a prefill chunk (S > 1), a single token where
    the kernel does not run — is `_paged_block_attend`: the new keys are
    written first, then a float32 online softmax runs over as many
    blocks as the longest row has, never over a long slot's capacity.

    ``window`` (a layer that sees only its latest ``window`` positions:
    key ``t`` is visible to query ``s`` iff ``s - window < t <= s``)
    bounds every read from below as well: the kernel starts at the row's
    first in-window page, the XLA lanes mask by position.  The page
    table is then a ring (logical page ``p`` at entry ``p % N``, N the
    table's width; see serving/paged_kv.py).
    """
    psz = int(page_size)
    quant = k_scale is not None
    s_new = q.shape[1] if hasattr(q, "shape") else 0
    n_pages = page_table.shape[1]
    s_cap = n_pages * psz
    off_concrete = None
    try:
        import numpy as _np
        raw = offset._data_ if isinstance(offset, Tensor) else offset
        if not isinstance(raw, jax.core.Tracer):
            off_concrete = _np.asarray(raw)
    except Exception:
        pass   # traced offset: caller owns the bound
    if window is None and off_concrete is not None \
            and (off_concrete + s_new > s_cap).any():
        raise ValueError(
            f"paged KV cache overflow: offset {off_concrete} + {s_new} "
            f"new tokens > page-table capacity {s_cap}")

    from ....pallas import flash_attention as _fa
    from ....utils import monitor as _monitor
    kernels_on = s_new == 1 and _fa._unsharded_kernels_on()

    def fn(qa, ka, va, kp, vp, pt, off, *scales):
        from ....quantization import quantize_kv_rows
        b, s, h_q, d = qa.shape
        off = off.astype(jnp.int32)
        pos = off[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        entry = pos // psz if window is None else (pos // psz) % n_pages
        page_ids = jnp.take_along_axis(pt.astype(jnp.int32), entry, axis=1)
        in_page = pos % psz
        h_kv = ka.shape[2]
        if kp.ndim == 3:
            # a lane-dense pool: a token's [h_kv, d] values are the
            # whole 128-lane rows in_page * token_rows + r of its page.
            # A scatter of single rows: XLA's own scatter on the chip,
            # where one of [token_rows, 128] windows becomes a loop
            token_rows = h_kv * d // 128
            pages = page_ids[..., None]
            rows = in_page[..., None] * token_rows + \
                jnp.arange(token_rows, dtype=jnp.int32)

            def put(pool, vals):
                return pool.at[pages, rows].set(
                    vals.reshape(b, s, token_rows, 128))
        else:
            def put(pool, vals):
                return pool.at[page_ids, in_page].set(vals)
        if quant:
            ks, vs = scales
            qmax = 127.0 if kp.dtype == jnp.int8 else 448.0
            qk, sk = quantize_kv_rows(ka, qmax, kp.dtype)
            qv, sv = quantize_kv_rows(va, qmax, vp.dtype)
            kp, vp = put(kp, qk), put(vp, qv)
            ks = ks.at[page_ids, in_page].set(sk)
            vs = vs.at[page_ids, in_page].set(sv)
        else:
            kp = put(kp, ka.astype(kp.dtype))
            vp = put(vp, va.astype(vp.dtype))
        use_kernel = kernels_on and not (quant and window is not None) \
            and _fa.paged_decode_pages_per_step(
                psz, h_kv, d, kp.dtype.itemsize) > 0
        if s_new == 1:
            _monitor.incr("pallas.paged_decode.kernel" if use_kernel
                          else "pallas.paged_decode.xla_lane")
        if use_kernel:
            out = _fa.paged_decode_attention(
                qa[:, 0], kp, vp, pt.astype(jnp.int32), off,
                scale=scale,
                k_scale=ks if quant else None,
                v_scale=vs if quant else None, window=window,
                h_kv=h_kv)[:, None]
        else:
            out = _paged_block_attend(
                qa, kp, vp, pt, off, *((ks, vs) if quant else ()), psz=psz,
                h_kv=h_kv, scale=None if scale is None else float(scale),
                window=window)
        if quant:
            return out, kp, vp, ks, vs
        return out, kp, vp

    args = (q, k, v, k_pool, v_pool, page_table, offset)
    if quant:
        args = args + (k_scale, v_scale)
    return apply_op("paged_masked_multihead_attention", fn, args)


def paged_cache_attention(q, k, v, cache, scale=None):
    """Attention against one `PagedKVCache` layer dict: dispatches the
    plain or quantized (int8/fp8, per-page scales) paged op, writes the
    functionally-updated pools — and scales, when quantized — back into
    the dict, and returns the attention output.  The single cache-path
    entry point the model families share, so adding a storage format
    never touches four attention call sites again.  A layer dict of
    another store kind is refused by name: a latent store's rows have no
    heads and no V (``paged_latent_attention``)."""
    if "latent_pool" in cache:
        raise ValueError(
            "paged_cache_attention reads a K/V page store; this layer's "
            "cache is a latent page store: call paged_latent_attention")
    if cache.get("k_scale") is not None:
        out, kp, vp, ks, vs = paged_masked_multihead_attention(
            q, k, v, cache["k_pool"], cache["v_pool"],
            cache["page_table"], cache["offset"], cache["page_size"],
            scale=scale, k_scale=cache["k_scale"],
            v_scale=cache["v_scale"], window=cache.get("window"))
        cache["k_scale"], cache["v_scale"] = ks, vs
    else:
        out, kp, vp = paged_masked_multihead_attention(
            q, k, v, cache["k_pool"], cache["v_pool"],
            cache["page_table"], cache["offset"], cache["page_size"],
            scale=scale, window=cache.get("window"))
    cache["k_pool"], cache["v_pool"] = kp, vp
    return out


def paged_latent_attention(q, row, w_kvb, cache, *, nope_dim, scale):
    """Latent (low-rank) attention against one LATENT layer dict of a
    `PagedKVCache` (``latent_pool`` [P, page_size, lanes]: one row
    ``[c, k_r]`` a token, padded to whole lane tiles; the same page
    table and offsets as any paged layer).

    q: [B, S, H, nope + rope] the new tokens' queries, the rope part
    rotated; row: [B, S, rank + rope] their latent rows ``[c, rope(k_r)]``
    (``c`` normed); w_kvb: [rank, H * (nope + v)], a head's columns
    ``[W_uk,h | W_uv,h]``.  Writes the rows through the page table at
    offset..offset+S, then reads:

    - a single token (S = 1) in the ABSORBED form — ``q~_h = W_uk,h^T
      q_nope,h``, every head against the same rows, ``o_h = W_uv,h
      sum_t p_t c_t`` — through the Pallas kernel
      ``pallas.mla.mla_decode_attention`` on a TPU (and in interpret
      mode) or its XLA lane, counted where traced
      (``pallas.mla_decode.kernel`` / ``.xla_lane``);
    - a chunk (S > 1) in the UP-PROJECTED form, ``_latent_block_attend``:
      each gathered block of rows becomes per-head keys and values once.

    Both equal ``softmax(q_h . [W_uk,h c, k_r] * scale) W_uv,h c`` in
    exact arithmetic.  Returns the heads' outputs [B, S, H, v]; the
    updated pool goes back into ``cache``."""
    from ....pallas import mla as _mla
    psz = int(cache["page_size"])
    width = int(cache["latent_width"])
    nope = int(nope_dim)
    scale = float(scale)
    s_new = q.shape[1]

    def fn(qa, ra, wa, pool, pt, off):
        b, s, h, _ = qa.shape
        rank = wa.shape[0]
        if ra.shape[-1] != width:
            raise ValueError(f"latent rows of {ra.shape[-1]} values for a "
                             f"store of rows of {width}")
        lanes = pool.shape[-1]
        off = off.astype(jnp.int32)
        pos = off[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        page_ids = jnp.take_along_axis(pt.astype(jnp.int32), pos // psz,
                                       axis=1)
        pad = ((0, 0), (0, 0), (0, lanes - width))
        pool = pool.at[page_ids, pos % psz].set(
            jnp.pad(ra.astype(pool.dtype), pad))
        if s_new > 1:
            return _latent_block_attend(
                qa, pool, pt, off, wa, psz=psz, nope=nope, width=width,
                scale=scale), pool
        w3 = wa.reshape(rank, h, -1)
        with scope("mla_absorb"):
            qt = jnp.einsum("bhd,chd->bhc", qa[:, 0, :, :nope],
                            w3[..., :nope],
                            preferred_element_type=jnp.float32)
        q_lat = jnp.pad(jnp.concatenate(
            [qt.astype(pool.dtype), qa[:, 0, :, nope:].astype(pool.dtype)],
            axis=-1), pad)
        with scope("mla_decode"):
            ot = _mla.mla_decode(q_lat, pool, pt.astype(jnp.int32), off,
                                 rank, scale)
        with scope("mla_absorb"):
            out = jnp.einsum("bhc,chd->bhd", ot.astype(wa.dtype),
                             w3[..., nope:],
                             preferred_element_type=jnp.float32)
        return out.astype(qa.dtype)[:, None], pool

    out, pool = apply_op(
        "paged_latent_attention", fn,
        (q, row, w_kvb, cache["latent_pool"], cache["page_table"],
         cache["offset"]))
    cache["latent_pool"] = pool
    return out


def fused_linear(x, weight, bias=None, transpose_weight=False, name=None):
    """reference: incubate/nn/functional/fused_matmul_bias.py
    fused_linear — alias of the fused matmul+bias epilogue (XLA fuses)."""
    return fused_matmul_bias(x, weight, bias, transpose_y=transpose_weight)


def fused_linear_activation(x, y, bias=None, trans_x=False, trans_y=False,
                            activation="gelu", name=None):
    """matmul + bias + activation in one fusion (reference:
    fused_gemm_epilogue kernels)."""
    from ....nn import functional as F
    out = fused_matmul_bias(x, y, bias, transpose_x=trans_x,
                            transpose_y=trans_y)
    act = activation or "identity"
    if act in ("none", "identity"):
        return out
    return getattr(F, act)(out)


def fused_dropout_add(x, y, p=0.5, training=True, mode="upscale_in_train",
                      name=None):
    """dropout(x) + y in one pass (reference:
    incubate/nn/functional/fused_dropout_add.py)."""
    from ....nn import functional as F
    return F.dropout(x, p=p, training=training, mode=mode) + y


def fused_bias_dropout_residual_layer_norm(
        x, residual, bias=None, ln_scale=None, ln_bias=None,
        dropout_rate=0.5, ln_epsilon=1e-5, training=True, mode=None,
        name=None):
    """(x + bias) → dropout → + residual → layer_norm, the transformer
    epilogue fusion (reference:
    incubate/nn/functional/fused_bias_dropout_residual_layer_norm)."""
    from ....nn import functional as F
    h = x if bias is None else x + bias
    h = F.dropout(h, p=dropout_rate, training=training)
    h = h + residual
    n = h.shape[-1]
    return F.layer_norm(h, n, weight=ln_scale, bias=ln_bias,
                        epsilon=ln_epsilon)


def fused_feedforward(x, linear1_weight, linear2_weight, linear1_bias=None,
                      linear2_bias=None, ln1_scale=None, ln1_bias=None,
                      ln2_scale=None, ln2_bias=None, dropout1_rate=0.5,
                      dropout2_rate=0.5, activation="relu",
                      ln1_epsilon=1e-5, ln2_epsilon=1e-5,
                      pre_layer_norm=False, training=True, mode=None,
                      name=None):
    """Transformer FFN block as one fusion (reference:
    incubate/nn/functional/fused_transformer.py fused_feedforward)."""
    from ....nn import functional as F
    n = x.shape[-1]
    residual = x
    if pre_layer_norm:
        x = F.layer_norm(x, n, weight=ln1_scale, bias=ln1_bias,
                         epsilon=ln1_epsilon)
    h = fused_matmul_bias(x, linear1_weight, linear1_bias)
    h = getattr(F, activation)(h)
    h = F.dropout(h, p=dropout1_rate, training=training)
    h = fused_matmul_bias(h, linear2_weight, linear2_bias)
    h = F.dropout(h, p=dropout2_rate, training=training)
    out = residual + h
    if not pre_layer_norm:
        out = F.layer_norm(out, n, weight=ln2_scale, bias=ln2_bias,
                           epsilon=ln2_epsilon)
    return out


def fused_multi_head_attention(x, qkv_weight, linear_weight,
                               pre_layer_norm=False, pre_ln_scale=None,
                               pre_ln_bias=None, ln_scale=None,
                               ln_bias=None, pre_ln_epsilon=1e-5,
                               qkv_bias=None, linear_bias=None,
                               cache_kv=None, attn_mask=None,
                               dropout_rate=0.5, attn_dropout_rate=0.5,
                               ln_epsilon=1e-5, training=True,
                               mode=None, ring_id=-1, add_residual=True,
                               num_heads=None, transpose_qkv_wb=False,
                               name=None):
    """Full MHA block fusion (reference:
    incubate/nn/functional/fused_transformer.py
    fused_multi_head_attention).  qkv_weight [3, H, D, E] (the
    reference's fused layout); attention itself rides the Pallas/XLA
    path of scaled_dot_product_attention."""
    from ....nn import functional as F
    b, s, e = x.shape
    residual = x
    h = x
    if pre_layer_norm:
        h = F.layer_norm(h, e, weight=pre_ln_scale, bias=pre_ln_bias,
                         epsilon=pre_ln_epsilon)
    if transpose_qkv_wb:
        nh = num_heads
        qkv = fused_matmul_bias(h, qkv_weight, qkv_bias)  # [B,S,3E]
        qkv = qkv.reshape([b, s, 3, nh, e // nh])
    else:
        nh = qkv_weight.shape[1]
        hd = qkv_weight.shape[2]
        w = qkv_weight.reshape([3 * nh * hd, e]).t()
        qkv = h @ w
        if qkv_bias is not None:
            qkv = qkv + qkv_bias.reshape([-1])
        qkv = qkv.reshape([b, s, 3, nh, hd])
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # [B,S,H,D]
    out = F.scaled_dot_product_attention(
        q, k, v, attn_mask=attn_mask,
        dropout_p=attn_dropout_rate if training else 0.0,
        is_causal=False, training=training)
    out = out.reshape([b, s, -1])
    out = fused_matmul_bias(out, linear_weight, linear_bias)
    out = F.dropout(out, p=dropout_rate, training=training)
    if add_residual:
        out = residual + out
    if not pre_layer_norm:
        out = F.layer_norm(out, e, weight=ln_scale, bias=ln_bias,
                           epsilon=ln_epsilon)
    return out


def fused_multi_transformer(x, ln_scales, ln_biases, qkv_weights,
                            qkv_biases, linear_weights, linear_biases,
                            ffn_ln_scales, ffn_ln_biases, ffn1_weights,
                            ffn1_biases, ffn2_weights, ffn2_biases,
                            pre_layer_norm=True, epsilon=1e-5,
                            cache_kvs=None, pre_caches=None,
                            seq_lens=None, rotary_embs=None,
                            rotary_emb_dims=0, time_step=None,
                            attn_mask=None, dropout_rate=0.0,
                            activation="gelu", training=False,
                            mode=None, trans_qkvw=True, ring_id=-1,
                            name=None):
    """Stacked decoder blocks in one call (reference:
    incubate/nn/functional/fused_transformer.py
    fused_multi_transformer — the inference fast path)."""
    h = x
    for i in range(len(qkv_weights)):
        ln_s = ln_scales[i] if ln_scales else None
        ln_b = ln_biases[i] if ln_biases else None
        h = fused_multi_head_attention(
            h, qkv_weights[i], linear_weights[i],
            pre_layer_norm=pre_layer_norm,
            pre_ln_scale=ln_s if pre_layer_norm else None,
            pre_ln_bias=ln_b if pre_layer_norm else None,
            ln_scale=None if pre_layer_norm else ln_s,
            ln_bias=None if pre_layer_norm else ln_b,
            qkv_bias=qkv_biases[i] if qkv_biases else None,
            linear_bias=linear_biases[i] if linear_biases else None,
            attn_mask=attn_mask, dropout_rate=dropout_rate,
            attn_dropout_rate=dropout_rate, ln_epsilon=epsilon,
            training=training)
        ffn_s = ffn_ln_scales[i] if ffn_ln_scales else None
        ffn_b = ffn_ln_biases[i] if ffn_ln_biases else None
        h = fused_feedforward(
            h, ffn1_weights[i], ffn2_weights[i],
            linear1_bias=ffn1_biases[i] if ffn1_biases else None,
            linear2_bias=ffn2_biases[i] if ffn2_biases else None,
            ln1_scale=ffn_s if pre_layer_norm else None,
            ln1_bias=ffn_b if pre_layer_norm else None,
            ln2_scale=None if pre_layer_norm else ffn_s,
            ln2_bias=None if pre_layer_norm else ffn_b,
            dropout1_rate=dropout_rate, dropout2_rate=dropout_rate,
            activation=activation, ln1_epsilon=epsilon,
            ln2_epsilon=epsilon, pre_layer_norm=pre_layer_norm,
            training=training)
    return h


def fused_ec_moe(x, gate, bmm0_weight, bmm0_bias, bmm1_weight,
                 bmm1_bias, act_type="gelu", name=None):
    """Expert-choice MoE FFN fusion (reference:
    incubate/nn/functional/fused_ec_moe.py — fused_ec_moe(x, gate,
    bmm0_weight, bmm0_bias, bmm1_weight, bmm1_bias, act_type)): `gate`
    is the precomputed [B, S, E] gate logits; dense einsum dispatch over
    the expert dim — the MXU-friendly realization."""
    from ....nn import functional as F
    gates = F.softmax(gate, axis=-1)                   # [B,S,E]
    h = jnp_einsum("bsd,edh->bseh", x, bmm0_weight)
    if bmm0_bias is not None:
        h = h + bmm0_bias[:, 0]                        # [E,H] broadcast
    h = getattr(F, act_type)(h)
    out = jnp_einsum("bseh,ehd->bsed", h, bmm1_weight)
    if bmm1_bias is not None:
        out = out + bmm1_bias[:, 0]
    return (out * gates.unsqueeze(-1)).sum(axis=2)


def jnp_einsum(eq, *ops):
    from ....tensor_ops.linalg import einsum
    return einsum(eq, *ops)
