"""Context parallelism for long sequences: ring attention + Ulysses.

Reference capability: the snapshot's long-context story is Megatron-SP +
the `sep` hybrid axis (reference: fleet/utils/sequence_parallel_utils.py,
fleet/base/topology.py:184 sep groups, meta_parallel/segment_parallel.py:26)
— it has NO ring attention (SURVEY.md §5 'Long-context'); this module
exceeds the reference, as the survey prescribes, with the two standard
context-parallel schemes:

1. **Ring attention** (`ring_flash_attention`): tokens sharded over `sep`;
   K/V blocks rotate around the ICI ring via `ppermute` while each step
   folds one block into a numerically-stable running softmax (the blockwise
   log-sum-exp merge of flash attention).  Compute and the neighbor
   exchange overlap — the ring rides the ICI torus.
2. **Ulysses / all-to-all sequence parallelism** (`ulysses_attention`):
   all-to-all re-shards activations seq→heads, runs full (flash) attention
   locally on head-sharded tensors, and all-to-alls back heads→seq.

Both are in-graph: wrapped in `shard_map` over the mesh and registered as
framework ops, so autograd and `to_static` see them like any other op.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..core.tensor import Tensor
from ..core.dispatch import apply_op
from .mesh import get_mesh, inside_manual_region


def _ring_attention_local(q, k, v, axis, causal, scale):
    """Per-shard ring attention body. q/k/v: [B, S_local, H, D] with the
    sequence dim sharded over `axis`."""
    size = lax.psum(1, axis)
    me = lax.axis_index(axis)
    b, s, h, d = q.shape

    qt = q.astype(jnp.float32).transpose(0, 2, 1, 3)   # [B,H,Sq,D]

    m0 = jnp.full((b, h, s), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, s), jnp.float32)
    acc0 = jnp.zeros((b, h, s, d), jnp.float32)

    def step(carry, t):
        m, l, acc, kb, vb = carry
        # block index currently resident: blocks rotate k/v to rank+1 each
        # tick, so at tick t we hold block (me - t) mod size
        j = (me - t) % size
        kt = kb.astype(jnp.float32).transpose(0, 2, 1, 3)   # [B,H,Sk,D]
        vt = vb.astype(jnp.float32).transpose(0, 2, 1, 3)
        scores = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
        if causal:
            # global positions: q row = me*s + iq, k col = j*s + ik
            iq = me * s + jnp.arange(s)[:, None]
            ik = j * s + jnp.arange(s)[None, :]
            scores = jnp.where(ik <= iq, scores, -jnp.inf)
        blk_max = jnp.max(scores, axis=-1)                   # [B,H,Sq]
        new_m = jnp.maximum(m, blk_max)
        # guard fully-masked rows (new_m = -inf): keep them at zero weight
        safe_m = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
        p = jnp.exp(scores - safe_m[..., None])
        p = jnp.where(jnp.isfinite(scores), p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vt)
        perm = [(i, (i + 1) % size) for i in range(size)]
        kb = lax.ppermute(kb, axis, perm)
        vb = lax.ppermute(vb, axis, perm)
        return (new_m, l, acc, kb, vb), ()

    (m, l, acc, _, _), _ = lax.scan(
        step, (m0, l0, acc0, k, v), jnp.arange(size))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)        # [B,S,H,D]


def ring_flash_attention(query, key, value, axis="sep", mesh=None,
                         causal=True, scale=None):
    """Tensor-level ring attention op: [B, S, H, D], S sharded over `axis`.

    Output sharding matches the input (seq-sharded over `axis`)."""
    mesh = mesh or get_mesh()
    if mesh is None or axis not in mesh.dim_names \
            or mesh.get_dim_size(axis) <= 1 or inside_manual_region():
        from ..pallas.flash_attention import flash_attention
        return flash_attention(query, key, value, causal=causal, scale=scale)

    jmesh = mesh.jax_mesh
    sc = scale if scale is not None else \
        1.0 / math.sqrt(int(query.shape[-1]))
    batch_axis = "dp" if "dp" in mesh.dim_names else None
    spec = P(batch_axis, axis, None, None)

    body = functools.partial(_ring_attention_local, axis=axis,
                             causal=causal, scale=sc)
    smapped = shard_map(body, mesh=jmesh, in_specs=(spec, spec, spec),
                        out_specs=spec, check_vma=False)

    return apply_op("ring_flash_attention",
                    lambda q, k, v: smapped(
                        jax.lax.with_sharding_constraint(
                            q, jax.sharding.NamedSharding(jmesh, spec)),
                        jax.lax.with_sharding_constraint(
                            k, jax.sharding.NamedSharding(jmesh, spec)),
                        jax.lax.with_sharding_constraint(
                            v, jax.sharding.NamedSharding(jmesh, spec))),
                    (query, key, value))


def _ulysses_local(q, k, v, axis, causal, scale, dropout_key=None):
    """all-to-all seq→heads, local full attention, all-to-all heads→seq.
    Local shapes: [B, S/sep, H, D] → [B, S, H/sep, D] → back."""
    def seq2head(t):
        return lax.all_to_all(t, axis, split_axis=2, concat_axis=1,
                              tiled=True)

    def head2seq(t):
        return lax.all_to_all(t, axis, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = seq2head(q), seq2head(k), seq2head(v)
    b, s, h, d = qh.shape
    qt = qh.astype(jnp.float32).transpose(0, 2, 1, 3)
    kt = kh.astype(jnp.float32).transpose(0, 2, 1, 3)
    vt = vh.astype(jnp.float32).transpose(0, 2, 1, 3)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
    if causal:
        iq = jnp.arange(s)[:, None]
        ik = jnp.arange(s)[None, :]
        scores = jnp.where(ik <= iq, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vt).transpose(0, 2, 1, 3)
    return head2seq(out.astype(q.dtype))


def ulysses_attention(query, key, value, axis="sep", mesh=None, causal=True,
                      scale=None):
    """DeepSpeed-Ulysses style sequence parallelism: requires
    num_heads % sep_degree == 0."""
    mesh = mesh or get_mesh()
    if mesh is None or axis not in mesh.dim_names \
            or mesh.get_dim_size(axis) <= 1 or inside_manual_region():
        from ..pallas.flash_attention import flash_attention
        return flash_attention(query, key, value, causal=causal, scale=scale)
    deg = mesh.get_dim_size(axis)
    h = int(query.shape[2])
    if h % deg != 0:
        raise ValueError(
            f"ulysses needs num_heads ({h}) divisible by {axis} degree "
            f"({deg}); use ring_flash_attention instead")

    jmesh = mesh.jax_mesh
    sc = scale if scale is not None else \
        1.0 / math.sqrt(int(query.shape[-1]))
    batch_axis = "dp" if "dp" in mesh.dim_names else None
    spec = P(batch_axis, axis, None, None)

    body = functools.partial(_ulysses_local, axis=axis, causal=causal,
                             scale=sc)
    smapped = shard_map(body, mesh=jmesh, in_specs=(spec, spec, spec),
                        out_specs=spec, check_vma=False)

    return apply_op("ulysses_attention",
                    lambda q, k, v: smapped(
                        jax.lax.with_sharding_constraint(
                            q, jax.sharding.NamedSharding(jmesh, spec)),
                        jax.lax.with_sharding_constraint(
                            k, jax.sharding.NamedSharding(jmesh, spec)),
                        jax.lax.with_sharding_constraint(
                            v, jax.sharding.NamedSharding(jmesh, spec))),
                    (query, key, value))


def split_sequence(x, axis="sep", mesh=None, seq_dim=1):
    """Commit a [B, S, ...] tensor seq-sharded over `axis` (the sep-scatter
    entering a context-parallel region)."""
    from .api import shard_constraint
    from .placement import Shard, Replicate
    mesh = mesh or get_mesh()
    if mesh is None or axis not in mesh.dim_names:
        return x
    placements = [Shard(seq_dim) if n == axis else Replicate()
                  for n in mesh.dim_names]
    return shard_constraint(x, mesh, placements=placements)
