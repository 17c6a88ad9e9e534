"""Sparse experts in one static-shaped program: sorted (token, expert)
pairs, the grouped matrix product ``expert_gmm`` and its XLA lane.

A token's router picks ``k`` of ``E`` experts; a chip holds ``E_held``
consecutive ones (``held`` = (first, count)) and computes the part of
the layer's result that they give.  Nothing is capped and no token is
dropped: the (token, expert) pairs whose expert is held are SORTED BY
EXPERT into one buffer of static size, each expert's rows padded to whole
row tiles (``group_pairs``), so that a row tile belongs to one expert
and the product over the stacked weights is one kernel:

    out[rows of tile t] = x[rows of tile t] @ w[tile_expert[t]]

``expert_gmm`` walks the tiles in use and no others: the tile -> expert
map and the number of tiles in use ride scalar prefetch, a tile past the
last in use repeats the last one's block indices (no copy is issued for
a block that did not change) and computes nothing, and an expert that no
token chose has no tile, so its weights are never read.  The cost
follows the pairs routed here, not tokens x experts.

The XLA lane (``jax.lax.ragged_dot`` over the same sorted buffer) is the
kernel's reference and the lane of CPUs and partitioned programs; which
lane a product took is decided when the op is traced and counted there
(``pallas.expert_gmm.kernel`` / ``pallas.expert_gmm.xla_lane``), as the
paged decode kernel's is.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .flash_attention import (_interpret, _mxu_f32,
                              _unsharded_kernels_on)

#: bytes of one weight block a grid step streams (each of gate and up)
_WEIGHT_BLOCK_BYTES = 4 << 20
_VMEM_LIMIT = 100 << 20


def router_logits(x, w):
    """``x`` [T, h] times the router ``w`` [h, E] in float32 at the
    highest precision, whatever the weights' type: a score rounded to
    bfloat16 chooses other experts."""
    return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def route_sigmoid_topk(router_logits, k, bias=None, scale=None):
    """``router_logits`` [T, E] float32 -> (experts [T, k] int32, gates
    [T, k] float32): the ``k`` largest sigmoid scores, normalised to sum
    1 over the chosen.  ``bias`` [E] (a selection bias, as bias-corrected
    load balancing keeps one) is added to the scores for the CHOICE
    alone: the gates are the chosen experts' unbiased scores.  ``scale``
    multiplies the normalised gates (a routed scaling factor).  With
    neither, the program is what it was before either existed."""
    scores = jax.nn.sigmoid(router_logits.astype(jnp.float32))
    if bias is None:
        top, idx = jax.lax.top_k(scores, k)
    else:
        _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
        top = jnp.take_along_axis(scores, idx, axis=-1)
    experts = idx.astype(jnp.int32)
    gates = top / jnp.sum(top, axis=-1, keepdims=True)
    return experts, gates if scale is None else gates * scale


def route_softmax_topk(router_logits, k):
    """``router_logits`` [T, E] float32 -> (experts [T, k] int32, gates
    [T, k] float32): softmax over ALL ``E`` experts, its ``k`` largest
    probabilities, renormalised to sum 1 over the chosen
    (``norm_topk_prob``, as the Qwen-MoE and OLMoE families route)."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    return idx.astype(jnp.int32), top / jnp.sum(top, axis=-1, keepdims=True)


#: the router's scorings: ``route_<scoring>_topk``
SCORINGS = ("sigmoid", "softmax")


def row_tile(tokens):
    """Rows of one tile of the sorted buffer, from the call's tokens: a
    decode tick's few rows an expert want the smallest tile the matrix
    unit takes; a prefill chunk's tens a tile that keeps it busy while an
    expert's weights stream.  A 128-token tick of 22 pairs a token over
    128 of 512 experts (~5.5 rows an expert) took 2,096 us a layer at
    16 rows and 2,461 at 128 (v5e,
    ``docs/perf/ubench_relu2_gmm.pr38.log``).  Only a call of 65-128
    tokens took another tile when the bound moved from 64: no other
    cell makes one (ticks of 32 and 56 rows; prefill members of whole
    512-token chunks a row)."""
    return 16 if tokens <= 128 else 128 if tokens <= 1024 else 256


class Groups(NamedTuple):
    """Held pairs sorted by expert (``group_pairs``)."""
    row_token: jax.Array      # [M] int32: the token of each buffer row, T = none
    pair_row: jax.Array       # [T, kk] int32: the buffer row of each pair
    pair_gate: jax.Array      # [T, kk] float32: its gate, 0 where not held
    tile_expert: jax.Array    # [M // tm] int32: the (local) expert of a tile
    tiles_used: jax.Array     # [1] int32
    sizes: jax.Array          # [E_held] int32: rows of each group, padded
    counts: jax.Array         # [E_held] int32: pairs of each held expert


def group_pairs(experts, gates, held, tm, valid=None):
    """Sort the pairs of held experts by expert.  ``experts``/``gates``
    [T, k]; ``held`` = (first expert id, how many).  ``valid`` [T] bool
    leaves a token's pairs out of ``counts`` (a dead row of a static
    batch is computed and not counted).  Static sizes throughout: the
    buffer has ``T * min(k, E_held)`` rows for pairs plus a tile of
    padding an expert."""
    first, n_held = held
    t, k = experts.shape
    kk = min(k, n_held)
    local = experts - first
    is_held = (local >= 0) & (local < n_held)
    if kk < k:
        # at most ``kk`` of a token's picks can be held: those first
        order = jnp.argsort(~is_held, axis=1, stable=True)[:, :kk]
        local = jnp.take_along_axis(local, order, axis=1)
        gates = jnp.take_along_axis(gates, order, axis=1)
        is_held = jnp.take_along_axis(is_held, order, axis=1)
    local = jnp.where(is_held, local, n_held).reshape(-1)       # [P]
    p = t * kk
    m = p + n_held * tm
    m = -(-m // tm) * tm
    one_hot = local[:, None] == jnp.arange(n_held)[None, :]
    raw = jnp.sum(one_hot, axis=0).astype(jnp.int32)            # [E_held]
    if valid is None:
        counts = raw
    else:
        counts = jnp.sum(one_hot & jnp.repeat(valid, kk)[:, None],
                         axis=0).astype(jnp.int32)
    sizes = -(-raw // tm) * tm
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    # rank of a pair inside its group, in pair order
    rank = jnp.cumsum(one_hot, axis=0) - 1
    rank = jnp.take_along_axis(
        rank, jnp.minimum(local, n_held - 1)[:, None], axis=1)[:, 0]
    row = jnp.where(local < n_held,
                    starts[jnp.minimum(local, n_held - 1)] + rank, m)
    token = jnp.repeat(jnp.arange(t, dtype=jnp.int32), kk)
    row_token = jnp.full((m,), t, jnp.int32).at[row].set(token,
                                                         mode="drop")
    tiles = m // tm
    tile_start = jnp.arange(tiles, dtype=jnp.int32) * tm
    tiles_used = (ends[-1] // tm).astype(jnp.int32)
    tile_expert = jnp.searchsorted(ends, tile_start, side="right")
    # a tile past the last in use names the last one's expert again
    last = tile_expert[jnp.maximum(tiles_used - 1, 0)]
    tile_expert = jnp.where(tile_start < ends[-1], tile_expert,
                            last).astype(jnp.int32)
    tile_expert = jnp.minimum(tile_expert, n_held - 1)
    return Groups(row_token, row.reshape(t, kk).astype(jnp.int32),
                  jnp.where(is_held, gates, 0.0).reshape(t, kk),
                  tile_expert, tiles_used.reshape(1), sizes, counts)


# ------------------------------------------------------------------
# the grouped matrix product
# ------------------------------------------------------------------

def _gmm_kernel(te_ref, used_ref, x_ref, *rest, act):
    from jax.experimental import pallas as pl
    del te_ref                      # consumed by the index maps
    if act == "swiglu":
        wg_ref, wu_ref, o_ref = rest
    else:
        w_ref, o_ref = rest

    @pl.when(pl.program_id(0) < used_ref[0])
    def _tile():
        # float32 sums with no bit of an operand dropped, whatever the
        # process's default matmul precision
        x = x_ref[...]
        if act == "swiglu":
            g = _mxu_f32(x, wg_ref[...], 0)
            u = _mxu_f32(x, wu_ref[...], 0)
            o_ref[...] = (g * jax.nn.sigmoid(g) * u).astype(o_ref.dtype)
        else:
            y = _mxu_f32(x, w_ref[...], 0)
            if act == "relu2":
                y = jnp.square(jnp.maximum(y, 0.0))
            o_ref[...] = y.astype(o_ref.dtype)


#: the up product's activation -> (the weights it takes, the kernel's
#: name in a trace); None: a plain product (the down projection)
ACTS = {"swiglu": (2, "expert_gmm"), "relu2": (1, "expert_gmm_relu2"),
        None: (1, "expert_gmm")}


def gmm_block_n(k, n, itemsize):
    """Columns of one weight block: whole K by as many columns as
    ``_WEIGHT_BLOCK_BYTES`` hold, a multiple of 128 that divides N; 0
    where the shapes are not whole (8, 128) tiles and the XLA lane runs."""
    if k % 128 or n % 128:
        return 0
    tn = max(128, min(n, _WEIGHT_BLOCK_BYTES // (k * itemsize)) // 128 * 128)
    while n % tn:
        tn -= 128
    return tn


@functools.partial(jax.jit,
                   static_argnames=("tm", "tn", "interpret", "act"))
def _gmm_call(x, weights, tile_expert, tiles_used, *, tm, tn, interpret,
              act):
    """``expert_gmm`` at fixed tiles; a program of its own inside the
    caller's, so the layers of a model lower ONE kernel a shape."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = x.shape
    n = weights[0].shape[2]
    n_blocks = n // tn

    def tile_of(t, used):
        return jnp.maximum(jnp.minimum(t, used[0] - 1), 0)

    def col_of(t, j, used):
        # a tile past the last in use keeps the last block where it is
        return jnp.where(t < used[0], j, n_blocks - 1)

    x_spec = pl.BlockSpec((tm, k),
                          lambda t, j, te, used: (tile_of(t, used), 0))
    w_spec = pl.BlockSpec(
        (None, k, tn),
        lambda t, j, te, used: (te[tile_of(t, used)], 0, col_of(t, j, used)))
    o_spec = pl.BlockSpec(
        (tm, tn),
        lambda t, j, te, used: (tile_of(t, used), col_of(t, j, used)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(m // tm, n_blocks),
        in_specs=[x_spec] + [w_spec] * len(weights), out_specs=o_spec)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, act=act),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=pltpu.InterpretParams() if interpret else False,
        name=ACTS[act][1],
    )(tile_expert, tiles_used, x, *weights)


def expert_gmm_xla(x, weights, sizes, act=None):
    """The XLA lane: ``ragged_dot`` over the same sorted buffer (rows
    past the groups come back zero), ``act`` as ``expert_gmm`` takes it."""
    prods = [jax.lax.ragged_dot(x, w, sizes,
                                preferred_element_type=jnp.float32)
             for w in weights]
    if act == "swiglu":
        out = jax.nn.silu(prods[0]) * prods[1]
    elif act == "relu2":
        out = jnp.square(jnp.maximum(prods[0], 0.0))
    else:
        out = prods[0]
    return out.astype(x.dtype)


def kernel_hosts(x, weights, tm):
    """Whether ``expert_gmm``'s kernel takes these shapes here."""
    k, n = weights[0].shape[1:]
    return _unsharded_kernels_on() \
        and tm % (32 // x.dtype.itemsize) == 0 \
        and gmm_block_n(k, n, weights[0].dtype.itemsize) > 0 \
        and x.dtype == weights[0].dtype


def expert_gmm(x, weights, groups, tm, lane=None, act=None):
    """``x`` [M, K], rows sorted by expert in tiles of ``tm``
    (``group_pairs``), times the stacked ``weights`` ([E_held, K, N]):
    (gate, up) with ``act`` "swiglu" gives ``silu(x g) * (x u)``; one
    matrix with ``act`` "relu2" gives ``relu(x w)^2``, with None the
    plain product.  Rows of no pair come back as whatever the
    lane leaves there (zero or unwritten): the caller reads pair rows
    only.  ``lane`` forces ``"kernel"`` or ``"xla"``; None asks
    ``kernel_hosts``."""
    from ..utils import monitor
    weights = tuple(weights)
    if ACTS[act][0] != len(weights):
        raise ValueError(f"act {act!r} takes {ACTS[act][0]} weight "
                         f"matrices, not {len(weights)}")
    use_kernel = kernel_hosts(x, weights, tm) if lane is None \
        else lane == "kernel"
    lane_name = "kernel" if use_kernel else "xla_lane"
    monitor.incr("pallas.expert_gmm." + lane_name)
    if act == "relu2":
        monitor.incr("pallas.expert_gmm.relu2." + lane_name)
    if not use_kernel:
        return expert_gmm_xla(x, weights, groups.sizes, act)
    k, n = weights[0].shape[1:]
    return _gmm_call(x, weights, groups.tile_expert, groups.tiles_used,
                     tm=tm, tn=gmm_block_n(k, n, weights[0].dtype.itemsize),
                     interpret=_interpret(), act=act)


def routed_experts(x, experts, gates, w_gate, w_up, w_down, held, valid=None,
                   lane=None, act="swiglu"):
    """The held experts' part of a sparse layer: ``sum_{e in T(t), e
    held} g_e f_e(x_t)``, each expert ``f_e`` of the activation ``act``:
    "swiglu", ``f_e(x) = (silu(x Wg_e) * (x Wu_e)) Wd_e``; "relu2",
    ``f_e(x) = relu(x Wu_e)^2 Wd_e`` (no gate: ``w_gate`` is None).
    ``x`` [T, h]; ``experts``/``gates`` [T, k] over ALL experts (gates
    normalised over all k chosen); the stacked weights hold the
    ``held[1]`` experts from ``held[0]`` on.  Returns (y [T, h],
    counts [E_held] int32: pairs computed for each held expert)."""
    t, h = x.shape
    tm = row_tile(t)
    groups = group_pairs(experts, gates, held, tm, valid)
    x_pad = jnp.concatenate([x, jnp.zeros((1, h), x.dtype)], axis=0)
    xs = x_pad[groups.row_token]                                 # [M, h]
    ups = (w_up,) if w_gate is None else (w_gate, w_up)
    act_out = expert_gmm(xs, ups, groups, tm, lane, act=act)
    out = expert_gmm(act_out, (w_down,), groups, tm, lane, act=None)
    m = out.shape[0]
    rows = groups.pair_row
    picked = out[jnp.minimum(rows, m - 1)]                       # [T, kk, h]
    picked = jnp.where((rows < m)[..., None], picked.astype(jnp.float32),
                       0.0)
    y = jnp.sum(picked * groups.pair_gate[..., None], axis=1)
    return y.astype(x.dtype), groups.counts
