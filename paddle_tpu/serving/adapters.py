"""Multi-tenant LoRA serving: a fixed adapter pool + batched gathered
low-rank updates over ONE base model.

Reference capability: S-LoRA / Punica — thousands of per-customer LoRA
adapters multiplexed over one deployed base model, with adapter weights
paged into a fixed device pool and heterogeneous-adapter batches served by
gathered low-rank matmuls.  TPU-native realization: the same static-shape
discipline as ``PagedKVCache`` and the compiled tick.  Every wrapped
projection owns preallocated stacks ``A [P, in, rank_pool]`` /
``B [P, rank_pool, out]`` / ``scale [P]`` with ``P = max_adapters + 1``;
pool slot 0 is permanently zero, so ``adapter_idx 0`` is an exact identity
and base-model requests ride the SAME program as adapter requests.
Adapters of any rank <= rank_pool are zero-padded into their slot (padding
columns multiply into exact zeros, so the padded update equals the unpadded
one).  A per-scheduler-slot int32 index vector selects each row's adapter:

    y += matmul(matmul(x, gather(A, idx)), gather(B, idx)) * gather(scale, idx)

static shapes throughout — one batched decode step serves any adapter mix.

Compiled-tick compatibility costs NOTHING here by construction: the delta
is computed by a framework op (``serving_lora_delta``), so the discovery
pass auto-captures the pool stacks and index vector into the tick's
re-gathered captures.  Hot-loading an adapter or re-pointing a slot just
swaps the capture's buffer — the jit signature never changes and the next
tick reads the new weights.

LRU protocol: adapters are hot-loaded into free pool slots; when the pool
is full, the least-recently-used slot with ZERO in-flight requests is
evicted (eviction never interrupts an in-flight request — pinned slots are
skipped, and admission backpressures when every slot is pinned).

Stretch lane: ``FLAGS_pallas_lora`` routes the update through a fused
Pallas gather-matmul kernel (scalar-prefetched adapter indices drive the
A/B block DMA directly — no materialized gathered copies), interpret-mode
tested on CPU; the XLA gather path stays the bit-equality default.
"""
from __future__ import annotations

import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dispatch import defop
from ..core.tensor import Tensor
from ..nn.layers_common import Linear
from ..nn.lora import DEFAULT_TARGETS, load_adapter_state
from ..utils.flags import flag
from . import stats
from .api import AdapterConfigError


# The active (pool, idx Tensor) while an engine model call is being
# adapted; None everywhere else, so patched projections are an exact
# pass-through for generate()/training/other engines sharing the model.
_ACTIVE = None


def _use_pallas():
    if not flag("FLAGS_pallas_lora"):
        return False
    from ..pallas.flash_attention import _unsharded_kernels_on
    return _unsharded_kernels_on()


def _pallas_delta(x, a_stack, b_stack, scale, idx):
    """Fused gather-matmul: grid over batch rows, the scalar-prefetched
    ``idx`` drives the A/B BlockSpec index maps, so each row's adapter
    blocks DMA straight from the pool — no gathered copies."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ..pallas.flash_attention import _interpret

    ns, seq, din = x.shape
    _, _, rp = a_stack.shape
    dout = b_stack.shape[-1]

    def kernel(idx_ref, x_ref, a_ref, b_ref, s_ref, out_ref):
        i = pl.program_id(0)
        s = s_ref[idx_ref[i]]
        xa = jnp.dot(x_ref[:].astype(jnp.float32),
                     a_ref[:].astype(jnp.float32),
                     preferred_element_type=jnp.float32)
        d = jnp.dot(xa, b_ref[:].astype(jnp.float32),
                    preferred_element_type=jnp.float32)
        out_ref[:] = (d * s).astype(out_ref.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(ns,),
        in_specs=[
            pl.BlockSpec((None, seq, din), lambda i, idx_ref: (i, 0, 0)),
            pl.BlockSpec((None, din, rp),
                         lambda i, idx_ref: (idx_ref[i], 0, 0)),
            pl.BlockSpec((None, rp, dout),
                         lambda i, idx_ref: (idx_ref[i], 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((None, seq, dout),
                               lambda i, idx_ref: (i, 0, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((ns, seq, dout), x.dtype),
        interpret=_interpret(),
    )(idx, x, a_stack, b_stack, scale.astype(jnp.float32))


@defop("serving_lora_delta", nondiff=True)
def lora_delta(y, x, a_stack, b_stack, scale, idx):
    """``y + (x @ A[idx]) @ B[idx] * scale[idx]`` per batch row.  A
    framework op so the compiled tick's discovery pass captures the pool
    stacks and index vector (hot-loads flow into the compiled program
    through the re-gathered captures, no retrace)."""
    if _use_pallas():
        return y + _pallas_delta(x, a_stack, b_stack, scale, idx)
    a = jnp.take(a_stack, idx, axis=0)
    b = jnp.take(b_stack, idx, axis=0)
    s = jnp.take(scale, idx, axis=0)
    d = jnp.matmul(jnp.matmul(x, a), b)
    return y + d * s[:, None, None]


class _Activation:
    __slots__ = ("pool", "idx")

    def __init__(self, pool, idx):
        self.pool = pool
        self.idx = idx


class _LayerStacks:
    __slots__ = ("A", "B", "scale", "in_features", "out_features")

    def __init__(self, in_features, out_features, pool_size, rank_pool,
                 dtype):
        self.in_features = in_features
        self.out_features = out_features
        self.A = Tensor(jnp.zeros((pool_size, in_features, rank_pool),
                                  dtype))
        self.B = Tensor(jnp.zeros((pool_size, rank_pool, out_features),
                                  dtype))
        self.scale = Tensor(jnp.zeros((pool_size,), dtype))
        self.A.stop_gradient = True
        self.B.stop_gradient = True
        self.scale.stop_gradient = True


class _AdapterEntry:
    __slots__ = ("layers", "rank", "alpha")

    def __init__(self, layers, rank, alpha):
        self.layers = layers
        self.rank = rank
        self.alpha = alpha


def _patch_linear(layer, qual_name):
    """Instance-level forward patch (idempotent).  NOT a forward hook —
    the compiled tick refuses models with layer hooks; an instance
    ``forward`` attribute is invisible to that check and to every other
    user of the layer (the patch is a no-op unless an activation is
    live AND this layer has pool stacks)."""
    if getattr(layer, "_lora_serving_name", None) is not None:
        return
    orig = layer.forward

    def patched(x, _orig=orig, _name=qual_name):
        y = _orig(x)
        act = _ACTIVE
        if act is None:
            return y
        ent = act.pool._stacks.get(_name)
        if ent is None:
            return y
        return lora_delta(y, x, ent.A, ent.B, ent.scale, act.idx)

    layer.forward = patched
    layer._lora_serving_name = qual_name


class AdapterPool:
    """Fixed device pool of hot-loaded adapters for one base model.

    ``max_adapters`` concurrent adapters (pool slot 0 is the reserved
    identity), each padded to ``rank_pool``.  ``register`` validates an
    adapter against the base model's projection shapes at construction
    time; ``acquire``/``release`` pin slots around in-flight requests;
    LRU eviction recycles only unpinned slots.
    """

    def __init__(self, model, max_adapters, rank_pool, num_rows,
                 targets=None):
        max_adapters = int(max_adapters)
        rank_pool = int(rank_pool)
        if max_adapters < 1:
            raise AdapterConfigError(
                f"max_adapters must be >= 1 to build an AdapterPool, "
                f"got {max_adapters}")
        if rank_pool < 1:
            raise AdapterConfigError(
                f"adapter_rank_pool must be >= 1, got {rank_pool}")
        self.max_adapters = max_adapters
        self.rank_pool = rank_pool
        self.pool_size = max_adapters + 1
        targets = tuple(targets) if targets is not None else DEFAULT_TARGETS
        self._stacks = {}
        for name, layer in model.named_sublayers():
            leaf = name.rsplit(".", 1)[-1]
            if leaf not in targets or not isinstance(layer, Linear):
                continue
            dtype = layer.weight._data_.dtype
            self._stacks[name] = _LayerStacks(
                int(layer.weight.shape[0]), int(layer.weight.shape[1]),
                self.pool_size, rank_pool, dtype)
            _patch_linear(layer, name)
        if not self._stacks:
            raise AdapterConfigError(
                f"AdapterPool found no Linear projections matching "
                f"targets {targets} on {type(model).__name__}")
        self._registry = {}
        # slot 0 = identity, never assigned/evicted
        self._slot_ids = [None] * self.pool_size
        self._slot_of = {}
        self._refs = [0] * self.pool_size
        self._last_use = [0] * self.pool_size
        self._use_tick = 0
        # per-scheduler-slot adapter index (row -> pool slot); the ONE
        # index vector the decode/tick lane gathers through
        self._idx_np = np.zeros((int(num_rows),), np.int32)
        self.idx = Tensor(jnp.asarray(self._idx_np))
        self.idx.stop_gradient = True

    # ---------------- registry ----------------
    def register(self, adapter_id, source):
        """Validate + register an adapter (path to a ``save_adapter``
        artifact, or an in-memory ``adapter_spec`` dict).  Raises
        ``AdapterConfigError`` on any infeasible config — rank over the
        pool's rank budget, unknown projection name, or factor shapes
        that don't match the base model's projections."""
        adapter_id = str(adapter_id)
        if not adapter_id:
            raise AdapterConfigError("adapter_id must be a non-empty "
                                     "string")
        spec = load_adapter_state(source) if isinstance(source, str) \
            else source
        if not isinstance(spec, dict) or not spec:
            raise AdapterConfigError(
                f"adapter {adapter_id!r}: spec must be a non-empty dict "
                f"of layer_name -> factors (got {type(spec).__name__})")
        layers, rank, alpha = {}, None, None
        for name, st in spec.items():
            if name not in self._stacks:
                raise AdapterConfigError(
                    f"adapter {adapter_id!r} targets projection "
                    f"{name!r} which the base model does not have "
                    f"(pool projections: {sorted(self._stacks)})")
            ent = self._stacks[name]
            A = np.asarray(st["A"])
            B = np.asarray(st["B"])
            r = int(st.get("rank", A.shape[-1]))
            if r > self.rank_pool:
                raise AdapterConfigError(
                    f"adapter {adapter_id!r} layer {name!r} has rank "
                    f"{r} > adapter_rank_pool {self.rank_pool}")
            if A.shape != (ent.in_features, r):
                raise AdapterConfigError(
                    f"adapter {adapter_id!r} layer {name!r}: lora_A "
                    f"shape {A.shape} does not match base projection "
                    f"[{ent.in_features}, rank={r}] — width/vocab "
                    f"mismatch vs the base model")
            if B.shape != (r, ent.out_features):
                raise AdapterConfigError(
                    f"adapter {adapter_id!r} layer {name!r}: lora_B "
                    f"shape {B.shape} does not match "
                    f"[rank={r}, {ent.out_features}] — width/vocab "
                    f"mismatch vs the base model")
            a = float(st.get("alpha", r))
            layers[name] = (A, B, a / float(r))
            rank = max(rank or 0, r)
            alpha = a
        self._registry[adapter_id] = _AdapterEntry(layers, rank, alpha)
        return adapter_id

    def known_ids(self):
        return sorted(self._registry)

    def loaded_ids(self):
        """Adapter ids currently resident in pool slots (gossip payload
        for router affinity)."""
        return sorted(self._slot_of)

    # ---------------- slot lifecycle ----------------
    def acquire(self, adapter_id):
        """Pin ``adapter_id``'s pool slot for one in-flight request,
        hot-loading it first if absent.  Returns the pool slot index, or
        None when every slot is pinned by in-flight requests (the caller
        backpressures admission — eviction never interrupts a request)."""
        slot = self._slot_of.get(adapter_id)
        if slot is None:
            slot = self._load(adapter_id)
            if slot is None:
                return None
        self._refs[slot] += 1
        self._use_tick += 1
        self._last_use[slot] = self._use_tick
        return slot

    def release(self, adapter_id):
        slot = self._slot_of.get(adapter_id)
        if slot is not None and self._refs[slot] > 0:
            self._refs[slot] -= 1

    def _load(self, adapter_id):
        ent = self._registry.get(adapter_id)
        if ent is None:
            raise KeyError(adapter_id)
        slot = None
        for s in range(1, self.pool_size):
            if self._slot_ids[s] is None:
                slot = s
                break
        if slot is None:
            # LRU among unpinned slots only
            victims = [s for s in range(1, self.pool_size)
                       if self._refs[s] == 0]
            if not victims:
                return None
            slot = min(victims, key=lambda s: self._last_use[s])
            del self._slot_of[self._slot_ids[slot]]
            self._slot_ids[slot] = None
            stats.incr("adapter.adapter_evictions")
        t0 = time.perf_counter()
        for name, stk in self._stacks.items():
            fac = ent.layers.get(name)
            if fac is None:
                # this adapter leaves the projection untouched: the slot
                # row must be an exact identity (it may have held another
                # adapter's factors)
                A_pad = np.zeros((stk.in_features, self.rank_pool),
                                 stk.A._data_.dtype)
                B_pad = np.zeros((self.rank_pool, stk.out_features),
                                 stk.B._data_.dtype)
                sc = 0.0
            else:
                A, B, sc = fac
                r = A.shape[-1]
                A_pad = np.zeros((stk.in_features, self.rank_pool),
                                 stk.A._data_.dtype)
                B_pad = np.zeros((self.rank_pool, stk.out_features),
                                 stk.B._data_.dtype)
                A_pad[:, :r] = A
                B_pad[:r, :] = B
            stk.A._data_ = stk.A._data_.at[slot].set(jnp.asarray(A_pad))
            stk.B._data_ = stk.B._data_.at[slot].set(jnp.asarray(B_pad))
            stk.scale._data_ = stk.scale._data_.at[slot].set(float(sc))
        stats.observe("adapter.adapter_load_ms",
                      (time.perf_counter() - t0) * 1e3)
        stats.incr("adapter.adapters_loaded")
        self._slot_ids[slot] = adapter_id
        self._slot_of[adapter_id] = slot
        self._refs[slot] = 0
        return slot

    # ---------------- per-row index plumbing ----------------
    def set_row(self, row, pool_slot):
        self._idx_np[row] = int(pool_slot)
        # a host copy: a tick in flight may still read the vector it was
        # given, which on the CPU is a view of the array it came from
        self.idx._data_ = jnp.asarray(self._idx_np.copy())

    def clear_row(self, row):
        self.set_row(row, 0)

    def row_tensor(self, rows):
        """A fresh int32 index Tensor for call-ordered lanes (chunked
        prefill batches requests by call row, not scheduler slot)."""
        return Tensor(jnp.asarray(np.asarray(rows, np.int32)))

    # ---------------- activation ----------------
    @contextlib.contextmanager
    def activate(self, idx=None):
        """Adapt target-model calls in this scope: patched projections
        apply the gathered low-rank update with ``idx`` (default: the
        persistent per-slot index vector).  Never wrap draft-model calls
        — speculation is gated off while adapters are in flight."""
        global _ACTIVE
        prev = _ACTIVE
        _ACTIVE = _Activation(self, idx if idx is not None else self.idx)
        try:
            yield
        finally:
            _ACTIVE = prev
