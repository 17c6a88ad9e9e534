"""Paged KV cache + prefix tree for the serving engine.

A full ``max_seq_len`` stripe reserved per slot up front makes a
request generating 40 tokens from a 10-token prompt squat the same HBM
as one that fills the slot.  This module brings the PagedAttention
(vLLM) / RadixAttention (SGLang) memory model to the TPU's static-shape
regime:

- **Fixed page pool per layer** ``[num_pages, page_size, H, D]`` plus an
  int32 page table ``[num_slots, pages_per_slot]`` and per-slot offsets.
  Shapes never change: the decode step stays ONE compiled XLA program
  (page-table/offset *values* are runtime data), while physical pages
  are assigned to a slot lazily as its sequence grows.
- **Lane-dense pages.**  A pool the paged decode kernel can host whose
  heads are narrower than the 128 lanes (``paged_pool_page_shape(
  page_size, H, D, itemsize)``: a rule on those four numbers, nothing
  else) LIVES as the kernel reads it, ``[num_pages, page_size *
  H * D / 128, 128]`` — the same bytes in the same order, a page's
  ``[page_size, H, D]`` row-major IS its ``[rows, 128]`` row-major — so
  no program ever relays a whole pool to get from the shape the write
  wants to the shape the kernel wants (at ``D = 64`` that was three
  copies a pool a tick).  At ``D = 128`` the 4-D pool is that view
  already, and a pool the kernel refuses is read by XLA: both stay
  4-D.  ``page_shape`` is a page as the host formats carry it,
  ``stored_page_shape`` as the device holds it.
- **Scratch page 0** is never allocated.  Free slots (and table entries
  not yet grown into) point at it, so the static-shape batch's dummy
  writes land in scratch and the per-row causal mask keeps every live
  row blind to it: free slots ride the batch harmlessly.
- **Prefix tree** (`PrefixTree`): refcounted, page-granular radix tree
  over prompt tokens.  Requests that share a system prompt attach the
  shared pages to their page table instead of recomputing prefill;
  pages whose refcount drops to zero stay cached until pool pressure
  evicts them LRU.  Shared pages are only ever *read*: a page enters
  the tree only when the prompt covers it entirely, and every write a
  slot performs lands at positions >= its private boundary.

Admission-time **reservations** make growth safe: `allocate()` records
how many pages the request may still claim (its worst case, ``ceil(
min(prompt+max_new, max_len)/page_size)`` minus shared), and
`available_pages` subtracts outstanding reservations — so admission
backpressure happens up front and `ensure_capacity` can never fail
mid-decode.

**Recurrent state beside pages.**  A layer that keeps a fixed-size state
per sequence instead of keys and values (``layer_states``: what the
model's config says of each layer) gets one state ROW per slot and one
scratch row, in arrays no page table indexes: the slot is all the
reservation it needs.  ``reset_state`` zeroes a slot's rows in place at
admission; a batch row finds its state row through ``state_rows`` (the
slot, or the scratch row for the surplus rows of a prefill call) and
says through ``valid_len`` how many of its positions are real.  A state
stored in another layout than it is read (the lane-packed SSM state,
``pallas/ssm.py``) names the shape ``read_state`` hands it back in.

**Page tables by layer kind.**  A layer whose attention sees only the
latest ``window`` positions (``layer_windows``: what the model's config
says of each layer) must not hold the pages that fell out of it while a
full-attention layer of the same model keeps them — so the two kinds do
not share page ids.  Window layers get pools, a page table
``[num_slots, ring_pages]`` and a free list of their own.  A slot's
window table is a RING: logical page ``p`` (``position // page_size``)
lives at entry ``p % ring_pages``, pages are taken from the window free
list as the sequence first grows into an entry, and once the ring is
full a new logical page reuses the entry of the page that fell out
(``ring_pages = ceil((window + slack) / page_size) + 1``, ``slack`` the
widest chunk one call writes, so the page reused is out of the window
of every position the call computes).  A slot never holds more than
``ring_pages`` window pages; ``release`` returns the pages of both
kinds.  A model with one kind of paged layer builds exactly the one
table and the pools it always built.

**Latent pages.**  A layer of latent (low-rank) attention caches ONE row
a token and no heads (``layer_latents``: what the model's config says of
each layer — the row's width, or None).  Such a layer is a paged layer
like any other — the same page table, free list, offsets and
reservations; a latent page is a page — but its kind owns its page's
shape: one pool ``latent_pool`` ``[num_pages, page_size, lanes]``, the
row padded to whole 128-lane tiles (``pallas.mla.latent_row_lanes``: 576
values live in 640 lanes) so that a page is the matrix the latent decode
kernel's DMA moves and a row's value part is a lane-aligned slice of its
key.  What carries K and V by name (``export_pages`` / ``adopt_pages``,
quantized storage) refuses a latent store by name.

**Layers that hold nothing.**  A layer that is a feed-forward part
alone (``layer_storeless``: what the model's config says of each layer)
keeps nothing between calls: it claims no pool, no page-table entry and
no state row, and its cache dict carries ``valid_len`` alone (an expert
layer leaves its routing counts there).  ``layers_without_store`` counts
them.
"""
from __future__ import annotations

import itertools

import numpy as np
import jax
import jax.numpy as jnp

from . import stats
from ..core.tensor import Tensor
from ..observability import scopes
from ..observability.tracing import span
from ..pallas import ssm
from ..utils.flags import flag as _flag


class PagedKVCache:
    """Block-granular KV storage: the scheduler-facing surface
    (allocate/release/advance/layer_caches) plus the page machinery
    (`ensure_capacity`, `prefill_table`, `prefill_view`, `make_shared`,
    `reclaim`).

    Host-side bookkeeping is plain numpy; device uploads are batched:
    mutations only mark the cache dirty, and `layer_caches()` uploads
    the offsets + page table ONCE per scheduler iteration.
    """

    def __init__(self, num_layers, num_slots, max_len, num_kv_heads=None,
                 head_dim=None, page_size=16, num_pages=None, dtype="float32",
                 layer_states=None, layer_windows=None, window_slack=1,
                 layer_latents=None, layer_storeless=None):
        self.num_slots = int(num_slots)
        self.page_size = int(page_size)
        self.max_len = int(max_len)
        self.pages_per_slot = -(-self.max_len // self.page_size)
        #: attention capacity per slot — max_len rounded up to pages
        self.capacity = self.pages_per_slot * self.page_size
        #: pages a request can actually hold K/V in (excludes scratch)
        self.usable_pages = int(num_pages) if num_pages else \
            self.num_slots * self.pages_per_slot
        if self.usable_pages < 1:
            raise ValueError(
                f"kv_pool_pages must be >= 1, got {self.usable_pages}")
        total = self.usable_pages + 1          # + scratch page 0
        self.offsets = np.zeros(self.num_slots, np.int32)
        self.table = np.zeros((self.num_slots, self.pages_per_slot),
                              np.int32)
        self._free_pages = list(range(total - 1, 0, -1))
        self._free_slots = list(range(self.num_slots - 1, -1, -1))
        self._private = {}       # slot -> [page ids owned by the slot]
        self._shared = {}        # slot -> leading tree-owned page count
        self._reserved = {}      # slot -> pages it may still claim
        self._dirty = True
        from ..quantization import kv_quant_params
        quant = kv_quant_params(dtype)
        #: "int8"/"fp8" when K/V are stored quantized with per-page
        #: scale arrays; None for plain float storage
        self.quant_dtype = dtype if quant else None
        store_dtype = quant[0] if quant else dtype

        def per_layer(values, what):
            values = list(values or [None] * num_layers)
            if len(values) != num_layers:
                raise ValueError(f"{len(values)} {what} for "
                                 f"{num_layers} layers")
            return values

        layer_states = per_layer(layer_states, "layer_states")
        layer_windows = per_layer(layer_windows, "layer_windows")
        layer_latents = per_layer(layer_latents, "layer_latents")
        bare = [bool(b) for b in per_layer(layer_storeless,
                                           "layer_storeless")]
        # each kind of paged layer owns its page's shape: as the host
        # formats carry it, and as it lives on the device; a layer that
        # holds nothing owns none
        kinds = {"none" if nothing else "state" if st is not None else
                 "latent" if lat is not None else "kv"
                 for st, lat, nothing in zip(layer_states, layer_latents,
                                             bare)}
        self.page_shape = self.stored_page_shape = None
        if "kv" in kinds:
            if num_kv_heads is None or head_dim is None:
                raise ValueError("layers that cache keys and values need "
                                 "num_kv_heads and head_dim")
            from ..pallas.flash_attention import paged_pool_page_shape
            #: a K/V page as the host formats carry it (``export_pages``,
            #: ``adopt_pages``): [page_size, H, D]
            self.page_shape = (self.page_size, int(num_kv_heads),
                               int(head_dim))
            #: a K/V page as it lives on the device: the paged decode
            #: kernel's [rows, 128] where the rule says so, else
            #: ``page_shape``
            self.stored_page_shape = paged_pool_page_shape(
                *self.page_shape, jnp.dtype(store_dtype).itemsize)
        widths = {int(w) for w in layer_latents if w is not None}
        if len(widths) > 1:
            raise ValueError(f"latent rows of {len(widths)} widths "
                             f"{sorted(widths)}: one store holds one")
        #: values of a latent layer's cached row (None: no such layer)
        self.latent_width = widths.pop() if widths else None
        #: a latent page as it lives on the device: [page_size, lanes]
        self.latent_page_shape = None
        #: bytes of one cached row as the arithmetic counts them
        self.latent_row_bytes = 0
        if self.latent_width is not None:
            if quant:
                from .api import LatentStoreError
                raise LatentStoreError(
                    f"cache_dtype={dtype!r}: a latent store keeps one row "
                    "a token and no per-page K/V scales; pass a float "
                    "cache_dtype")
            from ..pallas.mla import latent_row_lanes
            self.latent_page_shape = (
                self.page_size, latent_row_lanes(self.latent_width))
            self.latent_row_bytes = \
                self.latent_width * jnp.dtype(store_dtype).itemsize
        pool_shape = [total, *(self.stored_page_shape or ())]
        widths = {int(w) for w in layer_windows if w is not None}
        if len(widths) > 1:
            raise ValueError(
                f"window layers of {len(widths)} widths {sorted(widths)}: "
                "one ring table serves one width")
        #: positions a window layer's attention sees (None: no such layer)
        self.window = widths.pop() if widths else None
        #: entries of a slot's window table; the pages a slot's window
        #: layers can hold at most
        self.ring_pages = 0
        if self.window is not None:
            ring = -(-(self.window + int(window_slack)) // self.page_size) + 1
            # a window as long as the slot keeps what a full layer keeps
            self.ring_pages = ring if ring < self.pages_per_slot else 0
        self.table_w = np.zeros((self.num_slots, self.ring_pages), np.int32)
        self._free_w = list(range(self.num_slots * self.ring_pages, 0, -1))
        self._private_w = {}     # slot -> [window page ids, by ring entry]
        self._reserved_w = {}    # slot -> window pages it may still claim
        self._wrapped = {}       # slot -> highest logical page written
        pool_shape_w = [len(self._free_w) + 1] + pool_shape[1:]
        #: the state row that surplus rows of a prefill call read and
        #: write (a slot's row is its index)
        self.scratch_row = self.num_slots
        self.layers = []
        #: (layer, state name) -> the [H, P, N] a lane-packed row is read
        #: back in
        self._read_as = {}
        #: per layer, the names of the device arrays a model call updates
        #: (``flat_pools`` order)
        self._keys = []
        for state, window, latent, nothing in zip(
                layer_states, layer_windows, layer_latents, bare):
            if nothing:
                if state is not None or window is not None or \
                        latent is not None:
                    raise ValueError("a layer that holds nothing has no "
                                     "state, window or latent rows")
                self._keys.append(())
                self.layers.append({"valid_len": None})
                continue
            if state is not None:
                if window is not None or latent is not None:
                    raise ValueError("a recurrent layer has no window "
                                     "and no latent rows")
                # a row a slot, no page table: the recurrent layer's
                # fixed-size state — (stored shape, dtype[, the shape a
                # row is read back in])
                lay = {name: Tensor(jnp.zeros(
                    (self.num_slots + 1,) + tuple(spec[0]), dtype=spec[1]))
                    for name, spec in state.items()}
                self._read_as.update({(len(self.layers), name): spec[2]
                                      for name, spec in state.items()
                                      if len(spec) > 2})
                self._keys.append(tuple(state))
                lay.update(state_rows=None, valid_len=None)
                self.layers.append(lay)
                continue
            if latent is not None:
                if window is not None:
                    raise ValueError("a latent layer has no window")
                self.layers.append({
                    "latent_pool": Tensor(jnp.zeros(
                        (total,) + self.latent_page_shape,
                        dtype=store_dtype)),
                    "latent_width": self.latent_width,
                    "page_table": None, "offset": None,
                    "page_size": self.page_size})
                self._keys.append(("latent_pool",))
                continue
            ring = window is not None and self.ring_pages > 0
            shape = pool_shape_w if ring else pool_shape
            lay = {"k_pool": Tensor(jnp.zeros(shape, dtype=store_dtype)),
                   "v_pool": Tensor(jnp.zeros(shape, dtype=store_dtype)),
                   "page_table": None, "offset": None,
                   "page_size": self.page_size}
            if window is not None:
                lay["window"] = int(window)
            keys = ("k_pool", "v_pool")
            if quant and ring:
                raise ValueError("a quantized cache has no window layers "
                                 "yet: the ring pools keep no scales")
            if quant:
                # one float32 scale per cached token position, stored
                # page-major alongside the pools: a write only ever
                # touches its own row's scale, so old tokens never need
                # re-quantizing (paddle_tpu.quantization.quantize_kv_rows)
                lay["k_scale"] = Tensor(jnp.ones([total, self.page_size],
                                                 jnp.float32))
                lay["v_scale"] = Tensor(jnp.ones([total, self.page_size],
                                                 jnp.float32))
                keys += ("k_scale", "v_scale")
            self._keys.append(keys)
            self.layers.append(lay)
        self._paged = tuple(i for i, st in enumerate(layer_states)
                            if st is None and not bare[i])
        #: the layers that hold nothing
        self._storeless = tuple(i for i, nothing in enumerate(bare)
                                if nothing)
        #: the paged layers behind the ring table
        self._ringed = tuple(
            i for i in self._paged
            if layer_windows[i] is not None and self.ring_pages > 0)
        self._stateful = tuple(i for i, st in enumerate(layer_states)
                               if st is not None)
        #: the paged layers that keep latent rows
        self._latent = tuple(i for i in self._paged
                             if layer_latents[i] is not None)
        self._reset_jits = {}       # donating or not -> the reset program
        self._flush()

    @property
    def pools(self):
        """How many K/V page pools the cache holds (K and V of every
        layer that caches keys and values, both tables')."""
        return 2 * (len(self._paged) - len(self._latent))

    @property
    def latent_pools(self):
        """How many latent page pools the cache holds (one a latent
        layer)."""
        return len(self._latent)

    @property
    def layers_without_store(self):
        """How many layers hold no page and no state row."""
        return len(self._storeless)

    @property
    def pools_lane_dense(self):
        """Of ``pools``, those stored ``[pages, rows, 128]``."""
        return self.pools if len(self.stored_page_shape or ()) == 2 else 0

    # ---------------- recurrent state ----------------
    @property
    def has_state(self):
        """Whether any layer keeps a per-slot recurrent state."""
        return bool(self._stateful)

    @property
    def state_bytes(self):
        return sum(self.layers[i][k]._data_.nbytes
                   for i in self._stateful for k in self._keys[i])

    def _state_arrays(self):
        return tuple(self.layers[i][k]._data_
                     for i in self._stateful for k in self._keys[i])

    def reset_state(self, slot):
        """Zero ``slot``'s row of every state array, in place: ONE
        program over the donated arrays writes the rows and nothing
        else (an ``.at[slot].set(0)`` outside a donating program would
        copy every array).  Admission calls it; the tick and the prefill
        member never see a previous tenant's state."""
        if not self._stateful:
            return
        donating = bool(_flag("FLAGS_jit_donate_buffers", True))
        if donating not in self._reset_jits:
            def state_reset(arrays, row):
                return tuple(jax.lax.dynamic_update_slice(
                    a, jnp.zeros((1,) + a.shape[1:], a.dtype),
                    (row,) + (0,) * (a.ndim - 1)) for a in arrays)

            # held as an executable, like the tick and its members:
            # its HLO reaches the scope tables without a second compile
            self._reset_jits[donating] = jax.jit(
                state_reset, donate_argnums=(0,) if donating else ()
            ).lower(self._state_arrays(), np.int32(slot)).compile()
            scopes.publish(self._reset_jits[donating])
        with span("serving.state.reset"):
            it = iter(self._reset_jits[donating](self._state_arrays(),
                                                 np.int32(slot)))
            for i in self._stateful:
                for k in self._keys[i]:
                    self.layers[i][k] = Tensor(next(it))
        stats.incr("state.resets")

    @property
    def state_heads_per_tile(self):
        """Heads whose channels share one row of lanes in the stored SSM
        state (``pallas.ssm.heads_per_tile``; 0 with no such state)."""
        return max((shape[0] // self.layers[i][k].shape[1]
                    for (i, k), shape in self._read_as.items()), default=0)

    def read_state(self, slot):
        """``{layer: {name: array}}``: a host copy of ``slot``'s row of
        every state array, the SSM state as ``[H, P, N]`` whatever layout
        it is stored in.  The row is as the last call that named the
        slot left it — a released slot's stays until ``reset_state`` —
        so a snapshot, or a check against a reference, reads it here."""
        def host(i, k):
            row = np.asarray(self.layers[i][k]._data_[slot])
            shape = self._read_as.get((i, k))
            return row if shape is None else ssm.unpack_state(row, shape[0])

        return {i: {k: host(i, k) for k in self._keys[i]}
                for i in self._stateful}

    def read_latent(self, slot):
        """``{layer: array [offset, latent_width]}``: a host copy of the
        rows ``slot`` holds in every latent page pool, in position
        order, as the calls that wrote them left them (the prefill
        members and the ticks, whatever else was live) — what a check
        against a reference reads.  A released slot has none."""
        n = int(self.offsets[slot])
        pages = self.table[slot][:-(-n // self.page_size)]
        return {i: np.asarray(
            self.layers[i]["latent_pool"]._data_[jnp.asarray(pages)]
            .reshape(-1, self.latent_page_shape[-1])[:n, :self.latent_width])
            for i in self._latent}

    def state_rows(self, slots, rows):
        """int32 [rows]: the state row of each row of a prefill call —
        ``slots[i]`` for row i, the scratch row for the surplus rows."""
        out = np.full(rows, self.scratch_row, np.int32)
        out[:len(slots)] = slots
        return out

    # ---------------- pool accounting ----------------
    @property
    def free_slots(self):
        return len(self._free_slots)

    @property
    def free_page_count(self):
        return len(self._free_pages)

    @property
    def pages_in_use(self):
        return self.usable_pages - len(self._free_pages)

    @property
    def available_pages(self):
        """Pages admission may promise to a NEW request: the free list
        minus what already-admitted requests may still claim."""
        return len(self._free_pages) - sum(self._reserved.values())

    @property
    def window_pages_in_use(self):
        """Pages the slots' window tables hold (0 with no window kind)."""
        return self.num_slots * self.ring_pages - len(self._free_w)

    @property
    def window_pages_promised(self):
        """Window pages held or promised to admitted requests."""
        return self.window_pages_in_use + sum(self._reserved_w.values())

    def window_pages_held(self, slot):
        return len(self._private_w.get(slot, ()))

    # ---------------- slot lifecycle ----------------
    def allocate(self, reserve_pages, shared_pages=()):
        """Reserve a slot whose sequence may grow into `reserve_pages`
        fresh pages, with `shared_pages` (tree-owned, already full)
        prefixed onto its page table.  Returns the slot index, or None
        when no slot or not enough uncommitted pages remain — the
        caller keeps the request queued (backpressure, never a crash)."""
        if not self._free_slots or reserve_pages > self.available_pages:
            return None
        slot = self._free_slots.pop()
        for i, page in enumerate(shared_pages):
            self.table[slot, i] = page
        self._shared[slot] = len(shared_pages)
        self._private[slot] = []
        self._reserved[slot] = int(reserve_pages)
        if self.ring_pages:
            # the window pool has a ring a slot: the promise cannot fail
            self._private_w[slot] = []
            self._reserved_w[slot] = min(int(reserve_pages),
                                         self.ring_pages)
            self._wrapped[slot] = -1
        self.offsets[slot] = 0
        self._dirty = True
        return slot

    def release(self, slot):
        """Free the slot: its private pages return to the pool, its
        remaining reservation is dropped, and its table row falls back
        to the scratch page.  Tree-owned (shared) pages are NOT freed
        here — the prefix tree's refcounts govern those."""
        if slot in self._free_slots:
            raise ValueError(f"slot {slot} is already free")
        self._free_pages.extend(self._private.pop(slot, ()))
        self._shared.pop(slot, None)
        self._reserved.pop(slot, None)
        if self.ring_pages:
            self._free_w.extend(self._private_w.pop(slot, ()))
            self._reserved_w.pop(slot, None)
            self._wrapped.pop(slot, None)
            self.table_w[slot, :] = 0
        self.table[slot, :] = 0
        self.offsets[slot] = 0
        self._free_slots.append(slot)
        self._dirty = True

    def ensure_capacity(self, slot, pos):
        """Assign physical pages so position `pos` is writable.  Called
        before every write that may cross a page boundary; the
        admission-time reservation guarantees the pop cannot fail."""
        need_idx = int(pos) // self.page_size
        assigned = self._shared.get(slot, 0) + len(self._private[slot])
        while assigned <= need_idx:
            if not self._free_pages:      # pragma: no cover - reserved
                raise RuntimeError(
                    "KV page pool exhausted past its reservations — "
                    "admission accounting bug")
            if self._reserved[slot] <= 0:  # pragma: no cover - reserved
                raise RuntimeError(
                    f"slot {slot} grew past its page reservation")
            page = self._free_pages.pop()
            self._reserved[slot] -= 1
            self._private[slot].append(page)
            self.table[slot, assigned] = page
            assigned += 1
            self._dirty = True
        if self.ring_pages:
            self._grow_ring(slot, need_idx)

    def _grow_ring(self, slot, need_idx):
        """The window table's side of ``ensure_capacity``: ring entries
        up to logical page ``need_idx``'s get a page the first time the
        sequence grows into them; past the ring's length a logical page
        reuses the entry of the one that fell out of the window."""
        held = self._private_w[slot]
        while len(held) <= min(need_idx, self.ring_pages - 1):
            if not self._free_w or self._reserved_w[slot] <= 0:
                raise RuntimeError(     # pragma: no cover - a ring a slot
                    f"slot {slot} grew past its window reservation")
            page = self._free_w.pop()
            self._reserved_w[slot] -= 1
            self.table_w[slot, len(held)] = page
            held.append(page)
            self._dirty = True
        seen = self._wrapped[slot]
        if need_idx > seen:
            reused = max(0, need_idx - max(seen, self.ring_pages - 1))
            if reused:
                stats.incr("kv.window.pages_reclaimed", reused)
            self._wrapped[slot] = need_idx

    def set_offset(self, slot, off):
        self.offsets[slot] = int(off)
        self._dirty = True

    def rollback(self, slot, new_off):
        """Speculative-decoding accept-mask rollback: after a verify
        window wrote K/V past the accepted tokens, private pages lying
        WHOLLY past the new write horizon (`new_off` is where the next
        token lands, so its page stays) return to the free pool and the
        slot's reservation is re-credited — pool accounting is exactly
        what it was before the window grew them (``available_pages``
        unchanged: +1 free, +1 reserved per page), so `ensure_capacity`
        keeps its can-never-fail guarantee.  The rejected tokens' K/V in
        the pages that remain become scratch: causally masked until the
        offset passes them, and overwritten first.  Tree-owned (shared)
        pages are never touched — they hold prompt tokens, which are
        always behind the horizon."""
        self._refuse_window("rollback")
        shared = self._shared.get(slot, 0)
        keep = max(int(new_off) // self.page_size + 1, shared)
        priv = self._private[slot]
        while shared + len(priv) > keep:
            idx = shared + len(priv) - 1
            page = priv.pop()
            if page != self.table[slot, idx]:   # pragma: no cover
                raise RuntimeError(
                    f"slot {slot} page-table tail {self.table[slot, idx]}"
                    f" does not match private ownership {page}")
            self.table[slot, idx] = 0
            self._free_pages.append(page)
            self._reserved[slot] += 1
            self._dirty = True

    def advance(self, slots):
        """Bump the offsets of `slots` by one decoded token."""
        idx = list(slots)
        if idx:
            self.offsets[idx] += 1
        self._dirty = True

    # ---------------- prefix-tree ownership transfer ----------------
    def make_shared(self, slot, table_index):
        """Transfer the page at `table_index` of the slot's table from
        slot-private to caller (tree) ownership; returns its id.  The
        slot keeps using the page — only who frees it changes."""
        self._refuse_window("make_shared")
        shared = self._shared.get(slot, 0)
        # the shared prefix stays contiguous: pages become shared in
        # order, so the boundary just advances
        if table_index != shared:
            raise ValueError(
                f"non-contiguous share: index {table_index} with "
                f"shared boundary {shared}")
        page = int(self.table[slot, table_index])
        self._private[slot].remove(page)
        self._shared[slot] = shared + 1
        return page

    def reclaim(self, page):
        """Return a tree-owned page to the free pool (LRU eviction)."""
        self._free_pages.append(int(page))

    # ---------------- live page migration (serving/migration.py) ----------------
    def adopt_pages(self, reserve_pages, offset, k_pages, v_pages,
                    k_scales=None, v_scales=None):
        """Install migrated KV pages into free pool slots: the receive
        side of prefill/decode disaggregation.  ``k_pages``/``v_pages``
        are ``[num_layers, n, page_size, H, D]`` host arrays (the
        sender's pool rows, bit-exact), ``offset`` the migrated
        sequence's cached-token count, and ``reserve_pages`` how many
        MORE pages the resumed request may still claim while decoding.

        Adopted pages are slot-PRIVATE — shared/tree ownership never
        crosses replicas, so a migrated shared prefix arrives as a
        plain copy.  Returns the slot index, or None when no slot or
        not enough uncommitted pages remain (admission backpressure,
        exactly like `allocate`).  Geometry/dtype mismatches raise
        `PageMigrationError` — the sender falls back to decoding
        locally rather than corrupting this pool."""
        from .api import PageMigrationError
        k_pages = np.asarray(k_pages)
        v_pages = np.asarray(v_pages)
        self._refuse_state("adopt_pages")
        self._refuse_window("adopt_pages")
        self._refuse_latent("adopt_pages")
        pool = self.layers[0]["k_pool"]._data_
        want = (len(self.layers),) + self.page_shape
        if k_pages.ndim != 5 or k_pages.shape[0] != want[0] or \
                k_pages.shape[2:] != want[1:] or \
                v_pages.shape != k_pages.shape:
            raise PageMigrationError(
                f"page payload {k_pages.shape}/{v_pages.shape} does not "
                f"fit a [{want[0]}, n, {want[1]}, {want[2]}, {want[3]}] "
                "K/V page pool (layers/page_size/heads/head_dim "
                "mismatch)")
        if k_pages.dtype != pool.dtype:
            raise PageMigrationError(
                f"page payload dtype {k_pages.dtype} != pool dtype "
                f"{pool.dtype} (sender and receiver must share "
                "ServingConfig.cache_dtype)")
        quant = self.quant_dtype is not None
        if quant != (k_scales is not None):
            raise PageMigrationError(
                "per-page scales "
                + ("missing for a quantized pool"
                   if quant else "sent to an unquantized pool"))
        n = int(k_pages.shape[1])
        if n < 1 or n > self.pages_per_slot:
            raise PageMigrationError(
                f"{n} pages do not fit a {self.pages_per_slot}-page "
                "table row")
        if -(-int(offset) // self.page_size) > n:
            raise PageMigrationError(
                f"offset {offset} claims more cached tokens than the "
                f"{n} migrated pages hold")
        if not self._free_slots or \
                n + int(reserve_pages) > self.available_pages:
            return None                     # backpressure, never a crash
        slot = self._free_slots.pop()
        pages = [self._free_pages.pop() for _ in range(n)]
        self.table[slot, :] = 0
        self.table[slot, :n] = pages
        self._private[slot] = list(pages)
        self._shared[slot] = 0
        self._reserved[slot] = int(reserve_pages)
        self.offsets[slot] = int(offset)
        if quant:
            k_scales = np.asarray(k_scales)
            v_scales = np.asarray(v_scales)
        # the wire's [page_size, H, D] is the stored page's bytes
        stored = k_pages.shape[:2] + self.stored_page_shape
        k_pages, v_pages = k_pages.reshape(stored), v_pages.reshape(stored)
        # page-at-a-time scatter: every update is the SAME page shape
        # whatever the payload's page count, so the install compiles
        # once ever instead of once per distinct n
        for li, lay in enumerate(self.layers):
            kp, vp = lay["k_pool"]._data_, lay["v_pool"]._data_
            for j, pid in enumerate(pages):
                kp = kp.at[pid].set(jnp.asarray(k_pages[li, j]))
                vp = vp.at[pid].set(jnp.asarray(v_pages[li, j]))
            lay["k_pool"], lay["v_pool"] = Tensor(kp), Tensor(vp)
            if quant:
                ks, vs = lay["k_scale"]._data_, lay["v_scale"]._data_
                for j, pid in enumerate(pages):
                    ks = ks.at[pid].set(jnp.asarray(k_scales[li, j]))
                    vs = vs.at[pid].set(jnp.asarray(v_scales[li, j]))
                lay["k_scale"], lay["v_scale"] = Tensor(ks), Tensor(vs)
        self._dirty = True
        return slot

    def export_pages(self, slot):
        """Host snapshot of the slot's cached pages, layer-pooled: the
        send side of live migration.  Returns ``(offset, k, v,
        k_scales, v_scales)`` with ``k``/``v`` ``[num_layers, n,
        page_size, H, D]`` contiguous arrays covering every page the
        offset has written into (shared tree pages included — the COPY
        migrates; tree ownership stays here), scales None for float
        pools."""
        self._refuse_state("export_pages")
        self._refuse_window("export_pages")
        self._refuse_latent("export_pages")
        off = int(self.offsets[slot])
        n = max(1, -(-off // self.page_size))
        ids = [int(p) for p in self.table[slot, :n]]
        ks, vs, kss, vss = [], [], [], []
        for lay in self.layers:
            ks.append(np.asarray(lay["k_pool"]._data_)[ids])
            vs.append(np.asarray(lay["v_pool"]._data_)[ids])
            if self.quant_dtype is not None:
                kss.append(np.asarray(lay["k_scale"]._data_)[ids])
                vss.append(np.asarray(lay["v_scale"]._data_)[ids])
        wire = (len(ks), n) + self.page_shape
        k = np.ascontiguousarray(np.stack(ks)).reshape(wire)
        v = np.ascontiguousarray(np.stack(vs)).reshape(wire)
        if self.quant_dtype is None:
            return off, k, v, None, None
        return off, k, v, np.ascontiguousarray(np.stack(kss)), \
            np.ascontiguousarray(np.stack(vss))

    def _refuse_state(self, what):
        if self._stateful:
            from .api import RecurrentStateError
            raise RecurrentStateError(
                f"{what}: {len(self._stateful)} layers keep a recurrent "
                "state per slot, which pages do not carry")

    def _refuse_latent(self, what):
        if self._latent:
            from .api import LatentStoreError
            raise LatentStoreError(
                f"{what}: {len(self._latent)} layers keep a latent page "
                f"store (one row of {self.latent_width} values a token), "
                "and the page payload carries a K/V page store's k and v "
                "pages")

    def _refuse_window(self, what):
        if self.ring_pages:
            from .api import WindowLayerError
            raise WindowLayerError(
                f"{what}: {len(self._ringed)} sliding_attention layers "
                f"keep a ring of {self.ring_pages} pages a slot, and a "
                "page that fell out of the window is gone")

    # ---------------- device views ----------------
    def layer_caches(self, live=None):
        """Per-layer cache dicts for the batched decode step.  Flushes
        the (single, shared) offsets + page-table device arrays if any
        host-side mutation happened since the last call.  ``live`` (the
        slots that decode this step) is what every layer's ``valid_len``
        is made of: every other row's recurrent state stays as it was,
        and a layer that counts what it computes counts those rows."""
        self._flush()
        valid = np.zeros(self.num_slots, np.int32)
        valid[list(live or ())] = 1
        valid = Tensor(jnp.asarray(valid))
        for i in self._stateful:
            self.layers[i]["state_rows"] = None
        for lay in self.layers:
            lay["valid_len"] = valid
        return self.layers

    def table_arrays(self):
        """(page table, offsets) as the device holds them, flushed."""
        self._flush()
        return self._pt._data_, self._off._data_

    def window_table_array(self):
        """The window layers' page table as the device holds it, flushed;
        None with no window kind."""
        if not self.ring_pages:
            return None
        self._flush()
        return self._pt_w._data_

    def prefill_window_table(self, slots, rows):
        """``prefill_table`` for the window layers: row i carries
        ``slots[i]``'s ring; None with no window kind."""
        if not self.ring_pages:
            return None
        table = np.zeros((rows, self.ring_pages), np.int32)
        for row, slot in enumerate(slots):
            table[row] = self.table_w[slot]
        return table

    def prefill_table(self, slots, starts, rows):
        """Host arrays for one batched prefill-chunk call of ``rows``
        rows: row i carries ``slots[i]``'s page-table row at write offset
        ``starts[i]``; surplus rows point at the scratch page, so their
        pad writes vanish like any free slot's.  Returns (table [rows,
        pages_per_slot], offsets [rows])."""
        table = np.zeros((rows, self.pages_per_slot), np.int32)
        off = np.zeros(rows, np.int32)
        for row, (slot, start) in enumerate(zip(slots, starts)):
            table[row] = self.table[slot]
            off[row] = start
        return table, off

    def views_over(self, pools_flat, page_table, offset, state_rows=None,
                   valid_len=None, window_table=None):
        """Per-layer cache dicts over ``pools_flat`` (each layer's device
        arrays, flat in ``flat_pools`` order): the paged layers behind
        one page table and offset vector, the recurrent layers behind
        ``state_rows`` (None: row i is slot i); the window layers behind
        ``window_table``.  Every view carries ``valid_len``, each row's
        count of real positions (None: every position is real)."""
        pt, off = Tensor(page_table), Tensor(offset)
        pt_w = None if window_table is None else Tensor(window_table)
        rows = None if state_rows is None else Tensor(state_rows)
        valid = None if valid_len is None else Tensor(valid_len)
        views = []
        it = iter(pools_flat)
        for i, keys in enumerate(self._keys):
            view = {k: Tensor(next(it)) for k in keys}
            if "k_pool" in view or "latent_pool" in view:
                view.update(page_table=pt_w if i in self._ringed else pt,
                            offset=off, page_size=self.page_size)
                for name in ("window", "latent_width"):
                    if name in self.layers[i]:
                        view[name] = self.layers[i][name]
            elif i in self._stateful:
                view["state_rows"] = rows
            view["valid_len"] = valid
            views.append(view)
        return views

    def flat_pools(self, views=None):
        """The device arrays a model call updates, flat per layer (k, v,
        then the scales of a quantized cache; a recurrent layer's state
        arrays): of the cache itself, or of ``views`` after a call."""
        return tuple(lay[k]._data_
                     for lay, keys in zip(
                         self.layers if views is None else views,
                         self._keys)
                     for k in keys)

    def prefill_view(self, slots, starts, valid=None):
        """Per-layer cache dicts for one EAGER batched prefill-chunk
        call — the reference lane of the compiled prefill member
        (serving/compiled_tick.py), which takes ``prefill_table`` at its
        own row count instead: always [num_slots] rows over the cache's
        own pools.  Pool updates made by the model call are pulled back
        with `absorb_view`; until then the old and the new pools are
        both alive."""
        table, off = self.prefill_table(slots, starts, self.num_slots)
        rows = None
        if self._stateful:
            rows = jnp.asarray(self.state_rows(slots, self.num_slots))
        if valid is not None:
            valid = np.asarray(valid).copy()
            valid[len(slots):] = 0          # the surplus rows are nobody's
            valid = jnp.asarray(valid)
        table_w = self.prefill_window_table(slots, self.num_slots)
        return self.views_over(
            self.flat_pools(), jnp.asarray(table), jnp.asarray(off), rows,
            valid, None if table_w is None else jnp.asarray(table_w))

    def absorb_view(self, views):
        """Adopt the functionally-updated pools (and per-page scales)
        from a `prefill_view` model call back into the shared dicts."""
        self.absorb_pools(self.flat_pools(views))

    def absorb_pools(self, pools_flat):
        """Adopt functionally-updated pools (``flat_pools`` order) — what
        a compiled prefill chunk hands back after the old ones were
        donated to it.  Offsets and page table are the host's to set."""
        it = iter(pools_flat)
        for lay, keys in zip(self.layers, self._keys):
            for k in keys:
                lay[k] = Tensor(next(it))

    def absorb_tick(self, pools_flat, new_offsets, offsets_np=None):
        """Adopt one compiled scheduler tick's functionally-updated
        device state (serving/compiled_tick.py): the donated-through
        pools (``flat_pools`` order), the in-program-advanced offsets
        device array, and — when given — the host offset mirror that
        advanced in lockstep.  The dirty flag is NOT set: a later
        ``layer_caches()`` must not re-upload stale Tensors over the
        tick's outputs.  Device and host agree after this call for every
        row the device still runs; for a row it has already finished by
        eos, which rides this tick dead, the mirror is one step ahead
        until that row's delivery releases it."""
        self.absorb_pools(pools_flat)
        self._off = off_t = Tensor(new_offsets)
        for i in self._paged:
            self.layers[i]["offset"] = off_t
        if offsets_np is not None:
            self.offsets[:] = offsets_np

    def _flush(self):
        if not self._dirty:
            return
        # host copies, never views: the compiled tick runs one program
        # ahead of the host, and ``jnp.asarray`` of an aligned host array
        # is a zero-copy view on the CPU (``jnp.array`` copies it on the
        # device, later) — the mirrors' next in-place write would move
        # the arguments of a tick still in flight
        self._off = off = Tensor(jnp.asarray(self.offsets.copy()))
        self._pt = pt = Tensor(jnp.asarray(self.table.copy()))
        if self.ring_pages:
            self._pt_w = Tensor(jnp.asarray(self.table_w.copy()))
        for i in self._paged:
            self.layers[i]["offset"] = off
            self.layers[i]["page_table"] = \
                self._pt_w if i in self._ringed else pt
        self._dirty = False


class _PrefixNode:
    __slots__ = ("key", "page", "children", "refs", "tick", "parent")

    def __init__(self, key, page, parent):
        self.key = key
        self.page = page
        self.children = {}
        self.refs = 0
        self.tick = 0
        self.parent = parent


class PrefixTree:
    """Page-granular radix tree over prompt tokens (RadixAttention's
    structure): node = one FULL page of `page_size` prompt tokens
    holding the physical page that stores its K/V.

    Refcounts count *active requests* using the page.  A released
    request decrements; pages at refcount zero stay cached (warm
    prefix) until `evict()` reclaims them LRU under pool pressure.
    `match` never returns the whole prompt: at least the final token is
    always recomputed so the engine has last-token logits to sample
    from.

    Entries are keyed by ``scope`` (the request's LoRA adapter id; None
    = base model): the SAME prompt prefilled under different adapters
    produces different K/V, so each scope owns a private root and
    adapters never share cached prompt pages.  Eviction and accounting
    walk every scope's root."""

    def __init__(self, page_size):
        self.page_size = int(page_size)
        self.root = _PrefixNode(None, None, None)
        # scope -> root; the base scope aliases self.root so existing
        # single-tenant callers/tests see the historical structure
        self._roots = {None: self.root}
        self._ticks = itertools.count(1)

    def _scope_root(self, scope):
        root = self._roots.get(scope)
        if root is None:
            root = self._roots[scope] = _PrefixNode(None, None, None)
        return root

    def _page_key(self, prompt, i):
        p = self.page_size
        return tuple(np.asarray(prompt[i * p:(i + 1) * p]).tolist())

    def match(self, prompt, scope=None):
        """Longest cached page-aligned prefix of `prompt` within
        ``scope``, capped at ``(len-1)//page_size`` pages.  Acquires a
        reference on every matched node; returns (nodes, page_ids)."""
        limit = (len(prompt) - 1) // self.page_size
        node, nodes, pages = self._scope_root(scope), [], []
        for i in range(limit):
            child = node.children.get(self._page_key(prompt, i))
            if child is None:
                break
            child.refs += 1
            child.tick = next(self._ticks)
            nodes.append(child)
            pages.append(child.page)
            node = child
        return nodes, pages

    def insert(self, prompt, cache, slot, held_nodes, scope=None):
        """Register the prompt's fully-covered pages after its prefill
        completed, transferring ownership of the slot's corresponding
        private pages to the tree (refcount 1 for the inserting
        request).  Nodes in `held_nodes` (this request's match) are
        skipped; a node inserted concurrently by a twin request stops
        the walk — our duplicate pages simply stay slot-private.
        Appends newly created nodes to `held_nodes` and returns how
        many were inserted."""
        full = len(prompt) // self.page_size
        held = set(id(n) for n in held_nodes)
        node, inserted = self._scope_root(scope), 0
        for i in range(full):
            key = self._page_key(prompt, i)
            child = node.children.get(key)
            if child is not None:
                if id(child) not in held:
                    break               # a twin got here first
                node = child
                continue
            page = cache.make_shared(slot, i)
            child = _PrefixNode(key, page, node)
            child.refs = 1
            child.tick = next(self._ticks)
            node.children[key] = child
            held_nodes.append(child)
            inserted += 1
            node = child
        return inserted

    def release(self, nodes):
        for node in nodes:
            node.refs -= 1

    def evict(self, n_pages, reclaim):
        """Free up to `n_pages` pages by pruning LRU zero-ref leaves
        (interior nodes are protected while descendants exist).  Each
        victim's page goes through `reclaim`; returns pages freed."""
        freed = 0
        while freed < n_pages:
            victim, best = None, None
            stack = [n for root in self._roots.values()
                     for n in root.children.values()]
            while stack:
                node = stack.pop()
                if node.children:
                    stack.extend(node.children.values())
                elif node.refs == 0 and (best is None or node.tick < best):
                    victim, best = node, node.tick
            if victim is None:
                break
            del victim.parent.children[victim.key]
            reclaim(victim.page)
            freed += 1
        return freed

    def cached_pages(self):
        """Total pages the tree currently owns (any refcount)."""
        count, stack = 0, [n for root in self._roots.values()
                           for n in root.children.values()]
        while stack:
            node = stack.pop()
            count += 1
            stack.extend(node.children.values())
        return count
