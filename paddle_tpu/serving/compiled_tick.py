"""One compiled program per scheduler tick (ISSUE 13).

PR 8 fused the whole training step into ONE donated-buffer jit program
and the Python-dispatch ceiling disappeared (4.18x).  The serving
scheduler iteration was still on the wrong side of that line:
``Engine._decode_step`` orchestrated the batched decode call, per-slot
host sampling (an ``np.asarray`` host sync per non-greedy slot per
iteration), offset/page-table flushes, and eos/length bookkeeping as
separate compiled calls with host round-trips between them — at high
occupancy the Python glue WAS the tokens/sec ceiling.

:class:`CompiledServingTick` captures the full tick as one program over
device-resident scheduler state:

- **state** — last tokens, per-slot counts/limits/eos ids, alive masks, cache offsets, per-slot sampling
  params (temperature/top-k/top-p/repetition-penalty vectors + seen
  masks + per-request RNG keys) all live as fixed-shape device arrays;
  the page pools and page table are the ``PagedKVCache``'s own device
  arrays, donated through the program each tick;
- **program** — one jitted call runs the [num_slots, 1] model forward
  (replayed through the shared two-phase capture core,
  ``framework/capture.py``), the vectorized per-slot logit-processor
  chain + sampling, the token append, eos/max-length finish codes, and
  the offset advance; the batched-argmax fast path compiles its own
  leaner variant so an all-greedy batch stays bitwise the old argmax;
- **host boundary** — per tick the host reads back two small
  ``[num_slots]`` vectors, the finish codes and the tick's own tokens
  (appended to the requests' lists there: a live request's list grows
  every tick), and reads them one tick late: tick
  n+1 is launched from tick n's outputs as they stand (device futures)
  BEFORE tick n's codes are read, so the host's per-tick work runs under
  the next program instead of between two.  At most one tick is in
  flight unread; whatever reads or rewrites what it holds collects it
  first (``drain``).  Request admission and completion (and deadline
  eviction — a wall-clock decision) are the only times the scheduler
  state is re-made from the host.

The prefill chunk call is a member of the same program family (ISSUE
27): ``serving_prefill_r<rows>``, ONE donated program per call over the
same pools and the same capture list, with as many rows as the smallest
power-of-two bucket (capped at ``num_slots``) that holds the requests
that are prefilling.  Every member is compiled from shapes alone before
the first chunk call returns, so traffic meets no bucket cold; the eager
``Engine._prefill_chunk_eager`` stays as its reference and as the lane
for what one program cannot host.

Fallbacks latch the uncompiled scheduler byte-identically and warn once
with the typed :class:`TickFallbackWarning`: flag off
(``FLAGS_compiled_tick``), speculative decoding configured, a
framework tracer active, layer hooks installed, and non-greedy sampling
without a per-request ``SamplingParams.seed`` (the vectorized chain
derives each slot's stream from ``fold_in(PRNGKey(seed), n_generated)``
— without a seed the old path's global-RNG draws cannot be reproduced
in-program), and a model body one program cannot replay
(``capture.USER_TRACE_ERRORS``).  A failure of lowering, compiling or
running the tick is none of these: it propagates to the scheduler's
restart wrapper.  See docs/SERVING.md "Compiled scheduler tick".
"""
from __future__ import annotations

import time
import warnings
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import jax
import jax.numpy as jnp

from . import stats
from ..core import state as _state
from ..observability import scopes, tracing
from ..observability.tracing import scope, span
from ..core.tensor import Tensor
from ..framework.capture import (TRACE_LOCK, USER_TRACE_ERRORS, BindTracer,
                                 Installed, TraceEscape, describe_escape,
                                 run_discovery)
from ..utils.flags import flag as _flag


class TickFallbackWarning(UserWarning):
    """Warned once per reason when the compiled serving tick cannot host
    the current scheduler state and the engine latches the uncompiled
    (byte-identical) iteration instead."""


# ---------------------------------------------------------------------------
# vectorized per-slot sampling chain (shared by the compiled tick and the
# uncompiled lane's fused per-iteration sampling call)
# ---------------------------------------------------------------------------

def process_logits_rows(logits, temp, top_k, top_p, penalty, seen):
    """Per-row logit-processor chain over a whole batch at once —
    ``models.generation.apply_logit_processors`` semantics (HF order:
    repetition penalty → temperature → top-k → top-p), vectorized with
    per-slot knob vectors so every slot's chain runs inside one program.

    ``logits`` [ns, V] float; ``temp`` [ns] (0.0 = greedy: the row
    bypasses temperature/top-k/top-p and keeps its penalized logits for
    the argmax); ``top_k`` [ns] int32 (0 = off); ``top_p`` [ns] (>= 1.0
    = off); ``penalty`` [ns] (1.0 = off); ``seen`` [ns, V] bool emitted
    mask.  Off knobs reproduce the reference chain's skipped branches
    exactly (the k-th/threshold values are the same elements the
    reference's ``topk``/``masked_fill`` select)."""
    neg_inf = jnp.asarray(float("-inf"), logits.dtype)
    vocab = logits.shape[-1]
    pen = penalty[:, None].astype(logits.dtype)
    pen_on = (penalty != 1.0)[:, None]
    pos = logits > 0
    penalized = jnp.where(pos, logits / pen, logits * pen)
    logits = jnp.where(pen_on & seen, penalized, logits)
    greedy = temp == 0.0
    safe_t = jnp.where(greedy, 1.0, temp).astype(logits.dtype)
    x = logits / safe_t[:, None]
    # top-k: threshold at the row's k-th largest value (same element
    # topk()'s vals[:, -1] selects), k clamped to the vocab
    k = jnp.clip(top_k.astype(jnp.int32), 0, vocab)
    sorted_desc = jnp.sort(x, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(sorted_desc,
                              jnp.clip(k - 1, 0, vocab - 1)[:, None],
                              axis=-1)
    x = jnp.where((k > 0)[:, None] & (x < kth), neg_inf, x)
    # top-p: smallest prefix of the sorted row whose EXCLUSIVE mass is
    # below top_p survives (the first token always does)
    p_on = (top_p < 1.0)[:, None]
    sorted_p = jnp.sort(x, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted_p, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_p[:, None].astype(probs.dtype)
    minv = jnp.min(jnp.where(keep, sorted_p,
                             jnp.asarray(float("inf"), x.dtype)),
                   axis=-1, keepdims=True)
    x = jnp.where(p_on & (x < minv), neg_inf, x)
    return jnp.where(greedy[:, None], logits, x)


def choose_tokens(logits, temp, top_k, top_p, penalty, seen, keys, counts):
    """[ns, V] logits → [ns] int32 next tokens under per-slot params.

    Greedy rows (temp == 0) take the argmax of their (penalized) logits
    — bitwise the reference ``sample_next_token`` path.  Sampled rows
    draw ``jax.random.categorical`` from the processed logits under the
    slot's own key stream ``fold_in(base_key, n_generated)`` — the
    per-request seed makes the stream identical whichever lane (fused
    uncompiled call or compiled tick) executes the draw."""
    processed = process_logits_rows(logits, temp, top_k, top_p, penalty,
                                    seen)
    greedy_tok = jnp.argmax(processed, axis=-1).astype(jnp.int32)

    def draw(key, count, row):
        return jax.random.categorical(
            jax.random.fold_in(key, count), row)

    sampled_tok = jax.vmap(draw)(keys, counts, processed).astype(jnp.int32)
    return jnp.where(temp == 0.0, greedy_tok, sampled_tok)


@jax.jit
def fused_sample_call(logits, temp, top_k, top_p, penalty, seen, keys,
                      counts):
    """The uncompiled lane's ONE per-iteration sampling program: every
    active slot's processor chain + draw in a single jitted call instead
    of a host round-trip per non-greedy slot (ISSUE 13 satellite)."""
    return choose_tokens(logits, temp, top_k, top_p, penalty, seen,
                         keys, counts)


def sampling_hostable(sp):
    """Whether the vectorized chain can host this request's sampling:
    greedy always (penalty included — the chain penalizes before the
    argmax exactly like ``_sample_row``); non-greedy only with a
    per-request ``seed`` (the in-program stream is key-derived — global
    framework-RNG draws cannot be replayed inside one program)."""
    return sp.greedy or sp.seed is not None


def request_key(sp):
    """[2] uint32 base key for a seeded request's sampling stream."""
    return np.asarray(jax.random.PRNGKey(int(sp.seed)))


# ---------------------------------------------------------------------------
# routing counts of expert layers: out of the program with its outputs
# ---------------------------------------------------------------------------

def routing_counts(views, valid_len):
    """int32 [E_held + 2] from the ``moe_counts`` an expert layer left in
    its cache view (models/cohere_moe.py): the (token, held expert) pairs
    computed for each held expert, summed over the layers; then the
    experts hit, summed over the layers; then the tokens routed (real
    positions x layers).  None where no view carries any."""
    counts = [v["moe_counts"]._data_ for v in views if "moe_counts" in v]
    if not counts:
        return None
    hit = sum(jnp.sum(c > 0) for c in counts)
    routed = jnp.sum(valid_len) * len(counts)
    return jnp.concatenate([sum(counts), jnp.stack([hit, routed])]) \
        .astype(jnp.int32)


def count_routing(vec, prefill=False):
    """Add one program's ``routing_counts`` (on the host) to the
    registry: ``serving.moe.pairs_local``, ``.experts_hit``,
    ``.tokens_routed`` and ``.pairs_by_expert.<e>``; a prefill member's
    pairs and experts hit under ``serving.moe.prefill.*`` as well (a
    tick's are the rest)."""
    pairs = vec[:-2]
    stats.incr("moe.pairs_local", int(pairs.sum()))
    stats.incr("moe.experts_hit", int(vec[-2]))
    stats.incr("moe.tokens_routed", int(vec[-1]))
    if prefill:
        stats.incr("moe.prefill.pairs", int(pairs.sum()))
        stats.incr("moe.prefill.experts_hit", int(vec[-2]))
    for e, n in enumerate(pairs.tolist()):
        if n:
            stats.incr(f"moe.pairs_by_expert.{e}", n)


# ---------------------------------------------------------------------------
# the compiled tick
# ---------------------------------------------------------------------------

class _Launched:
    """One tick the device has been given and the host has not read."""
    __slots__ = ("fin", "tok", "active", "overlapped", "host_ms", "moe")

    def __init__(self, fin, tok, active, overlapped, moe=None):
        self.moe = moe                  # routing counts, a future, or None
        self.tok = tok                  # [num_slots] this tick's tokens
        self.fin = fin                  # [num_slots] finish codes, a future
        self.active = active            # slot -> request the host ran live
        self.overlapped = overlapped    # launched over an unread tick
        self.host_ms = 0.0              # build + launch; deliver is added


class CompiledServingTick:
    """Owns the device-resident scheduler state and the compiled program
    family — the per-mode jitted tick programs and the per-bucket prefill
    members — for one :class:`~paddle_tpu.serving.engine.Engine`.

    ``step()`` launches one compiled tick and returns True, or returns
    False after latching/flushing so the engine's uncompiled iteration
    (the byte-identical fallback) runs instead.  The tick it launched
    stays in flight, unread, until the next ``step()`` has launched its
    successor or a ``drain()`` collects it."""

    def __init__(self, engine):
        self.eng = engine
        self._built = False
        self._disabled = None          # permanent fallback reason
        self._warned = set()           # reason kinds already warned
        self._caps = []                # captured model tensors (params)
        self._jits = {}                # (mode, donating) -> jitted fn
        self._sigs = {}                # (mode, donating) -> arg avals
        self._ticks = {}               # (mode, donating) -> compiled tick
        self._prefill = {}             # (rows, donating) -> compiled member
        self._dev = None               # device state dict
        self._mut_seen = -1            # engine mutation counter synced
        self._h_counts = None          # host mirror of generated counts
        self._h_limits = None          # host copy of the token limits
        self._pending = None           # the _Launched tick not yet read
        self._sublayers = None
        # the static blocker (speculation) is known at construction:
        # warn right away — an all-greedy speculative engine never even
        # consults the tick (the spec step runs), so an iteration-time
        # warning would stay silent forever
        blk = self._static_blocker()
        if blk is not None:
            self._note_fallback(*blk)

    # ------------------------------------------------------------------
    # eligibility / fallback accounting
    # ------------------------------------------------------------------

    def _note_fallback(self, kind, reason, permanent=False,
                       counter="tick.fallbacks"):
        stats.incr(counter)
        if permanent:
            self._disabled = reason
        if kind not in self._warned:
            self._warned.add(kind)
            warnings.warn(
                f"compiled serving tick disabled ({reason}); running the "
                "uncompiled scheduler iteration", TickFallbackWarning)

    def _static_blocker(self):
        """(kind, reason, permanent) for configuration the tick can
        never host, known at engine start; None otherwise."""
        if self.eng._spec:
            return ("spec", "speculative decoding configured "
                    "(draft_model + speculation_k > 0)", True)
        return None

    def _model_blocker(self):
        """(kind, reason, permanent) for what stops the model call from
        running as one program — the lattice the tick and the prefill
        member share — or None."""
        eng = self.eng
        blk = self._static_blocker()
        if blk is not None:
            return blk
        if _state.STATE.tracer is not None:
            return ("tracer", "a framework tracer is active", False)
        if self._sublayers is None and hasattr(eng.model, "sublayers"):
            self._sublayers = list(
                eng.model.sublayers(include_self=True))
        for layer in self._sublayers or ():
            if layer._forward_pre_hooks or layer._forward_post_hooks:
                return ("hooks", "layer forward hooks installed", False)
        return None

    def _blocker(self):
        """(kind, reason, permanent) for the current scheduler state, or
        None when this tick can run compiled."""
        eng = self.eng
        blk = self._model_blocker()
        if blk is not None:
            return blk
        for req in eng._active.values():
            if not sampling_hostable(req.sampling):
                return ("sampling", "non-greedy sampling without a "
                        "per-request SamplingParams.seed — the "
                        "vectorized in-program chain cannot reproduce "
                        "global-RNG draws", False)
        return None

    @property
    def fallback_reason(self):
        return self._disabled

    # ------------------------------------------------------------------
    # capture (phase 1): discover the model forward's reads
    # ------------------------------------------------------------------

    def _capture(self):
        eng = self.eng
        cache = eng.cache
        views = [dict(lay) for lay in cache.layer_caches()]
        tok = jnp.zeros((cache.num_slots, 1), jnp.int32)
        exclude = {id(v) for view in views for v in view.values()
                   if isinstance(v, Tensor)}
        with TRACE_LOCK:
            # discovery runs under the SAME adapter activation as the
            # live tick, so the pool's A/B stacks, scales, and per-slot
            # index vector are read through op dispatch and join the
            # re-gathered captures — hot-loads and admission re-points
            # flow into the compiled program with no retrace, and the
            # identity slot 0 keeps base-only batches on this one program
            def _body(tok_arr):
                with eng._lora_ctx():
                    return eng.model(Tensor(tok_arr), caches=views)._data_

            # on shapes alone: the pass is after the forward's READS, and
            # run eagerly it builds (or loads) and runs a program per op
            # for them; every member of the family traces the same body
            disc = run_discovery(lambda: jax.eval_shape(_body, tok))
        if disc.uses_rng:
            raise TraceEscape(
                "model forward draws framework RNG (dropout in eval?) — "
                "the tick program feeds randomness only through "
                "per-slot sampling keys")
        self._caps = [t for t in disc.capture_list
                      if id(t) not in exclude]
        self._built = True

    # ------------------------------------------------------------------
    # the traced tick body (phase 2)
    # ------------------------------------------------------------------

    def _replay_model(self, tokens, pools, pt, off, caps, lora_idx=None,
                      state_rows=None, valid_len=None, pt_w=None):
        """The captured model call, replayed while ``jax.jit`` traces a
        member of the family: ``tokens`` [rows, s] against the flat
        ``pools`` through page table ``pt`` [rows, pages_per_slot] at
        write offsets ``off`` [rows].  ``lora_idx`` is the call's own
        adapter index (the prefill's row-ordered one); None activates
        the pool's persistent per-slot vector.  ``state_rows`` and
        ``valid_len`` are for the layers that keep a recurrent state:
        each row's state row (None: row i is slot i) and how many of its
        positions are real; ``pt_w`` is the window layers' ring table
        (None: the model has one kind of paged layer).  Returns the
        [rows, s, V] logits, the functionally-updated pools, flat, and
        the routing counts of the expert layers (`routing_counts`; None
        where no layer reports any)."""
        eng = self.eng
        cache = eng.cache
        tracer = BindTracer(rng_key=None)
        _state.STATE.tracer = tracer
        try:
            with Installed(list(zip(self._caps, caps))):
                views = cache.views_over(
                    pools, pt, off, state_rows, valid_len,
                    **({} if pt_w is None else {"window_table": pt_w}))
                idx = None if lora_idx is None else Tensor(lora_idx)
                with eng._lora_ctx(idx):
                    logits_t = eng.model(Tensor(tokens), caches=views)
                logits = logits_t._data_
                new_pools = cache.flat_pools(views)
                moe = routing_counts(views, valid_len)
        finally:
            _state.STATE.tracer = None
            tracer.rollback_mutations()
        return logits, new_pools, moe

    def _traced(self, mode, pools, pt, off, last, counts, alive, seen,
                limits, eos, temp, topk, topp, pen, keys, caps, pt_w=None):
        # dead/prefilling rows feed token 0 exactly like the uncompiled
        # step's zero-filled tok_in; their scratch writes are causally
        # masked (and prefill re-writes its positions next chunk) either
        # way
        with scope("tick_state"):
            tok_in = jnp.where(alive, last, jnp.zeros_like(last))[:, None]
        # a recurrent state has no mask to hide a write behind: only the
        # rows that decode may move theirs (a row mid-prefill keeps what
        # its chunks have built); an expert layer counts those rows
        logits, new_pools, moe = self._replay_model(
            tok_in, pools, pt, off, caps,
            valid_len=alive.astype(jnp.int32), pt_w=pt_w)
        logits = logits[:, -1, :]

        ns = logits.shape[0]
        # the tick's own lines carry scopes of their own, as the model's
        # layers do: no scope on a device operation means nobody named it
        with scope("sample"):
            if mode == "greedy":
                # the batched-argmax fast path, bitwise the uncompiled
                # lane's S.argmax over raw last-position logits
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                tok = choose_tokens(logits, temp, topk, topp, pen, seen,
                                    keys, counts)
        with scope("tick_state"):
            tok = jnp.where(alive, tok, last)
            rows = jnp.arange(ns)
            new_seen = seen.at[rows, tok].set(seen[rows, tok] | alive)
            new_counts = counts + alive.astype(counts.dtype)
            eos_hit = alive & (eos >= 0) & (tok == eos)
            len_hit = alive & (new_counts >= limits)
            fin = jnp.where(eos_hit, 1,
                            jnp.where(len_hit, 2, 0)).astype(jnp.int32)
            new_alive = alive & (fin == 0)
            new_last = jnp.where(alive, tok, last)
            new_off = off + alive.astype(off.dtype)
            # the tick's own tokens leave with ``fin`` (-1: the row made
            # none): the host appends them to the requests tick by tick
            made = jnp.where(alive, tok, -1).astype(jnp.int32)
        return (new_pools, new_off, new_last, new_counts,
                new_alive, new_seen, fin, made, moe)

    def _build_jit(self, mode, donating):
        from ..core.op_cache import ensure_compile_cache
        ensure_compile_cache()      # tier-2 persistent XLA compile cache

        def serving_tick(pools, pt, off, last, counts, alive, seen,
                         limits, eos, temp, topk, topp, pen, keys, caps,
                         pt_w):
            return self._traced(mode, pools, pt, off, last, counts,
                                alive, seen, limits, eos, temp,
                                topk, topp, pen, keys, caps, pt_w)

        # the program's name on the trace's ``XLA Modules`` line
        serving_tick.__name__ = serving_tick.__qualname__ = \
            "serving_tick_" + mode

        # every buffer the tick replaces is donated: the pools and the
        # last/counts/alive/seen scheduler state (``off`` is not — on a
        # dirty tick it is the cache's own offset array)
        donate = (0, 3, 4, 5, 6) if donating else ()
        return jax.jit(serving_tick, donate_argnums=donate)

    def lowered_text(self, mode="greedy", optimized=False):
        """StableHLO text of a tick program that has run in ``mode`` —
        what a reader checks to see which kernels are in it (Pallas
        kernels appear as ``tpu_custom_call``); with ``optimized`` the
        compiled program's HLO, layouts and the compiler's own copies
        included.  None if none has."""
        from ..core.state import no_grad
        for key, sig in self._sigs.items():
            if key[0] == mode:
                # re-traces through the model, as the scheduler loop does
                with TRACE_LOCK, no_grad():
                    low = self._jits[key].lower(*sig)
                    return low.compile().as_text() if optimized \
                        else low.as_text()
        return None

    # ------------------------------------------------------------------
    # the prefill member: one donated program per chunk call
    # ------------------------------------------------------------------

    def prefill_buckets(self):
        """Row counts the family has a prefill member for: the powers
        of two below the most rows one call takes (``num_slots``, or
        fewer by the call's token budget), and that number."""
        ns = self.eng._prefill_rows
        rows, out = 1, []
        while rows < ns:
            out.append(rows)
            rows *= 2
        return out + [ns]

    def _build_prefill_jit(self, rows, donating):
        has_state = self.eng.cache.has_state
        num_slots = self.eng.cache.num_slots

        def serving_prefill(pools, pt, off, tokens, last, lora_idx,
                            state_rows, caps, pt_w, valid):
            # the pad positions after a row's last real one must leave a
            # recurrent state as it was; ``valid`` is the host's count of
            # each row's real positions, 0 for a surplus row (routing
            # counts leave those out)
            logits, new_pools, moe = self._replay_model(
                tokens, pools, pt, off, caps, lora_idx, state_rows,
                valid, pt_w)
            # each row's logits at its last real position: all the host
            # ever reads of a chunk — padded to [num_slots, V], the eager
            # lane's shape, so that the host's row slices are the same
            # few programs whatever bucket ran
            with scope("pick_last"):
                picked = jnp.take_along_axis(
                    logits, last[:, None, None], axis=1)[:, 0]
                picked = jnp.pad(picked, ((0, num_slots - rows), (0, 0)))
            return new_pools, picked, moe

        # never ``serving_tick``: readers of the device trace tell ticks
        # from the other programs by that
        serving_prefill.__name__ = serving_prefill.__qualname__ = \
            f"serving_prefill_r{rows}"
        return jax.jit(serving_prefill,
                       donate_argnums=(0,) if donating else ())

    def _compile_prefill_family(self, donating):
        """Every member, compiled (or loaded from the tier-2 cache) from
        shapes alone — nothing runs, so no bucket is met cold in
        traffic, whatever row counts the admission loop has batched so
        far."""
        from ..core.op_cache import ensure_compile_cache
        ensure_compile_cache()
        eng = self.eng
        cache = eng.cache
        chunk = eng._chunk
        lowered = {}
        for rows in self.prefill_buckets():
            jit = self._build_prefill_jit(rows, donating)
            lora = None if eng.adapter_pool is None \
                else np.zeros(rows, np.int32)
            with TRACE_LOCK:
                pools, caps = self._donated_and_captured()
                args = (pools,
                        np.zeros((rows, cache.pages_per_slot), np.int32),
                        np.zeros(rows, np.int32),
                        np.zeros((rows, chunk), np.int32),
                        np.zeros(rows, np.int32), lora,
                        cache.state_rows((), rows) if cache.has_state
                        else None, caps,
                        cache.prefill_window_table((), rows),
                        np.zeros(rows, np.int32))
                lowered[rows] = jit.lower(*args)
            key = (f"prefill_r{rows}", donating)
            self._jits[key] = jit
            self._sigs[key] = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        # tracing is the interpreter's and serial; XLA's compiles (and
        # the cache's loads) are not, and a family has several
        with ThreadPoolExecutor(len(lowered)) as pool:
            built = {rows: pool.submit(low.compile)
                     for rows, low in lowered.items()}
            for rows, fut in built.items():
                self._prefill[rows, donating] = fut.result()
                scopes.publish(fut.result())

    def prefill_member(self, n):
        """(rows, program) of the compiled member that hosts a chunk call
        of ``n`` prefilling requests — the smallest bucket that holds
        them — or None when the call has to take the eager lane (counted
        under ``serving.prefill.fallbacks``, warned once a reason).  The
        first call runs the family's one discovery pass, if the tick has
        not yet, and compiles every member."""
        if not _flag("FLAGS_compiled_tick", True):
            return None
        if self._disabled is not None:
            stats.incr("prefill.fallbacks")
            return None
        blk = self._model_blocker()
        if blk is not None:
            self._note_fallback(*blk, counter="prefill.fallbacks")
            return None
        donating = bool(_flag("FLAGS_jit_donate_buffers", True))
        buckets = self.prefill_buckets()
        try:
            if not self._built:
                self._capture()
            if (buckets[0], donating) not in self._prefill:
                self._compile_prefill_family(donating)
        except USER_TRACE_ERRORS as e:
            # raised while tracing, so before anything was donated
            self._note_fallback("trace", describe_escape(e), True,
                                counter="prefill.fallbacks")
            return None
        rows = next(r for r in buckets if r >= n)
        return rows, self._prefill[rows, donating]

    def run_prefill(self, member, slots, starts, tokens, last, lora_rows):
        """One chunk call as ONE program over the donated pools: the
        ``prefill_member``'s [rows, chunk] ``tokens`` for ``slots`` at
        write offsets ``starts``; returns the logits at each row's
        ``last`` position, [num_slots, V] with the rows first.  The
        cache has adopted the new pools on return."""
        cache = self.eng.cache
        rows, program = member
        with span("serving.prefill.view"):
            table, off = cache.prefill_table(slots, starts, rows)
            state_rows = cache.state_rows(slots, rows) \
                if cache.has_state else None
            table_w = cache.prefill_window_table(slots, rows)
            valid = np.zeros(rows, np.int32)
            valid[:len(slots)] = last[:len(slots)] + 1
        with span("serving.prefill.model"):
            # TRACE_LOCK as in the tick: parameter slots may hold another
            # engine's tracers while it traces
            with TRACE_LOCK:
                pools, caps = self._donated_and_captured()
                new_pools, picked, moe = program(
                    pools, table, off, tokens, last, lora_rows, state_rows,
                    caps, table_w, valid)
            # the call's device time belongs to the call's span
            picked.block_until_ready()
            if moe is not None:
                count_routing(np.asarray(moe), prefill=True)
        with span("serving.prefill.absorb"):
            cache.absorb_pools(new_pools)
        stats.incr("prefill.compiled_hits")
        return Tensor(picked)

    # ------------------------------------------------------------------
    # host <-> device state sync
    # ------------------------------------------------------------------

    def flush_to_host(self):
        """Materialize device-side token progress back into the request
        objects (the step the uncompiled lane needs before it can take
        over mid-request): every collected tick has handed its tokens
        over already, so collecting the tick in flight is all of it."""
        self.drain()
        self._dev = None            # force a rebuild before the next tick

    def _rebuild(self):
        """(Re)upload the scheduler state from the request objects —
        the admission/completion host boundary."""
        eng = self.eng
        cache = eng.cache
        ns = cache.num_slots
        vocab = eng.cfg.vocab_size
        last = np.zeros(ns, np.int32)
        counts = np.zeros(ns, np.int32)
        limits = np.full(ns, np.iinfo(np.int32).max, np.int32)
        eos = np.full(ns, -1, np.int32)
        alive = np.zeros(ns, bool)
        temp = np.zeros(ns, np.float32)
        topk = np.zeros(ns, np.int32)
        topp = np.ones(ns, np.float32)
        pen = np.ones(ns, np.float32)
        keys = np.zeros((ns, 2), np.uint32)
        seen = np.zeros((ns, vocab), bool)
        for slot, req in eng._active.items():
            alive[slot] = True
            last[slot] = req.last_token
            counts[slot] = len(req.tokens)
            limits[slot] = min(req.max_new_tokens,
                               eng.max_len - req.prompt.size)
            if req.eos_token_id is not None:
                eos[slot] = req.eos_token_id
            sp = req.sampling
            temp[slot] = sp.temperature
            topk[slot] = sp.top_k or 0
            if sp.top_p is not None:
                topp[slot] = sp.top_p
            if sp.repetition_penalty is not None:
                pen[slot] = sp.repetition_penalty
            if not sp.greedy and sp.seed is not None:
                keys[slot] = request_key(sp)
            if req.seen is not None:
                seen[slot] = req.seen
        self._dev = {
            "last": jnp.asarray(last), "counts": jnp.asarray(counts),
            "limits": jnp.asarray(limits), "eos": jnp.asarray(eos),
            "alive": jnp.asarray(alive), "temp": jnp.asarray(temp),
            "topk": jnp.asarray(topk), "topp": jnp.asarray(topp),
            "pen": jnp.asarray(pen), "keys": jnp.asarray(keys),
            "seen": jnp.asarray(seen),
        }
        self._h_counts = counts.copy()
        self._h_limits = limits
        self._mut_seen = eng._mut

    # ------------------------------------------------------------------
    # one tick
    # ------------------------------------------------------------------

    def step(self):
        eng = self.eng
        if not _flag("FLAGS_compiled_tick", True):
            self.flush_to_host()        # flag flipped mid-run
            return False
        if self._disabled is not None:
            stats.incr("tick.fallbacks")
            return False
        blk = self._blocker()
        if blk is not None:
            self.flush_to_host()
            self._note_fallback(blk[0], blk[1], blk[2])
            return False
        if not self._built:
            try:
                self._capture()
            except USER_TRACE_ERRORS as e:
                self._note_fallback("capture", describe_escape(e), True)
                return False
        if eng._mut != self._mut_seen or self._dev is None:
            # a mutation of request/slot state: the tick in flight is
            # collected (inside the flush) before the state is re-made
            self.flush_to_host()
            if not eng._active:
                return True             # what it delivered finished them all
            with span("serving.tick.rebuild"):
                self._rebuild()
        return self._run()

    def drain(self):
        """Collect the tick in flight, if there is one: what anything
        that reads or rewrites what it holds does first — a mutation's
        flush and rebuild, a blocker's hand-over to the eager lane, a
        migration export, an engine with nothing left to launch, the
        scheduler's clean stop."""
        if self._pending is not None:
            stats.incr("tick.drains")
            self._collect()

    def _build_args(self, active):
        """Host work before the launch: page growth, the lazy flush of
        the page table, the program for this mode, its arguments."""
        eng = self.eng
        cache = eng.cache
        eng._max_active = max(eng._max_active, len(active))
        stats.set_value("max_active_slots", eng._max_active)
        # page-by-page growth exactly like the uncompiled step: the
        # admission reservation guarantees the host-side pop succeeds
        for slot in active:
            cache.ensure_capacity(slot, int(cache.offsets[slot]))
        # pages held against pages promised, summed over ticks
        stats.incr("kv.page_ticks_in_use",
                   cache.pages_in_use + cache.window_pages_in_use)
        stats.incr("kv.page_ticks_reserved",
                   cache.usable_pages - cache.available_pages
                   + cache.window_pages_promised)
        if cache.ring_pages or cache.latent_pools:
            # the contexts a window or latent layer's decode read covers
            ctx = cache.offsets[list(active)] + 1
            stats.incr("kv.context_token_ticks", int(ctx.sum()))
        if cache.ring_pages:
            # what the window layers hold against what one shared table
            # would have held for them, and the tokens a window layer's
            # decode read covers against the contexts
            stats.incr("kv.window.page_ticks_held",
                       cache.window_pages_in_use)
            stats.incr("kv.window.page_ticks_full_equiv",
                       cache.pages_in_use)
            stats.incr("kv.window.token_ticks",
                       int(np.minimum(ctx, cache.window).sum()))
        # page table / offsets: host mutations (admission, release,
        # growth) flow through the cache's own lazy flush; steady-state
        # ticks ride the previous program's device outputs
        pt, off = cache.table_arrays()
        pt_w = cache.window_table_array()
        mode = "greedy" if all(
            r.sampling.greedy and not r.sampling.uses_penalty
            for r in active.values()) else "mixed"
        donating = bool(_flag("FLAGS_jit_donate_buffers", True))
        key = (mode, donating)
        if key not in self._jits:
            self._jits[key] = self._build_jit(mode, donating)
        d = self._dev
        pools, caps = self._donated_and_captured()
        args = (pools, pt, off, d["last"], d["counts"],
                d["alive"], d["seen"], d["limits"],
                d["eos"], d["temp"], d["topk"], d["topp"],
                d["pen"], d["keys"], caps, pt_w)
        if key not in self._sigs:
            self._sigs[key] = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        if key not in self._ticks:
            # built as the prefill members are, the first time the mode
            # is met (what the jit's own first call would do): the tick
            # holds its executable, so its HLO reaches the scope tables
            # without a second compile
            self._ticks[key] = self._jits[key].lower(*args).compile()
            scopes.publish(self._ticks[key])
        return self._ticks[key], args

    def _donated_and_captured(self):
        """What every member of the family takes beside its own rows:
        the cache's pools and the captured tensors' current arrays.
        Read under ``TRACE_LOCK``."""
        return (self.eng.cache.flat_pools(),
                tuple(t._data_ for t in self._caps))

    def _run(self):
        """Launch the next tick, THEN read the one before it: the wait
        for ``fin`` and the deliveries it decides run under the program
        just launched."""
        eng = self.eng
        # a row at its token limit ended in the tick before this one, and
        # the host knows without ``fin``: it is left out of the launch
        # (growth, mirrors).  An eos is not known: its row rides the next
        # tick dead on the device — the program masks it, as it masks an
        # empty slot — and costs its own reservation at most one page
        # until its delivery releases it.
        live = {slot: req for slot, req in eng._active.items()
                if self._h_counts[slot] < self._h_limits[slot]}
        if not live:
            self.drain()        # the tick in flight finishes every row
            return True
        attrs = {"request_ids": sorted(r.id for r in live.values()),
                 "compiled_tick": True} if tracing.enabled() else {}
        with span("serving.tick", hist="serving.decode_ms", **attrs) as tick:
            try:
                nxt = self._launch(live)
            except USER_TRACE_ERRORS as e:
                # the model body cannot be traced (host reads of raw array
                # slots, data-dependent control flow) — raised during the
                # trace, so before the pools were donated: latch the
                # uncompiled scheduler permanently.  Any other failure
                # (lowering, compile, device) propagates to the scheduler's
                # restart wrapper, which fails the futures with the real
                # error and rebuilds the cache: the uncompiled iteration
                # would call the same kernels.
                self.flush_to_host()
                self._dev = None
                self._note_fallback("trace", describe_escape(e), True)
                return False
            collect_ms = self._collect()
            self._pending = nxt
        # the tick's time on the host: its launching call less the
        # collection of the tick before, plus (at its own collection) its
        # deliveries — all of it but the wait for the device's ``fin``
        nxt.host_ms = tick.ms - collect_ms
        return True

    def _launch(self, live):
        """Build and launch one tick over the ``live`` rows from the last
        one's outputs as they stand, and settle on the host what the
        next launch needs settled: the cache adopts the new pools and
        offsets (the old ones are donated and gone) and the host mirrors
        advance in lockstep, so page growth, admission and a fallback
        see the truth without reading the device."""
        cache = self.eng.cache
        # TRACE_LOCK covers reading the (possibly shared) parameter
        # slots AND the program call: while ANOTHER engine's tick
        # traces, those slots hold tracer arrays — gathering them
        # here would bake a leaked tracer into this engine's call
        with TRACE_LOCK:
            with span("serving.tick.build"):
                program, args = self._build_args(live)
            with span("serving.tick.launch"):
                (new_pools, new_off, new_last, new_counts, new_alive,
                 new_seen, fin, made, moe) = program(*args)
                rows = list(live)
                # a row that ends by eos in the tick in flight keeps its
                # device offset while this mirror moves on: a dirty flush
                # then uploads an offset one too far for a row that is
                # dead on the device and released at its delivery
                offsets_np = cache.offsets.copy()
                offsets_np[rows] += 1
                cache.absorb_tick(new_pools, new_off, offsets_np)
                self._dev.update(last=new_last, counts=new_counts,
                                 alive=new_alive, seen=new_seen)
                self._h_counts[rows] += 1
        return _Launched(fin, made, live, self._pending is not None, moe)

    def _collect(self):
        """Read the finish codes of the tick in flight — the one blocking
        read of a steady tick; it returns when that tick ends, while its
        successor runs — and deliver it.  Returns the milliseconds this
        took, the wait included; 0.0 with nothing in flight."""
        tick, self._pending = self._pending, None
        if tick is None:
            return 0.0
        # a row whose request has left since the launch — ended by eos a
        # tick earlier, cancelled — ran dead or for nobody: no token of
        # this tick is counted or delivered for it
        rows = {slot: req for slot, req in tick.active.items()
                if self.eng._active.get(slot) is req}
        with span("serving.tick.sync") as sync:
            fin_np = np.asarray(tick.fin)
            # the same program's outputs as ``fin``: no wait of their own
            toks = np.asarray(tick.tok)
            if tick.moe is not None:
                count_routing(np.asarray(tick.moe))
        with span("serving.tick.deliver") as deliver:
            for slot, req in rows.items():
                tok = int(toks[slot])
                if tok >= 0:        # -1: the row ran dead on the device
                    req.tokens.append(tok)
                    req.last_token = tok
                    if req.seen is not None:
                        req.seen[tok] = True
            self._deliver(tick, rows, self._ending(rows, fin_np))
        stats.observe("tick.host_ms", tick.host_ms + deliver.ms)
        return sync.ms + deliver.ms

    def _ending(self, rows, fin_np):
        """{slot: reason} of the ``rows`` this tick was the last of: "eos"
        or "length" by its finish code, None for a deadline the clock
        has passed — same per-token granularity (and precedence over
        eos/length) as the uncompiled ``_append_token``."""
        now = time.monotonic()
        evict = self.eng.scfg.deadline_policy == "evict"
        ending = {}
        for slot, req in rows.items():
            if evict and req.deadline is not None and now > req.deadline:
                ending[slot] = None
            elif fin_np[slot]:
                ending[slot] = "eos" if fin_np[slot] == 1 else "length"
        return ending

    def _deliver(self, tick, rows, ending):
        """One collected tick's accounting and deliveries: counters,
        deadline eviction, completions, releases."""
        eng = self.eng
        cache = eng.cache
        n_live = len(rows)
        stats.incr("decode_steps")
        stats.incr("tick.compiled_hits")
        if tick.overlapped:
            stats.incr("tick.overlapped")
        stats.incr("slot_steps", cache.num_slots)
        stats.incr("slot_steps_active", n_live)
        stats.incr("tokens_generated", n_live)
        if cache.has_state:
            # state rows the tick moved for a request, of all it
            # passed through the update
            stats.incr("state.row_ticks_live", n_live)
            stats.incr("state.row_ticks_total", cache.num_slots)

        now = time.monotonic()
        for slot, reason in ending.items():
            req = rows[slot]
            if reason is None:
                from .api import DeadlineExceededError
                eng._fail(req, DeadlineExceededError(
                    f"request {req.id} exceeded its deadline after "
                    f"{len(req.tokens)} token(s)"))
                stats.incr("requests_evicted_deadline")
            else:
                eng._complete(req, reason, now)
            eng._release(req)
        stats.set_value("active_slots", len(eng._active))
