"""Continuous-batching inference engine.

Reference capability: the serving stacks the reference feeds through
`AnalysisPredictor` put a request queue and a batcher in front of the
blocking `run()`.  TPU-native realization (Orca/vLLM-style): because
every decode step is the SAME static-shape compiled program (PR 1 caches
the executable), throughput is purely a matter of keeping that program
FED.  A background scheduler thread:

1. admits queued requests into free KV slots (chunked prefill, sampled
   first token → time-to-first-token),
2. runs ONE batched decode step per iteration over all `num_slots` slots
   — per-slot offsets (serving/paged_kv.py) let sequences of different
   ages share the step, and a finished/evicted slot is refilled on the
   next iteration without draining the batch,
3. applies per-request sampling params (the processor chain factored out
   of models/generation.py) and completes futures on EOS, max-tokens,
   deadline, or shutdown.

Requests never see each other: slots are independent batch rows, masked
to their own causal horizon.
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time
from collections import deque
from concurrent.futures import Future

import numpy as np

from . import stats
from ..observability import tracing
from ..observability.tracing import span
from ..utils import fault_injection as _fi
from .api import (DeadlineExceededError, EngineShutdownError,
                  LatentStoreError, QueueFullError, RecurrentStateError,
                  RequestCancelledError, RequestOutput, SamplingParams,
                  SchedulerStallError, ServingConfig, WindowLayerError)
from ..models.generation import recurrent_layer_states
from ..models.sparse_experts import SparseExpertMLP


class _Request:
    __slots__ = ("id", "prompt", "max_new_tokens", "sampling",
                 "eos_token_id", "deadline", "future", "submit_t",
                 "ttft_ms", "tokens", "seen", "last_token", "slot",
                 "prefill_pos", "shared_len", "prefix_nodes",
                 "draft_prefill_pos", "first_tok", "handoff", "resume",
                 "adapter_id", "adapter_slot", "trace")

    def __init__(self, rid, prompt, max_new_tokens, sampling,
                 eos_token_id, deadline):
        self.id = rid
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.sampling = sampling
        self.eos_token_id = eos_token_id
        self.deadline = deadline
        self.future = Future()
        self.submit_t = time.monotonic()
        self.ttft_ms = None
        self.tokens = []
        self.seen = None            # [V] bool, only under rep penalty
        self.last_token = 0
        self.slot = None
        self.prefill_pos = 0        # next prompt token to prefill
        self.shared_len = 0         # prompt tokens reused from the tree
        self.prefix_nodes = []      # tree nodes this request references
        self.draft_prefill_pos = 0  # draft-model prefill progress (spec)
        self.first_tok = None       # sampled first token awaiting draft
        self.handoff = None         # decode-replica target (disagg)
        self.resume = None          # migrated-page payload + prior state
        self.adapter_id = None      # LoRA adapter this request decodes
        self.adapter_slot = 0       # its pool slot (0 = base identity)
        self.trace = None           # _ReqTrace holder (tracing armed)


class _ReqTrace:
    """Per-request span holder, existing only when ``FLAGS_trace_dir``
    is set: the engine-side request span plus the phase spans hanging
    off it (queue wait, chunked prefill, decode, migration transfer /
    remote wait).  ``owns_root`` marks a request whose trace the ENGINE
    minted (no upstream context on the rpc envelope): only that owner
    ends the trace with a tail-sampling decision — routed requests
    leave both the winner mark and the decision to the router."""

    __slots__ = ("root", "queue", "prefill", "decode", "transfer",
                 "remote", "owns_root")

    def __init__(self, root, owns_root):
        self.root = root
        self.owns_root = owns_root
        self.queue = None
        self.prefill = None
        self.decode = None
        self.transfer = None
        self.remote = None

    def finish(self, status, latency_ms, **attrs):
        """Terminal close: end every still-open phase span with the
        request's outcome (``end`` is idempotent — already-closed spans
        keep their own status), end the request span, and make the
        tail-sampling decision iff this engine owns the root."""
        for sp in (self.queue, self.prefill, self.decode,
                   self.transfer, self.remote):
            if sp is not None:
                sp.end(status=status)
        self.root.end(status=status,
                      winner=True if self.owns_root and status == "ok"
                      else None, **attrs)
        if self.owns_root:
            tracing.decide(self.root.ctx.trace_id, status=status,
                           latency_ms=latency_ms)


#: tokens one prefill chunk call computes at most (rows x chunk): what
#: bounds its activations, whatever the model and however many slots
PREFILL_CALL_TOKENS = 2048


def _window_layers(cfg):
    """What ``cfg.layer_windows()`` says each layer's attention sees (None:
    every position; else its latest ``window``), or None when the config
    has no such method or no layer has a window."""
    windows = getattr(cfg, "layer_windows", None)
    windows = None if windows is None else windows()
    return windows if windows and any(w is not None for w in windows) \
        else None


def _latent_layers(cfg):
    """What ``cfg.layer_latents()`` says each layer caches a token (None:
    keys and values; else the width of its one latent row), or None when
    the config has no such method or no layer is latent."""
    latents = getattr(cfg, "layer_latents", None)
    latents = None if latents is None else latents()
    return latents if latents and any(w is not None for w in latents) \
        else None


def _storeless_layers(cfg):
    """What ``cfg.storeless_layers()`` says of each layer (True: it holds
    no page and no state row — a feed-forward part alone), or None when
    the config has no such method or no layer is one."""
    bare = getattr(cfg, "storeless_layers", None)
    bare = None if bare is None else bare()
    return bare if bare and any(bare) else None


class Engine:
    """`Engine(model).start()`; then `submit()` (async, returns a
    `Future[RequestOutput]`) or `generate()` (sync).  `shutdown()` stops
    the scheduler and fails every queued/in-flight future with
    `EngineShutdownError` — no leaked threads, no hung clients."""

    def __init__(self, model, config: ServingConfig | None = None):
        self.model = model
        self.cfg = model.config
        self.scfg = (config or ServingConfig()).validate()
        if hasattr(model, "eval"):
            model.eval()            # serving never wants dropout
        self.max_len = self.scfg.max_seq_len or self.cfg.max_seq_len
        self._kv_heads = getattr(self.cfg, "num_kv_heads",
                                 getattr(self.cfg, "num_heads", None))
        from ..quantization import kv_quant_params
        self._quant = kv_quant_params(self.scfg.cache_dtype) is not None
        # a quantized page packs 2x the baseline page's tokens in half
        # its bytes: the pages-in-use gauge at equal token load ~halves
        # and the pool's byte budget stretches (docs/SERVING.md)
        self._page_size = self.scfg.page_size * (2 if self._quant else 1)
        self._spec_k = int(self.scfg.speculation_k)
        self._spec = bool(self._spec_k > 0
                          and self.scfg.draft_model is not None)
        if self._spec:
            draft = self.scfg.draft_model
            if hasattr(draft, "eval"):
                draft.eval()
            dcfg = draft.config
            if dcfg.max_seq_len < self.max_len:
                raise ValueError(
                    f"draft_model.config.max_seq_len {dcfg.max_seq_len} "
                    f"< serving max_seq_len {self.max_len}; the draft "
                    "must cover every position it proposes for")
            if dcfg.vocab_size != self.cfg.vocab_size:
                raise ValueError(
                    f"draft_model vocab {dcfg.vocab_size} != target "
                    f"vocab {self.cfg.vocab_size}")
        self.draft_cache = None
        # which layers keep a fixed-size recurrent state per sequence
        # instead of keys and values: the model's config says
        self._layer_states = None
        if hasattr(self.cfg, "layer_states"):
            self._layer_states = recurrent_layer_states(
                self.cfg, next(iter(model.parameters()))._data_.dtype)
        self._refuse_for_recurrent_state()
        # which layers' attention sees a window of positions only: those
        # keep a ring of pages a slot behind a page table of their own
        # (None where no layer does)
        self._layer_windows = _window_layers(self.cfg)
        self._refuse_for_window_layers()
        # which layers cache one latent row a token instead of keys and
        # values: paged like any other, in a store of their own shape
        self._layer_latents = _latent_layers(self.cfg)
        self._refuse_for_latent_layers()
        self._pages_peak = 0
        self._queue: deque[_Request] = deque()
        self._active: dict[int, _Request] = {}
        # requests holding a slot whose prompt is mid-(chunked-)prefill
        self._prefilling: deque[_Request] = deque()
        # the prefill round launched and not yet finished: [(request,
        # its first token drawn on the device)] for the prompts its chunk
        # call completed; None between rounds
        self._prefill_ended = None
        self.prefix_tree = None
        self._max_active = 0
        # EVERY unresolved request, from submit() until its future
        # resolves — the audit set _fail_all drains.  A request can be
        # outside both _queue and _active (popped for admission, prefill
        # not yet finished); without this registry a scheduler crash in
        # that window would leave its client blocked forever.
        self._pending: dict[int, _Request] = {}
        # RLock: _fail/_complete pop the pending registry under the lock
        # and are reached from paths that already hold it (the queue
        # expiry sweep runs inside the admission critical section)
        self._lock = threading.RLock()
        self._work = threading.Condition(self._lock)
        self._running = False
        self._draining = False
        self._thread = None
        self._ids = itertools.count()
        self.cache = None
        # compiled scheduler tick (serving/compiled_tick.py): ONE
        # donated-buffer jit program per iteration over device-resident
        # state, with admission/completion as the only host boundary.
        # _mut counts host-lane mutations of request/slot state so the
        # tick knows when its device mirror must be rebuilt.
        self._tick = None
        self._mut = 0
        # pool-gauge throttle: publishing every iteration took the
        # metrics-registry lock in the hot loop (the same drift class as
        # the PR 8 tier-1 op-cache fix) — flush on-change or every
        # _POOL_PUBLISH_EVERY ticks
        self._pool_pub = None
        self._pool_iters = 0
        # scheduler-thread watchdog state (step_timeout_s > 0)
        self._sched_tid = None
        self._iter_deadline = None
        self._restarts = 0
        self._monitor = None
        self._monitor_stop = threading.Event()
        self._stall_swept = False
        self._preemption_handler = None
        # live KV-page migration (prefill/decode disaggregation): the
        # hosting ReplicaServer installs `migrator(req, header, blobs,
        # target) -> ack` (phase 1: transfer + remote adopt — once it
        # returns, the LOCAL pages are free) and `migration_awaiter(req,
        # ack) -> result payload` (phase 2: block for the remote decode
        # with no local resources held).  None = this engine never
        # migrates (the pre-disaggregation engine, byte-for-byte)
        self._migrator = None
        self.migration_awaiter = None
        self._migrating_out: dict[int, _Request] = {}
        self._migration_results: deque = deque()
        self._migrate_failed: set[int] = set()
        self._drain_migrate = False
        # cancellation (hedged-dispatch losers, chaos drills): ids whose
        # slot-resident state the SCHEDULER must unwind inside its own
        # iteration — prefill/decode run outside the lock, so another
        # thread can never release a live slot directly
        self._cancels: set[int] = set()
        # the hosting ReplicaServer stamps its name here so the
        # `engine_slow` gray-failure point can target one replica
        self.fault_name = None
        # multi-tenant LoRA (serving/adapters.py): preallocated A/B
        # stacks per wrapped projection + per-slot int32 adapter index.
        # Built (and the registry validated — typed AdapterConfigError)
        # at construction; None when max_adapters == 0, in which case
        # every model call below is byte-identical to the pre-LoRA
        # engine (the projection patches are inert without an active
        # pool context).
        self.adapter_pool = None
        if self.scfg.max_adapters > 0:
            from .adapters import AdapterPool
            self.adapter_pool = AdapterPool(
                model, self.scfg.max_adapters,
                self.scfg.adapter_rank_pool, self.scfg.num_slots)
            for aid, source in (self.scfg.adapters or {}).items():
                self.adapter_pool.register(aid, source)

    def _refuse_for_recurrent_state(self):
        """Typed refusals, at construction, of what cannot carry a
        recurrent state yet (docs/SERVING.md "Recurrent state beside
        pages")."""
        draft = self.scfg.draft_model
        if draft is not None and \
                recurrent_layer_states(draft.config) is not None:
            raise RecurrentStateError(
                "draft_model has layers that keep a recurrent state: "
                "the draft cache holds keys and values only")
        if self._layer_states is None:
            return
        n = sum(s is not None for s in self._layer_states)
        why = f"{n} of the model's layers keep a recurrent state"
        if self.scfg.enable_prefix_cache:
            raise RecurrentStateError(
                f"enable_prefix_cache=True: {why}, and a shared prefix "
                "would need the state as it stood at the prefix's end, "
                "which no page holds; pass enable_prefix_cache=False")
        if self._spec_k > 0:
            raise RecurrentStateError(
                f"speculation_k={self._spec_k}: {why}, and a rejected "
                "tail cannot be rolled back out of a recurrence by "
                "moving an offset")
        if self.scfg.role != "mixed":
            raise RecurrentStateError(
                f"role={self.scfg.role!r}: {why}, and page export / "
                "migration carries keys and values only")

    def _refuse_for_window_layers(self):
        """Typed refusals, at construction, of what cannot serve
        sliding_attention layers yet (docs/SERVING.md "Page tables by
        layer kind"): each would share, rewind or send pages that a
        window layer's ring has already given up."""
        draft = self.scfg.draft_model
        if draft is not None and _window_layers(draft.config) is not None:
            raise WindowLayerError(
                "draft_model has sliding_attention layers: the draft "
                "cache keeps one page table")
        if self._layer_windows is None:
            return
        n = sum(w is not None for w in self._layer_windows)
        why = f"{n} of the model's layers are sliding_attention layers"
        if self.scfg.enable_prefix_cache:
            raise WindowLayerError(
                f"enable_prefix_cache=True: {why}, whose ring keeps no "
                "page of a prefix that fell out of the window for the "
                "PrefixTree to share; pass enable_prefix_cache=False")
        if self._spec_k > 0:
            raise WindowLayerError(
                f"speculation_k={self._spec_k}: {why}, and rollback "
                "cannot bring back the page a rejected tail's ring entry "
                "overwrote")
        if self.scfg.role != "mixed":
            raise WindowLayerError(
                f"role={self.scfg.role!r}: {why}, and export_pages / "
                "adopt_pages carry one page table's pages")

    def _refuse_for_latent_layers(self):
        """Typed refusals, at construction, of what cannot serve a latent
        page store yet (docs/SERVING.md "Latent pages").  Prefix sharing
        and speculative rollback move pages through the page table alone
        and are NOT refused: a latent page is a page."""
        draft = self.scfg.draft_model
        if draft is not None and _latent_layers(draft.config) is not None:
            raise LatentStoreError(
                "draft_model has layers that keep a latent page store: "
                "the draft cache holds keys and values only")
        if self._layer_latents is None:
            return
        n = sum(w is not None for w in self._layer_latents)
        why = f"{n} of the model's layers keep a latent page store"
        if self._quant:
            raise LatentStoreError(
                f"cache_dtype={self.scfg.cache_dtype!r}: {why}, one row a "
                "token with no per-page K/V scales; pass a float "
                "cache_dtype")
        if self.scfg.role != "mixed":
            raise LatentStoreError(
                f"role={self.scfg.role!r}: {why}, and export_pages / "
                "adopt_pages carry a K/V page store's k and v pages")

    @property
    def migrator(self):
        return self._migrator

    @migrator.setter
    def migrator(self, fn):
        if fn is not None and self._layer_states is not None:
            raise RecurrentStateError(
                "migrator: page export / migration carries keys and "
                "values only, and the model's layers keep a recurrent "
                "state")
        if fn is not None and self._layer_windows is not None:
            raise WindowLayerError(
                "migrator: export_pages / adopt_pages carry one page "
                "table's pages, and the model's sliding_attention layers "
                "keep a ring of their own")
        if fn is not None and self._layer_latents is not None:
            raise LatentStoreError(
                "migrator: export_pages / adopt_pages carry a K/V page "
                "store's k and v pages, and the model's layers keep a "
                "latent page store")
        self._migrator = fn

    # ---------------- lifecycle ----------------
    def start(self):
        from ..observability.exporter import maybe_start_exporter
        maybe_start_exporter()          # no-op unless the flag names a path
        with self._lock:
            if self._running:
                return self
            stats.reset_serving_stats()
            stats.declare_tick_stats()
            stats.declare_migration_stats()
            stats.declare_adapter_stats()
            stats.declare_trace_stats()
            self.cache = self._new_cache()
            self._tick = self._make_tick()
            self._max_active = 0
            self._pool_pub = None
            self._pool_iters = 0
            self._queue_mark = (0, time.monotonic())
            self._running = True
            self._draining = False
            self._restarts = 0
            self._stall_swept = False
        self._thread = threading.Thread(
            target=self._loop, name="paddle-tpu-serving", daemon=True)
        self._thread.start()
        if self.scfg.step_timeout_s > 0:
            self._monitor_stop.clear()
            self._monitor = threading.Thread(
                target=self._stall_monitor,
                name="paddle-tpu-serving-watchdog", daemon=True)
            self._monitor.start()
        return self

    def _new_cache(self):
        """Fresh KV storage (and prefix tree, and the draft model's
        mirror cache when speculating) for a (re)started loop."""
        from .paged_kv import PagedKVCache, PrefixTree
        # +speculation_k positions of headroom: a verify window may
        # write K tokens past the last real position before the
        # accept-mask rollback rewinds them
        slot_len = self.max_len + self._spec_k
        if self._layer_states is not None:
            # a last chunk that would overrun the slot is shifted
            # left and re-feeds tokens, which pages forgive and a
            # recurrence does not: whole chunks always fit (the
            # extra table entries stay on the scratch page)
            chunk = min(self.scfg.prefill_chunk_tokens, slot_len)
            slot_len = -(-slot_len // chunk) * chunk
        cache = PagedKVCache(
            self.cfg.num_layers, self.scfg.num_slots, slot_len,
            self._kv_heads, getattr(self.cfg, "head_dim", None),
            page_size=self._page_size,
            num_pages=self.scfg.kv_pool_pages,
            dtype=self.scfg.cache_dtype,
            layer_states=self._layer_states,
            layer_windows=self._layer_windows,
            # the widest run of positions one call writes
            window_slack=min(self.scfg.prefill_chunk_tokens, slot_len),
            layer_latents=self._layer_latents,
            layer_storeless=_storeless_layers(self.cfg))
        stats.set_value("state.bytes", cache.state_bytes)
        stats.set_value("state.heads_per_tile", cache.state_heads_per_tile)
        stats.set_value("kv.pages_spanned",
                        cache.num_slots * cache.pages_per_slot)
        stats.set_value("kv.pools", cache.pools)
        stats.set_value("kv.pools_lane_dense", cache.pools_lane_dense)
        stats.set_value("kv.latent_pools", cache.latent_pools)
        stats.set_value("kv.latent_row_bytes", cache.latent_row_bytes)
        stats.set_value("kv.layers_without_store",
                        cache.layers_without_store)
        # a stand-in model of the tests may be no ``Layer``
        layers = self.model.sublayers() \
            if hasattr(self.model, "sublayers") else ()
        scorings = [m.scoring for m in layers
                    if isinstance(m, SparseExpertMLP)]
        for scoring in stats.SCORINGS:
            stats.set_value(f"moe.router_layers.{scoring}",
                            scorings.count(scoring))
        self.prefix_tree = PrefixTree(self._page_size) \
            if self.scfg.enable_prefix_cache else None
        # one compiled prefill program: every chunk is this wide
        self._chunk = min(self.scfg.prefill_chunk_tokens,
                          cache.capacity)
        # requests that share one chunk call, first come first served
        # (the rest wait a round): the call's token budget over a chunk
        self._prefill_rows = max(1, min(cache.num_slots,
                                        PREFILL_CALL_TOKENS // self._chunk))
        self._prefilling.clear()
        self._pages_peak = 0
        if self._spec:
            dcfg = self.scfg.draft_model.config
            # full preallocation for the small draft model: prefix
            # pages are never shared into the draft cache (the
            # draft prefills the whole prompt itself), so its pool
            # must never be the admission bottleneck
            self.draft_cache = PagedKVCache(
                dcfg.num_layers, self.scfg.num_slots,
                self.max_len + self._spec_k,
                getattr(dcfg, "num_kv_heads", dcfg.num_heads),
                dcfg.head_dim, page_size=self._page_size,
                num_pages=None, dtype=self.scfg.cache_dtype)
        return cache

    def _make_tick(self):
        """A fresh compiled-tick driver for a (re)started loop, or None
        with `FLAGS_compiled_tick` off — the flag-off scheduler is
        byte-identical to the pre-tick engine (no tick object, no state
        mirrors, no extra dispatches)."""
        from ..utils.flags import flag as _flag
        if not _flag("FLAGS_compiled_tick", True):
            return None
        from .compiled_tick import CompiledServingTick
        return CompiledServingTick(self)

    def shutdown(self, wait_s=30.0):
        """Stop the scheduler.  In-flight and queued futures resolve
        with `EngineShutdownError`; the scheduler thread is joined."""
        with self._work:
            self._running = False
            self._work.notify_all()
        self._monitor_stop.set()
        t = self._thread
        if t is not None:
            t.join(wait_s)
            if t.is_alive():            # pragma: no cover
                # fail the futures BEFORE raising: a scheduler wedged in
                # a compiled step must not strand every client blocked
                # on result() just because the join timed out
                self._fail_all(EngineShutdownError(
                    "engine shut down (scheduler thread wedged)"))
                raise RuntimeError(
                    "serving scheduler thread failed to stop within "
                    f"{wait_s}s")
        self._thread = None
        m = self._monitor
        if m is not None:
            m.join(wait_s)
            self._monitor = None
        # the loop's finally already failed everything; this covers a
        # shutdown() racing a never-started or crashed loop
        self._fail_all(EngineShutdownError("engine shut down"))
        if tracing.enabled():
            tracing.spool_now()     # crash-robust handoff to the collector

    def drain(self, deadline_s=None, migrate=False):
        """Graceful shutdown (the preemption/SIGTERM path): stop
        admissions immediately, fail every still-queued request with
        `EngineShutdownError`, let the slots already decoding run to
        completion within `deadline_s` (default
        `ServingConfig.drain_grace_s`), then shut the engine down —
        whatever is still unfinished at the deadline fails like a normal
        shutdown.  Idempotent; safe from any thread.

        ``migrate=True`` (needs an installed `migrator`): instead of
        decoding the in-flight slots out locally, their KV pages —
        prompt AND tokens emitted so far — stream to a surviving
        replica and each request resumes there with its cache intact
        (docs/SERVING.md "Prefill/decode disaggregation").  A failed
        transfer falls back to finishing locally, so migrate-on-drain
        can only ever speed a drain up."""
        deadline_s = self.scfg.drain_grace_s if deadline_s is None \
            else float(deadline_s)
        with self._work:
            if not self._running:
                return
            already = self._draining
            self._drain_migrate = bool(migrate) and \
                self.migrator is not None
            self._draining = True
            queued = list(self._queue)
            self._queue.clear()
            stats.set_value("queue_depth", 0)
            self._work.notify_all()
        if already:
            return
        from ..observability import flight_recorder as _fr
        _fr.record("serving", "drain_begin", queued=len(queued),
                   active=len(self._active),
                   deadline_s=round(deadline_s, 3))
        for req in queued:
            self._fail(req, EngineShutdownError(
                f"engine draining: request {req.id} was still queued"))
            stats.incr("requests_cancelled_drain")
        deadline = time.monotonic() + deadline_s
        while (self._active or self._prefilling
               or self._migrating_out) and \
                time.monotonic() < deadline:
            time.sleep(0.01)
        _fr.record("serving", "drain_end",
                   unfinished=len(self._active))
        self.shutdown()

    def install_preemption_drain(self, handler=None, deadline_s=None):
        """Wire `drain()` to the preemption notice: when SIGTERM (the
        TPU-pod eviction warning) arrives, the engine stops admitting,
        finishes in-flight requests within `deadline_s`, and fails the
        queue — instead of dying mid-token.  Installs a fresh
        `PreemptionHandler` when none is passed; returns the handler so
        training/serving co-located code can share it."""
        from ..distributed.fleet.elastic import PreemptionHandler
        if handler is None:
            handler = PreemptionHandler().install()
        handler.add_callback(lambda: self.drain(deadline_s))
        self._preemption_handler = handler
        return handler

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.shutdown()

    # ---------------- client API ----------------
    def submit(self, prompt_ids, max_new_tokens=None, sampling=None,
               eos_token_id=None, deadline_s=None, handoff=None,
               adapter_id=None):
        """Enqueue one request; returns a `Future[RequestOutput]`.
        Raises `QueueFullError` when the bounded queue is at capacity
        and `ValueError` for prompts the slot cache cannot hold.

        ``handoff`` (disaggregation): a migration target descriptor the
        hosting replica's `migrator` understands.  When set on an
        engine with a migrator installed, the request's KV pages are
        streamed to that replica once its prompt is hot and decoding
        resumes there; on any migration failure the request falls back
        to decoding locally — handoff can slow a request, never lose
        it.

        ``adapter_id``: decode under this registered LoRA adapter
        (multi-tenant serving, ``max_adapters > 0``).  An id absent
        from the registry fails THIS request's returned future with
        ``UnknownAdapterError`` — the scheduler never sees it."""
        prompt = np.asarray(
            prompt_ids._data_ if hasattr(prompt_ids, "_data_")
            else prompt_ids).astype(np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if prompt.size >= self.max_len:
            raise ValueError(
                f"prompt of {prompt.size} tokens leaves no room to "
                f"decode in a {self.max_len}-token slot")
        sampling = (sampling or SamplingParams()).validate()
        max_new = int(self.scfg.default_max_new_tokens
                      if max_new_tokens is None else max_new_tokens)
        if max_new < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{max_new}")
        # infeasible requests are rejected up front: admission
        # backpressure only helps when the pool could EVER fit it
        psz = self._page_size
        pool = self.scfg.kv_pool_pages or \
            self.scfg.num_slots * \
            (-(-(self.max_len + self._spec_k) // psz))
        need = -(-(min(prompt.size + max_new, self.max_len)
                   + self._spec_k) // psz)
        if need > pool:
            raise ValueError(
                f"request needs {need} KV pages (prompt "
                f"{prompt.size} + max_new {max_new}) but the pool "
                f"holds {pool}; raise ServingConfig.kv_pool_pages")
        if adapter_id is not None:
            known = self.adapter_pool.known_ids() \
                if self.adapter_pool is not None else []
            if str(adapter_id) not in known:
                from .api import UnknownAdapterError
                msg = (f"adapter_id {adapter_id!r} is not in this "
                       f"engine's registry (registered: {known})")
                if self.adapter_pool is None:
                    msg += ("; the engine has no adapter pool — set "
                            "ServingConfig.max_adapters > 0")
                fut = Future()
                fut.set_exception(UnknownAdapterError(msg))
                return fut
        deadline = (time.monotonic() + deadline_s) \
            if deadline_s is not None else None
        req = _Request(next(self._ids), prompt, max_new, sampling,
                       eos_token_id, deadline)
        if adapter_id is not None:
            req.adapter_id = str(adapter_id)
        if handoff is not None:
            req.handoff = handoff
        if tracing.enabled():
            # a routed request arrives on an rpc handler thread with the
            # router's attempt span bound (distributed/rpc bind_wire) —
            # the engine span is then a CHILD and the router keeps the
            # sampling decision; with no upstream context (local
            # clients) the engine mints the root and owns the decision
            parent = tracing.current()
            root = tracing.start_span(
                "engine.request", parent=parent, rid=req.id,
                prompt_tokens=int(prompt.size))
            req.trace = _ReqTrace(root, owns_root=parent is None)
            req.trace.queue = tracing.start_span(
                "engine.queue", parent=root)
        with self._work:
            if not self._running:
                raise EngineShutdownError(
                    "engine is not running (call start())")
            if self._draining:
                raise EngineShutdownError(
                    "engine is draining (preemption notice); not "
                    "accepting new requests")
            if len(self._queue) >= self.scfg.max_queue:
                stats.incr("requests_rejected_queue_full")
                raise QueueFullError(
                    f"request queue is full ({self.scfg.max_queue} "
                    "waiting); retry later or raise "
                    "ServingConfig.max_queue")
            self._queue.append(req)
            self._pending[req.id] = req
            stats.incr("requests_submitted")
            stats.set_value("queue_depth", len(self._queue))
            self._work.notify()
        req.future.request_id = req.id       # cancel()'s handle
        return req.future

    def generate(self, prompt_ids, max_new_tokens=None, sampling=None,
                 eos_token_id=None, deadline_s=None, timeout=None,
                 adapter_id=None):
        """Sync client: submit + wait.  Returns a `RequestOutput`."""
        fut = self.submit(prompt_ids, max_new_tokens=max_new_tokens,
                          sampling=sampling, eos_token_id=eos_token_id,
                          deadline_s=deadline_s, adapter_id=adapter_id)
        return fut.result(timeout or self.scfg.request_timeout_s)

    def submit_resume(self, prompt_ids, prior_tokens, pages,
                      max_new_tokens=None, sampling=None,
                      eos_token_id=None, deadline_s=None, ttft_ms=None):
        """Resume a migrated request from its transferred KV pages: the
        receive side of prefill/decode disaggregation (and of drained-
        replica recovery).  `pages` is `migration.unpack`'s dict —
        layer-pooled K/V page arrays (+ per-page scales), offset — and
        `prior_tokens` the tokens the sender already emitted (>= 1: the
        prefill replica samples the first token before handing off).
        The request enters the admission queue like any other; once the
        pool adopts its pages it decodes from where the sender stopped,
        bit-equal to never having moved, with the prompt never
        recomputed.  Raises `PageMigrationError` for payloads this
        engine's pool can never hold."""
        from .api import PageMigrationError
        if self._layer_states is not None:
            raise RecurrentStateError(
                "submit_resume: migrated pages carry keys and values "
                "only, and the model's layers keep a recurrent state")
        prompt = np.asarray(
            prompt_ids._data_ if hasattr(prompt_ids, "_data_")
            else prompt_ids).astype(np.int32).reshape(-1)
        prior = [int(t) for t in np.asarray(prior_tokens).reshape(-1)]
        if prompt.size == 0 or not prior:
            raise ValueError("resume needs a prompt and >= 1 prior token")
        sampling = (sampling or SamplingParams()).validate()
        max_new = int(self.scfg.default_max_new_tokens
                      if max_new_tokens is None else max_new_tokens)
        if len(prior) >= max_new:
            raise ValueError(
                f"{len(prior)} prior tokens already exhaust the "
                f"max_new_tokens={max_new} budget — nothing to resume")
        if prompt.size + len(prior) >= self.max_len:
            raise ValueError(
                f"prompt {prompt.size} + {len(prior)} prior tokens "
                f"leave no room to decode in a {self.max_len}-token slot")
        if int(pages["offset"]) != prompt.size + len(prior) - 1:
            raise PageMigrationError(
                f"offset {pages['offset']} inconsistent with prompt "
                f"{prompt.size} + {len(prior)} prior tokens (expected "
                f"{prompt.size + len(prior) - 1} cached positions)")
        psz = self._page_size
        pool = self.scfg.kv_pool_pages or \
            self.scfg.num_slots * \
            (-(-(self.max_len + self._spec_k) // psz))
        need = -(-(min(prompt.size + max_new, self.max_len)
                   + self._spec_k) // psz)
        if need > pool:
            raise PageMigrationError(
                f"resumed request needs {need} KV pages but the pool "
                f"holds {pool}")
        deadline = (time.monotonic() + deadline_s) \
            if deadline_s is not None else None
        req = _Request(next(self._ids), prompt, max_new, sampling,
                       eos_token_id, deadline)
        req.resume = dict(pages)
        req.tokens = prior
        req.last_token = prior[-1]
        req.ttft_ms = ttft_ms
        if tracing.enabled():
            # the adopting side of a migration: handle_resume_begin
            # binds the SENDER's transfer-span context before calling
            # here, so the resumed decode parents the transfer span and
            # the whole hop chain stays one trace
            parent = tracing.current()
            root = tracing.start_span(
                "engine.request", parent=parent, rid=req.id,
                resumed=True, prior_tokens=len(prior),
                prompt_tokens=int(prompt.size))
            req.trace = _ReqTrace(root, owns_root=parent is None)
            req.trace.queue = tracing.start_span(
                "engine.queue", parent=root)
        with self._work:
            if not self._running:
                raise EngineShutdownError(
                    "engine is not running (call start())")
            if self._draining:
                raise EngineShutdownError(
                    "engine is draining; not adopting migrated requests")
            if len(self._queue) >= self.scfg.max_queue:
                stats.incr("requests_rejected_queue_full")
                raise QueueFullError(
                    f"request queue is full ({self.scfg.max_queue} "
                    "waiting); the sender should fall back or retry")
            self._queue.append(req)
            self._pending[req.id] = req
            stats.incr("requests_submitted")
            stats.set_value("queue_depth", len(self._queue))
            self._work.notify()
        req.future.request_id = req.id       # cancel()'s handle
        return req.future

    def cancel(self, request_id):
        """Best-effort cancel of one pending request (the hedged-
        dispatch loser path; ``request_id`` is the engine id stamped on
        the submitted future as ``future.request_id``).  A queued
        request is failed with `RequestCancelledError` right here; a
        slot-resident one (prefilling/decoding) is handed to the
        scheduler, which unwinds it inside its next iteration —
        releasing its slot, KV pages, prefix-tree refs and adapter rows
        through the same exactly-once `_release` path every completion
        takes.  Returns True when the request was pending and the
        cancellation was applied or scheduled; False when it is unknown,
        already resolved, or mid-migration (its pages are in flight to
        another replica — it will resolve through the migration
        protocol, and first-answer-wins delivery makes a late result
        harmless)."""
        with self._work:
            req = self._pending.get(request_id)
            if req is None or req.future.done():
                return False
            if req.id in self._migrating_out:
                return False
            try:
                self._queue.remove(req)
            except ValueError:
                # slot-resident or mid-admission: the scheduler owns
                # slot state — let it apply the cancellation
                self._cancels.add(req.id)
                self._work.notify()
                return True
            self._fail(req, RequestCancelledError(
                f"request {req.id} cancelled while queued"))
            stats.incr("requests_cancelled")
            stats.set_value("queue_depth", len(self._queue))
            return True

    def _process_cancels_locked(self):
        if not self._cancels:
            return
        cancels, self._cancels = self._cancels, set()
        for cid in cancels:
            req = self._pending.get(cid)
            if req is None or req.id in self._migrating_out:
                continue
            try:
                self._prefilling.remove(req)
            except ValueError:
                pass
            try:
                self._queue.remove(req)
            except ValueError:
                pass
            self._fail(req, RequestCancelledError(
                f"request {req.id} cancelled"))
            stats.incr("requests_cancelled")
            self._release(req)
        stats.set_value("queue_depth", len(self._queue))
        stats.set_value("active_slots", len(self._active))

    def stats(self):
        return stats.serving_stats()

    # ---------------- multi-tenant LoRA ----------------
    def register_adapter(self, adapter_id, source):
        """Validate + register an adapter on a live engine (the
        ``ServingConfig.adapters`` registry path, but hot).  ``source``
        is a ``save_adapter`` artifact dir or an ``adapter_spec`` dict.
        Raises ``AdapterConfigError`` for infeasible configs."""
        if self.adapter_pool is None:
            from .api import AdapterConfigError
            raise AdapterConfigError(
                "engine has no adapter pool — construct it with "
                "ServingConfig(max_adapters=...) > 0")
        with self._lock:
            return self.adapter_pool.register(adapter_id, source)

    def loaded_adapters(self):
        """Adapter ids currently hot in pool slots — the set gossip
        advertises for router affinity."""
        if self.adapter_pool is None:
            return []
        with self._lock:
            return self.adapter_pool.loaded_ids()

    def _lora_ctx(self, idx=None):
        """Activation scope for TARGET-model calls: patched projections
        apply the gathered low-rank update.  A no-op context when the
        engine has no adapter pool."""
        if self.adapter_pool is None:
            return contextlib.nullcontext()
        return self.adapter_pool.activate(idx)

    # ---------------- scheduler ----------------
    def _loop(self):
        """Restart wrapper: a crashed or stalled iteration fails every
        outstanding future (clients always see the real error, never a
        silent hang) and the loop restarts with a fresh slot cache, up
        to `max_scheduler_restarts` times."""
        self._sched_tid = threading.get_ident()
        try:
            while True:
                try:
                    self._loop_once()
                    return                       # clean shutdown
                except BaseException as exc:
                    with self._work:
                        running = self._running
                    if not running:
                        return                   # shutdown racing a crash
                    # never die silently: fail the futures so clients
                    # see the real error.  EXCEPT when the stall monitor
                    # already swept — a request submitted between that
                    # sweep and this unwind is healthy work for the
                    # restarted loop, not part of the stalled batch.
                    swept, self._stall_swept = self._stall_swept, False
                    if not (swept and
                            isinstance(exc, SchedulerStallError)):
                        self._fail_all(exc)
                    stats.incr("scheduler_restarts")
                    from ..observability import flight_recorder as _fr
                    _fr.record("serving", "scheduler_restart",
                               error=type(exc).__name__,
                               restarts=self._restarts + 1)
                    if self._restarts >= self.scfg.max_scheduler_restarts:
                        with self._work:
                            self._running = False
                        raise
                    self._restarts += 1
                    # the crash may have left slots/pages torn
                    # mid-write (or donated through a failed tick
                    # program): rebuild rather than trust them
                    self.cache = self._new_cache()
                    self._tick = self._make_tick()
        finally:
            self._fail_all(EngineShutdownError("engine shut down"))
            stats.set_value("active_slots", 0)
            stats.set_value("queue_depth", 0)
            if self.cache is not None:
                self._publish_pool_stats(force=True)

    def _loop_once(self):
        from ..core.state import no_grad
        budget = self.scfg.step_timeout_s
        with no_grad():
            while True:
                with self._work:
                    if not self._running:
                        break
                    # no span for an empty queue: idle passes land here
                    with span("serving.admit") if self._queue \
                            else contextlib.nullcontext():
                        admits = self._admit_locked()
                    if not admits and not self._active \
                            and not self._prefilling:
                        self._iter_deadline = None
                        self._work.wait(self.scfg.idle_wait_s)
                        continue
                if budget > 0:
                    self._iter_deadline = time.monotonic() + budget
                # one iteration with work to do: ``serving.admit`` lies
                # before it, as the admission block always lay before
                # ``tick_ms``'s clock, so an idle wait is in neither
                with span("serving.iteration", hist="serving.tick_ms"):
                    self._iterate(admits)
                self._iter_deadline = None
            if self._tick is not None:
                # a clean stop delivers what the tick in flight finished
                self._tick.drain()

    def _admit_locked(self):
        """The locked block of one iteration: cancels, expiry, admission
        of queued requests into free slots; returns [(request, slot)].
        ``serving.queue.request_ms`` integrates the depth of the queue
        each iteration left behind over the time to the next one, idle
        waits included."""
        self._process_migration_results_locked()
        self._process_cancels_locked()
        self._expire_queued_locked()
        admits = []
        while self._queue and self.cache.free_slots:
            slot = self._try_admit(self._queue[0])
            if slot is None:
                break       # page backpressure: FIFO
            admits.append((self._queue.popleft(), slot))
        now = time.monotonic()
        for req, _ in admits:
            stats.observe("queue_wait_ms", (now - req.submit_t) * 1e3)
        left, since = self._queue_mark
        if left:
            stats.incr("queue.request_ms", left * (now - since) * 1e3)
        self._queue_mark = (len(self._queue), now)
        stats.set_value("queue_depth", len(self._queue))
        return admits

    def _iterate(self, admits):
        if _fi.active("engine_slow") is not None:
            # gray-failure drill: a per-iteration stall on this
            # replica — heartbeats stay healthy, every request
            # hashed here just gets slower (docs/RESILIENCE.md)
            _fi.check_rpc("engine_slow", self.fault_name or "")
        if self._draining and \
                self._drain_migrate and self.migrator is not None:
            # preemption recovery: stream the still-decoding
            # slots' pages to survivors instead of racing the
            # drain deadline token by token
            self._migrate_out_active()
        for req, slot in admits:
            if req.resume is not None:
                self._activate_resumed(req, slot)
            else:
                self._start_prefill(req, slot)
        # ONE batched chunk call covers every prefilling request, then
        # the decode step runs: long prompts advance without ever
        # blocking in-flight streams for more than a chunk.  A compiled
        # tick is launched BEHIND the chunk call, which is then read
        # (first tokens, routing counts, activations) while that tick
        # runs; with no compiled tick to follow, it is read first, as the
        # decode step that follows takes the rows it activates
        if self._prefilling:
            with span("serving.prefill_round"):
                self._prefill_round()
        tick = self._tick if self._active and not self._can_speculate() \
            else None
        ticked = False
        try:
            ticked = tick is not None and tick.step()
        finally:
            # on every way out of the iteration, a stop or a crash
            # included: a launched chunk call is read exactly once
            self._finish_prefill(ticked and tick.in_flight)
        if self._active and not ticked:
            if self._can_speculate():
                self._spec_step()
            elif tick is None and self._tick is not None \
                    and self._tick.step():
                pass        # ONE compiled program runs the tick
            elif self._active:  # (the tick's drain may have ended them all)
                self._decode_step()
        with span("serving.publish"):
            self._publish_pool_stats()

    def _stall_monitor(self):
        """Scheduler-iteration watchdog (armed by step_timeout_s > 0):
        when one iteration blows its budget, fail every outstanding
        future RIGHT NOW (clients unblock even if the scheduler is
        wedged inside a compiled step) and async-raise into the
        scheduler thread so the restart wrapper rebuilds the loop."""
        budget = self.scfg.step_timeout_s
        poll = max(min(budget / 4.0, 0.25), 0.005)
        while not self._monitor_stop.wait(poll):
            deadline = self._iter_deadline
            if deadline is None or time.monotonic() < deadline:
                continue
            self._iter_deadline = None
            exc = SchedulerStallError(
                f"scheduler iteration exceeded its "
                f"step_timeout_s={budget:g}s budget; failing all "
                "outstanding requests and restarting the decode loop")
            stats.incr("scheduler_stalls")
            from ..distributed.watchdog import (all_thread_stacks,
                                                async_raise)
            from ..observability import flight_recorder as _fr
            _fr.record("serving", "scheduler_stall", budget_s=budget)
            _fr.dump(reason="serving-stall", error=exc, once=True,
                     extra={"stall": {
                         "op": "serving::step", "seq": None,
                         "budget_s": budget,
                         "threads": all_thread_stacks()}})
            self._stall_swept = True
            self._fail_all(exc)
            if self._sched_tid is not None:
                async_raise(self._sched_tid, SchedulerStallError)

    def _expire_queued_locked(self):
        if self.scfg.deadline_policy != "evict":
            return
        now = time.monotonic()
        keep = deque()
        for req in self._queue:
            if req.deadline is not None and now > req.deadline:
                self._fail(req, DeadlineExceededError(
                    f"request {req.id} expired after "
                    f"{now - req.submit_t:.3f}s in queue"))
                stats.incr("requests_evicted_deadline")
            else:
                keep.append(req)
        self._queue = keep

    def _try_admit(self, req):
        """Reserve a slot + worst-case page budget for `req` (called
        under the lock).  Matches the prompt against the prefix tree
        first — shared pages shrink the reservation — and evicts LRU
        zero-ref tree pages under pool pressure.  Returns the slot, or
        None when the pool cannot promise the pages yet (the request
        stays queued: backpressure, never a crash)."""
        psz = self._page_size
        # +speculation_k: the verify window may write past the last
        # real token before rollback, so the reservation covers it
        total = min(req.prompt.size + req.max_new_tokens, self.max_len) \
            + self._spec_k
        if req.adapter_id is not None:
            # pin (hot-loading first if cold) the adapter's pool slot
            # for this request's lifetime.  None = every slot is pinned
            # by in-flight requests: the request stays queued — LRU
            # eviction never touches a slot with live traffic.
            pool_slot = self.adapter_pool.acquire(req.adapter_id)
            if pool_slot is None:
                return None
            req.adapter_slot = pool_slot
        if req.resume is not None:
            # migrated request: adopt its transferred pages instead of
            # reserving for a prefill it will never run.  Adopted pages
            # are slot-private; the reservation covers only the growth
            # still ahead of the offset.
            pay = req.resume
            n = int(pay["k_pages"].shape[1])
            reserve = max(0, -(-total // psz) - n)
            slot = self.cache.adopt_pages(
                reserve, pay["offset"], pay["k_pages"], pay["v_pages"],
                pay["k_scales"], pay["v_scales"])
            if slot is None:
                return None         # pool backpressure: stays queued
            if self._spec:
                dslot = self.draft_cache.allocate(
                    self.draft_cache.pages_per_slot)
                if dslot != slot:   # pragma: no cover - invariant
                    raise RuntimeError(
                        f"draft cache slot {dslot} diverged from "
                        f"target slot {slot}")
                # the draft never saw this prompt; teacher-forced
                # catch-up re-converges it from position 0
                self.draft_cache.set_offset(slot, 0)
            stats.incr("migration.pages_received", n)
            return slot
        nodes, pages = [], []
        if self.prefix_tree is not None:
            # tree entries are scoped by adapter id: a prompt prefilled
            # under one adapter produces DIFFERENT K/V than under
            # another (or under the base), so adapters never share
            # cached prompt pages
            nodes, pages = self.prefix_tree.match(req.prompt,
                                                  scope=req.adapter_id)
        need = -(-total // psz) - len(pages)
        short = need - self.cache.available_pages
        if short > 0 and self.prefix_tree is not None:
            freed = self.prefix_tree.evict(short, self.cache.reclaim)
            if freed:
                stats.incr("prefix_cache_evictions", freed)
        slot = self.cache.allocate(need, pages)
        if slot is None:
            if nodes:
                self.prefix_tree.release(nodes)
            if req.adapter_id is not None:
                self.adapter_pool.release(req.adapter_id)
            return None
        if self._spec:
            # mirror the slot in the draft cache: same free-slot stack
            # discipline on both sides keeps the indices identical, and
            # the draft pool is fully preallocated so this cannot fail
            dslot = self.draft_cache.allocate(
                self.draft_cache.pages_per_slot)
            if dslot != slot:       # pragma: no cover - invariant
                raise RuntimeError(
                    f"draft cache slot {dslot} diverged from target "
                    f"slot {slot}")
        if self.prefix_tree is not None:
            stats.incr("prefix_cache_hits" if pages
                       else "prefix_cache_misses")
            if pages:
                stats.incr("prefix_cache_hit_tokens", len(pages) * psz)
        req.prefix_nodes = nodes
        req.shared_len = len(pages) * psz
        return slot

    def _start_prefill(self, req, slot):
        """Arm chunked prefill: the slot's clock starts at the shared
        prefix length — those tokens' KV pages came from the tree and
        are never recomputed.  The draft model (speculation) always
        prefills from 0: shared pages belong to the TARGET cache."""
        req.slot = slot
        req.prefill_pos = req.shared_len
        req.first_tok = None
        tr = req.trace
        if tr is not None:
            if tr.queue is not None:
                tr.queue.end(slot=slot)
            tr.prefill = tracing.start_span(
                "engine.prefill", parent=tr.root, slot=slot,
                prompt_tokens=int(req.prompt.size),
                shared_len=req.shared_len)
            if req.adapter_id is not None:
                # the pool slot was pinned during admission (a cold
                # adapter paid its hot-load there)
                tr.prefill.event("adapter_acquire",
                                 adapter_id=req.adapter_id,
                                 pool_slot=req.adapter_slot)
        if self.adapter_pool is not None:
            # the slot's row of the persistent adapter-index vector now
            # points at this request's pool slot (0 for base requests);
            # the compiled tick re-gathers the vector every iteration,
            # so the update flows into the SAME compiled program
            self.adapter_pool.set_row(slot, req.adapter_slot)
            if req.adapter_id is not None:
                stats.adapter_observe(req.adapter_id)
        self.cache.set_offset(slot, req.shared_len)
        # the slot's previous tenant left its recurrent state behind
        self.cache.reset_state(slot)
        if self._spec:
            req.draft_prefill_pos = 0
            self.draft_cache.set_offset(slot, 0)
        self._prefilling.append(req)

    def _prefill_round(self):
        """Launch one `prefill_chunk_tokens`-wide chunk for EVERY
        prefilling request, batched into a single model call, THEN the
        decode step runs — long prompts no longer starve in-flight
        streams, and a burst of admissions costs one call, not one per
        request.  Only the host's mirrors move here (offsets, page
        growth); each prompt the chunk completes has its first token
        drawn behind the call on the device, and `_finish_prefill` reads
        them.

        Static shapes: a round's model call is one of a few [rows, C]
        programs, rows the smallest power-of-two bucket (capped at
        num_slots) that holds the prefilling requests, all compiled
        before the first call returns; surplus rows of a bucket ride the
        scratch page like free decode slots.  (The eager lane computes
        all num_slots rows op by op.)  A final short chunk is
        left-shifted to start at ``min(offset, capacity - C)`` — re-fed
        positions recompute bitwise-identical K/V (same tokens, same
        cache contents), and pad positions past the prompt scatter into
        unassigned table entries, i.e. the scratch page, which no causal
        mask ever exposes."""
        now = time.monotonic()
        if self.scfg.deadline_policy == "evict":
            for req in list(self._prefilling):
                if req.deadline is not None and now > req.deadline:
                    self._prefilling.remove(req)
                    self._fail(req, DeadlineExceededError(
                        f"request {req.id} exceeded its deadline "
                        f"mid-prefill at {req.prefill_pos}/"
                        f"{req.prompt.size} tokens"))
                    stats.incr("requests_evicted_deadline")
                    self._release(req)
        if not self._prefilling:
            return
        self._prefill_ended = ended = []
        reqs = list(self._prefilling)       # each holds a slot: <= B
        chunk = self._chunk
        tgt = [r for r in reqs if r.prefill_pos < r.prompt.size]
        tgt = tgt[:self._prefill_rows]
        if tgt:
            logits, starts = self._prefill_chunk_call(
                self.model, self.cache, tgt,
                [r.prefill_pos for r in tgt])
            for row, req in enumerate(tgt):
                plen = req.prompt.size
                start = starts[row]
                req.prefill_pos = min(start + chunk, plen)
                self.cache.set_offset(req.slot, req.prefill_pos)
                if req.trace is not None and \
                        req.trace.prefill is not None:
                    req.trace.prefill.event(
                        "chunk", start=int(start),
                        pos=int(req.prefill_pos))
                if req.prefill_pos < plen:
                    continue
                # prompt fully cached: the first token is drawn from the
                # last REAL position of this row's chunk, now — behind
                # the call and ahead of the tick launched after it, for
                # an op dispatched after that tick would run after it
                if req.sampling.uses_penalty:
                    seen = np.zeros(self.cfg.vocab_size, bool)
                    seen[req.prompt] = True
                    req.seen = seen
                ended.append(
                    (req, self._draw_row(logits[row:row + 1, :], req)))
        if self._spec:
            # the draft model's own chunked prefill, same cadence: its
            # cache must hold the whole prompt before the request can
            # decode speculatively (no shared pages on the draft side)
            dr = [r for r in reqs if r.draft_prefill_pos
                  < r.prompt.size][:self._prefill_rows]
            if dr:
                _, dstarts = self._prefill_chunk_call(
                    self.scfg.draft_model, self.draft_cache, dr,
                    [r.draft_prefill_pos for r in dr])
                for row, req in enumerate(dr):
                    req.draft_prefill_pos = min(
                        dstarts[row] + chunk, req.prompt.size)
                    self.draft_cache.set_offset(req.slot,
                                                req.draft_prefill_pos)

    def _finish_prefill(self, overlapped):
        """The read of the round `_prefill_round` launched, if one is
        open: the wait for its chunk call (``serving.prefill.collect``:
        the member's routing counts and the first tokens drawn behind
        it), each completed prompt's first token, ``ttft_ms`` and prefix
        tree entry, then the activation of every prefilling request whose
        caches are all ready.  ``overlapped``: a compiled tick was
        launched behind the call before this read."""
        ended, self._prefill_ended = self._prefill_ended, None
        if ended is None:
            return
        with span("serving.prefill.collect"):
            if self._tick is not None:
                self._tick.collect_prefill(overlapped)
            firsts = [int(np.asarray(tok).reshape(-1)[0])
                      for _, tok in ended]
        for (req, _), tok in zip(ended, firsts):
            req.first_tok = tok
            req.ttft_ms = (time.monotonic() - req.submit_t) * 1e3
            stats.observe("ttft_ms", req.ttft_ms)
            stats.incr("prefill_steps")
            if req.trace is not None and req.trace.prefill is not None:
                req.trace.prefill.event(
                    "first_token", ttft_ms=round(req.ttft_ms, 3))
            if self.prefix_tree is not None:
                self.prefix_tree.insert(req.prompt, self.cache, req.slot,
                                        req.prefix_nodes,
                                        scope=req.adapter_id)
        # activate when every cache the request decodes against is
        # ready (target always; draft too when speculating)
        for req in list(self._prefilling):
            if req.prefill_pos < req.prompt.size or req.first_tok is None:
                continue
            if self._spec and req.draft_prefill_pos < req.prompt.size:
                continue
            try:
                self._prefilling.remove(req)
            except ValueError:
                continue    # a concurrent stall sweep already swept it
            tok, req.first_tok = req.first_tok, None
            if self._migrate_ready(req, tok):
                # disaggregation handoff: the prompt's pages are hot —
                # stream them to the decode replica instead of joining
                # this replica's decode batch
                req.tokens = [tok]
                req.last_token = tok
                if req.seen is not None:
                    req.seen[tok] = True
                stats.incr("tokens_generated")
                self._begin_migration(req)
                continue
            self._active[req.slot] = req
            tr = req.trace
            if tr is not None:
                if tr.prefill is not None:
                    tr.prefill.end()
                tr.decode = tracing.start_span(
                    "engine.decode", parent=tr.root, slot=req.slot,
                    spec=self._spec)
            self._append_token(req, tok)
        stats.set_value("active_slots", len(self._active))

    def _prefill_chunk_call(self, model, cache, reqs, offs):
        """One batched prefill-chunk call of `model` against `cache` for
        `reqs` at per-request progress `offs`; returns (logits [rows, V]
        at each row's last real position of the chunk, starts).

        The target model's call is ONE donated program of the compiled
        tick's family, with as many rows as the smallest bucket that
        holds `reqs` (``CompiledServingTick.prefill_member``).  What one
        program cannot host — the tick's own blockers, and the draft
        model — runs the eager `[num_slots, chunk]` call op by op, the
        lane the compiled one is tested against."""
        chunk = self._chunk
        cap = cache.capacity
        member = None
        if model is self.model and self._tick is not None:
            member = self._tick.prefill_member(len(reqs))
        rows = member[0] if member is not None else cache.num_slots
        tokens = np.zeros((rows, chunk), np.int32)
        last = np.zeros(rows, np.int32)
        # chunked prefill batches by CALL ROW, not scheduler slot: the
        # adapter index for this call is row-ordered (scratch rows ride
        # the identity slot 0).  Draft-model calls are never adapted.
        lora_rows = np.zeros(rows, np.int32) \
            if self.adapter_pool is not None and model is self.model \
            else None
        starts = []
        useful = 0
        seen_full = seen_window = 0
        for row, (req, off) in enumerate(zip(reqs, offs)):
            start = min(off, cap - chunk)
            if start != off and cache.has_state:  # pragma: no cover
                raise RuntimeError(
                    f"prefill chunk at {off} shifted to {start}: tokens "
                    "would be fed to a recurrent state twice")
            end = min(start + chunk, req.prompt.size)
            tokens[row, :end - start] = req.prompt[start:end]
            last[row] = end - 1 - start
            useful += end - off
            if cache.ring_pages or cache.latent_pools:
                # positions the new tokens see in a full (or latent)
                # layer and in a window layer
                seen = np.arange(off, end, dtype=np.int64) + 1
                seen_full += int(seen.sum())
                if cache.ring_pages:
                    seen_window += int(
                        np.minimum(seen, cache.window).sum())
            cache.ensure_capacity(req.slot, end - 1)
            starts.append(start)
            if lora_rows is not None:
                lora_rows[row] = req.adapter_slot
        slots = [r.slot for r in reqs]
        attrs = {"request_ids": [r.id for r in reqs]} \
            if tracing.enabled() else {}
        with span("serving.prefill_chunk", **attrs) as sp:
            if member is not None:
                logits = self._tick.run_prefill(member, slots, starts,
                                                tokens, last, lora_rows)
                launches = 1
            else:
                logits, launches = self._prefill_chunk_eager(
                    model, cache, slots, starts, tokens, last, lora_rows)
        stats.observe("prefill_ms", sp.ms)
        stats.incr("prefill_chunks", len(reqs))
        # what the call computed against what it was for, and how many
        # programs it launched
        stats.incr("prefill.tokens_computed", rows * chunk)
        stats.incr("prefill.tokens_useful", useful)
        stats.incr("prefill.launches", launches)
        if cache.ring_pages or cache.latent_pools:
            stats.incr("prefill.context_tokens", seen_full)
        if cache.ring_pages:
            stats.incr("prefill.window_context_tokens", seen_window)
        return logits, starts

    def _prefill_chunk_eager(self, model, cache, slots, starts, tokens,
                             last, lora_rows):
        """The eager lane of a chunk call: `model` over all `num_slots`
        rows through the op funnel; returns ([num_slots, V] logits at
        each row's `last` position, the programs the funnel launched)."""
        import jax.numpy as jnp
        from ..core.tensor import Tensor
        from ..core.op_cache import dispatch_count
        from ..framework.capture import TRACE_LOCK
        lora = contextlib.nullcontext() if lora_rows is None else \
            self.adapter_pool.activate(
                self.adapter_pool.row_tensor(lora_rows))
        launches0 = dispatch_count()
        with span("serving.prefill.view"):
            views = cache.prefill_view(slots, starts, last + 1)
        with span("serving.prefill.model"), TRACE_LOCK, lora:
            # a shared model may be mid-capture
            logits = model(Tensor(tokens), caches=views)
        with span("serving.prefill.absorb"):
            cache.absorb_view(views)
        launches = dispatch_count() - launches0
        picked = jnp.take_along_axis(logits._data_, last[:, None, None],
                                     axis=1)[:, 0]
        return Tensor(picked), launches

    # ---------------- live KV-page migration (disaggregation) ----------------
    def _migrate_ready(self, req, tok):
        """Whether this just-prefilled request should hand off: a target
        was assigned, a migrator is installed, and the request will not
        finish on this very token (migrating a finished request is pure
        waste) nor has it already blown its deadline."""
        if req.handoff is None or self.migrator is None:
            return False
        if req.adapter_id is not None:
            # adapter requests decode where their adapter is pinned:
            # the resume path carries no adapter state, and the target
            # replica may not have the adapter hot — decode locally
            return False
        if req.max_new_tokens <= 1:
            return False
        if req.eos_token_id is not None and tok == req.eos_token_id:
            return False
        if req.prompt.size + 1 >= self.max_len:
            return False
        if self.scfg.deadline_policy == "evict" and \
                req.deadline is not None and \
                time.monotonic() > req.deadline:
            return False
        return True

    def _begin_migration(self, req):
        """Export the slot's pages (scheduler thread: the only cache
        writer) and ship them from a background thread — the transfer
        must not stall other slots' decode.  The slot and its pages
        stay held until the outcome lands: success releases them,
        failure re-activates the request locally with nothing lost."""
        from . import migration
        header, blobs = migration.export_slot(self.cache, req.slot)
        self._migrating_out[req.id] = req
        self._mut += 1          # slot left the active set: tick rebuilds
        tr = req.trace
        if tr is not None:
            # close whatever phase the request was in (prefill handoff
            # or drain-time mid-decode) and open the transfer span
            # BEFORE the migrator runs: fleet._migration_meta ships
            # THIS span's context in the meta dict, so the remote
            # resumed decode parents the transfer hop
            if tr.prefill is not None:
                tr.prefill.end()
            if tr.decode is not None:
                tr.decode.end(status="migrated",
                              tokens=len(req.tokens))
                tr.decode = None
            tr.transfer = tracing.start_span(
                "engine.migrate", parent=tr.root,
                target=str((req.handoff or {}).get("name")),
                pages=int(header["num_pages"]),
                tokens=len(req.tokens))
        stats.incr("migration.pages_sent", header["num_pages"])
        threading.Thread(
            target=self._migrate_async,
            args=(req, header, blobs, req.handoff),
            name=f"migrate-{req.id}", daemon=True).start()

    def _migrate_async(self, req, header, blobs, target):
        """Background transfer thread.  Phase 1 (`migrator`): ship the
        frames + remote adopt — timed as ``migrate_ms``; a failure here
        is recoverable (the local slot still holds everything) and
        falls back.  Phase 2 (`migration_awaiter`): wait out the remote
        decode holding NOTHING locally; a failure here (target died
        mid-decode) fails the future with `EngineShutdownError`, which
        the router answers with an idempotent resubmission."""
        tr = req.trace
        t0 = time.monotonic()
        try:
            ack = self.migrator(req, header, blobs, target)
        except Exception as e:              # noqa: BLE001
            stats.observe("migration.migrate_ms",
                          (time.monotonic() - t0) * 1e3)
            if tr is not None and tr.transfer is not None:
                tr.transfer.end(status=type(e).__name__)
            self._post_migration(req, "fail", e)
            return
        stats.observe("migration.migrate_ms",
                      (time.monotonic() - t0) * 1e3)
        if tr is not None and tr.transfer is not None:
            tr.transfer.end()
        if self.migration_awaiter is None:
            # single-phase migrator (tests): phase 1 returned the result
            self._post_migration(req, "done", ack)
            return
        self._post_migration(req, "sent", None)
        if tr is not None:
            # phase 2 holds nothing locally — the span makes the remote
            # decode wait attributable in the critical path
            tr.remote = tracing.start_span(
                "engine.remote_wait", parent=tr.root)
        try:
            payload = self.migration_awaiter(req, ack)
        except Exception as e:              # noqa: BLE001
            if tr is not None and tr.remote is not None:
                tr.remote.end(status=type(e).__name__)
            self._post_migration(req, "lost", e)
            return
        if tr is not None and tr.remote is not None:
            tr.remote.end()
        self._post_migration(req, "done", payload)

    def _post_migration(self, req, kind, val):
        with self._work:
            self._migration_results.append((req, kind, val))
            self._work.notify()

    def _process_migration_results_locked(self):
        """Land transfer outcomes (scheduler thread, under the lock):

        ``sent``  remote adopted the pages — release the local slot;
                  the request keeps only a result relay in flight
        ``done``  remote stream arrived — complete the future (and free
                  the slot if no ``sent`` preceded: single-phase tests)
        ``fail``  phase-1 failure — re-activate locally, nothing lost
        ``lost``  target died AFTER adopting — local pages are gone, so
                  fail the future loudly; the router's idempotent
                  resubmission re-runs the request on a survivor
        """
        while self._migration_results:
            req, kind, val = self._migration_results.popleft()
            if req.id not in self._migrating_out:
                continue        # swept by _fail_all/shutdown already
            if kind == "sent":
                self._release(req)      # keeps riding _migrating_out
                continue
            del self._migrating_out[req.id]
            if kind == "fail":
                stats.incr("migration.fallbacks")
                from ..observability import flight_recorder as _fr
                _fr.record("serving", "migration_fallback",
                           request_id=req.id,
                           error=type(val).__name__)
                self._migrate_failed.add(req.id)
                self._active[req.slot] = req
                self._mut += 1
                tr = req.trace
                if tr is not None:
                    # mid-transfer fallback: the failed transfer span
                    # already closed with its error; the local decode
                    # resumes under the SAME trace, marked as such
                    tr.root.event("migration_fallback",
                                  error=type(val).__name__)
                    tr.decode = tracing.start_span(
                        "engine.decode", parent=tr.root,
                        slot=req.slot, fallback=True)
                continue
            if kind == "lost":
                stats.incr("migration.remote_failures")
                self._fail(req, EngineShutdownError(
                    f"request {req.id}: migration target died after "
                    f"adopting its pages ({type(val).__name__}: {val}); "
                    "resubmit"))
                continue
            self._complete_migrated(req, val)
            self._release(req)

    def _complete_migrated(self, req, payload):
        """Resolve a handed-off request's future with the stream the
        decode replica produced (prior tokens included — bit-equal to
        having decoded here)."""
        out = RequestOutput(
            request_id=req.id, prompt_ids=req.prompt,
            output_ids=np.asarray(payload["output_ids"], np.int32),
            finish_reason=payload["finish_reason"], ttft_ms=req.ttft_ms,
            latency_ms=(time.monotonic() - req.submit_t) * 1e3,
            decoded_by=payload.get("replica"))
        with self._lock:
            self._pending.pop(req.id, None)
        try:
            if not req.future.done():
                req.future.set_result(out)
        except Exception:       # lost the race to a concurrent _fail
            return
        stats.incr("requests_completed")
        stats.incr("migration.migrations")
        if req.trace is not None:
            req.trace.finish(
                "ok", out.latency_ms,
                finish_reason=payload["finish_reason"],
                migrated_to=payload.get("replica"))
        from ..observability import flight_recorder as _fr
        _fr.record("serving", "request_done", request_id=req.id,
                   reason=payload["finish_reason"],
                   tokens=int(np.asarray(payload["output_ids"]).size),
                   migrated_to=payload.get("replica"))

    def _activate_resumed(self, req, slot):
        """Receive side: the adopted request enters the decode batch
        exactly where the sender stopped — tokens, last token, penalty
        state and cache offset all continue, the prompt is never
        recomputed."""
        req.slot = slot
        if req.sampling.uses_penalty:
            seen = np.zeros(self.cfg.vocab_size, bool)
            seen[req.prompt] = True
            seen[np.asarray(req.tokens, np.int32)] = True
            req.seen = seen
        req.resume = None
        self._active[slot] = req
        self._mut += 1
        tr = req.trace
        if tr is not None:
            if tr.queue is not None:
                tr.queue.end(slot=slot)
            tr.decode = tracing.start_span(
                "engine.decode", parent=tr.root, slot=slot,
                resumed=True, prior_tokens=len(req.tokens))
        stats.incr("migration.resumed_requests")
        stats.set_value("active_slots", len(self._active))

    def _migrate_out_active(self):
        """Drain-time preemption recovery: every slot still decoding is
        exported and resumed on a survivor (mid-stream: its emitted
        tokens ride along), so a drain costs one page transfer instead
        of re-running the prompt elsewhere."""
        if self._tick is not None:
            # the compiled tick keeps token buffers device-resident and
            # one tick in flight; the export ships req.tokens, so collect
            # it and sync the host mirror first
            self._tick.flush_to_host()
        now = time.monotonic()
        for slot, req in list(self._active.items()):
            if req.id in self._migrate_failed:
                continue        # one failed transfer: decode it out here
            if self.scfg.deadline_policy == "evict" and \
                    req.deadline is not None and now > req.deadline:
                continue        # about to be evicted anyway
            if len(req.tokens) >= req.max_new_tokens:
                continue        # finishing this iteration regardless
            del self._active[slot]
            self._begin_migration(req)
        stats.set_value("active_slots", len(self._active))

    # forced gauge flush cadence: a steady-state decode stretch whose
    # page counts never move publishes at most once per this many
    # iterations instead of taking the metrics-registry lock every tick
    _POOL_PUBLISH_EVERY = 64

    def _publish_pool_stats(self, force=False):
        in_use = self.cache.pages_in_use
        self._pages_peak = max(self._pages_peak, in_use)
        snap = (in_use, self.cache.free_page_count, self._pages_peak)
        self._pool_iters += 1
        if not force and snap == self._pool_pub and \
                self._pool_iters % self._POOL_PUBLISH_EVERY:
            return
        self._pool_pub = snap
        stats.set_value("kv_pages_in_use", in_use)
        stats.set_value("kv_pages_free", self.cache.free_page_count)
        stats.set_value("kv_pages_peak", self._pages_peak)

    # ---------------- speculative decoding (speculation_k > 0) ----------------
    def _can_speculate(self):
        """Speculation engages when every active request samples greedily
        without repetition penalty (accept = exact argmax match) and the
        verify window's K+1 writes fit every slot's table; otherwise this
        iteration takes the plain decode step — the draft's catch-up
        machinery (`_known_token` teacher forcing) absorbs the lag."""
        if not self._spec:
            return False
        if self.adapter_pool is not None and any(
                r.adapter_id is not None for r in self._active.values()):
            # the draft model has no adapter pool: its proposals would
            # come from the BASE distribution while the target verifies
            # under the adapter — acceptance collapses.  Adapter
            # iterations take the plain (or compiled-tick) step.
            return False
        K = self._spec_k
        for req in self._active.values():
            sp = req.sampling
            if not sp.greedy or sp.uses_penalty:
                return False
            if int(self.cache.offsets[req.slot]) + K >= \
                    self.cache.capacity:
                return False
        return True

    @staticmethod
    def _known_token(req, pos):
        """The true token at `pos` of a request's sequence (prompt +
        emitted tokens) — teacher-forcing input for draft positions the
        engine has already committed."""
        if pos < req.prompt.size:
            return int(req.prompt[pos])
        return int(req.tokens[pos - req.prompt.size])

    def _spec_step(self):
        """One speculative window over the continuous batch:

        1. **draft** — K `[num_slots, 1]` steps of the draft model on
           its mirror cache propose K tokens per slot.  Positions the
           engine already knows (draft lagging after a bonus token or a
           plain-step fallback) are teacher-forced, so the draft
           re-converges instead of compounding stale guesses.
        2. **verify** — ONE `[num_slots, K+1]` target-model call scores
           `[last_token, d_1..d_K]`; its K+1 greedy argmaxes are the
           true next tokens at every window position.
        3. **accept + rollback** — per slot, the leading run of drafts
           matching the target is accepted plus the bonus token after
           it (a+1 tokens per window).  Offsets move to the accept
           boundary and `PagedKVCache.rollback` returns pages wholly
           past the new horizon — rejected K/V beyond it stays as
           scratch (causally masked, overwritten before exposure).

        Static shapes throughout: the draft step, the verify call, and
        the rollback (pointer/offset moves) never depend on how many
        tokens were accepted."""
        from ..core.tensor import Tensor
        from ..framework.capture import TRACE_LOCK
        from ..tensor_ops import search as S
        K = self._spec_k
        ns = self.cache.num_slots
        active = dict(self._active)
        n_active = len(active)
        self._max_active = max(self._max_active, n_active)
        stats.set_value("max_active_slots", self._max_active)
        attrs = {"request_ids": sorted(r.id for r in active.values())} \
            if tracing.enabled() else {}
        tgt_off = {s: int(self.cache.offsets[s]) for s in active}
        d_off0 = {s: int(self.draft_cache.offsets[s]) for s in active}

        # --- draft: K proposer steps on the mirror cache ---
        prev_out = {s: 0 for s in active}
        draft_out = {s: [] for s in active}
        with span("serving.spec_draft", **attrs):
            for j in range(K):
                tok_in = np.zeros((ns, 1), np.int32)
                for s, req in active.items():
                    p = d_off0[s] + j
                    tok_in[s, 0] = self._known_token(req, p) \
                        if p <= tgt_off[s] else prev_out[s]
                    self.draft_cache.ensure_capacity(s, p)
                with TRACE_LOCK:    # shared model may be mid-capture
                    logits = self.scfg.draft_model(
                        Tensor(tok_in),
                        caches=self.draft_cache.layer_caches())
                self.draft_cache.advance(active.keys())
                toks = np.asarray(
                    S.argmax(logits[:, -1, :], axis=-1)._data_)
                for s in active:
                    prev_out[s] = int(toks[s])
                    draft_out[s].append(int(toks[s]))

        # --- verify: one batched K+1 target call ---
        with span("serving.spec_verify", **attrs):
            tok_in = np.zeros((ns, K + 1), np.int32)
            caps = {}
            proposed = 0
            for s, req in active.items():
                # a lagging draft (bonus token / fallback steps) yields
                # fewer usable proposals this window; the tail positions
                # are padding that the accept cap below always rejects
                lag = tgt_off[s] - d_off0[s]
                cap = max(0, K - lag)
                caps[s] = cap
                tok_in[s, 0] = req.last_token
                for i in range(1, K + 1):
                    tok_in[s, i] = draft_out[s][lag + i - 1] \
                        if i <= cap else req.last_token
                proposed += cap
                self.cache.ensure_capacity(s, tgt_off[s] + K)
            with TRACE_LOCK:    # shared model may be mid-capture
                logits = self.model(Tensor(tok_in),
                                    caches=self.cache.layer_caches())
            t = np.asarray(S.argmax(logits, axis=-1)._data_)  # [ns, K+1]

        # --- accept mask + rollback ---
        with span("serving.spec_rollback"):
            accepted = 0
            for s, req in active.items():
                a = 0
                while a < caps[s] and tok_in[s, a + 1] == t[s, a]:
                    a += 1
                accepted += a
                for i in range(a + 1):
                    self._append_token(req, int(t[s, i]))
                    if req.slot is None:    # eos/length/deadline
                        break               # mid-window truncates the rest
                if req.slot is None:
                    continue                # _release returned the pages
                new_off = tgt_off[s] + a + 1
                self.cache.set_offset(s, new_off)
                self.cache.rollback(s, new_off)
                # the draft cache is valid through the accepted prefix
                # it wrote itself (never past what IT cached this window)
                d_new = min(d_off0[s] + K, new_off)
                self.draft_cache.set_offset(s, d_new)
                self.draft_cache.rollback(s, d_new)
        stats.incr("spec_windows")
        stats.incr("spec_proposed_tokens", proposed)
        stats.incr("spec_accepted_tokens", accepted)
        stats.incr("slot_steps", ns)
        stats.incr("slot_steps_active", n_active)
        stats.set_value("active_slots", len(self._active))

    def _decode_step(self):
        """One batched step over ALL slots: the continuous batch."""
        from ..core.tensor import Tensor
        from ..framework.capture import TRACE_LOCK
        from ..tensor_ops import search as S
        attrs = {"request_ids": sorted(r.id for r in
                                       self._active.values())} \
            if tracing.enabled() else {}
        with span("serving.decode", **attrs):
            n_active = len(self._active)
            self._max_active = max(self._max_active, n_active)
            stats.set_value("max_active_slots", self._max_active)
            # page-by-page growth: assign a fresh page only when a
            # row's write position crosses a page boundary (the
            # admission reservation guarantees the page exists)
            for slot in self._active:
                self.cache.ensure_capacity(
                    slot, int(self.cache.offsets[slot]))
            tok_in = np.zeros((self.cache.num_slots, 1), np.int32)
            for slot, req in self._active.items():
                tok_in[slot, 0] = req.last_token
            caches = self.cache.layer_caches(self._active)
            with TRACE_LOCK, self._lora_ctx():
                logits = self.model(Tensor(tok_in), caches=caches)
            self.cache.advance(self._active.keys())
            last = logits[:, -1, :]                  # [num_slots, V]
            all_greedy = all(
                r.sampling.greedy and not r.sampling.uses_penalty
                for r in self._active.values())
            toks = None
            if all_greedy:
                toks = np.asarray(
                    S.argmax(last, axis=-1)._data_)  # one batched argmax
            elif self._fused_sampling_ok():
                # ISSUE 13 satellite: one fused jitted sampling call
                # over every active slot instead of an np.asarray host
                # round-trip per non-greedy slot per iteration
                toks = self._fused_sample(last)
            for slot, req in list(self._active.items()):
                tok = int(toks[slot]) if toks is not None else \
                    self._sample_row(last[slot:slot + 1, :], req)
                self._append_token(req, tok)
        stats.incr("decode_steps")
        stats.incr("slot_steps", self.cache.num_slots)
        stats.incr("slot_steps_active", n_active)
        stats.set_value("active_slots", len(self._active))

    def _fused_sampling_ok(self):
        """Whether ONE fused jitted call can sample every active slot
        this iteration: the flag is on and each request is greedy or
        carries a per-request seed (the vectorized chain's streams are
        key-derived — unseeded sampling keeps the per-row host path)."""
        from ..utils.flags import flag as _flag
        if not _flag("FLAGS_serving_fused_sampling", True):
            return False
        from .compiled_tick import sampling_hostable
        return all(sampling_hostable(r.sampling)
                   for r in self._active.values())

    def _fused_sample(self, last):
        """One jitted per-iteration sampling call over all slots —
        exactly the vectorized processor chain the compiled tick runs
        in-program, so a request's token stream is identical whichever
        lane draws it.  Returns np [num_slots] tokens."""
        from .compiled_tick import fused_sample_call, request_key
        ns = self.cache.num_slots
        vocab = self.cfg.vocab_size
        temp = np.zeros(ns, np.float32)
        topk = np.zeros(ns, np.int32)
        topp = np.ones(ns, np.float32)
        pen = np.ones(ns, np.float32)
        keys = np.zeros((ns, 2), np.uint32)
        counts = np.zeros(ns, np.int32)
        seen = np.zeros((ns, vocab), bool)
        for slot, req in self._active.items():
            sp = req.sampling
            temp[slot] = sp.temperature
            topk[slot] = sp.top_k or 0
            if sp.top_p is not None:
                topp[slot] = sp.top_p
            if sp.repetition_penalty is not None:
                pen[slot] = sp.repetition_penalty
            counts[slot] = len(req.tokens)
            if not sp.greedy and sp.seed is not None:
                keys[slot] = request_key(sp)
            if req.seen is not None:
                seen[slot] = req.seen
        return np.asarray(fused_sample_call(
            last._data_, temp, topk, topp, pen, seen, keys, counts))

    def _sample_row(self, logits_row, req):
        """[1, V] logits → one token under the request's params, read
        to the host (`_draw_row`)."""
        return int(np.asarray(self._draw_row(logits_row, req))
                   .reshape(-1)[0])

    def _draw_row(self, logits_row, req):
        """[1, V] logits → one token under the request's params (the
        processor chain shared with models/generation), on the device
        and unread.  Seeded non-greedy requests draw from their own key
        stream (the same ``fold_in(PRNGKey(seed), n_generated)`` the
        fused call and the compiled tick use, so the stream is
        lane-independent from token 0); everything else is the
        historical global-RNG path."""
        from ..core.tensor import Tensor
        from ..models.generation import sample_next_token
        from ..utils.flags import flag as _flag
        sp = req.sampling
        if not sp.greedy and sp.seed is not None and \
                _flag("FLAGS_serving_fused_sampling", True):
            from .compiled_tick import fused_sample_call, request_key
            seen = req.seen[None, :] if req.seen is not None else \
                np.zeros((1, self.cfg.vocab_size), bool)
            return fused_sample_call(
                logits_row._data_,
                np.asarray([sp.temperature], np.float32),
                np.asarray([sp.top_k or 0], np.int32),
                np.asarray([sp.top_p if sp.top_p is not None else 1.0],
                           np.float32),
                np.asarray([sp.repetition_penalty
                            if sp.repetition_penalty is not None
                            else 1.0], np.float32),
                seen, request_key(sp)[None, :],
                np.asarray([len(req.tokens)], np.int32))
        seen_t = Tensor(req.seen[None, :]) if req.seen is not None \
            else None
        return sample_next_token(
            logits_row, temperature=sp.temperature, top_k=sp.top_k,
            top_p=sp.top_p, repetition_penalty=sp.repetition_penalty,
            seen=seen_t)._data_

    def _append_token(self, req, tok):
        """Account one generated token, then finish/evict the request
        if it hit EOS, its token budget, slot capacity, or deadline."""
        self._mut += 1          # host-lane mutation: tick mirrors stale
        req.tokens.append(tok)
        req.last_token = tok
        if req.seen is not None:
            req.seen[tok] = True
        stats.incr("tokens_generated")
        now = time.monotonic()
        if self.scfg.deadline_policy == "evict" and \
                req.deadline is not None and now > req.deadline:
            self._fail(req, DeadlineExceededError(
                f"request {req.id} exceeded its deadline after "
                f"{len(req.tokens)} token(s)"))
            stats.incr("requests_evicted_deadline")
            self._release(req)
            return
        reason = None
        if req.eos_token_id is not None and tok == req.eos_token_id:
            reason = "eos"
        elif len(req.tokens) >= req.max_new_tokens:
            reason = "length"
        elif req.prompt.size + len(req.tokens) >= self.max_len:
            reason = "length"       # slot capacity: no room to decode
        if reason is not None:
            self._complete(req, reason, now)
            self._release(req)

    def _complete(self, req, reason, now):
        out = RequestOutput(
            request_id=req.id, prompt_ids=req.prompt,
            output_ids=np.asarray(req.tokens, np.int32),
            finish_reason=reason, ttft_ms=req.ttft_ms,
            latency_ms=(now - req.submit_t) * 1e3, slot=req.slot)
        with self._lock:
            self._pending.pop(req.id, None)
        try:
            if not req.future.done():
                req.future.set_result(out)
        except Exception:       # lost the race to a concurrent _fail
            return
        stats.incr("requests_completed")
        if req.trace is not None:
            if req.trace.decode is not None:
                req.trace.decode.set(tokens=len(req.tokens))
            req.trace.finish("ok", out.latency_ms,
                             finish_reason=reason,
                             tokens=len(req.tokens))
        # labeled by the same request_id the span args carry, so one
        # request's trace and metrics can be joined post-hoc
        stats.request_observe("request_tokens", req.id, len(req.tokens),
                              help="tokens generated per request")
        from ..observability import flight_recorder as _fr
        _fr.record("serving", "request_done", request_id=req.id,
                   reason=reason, tokens=len(req.tokens),
                   ttft_ms=round(req.ttft_ms, 3)
                   if req.ttft_ms is not None else None)

    def _fail(self, req, exc):
        with self._lock:
            self._pending.pop(req.id, None)
        try:
            if req.future.done():
                return
            req.future.set_exception(exc)
        except Exception:       # resolved by a concurrent completer
            return
        if req.trace is not None:
            req.trace.finish(
                type(exc).__name__,
                (time.monotonic() - req.submit_t) * 1e3,
                error=str(exc)[:200])
        from ..observability import flight_recorder as _fr
        _fr.record("serving", "request_failed", request_id=req.id,
                   error=type(exc).__name__)

    def _release(self, req):
        if req.slot is None:
            return
        self._mut += 1          # slot membership changed: tick rebuilds
        if self._active.get(req.slot) is req:
            del self._active[req.slot]
        # a request holds its slot and pages from admission on (prefill
        # included), not only once it is active
        self.cache.release(req.slot)
        if self._spec and self.draft_cache is not None:
            self.draft_cache.release(req.slot)
        if req.prefix_nodes and self.prefix_tree is not None:
            self.prefix_tree.release(req.prefix_nodes)
            req.prefix_nodes = []
        if self.adapter_pool is not None:
            self.adapter_pool.clear_row(req.slot)
            if req.adapter_id is not None:
                self.adapter_pool.release(req.adapter_id)
                req.adapter_id = None   # released exactly once
                req.adapter_slot = 0
        req.slot = None

    def _fail_all(self, exc):
        """Fail EVERY outstanding future — queued, mid-admission, and
        slot-resident alike (the `_pending` registry is the audit set;
        `_queue` + `_active` alone would miss a request popped for
        admission whose prefill never finished)."""
        with self._lock:
            reqs = list(self._pending.values())
            self._pending.clear()
            self._queue.clear()
            self._active.clear()
            self._prefilling.clear()
            self._migrating_out.clear()
            self._migration_results.clear()
            self._cancels.clear()
        for req in reqs:
            if not req.future.done():
                self._fail(req, exc)
                stats.incr("requests_cancelled_shutdown")
