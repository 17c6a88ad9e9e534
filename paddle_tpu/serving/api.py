"""Serving frontend types: config, sampling params, results, errors.

The engine (serving/engine.py) consumes these; clients construct a
`ServingConfig`, `Engine(model, config).start()`, then call the sync
`generate()` or async `submit() -> Future` APIs.  Admission control is
part of the contract: a bounded queue rejects with `QueueFullError`
instead of buffering unboundedly, and per-request deadlines evict the
slot (`DeadlineExceededError`) so one slow client cannot squat capacity.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


class ServingError(RuntimeError):
    """Base class for serving-layer failures."""


class QueueFullError(ServingError):
    """Admission rejected: the bounded request queue is at capacity.

    When the serving router sheds a request because every ready replica
    is at capacity, ``retry_after_s`` carries the suggested client
    backoff (the fleet analog of an HTTP 429 Retry-After header)."""

    def __init__(self, *args, retry_after_s=None):
        super().__init__(*args)
        self.retry_after_s = retry_after_s


class NoReplicaError(ServingError):
    """The router found no ready replica to route to (none registered,
    all dead, or all draining) and the request's deadline/patience ran
    out — the loud alternative to a client hanging on a dead fleet."""


class DeadlineExceededError(ServingError):
    """The request's deadline passed; its slot was evicted (or it was
    dropped from the queue before ever reaching a slot)."""


class EngineShutdownError(ServingError):
    """The engine stopped (or is draining) while the request was queued
    or in flight."""


class RequestCancelledError(ServingError):
    """The request was cancelled via ``Engine.cancel`` before it
    finished — the hedged-dispatch loser path: the router got its
    answer from another replica, so this attempt's slot, KV pages and
    adapter rows were released and its future failed with this error
    (which the router's first-answer-wins delivery never surfaces to
    the client)."""


class SchedulerStallError(ServingError):
    """One scheduler iteration exceeded ``ServingConfig.step_timeout_s``;
    the engine failed every outstanding future and restarted its loop
    (bounded by ``max_scheduler_restarts``)."""


class AdapterConfigError(ServingError):
    """An adapter registration is infeasible for this engine's pool at
    construction time — rank over ``adapter_rank_pool``, factor shapes
    that don't match the base model's projection widths/vocab, or a
    projection name the base model does not have.  Raised from
    ``Engine(...)``/``AdapterPool.register`` so the misconfiguration
    surfaces as a typed error naming the offending layer, never as a
    shape error mid-decode."""


class UnknownAdapterError(ServingError):
    """A request named an ``adapter_id`` absent from the engine's
    adapter registry.  Delivered by failing THAT request's future (the
    scheduler never sees the request); the message names the registered
    ids so the client can correct itself."""


class PageMigrationError(ServingError):
    """A KV-page migration payload cannot be adopted by the target
    replica's pool — incompatible page size / dtype / layer geometry, or
    an inconsistent offset.  The sending replica treats this exactly
    like a dead target: it falls back to decoding locally."""


class RecurrentStateError(ServingError, ValueError):
    """The configuration asks for a mechanism that cannot yet carry the
    fixed-size recurrent state some of the model's layers keep per
    sequence (what ``model.config.layer_states()`` reports): prefix
    sharing, speculative rollback, and page export / migration all
    move or rewind keys and values by page or by
    offset, and a recurrence has neither.  Raised at ``Engine``
    construction (and by the page export / adopt calls themselves) and
    names the mechanism — never a wrong answer mid-decode."""


class WindowLayerError(ServingError, ValueError):
    """The configuration asks for a mechanism that cannot yet serve a
    model with sliding_attention layers (what
    ``model.config.layer_windows()`` reports): such a layer keeps a ring
    of pages a slot and gives up the page that fell out of its window, so
    a shared prefix's pages (``enable_prefix_cache``), a speculative
    window's ``rollback`` and page export / adoption have nothing to
    share, rewind or send for those layers.  Raised at ``Engine``
    construction (and by the cache calls themselves) and names the layer
    kind — never a wrong answer mid-decode."""


class LatentStoreError(ServingError, ValueError):
    """The configuration asks for a mechanism that cannot yet serve a
    model whose layers keep a latent page store (what
    ``model.config.layer_latents()`` reports: one row a token, no heads,
    no V): page export / adoption and migration carry a K/V page store's
    ``k`` and ``v`` pages by name, quantized storage keeps per-page K/V
    scales, and a draft model's mirror cache holds keys and values only.
    What moves pages through the page table alone — prefix sharing,
    speculative rollback — works unchanged: a latent page is a page.
    Raised at ``Engine`` construction (and by the cache calls
    themselves) and names the store kind — never a wrong answer
    mid-decode."""


@dataclass(frozen=True)
class SamplingParams:
    """Per-request decoding knobs — the same semantics (and HF processor
    order) as `models.generation.generate`; temperature=0.0 is greedy.

    ``seed`` pins a non-greedy request to its own deterministic sampling
    stream (``fold_in(PRNGKey(seed), n_generated)`` per draw) instead of
    the process-global RNG.  Seeded requests are reproducible across
    runs AND lane-independent — the per-row host path, the fused
    per-iteration sampling call, and the compiled scheduler tick all
    draw the identical token — which is also what makes a sampled
    request *hostable* by the compiled tick (docs/SERVING.md)."""

    temperature: float = 0.0
    top_k: int | None = None
    top_p: float | None = None
    repetition_penalty: float | None = None
    seed: int | None = None

    def validate(self):
        if self.top_p is not None and not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if self.repetition_penalty is not None and \
                self.repetition_penalty <= 0.0:
            raise ValueError("repetition_penalty must be > 0, got "
                             f"{self.repetition_penalty}")
        if self.temperature < 0.0:
            raise ValueError(
                f"temperature must be >= 0, got {self.temperature}")
        if self.seed is not None and int(self.seed) < 0:
            raise ValueError(f"seed must be >= 0, got {self.seed}")
        return self

    @property
    def greedy(self):
        return self.temperature == 0.0

    @property
    def uses_penalty(self):
        return self.repetition_penalty is not None and \
            self.repetition_penalty != 1.0


@dataclass
class ServingConfig:
    """Engine knobs (docs/KNOBS.md "serving" table).

    num_slots                decode-batch width = max concurrent
                             sequences (the ONE compiled decode step is
                             [num_slots, 1] whatever mix occupies it)
    max_queue                bounded admission queue; submit() past this
                             raises QueueFullError
    max_seq_len              per-slot KV capacity; None → model's
                             config.max_seq_len
    default_max_new_tokens   per-request cap when submit() passes None
    request_timeout_s        sync generate()'s Future.result timeout
    deadline_policy          "evict": a request past its deadline_s is
                             failed and its slot freed; "ignore":
                             deadlines are recorded but never enforced
    cache_dtype              KV-cache element type.  "int8" (or "fp8"
                             on jax builds with float8) stores paged
                             K/V quantized with per-page scale arrays
                             and a dequant-fused read; each quantized
                             page packs 2x page_size tokens in half the
                             baseline page's bytes, so the pages-in-use
                             gauge at equal token load ~halves
    idle_wait_s              scheduler sleep when no work is queued
    drain_grace_s            `drain()` deadline when none is passed: how
                             long in-flight slots may run on before the
                             engine shuts down anyway (the SIGTERM path)
    step_timeout_s           scheduler-iteration watchdog budget: an
                             iteration (prefills + one decode step)
                             exceeding it fails every outstanding future
                             with SchedulerStallError and restarts the
                             loop; 0 (default) disables the watchdog
    max_scheduler_restarts   bounded retries for the scheduler loop
                             after a crash or stall before the engine
                             gives up and stops accepting work
    page_size                tokens per KV page (serving/paged_kv.py);
                             pick a divisor of max_seq_len
    kv_pool_pages            physical pages in the pool; None →
                             num_slots * ceil(max_seq_len / page_size),
                             i.e. every slot can reach max_seq_len at
                             once
    enable_prefix_cache      keep released prompt pages in a refcounted
                             prefix tree so requests sharing a system
                             prompt reuse its KV instead of recomputing
                             prefill
    prefill_chunk_tokens     prompts prefill this many tokens per
                             scheduler iteration, interleaved with
                             decode steps, so a long prompt cannot
                             starve in-flight streams (one compiled
                             prefill program a row bucket)
    draft_model              small proposer model for speculative
                             decoding (same tokenizer/vocab as the
                             target; its config.max_seq_len must cover
                             max_seq_len).  None (default) = no
                             speculation
    speculation_k            draft tokens proposed per slot per
                             scheduler iteration; the target model
                             verifies all K+1 positions in ONE batched
                             call and an accept-mask rollback rewinds
                             the rejected tail (0 = off — the decode
                             loop is bitwise the plain one).
                             Speculation engages when
                             every active request is greedy without
                             repetition penalty; mixed batches fall
                             back to the plain step for that iteration
    role                     prefill/decode disaggregation role this
                             engine's replica advertises to the fleet:
                             "mixed" (default — byte-identical to the
                             pre-disaggregation fleet), "prefill"
                             (prefers prefill work; hands finished
                             prompts' KV pages to a decode replica),
                             or "decode" (receives migrated pages and
                             runs the pure-decode hot loop).  Roles are
                             routing preferences, never hard fences: a
                             replica of any role still serves whatever
                             the router sends it (docs/SERVING.md
                             "Prefill/decode disaggregation")
    max_adapters             concurrent hot LoRA adapters multiplexed
                             over the base model (docs/SERVING.md
                             "Multi-tenant serving").  0 (default) = no
                             adapter pool — the engine is byte-identical
                             to the pre-LoRA engine.  >0 preallocates
                             per-projection A/B stacks of
                             max_adapters+1 slots (slot 0 = the exact
                             identity base requests ride) and enables
                             submit(..., adapter_id=...)
    adapter_rank_pool        fixed rank budget every pool slot is padded
                             to; registering an adapter with rank >
                             adapter_rank_pool raises AdapterConfigError
                             at construction
    adapters                 adapter registry {adapter_id: source},
                             source a save_adapter() artifact dir or an
                             in-memory nn.lora.adapter_spec dict.
                             Validated at Engine construction (typed
                             AdapterConfigError naming the layer, never
                             a shape error mid-decode); more can be
                             registered later via
                             Engine.register_adapter
    """

    num_slots: int = 4
    max_queue: int = 64
    max_seq_len: int | None = None
    default_max_new_tokens: int = 64
    request_timeout_s: float = 120.0
    deadline_policy: str = "evict"
    cache_dtype: str = "float32"
    idle_wait_s: float = 0.005
    drain_grace_s: float = 30.0
    step_timeout_s: float = 0.0
    max_scheduler_restarts: int = 2
    page_size: int = 16
    kv_pool_pages: int | None = None
    enable_prefix_cache: bool = True
    prefill_chunk_tokens: int = 32
    draft_model: object | None = None
    speculation_k: int = 0
    role: str = "mixed"
    max_adapters: int = 0
    adapter_rank_pool: int = 8
    adapters: dict | None = None

    def validate(self):
        if self.role not in ("mixed", "prefill", "decode"):
            raise ValueError(
                "role must be 'mixed', 'prefill' or 'decode', got "
                f"{self.role!r}")
        if self.num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got "
                             f"{self.num_slots}")
        if self.max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got "
                             f"{self.max_queue}")
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got "
                             f"{self.page_size}")
        if self.kv_pool_pages is not None and self.kv_pool_pages < 1:
            raise ValueError(f"kv_pool_pages must be >= 1, got "
                             f"{self.kv_pool_pages}")
        if self.prefill_chunk_tokens < 1:
            raise ValueError(f"prefill_chunk_tokens must be >= 1, got "
                             f"{self.prefill_chunk_tokens}")
        if self.deadline_policy not in ("evict", "ignore"):
            raise ValueError(
                "deadline_policy must be 'evict' or 'ignore', got "
                f"{self.deadline_policy!r}")
        if self.drain_grace_s < 0:
            raise ValueError(f"drain_grace_s must be >= 0, got "
                             f"{self.drain_grace_s}")
        if self.step_timeout_s < 0:
            raise ValueError(f"step_timeout_s must be >= 0, got "
                             f"{self.step_timeout_s}")
        if self.max_scheduler_restarts < 0:
            raise ValueError(f"max_scheduler_restarts must be >= 0, "
                             f"got {self.max_scheduler_restarts}")
        from ..quantization import kv_quant_params
        kv_quant_params(self.cache_dtype)   # an fp8 this jax lacks raises
        if self.speculation_k < 0:
            raise ValueError(f"speculation_k must be >= 0, got "
                             f"{self.speculation_k}")
        if self.speculation_k > 0 and self.draft_model is None:
            raise ValueError(
                "speculation_k > 0 needs a draft_model to propose "
                "tokens; pass ServingConfig(draft_model=...)")
        if self.max_adapters < 0:
            raise ValueError(f"max_adapters must be >= 0, got "
                             f"{self.max_adapters}")
        if self.adapter_rank_pool < 1:
            raise ValueError(f"adapter_rank_pool must be >= 1, got "
                             f"{self.adapter_rank_pool}")
        if self.adapters and self.max_adapters == 0:
            raise ValueError(
                "ServingConfig.adapters given but max_adapters == 0 — "
                "set max_adapters to the concurrent-adapter budget")
        return self


@dataclass
class RequestOutput:
    """What a completed request's Future resolves to."""

    request_id: int
    prompt_ids: np.ndarray          # [S] int32, as submitted
    output_ids: np.ndarray          # [T] int32 generated tokens
    finish_reason: str              # "eos" | "length"
    ttft_ms: float                  # submit → first token
    latency_ms: float               # submit → completion
    #: replica that decoded the tail of this request (fleet only): the
    #: submit target unless KV-page migration resumed it elsewhere
    decoded_by: str | None = None
    #: the slot whose pages and state rows held the request (None where
    #: another replica decoded it).  Its recurrent state rows stay as the
    #: request's last tick left them until the slot's next admission
    #: (`PagedKVCache.read_state`).
    slot: int | None = None

    @property
    def ids(self):
        """[S+T] prompt + generated, the `generate()`-shaped view."""
        return np.concatenate([self.prompt_ids, self.output_ids])
