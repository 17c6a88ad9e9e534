"""Serving observability: queue depth, TTFT, per-token latency, slot
occupancy, throughput.

Reference capability: the reference's serving deployments watch
predictor QPS through paddle/fluid/platform/monitor.h counters.
TPU-native realization: the engine publishes its counters through
`paddle_tpu.utils.monitor` under the ``serving.`` prefix (thread-safe —
the scheduler thread writes while clients read `all_stats()`), and
`serving_stats()` derives the dashboard quantities (averages, occupancy,
tokens/sec) from the raw counters at read time.
"""
from __future__ import annotations

from ..pallas.moe import SCORINGS
from ..utils import monitor

PREFIX = "serving."


def incr(name, value=1):
    return monitor.incr(PREFIX + name, value)


def request_observe(name, request_id, value, help=""):  # noqa: A002
    """Per-request labeled series ``serving.<name>{request_id=...}`` —
    the same monotonically increasing id the engine puts in the
    ``request_ids`` of its ``serving.prefill_chunk``/``serving.tick``
    phase spans, so one request's trace spans and metrics join on it.  Cardinality is bounded TWICE:
    ``reset_serving_stats()`` clears the families at engine start, and
    within one engine run the family is LRU-rotated to at most
    ``FLAGS_serving_request_label_cap`` children (the oldest request's
    series is dropped when a new request would exceed the cap), so a
    long-lived engine's registry converges instead of growing one child
    per request forever."""
    from ..observability import registry as _registry
    from ..utils.flags import flag as _flag
    cap = int(_flag("FLAGS_serving_request_label_cap", 1024) or 0)
    _registry.counter(PREFIX + name, help,
                      labelnames=("request_id",)) \
        .labels_lru(cap, request_id=str(request_id)).inc(value)


def set_value(name, value):
    monitor.set_value(PREFIX + name, value)


def observe(name, value):
    monitor.observe(PREFIX + name, value)


ROUTER_PREFIX = PREFIX + "router."


def route_observe(replica, role="mixed"):
    """One routed request: the per-replica labeled counter
    ``serving.router.requests_routed{replica=...}``, the per-role
    ``serving.router.requests_routed_role{role=...}`` disaggregation
    view, plus the flat total the snapshot reads."""
    from ..observability import registry as _registry
    _registry.counter(ROUTER_PREFIX + "requests_routed",
                      "requests routed per replica",
                      labelnames=("replica",)) \
        .labels(replica=str(replica)).inc()
    _registry.counter(ROUTER_PREFIX + "requests_routed_role",
                      "requests routed per replica role",
                      labelnames=("role",)) \
        .labels(role=str(role or "mixed")).inc()
    monitor.incr(ROUTER_PREFIX + "requests_routed_total")


def health_observe(replica, score):
    """Publish one replica's current health score (EWMA-latency-based,
    error-inflated — serving/router.py `_ReplicaHealth`) as the
    ``serving.router.replica_health_score{replica=...}`` gauge the
    gray-failure dashboard plots against the ejection threshold."""
    from ..observability import registry as _registry
    _registry.gauge(ROUTER_PREFIX + "replica_health_score",
                    "per-replica health score (EWMA latency ms, "
                    "error-inflated); outliers vs the fleet median "
                    "are ejected",
                    labelnames=("replica",)) \
        .labels(replica=str(replica)).set(float(score))


def reset_serving_stats():
    """Clear every ``serving.*`` counter EXCEPT the router's (engine
    start does this so each engine run's snapshot is self-contained;
    the router outlives engine restarts across the fleet, so its
    counters reset only with the router — `reset_router_stats`)."""
    for key in monitor.all_stats():
        if key.startswith(PREFIX) and not key.startswith(ROUTER_PREFIX):
            monitor.reset(key)


def declare_tick_stats():
    """Get-or-create the compiled-tick metric families at engine start
    so the Prometheus exposition carries the full tick schema before
    the first iteration — a dashboard must see ``tick_fallbacks`` at 0,
    not a missing series, on an engine that never fell back
    (tools/check_telemetry.py --serving-tick gates on exactly this)."""
    from ..observability import registry as _registry
    _registry.counter(PREFIX + "tick.compiled_hits",
                      "scheduler iterations run as ONE compiled tick "
                      "program")
    _registry.counter(PREFIX + "tick.fallbacks",
                      "scheduler iterations that latched the "
                      "uncompiled fallback")
    _registry.counter(PREFIX + "tick.overlapped",
                      "compiled ticks launched while the one before was "
                      "still unread")
    _registry.counter(PREFIX + "tick.drains",
                      "collections of the tick in flight with no launch "
                      "over them: a mutation, a blocker, nothing left to "
                      "launch")
    _registry.histogram(PREFIX + "tick_ms",
                        "wall time of one scheduler iteration (ms)")
    _registry.histogram(PREFIX + "tick.host_ms",
                        "host time of one compiled tick: the tick less "
                        "its wait for the device (ms)")
    _registry.histogram(PREFIX + "queue_wait_ms",
                        "submit to admission, per admitted request (ms)")
    for name, text in (
            ("queue.request_ms", "queue depth integrated over time "
                                 "(request-milliseconds)"),
            ("kv.page_ticks_in_use", "KV pages held, summed over ticks"),
            ("kv.page_ticks_reserved", "KV pages held or promised to "
                                       "admitted requests, summed over "
                                       "ticks"),
            ("kv.window.page_ticks_held", "pages the slots' window "
                                          "tables held, summed over ticks"),
            ("kv.window.page_ticks_full_equiv", "pages one shared table "
                                                "would have held for the "
                                                "window layers, summed "
                                                "over ticks"),
            ("kv.window.pages_reclaimed", "ring entries a new logical "
                                          "page reused: pages that fell "
                                          "out of the window"),
            ("kv.window.token_ticks", "tokens a window layer's decode "
                                      "read covers, summed over rows and "
                                      "ticks"),
            ("kv.context_token_ticks", "context lengths of the decoding "
                                       "rows, summed over ticks (a model "
                                       "with window layers)"),
            ("prefill.context_tokens", "positions the prefilled tokens "
                                       "see in a full attention layer (a "
                                       "model with window layers)"),
            ("prefill.window_context_tokens", "positions they see in a "
                                              "window layer"),
            ("moe.pairs_local", "(token, held expert) pairs the expert "
                                "layers computed"),
            ("moe.prefill.pairs", "of those pairs, the prefill members'"),
            ("moe.prefill.experts_hit", "of the experts hit, the prefill "
                                        "members'"),
            ("moe.tokens_routed", "tokens the expert layers routed "
                                  "(real positions x layers)"),
            ("moe.experts_hit", "held experts with at least one pair, "
                                "summed over layers and programs"),
            ("prefill.tokens_computed", "token positions the prefill "
                                        "chunk calls computed"),
            ("prefill.tokens_useful", "of those, new prompt tokens"),
            ("prefill.launches", "programs launched inside prefill "
                                 "chunk calls: one a compiled call, "
                                 "every eager-op dispatch of an eager "
                                 "one"),
            ("prefill.compiled_hits", "prefill chunk calls run as ONE "
                                      "compiled program"),
            ("prefill.fallbacks", "prefill chunk calls that took the "
                                  "eager lane because one program could "
                                  "not host them"),
            ("prefill.overlapped", "compiled prefill chunk calls read "
                                   "after the next tick was launched "
                                   "behind them")):
        _registry.counter(PREFIX + name, text)
    # recurrent state beside pages (zero for a model without such layers)
    for name, text in (
            ("state.resets", "slots whose recurrent state an admission "
                             "reset"),
            ("state.row_ticks_live", "state rows a compiled tick moved "
                                     "for a decoding request"),
            ("state.row_ticks_total", "state rows a compiled tick passed "
                                      "through the update")):
        _registry.counter(PREFIX + name, text)
    _registry.histogram(PREFIX + "state.reset_ms",
                        "one admission's reset of its slot's recurrent "
                        "state (ms)")
    _registry.gauge(PREFIX + "state.bytes",
                    "bytes of the per-slot recurrent state arrays")
    _registry.gauge(PREFIX + "state.heads_per_tile",
                    "heads whose channels share one row of lanes in the "
                    "lane-packed SSM state (0 without one)")
    _registry.gauge(PREFIX + "kv.pages_spanned",
                    "page-table entries of the decode batch: slots x "
                    "pages a slot")
    _registry.gauge(PREFIX + "kv.pools",
                    "page pools the cache holds: K and V of every paged "
                    "layer")
    _registry.gauge(PREFIX + "kv.pools_lane_dense",
                    "of those, the pools stored [pages, rows, 128] as the "
                    "paged decode kernel reads them")
    _registry.gauge(PREFIX + "kv.latent_pools",
                    "latent page pools the cache holds: one a layer of "
                    "latent attention")
    _registry.gauge(PREFIX + "kv.latent_row_bytes",
                    "bytes of one cached latent row as the arithmetic "
                    "counts them (its values, not the lanes it lives in)")
    _registry.gauge(PREFIX + "kv.layers_without_store",
                    "layers that hold no page and no state row: a "
                    "feed-forward part alone")
    for scoring in SCORINGS:
        _registry.gauge(PREFIX + f"moe.router_layers.{scoring}",
                        f"expert layers whose router scores by {scoring}")


def declare_migration_stats():
    """Get-or-create the KV-page-migration metric families at engine
    start so the Prometheus exposition carries the full disaggregation
    schema before the first transfer — a dashboard must see
    ``migrations`` at 0, not a missing series, on a replica that never
    migrated (tools/check_telemetry.py --migration gates on this)."""
    from ..observability import registry as _registry
    _registry.counter(PREFIX + "migration.pages_sent",
                      "KV pages exported to another replica")
    _registry.counter(PREFIX + "migration.pages_received",
                      "KV pages adopted from another replica")
    _registry.counter(PREFIX + "migration.migrations",
                      "requests whose decode was handed off and "
                      "completed remotely")
    _registry.counter(PREFIX + "migration.resumed_requests",
                      "migrated requests resumed from adopted pages "
                      "on this replica")
    _registry.counter(PREFIX + "migration.fallbacks",
                      "failed transfers that fell back to decoding "
                      "locally (dead target, pool full, timeout)")
    _registry.counter(PREFIX + "migration.remote_failures",
                      "targets that died AFTER adopting pages; the "
                      "request was failed for router resubmission")
    _registry.histogram(PREFIX + "migration.migrate_ms",
                        "wall time of one page transfer + remote "
                        "resume handshake (ms)")


def declare_adapter_stats():
    """Get-or-create the multi-tenant LoRA metric families at engine
    start so the Prometheus exposition carries the full adapter schema
    before the first hot-load — a dashboard must see
    ``adapter_evictions`` at 0, not a missing series, on an engine that
    never evicted (tools/check_telemetry.py --lora gates on this)."""
    from ..observability import registry as _registry
    _registry.counter(PREFIX + "adapter.adapters_loaded",
                      "adapters hot-loaded into pool slots")
    _registry.counter(PREFIX + "adapter.adapter_evictions",
                      "LRU evictions of idle adapters from pool slots")
    _registry.counter(PREFIX + "adapter.requests_routed_adapter_total",
                      "requests admitted carrying any adapter_id")
    _registry.counter(PREFIX + "adapter.requests_routed_adapter",
                      "requests admitted per adapter",
                      labelnames=("adapter",))
    _registry.histogram(PREFIX + "adapter.adapter_load_ms",
                        "wall time of one adapter hot-load into its "
                        "pool slot (ms)")


def declare_trace_stats():
    """Get-or-create the distributed-tracing metric families at router/
    engine start so the Prometheus exposition carries the full tracing
    schema before the first span — a dashboard must see
    ``trace_spans_dropped`` at 0, not a missing series, on a process
    that never overflowed its span ring (tools/check_telemetry.py
    --trace gates on this)."""
    from ..observability import registry as _registry
    _registry.counter(PREFIX + "trace.spans",
                      "completed spans recorded into the per-process "
                      "trace ring")
    _registry.counter(PREFIX + "trace.spans_dropped",
                      "completed spans dropped oldest-first when the "
                      "ring exceeded FLAGS_trace_buffer_cap")
    _registry.counter(PREFIX + "trace.decisions",
                      "tail-sampling decisions made at root-request "
                      "completion (exactly one per trace)")
    _registry.counter(PREFIX + "trace.decisions_kept",
                      "tail-sampling decisions that KEPT the trace "
                      "(error/evicted/deadline, latency threshold, or "
                      "probabilistic floor)")
    _registry.counter(PREFIX + "trace.spools",
                      "atomic JSONL spool writes under FLAGS_trace_dir")


def adapter_observe(adapter_id):
    """One admitted adapter request: the per-adapter labeled counter
    ``serving.adapter.requests_routed_adapter{adapter=...}`` plus the
    flat total the snapshot reads.  Cardinality is bounded by the
    engine run, like ``request_tokens`` (``reset_serving_stats()``
    clears the family at engine start)."""
    from ..observability import registry as _registry
    _registry.counter(PREFIX + "adapter.requests_routed_adapter",
                      "requests admitted per adapter",
                      labelnames=("adapter",)) \
        .labels(adapter=str(adapter_id)).inc()
    monitor.incr(PREFIX + "adapter.requests_routed_adapter_total")


def declare_router_stats():
    """Get-or-create every ``serving.router.*`` metric family so the
    Prometheus exposition carries the full fleet schema from router
    start — a dashboard must see ``requests_shed`` at 0, not a missing
    series, before the first shed (tools/check_telemetry.py --router
    gates on exactly this)."""
    from ..observability import registry as _registry
    _registry.counter(ROUTER_PREFIX + "requests_routed",
                      "requests routed per replica",
                      labelnames=("replica",))
    _registry.counter(ROUTER_PREFIX + "requests_routed_role",
                      "requests routed per replica role",
                      labelnames=("role",))
    for name, doc in (
            ("requests_routed_total", "requests routed, all replicas"),
            ("requests_shed", "fail-fast rejections: every ready "
                              "replica at capacity"),
            ("failovers", "replica deaths detected mid-request"),
            ("resubmissions", "re-sends under the same idempotent id"),
            ("requests_recovered", "requests completed after >= 1 "
                                   "resubmission"),
            ("replicas_lost", "replicas marked sticky-dead"),
            ("ejections", "replicas ejected by the gray-failure "
                          "guardian (health-score outliers; reversible, "
                          "unlike sticky-dead)"),
            ("readmissions", "ejected replicas readmitted after "
                             "sustained canary recovery"),
            ("hedges", "hedge requests fired past the latency "
                       "percentile (same idempotent rid)"),
            ("hedge_wins", "requests whose hedge answered before the "
                           "primary attempt"),
            ("breaker_open", "circuit-breaker closed->open transitions "
                             "(per-replica rpc breakers)"),
            ("retry_budget_exhausted", "resubmissions refused by the "
                                       "fleet-wide token-bucket retry "
                                       "budget")):
        _registry.counter(ROUTER_PREFIX + name, doc)
    _registry.gauge(ROUTER_PREFIX + "replicas_alive",
                    "ready replicas in the routing ring")
    _registry.gauge(ROUTER_PREFIX + "replica_health_score",
                    "per-replica health score (EWMA latency ms, "
                    "error-inflated); outliers vs the fleet median "
                    "are ejected",
                    labelnames=("replica",))
    _registry.histogram(ROUTER_PREFIX + "route_latency_ms",
                        "submit-to-completion through the fleet (ms)")


def reset_router_stats():
    """Clear the ``serving.router.*`` counters (router start).  Labeled
    children (``requests_routed{replica=...}``) reset with their family
    — ``monitor.reset`` resolves the flat key back to the registry
    metric."""
    declare_router_stats()
    for key in monitor.all_stats():
        if key.startswith(ROUTER_PREFIX):
            monitor.reset(key)


def serving_stats():
    """One consistent snapshot of the serving counters plus derived
    quantities:

    - ``ttft_ms_avg``       mean time-to-first-token (submit → first
                            sampled token, prefill inclusive)
    - ``per_token_ms_avg``  mean decode-step wall time (each active
                            request gains one token per step)
    - ``slot_occupancy``    active-slot steps / total slot steps — how
                            full the continuous batch ran
    - ``tokens_per_sec``    generated tokens / engine busy time
                            (prefill + decode wall)

    Compiled-tick quantities (ISSUE 13): ``tick_ms_avg`` — mean wall
    time of one whole scheduler iteration (admissions + prefill chunk +
    decode, whichever lane ran it) — plus ``tick_compiled_hits`` /
    ``tick_fallbacks`` counting iterations the ONE-program compiled
    tick executed vs iterations that latched the uncompiled scheduler
    (flag off mid-run, speculation, unhostable sampling, hooks),
    ``tick_overlapped`` (ticks launched while the one before was still
    unread), ``tick_drains`` (collections of the tick in flight that no
    launch covered) and ``tick_overlap_share`` = overlapped / hits, and
    ``prefill_compiled_hits`` / ``prefill_fallbacks`` the same for
    prefill chunk calls (the draft model's eager calls are in
    neither), with ``prefill_overlap_share`` = the compiled calls read
    after the next tick was launched behind them / compiled calls; all
    ride the Prometheus exposition
    (``serving_tick_ms`` histogram, ``serving_tick_compiled_hits`` /
    ``serving_tick_fallbacks`` counters, gated by
    tools/check_telemetry.py --serving-tick).

    KV-pool quantities:
    ``kv_pages_in_use``/``kv_pages_free`` pool gauges plus the
    ``kv_pages_peak`` high-water mark (the int8-KV capacity gate reads
    it: at equal token load a quantized pool's peak ~halves),
    ``prefix_cache_hits``/``misses``/``evictions`` and
    ``prefix_cache_hit_tokens`` tree counters, ``prefill_chunks`` and
    ``prefill_chunk_ms_avg`` chunked-prefill cadence, and
    ``max_active_slots`` — the high-water mark of concurrent decoding
    sequences (the pool admits more of them than it has
    ``max_seq_len``-long stretches of pages).
    ``kv_pages_streamed_per_tick`` (pages held, a mean over the
    compiled ticks: what the paged decode kernel reads) stands beside
    ``kv_pages_spanned_per_tick`` (slots x pages a slot: what a walk
    over every table entry would pay), and
    ``paged_decode_kernel_traces`` / ``paged_decode_xla_lane_traces``
    count the traces of a single-token paged read that chose the
    Pallas kernel or the XLA gather lane (process-wide, not reset at
    engine start: a program is traced once and run many times).
    ``kv_pools_lane_dense`` of ``kv_pools``: the page pools stored as
    the kernel reads them, ``[pages, rows, 128]`` — all of them or none,
    by the kernel's rule on ``page_size``, kv heads, head size and
    element size (serving/paged_kv.py).

    Window-layer and expert-layer quantities (None or zero for a model
    with neither): ``window_pages_held_share`` — pages the slots' window
    rings held over the pages one shared table would have held for those
    layers, summed over the compiled ticks — and
    ``window_pages_reclaimed`` (ring entries reused);
    ``expert_router_scoring`` — what the expert layers' routers score
    by, "sigmoid" or "softmax" (None without expert layers) —,
    ``expert_pairs_per_token`` — (token, held expert) pairs the expert
    layers computed over the tokens they routed (``k * held / experts``
    for an even router) — and ``expert_gmm_kernel_traces`` /
    ``expert_gmm_xla_lane_traces``, the lanes the grouped expert product
    took when traced (process-wide, like the paged read's).

    Latent-store quantities (a model whose layers keep latent rows, zero
    otherwise): ``kv_latent_pools`` (one pool a latent layer) and
    ``kv_latent_row_bytes`` (a cached row's values times the pool's
    element size), and ``mla_decode_kernel_traces`` /
    ``mla_decode_xla_lane_traces``, the lanes the single-token latent
    read took when traced (process-wide, like the paged read's), and
    ``mla_decode_pages_per_step``, the pages a step of the kernel copied
    when it was last traced (0: never).

    ``kv_layers_without_store``: the model's layers that hold no page
    and no state row (a feed-forward part alone), and
    ``expert_gmm_relu2_kernel_traces`` / ``_xla_lane_traces``: of the
    grouped products, the relu² experts' up products by lane.

    Recurrent-state quantities (a model whose layers keep a per-slot
    state beside the pages, zero otherwise): ``state_bytes`` (the state
    arrays' size), ``state_resets`` and ``state_reset_ms_avg`` (an
    admission's in-place reset of its slot's rows), and
    ``state_rows_live_share`` — of the state rows the compiled ticks
    passed through the update, the share that belonged to a decoding
    request; ``state_heads_per_tile``, the heads a row of lanes holds in
    the lane-packed SSM state, and ``ssm_update_kernel_traces`` /
    ``ssm_update_xla_lane_traces``, the lanes the one-token state update
    took when traced (process-wide, like the paged read's).

    Speculative-decoding quantities (``speculation_k > 0``, zero
    otherwise): ``spec_windows`` (draft→verify→rollback iterations),
    ``spec_proposed_tokens``/``spec_accepted_tokens`` and the derived
    ``spec_acceptance_rate``, and per-phase latency
    ``spec_draft_ms_avg``/``spec_verify_ms_avg``/
    ``spec_rollback_ms_avg`` — all in the Prometheus exposition too.

    Migration quantities (prefill/decode disaggregation, zero without
    it): ``migrations`` (requests handed off and completed remotely),
    ``migration_pages_sent``/``migration_pages_received`` page-transfer
    volume, ``migration_resumed_requests`` (requests resumed here from
    adopted pages), ``migration_fallbacks`` (failed transfers that
    decoded locally instead), and ``migrate_ms_avg`` — all declared at
    engine start and in the Prometheus exposition, gated by
    tools/check_telemetry.py --migration, which also requires the
    router's per-role ``requests_routed_role{role=...}`` family.

    Multi-tenant LoRA quantities (``max_adapters > 0``, zero
    otherwise): ``adapters_loaded`` (hot-loads into pool slots),
    ``adapter_evictions`` (LRU evictions of idle adapters),
    ``adapter_load_ms_avg`` (mean hot-load wall time), and
    ``requests_routed_adapter`` — total admitted adapter requests, with
    the per-adapter ``requests_routed_adapter{adapter=...}`` series in
    the Prometheus exposition (gated by check_telemetry.py --lora).

    Fleet/router quantities (``serving.router.*``, zero without a
    router; per-replica ``requests_routed{replica=...}`` series live in
    the Prometheus exposition): ``router_requests_routed`` total,
    ``router_requests_shed`` (fail-fast admission rejections),
    ``router_failovers`` (replica deaths detected mid-request),
    ``router_resubmissions`` (re-sends under the same idempotent id),
    ``router_requests_recovered`` (requests that completed after >= 1
    resubmission), ``router_replicas_alive``/``router_replicas_lost``,
    and ``router_route_latency_ms_avg`` (submit → completion through
    the fleet).

    Gray-failure guardian quantities (ISSUE 17, zero with the guardian
    off): ``router_ejections``/``router_readmissions`` (reversible
    health-score ejections and canary readmissions),
    ``router_hedges``/``router_hedge_wins`` (hedged dispatch),
    ``router_breaker_open`` (circuit-breaker trips),
    ``router_retry_budget_exhausted`` (token-bucket refusals), and
    ``requests_cancelled`` (engine-side hedged-loser cancellations);
    the per-replica ``replica_health_score{replica=...}`` gauge rides
    the Prometheus exposition (gated by check_telemetry.py
    --gray-failure).
    """
    s = monitor.all_stats()

    def g(name, default=0):
        return s.get(PREFIX + name, default)

    def avg(name):
        count = g(name + ".count")
        return (g(name + ".sum") / count) if count else None

    busy_s = (g("prefill_ms.sum") + g("decode_ms.sum")
              + g("spec_draft_ms.sum") + g("spec_verify_ms.sum")
              + g("spec_rollback_ms.sum")) / 1e3
    tokens = g("tokens_generated")
    slot_steps = g("slot_steps")
    active_steps = g("slot_steps_active")
    spec_proposed = g("spec_proposed_tokens")
    return {
        "queue_depth": g("queue_depth"),
        "active_slots": g("active_slots"),
        "requests_submitted": g("requests_submitted"),
        "requests_completed": g("requests_completed"),
        "requests_rejected_queue_full": g("requests_rejected_queue_full"),
        "requests_evicted_deadline": g("requests_evicted_deadline"),
        "requests_cancelled_shutdown": g("requests_cancelled_shutdown"),
        "requests_cancelled_drain": g("requests_cancelled_drain"),
        "scheduler_restarts": g("scheduler_restarts"),
        "scheduler_stalls": g("scheduler_stalls"),
        "tokens_generated": tokens,
        "prefill_steps": g("prefill_steps"),
        "prefill_chunks": g("prefill_chunks"),
        "prefill_chunk_ms_avg": avg("prefill_chunk_ms"),
        "decode_steps": g("decode_steps"),
        "tick_ms_avg": avg("tick_ms"),
        "tick_compiled_hits": g("tick.compiled_hits"),
        "tick_fallbacks": g("tick.fallbacks"),
        "tick_overlapped": g("tick.overlapped"),
        "tick_drains": g("tick.drains"),
        "tick_overlap_share": (g("tick.overlapped")
                               / g("tick.compiled_hits"))
        if g("tick.compiled_hits") else None,
        "prefill_compiled_hits": g("prefill.compiled_hits"),
        "prefill_fallbacks": g("prefill.fallbacks"),
        "prefill_overlap_share": (g("prefill.overlapped")
                                  / g("prefill.compiled_hits"))
        if g("prefill.compiled_hits") else None,
        "state_bytes": g("state.bytes"),
        "state_heads_per_tile": g("state.heads_per_tile"),
        "ssm_update_kernel_traces": s.get("pallas.ssm_update.kernel", 0),
        "ssm_update_xla_lane_traces": s.get("pallas.ssm_update.xla_lane",
                                            0),
        "state_resets": g("state.resets"),
        "state_reset_ms_avg": avg("state.reset_ms"),
        "state_rows_live_share": (g("state.row_ticks_live")
                                  / g("state.row_ticks_total"))
        if g("state.row_ticks_total") else None,
        "kv_pages_streamed_per_tick": (g("kv.page_ticks_in_use")
                                       / g("tick.compiled_hits"))
        if g("tick.compiled_hits") else None,
        "kv_pages_spanned_per_tick": g("kv.pages_spanned"),
        "kv_pools": g("kv.pools"),
        "kv_pools_lane_dense": g("kv.pools_lane_dense"),
        "kv_latent_pools": g("kv.latent_pools"),
        "kv_latent_row_bytes": g("kv.latent_row_bytes"),
        "kv_layers_without_store": g("kv.layers_without_store"),
        "expert_gmm_relu2_kernel_traces": s.get(
            "pallas.expert_gmm.relu2.kernel", 0),
        "expert_gmm_relu2_xla_lane_traces": s.get(
            "pallas.expert_gmm.relu2.xla_lane", 0),
        "mla_decode_kernel_traces": s.get("pallas.mla_decode.kernel", 0),
        "mla_decode_xla_lane_traces": s.get("pallas.mla_decode.xla_lane",
                                            0),
        "mla_decode_pages_per_step": s.get(
            "pallas.mla_decode.pages_per_step", 0),
        "expert_router_scoring": "+".join(
            n for n in SCORINGS
            if g(f"moe.router_layers.{n}")) or None,
        "expert_pairs_per_token": (g("moe.pairs_local")
                                   / g("moe.tokens_routed"))
        if g("moe.tokens_routed") else None,
        "expert_gmm_kernel_traces": s.get("pallas.expert_gmm.kernel", 0),
        "expert_gmm_xla_lane_traces": s.get("pallas.expert_gmm.xla_lane",
                                            0),
        "window_pages_held_share": (g("kv.window.page_ticks_held")
                                    / g("kv.window.page_ticks_full_equiv"))
        if g("kv.window.page_ticks_full_equiv") else None,
        "window_pages_reclaimed": g("kv.window.pages_reclaimed"),
        "paged_decode_kernel_traces": s.get(
            "pallas.paged_decode.kernel", 0),
        "paged_decode_xla_lane_traces": s.get(
            "pallas.paged_decode.xla_lane", 0),
        "kv_pages_in_use": g("kv_pages_in_use"),
        "kv_pages_free": g("kv_pages_free"),
        "kv_pages_peak": g("kv_pages_peak"),
        "spec_windows": g("spec_windows"),
        "spec_proposed_tokens": spec_proposed,
        "spec_accepted_tokens": g("spec_accepted_tokens"),
        "spec_acceptance_rate": (g("spec_accepted_tokens")
                                 / spec_proposed) if spec_proposed
        else None,
        "spec_draft_ms_avg": avg("spec_draft_ms"),
        "spec_verify_ms_avg": avg("spec_verify_ms"),
        "spec_rollback_ms_avg": avg("spec_rollback_ms"),
        "migrations": g("migration.migrations"),
        "migration_pages_sent": g("migration.pages_sent"),
        "migration_pages_received": g("migration.pages_received"),
        "migration_resumed_requests": g("migration.resumed_requests"),
        "migration_fallbacks": g("migration.fallbacks"),
        "migrate_ms_avg": avg("migration.migrate_ms"),
        "prefix_cache_hits": g("prefix_cache_hits"),
        "prefix_cache_misses": g("prefix_cache_misses"),
        "prefix_cache_evictions": g("prefix_cache_evictions"),
        "prefix_cache_hit_tokens": g("prefix_cache_hit_tokens"),
        "max_active_slots": g("max_active_slots"),
        "adapters_loaded": g("adapter.adapters_loaded"),
        "adapter_evictions": g("adapter.adapter_evictions"),
        "adapter_load_ms_avg": avg("adapter.adapter_load_ms"),
        "requests_routed_adapter": g(
            "adapter.requests_routed_adapter_total"),
        "ttft_ms_avg": avg("ttft_ms"),
        "per_token_ms_avg": avg("decode_ms"),
        "slot_occupancy": (active_steps / slot_steps) if slot_steps
        else 0.0,
        "tokens_per_sec": (tokens / busy_s) if busy_s > 0 else 0.0,
        "router_requests_routed": g("router.requests_routed_total"),
        "router_requests_shed": g("router.requests_shed"),
        "router_failovers": g("router.failovers"),
        "router_resubmissions": g("router.resubmissions"),
        "router_requests_recovered": g("router.requests_recovered"),
        "router_replicas_alive": g("router.replicas_alive"),
        "router_replicas_lost": g("router.replicas_lost"),
        "router_route_latency_ms_avg": avg("router.route_latency_ms"),
        "router_ejections": g("router.ejections"),
        "router_readmissions": g("router.readmissions"),
        "router_hedges": g("router.hedges"),
        "router_hedge_wins": g("router.hedge_wins"),
        "router_breaker_open": g("router.breaker_open"),
        "router_retry_budget_exhausted": g(
            "router.retry_budget_exhausted"),
        "requests_cancelled": g("requests_cancelled"),
        "trace_spans": g("trace.spans"),
        "trace_spans_dropped": g("trace.spans_dropped"),
        "trace_decisions": g("trace.decisions"),
        "trace_decisions_kept": g("trace.decisions_kept"),
        "trace_spools": g("trace.spools"),
    }
