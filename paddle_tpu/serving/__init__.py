"""`paddle_tpu.serving` — continuous-batching inference engine + fleet.

The single-shot entry points (`models.generation.generate`,
`inference.Predictor.run`) decode one fixed batch to completion.  This
package turns the compile-once decode step into a multi-tenant server:
a paged KV cache with shared-prefix reuse and chunked prefill
(`paged_kv`), a background scheduler with Orca-style continuous
batching (`engine`), admission control with bounded queueing and
per-request deadlines (`api`), serving metrics through `utils.monitor` (`stats`), and —
scaling past one process — replicated engines behind a drain-aware,
session-affine router that loses zero requests when a replica dies
(`router`, `fleet`).  See docs/SERVING.md.
"""
from __future__ import annotations

from .adapters import AdapterPool  # noqa: F401
from .api import (  # noqa: F401
    AdapterConfigError, DeadlineExceededError, EngineShutdownError,
    LatentStoreError, NoReplicaError, PageMigrationError, QueueFullError,
    RecurrentStateError, RequestCancelledError, RequestOutput,
    SamplingParams,
    SchedulerStallError, ServingConfig, ServingError,
    UnknownAdapterError, WindowLayerError,
)
from .compiled_tick import (  # noqa: F401
    CompiledServingTick, TickFallbackWarning,
)
from .engine import Engine  # noqa: F401
from .fleet import ReplicaConfig, ReplicaServer, ServingFleet  # noqa: F401
from .paged_kv import PagedKVCache, PrefixTree  # noqa: F401
from .router import HashRing, RouterConfig, ServingRouter  # noqa: F401
from .stats import (  # noqa: F401
    reset_router_stats, reset_serving_stats, serving_stats,
)

__all__ = [
    "Engine", "ServingConfig", "SamplingParams", "RequestOutput",
    "CompiledServingTick", "TickFallbackWarning",
    "PagedKVCache", "PrefixTree", "ServingError",
    "QueueFullError", "DeadlineExceededError", "EngineShutdownError",
    "SchedulerStallError", "NoReplicaError", "PageMigrationError",
    "RequestCancelledError", "RecurrentStateError", "WindowLayerError",
    "LatentStoreError",
    "AdapterConfigError", "UnknownAdapterError", "AdapterPool",
    "serving_stats", "reset_serving_stats", "reset_router_stats",
    "ServingRouter", "RouterConfig", "HashRing", "ServingFleet",
    "ReplicaServer", "ReplicaConfig",
]
