"""Online serving layer: a multi-index front door over micro-batching
execution cores.

* :class:`~repro.serve.service.RetrievalService` — the front door: a
  registry of named, versioned indexes (in-memory or lazily loaded from
  saved artifacts), an async ``query() → QueryHandle`` API served by a
  background drain-loop thread with admission control, and zero-downtime
  ``stage`` / canary / ``promote`` / ``rollback`` hot-swap.
* :class:`~repro.serve.engine.ServeEngine` — the per-index execution
  core: ``submit``/``drain`` request queue dispatching micro-batches to
  any index (dense / compressed / IVF / sharded), latency percentiles,
  per-request ``k`` / ``nprobe`` overrides.
* :class:`~repro.serve.batcher.MicroBatcher` — coalesces queued requests
  into padded micro-batches (bucketed row counts bound jit recompiles).
* :class:`~repro.serve.shadow.ShadowScorer` — online quality validation
  against a reference index on a sampled fraction of traffic (also the
  hot-swap canary mechanism).
* :class:`~repro.serve.metrics.LatencyStats` — streaming latency
  percentile tracking, mergeable across engines for the service snapshot.
* :class:`~repro.serve.limits.RateLimiter` — per-index token-bucket rate
  limiting with priority lanes, consulted before admission
  (:class:`~repro.serve.service.RateLimited` is the shed signal).
* :class:`~repro.serve.cache.ResultCache` — hot-query result cache keyed
  on (index, epoch, version, k, nprobe, query-hash); epoch-keyed
  invalidation on live updates / compaction / promote.
* :class:`~repro.serve.batcher.AdaptiveBatcher` — queue-depth-driven
  micro-batch sizing (small batches at low load, wide at saturation).
* :class:`~repro.serve.trace.Recorder` — spans (``repro.*``, on the
  profiler's clock) and per-batch / per-request records of the drain
  path, plus garbage-collection pauses; one per process
  (:func:`~repro.serve.trace.default_recorder`).
"""

from repro.serve.batcher import AdaptiveBatcher, MicroBatch, MicroBatcher
from repro.serve.cache import ResultCache
from repro.serve.engine import ServeEngine, ServeResult
from repro.serve.limits import RateLimiter, TokenBucket
from repro.serve.metrics import LatencyStats
from repro.serve.router import (IndexEntry, IndexRegistry, IndexVersion,
                                load_engine)
from repro.serve.service import (CanaryFailed, QueryHandle, QueryOptions,
                                 QueueFull, RateLimited, RetrievalService,
                                 ServiceClosed)
from repro.serve.shadow import ShadowScorer
from repro.serve.stats import (IndexStats, ServiceStats, ShardStats,
                               VersionStats)
from repro.serve.trace import Recorder, default_recorder

__all__ = [
    "AdaptiveBatcher", "MicroBatch", "MicroBatcher",
    "ServeEngine", "ServeResult", "load_engine",
    "LatencyStats", "ShadowScorer",
    "RateLimiter", "TokenBucket", "ResultCache",
    "IndexEntry", "IndexRegistry", "IndexVersion",
    "RetrievalService", "QueryOptions", "QueryHandle",
    "QueueFull", "RateLimited", "CanaryFailed", "ServiceClosed",
    "ServiceStats", "IndexStats", "VersionStats", "ShardStats",
    "Recorder", "default_recorder",
]
