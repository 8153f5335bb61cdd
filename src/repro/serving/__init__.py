"""Continuous-batching serving engine (paged KV cache, mid-stream joins).

Layering::

    traffic      arrival processes (Poisson / batch, shared-prefix pools)
                 -> Request lists
    request      Request / RequestResult accounting
    paged_kv     PagedKVCache — ref-counted page pool + prefix trie +
                 copy-on-write sharing + free list
    scheduler    RequestQueue + Scheduler — ragged requests -> fixed slots
                 (bounded head-of-line skip-ahead, lazy/reserve admission)
    engine       ServeEngine — prefill-on-join (suffix-only on prefix
                 hits), fused masked decode chunks, preemption/requeue on
                 page pressure, free-on-finish, per-request latency +
                 J/token accounting; chaos injection, snapshot/restore
                 (EngineCrash recovery), graceful degradation under
                 emergency caps
    spans        SpanRecorder — the engine's phases and counts on
                 perf_counter, annotated on jax.profiler traces

See docs/serving_engine.md, docs/prefix_cache.md and
docs/fault_tolerance.md.
"""
from repro.serving.engine import (ChunkStats, EnergyAwareAdmission,
                                  EngineConfig, EngineCrash, EngineReport,
                                  ServeEngine)
from repro.serving.paged_kv import CopySpec, PagedKVCache
from repro.serving.request import Request, RequestResult
from repro.serving.scheduler import RequestQueue, Scheduler
from repro.serving.spans import Span, SpanRecorder
from repro.serving.traffic import batch_trace, poisson_trace

__all__ = [
    "ChunkStats", "CopySpec", "EnergyAwareAdmission", "EngineConfig",
    "EngineCrash", "EngineReport", "PagedKVCache", "Request",
    "RequestQueue", "RequestResult", "Scheduler", "ServeEngine", "Span",
    "SpanRecorder", "batch_trace", "poisson_trace",
]
