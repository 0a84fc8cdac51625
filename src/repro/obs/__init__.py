"""repro.obs — stage-level tracing + metrics for the bridges engine.

Three pieces (DESIGN.md §Observability):

* **Span tracer** (``tracer.py``) — nested wall-clock spans with
  device-sync boundaries, and named counts the program reports (e.g.
  ``certificate/rounds``); Chrome-trace JSON + per-stage rollups. Off by
  default: the module-level tracer is the no-op ``NULL_TRACER`` until
  ``enable_tracing()``; instrumented code always goes through
  ``get_tracer()`` so flipping the switch needs no re-plumbing (and adds
  no retraces — spans wrap host dispatch only). ``disable_tracing()``
  hands back the tracer it removes and ``last_tracer()`` keeps the most
  recent one, so spans and counts outlive the session.

* **Metrics registry** (``metrics.py``) — counters, gauges, fixed-bucket
  latency histograms with p50/p95/p99, one ``snapshot()`` dict. A
  process-global registry backs the runtime substrate (watchdog
  heartbeats, failure-injection counters); components that want isolation
  (tests, per-engine serving stats) construct their own.

* **Profiler hooks** (``profile.py``) — the opt-in ``jax.profiler.trace``
  capture. Every live span is also a profiler annotation, so the capture
  holds the engine's spans as host events on the profiler's clock, and
  the device operations carry the matching ``jax.named_scope`` labels
  (stages and their phases) in their HLO metadata.
"""
from __future__ import annotations

from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_latency_buckets,
)
from repro.obs.profile import profiler_trace
from repro.obs.tracer import (
    NULL_TRACER,
    STAGE_PREFIXES,
    NullTracer,
    Span,
    Tracer,
)

_TRACER: Tracer | NullTracer = NULL_TRACER
_LAST_TRACER: Tracer | None = None
_METRICS = MetricsRegistry()


def get_tracer() -> Tracer | NullTracer:
    """The process-current tracer. Instrumented code calls this at use
    time (never caches it), so enabling tracing mid-process takes effect
    everywhere immediately."""
    return _TRACER


def enable_tracing(tracer: Tracer | None = None) -> Tracer:
    """Install (and return) a live tracer as the process tracer."""
    global _TRACER, _LAST_TRACER
    _TRACER = _LAST_TRACER = tracer if tracer is not None else Tracer()
    return _TRACER


def disable_tracing() -> Tracer | NullTracer:
    """Back to the no-op tracer; returns the tracer it removes
    (``NULL_TRACER`` where tracing was already off)."""
    global _TRACER
    removed, _TRACER = _TRACER, NULL_TRACER
    return removed


def last_tracer() -> Tracer | None:
    """The most recently enabled live tracer (current or already
    disabled), or None if tracing was never enabled in this process."""
    return _LAST_TRACER


def get_metrics() -> MetricsRegistry:
    """The process-global metrics registry (watchdog heartbeats, failure
    counters, anything fleet-level)."""
    return _METRICS


def snapshot() -> dict:
    """One-call rollup of the global metrics registry."""
    return _METRICS.snapshot()


__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NullTracer",
    "NULL_TRACER",
    "Span",
    "STAGE_PREFIXES",
    "Tracer",
    "default_latency_buckets",
    "disable_tracing",
    "enable_tracing",
    "get_metrics",
    "get_tracer",
    "last_tracer",
    "profiler_trace",
    "snapshot",
]
