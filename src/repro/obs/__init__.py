"""repro.obs — live serving observability.

Three pieces, all import-light (stdlib only at import time):

* :mod:`repro.obs.metrics` — the ``MetricsSink`` protocol (counters,
  gauges, histograms with explicit bucket bounds) with in-memory, JSONL,
  and logging implementations, plus ``MetricsRegistry``, an aggregating
  registry that is lock-free on the observation hot path.
* :mod:`repro.obs.tracing` — hierarchical ``Span``s on the monotonic
  clock with per-request trace ids, emitted as structured events
  covering submit → admission → collate → bucket dispatch → per-chunk
  solve → artifact fetch (plus the fault events: retries, ladder level,
  quarantine, deadline cuts, degraded answers); ``region`` marks the
  solve path's layers, also on the profiler's host timeline.
* :mod:`repro.obs.profiler` — an opt-in ``jax.profiler`` trace-capture
  hook around a named dispatch.

``now()`` is the one monotonic clock shared by spans, deadlines, and
wait/solve stats across ``serve/`` and the chunked drivers.
"""
from . import profiler
from .metrics import (
    Counter,
    Gauge,
    Histogram,
    History,
    InMemorySink,
    JSONLSink,
    LoggingSink,
    MetricsRegistry,
    MetricsSink,
    NullSink,
    jsonable,
    now,
)
from .tracing import Span, Tracer, new_id, region, span_tree

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "History",
    "InMemorySink",
    "JSONLSink",
    "LoggingSink",
    "MetricsRegistry",
    "MetricsSink",
    "NullSink",
    "Span",
    "Tracer",
    "jsonable",
    "new_id",
    "now",
    "profiler",
    "region",
    "span_tree",
]
