"""Observability subsystem: metrics registry, span tracing, exporters, and
the ``BENCH_*.json`` perf-trajectory recorder.

The layer every perf/robustness PR reports through:

* :mod:`repro.obs.metrics` -- thread-aware registry of counters / gauges /
  histograms with a lock-free fast path and a consistent ``snapshot()``;
* :mod:`repro.obs.trace`   -- nestable span timers (admission and its
  joins, compactions and fresh groups, group steps, AOT compiles, decodes,
  the driver's inbox and hand-back) with optional
  ``jax.profiler.TraceAnnotation`` pass-through so spans land in XLA
  profiles;
* :mod:`repro.obs.export`  -- Prometheus-text and NDJSON renderers over a
  registry snapshot;
* :mod:`repro.obs.bench`   -- ``BENCH_*.json`` records (run metadata +
  named metric series) plus the ``compare()`` ratchet that fails on
  regression beyond a per-metric tolerance.

See ``docs/observability.md`` for the metric catalog, span hierarchy,
BENCH schema and ratchet workflow.
"""
from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .trace import Tracer, NULL_TRACER

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "Tracer", "NULL_TRACER"]
