"""Observability for the serving stack: per-query span trees and batch
phases on the profiler's clock (``tracer``), a lock-protected metrics
registry with streaming histograms (``metrics``), and Prometheus/JSON
exposition (``export``). It imports nothing from the rest of ``repro``, so
every layer (core, runtime, serving, benches) can instrument itself without
cycles; JAX's profiler is its one import outside the standard library, and
``tracer`` guards it. Naming and span taxonomy: DESIGN.md §12.
"""

from repro.obs.metrics import (
    DEFAULT_TIME_BUCKETS,
    GLOBAL,
    Counter,
    Gauge,
    Histogram,
    HistogramSnapshot,
    MetricsRegistry,
    merged_snapshot,
    time_buckets,
)
from repro.obs.tracer import Span, TraceContext, Tracer, phase
from repro.obs.export import write_metrics_snapshot

__all__ = [
    "DEFAULT_TIME_BUCKETS",
    "GLOBAL",
    "Counter",
    "Gauge",
    "Histogram",
    "HistogramSnapshot",
    "MetricsRegistry",
    "merged_snapshot",
    "time_buckets",
    "Span",
    "TraceContext",
    "Tracer",
    "phase",
    "write_metrics_snapshot",
]
