"""Unified observability plane: span tracing, a typed metrics registry,
and exporters (Chrome/Perfetto trace JSON, Prometheus text, JSONL).

Design contract (pinned by tests/test_obs.py):

  - ONE ``Tracer`` protocol serves BOTH execution planes, each on one
    clock: the cluster plane stamps the wall clock (``wall_time``, the
    profiler-aligned ``perf_counter``; its ``scope`` spans also enter a
    profiler annotation), the sim plane its discrete-event virtual
    time. Exporters never care which plane produced the trace.
  - ``NULL_TRACER`` is the zero-cost default: every hot path guards on
    ``tracer.enabled`` before building span arguments, and the no-op
    methods themselves allocate nothing.
  - Tracing must be *bitwise invisible*: token streams with tracing on
    vs off are identical on both planes.

This package imports no jax and nothing from ``repro.serving`` — the
serving layers depend on it, never the reverse.
"""
from repro.obs.clock import wall_time
from repro.obs.export import (to_jsonl, to_perfetto, to_prometheus,
                              write_perfetto)
from repro.obs.hub import Observability, ObservabilityHub
from repro.obs.registry import (Counter, Gauge, Histogram, MetricsRegistry)
from repro.obs.trace import (NULL_TRACER, NullTracer, Span, TimelineTracer,
                             Tracer)

__all__ = [
    "Tracer", "NullTracer", "NULL_TRACER", "TimelineTracer", "Span",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "ObservabilityHub", "Observability",
    "to_perfetto", "to_prometheus", "to_jsonl", "write_perfetto",
    "wall_time",
]
