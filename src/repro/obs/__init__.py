"""Observability for the serving engine: tracing, metrics, exporters.

- ``trace``: the engine's always-on :class:`PhaseRecorder` (phase
  clock, span log, profiler annotations) and the opt-in ring-buffered
  :class:`Tracer` (strictly no-op when disabled), :class:`TraceConfig`.
- ``metrics``: :class:`MetricsRegistry` of counters/gauges/histograms
  with Prometheus text exposition.
- ``derive``: typed :class:`TrafficSnapshot` for the adaptive
  controller, and the fold of ``stats()`` onto the registry.
- ``export``: Chrome/Perfetto trace_event JSON writer + validator,
  Prometheus file writer.
"""
from .derive import TrafficSnapshot, fold_engine_metrics
from .metrics import (
    TPOT_BUCKETS,
    TTFT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from .trace import PHASE_BUCKETS, PhaseRecorder, TraceConfig, Tracer
from .export import to_perfetto, validate_perfetto, write_metrics, write_trace

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "PHASE_BUCKETS",
    "PhaseRecorder",
    "TPOT_BUCKETS",
    "TTFT_BUCKETS",
    "TraceConfig",
    "Tracer",
    "TrafficSnapshot",
    "fold_engine_metrics",
    "to_perfetto",
    "validate_perfetto",
    "write_metrics",
    "write_trace",
]
