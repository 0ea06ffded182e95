"""Observability for the SliceMoE serving stack.

* :mod:`repro.obs.timeline` — charge-path event tracing and
  Chrome-trace/Perfetto export (attach with
  ``engine.attach_tracer(TimelineTracer())``);
* :mod:`repro.obs.metrics` — counter/gauge/histogram registry with
  JSONL time series + Prometheus text exposition, sampled per decode
  step via ``scheduler.attach_metrics(MetricsRegistry())``;
* :mod:`repro.obs.report` — stall/overlap/waste analysis of an
  exported trace (CLI: ``scripts/trace_report.py``).
* :mod:`repro.obs.hosttrace` — wall-clock host spans and counters
  (``engine.host_trace``; profiler annotations, a bounded ring,
  collector pauses, compiles by span).

See docs/observability.md for the trace schema, span model and
metrics catalog.
"""

from repro.obs.timeline import (TimelineTracer, TraceEvent, chrome_trace,
                                events_equal, export_chrome_trace,
                                first_divergence)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               MetricsSampler)
from repro.obs.report import format_trace_report, load_trace, trace_report

__all__ = [
    "TimelineTracer", "TraceEvent", "chrome_trace", "export_chrome_trace",
    "events_equal", "first_divergence",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "MetricsSampler",
    "trace_report", "format_trace_report", "load_trace",
]
