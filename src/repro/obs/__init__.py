"""Telemetry subsystem: span tracing, metrics, structured run reports
(DESIGN.md §12).

Everything here is host-side Python on the injectable-clock convention;
no instrument traces into a jaxpr — traced-vs-untraced runs are
jaxpr-identical (tests/test_obs.py).
"""
from repro.obs.metrics import (Counter, CounterGroup, DEPTH_EDGES, Gauge,
                               Histogram, LATENCY_EDGES, MetricsRegistry,
                               default_registry, exp_edges)
from repro.obs.report import (RunReport, exchange_section,
                              totals_from_trace)
from repro.obs.trace import (Event, Span, Trace, current_trace,
                             maybe_span, tracing)

__all__ = [
    "Counter", "CounterGroup", "DEPTH_EDGES", "Event", "Gauge",
    "Histogram", "LATENCY_EDGES", "MetricsRegistry", "RunReport", "Span",
    "Trace", "current_trace", "default_registry", "exchange_section",
    "exp_edges", "maybe_span", "totals_from_trace", "tracing",
]
