"""Serving observability: tracing, metrics, events, SLOs.

Spans, the compile counter and the metric registry are DESIGN.md §12;
the sampled event log and SLO tracking are §13.

Zero-dependency by design -- the serve stack imports this package
unconditionally, so it must cost nothing when disarmed: ``span()``/
``trace_point()`` pay one module-global ``None`` check (the
``fault_point`` contract), and registry-backed counters are plain
attribute adds.
"""

from .events import EventLog
from .metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricRegistry,
    DEFAULT_LATENCY_BUCKETS,
)
from .slo import SLObjective, SLOTracker, slo_status
from .stats import RegistryBackedStats
from .trace import (
    SPANS,
    Span,
    SpanStat,
    Tracer,
    set_tracer,
    span,
    trace_point,
    tracer_armed,
)

__all__ = [
    "RegistryBackedStats",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricRegistry",
    "DEFAULT_LATENCY_BUCKETS",
    "EventLog",
    "SLObjective",
    "SLOTracker",
    "slo_status",
    "SPANS",
    "Span",
    "SpanStat",
    "Tracer",
    "set_tracer",
    "span",
    "trace_point",
    "tracer_armed",
]
