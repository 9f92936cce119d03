"""Observability: the metrics registry and span tracing.

The reference delegated all runtime visibility to Spark's UI and task
metrics; this package is the standalone replacement — counters/gauges/
histograms (:mod:`.metrics`) and nested spans with a JSONL event log and
Perfetto forwarding (:mod:`.tracing`). The engine, frame, serving,
failure, and packer layers publish into the default registry at module
import; ``ScoringServer`` exports it as a Prometheus scrape on its Arrow
port (``GET /metrics``). See ``docs/observability.md`` for the metric
catalog and span conventions.

Kill switch: ``TFT_OBS=0`` in the environment, or
``tft.utils.set_config(observability=False)``.
"""

from .metrics import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    counter,
    enabled,
    gauge,
    histogram,
    registry,
    render_prometheus,
    snapshot,
)
from .tracing import (
    Span,
    SpanChain,
    TraceContext,
    current_span,
    current_trace,
    event,
    new_trace,
    set_annotations,
    set_trace_sink,
    span,
    trace_sink,
    use_trace,
)
from . import flight
from . import programs
from . import slo
from . import timeseries
from . import export
from . import aggregate
from . import drift
from . import requests

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "DEFAULT_BUCKETS",
    "counter",
    "gauge",
    "histogram",
    "registry",
    "snapshot",
    "render_prometheus",
    "enabled",
    "Span",
    "SpanChain",
    "TraceContext",
    "span",
    "event",
    "current_span",
    "current_trace",
    "new_trace",
    "use_trace",
    "set_annotations",
    "set_trace_sink",
    "trace_sink",
    "flight",
    "programs",
    "slo",
    "timeseries",
    "export",
    "aggregate",
    "drift",
    "requests",
]
