"""Dependency-free observability: metrics registry, request trace contexts
(whose stages are also spans of the JAX profiler's trace), tail-sampled
flight recorder, SLO monitor, JSONL event log, and a stdlib HTTP
exposition/debug server.

Everything in this package is importable without JAX so the hot paths can
instrument themselves unconditionally; the cost of a disabled registry
(`NULL_REGISTRY`) is a no-op method call.  See `docs/observability.md`
for the metric catalog and trace-context model.
"""

from repro.obs.events import (
    EventLog,
    emit,
    get_event_log,
    read_events,
    set_event_log,
)
from repro.obs.metrics import (
    NULL_REGISTRY,
    Buckets,
    Counter,
    Gauge,
    Histogram,
    LabelCardinalityError,
    MetricsRegistry,
    get_registry,
    merge_snapshots,
    parse_exposition,
    set_registry,
)
from repro.obs.recorder import (
    FlightRecorder,
    get_recorder,
    new_batch_id,
    set_recorder,
)
from repro.obs.server import MetricsServer, ReadyState
from repro.obs.slo import SLOMonitor, SLOSpec
from repro.obs.trace import TraceContext, new_trace_id

__all__ = [
    "Buckets",
    "Counter",
    "EventLog",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "LabelCardinalityError",
    "MetricsRegistry",
    "MetricsServer",
    "NULL_REGISTRY",
    "ReadyState",
    "SLOMonitor",
    "SLOSpec",
    "TraceContext",
    "emit",
    "get_event_log",
    "get_recorder",
    "get_registry",
    "merge_snapshots",
    "new_batch_id",
    "new_trace_id",
    "parse_exposition",
    "read_events",
    "set_event_log",
    "set_recorder",
    "set_registry",
]
