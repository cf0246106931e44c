"""Observability plane: structured tracing, metrics, and trace exporters.

``Tracer`` collects typed lifecycle events from every layer (runtime,
coordinators, gossip, serve pool, execution backends) with logical *and*
wall timestamps, and spans of host time inside the serving path (fleet
waves, runtime ticks, engine step phases, request waits);
``MetricsRegistry`` rolls the events into the deterministic snapshot that
becomes ``RunReport.telemetry``; ``obs.export`` writes Perfetto
``trace_event`` JSON and JSONL streams.  See each module's
docstring for the contracts (zero-overhead off path, dual clocks,
deterministic snapshots).
"""

from .export import to_perfetto, write_jsonl, write_trace
from .metrics import MetricsRegistry
from .trace import EVENT_KINDS, NO_SPAN, EventTracer, Span, TraceEvent, Tracer

__all__ = [
    "EVENT_KINDS", "EventTracer", "MetricsRegistry", "NO_SPAN", "Span",
    "TraceEvent", "Tracer", "to_perfetto", "write_jsonl", "write_trace",
]
