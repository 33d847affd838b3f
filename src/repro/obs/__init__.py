"""Unified serving observability: metrics registry, per-request span
recorder, Chrome-trace exporter.  See README.md in this directory for
the metric catalog and the trace event schema.

Zero dependencies (no jax/numpy) and host-scalars-only by design: the
tick loop records here without ever forcing a device->host sync.
"""
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.trace import (TERMINAL_EVENTS, TraceRecorder, chrome_trace,
                             save_chrome_trace, span_of)

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "Observability", "TraceRecorder", "TERMINAL_EVENTS",
           "chrome_trace", "save_chrome_trace", "span_of"]


class Observability:
    """The pair a `ServingPipeline` records into: a metrics registry
    (always present; pass ``MetricsRegistry(enabled=False)`` for a
    no-op one) and an optional trace recorder (``None`` = tracing off,
    which costs the tick loop nothing)."""

    def __init__(self, metrics: "MetricsRegistry" = None,
                 trace: "TraceRecorder" = None) -> None:
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.trace = trace

    @classmethod
    def with_trace(cls, max_events: int = None,
                   annotate=None) -> "Observability":
        kw = {} if max_events is None else {"max_events": max_events}
        return cls(trace=TraceRecorder(annotate=annotate, **kw))
