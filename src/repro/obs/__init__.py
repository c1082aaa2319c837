"""Unified observability layer (DESIGN.md §14).

Four pieces, one import point:

* :mod:`repro.obs.trace` — nested wall-clock span tracing over the
  telemetry JSONL stream, optionally on a ``jax.profiler`` trace's
  clock, and the named scopes of the serve program;
* :mod:`repro.obs.metrics` — typed counters/gauges/mergeable
  histograms with per-deadline-class latency percentiles;
* :mod:`repro.obs.schema` — the central event-schema registry every
  ``Telemetry.event`` emitter declares through (validated by tier-1
  tests, rendered into DESIGN.md §8);
* :mod:`repro.obs.profile` — XLA chrome-trace capture summarizer
  (per-phase top-K op attribution and summary diffs).
"""
from repro.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    LATENCY_BUCKETS,
    MetricsRegistry,
    REGISTRY,
)
from repro.obs.schema import (  # noqa: F401
    EVENT_SCHEMAS,
    EventSchema,
    render_markdown,
    validate_event,
    validate_events,
)
from repro.obs.trace import (  # noqa: F401
    NULL_TRACER,
    SCOPES,
    NullTracer,
    Span,
    SpanTracer,
    scope_map,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "LATENCY_BUCKETS",
    "MetricsRegistry", "REGISTRY",
    "EVENT_SCHEMAS", "EventSchema", "render_markdown",
    "validate_event", "validate_events",
    "NULL_TRACER", "NullTracer", "SCOPES", "Span", "SpanTracer",
    "scope_map",
]
