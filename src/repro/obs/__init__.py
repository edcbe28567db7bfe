"""repro.obs — the reliability flight recorder (DESIGN.md §17).

One deterministic, causally-ordered record of what the reliability stack
did and why: typed trace events on a step-clock (never wall-clock), a
metrics registry fed from the existing FaultStats containers, JSONL /
Chrome-trace / markdown exporters, and host spans on the profiler's clock
(``obs.profile``) kept strictly outside the deterministic event log.

Quick use::

    from repro.obs import TraceRecorder
    rec = TraceRecorder()
    eng = ServingEngine(cfg, params, rel, recorder=rec)
    eng.serve(requests, ...)
    rec.to_jsonl("trace.jsonl")
    rec.to_chrome_trace("trace.json")    # load in Perfetto
    print(rec.summary_markdown())        # or: python -m repro.obs.report
"""

from repro.obs.events import (
    ENVELOPE_FIELDS,
    EVENT_KINDS,
    EventSchemaError,
    validate_event,
    validate_events,
)
from repro.obs.export import (
    read_jsonl,
    summary_markdown,
    to_chrome_trace,
    to_jsonl,
)
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.recorder import TraceRecorder

__all__ = [
    "ENVELOPE_FIELDS",
    "EVENT_KINDS",
    "Counter",
    "EventSchemaError",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "TraceRecorder",
    "read_jsonl",
    "summary_markdown",
    "to_chrome_trace",
    "to_jsonl",
    "validate_event",
    "validate_events",
]
