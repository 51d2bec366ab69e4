"""repro.obs — unified telemetry for the reconciliation stack.

One typed metrics registry (``Recorder`` + ``SCHEMA``, DESIGN.md §14)
absorbing every layer's ad-hoc stats ledger behind derived snapshots, and
one zero-dep span tracer (``Tracer``/``NULL_TRACER``) exporting JSONL and
Chrome-trace timelines of the whole serving stack, installed once per
process with ``set_tracer``/``use_tracer``.
"""
from repro.obs.metrics import SCHEMA, MetricSpec, MetricsError, Recorder
from repro.obs.trace import (
    NULL_TRACER,
    NullTracer,
    Tracer,
    current_tracer,
    load_events,
    set_tracer,
    use_tracer,
)

__all__ = [
    "SCHEMA",
    "MetricSpec",
    "MetricsError",
    "Recorder",
    "Tracer",
    "NullTracer",
    "NULL_TRACER",
    "load_events",
    "set_tracer",
    "current_tracer",
    "use_tracer",
]
