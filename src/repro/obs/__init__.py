"""Serving observability (DESIGN.md §12): telemetry + metrics.

Everything in this package is **host-side observation** — no module here
may dispatch device computation, insert a ``block_until_ready`` the engine
did not already perform, or feed a value back into scheduling.  That is
what makes the load-bearing contract checkable: serving with telemetry
enabled is token-bit-identical to serving with it disabled
(tests/test_engine_differential.py), so operators never trade correctness
evidence for visibility.
"""
from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .telemetry import (BoundedLog, EVENT_SCHEMA, EventTrace, Percentiles,
                        RequestRecord, SCHEMA_VERSION, Telemetry, TickProfiler,
                        span)

__all__ = [
    "BoundedLog", "Counter", "EVENT_SCHEMA", "EventTrace", "Gauge",
    "Histogram", "MetricsRegistry", "Percentiles", "RequestRecord",
    "SCHEMA_VERSION", "Telemetry", "TickProfiler", "span",
]
