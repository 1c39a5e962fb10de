"""repro.obs — observability for the TC-MIS stack (DESIGN.md §14, §17).

Four legs, importable independently:

* `rounds`   — on-device round-telemetry buffer layout + host `RoundTrace`
               (numpy-only; `core.engine` imports its column constants)
* `trace`    — `Trace` / `trace_span` span tracing + JSONL export
* `metrics`  — `MetricsRegistry` counters/gauges/fixed-bucket histograms
               (p50/p95/p99) + the process-wide `REGISTRY`
* `promtext` — Prometheus text exposition over a metrics snapshot

`python -m repro.obs report trace.jsonl` renders the JSONL stream.
"""
from .metrics import (
    DEFAULT_BUCKETS,
    QUANTILES,
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from .promtext import to_promtext, write_promtext
from .rounds import (
    COL_ALIVE,
    COL_FRONTIER,
    COL_SELECTED,
    COL_TILES_SKIPPED,
    COLUMN_NAMES,
    TELEMETRY_COLS,
    TELEMETRY_FILL,
    RoundTrace,
)
from .trace import JsonlWriter, Span, Trace, trace_span

__all__ = [
    "DEFAULT_BUCKETS",
    "QUANTILES",
    "REGISTRY",
    "to_promtext",
    "write_promtext",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "COL_ALIVE",
    "COL_FRONTIER",
    "COL_SELECTED",
    "COL_TILES_SKIPPED",
    "COLUMN_NAMES",
    "TELEMETRY_COLS",
    "TELEMETRY_FILL",
    "RoundTrace",
    "JsonlWriter",
    "Span",
    "Trace",
    "trace_span",
]
