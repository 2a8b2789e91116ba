"""Request-to-kernel observability: span tracing, metrics, compile
ledger (DESIGN.md §8).

Three independent parts with one shared rule — no module here reads a
wall clock except `repro.obs.clock`:

  - `trace`   — `Tracer` / `Span`: nested spans over the request
    lifecycle and core pipeline stages, JSON-lines + Chrome trace
    export.
  - `metrics` — `MetricsRegistry`, `Counter` / `Gauge` / `Histogram`,
    exact nearest-rank `percentile`; JSON + Prometheus exposition.
  - `ledger`  — `CompileLedger`: every cached-program build, per-shape
    compile, and trace-time kernel dispatch; its listener bills JAX's
    own compile events to the ambient tracer's open spans.

`validate` holds the trace/metrics schema validators the CI obs job
runs (``python -m repro.obs.validate``).
"""

from repro.obs.clock import default_clock
from repro.obs.ledger import (
    CompileLedger,
    LedgerEvent,
    get_ledger,
    listen_to_jax,
)
from repro.obs.metrics import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    percentile,
)
from repro.obs.trace import Span, Tracer, get_tracer, set_tracer, use_tracer

# every JAX compile billed to the span that caused it (ledger docstring)
listen_to_jax()

__all__ = [
    "DEFAULT_BUCKETS",
    "CompileLedger",
    "Counter",
    "Gauge",
    "Histogram",
    "LedgerEvent",
    "MetricsRegistry",
    "Span",
    "Tracer",
    "default_clock",
    "get_ledger",
    "get_tracer",
    "percentile",
    "set_tracer",
    "use_tracer",
]
