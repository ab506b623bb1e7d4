"""``repro.obs`` — runtime observability: tracing, metrics, overlap analysis.

Three dependency-free pieces threaded through every runtime layer:

* :mod:`repro.obs.trace` — nestable spans and instant events on an
  injected clock, per worker/stream, exportable as Chrome trace-event
  JSON (open in Perfetto) or a plain-text timeline.  :data:`NULL_TRACER`
  makes capture zero-cost when disabled.
* :mod:`repro.obs.metrics` — named counters/gauges/histograms with
  labeled children, snapshot/diff/merge, and a swappable process-global
  default registry.
* :mod:`repro.obs.overlap` — derives the paper's compute/transfer overlap
  efficiency figure from a trace instead of hand-maintaining it.

:mod:`repro.obs.compiles` counts what JAX traces, lowers and compiles while
a launch runs (it imports JAX only when a watch first opens).

See ``docs/observability.md`` for the full API walkthrough.
"""

from .metrics import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_registry,
    set_default_registry,
    use_registry,
)
from .overlap import DeviceOverlap, OverlapReport, analyze
from .trace import (
    CHROME_REQUIRED_KEYS,
    NULL_TRACER,
    NullTracer,
    Tracer,
    profiler_clock,
)
from .validate import validate_chrome_trace

__all__ = [
    "CHROME_REQUIRED_KEYS", "Counter", "DEFAULT_BUCKETS", "DeviceOverlap",
    "Gauge", "Histogram", "MetricsRegistry", "NULL_TRACER",
    "NullTracer", "OverlapReport", "Tracer", "analyze", "default_registry",
    "profiler_clock", "set_default_registry", "use_registry",
    "validate_chrome_trace",
]
