"""repro.obs — structured tracing, unified metrics, drift attribution.

Three pieces, one package:

- **Span tree** (`spans`): thread-local `trace_scope()` arms tracing;
  hot paths emit `span()` / `event()` / `annotate()`.  Disarmed, every
  emit is one integer check (the `scrub` discipline) — zero cost on
  jitted paths, no counters, no allocations.  `trace_scope(profiler=
  True)` writes the spans into a running `jax.profiler` trace instead,
  with GC pauses and JAX compile stages beside them.
- **Metrics registry** (`metrics`): typed counters / gauges /
  histograms under one lock.  `guard.health` and `ServeTelemetry`
  both write here now.
- **Attribution** (`clock`, `attribution`): the sim clock stamps
  `measured_us` on dispatch spans next to the planner's `modeled_us`;
  per-shape-class drift histograms feed `drift_report()`, judged
  against the calibration gate's `MAX_LOG_SPREAD`.

Exporters (`export`): `trace.digest()` (span-kind counts, folded into
`bench.Provenance`), `trace.render()` (deterministic text tree),
`trace.export_chrome(path)` (Chrome-tracing / Perfetto JSON).
"""

from repro.obs.attribution import (
    dispatch,
    drift_report,
    measured,
    record_drift,
    shape_class_token,
)
from repro.obs.clock import SimClock
from repro.obs.export import (
    digest,
    export_chrome,
    render_text,
    to_chrome,
    validate_chrome,
)
from repro.obs.metrics import (
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    Registry,
    percentile_nearest_rank,
)
from repro.obs.spans import (
    NULL_SPAN,
    Span,
    Trace,
    annotate,
    current_span,
    current_trace,
    event,
    span,
    trace_scope,
    tracing,
)

__all__ = [
    "NULL_SPAN",
    "REGISTRY",
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "SimClock",
    "Span",
    "Trace",
    "annotate",
    "current_span",
    "current_trace",
    "digest",
    "dispatch",
    "drift_report",
    "event",
    "export_chrome",
    "measured",
    "percentile_nearest_rank",
    "record_drift",
    "render_text",
    "shape_class_token",
    "span",
    "to_chrome",
    "trace_scope",
    "tracing",
    "validate_chrome",
]
