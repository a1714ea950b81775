"""Attribution clock — the injectable `measured_us` source.

`SimClock` "measures" a dispatch at exactly the cost model's
prediction, so traces are deterministic, integer-exact across hosts,
and per-class drift is identically zero — any non-zero drift in a
sim-clock run means the modeled/measured plumbing itself broke.

There is no host-clock measurer: under `jit` a thunk's wall time is its
tracing, not its execution.  Device time comes from a profiler trace,
into which `trace_scope(profiler=True)` writes the spans and the named
scopes tag the ops (`repro.obs.spans`).
"""

from __future__ import annotations

from typing import Any, Callable


class SimClock:
    """Modeled measurer: measured == modeled, exactly."""

    def measure(
        self, fn: Callable[[], Any], modeled_us: float | None = None
    ) -> tuple[Any, float | None]:
        return fn(), modeled_us
