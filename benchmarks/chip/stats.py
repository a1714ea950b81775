"""Arithmetic the metric readers share."""

from __future__ import annotations

import math


def percentile(values, p: float) -> float | None:
    """Nearest-rank percentile of all values, or None for no values."""
    if not values:
        return None
    ordered = sorted(values)
    return float(ordered[max(1, math.ceil(p / 100 * len(ordered))) - 1])


def traced_steps(rec: dict):
    """(step record, wall seconds in the trace, device-busy seconds) of
    every step of the traced window, matched in order."""
    tr = rec.get("trace")
    if not tr:
        return []
    steps = rec["steps"]
    n = min(len(steps), len(tr["step_spans_s"]))
    return [(steps[i], tr["step_spans_s"][i], tr["step_busy_s"][i])
            for i in range(n)]


def host_ms_per_tick(rec: dict) -> float | None:
    """Each traced tick's wall time less the device-busy time inside it,
    summed, over the ticks."""
    steps = traced_steps(rec)
    if not steps:
        return None
    return sum(wall - busy for _, wall, busy in steps) / len(steps) * 1e3


def compiles_per_tick(rec: dict) -> float | None:
    """Executables compiled or loaded from the persistent cache inside
    the window, per tick."""
    ticks = len(rec["steps"])
    if not ticks:
        return None
    return rec["compile_counts"]["loaded_or_compiled"] / ticks
