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


def compile_ms_per_tick(rec: dict) -> float | None:
    """Milliseconds JAX spent tracing, lowering, and compiling or loading
    programs inside the window, per tick."""
    ticks = len(rec["steps"])
    if not ticks:
        return None
    return rec["compile_counts"]["stage_ms"] / ticks


def decode_scope_ms(rec: dict, scope: str) -> float | None:
    """Device time under a named scope per traced pure-decode tick (the
    ticks of `decode_device_ms`), or None where the scope read nothing."""
    tr = rec.get("trace") or {}
    split = tr.get("decode_scope_s")
    if not split or not split[scope]:
        return None
    return split[scope] / tr["decode_scope_ticks"] * 1e3
