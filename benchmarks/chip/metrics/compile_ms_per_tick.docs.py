"""Milliseconds JAX spent tracing, lowering, and compiling or loading
programs inside the window (its monitoring durations), per scheduler
tick, in the docs cell."""

from chip.stats import compile_ms_per_tick


def read(rec):
    return compile_ms_per_tick(rec)
