"""Scheduler host time per tick in the chat cell: each traced
`step()`'s wall time less the device-busy time inside it, summed, over
the ticks."""

from chip.stats import host_ms_per_tick


def read(rec):
    return host_ms_per_tick(rec)
