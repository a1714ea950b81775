"""Reduce a profiler trace (`.xplane.pb`) to busy intervals, idle gaps,
per-span device time and collective time.

A device's busy time is the union of the intervals in which an operation
of its "XLA Ops" line ran.  Host spans are the benchmark's own
annotations (names starting with `bench.`), on the same clock.  Times
here are nanoseconds since the trace began; results are in seconds.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

HOST_PREFIX = "bench."
_COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?(\.\d+)?\b")


@dataclasses.dataclass
class Trace:
    # device plane name -> [(op name, start_ns, end_ns)] of its XLA ops
    device: dict[str, list[tuple[str, float, float]]]
    # the benchmark's host annotations: [(name, start_ns, end_ns)]
    host: list[tuple[str, float, float]]


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"{len(files)} xplane files under {log_dir}")
    return files[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
            device[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    host.sort(key=lambda s: s[1])
    return Trace(device=device, host=host)


# ------------------------------------------------------------ intervals
def merge(events) -> list[tuple[float, float]]:
    """Union of (name, start, end) events as sorted disjoint intervals."""
    out: list[list[float]] = []
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_within(merged, a: float, b: float) -> float:
    """Nanoseconds of [a, b] that the merged intervals cover."""
    return sum(max(0.0, min(e, b) - max(s, a)) for s, e in merged)


def span_busy(merged, spans) -> list[float]:
    """Device-busy nanoseconds inside each host span."""
    return [busy_within(merged, s, e) for _, s, e in spans]


def is_collective(op_name: str) -> bool:
    head = op_name.split(" = ", 1)[0]
    return bool(_COLLECTIVE.search(head))


def collective_ns(events, a: float, b: float) -> float:
    """Device time of collective operations inside [a, b]."""
    return sum(max(0.0, min(e, b) - max(s, a))
               for n, s, e in events if is_collective(n))


def op_key(op_name: str) -> str:
    """A short name for an op: its HLO instruction name."""
    return op_name.split(" = ", 1)[0].lstrip("%")


def top_ops(events, a: float, b: float, k: int = 10):
    """[(op, seconds)] of the k ops with the most device time in [a, b]."""
    tot: dict[str, float] = {}
    for n, s, e in events:
        d = min(e, b) - max(s, a)
        if d > 0:
            key = op_key(n)
            tot[key] = tot.get(key, 0.0) + d
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9] for name, ns in top]


def idle_gaps(merged, a: float, b: float, host_spans, k: int = 10):
    """[(label, seconds)] of the k longest gaps in device activity inside
    [a, b], each labelled with the innermost host annotation that covers
    most of it ("none" where no annotation does)."""
    edges = [(max(s, a), min(e, b)) for s, e in merged if e > a and s < b]
    gaps, cur = [], a
    for s, e in edges:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < b:
        gaps.append((cur, b))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for gs, ge in gaps[:k]:
        best, cover = "none", 0.0
        for name, s, e in host_spans:
            c = min(e, ge) - max(s, gs)
            # innermost wins a tie: spans are sorted by start
            if c > 0 and c >= cover:
                best, cover = name, c
        out.append([best, (ge - gs) * 1e-9])
    return out
