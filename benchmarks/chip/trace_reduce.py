"""Reduce a profiler trace (`.xplane.pb`) to busy intervals, idle gaps,
per-span device time, collective time and device time per named scope.

A device's busy time is the union of the intervals in which an operation
of its "XLA Ops" line ran.  Host spans are the benchmark's own
annotations (names starting with `bench.`), on the same clock; beside
them are JAX's compile stages and the program's spans (`repro.*`,
written while `repro.obs.trace_scope(profiler=True)` is armed).  Times
here are nanoseconds since the trace began; results are in seconds.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

HOST_PREFIX = "bench."
PROGRAM_PREFIX = "repro."
JAX_STAGES = ("trace_to_jaxpr_dynamic", "lower_sharding_computation",
              "backend_compile_and_load", "np.asarray(jax.Array)")
# `%name = <shape> <opcode>(` of an HLO instruction, as the compiled
# module's text and the trace's op events both print it
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.+?) ([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?(\.\d+)?\b")


@dataclasses.dataclass
class Trace:
    # device plane name -> [(op name, start_ns, end_ns)] of its XLA ops
    device: dict[str, list[tuple[str, float, float]]]
    # the benchmark's host annotations: [(name, start_ns, end_ns)]
    host: list[tuple[str, float, float]]
    # the program's spans and JAX's stages, names cut at "#"
    program: list[tuple[str, float, float]]
    # device plane name -> [(module name, start_ns, end_ns)] of its
    # "XLA Modules" line: one entry per program run
    modules: dict[str, list[tuple[str, float, float]]]


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"{len(files)} xplane files under {log_dir}")
    return files[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device, modules, host, program = {}, {}, [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {"XLA Ops": [], "XLA Modules": []}
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name].extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
            device[plane.name] = lines["XLA Ops"]
            modules[plane.name] = sorted(lines["XLA Modules"],
                                         key=lambda m: m[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    span = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    if e.name.startswith(HOST_PREFIX):
                        host.append(span)
                    else:
                        name = e.name.split("#", 1)[0]
                        if name.startswith(PROGRAM_PREFIX) or name in JAX_STAGES:
                            program.append((name,) + span[1:])
    host.sort(key=lambda s: s[1])
    program.sort(key=lambda s: s[1])
    return Trace(device=device, host=host, program=program, modules=modules)


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
    [a, b], each labelled with the innermost (shortest) host span, the
    benchmark's or the program's, that covers more than half of it; where
    none does, the span that covers most of it ("none" where no span
    meets it)."""
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
        meets = [(min(e, ge) - max(s, gs), e - s, name)
                 for name, s, e in host_spans if min(e, ge) > max(s, gs)]
        most = [m for m in meets if 2 * m[0] > ge - gs]
        if most:
            label = min(most, key=lambda m: m[1])[2]
        else:
            label = max(meets, key=lambda m: m[0])[2] if meets else "none"
        out.append([label, (ge - gs) * 1e-9])
    return out


# ------------------------------------------------------- named scopes
def hlo_ops(text: str) -> dict[str, tuple[str, str]]:
    """{instruction: (signature, op_name)} of a compiled module's HLO
    text; the signature is its result shape and opcode, as an op event
    of the trace prints them."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = (f"{m.group(2)} {m.group(3)}",
                               op.group(1) if op else "")
    return out


def scope_of(op_name: str) -> str:
    """The outermost of `attention`, `lm_head` and `mm.*` on an op's
    op_name path ("mm" for any `mm.<shape class>`), else "unscoped"."""
    for part in op_name.split("/"):
        if part in ("attention", "lm_head"):
            return part
        if part.startswith("mm."):
            return "mm"
    return "unscoped"


def innermost(events) -> list[tuple[str, float, float]]:
    """Disjoint pieces covering the union of (name, start, end) events,
    each named by the innermost event running then: an op nested in a
    loop's op counts once, as itself."""
    out, stack, t = [], [], float("-inf")

    def close(until):
        nonlocal t
        while stack and stack[-1][1] <= until:
            name, end = stack.pop()
            if end > t:
                out.append((name, t, end))
                t = end

    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        close(s)
        if stack and s > t:
            out.append((stack[-1][0], t, s))
        t = max(t, s)
        stack.append((name, e))
    close(float("inf"))
    return out


def scope_split(events, modules, programs, spans) -> dict[str, float]:
    """Device nanoseconds inside the host spans, by named scope.

    Each instant of device work goes to the innermost op running then,
    and that op to a scope by its HLO op_name (`scope_of`).  The op is
    looked up in the compiled program that its module run (`modules`, the
    "XLA Modules" line) executed: the one of `programs` (`hlo_ops` of
    each) that holds the signature of every op of that run.  Ops of any
    other program, and ops outside every scope, are "unscoped", so the
    parts sum to the device time inside the spans."""
    out = dict.fromkeys(("attention", "mm", "lm_head", "unscoped"), 0.0)
    spans = sorted((s, e) for _, s, e in spans)
    if not spans:
        return out
    a, b = spans[0][0], spans[-1][1]
    events = sorted((ev for ev in events if ev[2] > a and ev[1] < b),
                    key=lambda x: x[1])
    starts = [ev[1] for ev in events]
    mod_starts = [m[1] for m in modules]
    span_starts = [s for s, _ in spans]
    parsed: dict[str, tuple[str, str] | None] = {}
    chosen: dict[str, dict | None] = {}

    def parse(text):
        if text not in parsed:
            m = _INSTR.match(text)
            parsed[text] = (m.group(1), f"{m.group(2)} {m.group(3)}") \
                if m else None
        return parsed[text]

    def program_of(name, ms, me):
        """The program a module run executed, chosen once per module."""
        if name not in chosen:
            lo, hi = (bisect.bisect_left(starts, ms),
                      bisect.bisect_right(starts, me))
            sigs = {parse(ev[0]) for ev in events[lo:hi]} - {None}
            chosen[name] = next(
                (p for p in programs
                 if sigs and all(p.get(k, ("",))[0] == sig
                                 for k, sig in sigs)), None)
        return chosen[name]

    for text, s, e in innermost(events):
        i = bisect.bisect_right(span_starts, s) - 1
        # the piece's time inside the spans it meets
        inside = 0.0
        j = max(i, 0)
        while j < len(spans) and spans[j][0] < e:
            inside += max(0.0, min(e, spans[j][1]) - max(s, spans[j][0]))
            j += 1
        if inside <= 0:
            continue
        scope = "unscoped"
        k = bisect.bisect_right(mod_starts, s) - 1
        key = parse(text)
        if key and k >= 0 and s < modules[k][2]:
            prog = program_of(*modules[k])
            if prog and key[0] in prog:
                scope = scope_of(prog[key[0]][1])
        out[scope] += inside
    return out
