"""Thread-local span tree — the structured-tracing half of repro.obs.

`trace_scope()` is layered exactly like `mm_config()` / `fault_scope()`:
a thread-local stack of trace layers, pushed by a contextmanager and
popped on exit, so nested scopes compose (spans always land in the
*innermost* trace) and a fresh thread starts disarmed.  Hot paths emit
spans through `span()` / `event()` / `annotate()`; all three follow the
`validate.scrub` discipline — with no scope armed they return a shared
null object and touch nothing, so tracing disarmed costs one integer
check per call site and shows no extra counters anywhere.

Span kinds emitted by the instrumented stack:

  dispatch   one guarded matmul dispatch (kernels/ops): site, dims,
             backend, epilogue; annotated along the way with the tune
             cache key, the ladder rung that delivered, the planner's
             modeled_us and (clock armed) the measured_us
  rung       one degradation-ladder attempt (guard/fallback): level,
             index, and the typed GuardError when the level failed
  plan       one planner resolution (core/planner, sparse/planner):
             mode, dims, candidate count, chosen schedule/blocks,
             modeled_us
  tune       one tuned-cache lookup (tune/runtime): cache key, hit/miss,
             the cached schedule (split-K hits are the GEMV ledger)
  validate   a pre-dispatch plan rejection (guard/validate)
  retry      a transient re-execution (guard/fallback.retry_call)
  tick       one scheduler step (serve/sched/loop); children admit /
             prefill / decode / sync / scatter / bookkeep
  sync       a device-to-host read (argmax tokens, the NaN scrub)
  scatter    KV slab growth and the scatter of prefilled rows
  bookkeep   per-row token bookkeeping, completions, telemetry

The tree itself is plain data (`Span`); exporters live in
`repro.obs.export` and are reachable through `Trace.export_chrome` /
`Trace.render` / `Trace.digest`.

`trace_scope(profiler=True)` is the profiler sink: instead of building a
tree, each `span(kind)` enters a `jax.profiler.TraceAnnotation` named
``repro.<kind>``, so the spans land in the profiler's own trace on the
clock of the device's ops.  While a sink is armed, garbage collections
appear as ``repro.gc`` annotations and, with JAX's trace, lowering and
compile-or-load durations, feed millisecond histograms of `REGISTRY`
(`SINK_HISTOGRAMS`; the JAX stages also per ``<name>/<fun_name>``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import threading
import time
from typing import Any, Iterator

from repro.obs.metrics import REGISTRY

_TLS = threading.local()
_ARM_LOCK = threading.Lock()
# Process-wide count of open trace scopes: the disarmed fast path is one
# falsy check on this int, before any thread-local attribute lookup.
_ARMED = 0
# Open profiler sinks; the GC callback and the JAX listener are
# registered while this is non-zero.
_SINKS = 0
_ANNOTATION: Any = None          # jax.profiler.TraceAnnotation, once armed
_GC_OPEN: list[tuple[Any, float]] = []

# JAX monitoring duration events -> REGISTRY histograms (milliseconds)
JAX_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "jax_trace_ms",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax_lower_ms",
    "/jax/core/compile/backend_compile_duration": "jax_compile_or_load_ms",
}
SINK_HISTOGRAMS = ("gc_ms", *JAX_STAGES.values())


@dataclasses.dataclass
class Span:
    """One node of the trace tree.

    `modeled_us` / `measured_us` are the attribution pair: the cost
    model's prediction and the armed clock's observation for the same
    region (either may be absent).  Everything else rides in `attrs`.
    """

    kind: str
    name: str
    attrs: dict[str, Any] = dataclasses.field(default_factory=dict)
    children: list["Span"] = dataclasses.field(default_factory=list)
    modeled_us: float | None = None
    measured_us: float | None = None

    def set(self, **attrs: Any) -> "Span":
        """Merge attributes; modeled_us / measured_us land on the typed
        fields so exporters and the drift meter find them uniformly."""
        for key in ("modeled_us", "measured_us"):
            if key in attrs:
                val = attrs.pop(key)
                if val is not None:
                    setattr(self, key, float(val))
        self.attrs.update(attrs)
        return self

    def walk(self) -> Iterator["Span"]:
        """This span and every descendant, depth-first."""
        yield self
        for child in self.children:
            yield from child.walk()

    @property
    def drift_log(self) -> float | None:
        """log(measured / modeled) when both sides exist and are
        positive — the per-span attribution residual."""
        import math

        if not self.modeled_us or not self.measured_us:
            return None
        if self.modeled_us <= 0 or self.measured_us <= 0:
            return None
        return math.log(self.measured_us / self.modeled_us)


class _NullSpan:
    """The disarmed sentinel: every mutation is a no-op."""

    __slots__ = ()

    def set(self, **attrs: Any) -> "_NullSpan":
        del attrs
        return self


NULL_SPAN = _NullSpan()


class Trace:
    """One trace scope's collected span forest plus its armed clock."""

    def __init__(self, clock: Any = None):
        self.clock = clock
        self.roots: list[Span] = []

    def spans(self) -> Iterator[Span]:
        for root in self.roots:
            yield from root.walk()

    def digest(self) -> dict[str, int]:
        """Span-kind counts (plus ``total``) — the provenance fragment."""
        from repro.obs import export

        return export.digest(self)

    def render(self) -> str:
        """Deterministic text tree (the test-facing exporter)."""
        from repro.obs import export

        return export.render_text(self)

    def export_chrome(self, path: str) -> str:
        """Write the Chrome-trace/Perfetto JSON document; returns path."""
        from repro.obs import export

        return export.export_chrome(self, path)


@dataclasses.dataclass
class _Layer:
    trace: Trace
    open: list[Span] = dataclasses.field(default_factory=list)
    profiler: bool = False


def _layers() -> list[_Layer]:
    stack = getattr(_TLS, "layers", None)
    if stack is None:
        stack = _TLS.layers = []
    return stack


def tracing() -> bool:
    """Is a tree-building trace scope innermost on *this* thread?  The
    hot-path check: call sites compute span attributes only when it
    holds, and the profiler sink builds no tree."""
    if not _ARMED:
        return False
    layers = getattr(_TLS, "layers", None)
    return bool(layers) and not layers[-1].profiler


def current_trace() -> Trace | None:
    """The innermost armed trace, or None."""
    if not _ARMED:
        return None
    layers = getattr(_TLS, "layers", None)
    return layers[-1].trace if layers else None


def current_span() -> Span | None:
    """The innermost *open* span of the armed trace, or None."""
    if not _ARMED:
        return None
    layers = getattr(_TLS, "layers", None)
    if not layers or not layers[-1].open:
        return None
    return layers[-1].open[-1]


def open_span(kind: str) -> Span | None:
    """The innermost open span of `kind` in the armed trace, or None.

    This is how nested dispatch wrappers *join* one logical dispatch
    instead of stacking spans: `skewmm.matmul` opens the dispatch span,
    and the `kernels.ops` wrapper it delegates to finds it open and
    decorates it rather than opening a second one.
    """
    if not _ARMED:
        return None
    layers = getattr(_TLS, "layers", None)
    if not layers or not layers[-1].open:
        return None
    for sp in reversed(layers[-1].open):
        if sp.kind == kind:
            return sp
    return None


@contextlib.contextmanager
def trace_scope(clock: Any = None, *, profiler: bool = False) -> Iterator[Trace]:
    """Arm structured tracing for the dynamic extent of the block.

    Layered like `mm_config()`: scopes nest (spans land in the innermost
    trace), the stack is thread-local, and exit always restores the
    enclosing state.  `clock` is an attribution clock (`SimClock` from
    `repro.obs.clock`, or None for structure-only traces); dispatch
    sites consult it through `measured()`.

        with trace_scope(clock=SimClock()) as tr:
            out = skew_matmul(a, b)
        tr.export_chrome("trace.json")

    With `profiler=True` the layer is the profiler sink (module
    docstring): spans become `repro.<kind>` annotations in a running
    `jax.profiler` trace, the yielded `Trace` stays empty, and the GC
    callback and JAX listener are registered until the last sink exits.
    """
    global _ARMED
    if profiler and clock is not None:
        raise ValueError("the profiler sink takes no attribution clock")
    layer = _Layer(trace=Trace(clock=clock), profiler=profiler)
    layers = _layers()
    layers.append(layer)
    with _ARM_LOCK:
        if profiler:
            _arm_sink()
        _ARMED += 1
    try:
        yield layer.trace
    finally:
        with _ARM_LOCK:
            _ARMED -= 1
            if profiler:
                _disarm_sink()
        layers.pop()


def _arm_sink() -> None:
    """Register the GC callback and the JAX listener (first sink only);
    called under `_ARM_LOCK`."""
    global _SINKS, _ANNOTATION
    _SINKS += 1
    if _SINKS > 1:
        return
    import jax
    import jax.monitoring

    _ANNOTATION = jax.profiler.TraceAnnotation
    gc.callbacks.append(_on_gc)
    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def _disarm_sink() -> None:
    global _SINKS
    _SINKS -= 1
    if _SINKS:
        return
    import jax.monitoring

    gc.callbacks.remove(_on_gc)
    jax.monitoring.unregister_event_duration_listener(_on_jax_duration)


def _on_gc(phase: str, info: dict) -> None:
    """`gc.callbacks` hook: a `repro.gc` annotation over each collection,
    its pause in the `gc_ms` histogram."""
    del info
    if phase == "start":
        ann = _ANNOTATION("repro.gc")
        ann.__enter__()
        _GC_OPEN.append((ann, time.perf_counter()))
    elif _GC_OPEN:
        ann, t0 = _GC_OPEN.pop()
        ann.__exit__(None, None, None)
        REGISTRY.observe("gc_ms", (time.perf_counter() - t0) * 1e3)
        REGISTRY.inc("gc_collections")


def _on_jax_duration(event: str, secs: float, **kw: Any) -> None:
    name = JAX_STAGES.get(event)
    if name is None:
        return
    ms = secs * 1e3
    REGISTRY.observe(name, ms)
    fun = kw.get("fun_name")
    if fun:
        REGISTRY.observe(f"{name}/{fun}", ms)


@contextlib.contextmanager
def span(kind: str, name: str = "", **attrs: Any) -> Iterator[Span | _NullSpan]:
    """Open a span for the extent of the block (no-op when disarmed).

    The yielded object supports ``.set(**attrs)`` either way, so call
    sites never branch on armed-ness themselves.
    """
    if not _ARMED:
        yield NULL_SPAN
        return
    layers = getattr(_TLS, "layers", None)
    if not layers:
        yield NULL_SPAN
        return
    layer = layers[-1]
    if layer.profiler:
        # TraceMe would fold keyword arguments into the name: pass none
        with _ANNOTATION(f"repro.{kind}"):
            yield NULL_SPAN
        return
    sp = Span(kind=kind, name=name)
    sp.set(**attrs)
    parent = layer.open[-1] if layer.open else None
    (parent.children if parent is not None else layer.trace.roots).append(sp)
    layer.open.append(sp)
    try:
        yield sp
    finally:
        layer.open.pop()


def event(kind: str, name: str = "", **attrs: Any) -> Span | _NullSpan:
    """Emit a leaf span with no extent (no-op when disarmed)."""
    if not _ARMED:
        return NULL_SPAN
    layers = getattr(_TLS, "layers", None)
    if not layers:
        return NULL_SPAN
    layer = layers[-1]
    if layer.profiler:
        return NULL_SPAN
    sp = Span(kind=kind, name=name)
    sp.set(**attrs)
    parent = layer.open[-1] if layer.open else None
    (parent.children if parent is not None else layer.trace.roots).append(sp)
    return sp


def annotate(kind: str | None = None, **attrs: Any) -> bool:
    """Set attributes on the nearest enclosing open span (of `kind`,
    when given).  Returns whether a span was found; no-op disarmed.

    This is how inner layers decorate the outer dispatch span — the
    tune lookup stamps its cache key, the planner its modeled_us, the
    ladder the rung that delivered — without threading span handles
    through every signature.
    """
    if not _ARMED:
        return False
    layers = getattr(_TLS, "layers", None)
    if not layers or not layers[-1].open:
        return False
    for sp in reversed(layers[-1].open):
        if kind is None or sp.kind == kind:
            sp.set(**attrs)
            return True
    return False
