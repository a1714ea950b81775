"""Planned (skew-aware) matmul — the framework's matmul primitive.

Every matmul in every model flows through `matmul()`.  It consults the
skew-aware planner (AMP-budgeted, aspect-ratio-adaptive, schedule-diverse —
the paper's mechanism made explicit) and dispatches to one of two backends:

  * "pallas" — the blocked TPU kernel family in `repro.kernels.skew_matmul`,
    using the planner's block shapes *and schedule* (K-inner /
    A-resident / B-resident / batched-grid) as its BlockSpec tiling.  On CPU
    this runs in interpret mode (tests/benchmarks only).
  * "xla"    — `jax.lax.dot_general` with preferred_element_type=f32.  Used
    for full-model dry-runs (XLA's own tiling then applies; the plan is still
    computed and logged so the roofline analysis can compare).

Configuration is *context-scoped* (repro.core.config), mirroring Poplar's
session-scoped engine options: `backend`, `amp`, `chip`, `plan_mode`,
`out_dtype` and `interpret` resolve through the `mm_config` stack —

    with mm_config(amp=0.3, chip="ipu_gc200", backend="pallas"):
        logits = model(params, batch)     # every matmul re-planned

— with explicit per-call kwargs as the innermost layer and the
REPRO_MM_BACKEND env var as the outermost.

Fused epilogues are *structured* (repro.core.epilogue): pass an
``Epilogue(bias=..., act="gelu", residual=..., scale=...)`` carrying its own
operands, or keep the legacy string surface
(``matmul(..., epilogue="bias_gelu", bias=...)``) which routes through
`Epilogue.parse`.  Both backends fuse ``act(scale * (a@b) + bias) +
residual`` at fp32 accumulator width, so they stay numerically aligned.

Plan capture: wrap a region in ``with plan_capture() as log:`` to collect the
`MatmulCost` of every matmul traced inside it without mutating global state
(captures nest).  Non-(…mk,kn) contractions issued through `einsum_mm` log an
`UnplannedContraction` marker so the captured workload is complete.
`enable_plan_log` / `plan_log` remain as thin shims over a process-global
capture for legacy callers.
"""

from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Iterator

import jax
import jax.numpy as jnp

from repro.core import config, epilogue as epilogue_mod, hw
from repro.core.config import MatmulConfig, mm_config  # noqa: F401  (re-export)
from repro.core.epilogue import Epilogue  # noqa: F401  (re-export)
from repro.core.planner import plan_matmul
from repro.obs import attribution as _obs

_ACTIVE_LOGS: list[list] = []
_LEGACY_LOG: list = []

# Legacy token vocabulary, re-exported for callers of the string surface.
EPILOGUE_TOKENS = epilogue_mod.EPILOGUE_TOKENS


def parse_epilogue(epilogue: str | None) -> tuple[str, ...]:
    """Legacy shim: validate a token-string spec, return its tokens.

    The structured path is `Epilogue.parse` (which also checks operand
    presence); this keeps the old call surface for kernel-level users.
    """
    return tuple(t for t, _ in epilogue_mod.normalize_spec(epilogue))


@dataclasses.dataclass(frozen=True)
class UnplannedContraction:
    """Plan-log marker for a contraction the planner did not decompose.

    `einsum_mm` records one of these per call so `plan_capture()` still
    sees the full workload: consumers that aggregate `MatmulCost` entries
    should filter on isinstance, and can surface these as the "unplanned
    residue" of a model (ideally empty).
    """

    spec: str
    a_shape: tuple[int, ...]
    b_shape: tuple[int, ...]
    dtype_bytes: int


def _deregister_log(log: list) -> None:
    # identity-based removal: lists compare by value, so `.remove()` could
    # drop a different (equal-content, e.g. empty) capture.
    for i, entry in enumerate(_ACTIVE_LOGS):
        if entry is log:
            del _ACTIVE_LOGS[i]
            return


@contextlib.contextmanager
def plan_capture() -> Iterator[list]:
    """Collect the plan of every matmul traced inside the block."""
    log: list = []
    _ACTIVE_LOGS.append(log)
    try:
        yield log
    finally:
        _deregister_log(log)


def enable_plan_log(enabled: bool = True) -> None:
    """Legacy shim over a process-global plan_capture."""
    if enabled:
        _LEGACY_LOG.clear()
        if not any(entry is _LEGACY_LOG for entry in _ACTIVE_LOGS):
            _ACTIVE_LOGS.append(_LEGACY_LOG)
    else:
        _deregister_log(_LEGACY_LOG)


def plan_log() -> list:
    return list(_LEGACY_LOG)


def _record(cost) -> None:
    for log in _ACTIVE_LOGS:
        log.append(cost)


def record_plan(cost) -> None:
    """Public capture hook for out-of-module planned entry points.

    The sparse/grouped wrappers in `repro.kernels.ops` have no skewmm
    wrapper to record through; they append their `SparseMatmulCost` here
    so `plan_capture()` still sees the complete workload (MoE expert
    GEMMs included).
    """
    _record(cost)


def matmul(a: jax.Array, b: jax.Array, *, backend: str | None = None,
           amp: float | None = None, plan_mode: str | None = None,
           chip: hw.ChipSpec | str | None = None,
           epilogue: Epilogue | str | None = None,
           bias: jax.Array | None = None,
           residual: jax.Array | None = None,
           out_dtype: jnp.dtype | None = None,
           interpret: bool | None = None) -> jax.Array:
    """C[..., m, n] = epilogue(A[..., m, k] @ B[k, n]), skew-planned.

    Leading batch dims of `a` either fold into m or ride in the grid as a
    batched-grid plan — the planner weighs the padding both ways.  All
    config kwargs default to the active `mm_config` context (see module
    docstring); `chip` accepts a registered name string.  `epilogue` is an
    `Epilogue` object or a legacy token string (operands via bias= /
    residual=, with `residual` broadcast-matching the output shape and
    `bias` a (n,) vector).
    """
    if b.ndim != 2:
        raise ValueError(f"rhs must be 2-D (weights), got {b.shape}")
    *lead, m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch: {a.shape} @ {b.shape}")

    cfg = config.resolve(backend=backend, amp=amp, plan_mode=plan_mode,
                         chip=chip, out_dtype=out_dtype, interpret=interpret)
    # One validation point for both backends: operand-presence and token
    # errors raise ValueError here (never a bare assert).
    ep = Epilogue.parse(epilogue, bias=bias, residual=residual)

    batch = 1
    for s in lead:
        batch *= s
    dtype_bytes = jnp.dtype(a.dtype).itemsize
    # The named scope tags the device ops with the shape class.  The
    # dispatch span opens *before* planning so the tune lookup and the
    # planner annotate this span (cache key, modeled_us) — the ops
    # wrapper below joins it rather than opening a second one.
    with jax.named_scope("mm." + _obs.shape_class_token(m, k, n, batch)), \
            _obs.dispatch("dense", m=m, k=k, n=n, batch=batch,
                          backend=cfg.backend, epilogue=str(ep.spec)) as dsp:
        cost = plan_matmul(m, k, n, dtype_bytes=dtype_bytes, amp=cfg.amp,
                           chip=cfg.chip_spec, mode=cfg.plan_mode,
                           batch=batch, mesh_shape=cfg.mesh_shape,
                           sharding=cfg.sharding)
        _record(cost)

        out_dtype = cfg.out_dtype or a.dtype
        if cfg.backend == "pallas":
            from repro.kernels import ops  # lazy: kernels import pallas
            kw = dict(plan=cost.plan, out_dtype=out_dtype,
                      interpret=cfg.interpret)
            res = ep.residual
            if cost.plan.batch_grid and lead:
                a3 = a.reshape(batch, m, k)
                if res is not None:
                    res = jnp.broadcast_to(res, (*lead, m, n)).reshape(
                        batch, m, n)
                out = ops.skew_matmul_batched(
                    a3, b, epilogue=ep.replace(residual=res), **kw)
            else:
                a2 = a.reshape(batch * m, k)
                if res is not None:
                    res = jnp.broadcast_to(res, (*lead, m, n)).reshape(
                        batch * m, n)
                out = ops.skew_matmul(a2, b,
                                      epilogue=ep.replace(residual=res),
                                      **kw)
            return out.reshape(*lead, m, n)

        # XLA backend: fp32 accumulation + fp32 epilogue to match the
        # kernel.  This *is* the ladder's reference rung, selected by
        # config rather than by degradation — attributed as such.
        def ref_run() -> jax.Array:
            z = jax.lax.dot_general(
                a, b, (((a.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            z = epilogue_mod.apply_spec(z, ep.spec, ep.operands())
            return z.astype(out_dtype)

        _obs.annotate("dispatch", rung="reference", rung_index=3,
                      kernel="xla_dot")
        return _obs.measured(dsp, ref_run)


def einsum_mm(spec: str, a: jax.Array, b: jax.Array, **kw) -> jax.Array:
    """einsum wrapper for the handful of non-(…mk,kn) contractions.

    Falls back to jnp.einsum with f32 accumulation; exists so models have a
    single import site for all contractions and the plan log stays
    complete: each call records an `UnplannedContraction` marker so
    `plan_capture()` sees the full workload even where the planner has no
    decomposition to offer.
    """
    _record(UnplannedContraction(
        spec=spec, a_shape=tuple(a.shape), b_shape=tuple(b.shape),
        dtype_bytes=jnp.dtype(a.dtype).itemsize))
    return jnp.einsum(spec, a, b,
                      preferred_element_type=jnp.float32).astype(a.dtype)


# Convenience partials used across the model zoo.
matmul_xla = partial(matmul, backend="xla")
matmul_pallas = partial(matmul, backend="pallas")
