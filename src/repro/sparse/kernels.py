"""Pallas block-sparse & grouped matmul kernels (the BSR schedule family).

The dense schedule family (`repro.kernels.skew_matmul`) re-tiled on a
`BlockSparseLayout`: the kernel grid iterates only the *padded row
width* of the structure (s_max steps per row block) and gather-based
index maps — `cols` / `nnz` delivered through Pallas scalar prefetch —
pick the nonzero column block each step, so zero blocks are never
streamed.  Invalid tail steps (s >= nnz[row]) are masked in-kernel, which
keeps rows with fewer nonzero blocks (or none) correct.

Schedules mirror the dense family exactly, so density-1.0 output is
bit-for-bit identical to the dense kernels (same block shapes, same
accumulation order, same fused-epilogue flush):

  "k_inner"    — grid (gm, gn, s); fp32 VMEM scratch accumulator,
                 output written once on the last s step.
  "a_resident" — grid (gm, s, gn); each nonzero A block pinned across
                 the n sweep; while s_max > 1 the row strip's partial
                 sums live in an fp32 scratch and each output block is
                 written once, on the last s step.
  "b_resident" — grid (gn, s, gm); kept for schedule parity.  With
                 row-major (CSR) structure the B block index varies with
                 the inner row index, so B is *not* actually resident —
                 the cost model prices it honestly and the sparse
                 planner skips it (a CSC layout is the ROADMAP fix).

`grouped_matmul_padded` is the block-diagonal fast path for MoE expert
GEMMs: `groups` independent matmuls with per-group rhs, K-inner with the
group index as a leading parallel grid dim and *regular* index maps (the
structure is implied, no gather).

Fused epilogues reuse the structured table from `repro.core.epilogue`
(one op table shared with the dense kernels, the XLA backend and the
oracles).  The grouped kernel supports scale / act / residual; a bias
epilogue (a per-group (n,) vector) is rejected at the ops layer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import epilogue as epilogue_mod

# One definition of the epilogue flush, the strip accumulation and the
# VMEM-limited CompilerParams, shared with the dense kernels so the two
# families cannot drift.
from repro.kernels.skew_matmul import (
    _apply_epilogue,
    _epilogue_refs,
    compiler_params,
    epilogue_blocks,
    held_until_last,
    strip_accumulate,
)


# --------------------------------------------------------------- kernel bodies
def _bsr_k_inner_kernel(cols_ref, nnz_ref, a_ref, b_ref, *rest, spec, s_steps):
    del cols_ref  # consumed by the index maps
    tokens = tuple(t for t, _ in spec)
    acc_ref = rest[-1]
    o_ref = rest[-2]
    bias_ref, res_ref = _epilogue_refs(rest[:-2], tokens)
    i = pl.program_id(0)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s < nnz_ref[i])
    def _accum():
        acc_ref[...] += jnp.dot(
            a_ref[...], b_ref[...], preferred_element_type=jnp.float32
        )

    @pl.when(s == s_steps - 1)
    def _flush():
        z = _apply_epilogue(acc_ref[...], spec, bias_ref, res_ref)
        o_ref[...] = z.astype(o_ref.dtype)


def _bsr_resident_kernel(
    cols_ref, nnz_ref, a_ref, b_ref, *rest, spec, s_steps, row_axis
):
    """Shared a_resident / b_resident body: s is the middle grid dim and
    the inner dim indexes the fp32 strip scratch that carries the partial
    sums while s_max > 1 (as in the dense resident kernels).  Invalid
    tail steps contribute an exact zero (partial * 0.0), which at density
    1.0 degenerates to the dense body bit-for-bit (partial * 1.0)."""
    del cols_ref
    tokens = tuple(t for t, _ in spec)
    if s_steps > 1:
        *rest, strip_ref = rest
    o_ref = rest[-1]
    bias_ref, res_ref = _epilogue_refs(rest[:-1], tokens)
    i = pl.program_id(row_axis)
    s = pl.program_id(1)
    flag = (s < nnz_ref[i]).astype(jnp.float32)
    partial = flag * jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    def flush(acc):
        z = _apply_epilogue(acc, spec, bias_ref, res_ref)
        o_ref[...] = z.astype(o_ref.dtype)

    if s_steps == 1:
        flush(partial)
        return
    strip_accumulate(partial, strip_ref, s, s_steps, flush)


def _grouped_k_inner_kernel(a_ref, b_ref, *rest, spec, n_k_steps):
    tokens = tuple(t for t, _ in spec)
    acc_ref = rest[-1]
    o_ref = rest[-2]
    bias_ref, res_ref = _epilogue_refs(rest[:-2], tokens)
    k_step = pl.program_id(3)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[0], b_ref[0], preferred_element_type=jnp.float32)

    @pl.when(k_step == n_k_steps - 1)
    def _flush():
        z = _apply_epilogue(acc_ref[...], spec, bias_ref, res_ref)
        o_ref[...] = z.astype(o_ref.dtype).reshape(o_ref.shape)


# ------------------------------------------------------------------- entries
_BSR_STATIC_ARGS = (
    "bm",
    "bk",
    "bn",
    "schedule",
    "epilogue",
    "out_dtype",
    "interpret",
)
_GROUPED_STATIC_ARGS = ("bm", "bk", "bn", "epilogue", "out_dtype", "interpret")


@functools.partial(jax.jit, static_argnames=_BSR_STATIC_ARGS)
def block_sparse_matmul_padded(
    cols: jax.Array,
    nnz: jax.Array,
    a: jax.Array,
    b: jax.Array,
    bias=None,
    residual=None,
    *,
    bm: int,
    bk: int,
    bn: int,
    schedule: str = "k_inner",
    epilogue=None,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """C = epilogue(sparse(A) @ B) over pre-padded operands.

    `cols` (gm, s_max) / `nnz` (gm,) are the layout's int32 index tables
    (see `BlockSparseLayout.device_arrays`); (bm, bk) must equal the
    layout block shape and all dims must be pre-padded to block
    multiples.  `epilogue` is a static `Epilogue.spec` tuple or legacy
    token string, as in the dense kernels.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (
        f"operands must be pre-padded to block multiples: "
        f"{(m, k, n)} vs {(bm, bk, bn)}"
    )
    gm, gn = m // bm, n // bn
    s_steps = cols.shape[1]
    assert cols.shape == (gm, s_steps) and nnz.shape == (gm,), (
        cols.shape,
        nnz.shape,
        gm,
    )
    spec = epilogue_mod.normalize_spec(epilogue)
    tokens = tuple(t for t, _ in spec)

    operands = [a, b]
    if "bias" in tokens:
        assert bias is not None and bias.shape == (n,), (
            "epilogue names 'bias': pass a pre-padded (n,) vector"
        )
        operands.append(bias.reshape(1, n))
    if "residual" in tokens:
        assert residual is not None and residual.shape == (m, n), (
            "epilogue names 'residual': pass a pre-padded (m, n) array"
        )
        operands.append(residual)
    pipelined = [
        ((bm, bk), a.dtype),
        ((bk, bn), b.dtype),
        ((bm, bn), out_dtype),
        *epilogue_blocks(tokens, bias, residual, bm, bn),
    ]
    acc = ((bm, bn), jnp.float32)

    if schedule == "k_inner":
        grid = (gm, gn, s_steps)
        in_specs = [
            pl.BlockSpec((bm, bk), lambda i, j, s, cols, nnz: (i, cols[i, s])),
            pl.BlockSpec((bk, bn), lambda i, j, s, cols, nnz: (cols[i, s], j)),
        ]
        if "bias" in tokens:
            in_specs.append(pl.BlockSpec((1, bn), lambda i, j, s, cols, nnz: (0, j)))
        if "residual" in tokens:
            in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, s, cols, nnz: (i, j)))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, s, cols, nnz: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        )
        return pl.pallas_call(
            functools.partial(_bsr_k_inner_kernel, spec=spec, s_steps=s_steps),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
            compiler_params=compiler_params(
                ("parallel", "parallel", "arbitrary"),
                pipelined=pipelined,
                resident=[acc, acc],
            ),
            interpret=interpret,
        )(cols, nnz, *operands)

    if schedule == "a_resident":
        # grid (m, s, n): n innermost — the nonzero A block pinned
        # across the whole n sweep, streamed exactly once.
        grid = (gm, s_steps, gn)
        in_specs = [
            pl.BlockSpec((bm, bk), lambda i, s, j, cols, nnz: (i, cols[i, s])),
            pl.BlockSpec((bk, bn), lambda i, s, j, cols, nnz: (cols[i, s], j)),
        ]
        if "bias" in tokens:
            in_specs.append(pl.BlockSpec((1, bn), lambda i, s, j, cols, nnz: (0, j)))
        if "residual" in tokens:
            in_specs.append(pl.BlockSpec((bm, bn), lambda i, s, j, cols, nnz: (i, j)))
        out_map, inner_pos, n_inner = (lambda i, s, j, cols, nnz: (i, j)), 1, gn
        row_axis = 0
    elif schedule == "b_resident":
        # grid (n, s, m): m innermost (see module docstring on residency).
        grid = (gn, s_steps, gm)
        in_specs = [
            pl.BlockSpec((bm, bk), lambda j, s, i, cols, nnz: (i, cols[i, s])),
            pl.BlockSpec((bk, bn), lambda j, s, i, cols, nnz: (cols[i, s], j)),
        ]
        if "bias" in tokens:
            in_specs.append(pl.BlockSpec((1, bn), lambda j, s, i, cols, nnz: (0, j)))
        if "residual" in tokens:
            in_specs.append(pl.BlockSpec((bm, bn), lambda j, s, i, cols, nnz: (i, j)))
        out_map, inner_pos, n_inner = (lambda j, s, i, cols, nnz: (i, j)), 0, gm
        row_axis = 2
    else:
        raise ValueError(f"unknown schedule {schedule!r}")

    scratch, resident = [], [acc]
    if s_steps > 1:
        out_map = held_until_last(out_map, s_steps, inner_pos)
        scratch = [pltpu.VMEM((n_inner, bm, bn), jnp.float32)]
        resident.append(((n_inner, bm, bn), jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), out_map),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        functools.partial(
            _bsr_resident_kernel,
            spec=spec,
            s_steps=s_steps,
            row_axis=row_axis,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=compiler_params(
            ("parallel", "arbitrary", "arbitrary"),
            pipelined=pipelined,
            resident=resident,
        ),
        interpret=interpret,
    )(cols, nnz, *operands)


@functools.partial(jax.jit, static_argnames=_GROUPED_STATIC_ARGS)
def grouped_matmul_padded(
    a: jax.Array,
    b: jax.Array,
    residual=None,
    *,
    bm: int,
    bk: int,
    bn: int,
    epilogue=None,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """C[g] = epilogue(A[g] @ B[g]): per-group rhs, K-inner, group dim
    leading the grid as an extra parallel dimension.

    The MoE expert-GEMM fast path (block-diagonal structure, regular
    index maps).  Epilogue ops: scale / act / residual (residual shaped
    (groups, m, n)); bias is rejected upstream in `ops.grouped_matmul`.
    """
    g, m, k = a.shape
    g2, k2, n = b.shape
    assert g == g2 and k == k2, (a.shape, b.shape)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (
        f"operands must be pre-padded to block multiples: "
        f"{(m, k, n)} vs {(bm, bk, bn)}"
    )
    spec = epilogue_mod.normalize_spec(epilogue)
    tokens = tuple(t for t, _ in spec)
    assert "bias" not in tokens, "grouped epilogue cannot name 'bias'"
    gm, gn, gk = m // bm, n // bn, k // bk

    operands = [a, b]
    in_specs = [
        pl.BlockSpec((1, bm, bk), lambda g_, i, j, kk: (g_, i, kk)),
        pl.BlockSpec((1, bk, bn), lambda g_, i, j, kk: (g_, kk, j)),
    ]
    if "residual" in tokens:
        assert residual is not None and residual.shape == (g, m, n)
        operands.append(residual)
        in_specs.append(pl.BlockSpec((1, bm, bn), lambda g_, i, j, kk: (g_, i, j)))

    return pl.pallas_call(
        functools.partial(_grouped_k_inner_kernel, spec=spec, n_k_steps=gk),
        grid=(g, gm, gn, gk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn), lambda g_, i, j, kk: (g_, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=compiler_params(
            ("parallel", "parallel", "parallel", "arbitrary"),
            pipelined=[
                ((bm, bk), a.dtype),
                ((bk, bn), b.dtype),
                ((bm, bn), out_dtype),
                *epilogue_blocks(tokens, None, residual, bm, bn),
            ],
            resident=[((bm, bn), jnp.float32)] * 2,
        ),
        interpret=interpret,
    )(*operands)
