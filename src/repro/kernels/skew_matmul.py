"""Blocked TPU matmul kernels: a planner-selected *schedule family*.

This is the paper's object of study, TPU-native: a matmul whose
work-decomposition (block shapes, grid, loop order) is *explicitly
parameterized* so the skew-aware planner (repro.core.planner) controls it,
exactly as Poplar's AMP knob controls the vertex decomposition on the IPU.

Schedules (mirroring costmodel.SCHEDULES — grid loop order decides which
operand is re-streamed and which stays VMEM-resident):

  "k_inner"    — grid (m, n, k), K innermost and sequential; a VMEM fp32
                 scratch accumulates across K and the output block is written
                 once on the last K step.  A is revisited per n-block, B per
                 m-block: the C-write-once / A,B-revisit pattern.
  "a_resident" — grid (m, k, n), N innermost and sequential.  The A block is
                 pinned in VMEM across the whole n sweep (streamed exactly
                 once).  While gk > 1 the partial products of the whole
                 (bm, n) row strip accumulate in an fp32 VMEM scratch.  The
                 planner picks this for right-skewed (m << n) shapes — the
                 LM-head / vocab-projection class — where re-streaming A per
                 n-block is the dominant waste.
  "b_resident" — grid (n, k, m), M innermost; the mirror image.  B streamed
                 once; chosen for left-skewed (m >> n) shapes.

  A batched-grid variant (skew_matmul_batched_padded) puts a leading batch
  dim in the grid as an extra parallel dimension instead of folding it into
  m — the planner selects it when folding would straddle batch boundaries
  with badly padded row blocks.

Fused epilogues: every schedule can fuse ``out = act(scale * acc + bias) +
residual`` into the last-K flush (act in {gelu, silu}), so linear layers
stop paying a separate elementwise HBM pass.  ``epilogue`` is a *static
spec*: the hashable tuple from `Epilogue.spec` (the structured surface in
repro.core.epilogue — how ops.py calls in) or a legacy underscore-joined
token string, e.g. "bias_gelu"; the bias / residual operands must be passed
iff named.  The op semantics live in ONE table (epilogue.EPILOGUE_OPS)
shared with the XLA backend and the jnp oracle.

Note on the resident schedules: on the TPU an output block is written back
when its block index changes and is never read back, so a kernel must not
accumulate through an output block it revisits non-consecutively.  With
gk > 1 the resident kernels keep the partial sums of the whole inner strip
in an fp32 scratch, and their output index map holds the block index still
until the last k step, so each output block is written back once.  When
gk == 1 (the case the planner picks at real sizes: the whole contraction
in one block) there is no revisit at all.

Every kernel states its scoped-VMEM limit (`compiler_params`): Mosaic's
default limit is far below the blocks the planner admits.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The epilogue op table + spec normalization live in core.epilogue so the
# kernel, the XLA backend and the jnp oracle share one definition.
from repro.core import epilogue as epilogue_mod

# Legacy re-export for kernel-level callers of the string surface.
from repro.core.skewmm import parse_epilogue  # noqa: E402, F401

# Physical VMEM of one TPU v5e TensorCore.  Mosaic compiles a kernel under
# a scoped-VMEM limit far below it unless the kernel asks for more.
VMEM_CAPACITY_BYTES = 128 * 1024**2
# Never ask for less than Mosaic's own default limit.
_VMEM_FLOOR_BYTES = 32 * 1024**2
# Mosaic's internal scratch, on top of the buffers the kernel names.
_VMEM_MARGIN_BYTES = 4 * 1024**2


def vmem_buffer_bytes(shape, dtype) -> int:
    """VMEM bytes of one buffer: its minor two dims padded to the
    (sublane, lane) tile of its dtype (8 x 128 words, packed for narrow
    dtypes)."""
    itemsize = jnp.dtype(dtype).itemsize
    *lead, rows, cols = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    sub = 8 * max(1, 4 // itemsize)
    n = -(-rows // sub) * sub * (-(-cols // 128) * 128) * itemsize
    for d in lead:
        n *= d
    return n


def compiler_params(semantics, *, pipelined,
                    resident=()) -> pltpu.CompilerParams:
    """CompilerParams whose scoped-VMEM limit covers what the kernel
    allocates.

    `pipelined` lists the (block shape, dtype) of every input and output
    block; Pallas double-buffers each.  `resident` lists single buffers:
    scratch, and the fp32 values the body materializes (a dot result).
    A kernel that cannot fit the chip's VMEM raises here, at trace time,
    instead of failing inside the compiler.
    """
    need = (2 * sum(vmem_buffer_bytes(s, d) for s, d in pipelined)
            + sum(vmem_buffer_bytes(s, d) for s, d in resident))
    limit = max(_VMEM_FLOOR_BYTES, need + _VMEM_MARGIN_BYTES)
    if limit > VMEM_CAPACITY_BYTES:
        raise ValueError(
            f"kernel needs {need} B of VMEM plus a {_VMEM_MARGIN_BYTES} "
            f"B margin, more than the {VMEM_CAPACITY_BYTES} B of one "
            f"TPU v5e core")
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=limit)


def epilogue_blocks(tokens, bias, residual, bm: int, bn: int) -> list:
    """(block shape, dtype) of the epilogue operands a kernel streams."""
    out = []
    if "bias" in tokens:
        out.append(((1, bn), bias.dtype))
    if "residual" in tokens:
        out.append(((bm, bn), residual.dtype))
    return out


def _apply_epilogue(z, spec, bias_ref, res_ref):
    """Apply the static spec at accumulator (f32) width via the shared
    op table; array operands are read out of their pallas refs here."""
    operands = {}
    if bias_ref is not None:
        operands["bias"] = bias_ref[...]
    if res_ref is not None:
        operands["residual"] = res_ref[...]
    return epilogue_mod.apply_spec(z, spec, operands)


def _epilogue_refs(refs, tokens):
    """Split kernel refs [a, b, (bias), (residual)] after the operands."""
    it = iter(refs)
    bias_ref = next(it) if "bias" in tokens else None
    res_ref = next(it) if "residual" in tokens else None
    return bias_ref, res_ref


def strip_accumulate(partial, strip_ref, k_step, n_k_steps: int, flush):
    """Accumulate `partial` into slot `pl.program_id(2)` of an fp32 strip
    scratch over the k steps (grid axis 1); on the last step hand the
    full sum to `flush`.  Shared by the dense and block-sparse resident
    schedules, whose inner grid axis (2) indexes the strip."""
    slot = pl.program_id(2)

    @pl.when(k_step == 0)
    def _first():
        strip_ref[slot] = partial

    @pl.when(jnp.logical_and(k_step > 0, k_step < n_k_steps - 1))
    def _middle():
        strip_ref[slot] += partial

    @pl.when(k_step == n_k_steps - 1)
    def _last():
        flush(strip_ref[slot] + partial)


def held_until_last(index_map, n_k_steps: int, inner_pos: int):
    """Wrap an output index map of a (outer, k, inner) grid so the block
    index stays at inner block 0 until the last k step.  The output block
    then changes — and is written back — only after its one real write."""
    def held(*idx):
        blocks = list(index_map(*idx))
        last = idx[1] == n_k_steps - 1
        blocks[inner_pos] = jnp.where(last, blocks[inner_pos], 0)
        return tuple(blocks)
    return held


# --------------------------------------------------------------- kernel bodies
def _k_inner_kernel(*refs, spec, n_k_steps: int, k_axis: int):
    tokens = tuple(t for t, _ in spec)
    a_ref, b_ref, *rest = refs
    acc_ref = rest[-1]
    o_ref = rest[-2]
    bias_ref, res_ref = _epilogue_refs(rest[:-2], tokens)
    k_step = pl.program_id(k_axis)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    a = a[0] if a.ndim == 3 else a          # batched-grid: (1, bm, bk) block
    acc_ref[...] += jnp.dot(a, b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k_step == n_k_steps - 1)
    def _flush():
        z = _apply_epilogue(acc_ref[...], spec, bias_ref, res_ref)
        o_ref[...] = z.astype(o_ref.dtype).reshape(o_ref.shape)


def _resident_kernel(*refs, spec, n_k_steps: int):
    """Shared body for a_resident / b_resident: k is the *middle* grid dim
    and the inner dim (grid axis 2) indexes the fp32 strip scratch that
    carries the partial sums while gk > 1."""
    tokens = tuple(t for t, _ in spec)
    a_ref, b_ref, *rest = refs
    if n_k_steps > 1:
        *rest, strip_ref = rest
    o_ref = rest[-1]
    bias_ref, res_ref = _epilogue_refs(rest[:-1], tokens)
    partial = jnp.dot(a_ref[...], b_ref[...],
                      preferred_element_type=jnp.float32)

    def flush(acc):
        z = _apply_epilogue(acc, spec, bias_ref, res_ref)
        o_ref[...] = z.astype(o_ref.dtype)

    if n_k_steps == 1:
        flush(partial)
        return
    strip_accumulate(partial, strip_ref, pl.program_id(1), n_k_steps, flush)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "schedule",
                                             "epilogue", "out_dtype",
                                             "interpret"))
def skew_matmul_padded(a: jax.Array, b: jax.Array, bias=None, residual=None,
                       *, bm: int, bk: int, bn: int,
                       schedule: str = "k_inner", epilogue=None,
                       out_dtype=jnp.float32,
                       interpret: bool = False) -> jax.Array:
    """C = epilogue(A @ B) where block shapes divide the (pre-padded) dims.

    `epilogue` is a static spec: an `Epilogue.spec` tuple or a legacy
    token string (both hashable, so they key the jit cache).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (
        f"operands must be pre-padded to block multiples: "
        f"{(m, k, n)} vs {(bm, bk, bn)}")
    spec = epilogue_mod.normalize_spec(epilogue)
    tokens = tuple(t for t, _ in spec)
    gm, gn, gk = m // bm, n // bn, k // bk

    operands = [a, b]
    if "bias" in tokens:
        assert bias is not None and bias.shape == (n,), (
            "epilogue names 'bias': pass a pre-padded (n,) vector")
        operands.append(bias.reshape(1, n))
    if "residual" in tokens:
        assert residual is not None and residual.shape == (m, n), (
            "epilogue names 'residual': pass a pre-padded (m, n) array")
        operands.append(residual)
    pipelined = [((bm, bk), a.dtype), ((bk, bn), b.dtype),
                 ((bm, bn), out_dtype),
                 *epilogue_blocks(tokens, bias, residual, bm, bn)]
    acc = ((bm, bn), jnp.float32)

    if schedule == "k_inner":
        grid = (gm, gn, gk)
        in_specs = [
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ]
        if "bias" in tokens:
            in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        if "residual" in tokens:
            in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)))
        return pl.pallas_call(
            functools.partial(_k_inner_kernel, spec=spec, n_k_steps=gk,
                              k_axis=2),
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            compiler_params=compiler_params(
                ("parallel", "parallel", "arbitrary"), pipelined=pipelined,
                resident=[acc, acc]),
            interpret=interpret,
        )(*operands)

    if schedule == "a_resident":
        # grid (m, k, n): n innermost — A block pinned across the n sweep.
        grid = (gm, gk, gn)
        in_specs = [
            pl.BlockSpec((bm, bk), lambda i, kk, j: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, kk, j: (kk, j)),
        ]
        if "bias" in tokens:
            in_specs.append(pl.BlockSpec((1, bn), lambda i, kk, j: (0, j)))
        if "residual" in tokens:
            in_specs.append(pl.BlockSpec((bm, bn), lambda i, kk, j: (i, j)))
        out_map, inner_pos, n_inner = (lambda i, kk, j: (i, j)), 1, gn
    elif schedule == "b_resident":
        # grid (n, k, m): m innermost — B block pinned across the m sweep.
        grid = (gn, gk, gm)
        in_specs = [
            pl.BlockSpec((bm, bk), lambda j, kk, i: (i, kk)),
            pl.BlockSpec((bk, bn), lambda j, kk, i: (kk, j)),
        ]
        if "bias" in tokens:
            in_specs.append(pl.BlockSpec((1, bn), lambda j, kk, i: (0, j)))
        if "residual" in tokens:
            in_specs.append(pl.BlockSpec((bm, bn), lambda j, kk, i: (i, j)))
        out_map, inner_pos, n_inner = (lambda j, kk, i: (i, j)), 0, gm
    else:
        raise ValueError(f"unknown schedule {schedule!r}")

    scratch, resident = [], [acc]
    if gk > 1:
        out_map = held_until_last(out_map, gk, inner_pos)
        scratch = [pltpu.VMEM((n_inner, bm, bn), jnp.float32)]
        resident.append(((n_inner, bm, bn), jnp.float32))
    return pl.pallas_call(
        functools.partial(_resident_kernel, spec=spec, n_k_steps=gk),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), out_map),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=scratch,
        compiler_params=compiler_params(
            ("parallel", "arbitrary", "arbitrary"), pipelined=pipelined,
            resident=resident),
        interpret=interpret,
    )(*operands)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "epilogue",
                                             "out_dtype", "interpret"))
def skew_matmul_batched_padded(a: jax.Array, b: jax.Array, bias=None,
                               residual=None, *, bm: int, bk: int, bn: int,
                               epilogue=None,
                               out_dtype=jnp.float32,
                               interpret: bool = False) -> jax.Array:
    """C[nb] = epilogue(A[nb] @ B): leading batch dim in the grid (K-inner).

    The planner selects this over folding the batch into m when folding
    would straddle batch boundaries with a badly padded row block.
    """
    nb, m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (
        f"operands must be pre-padded to block multiples: "
        f"{(m, k, n)} vs {(bm, bk, bn)}")
    spec = epilogue_mod.normalize_spec(epilogue)
    tokens = tuple(t for t, _ in spec)
    gm, gn, gk = m // bm, n // bn, k // bk

    operands = [a, b]
    in_specs = [
        pl.BlockSpec((1, bm, bk), lambda nb_, i, j, kk: (nb_, i, kk)),
        pl.BlockSpec((bk, bn), lambda nb_, i, j, kk: (kk, j)),
    ]
    if "bias" in tokens:
        assert bias is not None and bias.shape == (n,)
        operands.append(bias.reshape(1, n))
        in_specs.append(pl.BlockSpec((1, bn), lambda nb_, i, j, kk: (0, j)))
    if "residual" in tokens:
        assert residual is not None and residual.shape == (nb, m, n)
        operands.append(residual)
        in_specs.append(
            pl.BlockSpec((1, bm, bn), lambda nb_, i, j, kk: (nb_, i, j)))
    pipelined = [((bm, bk), a.dtype), ((bk, bn), b.dtype),
                 ((bm, bn), out_dtype),
                 *epilogue_blocks(tokens, bias, residual, bm, bn)]
    acc = ((bm, bn), jnp.float32)

    return pl.pallas_call(
        functools.partial(_k_inner_kernel, spec=spec, n_k_steps=gk,
                          k_axis=3),
        grid=(nb, gm, gn, gk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn), lambda nb_, i, j, kk: (nb_, i, j)),
        out_shape=jax.ShapeDtypeStruct((nb, m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=compiler_params(
            ("parallel", "parallel", "parallel", "arbitrary"),
            pipelined=pipelined, resident=[acc, acc]),
        interpret=interpret,
    )(*operands)
