"""Split-K / tree-reduction GEMV kernels for the extreme-skew decode regime.

Decode is the paper's right-skew limit: m = a handful of rows against tens
of thousands of cache columns.  No dense loop order can feed a matrix
engine there — a (8, bk) x (bk, bn) pass fills 8 of 128 MXU rows no matter
which operand stays resident.  The split-K family spends the hardware the
way the IPU's tile fabric wants to be spent at these shapes (Jia et al.
2019's reduction-tree observation): parallelize over K *and* N instead.

Two passes (two pallas_calls under one jit):

  pass 1 — grid (k_splits, n_blocks): each step computes one fp32 partial
           product A[:, s*bk:(s+1)*bk] @ B[s*bk:(s+1)*bk, j*bn:(j+1)*bn]
           and writes it to its own slot of a (k_splits, m, n) accumulator.
           Every output slot is written exactly once, so both grid dims are
           parallel — this is the K-parallelism the cost model prices at
           `chip.gemv_splitk_frac`.
  pass 2 — grid (n_blocks,): loads the (k_splits, m, bn) partial slab and
           folds it with a static pairwise (binary-tree) reduction, then
           applies the structured epilogue ONCE at fp32 width and casts to
           the output dtype.  The PR 2 epilogue table (core.epilogue) is
           shared with the dense kernels and the jnp oracle.

Determinism: the pairwise fold is a fixed static tree per k_splits, so the
floating-point summation order is a pure function of the split count — and
when the additions are exact (integer-valued operands, or any case without
rounding) the result is bitwise identical across split counts and to the
XLA oracle (tested in tests/test_gemv.py).

The m dimension is NOT blocked: callers pass `bm = full padded m` plans
(planner invariant — splitting a handful of rows only shrinks row fill
further), and ops.py pads m to the sublane granule before calling in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import epilogue as epilogue_mod
from repro.kernels.skew_matmul import (_apply_epilogue, _epilogue_refs,
                                       compiler_params, epilogue_blocks)


def tree_sum(parts):
    """Static pairwise fold over the leading axis: a fixed binary tree.

    Handles any length (odd tails carry to the next level unchanged), so
    the reduction depth is ceil(log2(k_splits)) — the "tree" in
    split-K/tree-reduction.  Shape is static, so this unrolls at trace
    time into a fixed summation order.
    """
    while parts.shape[0] > 1:
        half = parts.shape[0] // 2
        folded = parts[:half] + parts[half:2 * half]
        if parts.shape[0] % 2:
            folded = jnp.concatenate([folded, parts[2 * half:]], axis=0)
        parts = folded
    return parts[0]


def _partial_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = jnp.dot(a_ref[...], b_ref[...],
                         preferred_element_type=jnp.float32,
                         ).reshape(o_ref.shape)


def _reduce_kernel(*refs, spec, n_splits: int):
    tokens = tuple(t for t, _ in spec)
    p_ref, *rest = refs
    o_ref = rest[-1]
    bias_ref, res_ref = _epilogue_refs(rest[:-1], tokens)
    acc = tree_sum(p_ref[...])
    z = _apply_epilogue(acc, spec, bias_ref, res_ref)
    o_ref[...] = z.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "bn", "epilogue",
                                             "out_dtype", "interpret"))
def gemv_splitk_padded(a: jax.Array, b: jax.Array, bias=None, residual=None,
                       *, bk: int, bn: int, epilogue=None,
                       out_dtype=jnp.float32,
                       interpret: bool = False) -> jax.Array:
    """C = epilogue(A @ B) via split-K partials + one tree-reduce pass.

    Block shapes must divide the (pre-padded) K and N dims; the whole m
    extent rides in every block.  `epilogue` is the same static spec the
    dense kernels take and is applied once, after the final reduce.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert k % bk == 0 and n % bn == 0, (
        f"operands must be pre-padded to block multiples: "
        f"{(k, n)} vs {(bk, bn)}")
    spec = epilogue_mod.normalize_spec(epilogue)
    tokens = tuple(t for t, _ in spec)
    gk, gn = k // bk, n // bn

    # ---- pass 1: fp32 partial products, parallel over (k_splits, n).
    partials = pl.pallas_call(
        _partial_kernel,
        grid=(gk, gn),
        in_specs=[
            pl.BlockSpec((m, bk), lambda s, j: (0, s)),
            pl.BlockSpec((bk, bn), lambda s, j: (s, j)),
        ],
        out_specs=pl.BlockSpec((1, m, bn), lambda s, j: (s, 0, j)),
        out_shape=jax.ShapeDtypeStruct((gk, m, n), jnp.float32),
        compiler_params=compiler_params(
            ("parallel", "parallel"),
            pipelined=[((m, bk), a.dtype), ((bk, bn), b.dtype),
                       ((m, bn), jnp.float32)],
            resident=[((m, bn), jnp.float32)]),
        interpret=interpret,
    )(a, b)

    # ---- pass 2: tree-reduce the splits, fused epilogue at the flush.
    operands = [partials]
    in_specs = [pl.BlockSpec((gk, m, bn), lambda j: (0, 0, j))]
    if "bias" in tokens:
        assert bias is not None and bias.shape == (n,), (
            "epilogue names 'bias': pass a pre-padded (n,) vector")
        operands.append(bias.reshape(1, n))
        in_specs.append(pl.BlockSpec((1, bn), lambda j: (0, j)))
    if "residual" in tokens:
        assert residual is not None and residual.shape == (m, n), (
            "epilogue names 'residual': pass a pre-padded (m, n) array")
        operands.append(residual)
        in_specs.append(pl.BlockSpec((m, bn), lambda j: (0, j)))

    return pl.pallas_call(
        functools.partial(_reduce_kernel, spec=spec, n_splits=gk),
        grid=(gn,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((m, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=compiler_params(
            ("parallel",),
            pipelined=[((gk, m, bn), jnp.float32), ((m, bn), out_dtype),
                       *epilogue_blocks(tokens, bias, residual, m, bn)],
            # the tree's levels: gk/2 + gk/4 + ... partial slabs
            resident=[((gk, m, bn), jnp.float32)]),
        interpret=interpret,
    )(*operands)
