"""Mamba-2 SSD (state-space duality) chunked-scan Pallas TPU kernel.

The SSD algorithm splits the sequence into chunks: within a chunk the output
is a (masked, decay-weighted) quadratic attention-like matmul — MXU work,
and exactly the kind of skewed GEMM the paper studies ((Q x S) x (S x P)
with S=128 state dims) — while across chunks a small recurrent state
(P x S per head) is carried.  We carry the state in VMEM scratch across the
sequential chunk grid dimension.

Grid: (batch, heads, n_chunks), chunk dim sequential.  B/C are shared across
the heads of a group via BlockSpec head-index mapping (h // rep), mirroring
GQA in the attention kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, state_ref, *,
                chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    a = a_ref[0]                                     # () — this head's A_log
    x = x_ref[0, 0].astype(jnp.float32)              # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)            # (Q, 1)
    bm = b_ref[0, 0].astype(jnp.float32)             # (Q, S)
    cm = c_ref[0, 0].astype(jnp.float32)             # (Q, S)

    neg_a = -jnp.exp(a.astype(jnp.float32))          # A < 0
    da = dt[:, 0] * neg_a                            # (Q,)
    cum = jnp.cumsum(da)                             # (Q,) running log-decay
    xdt = x * dt                                     # (Q, P)

    # --- intra-chunk: masked decay attention  G[i,j] = exp(cum_i - cum_j)
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = rows >= cols
    decay = jnp.exp(cum[:, None] - cum[None, :])
    g = jnp.where(causal, decay, 0.0)
    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * g
    y_intra = jax.lax.dot_general(scores, xdt, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # --- inter-chunk: contribution of the carried state  (Q,S) @ (S,P)
    c_decay = cm * jnp.exp(cum)[:, None]             # (Q, S)
    y_inter = jax.lax.dot_general(c_decay, state_ref[...],
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # --- state update:
    # state' = e^{cum_last} state + sum_j e^{cum_last-cum_j} B_j (x dt)_j
    last = cum[chunk - 1]
    b_decay = bm * jnp.exp(last - cum)[:, None]      # (Q, S)
    state_ref[...] = state_ref[...] * jnp.exp(last) + jax.lax.dot_general(
        b_decay, xdt, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (S, P)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, a_log: jax.Array, b_mat: jax.Array,
             c_mat: jax.Array, *, chunk: int = 128,
             interpret: bool = False) -> jax.Array:
    """x (B,L,H,P), dt (B,L,H) positive, a_log (H,), b/c (B,L,G,S).

    L % chunk == 0.  Returns y (B,L,H,P).
    """
    bsz, length, h, p = x.shape
    g, s = b_mat.shape[2], b_mat.shape[3]
    assert h % g == 0 and length % chunk == 0
    rep = h // g
    n_chunks = length // chunk

    # layout: x -> (B,H,L,P); dt -> (B,H,L,1); b,c -> (B,G,L,S)
    xt = jnp.moveaxis(x, 2, 1)
    dtt = jnp.moveaxis(dt, 2, 1)[..., None]
    bt = jnp.moveaxis(b_mat, 2, 1)
    ct = jnp.moveaxis(c_mat, 2, 1)

    out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(bsz, h, n_chunks),
        in_specs=[
            pl.BlockSpec((1,), lambda bb, hh, cc: (hh,)),
            pl.BlockSpec((1, 1, chunk, p), lambda bb, hh, cc: (bb, hh, cc, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda bb, hh, cc: (bb, hh, cc, 0)),
            pl.BlockSpec((1, 1, chunk, s),
                         lambda bb, hh, cc, r=rep: (bb, hh // r, cc, 0)),
            pl.BlockSpec((1, 1, chunk, s),
                         lambda bb, hh, cc, r=rep: (bb, hh // r, cc, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p),
                               lambda bb, hh, cc: (bb, hh, cc, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, h, length, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((s, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(a_log, xt, dtt, bt, ct)
    return jnp.moveaxis(out, 1, 2)
