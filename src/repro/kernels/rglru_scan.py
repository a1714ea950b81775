"""RG-LRU (Real-Gated Linear Recurrent Unit) chunked-scan Pallas TPU kernel.

Griffin/RecurrentGemma's recurrence:

    a_t = exp(-c * softplus(Lambda) * sigmoid(r_t))
    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (sigmoid(i_t) * x_t)

Elementwise (VPU) work with a sequential dependence.  The kernel processes
the sequence in chunks carried through VMEM scratch; within a chunk the
recurrence h_t = a_t h_{t-1} + b_t is solved with a Hillis-Steele scan over
the associative composition of first-order recurrences,

    (a2, b2) o (a1, b1) = (a1*a2, a2*b1 + b2),

log2(chunk) vectorized rounds, numerically stable (a in [0,1], no exp of
positive cumulants — the naive prefix form exp(-cumsum(log a)) overflows for
the strong-decay gate regimes RG-LRU actually visits).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(x_ref, r_ref, i_ref, lam_ref, y_ref, h_ref, *, c: float,
                  chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)              # (Q, D)
    r = jax.nn.sigmoid(r_ref[0, 0].astype(jnp.float32))
    gate_i = jax.nn.sigmoid(i_ref[0, 0].astype(jnp.float32))
    lam = jax.nn.softplus(lam_ref[...].astype(jnp.float32))  # (D,)

    log_a = -c * r * lam[None, :]                    # (Q, D), <= 0
    a = jnp.exp(log_a)
    mult = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    b = mult * gate_i * x                            # (Q, D)

    # Hillis-Steele inclusive scan of (a, b) under recurrence composition.
    offset = 1
    while offset < chunk:
        a_prev = jnp.pad(a[:-offset], ((offset, 0), (0, 0)),
                         constant_values=1.0)
        b_prev = jnp.pad(b[:-offset], ((offset, 0), (0, 0)))
        b = a * b_prev + b
        a = a * a_prev
        offset *= 2

    h0 = h_ref[...]                                  # (1, D)
    h_all = b + a * h0                               # (Q, D): h_t
    y_ref[0, 0] = h_all.astype(y_ref.dtype)
    h_ref[...] = h_all[chunk - 1:chunk, :]           # carry (1, D)


@functools.partial(jax.jit, static_argnames=("c", "chunk", "interpret"))
def rglru_scan(x: jax.Array, r_gate: jax.Array, i_gate: jax.Array,
               a_param: jax.Array, *, c: float = 8.0, chunk: int = 128,
               interpret: bool = False) -> jax.Array:
    """x, r_gate, i_gate (B, L, D) pre-sigmoid logits; a_param (D,)."""
    bsz, length, d = x.shape
    assert length % chunk == 0
    n_chunks = length // chunk
    xr = x.reshape(bsz, n_chunks, chunk, d)
    rr = r_gate.reshape(bsz, n_chunks, chunk, d)
    ir = i_gate.reshape(bsz, n_chunks, chunk, d)

    out = pl.pallas_call(
        functools.partial(_rglru_kernel, c=c, chunk=chunk),
        grid=(bsz, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, d), lambda bb, cc: (bb, cc, 0, 0)),
            pl.BlockSpec((1, 1, chunk, d), lambda bb, cc: (bb, cc, 0, 0)),
            pl.BlockSpec((1, 1, chunk, d), lambda bb, cc: (bb, cc, 0, 0)),
            pl.BlockSpec((d,), lambda bb, cc: (0,)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, d), lambda bb, cc: (bb, cc, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, n_chunks, chunk, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xr, rr, ir, a_param)
    return out.reshape(bsz, length, d)
