"""Flash attention (blockwise online-softmax) Pallas TPU kernel.

Supports the model zoo's attention variants in one kernel:
  * causal masking,
  * sliding-window (local) attention  — gemma2 / recurrentgemma local layers,
  * logit soft-capping               — gemma2,
  * GQA via BlockSpec head-index mapping (kv head = q head // group), so K/V
    are never materialized per-q-head.

Grid: (batch, q_heads, q_blocks, kv_blocks) with the kv dimension sequential;
running (max, sum, acc) state lives in VMEM scratch.  Fully-masked kv blocks
(beyond the causal frontier or outside the window) are skipped with pl.when —
the kernel-level analogue of not emitting vertices for empty tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale: float, causal: bool, window: int | None,
               softcap: float, bq: int, bkv: int, n_kv_steps: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bkv
    # Block-level reachability: skip blocks with no unmasked entry.
    reachable = jnp.bool_(True)
    if causal:
        reachable = jnp.logical_and(reachable, k_start <= q_start + bq - 1)
    if window is not None:
        # the oldest kv any row of this q block can see belongs to its oldest
        # row: col > q_start - window; block overlaps iff its newest col does.
        reachable = jnp.logical_and(
            reachable, k_start + bkv - 1 > q_start - window)

    @pl.when(reachable)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (bkv, d)
        v = v_ref[0, 0].astype(jnp.float32)            # (bkv, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = jnp.ones((bq, bkv), dtype=bool)
        if causal:
            mask &= cols <= rows
        if window is not None:
            mask &= cols > rows - window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]                            # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_kv_steps - 1)
    def _flush():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "bq", "bkv", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    softcap: float = 0.0, scale: float | None = None,
                    bq: int = 128, bkv: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q (B,Hq,S,D); k,v (B,Hkv,S,D), Hq % Hkv == 0; S % bq == S % bkv == 0."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    assert sq % bq == 0 and skv % bkv == 0, (sq, bq, skv, bkv)
    gq, gkv = sq // bq, skv // bkv
    scale = scale if scale is not None else d ** -0.5

    # Flatten batch into the grid's first dim; heads second.
    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, bq=bq, bkv=bkv, n_kv_steps=gkv)
    return pl.pallas_call(
        kernel,
        grid=(b, hq, gq, gkv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bb, h, i, j: (bb, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda bb, h, i, j, g=group: (bb, h // g, j, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda bb, h, i, j, g=group: (bb, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda bb, h, i, j: (bb, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
