"""Shared layer primitives.  Every dense contraction routes through
repro.core.skewmm so the paper's planner sees the full workload."""

from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.core import skewmm
from repro.core.epilogue import Epilogue


def dtype_of(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


# ------------------------------------------------------------------ init
def linear_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (d_in, d_out), jnp.float32)
            * (d_in ** -0.5)).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02
            ).astype(dtype)


# ------------------------------------------------------------------ norms
def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    """Variance reduced in fp32 (fused into the reduce); scale applied in
    the native dtype — §Perf iteration B1.  (B2, computing the variance as
    a bf16 self-dot with fp32 accumulation, measured WORSE — see
    EXPERIMENTS.md §Perf — and was reverted.)"""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    scale = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * scale * (1.0 + w).astype(x.dtype)


# ------------------------------------------------------------------ rope
def rope_freqs(positions: jax.Array, dim: int, theta: float):
    """positions (..., S) -> cos, sin (..., S, dim//2), fp32."""
    half = dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x (B, S, H, D) with rope on the full last dim (half-split convention).

    cos/sin are (B, S, D/2) or (S, D/2); broadcast over heads.  Angles are
    computed in fp32 (rope_freqs); the rotation itself runs in x's dtype
    (bf16-safe: it is an isometry applied once, no error compounding).
    """
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == x.ndim - 2:          # (S, half) -> (S, 1, half)
        cos, sin = cos[:, None, :], sin[:, None, :]
    else:                               # (B, S, half) -> (B, S, 1, half)
        cos, sin = cos[..., None, :], sin[..., None, :]
    cos, sin = cos.astype(x.dtype), sin.astype(x.dtype)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos(positions: jax.Array, d: int) -> jax.Array:
    half = d // 2
    inv = 10000.0 ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ------------------------------------------------------------------ MLP
def init_mlp(key, cfg, d_ff: int | None = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 3)
    if cfg.mlp_type == "swiglu":
        return {"w_gate": linear_init(ks[0], d, f, dt),
                "w_up": linear_init(ks[1], d, f, dt),
                "w_down": linear_init(ks[2], f, d, dt)}
    return {"w_up": linear_init(ks[0], d, f, dt),
            "w_down": linear_init(ks[1], f, d, dt)}


def mlp(x: jax.Array, p: dict, cfg, residual: jax.Array | None = None
        ) -> jax.Array:
    """MLP with the activation fused into the up/gate projection's epilogue
    and (optionally) the block's residual add fused into the down
    projection — each linear is a single planned kernel, no separate
    elementwise HBM pass.  The epilogue runs at fp32 accumulator width
    before the one cast to the native dtype (§Perf iteration B1 still
    holds: matmuls accumulate fp32 inside skewmm).  Its device ops sit
    under the named scope `mlp`."""
    with jax.named_scope("mlp"):
        if cfg.mlp_type == "swiglu":
            g = skewmm.matmul(x, p["w_gate"], epilogue=Epilogue(act="silu"))
            u = skewmm.matmul(x, p["w_up"])
            h = g * u
        else:
            h = skewmm.matmul(x, p["w_up"], epilogue=Epilogue(act="gelu"))
        if residual is not None:
            return skewmm.matmul(h, p["w_down"],
                                 epilogue=Epilogue(residual=residual))
        return skewmm.matmul(h, p["w_down"])


# ------------------------------------------------- blockwise attention (jnp)
# Cost probes (launch.costprobe) force single-trip chunking so XLA's
# cost_analysis (which counts while-loop bodies once) sees the full extent.
CHUNK_OVERRIDE: tuple[int, int] | None = None


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int | None = None,
                        softcap: float = 0.0, scale: float | None = None,
                        q_positions: jax.Array | None = None,
                        kv_positions: jax.Array | None = None,
                        q_chunk: int = 512, kv_chunk: int = 1024) -> jax.Array:
    """Memory-efficient attention in pure JAX (O(S*chunk) activations).

    Shapes: q (B, Hq, Sq, D); k, v (B, Hkv, Skv, D) with Hq % Hkv == 0.
    Semantically identical to kernels.ref.attention_ref; used for the
    full-model CPU/dry-run path (the Pallas kernel is the TPU-runtime path).
    q_positions / kv_positions (defaults arange) drive causal/window masks so
    prefill-with-offset and ring caches reuse the same code.  Either may be
    1-D (shared across the batch) or 2-D (B, S) — per-row positions, the
    continuous-batching decode case where every live request sits at its
    own depth.  1-D positions broadcast, so the masks (and hence the
    outputs) are bit-identical to the pre-batched-positions behaviour.
    """
    if CHUNK_OVERRIDE is not None:
        q_chunk, kv_chunk = CHUNK_OVERRIDE
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    dv = v.shape[-1]                    # may differ from d (MLA)
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    qp = (jnp.arange(sq, dtype=jnp.int32) if q_positions is None
          else q_positions)
    kp = (jnp.arange(skv, dtype=jnp.int32) if kv_positions is None
          else kv_positions)
    # normalize positions to (B, S): per-row masks below, shared
    # positions just broadcast (identical values on every row).
    qp = jnp.broadcast_to(qp, (b, sq))
    kp = jnp.broadcast_to(kp, (b, skv))

    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    # pad seq dims to chunk multiples
    sq_p = -(-sq // q_chunk) * q_chunk
    skv_p = -(-skv // kv_chunk) * kv_chunk
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
        qp = jnp.pad(qp, ((0, 0), (0, sq_p - sq)), constant_values=2**30)
    if skv_p != skv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
        kp = jnp.pad(kp, ((0, 0), (0, skv_p - skv)), constant_values=-1)

    nq, nk = sq_p // q_chunk, skv_p // kv_chunk
    qc = q.reshape(b, hq, nq, q_chunk, d)
    kc = k.reshape(b, hkv, nk, kv_chunk, d)
    vc = v.reshape(b, hkv, nk, kv_chunk, dv)
    qpc = qp.reshape(b, nq, q_chunk)
    kpc = kp.reshape(b, nk, kv_chunk)

    def kv_step(carry, inp):
        m_prev, l_prev, acc, qi, qpi = carry
        kj, vj, kpj = inp                       # (B,Hkv,ck,D), (B,ck)
        kje = jnp.repeat(kj, group, axis=1)     # (B,Hq,ck,D)
        vje = jnp.repeat(vj, group, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", qi.astype(jnp.float32),
                       kje.astype(jnp.float32)) * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        # kv positions < 0 are invalid (padding / unfilled ring slots).
        mask = jnp.broadcast_to(kpj[:, None, :] >= 0,
                                (b, q_chunk, kv_chunk))
        if causal:
            mask &= kpj[:, None, :] <= qpi[:, :, None]
        if window is not None:
            mask &= kpj[:, None, :] > qpi[:, :, None] - window
        s = jnp.where(mask[:, None], s, -1e30)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask[:, None], p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bhqk,bhkd->bhqd", p,
                                       vje.astype(jnp.float32))
        return (m_new, l_new, acc, qi, qpi), None

    kv_step = jax.checkpoint(kv_step)
    kc_t = jnp.moveaxis(kc, 2, 0)
    vc_t = jnp.moveaxis(vc, 2, 0)
    kpc_t = jnp.moveaxis(kpc, 1, 0)

    def q_step(_, inp):
        qi, qpi = inp                           # (B,Hq,cq,D), (B,cq)
        init = (jnp.full((b, hq, q_chunk, 1), -1e30, jnp.float32),
                jnp.zeros((b, hq, q_chunk, 1), jnp.float32),
                jnp.zeros((b, hq, q_chunk, dv), jnp.float32),
                qi, qpi)
        (m, l, acc, _, _), _ = jax.lax.scan(kv_step, init,
                                            (kc_t, vc_t, kpc_t))
        out = acc / jnp.maximum(l, 1e-30)
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None,
                           (jnp.moveaxis(qc, 2, 0),
                            jnp.moveaxis(qpc, 1, 0)))
    out = jnp.moveaxis(outs, 0, 2).reshape(b, hq, sq_p, dv)
    return out[:, :, :sq]
