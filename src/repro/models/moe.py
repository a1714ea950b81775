"""Mixture-of-Experts layer: sort-based dispatch + grouped expert GEMMs.

Design notes (DESIGN.md §4): the classic Mesh-TF one-hot dispatch einsum
materializes a (tokens, E, capacity) tensor — at deepseek-v3 scale (E=256)
that is tens of TB and a non-starter.  We instead use the sort/gather
formulation: tokens are argsorted by expert id, packed into (E, capacity)
slots (capacity-dropped like Switch), the expert GEMMs run as *planned*
grouped matmuls (`repro.kernels.ops.grouped_matmul` — block-diagonal
structure, recorded into `plan_capture()` with schedule/blocks
provenance; the resolved `MatmulConfig` backend picks the grouped Pallas
kernel or the `jnp.einsum` fallback), sharded over the "model" axis = EP,
and results scatter-add back with the router weights.

The expert GEMMs are exactly the paper's skewed-MM regime (deepseek:
7168 -> 2048, strongly right-skewed per expert) — see DESIGN.md §5.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models import layers
from repro.models.layers import linear_init

# Capacity-slot accounting (serve.sched telemetry).  Slot counts are
# *static* — (E, capacity) comes from shapes, and the best case fill is
# min(T*k, E*cap) — so recording them is trace-safe and costs nothing at
# runtime.  Opt-in: benches and the serving scheduler enable it; training
# and plain forward passes leave the guard.health ledger untouched.
_TRACK_SLOTS = False


@contextlib.contextmanager
def track_capacity_slots():
    """Record moe_slots_total / moe_slots_filled / moe_slots_underfilled
    into guard.health for every MoE dispatch in scope."""
    global _TRACK_SLOTS
    prev = _TRACK_SLOTS
    _TRACK_SLOTS = True
    try:
        yield
    finally:
        _TRACK_SLOTS = prev


def tracking_capacity_slots() -> bool:
    """Is `track_capacity_slots` armed?  It records while tracing, so a
    caller that holds compiled programs runs eagerly while it is."""
    return _TRACK_SLOTS


def init_moe(key, cfg) -> dict:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    dt = layers.dtype_of(cfg)
    ks = jax.random.split(key, 5)

    def stack_init(k, d_in, d_out):
        keys = jax.random.split(k, e)
        return jnp.stack([linear_init(kk, d_in, d_out, dt) for kk in keys])

    p = {
        "router": (jax.random.normal(ks[0], (d, e), jnp.float32) * d ** -0.5
                   ).astype(jnp.float32),           # router kept fp32
        "w_gate": stack_init(ks[1], d, f),           # (E, D, F)
        "w_up": stack_init(ks[2], d, f),
        "w_down": stack_init(ks[3], f, d),           # (E, F, D)
    }
    if cfg.n_shared_experts:
        p["shared"] = layers.init_mlp(
            ks[4], cfg, d_ff=cfg.moe_d_ff * cfg.n_shared_experts)
    return p


def _capacity(n_tokens: int, cfg) -> int:
    c = int(n_tokens * cfg.n_experts_per_tok * cfg.capacity_factor
            / cfg.n_experts)
    return max(8, -(-c // 8) * 8)


def _dispatch_compute_combine(xf, p, cfg, *, n_local_experts: int,
                              expert_offset):
    """Route xf (T, D) to `n_local_experts` experts [offset, offset+n) and
    return their weighted contribution (T, D) fp32 + the router aux loss.

    Pure local math — used per-shard inside the shard_map path (where each
    model shard owns a contiguous expert slice and every token copy routes
    only to the local slice) and globally in the single-host fallback.
    """
    t, d = xf.shape
    e, k = cfg.n_experts, cfg.n_experts_per_tok
    logits = (xf.astype(jnp.float32) @ p["router"])          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_i = jax.lax.top_k(probs, k)                 # (T, K)
    gate_w = gate_w / jnp.maximum(
        jnp.sum(gate_w, axis=-1, keepdims=True), 1e-9)

    frac_tokens = jnp.mean(
        (jax.nn.one_hot(gate_i, e, dtype=jnp.float32)).sum(1), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = cfg.router_aux_coef * e * jnp.sum(frac_tokens * frac_probs)

    cap = _capacity(t, cfg)
    if _TRACK_SLOTS:
        from repro.guard import health as _health
        total = n_local_experts * cap
        filled = min(t * k, total)
        _health.record("moe_slots_total", total)
        _health.record("moe_slots_filled", filled)
        _health.record("moe_slots_underfilled", total - filled)
    flat_e = gate_i.reshape(-1)                              # (T*K,)
    flat_t = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    flat_w = gate_w.reshape(-1)
    # retarget to the local expert slice; out-of-slice -> dropped
    local_e = flat_e - expert_offset
    in_slice = (local_e >= 0) & (local_e < n_local_experts)
    local_e = jnp.where(in_slice, local_e, n_local_experts)
    order = jnp.argsort(local_e)
    se, st, sw = local_e[order], flat_t[order], flat_w[order]
    keep_slice = se < n_local_experts
    start = jnp.searchsorted(se, jnp.arange(n_local_experts), side="left")
    rank = jnp.arange(t * k, dtype=jnp.int32) - start[
        jnp.minimum(se, n_local_experts - 1)]
    keep = keep_slice & (rank < cap)
    slot = jnp.where(keep, se * cap + rank, n_local_experts * cap)

    gathered = xf[st] * keep[:, None].astype(xf.dtype)       # (T*K, D)
    slots = jnp.zeros((n_local_experts * cap, d), xf.dtype).at[slot].set(
        gathered, mode="drop").reshape(n_local_experts, cap, d)

    if cfg.mlp_type == "swiglu":
        g = ops.grouped_matmul(slots, p["w_gate"], out_dtype=jnp.float32)
        u = ops.grouped_matmul(slots, p["w_up"], out_dtype=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(xf.dtype)
    else:
        # act fused into the expert GEMM's epilogue (fp32, one cast).
        h = ops.grouped_matmul(slots, p["w_up"], epilogue="gelu",
                               out_dtype=xf.dtype)
    y_slots = ops.grouped_matmul(h, p["w_down"], out_dtype=jnp.float32)
    y_slots = y_slots.reshape(n_local_experts * cap, d)

    contrib = jnp.take(y_slots, jnp.minimum(slot, n_local_experts * cap - 1),
                       axis=0)
    contrib = contrib * (sw * keep)[:, None]
    y = jnp.zeros((t, d), jnp.float32).at[st].add(contrib)
    return y, aux


def moe_mlp_shardmap(x: jax.Array, p: dict, cfg, mesh):
    """Expert-parallel MoE via shard_map (production path).

    Token activations are replicated over "model" (they arrive sharded on
    batch only), so each (data, model) shard routes its token copy to its
    own expert slice with ZERO dispatch communication; the only collective
    is one psum of the (T_local, D) output over "model" per layer —
    §Perf iteration A3.
    """
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import dp_axes
    b, s, d = x.shape
    e = cfg.n_experts
    msz = mesh.shape["model"]
    n_local = max(e // msz, 1)
    dp = dp_axes(mesh)

    def body(xl, router, wg, wu, wd):
        tl = xl.shape[0] * xl.shape[1]
        xf = xl.reshape(tl, d)
        m_idx = jax.lax.axis_index("model") if n_local < e else 0
        pl = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
        y, aux = _dispatch_compute_combine(
            xf, pl, cfg, n_local_experts=n_local,
            expert_offset=m_idx * n_local)
        y = jax.lax.psum(y, "model")
        # aux is identical on every model shard (computed from the
        # model-replicated token copy): average over data shards only.
        aux = jax.lax.pmean(aux, dp)
        return y.reshape(xl.shape).astype(x.dtype), aux

    try:
        from jax import shard_map
    except ImportError:  # older jax keeps it in experimental
        from jax.experimental.shard_map import shard_map
    y, aux = shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(dp, None, None), P()),
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if cfg.n_shared_experts:
        y = y + layers.mlp(x.reshape(b * s, d), p["shared"], cfg).reshape(
            b, s, d)
    return y, aux


def moe_mlp(x: jax.Array, p: dict, cfg):
    """x (B, S, D) -> (y (B, S, D), aux_loss scalar fp32).

    Dispatches to the shard_map expert-parallel path when a production
    annotation mesh is active (launch.dryrun / costprobe / trainer), else
    runs the single-host fallback (identical math, full expert range)."""
    from repro.distributed import sharding as shd
    mesh = shd._ANNOTATE_MESH
    if mesh is not None and "model" in mesh.axis_names:
        msz = mesh.shape["model"]
        dp_sz = 1
        for a in shd.dp_axes(mesh):
            dp_sz *= mesh.shape[a]
        if cfg.n_experts % msz == 0 and x.shape[0] % dp_sz == 0:
            return moe_mlp_shardmap(x, p, cfg, mesh)

    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    y, aux = _dispatch_compute_combine(
        xf, p, cfg, n_local_experts=cfg.n_experts, expert_offset=0)
    y = y.astype(x.dtype)
    if cfg.n_shared_experts:
        # shared-expert output lands on the routed sum via the fused epilogue
        y = layers.mlp(xf, p["shared"], cfg, residual=y)
    return y.reshape(b, s, d), aux
