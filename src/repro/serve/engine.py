"""Serving engine: prefill + single-token decode for every block kind.

`prefill` runs the full-sequence forward while emitting cache entries per
layer (lax.scan's ys gives the layer-stacked cache for free);
`decode_step` advances one token against the cache.  Both are pure
functions of (params, cache, ...) so they pjit/shard cleanly; batch dims
shard over "data", heads/latents over "model" (see distributed.sharding).

Decode-time attention is the maximally skewed matmul regime of the paper
(m = batch rows vs n = 32k+ cache columns); the MLA path additionally uses
the low-rank "absorbed" form so decode never materializes full K/V.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import config as mmcfg
from repro.core import skewmm
from repro.models import attention as attn_mod
from repro.models import layers, moe, rglru, ssm, transformer
from repro.models.layers import rmsnorm
from repro.obs import spans as _obs
from repro.serve import kvcache


# =====================================================================
# prefill
# =====================================================================
def _place_kv(t: jax.Array, cache_len: int) -> jax.Array:
    """t (B, S, ...) -> (B, L, ...) holding the last L tokens at slots
    pos % L (ring) or [0:S] (full, S <= L).  Delegates to the jit-safe
    on-device helper in serve.kvcache (no host round-trip)."""
    return kvcache.place_kv(t, cache_len)


def _block_prefill(x, p, cfg: ModelConfig, kind: str, positions, max_len):
    """block_fwd + cache capture.  Returns (x, cache_entry)."""
    entry = {}
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    if kind.startswith("attn"):
        window = cfg.local_window if kind == "attn_local" else None
        clen = kvcache.attn_cache_len(cfg, kind, max_len)
        if cfg.use_mla:
            latent, k_rope = attn_mod.mla_latent(h, p["attn"], cfg, positions)
            entry = {"latent": _place_kv(latent, clen),
                     "k_rope": _place_kv(k_rope, clen)}
            h = attn_mod.mla_attn(h, p["attn"], cfg, positions=positions,
                                  window=window)
        else:
            q, k, v = attn_mod.gqa_project(h, p["attn"], cfg, positions)
            b, s, _ = h.shape
            # the projections stay outside: their ops are under `mm.*`
            with jax.named_scope("attention"):
                with jax.named_scope("kv_write"):
                    entry = {"k": _place_kv(k, clen), "v": _place_kv(v, clen)}
                ctx = layers.blockwise_attention(
                    jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                    jnp.swapaxes(v, 1, 2), causal=True, window=window,
                    softcap=cfg.attn_softcap,
                    q_positions=positions, kv_positions=positions)
                ctx = jnp.swapaxes(ctx, 1, 2).reshape(
                    b, s, cfg.n_heads * cfg.head_dim)
            h = skewmm.matmul(ctx, p["attn"]["wo"])
    elif kind == "ssm":
        h, entry = _ssm_prefill(h, p["mixer"], cfg)
    elif kind == "rec":
        h, entry = _rec_prefill(h, p["mixer"], cfg)
    if cfg.use_post_norm:
        h = rmsnorm(h, p["post_ln1"], cfg.norm_eps)
    x = x + h
    if kind != "ssm":
        h = rmsnorm(x, p["ln2"], cfg.norm_eps)
        if kind.endswith("_moe"):
            h, _ = moe.moe_mlp(h, p["moe"], cfg)
        else:
            h = layers.mlp(h, p["mlp"], cfg)
        if cfg.use_post_norm:
            h = rmsnorm(h, p["post_ln2"], cfg.norm_eps)
        x = x + h
    return x, entry


def _ssm_prefill(x, p, cfg):
    b, length, _ = x.shape
    di, h_, hp = cfg.d_inner, cfg.ssm_heads, cfg.ssm_head_dim
    g, s_ = cfg.ssm_groups, cfg.ssm_state
    z, xs, b_mat, c_mat, dt, conv_state = ssm._ssm_project(x, p, cfg)
    y, state = ssm.ssd_chunked(
        xs.reshape(b, length, h_, hp), dt, p["a_log"],
        b_mat.reshape(b, length, g, s_), c_mat.reshape(b, length, g, s_),
        chunk=cfg.ssm_chunk, return_state=True)
    y = y + p["d_skip"].astype(y.dtype)[None, None, :, None] * \
        xs.reshape(b, length, h_, hp)
    y = y.reshape(b, length, di)
    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                p["out_norm"], cfg.norm_eps)
    out = skewmm.matmul(y, p["out_proj"])
    entry = {"state": state.astype(jnp.float32), **conv_state}
    return out, entry


def _rec_prefill(x, p, cfg):
    branch = skewmm.matmul(x, p["proj_x"])
    gate = jax.nn.gelu(skewmm.matmul(x, p["proj_gate"]).astype(jnp.float32)
                       ).astype(x.dtype)
    xc, conv_state = ssm.causal_conv1d(branch, p["conv_w"])
    r = rglru.gate_proj(xc, p["w_r"])
    i = rglru.gate_proj(xc, p["w_i"])
    h, lru = rglru.rglru_jnp(xc, r, i, p["a_param"], c=cfg.rglru_c,
                             return_state=True)
    out = skewmm.matmul(h * gate, p["proj_out"])
    return out, {"lru": lru, "conv": conv_state}


def prefill(params, cfg: ModelConfig, tokens, *, max_len: int,
            prefix_embeds=None, last_index=None,
            mm: mmcfg.MatmulConfig | None = None):
    """tokens (B, S) -> (cache, last-position logits (B, V)).

    The cache is sized for max_len; positions [0, T) are filled.
    `last_index` (B,) int32 selects the per-row logit position instead of
    the shared final column — the right-padded-prompt case where row b's
    last real token sits at its own index (continuous batching).
    `mm` scopes a matmul configuration over every contraction of the
    prefill (equivalent to wrapping the call in ``with mm_config(...)``;
    an enclosing context still applies when mm is None).
    """
    with mmcfg.scope(mm):
        x = transformer.embed_tokens(params, cfg, tokens)
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        total = x.shape[1]
        positions = jnp.arange(total, dtype=jnp.int32)
        if cfg.pos_embedding == "sinusoidal":
            x = x + layers.sinusoidal_pos(positions,
                                          cfg.d_model)[None].astype(x.dtype)
        cache = {}
        for si, (unit, n) in enumerate(cfg.stage_list()):

            def unit_prefill(x, unit_params, unit=unit):
                entries = {}
                for i, kind in enumerate(unit):
                    x, e = _block_prefill(x, unit_params[f"b{i}"], cfg, kind,
                                          positions, max_len)
                    entries[f"b{i}"] = e
                return x, entries

            x, stage_cache = jax.lax.scan(
                jax.checkpoint(unit_prefill), x, params[f"stage{si}"])
            cache[f"stage{si}"] = stage_cache
        h = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        if last_index is None:
            last = h[:, -1]
        else:
            last = h[jnp.arange(h.shape[0]), last_index]
        logits = transformer.unembed(params, cfg, last)
        return cache, logits


# =====================================================================
# decode
# =====================================================================
def _decode_gqa(h, p, cfg: ModelConfig, entry, pos, window):
    """h (B, 1, D); entry k/v (B, L, KV, hd); pos scalar int32, or (B,)
    per-row positions (continuous batching — every live request at its
    own depth; the scalar path is kept verbatim for bit-compatibility)."""
    b = h.shape[0]
    hq, hd = cfg.n_heads, cfg.head_dim
    clen = entry["k"].shape[1]
    is_ring = window is not None
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        q_pos = jnp.full((1,), pos, jnp.int32)
    else:
        q_pos = pos[:, None]
    q, k_new, v_new = attn_mod.gqa_project(h, p, cfg, q_pos)
    slot = jnp.mod(pos, clen) if is_ring else pos
    # the projections stay outside: their ops are under `mm.*`
    with jax.named_scope("attention"):
        with jax.named_scope("kv_write"):
            if pos.ndim == 0:
                k_cache = jax.lax.dynamic_update_slice(
                    entry["k"], k_new, (0, slot, 0, 0))
                v_cache = jax.lax.dynamic_update_slice(
                    entry["v"], v_new, (0, slot, 0, 0))
            else:
                rows = jnp.arange(b)
                k_cache = entry["k"].at[rows, slot].set(k_new[:, 0])
                v_cache = entry["v"].at[rows, slot].set(v_new[:, 0])
        kv_pos = kvcache.kv_slot_positions(pos, clen, is_ring)
        ctx = layers.blockwise_attention(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k_cache, 1, 2),
            jnp.swapaxes(v_cache, 1, 2),
            causal=True, window=window, softcap=cfg.attn_softcap,
            q_positions=q_pos, kv_positions=kv_pos)
        ctx = jnp.swapaxes(ctx, 1, 2).reshape(b, 1, hq * hd)
    out = skewmm.matmul(ctx, p["wo"])
    return out, {"k": k_cache, "v": v_cache}


def _decode_mla(h, p, cfg: ModelConfig, entry, pos):
    """Absorbed-form MLA decode: scores/values via the latent cache.
    pos scalar, or (B,) per-row (scalar path kept verbatim)."""
    b = h.shape[0]
    nh, nope, rd = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    kvr, vd = cfg.kv_lora_rank, cfg.v_head_dim
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos1 = jnp.full((1,), pos, jnp.int32)
        latent_new, k_rope_new = attn_mod.mla_latent(h, p, cfg, pos1)
        latent = jax.lax.dynamic_update_slice(entry["latent"], latent_new,
                                              (0, pos, 0))
        k_rope = jax.lax.dynamic_update_slice(entry["k_rope"], k_rope_new,
                                              (0, pos, 0))
        valid = jnp.arange(latent.shape[1]) <= pos
        valid = valid[None]                                # (1, L)
    else:
        pos1 = pos[:, None]
        latent_new, k_rope_new = attn_mod.mla_latent(h, p, cfg, pos1)
        rows = jnp.arange(b)
        latent = entry["latent"].at[rows, pos].set(latent_new[:, 0])
        k_rope = entry["k_rope"].at[rows, pos].set(k_rope_new[:, 0])
        valid = jnp.arange(latent.shape[1])[None, :] <= pos[:, None]
    q_nope, q_rope = attn_mod.mla_queries(h, p, cfg, pos1)
    q_nope, q_rope = q_nope[:, 0], q_rope[:, 0]            # (B, H, *)
    wkv_b = p["wkv_b"].reshape(kvr, nh, nope + vd)
    wk, wv = wkv_b[..., :nope], wkv_b[..., nope:]
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope.astype(jnp.float32),
                       wk.astype(jnp.float32))             # (B, H, kvr)
    scores = jnp.einsum("bhr,blr->bhl", q_lat,
                        latent.astype(jnp.float32))
    scores += jnp.einsum("bhd,bld->bhl", q_rope.astype(jnp.float32),
                         k_rope.astype(jnp.float32))
    scores *= (nope + rd) ** -0.5
    if cfg.attn_softcap > 0.0:
        scores = cfg.attn_softcap * jnp.tanh(scores / cfg.attn_softcap)
    scores = jnp.where(valid[:, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    ctx_lat = jnp.einsum("bhl,blr->bhr", w, latent.astype(jnp.float32))
    ctx = jnp.einsum("bhr,rhv->bhv", ctx_lat, wv.astype(jnp.float32))
    ctx = ctx.reshape(b, 1, nh * vd).astype(h.dtype)
    out = skewmm.matmul(ctx, p["wo"])
    return out, {"latent": latent, "k_rope": k_rope}


def _decode_ssm(h, p, cfg: ModelConfig, entry):
    b = h.shape[0]
    di, nh, hp = cfg.d_inner, cfg.ssm_heads, cfg.ssm_head_dim
    g, s_ = cfg.ssm_groups, cfg.ssm_state
    z, xs, b_mat, c_mat, dt, conv = ssm._ssm_project(
        h, p, cfg, conv_state=entry)
    y, state = ssm.ssd_decode_step(
        entry["state"], xs[:, 0].reshape(b, nh, hp), dt[:, 0],
        p["a_log"], b_mat[:, 0].reshape(b, g, s_),
        c_mat[:, 0].reshape(b, g, s_))
    y = y + p["d_skip"].astype(y.dtype)[None, :, None] * \
        xs[:, 0].reshape(b, nh, hp)
    y = y.reshape(b, 1, di)
    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                p["out_norm"], cfg.norm_eps)
    return skewmm.matmul(y, p["out_proj"]), {"state": state, **conv}


def _decode_rec(h, p, cfg: ModelConfig, entry):
    branch = skewmm.matmul(h, p["proj_x"])
    gate = jax.nn.gelu(skewmm.matmul(h, p["proj_gate"]).astype(jnp.float32)
                       ).astype(h.dtype)
    xc, conv = ssm.causal_conv1d(branch, p["conv_w"], state=entry["conv"])
    r = rglru.gate_proj(xc, p["w_r"])
    i = rglru.gate_proj(xc, p["w_i"])
    y, lru = rglru.rglru_decode_step(entry["lru"], xc[:, 0], r[:, 0],
                                     i[:, 0], p["a_param"], c=cfg.rglru_c)
    out = skewmm.matmul(y[:, None].astype(h.dtype) * gate, p["proj_out"])
    return out, {"lru": lru, "conv": conv}


def _block_decode(x, p, cfg: ModelConfig, kind: str, entry, pos):
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    if kind.startswith("attn"):
        window = cfg.local_window if kind == "attn_local" else None
        if cfg.use_mla:
            h, new_entry = _decode_mla(h, p["attn"], cfg, entry, pos)
        else:
            h, new_entry = _decode_gqa(h, p["attn"], cfg, entry, pos, window)
    elif kind == "ssm":
        h, new_entry = _decode_ssm(h, p["mixer"], cfg, entry)
    elif kind == "rec":
        h, new_entry = _decode_rec(h, p["mixer"], cfg, entry)
    if cfg.use_post_norm:
        h = rmsnorm(h, p["post_ln1"], cfg.norm_eps)
    x = x + h
    if kind != "ssm":
        h = rmsnorm(x, p["ln2"], cfg.norm_eps)
        if kind.endswith("_moe"):
            h, _ = moe.moe_mlp(h, p["moe"], cfg)
        else:
            h = layers.mlp(h, p["mlp"], cfg)
        if cfg.use_post_norm:
            h = rmsnorm(h, p["post_ln2"], cfg.norm_eps)
        x = x + h
    return x, new_entry


def decode_step(params, cfg: ModelConfig, cache, tokens, pos,
                mm: mmcfg.MatmulConfig | None = None):
    """One decode step.  tokens (B,) int32; pos () int32 — the absolute
    position being generated — or (B,) int32 per-row positions (the
    continuous-batching case: each live request decodes at its own
    depth).  Returns (logits (B, V), new_cache).

    `mm` scopes a matmul configuration over the step's contractions (the
    maximally right-skewed regime — a decode-serving thread can pin e.g.
    a lower AMP without touching any model code)."""
    pos = jnp.asarray(pos, jnp.int32)
    with mmcfg.scope(mm):
        x = transformer.embed_tokens(params, cfg, tokens[:, None])
        if cfg.pos_embedding == "sinusoidal":
            if pos.ndim == 0:
                pe = layers.sinusoidal_pos(
                    jnp.full((1,), pos, jnp.int32), cfg.d_model)[None]
            else:
                pe = layers.sinusoidal_pos(pos[:, None], cfg.d_model)
            x = x + pe.astype(x.dtype)
        new_cache = {}
        for si, (unit, n) in enumerate(cfg.stage_list()):

            def unit_decode(x, scanned, unit=unit):
                unit_params, unit_cache = scanned
                entries = {}
                for i, kind in enumerate(unit):
                    x, e = _block_decode(x, unit_params[f"b{i}"], cfg, kind,
                                         unit_cache[f"b{i}"], pos)
                    entries[f"b{i}"] = e
                return x, entries

            x, stage_cache = jax.lax.scan(
                unit_decode, x, (params[f"stage{si}"], cache[f"stage{si}"]))
            new_cache[f"stage{si}"] = stage_cache
        h = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = transformer.unembed(params, cfg, h[:, 0])
        return logits, new_cache


def guarded_decode_step(params, cfg: ModelConfig, cache, tokens, pos,
                        mm: mmcfg.MatmulConfig | None = None):
    """`decode_step` with a serving-boundary NaN scrub.

    Decode is where a poisoned kernel is most damaging — one non-finite
    logit silently corrupts every subsequent sampled token.  This wrapper
    adds the last net of the guard ladder: a *concrete* finiteness check
    on the logits (it synchronizes, so it belongs at the serving boundary,
    not inside a jitted loop — do not jit this function; jit the model
    step it wraps), and on failure a re-run of the whole step on the XLA
    reference backend, which bypasses the pallas kernels entirely.  The
    logits are themselves a `fault_scope` injection site ("decode") so the
    scrub path is exercisable end to end; the reference re-run is outside
    the injection, mirroring how a real backend-specific corruption would
    not follow the computation to XLA.  Scrubs are counted in guard
    health ("scrubbed_batches"); a step whose *reference* re-run still
    produces non-finite logits raises `NumericFault` (genuinely bad
    params/inputs — no backend can fix that, and returning it would be a
    silent escape).
    """
    from repro.guard import faults as _faults
    from repro.guard import health as _health
    from repro.guard.fallback import NumericFault

    logits, new_cache = decode_step(params, cfg, cache, tokens, pos, mm)
    logits, injected = _faults.maybe_poison(logits, "decode")
    if _all_finite(logits):
        return logits, new_cache
    if injected:
        _health.record("faults_caught", injected)
    _health.record("scrubbed_batches")
    with mmcfg.scope(mm), mmcfg.mm_config(backend="xla"):
        logits, new_cache = decode_step(params, cfg, cache, tokens, pos)
    if not _all_finite(logits):
        raise NumericFault(
            "decode_step logits non-finite even on the XLA reference "
            "backend")
    return logits, new_cache


def _all_finite(logits) -> bool:
    """The scrub's host read: it waits for the step on the device."""
    with _obs.span("sync"):
        return bool(jnp.isfinite(logits).all())
