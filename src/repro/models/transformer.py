"""Decoder-only LM assembly: stage-wise scan over stacked repeating units.

Layers are grouped into stages of identical repeating units (cfg.stage_list)
and executed with jax.lax.scan over unit-stacked params + jax.checkpoint —
this keeps the HLO size O(distinct units) for 61-88-layer models and gives
pipeline-free activation-memory relief (DESIGN.md §4).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import skewmm
from repro.models import blocks, layers
from repro.models.layers import embed_init, linear_init, rmsnorm


def init_lm(cfg, key) -> dict:
    """Seeded parameters.  Each stage's repeating units are built by one
    `vmap` over their keys, so the stacked layout is produced directly
    (no per-layer copies to stack); `build_model` runs this under
    `jax.jit`, so no float32 draw outlives its cast."""
    dt = layers.dtype_of(cfg)
    keys = jax.random.split(key, 8)
    params: dict = {
        "embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model, dt),
        "final_norm": jnp.zeros((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = linear_init(keys[1], cfg.d_model,
                                        cfg.vocab_size, dt)
    stage_keys = jax.random.split(keys[2], len(cfg.stage_list()))
    for si, (unit, n) in enumerate(cfg.stage_list()):
        def init_unit(ukey, unit=unit):
            ukeys = jax.random.split(ukey, len(unit))
            return {f"b{i}": blocks.init_block(ukeys[i], cfg, kind)
                    for i, kind in enumerate(unit)}

        params[f"stage{si}"] = jax.vmap(init_unit)(
            jax.random.split(stage_keys[si], n))
    if cfg.mtp_heads:
        # deepseek-style MTP: next-next-token head = proj([h; emb]) + block
        params["mtp"] = {
            "proj": linear_init(keys[3], 2 * cfg.d_model, cfg.d_model, dt),
            "norm": jnp.zeros((cfg.d_model,), dt),
            "block": blocks.init_block(keys[4], cfg, "attn_dense"),
        }
    return params


def _run_stages(x, params, cfg, positions):
    aux_total = jnp.zeros((), jnp.float32)
    for si, (unit, n) in enumerate(cfg.stage_list()):

        def unit_fwd(carry, unit_params, unit=unit):
            x, aux = carry
            for i, kind in enumerate(unit):
                x, a = blocks.block_fwd(x, unit_params[f"b{i}"], cfg, kind,
                                        positions)
                aux = aux + a
            return (x, aux), None

        unit_fwd = jax.checkpoint(unit_fwd)
        (x, aux_total), _ = jax.lax.scan(
            unit_fwd, (x, aux_total), params[f"stage{si}"])
    return x, aux_total


def embed_tokens(params, cfg, tokens):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def forward_hidden(params, cfg, tokens, *, prefix_embeds=None):
    """tokens (B, S) [+ prefix_embeds (B, F, D)] -> (hidden (B,T,D), aux)."""
    x = embed_tokens(params, cfg, tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    total = x.shape[1]
    positions = jnp.arange(total, dtype=jnp.int32)
    if cfg.pos_embedding == "sinusoidal":
        x = x + layers.sinusoidal_pos(positions, cfg.d_model)[None].astype(
            x.dtype)
    x, aux = _run_stages(x, params, cfg, positions)
    return rmsnorm(x, params["final_norm"], cfg.norm_eps), aux


def unembed(params, cfg, h):
    """h (..., D) -> logits (..., V), final softcap applied, fp32; under
    the named scope `lm_head`."""
    with jax.named_scope("lm_head"):
        w = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
        logits = skewmm.matmul(h, w, out_dtype=jnp.float32)
        if cfg.final_softcap > 0.0:
            logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
        return logits


def mtp_hidden(params, cfg, h, tokens):
    """deepseek MTP: predict token t+2 from [h_t ; emb(token_{t+1})]."""
    p = params["mtp"]
    emb_next = embed_tokens(params, cfg, tokens)[:, 1:]      # (B, S-1, D)
    h_trunc = h[:, :-1]
    cat = jnp.concatenate([rmsnorm(h_trunc, p["norm"], cfg.norm_eps),
                           emb_next], axis=-1)
    x = skewmm.matmul(cat, p["proj"])
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    x, _ = blocks.block_fwd(x, p["block"], cfg, "attn_dense", positions)
    return x                                                  # (B, S-1, D)
