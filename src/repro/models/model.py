"""Model builder: family dispatch, param counting, MODEL_FLOPS accounting."""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, get_config
from repro.models import encdec, transformer


@dataclasses.dataclass(frozen=True)
class ModelBundle:
    cfg: ModelConfig
    init: Callable[[jax.Array], Any]
    # hidden_fn(params, batch) -> (hidden (B, T, D), aux_loss)
    hidden_fn: Callable[[Any, dict], tuple[jax.Array, jax.Array]]
    # logits_fn(params, hidden) -> fp32 logits
    logits_fn: Callable[[Any, jax.Array], jax.Array]


def build_model(cfg: ModelConfig | str) -> ModelBundle:
    if isinstance(cfg, str):
        cfg = get_config(cfg)

    if cfg.family == "encdec":
        def hidden_fn(params, batch):
            return encdec.forward_hidden(params, cfg, batch["tokens"],
                                         batch["frames"])
        return ModelBundle(
            cfg=cfg,
            init=jax.jit(functools.partial(encdec.init_encdec, cfg)),
            hidden_fn=hidden_fn,
            logits_fn=lambda p, h: transformer.unembed(p, cfg, h),
        )

    def hidden_fn(params, batch):
        return transformer.forward_hidden(
            params, cfg, batch["tokens"],
            prefix_embeds=batch.get("prefix_embeds"))

    return ModelBundle(
        cfg=cfg,
        init=jax.jit(functools.partial(transformer.init_lm, cfg)),
        hidden_fn=hidden_fn,
        logits_fn=lambda p, h: transformer.unembed(p, cfg, h),
    )


# ------------------------------------------------------------- accounting
def count_params(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def param_shapes(cfg: ModelConfig):
    """Parameter ShapeDtypeStructs without allocating (for dry-runs)."""
    bundle = build_model(cfg)
    return jax.eval_shape(bundle.init, jax.ShapeDtypeStruct((2,), jnp.uint32))


def count_params_active(cfg: ModelConfig, shapes=None) -> tuple[int, int]:
    """(total_params, active_params): MoE expert stacks count k/E active."""
    shapes = shapes if shapes is not None else param_shapes(cfg)
    total = active = 0
    ratio = (cfg.n_experts_per_tok / cfg.n_experts) if cfg.n_experts else 1.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        names = [getattr(k, "key", str(k)) for k in path]
        n = 1
        for s in leaf.shape:
            n *= s
        total += n
        is_expert = any(nm in ("w_gate", "w_up", "w_down") for nm in names) \
            and leaf.ndim >= 3 and "moe" in names
        active += int(n * ratio) if is_expert else n
    return total, active


def model_flops(cfg: ModelConfig, *, tokens: int, mode: str = "train",
                shapes=None) -> float:
    """MODEL_FLOPS per the brief: 6*N*D train (N active for MoE), 2*N*D for
    a forward/decode pass."""
    total, active = count_params_active(cfg, shapes)
    embed = cfg.vocab_size * cfg.d_model
    n = active - embed  # standard convention: exclude embedding lookup
    mult = 6.0 if mode == "train" else 2.0
    # tied unembed still does a (d x V) matmul per token: count it once.
    n = n + (0 if not cfg.tie_embeddings else embed)
    return mult * n * tokens
