"""MoE-specific tests: shard_map path equivalence, capacity behavior,
expert-parallel spec wiring (added during §Perf iteration A3)."""

import dataclasses

import jax
from repro.compat import make_mesh
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.distributed import sharding as shd
from repro.models import moe

RNG = np.random.default_rng(23)


def _cfg(nodrop=True, experts=8, topk=2):
    cfg = get_config("dbrx-132b").reduced()
    return dataclasses.replace(
        cfg, n_experts=experts, n_experts_per_tok=topk,
        capacity_factor=float(experts) if nodrop else 1.25)


def _params(cfg):
    return moe.init_moe(jax.random.PRNGKey(0), cfg)


def test_shardmap_path_matches_fallback():
    cfg = _cfg()
    p = _params(cfg)
    x = jnp.asarray(RNG.normal(size=(2, 16, cfg.d_model)) * 0.3, jnp.float32)
    y1, aux1 = moe.moe_mlp(x, p, cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    shd.set_annotation_mesh(mesh)
    try:
        y2, aux2 = moe.moe_mlp(x, p, cfg)
    finally:
        shd.set_annotation_mesh(None)
    np.testing.assert_allclose(y1, y2, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(aux1, aux2, rtol=1e-4)


def test_shardmap_multidevice_if_available():
    n = jax.device_count()
    cfg = _cfg(experts=8, topk=2)
    if 8 % n != 0:
        pytest.skip("expert count not divisible by device count")
    p = _params(cfg)
    x = jnp.asarray(RNG.normal(size=(n, 16, cfg.d_model)) * 0.3, jnp.float32)
    y1, _ = moe.moe_mlp(x, p, cfg)
    mesh = make_mesh((1, n), ("data", "model"))
    shd.set_annotation_mesh(mesh)
    try:
        y2, _ = moe.moe_mlp(x, p, cfg)
    finally:
        shd.set_annotation_mesh(None)
    np.testing.assert_allclose(y1, y2, rtol=2e-3, atol=2e-3)


def test_capacity_drops_tokens_not_correctness():
    """With tight capacity the layer still runs; outputs differ only by
    dropped contributions (bounded by gate weights)."""
    cfg_tight = _cfg(nodrop=False)
    cfg_loose = _cfg(nodrop=True)
    p = _params(cfg_tight)
    x = jnp.asarray(RNG.normal(size=(2, 32, cfg_tight.d_model)) * 0.3,
                    jnp.float32)
    y_t, _ = moe.moe_mlp(x, p, cfg_tight)
    y_l, _ = moe.moe_mlp(x, p, cfg_loose)
    assert bool(jnp.all(jnp.isfinite(y_t)))
    # loose capacity keeps everything; tight may drop but never explode
    assert float(jnp.max(jnp.abs(y_t))) <= float(jnp.max(jnp.abs(y_l))) * 5


def test_aux_loss_decreases_for_balanced_router():
    cfg = _cfg()
    p = _params(cfg)
    t, d, e = 64, cfg.d_model, cfg.n_experts
    x = jnp.asarray(RNG.normal(size=(t, d)), jnp.float32)
    _, aux_rand = moe._dispatch_compute_combine(
        x, p, cfg, n_local_experts=e, expert_offset=0)
    assert float(aux_rand) > 0


def test_fsdp_specs_shard_params_over_data():
    from jax.sharding import AbstractMesh
    from repro.models.model import param_shapes
    mesh = AbstractMesh((16, 16), ("data", "model"))
    shapes = param_shapes(get_config("deepseek-v3-671b"))
    specs = shd.tree_param_specs(shapes, mesh, fsdp=True)
    moe_spec = specs["stage1"]["b0"]["moe"]
    # experts: (R, E, D, F) -> E on model + one dim on data (FSDP)
    assert "data" in jax.tree_util.tree_leaves(
        [list(tuple(moe_spec["w_gate"]))])
    assert tuple(moe_spec["w_gate"])[1] == "model"
