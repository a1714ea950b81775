"""Sharding-rule unit tests.

Rules are evaluated against an AbstractMesh(16,16) — the production shape —
so divisibility behaviour is tested realistically regardless of how many
devices this host has.
"""

import jax
from repro.compat import make_mesh
import jax.numpy as jnp
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs.base import all_arch_ids, get_config
from repro.distributed import sharding as shd
from repro.models.model import param_shapes

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_param_specs_cover_tree_and_divide():
    for arch in all_arch_ids():
        shapes = param_shapes(get_config(arch))
        specs = shd.tree_param_specs(shapes, MESH)
        flat_shapes = jax.tree.leaves(shapes)
        flat_specs = jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        assert len(flat_shapes) == len(flat_specs)
        for sh, sp in zip(flat_shapes, flat_specs):
            for dim, axes in zip(sh.shape, tuple(sp)):
                if axes is not None:
                    n = 16 if isinstance(axes, str) else 16 ** len(axes)
                    assert dim % n == 0, (arch, sh.shape, sp)


def test_param_specs_shard_the_big_matmuls():
    shapes = param_shapes(get_config("gemma2-27b"))
    specs = shd.tree_param_specs(shapes, MESH)
    assert tuple(specs["embed"]) == ("model", None)
    s0 = specs["stage0"]["b0"]
    assert tuple(s0["attn"]["wq"]) == (None, None, "model")
    assert tuple(s0["attn"]["wo"]) == (None, "model", None)
    assert tuple(s0["mlp"]["w_gate"]) == (None, None, "model")
    assert tuple(s0["mlp"]["w_down"]) == (None, "model", None)


def test_moe_experts_sharded_on_model():
    shapes = param_shapes(get_config("deepseek-v3-671b"))
    specs = shd.tree_param_specs(shapes, MESH)
    moe_spec = specs["stage1"]["b0"]["moe"]
    # stacked (R, E, D, F): expert dim sharded
    assert tuple(moe_spec["w_gate"]) == (None, "model", None, None)
    assert tuple(moe_spec["w_down"]) == (None, "model", None, None)


def test_zero1_shards_largest_replicated_dim():
    spec = shd.zero1_spec(P(None, "model"), (4096, 2048), MESH)
    assert tuple(spec) == ("data", "model")
    # indivisible dim stays replicated
    spec = shd.zero1_spec(P(None,), (31,), MESH)
    assert tuple(spec) == (None,)
    # prefers the largest eligible dim
    spec = shd.zero1_spec(P(None, None), (64, 4096), MESH)
    assert tuple(spec) == (None, "data")


def _norm(spec):
    """Unwrap 1-tuple axes: older jax PartitionSpec doesn't normalize them."""
    return tuple(a[0] if isinstance(a, tuple) and len(a) == 1 else a
                 for a in tuple(spec))


def test_batch_spec_pod_composition():
    spec = shd.batch_spec((256, 4096), MESH)
    assert _norm(spec)[0] == "data"
    spec3 = shd.batch_spec((256, 4096), MESH3)
    assert _norm(spec3)[0] == ("pod", "data")
    # batch=1 (long_500k): replicated
    assert _norm(shd.batch_spec((1, 8), MESH))[0] is None


def test_cache_specs_rules():
    kv = jax.ShapeDtypeStruct((4, 32, 64, 16, 128), jnp.bfloat16)
    assert _norm(shd.cache_leaf_spec("k", kv, MESH)) == \
        (None, "data", None, "model", None)
    # MQA (kv=1): sequence dim takes the model axis instead
    kv1 = jax.ShapeDtypeStruct((4, 32, 4096, 1, 128), jnp.bfloat16)
    assert _norm(shd.cache_leaf_spec("k", kv1, MESH)) == \
        (None, "data", "model", None, None)
    lat = jax.ShapeDtypeStruct((58, 32, 4096, 512), jnp.bfloat16)
    assert _norm(shd.cache_leaf_spec("latent", lat, MESH)) == \
        (None, "data", "model", None)
    ssm = jax.ShapeDtypeStruct((64, 32, 80, 128, 64), jnp.float32)
    assert _norm(shd.cache_leaf_spec("state", ssm, MESH)) == \
        (None, "data", "model", None, None)


def test_guard_falls_back_to_replication():
    spec = shd._guard(P("model", None), (31, 64), MESH)
    assert tuple(spec) == (None, None)


def test_shard_like_puts_arrays():
    n = jax.device_count()
    mesh = make_mesh((1, n), ("data", "model"))
    tree = {"w": jnp.ones((4, n * 2), jnp.float32)}
    out = shd.shard_like(tree, {"w": P(None, "model")}, mesh)
    assert out["w"].sharding.spec == P(None, "model")


def test_guard_raises_on_overlong_spec():
    """A spec with more entries than the value has dims is a rule bug —
    the old zip() silently truncated it; now it raises."""
    import pytest

    with pytest.raises(ValueError, match="outrank"):
        shd._guard(P("model", None, None), (64, 64), MESH)
    # exact-rank and under-rank specs still pass through
    assert tuple(shd._guard(P("model", None), (64, 64), MESH))[0] == "model"
    assert len(tuple(shd._guard(P("model"), (64, 64, 64), MESH))) == 3


def test_param_spec_unmatched_counter():
    """Silent replication of an unrecognized >=2-D weight is counted."""
    from repro.obs import metrics

    leaf = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    before = metrics.REGISTRY.value("sharding.unmatched_params")
    spec = shd.param_spec(["mystery_weight"], leaf, MESH)
    assert tuple(spec) == (None, None)
    assert metrics.REGISTRY.value("sharding.unmatched_params") == before + 1
    # recognized names and vectors don't count
    shd.param_spec(["wq"], leaf, MESH)
    shd.param_spec(["bias"], jax.ShapeDtypeStruct((256,), jnp.float32), MESH)
    assert metrics.REGISTRY.value("sharding.unmatched_params") == before + 1
