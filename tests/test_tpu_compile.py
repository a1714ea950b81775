"""The planner's real-size plans compile for a described TPU v5e.

Nothing runs here: each test lowers a kernel at real widths and compiles
it for one chip of a `v5e:2x2` topology that is described, not attached,
with the TPU compiler that ships with JAX.  A plan whose blocks exceed the
kernel's scoped-VMEM limit, or a block the compiler cannot tile, fails
here instead of on the chip.  The topology is described inside a fixture,
so collecting this file never loads the TPU library.
"""

import contextlib

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.core.planner import plan_matmul
from repro.kernels import gemv_splitk, skew_matmul


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compile(fn, *shapes):
    with _no_persistent_cache():
        return jax.jit(fn).lower(*shapes).compile().as_text()


def _up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


@pytest.mark.parametrize("mkn", [
    (2048, 3072, 8192),      # k_inner (1024, 1536, 2048): 34 MiB of VMEM
    (4096, 4096, 4096),      # k_inner (512, 1024, 4096)
    (8, 3072, 200064),       # a_resident: the phi4-mini LM head at decode
])
def test_planned_dense_compiles(one_chip, mkn):
    m, k, n = mkn
    p = plan_matmul(m, k, n, chip="tpu_v5e").plan
    assert p.schedule != "splitk"
    mp, kp, np_ = _up(m, p.bm), _up(k, p.bk), _up(n, p.bn)
    text = _compile(
        lambda a, b: skew_matmul.skew_matmul_padded(
            a, b, bm=p.bm, bk=p.bk, bn=p.bn, schedule=p.schedule,
            out_dtype=jnp.bfloat16),
        jax.ShapeDtypeStruct((mp, kp), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((kp, np_), jnp.bfloat16, sharding=one_chip))
    assert "tpu_custom_call" in text


def test_gemv_splitk_compiles(one_chip):
    m, k, n, bk, bn = 8, 3072, 200704, 1536, 2048
    text = _compile(
        lambda a, b: gemv_splitk.gemv_splitk_padded(
            a, b, bk=bk, bn=bn, out_dtype=jnp.bfloat16),
        jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((k, n), jnp.bfloat16, sharding=one_chip))
    assert "tpu_custom_call" in text


def test_batched_k_inner_compiles(one_chip):
    text = _compile(
        lambda a, b: skew_matmul.skew_matmul_batched_padded(
            a, b, bm=512, bk=1024, bn=2048, epilogue="silu",
            out_dtype=jnp.bfloat16),
        jax.ShapeDtypeStruct((4, 512, 3072), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((3072, 8192), jnp.bfloat16, sharding=one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("schedule,mkn,blocks", [
    ("a_resident", (64, 4096, 8192), (64, 1024, 1024)),
    ("b_resident", (8192, 4096, 128), (1024, 1024, 128)),
])
def test_resident_strip_compiles(one_chip, schedule, mkn, blocks):
    """gk = 4 with 8 inner blocks: the fp32 strip scratch fits and tiles."""
    (m, k, n), (bm, bk, bn) = mkn, blocks
    text = _compile(
        lambda a, b: skew_matmul.skew_matmul_padded(
            a, b, bm=bm, bk=bk, bn=bn, schedule=schedule,
            out_dtype=jnp.bfloat16),
        jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((k, n), jnp.bfloat16, sharding=one_chip))
    assert "tpu_custom_call" in text
