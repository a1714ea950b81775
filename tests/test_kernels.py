"""Per-kernel allclose tests vs the pure-jnp oracles (interpret mode on CPU).

Shape/dtype sweeps per the brief; hypothesis property tests live in
tests/test_properties.py.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.costmodel import BlockPlan
from repro.core.epilogue import Epilogue
from repro.kernels import ops, ref, skew_matmul

RNG = np.random.default_rng(42)


def _arr(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-3, atol=1e-4)


# ---------------------------------------------------------------- skew matmul
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mkn", [
    (128, 256, 128),     # aligned square
    (100, 200, 300),     # unaligned everything
    (8, 512, 1024),      # decode-style GEMV batch
    (1, 384, 1000),      # extreme right-skew (vocab-sliver)
    (700, 64, 7),        # extreme left-skew, tiny n
    (256, 2048, 512),    # contraction-heavy (paper right-skew of A)
])
def test_skew_matmul_matches_oracle(mkn, dtype):
    m, k, n = mkn
    a, b = _arr((m, k), dtype, 0.3), _arr((k, n), dtype, 0.3)
    got = ops.skew_matmul(a, b)
    want = ref.matmul_ref(a, b)
    assert got.dtype == want.dtype and got.shape == (m, n)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_skew_matmul_explicit_plan():
    a, b = _arr((256, 512)), _arr((512, 384))
    got = ops.skew_matmul(a, b, plan=BlockPlan(bm=64, bk=128, bn=128))
    np.testing.assert_allclose(got, ref.matmul_ref(a, b), rtol=2e-3, atol=1e-4)


def test_skew_matmul_out_dtype():
    a, b = _arr((64, 128), jnp.bfloat16), _arr((128, 64), jnp.bfloat16)
    got = ops.skew_matmul(a, b, out_dtype=jnp.float32)
    assert got.dtype == jnp.float32


# ------------------------------------------- schedule family x fused epilogues
_SCHED_SHAPES = [
    (96, 256, 128),      # square-ish
    (384, 256, 48),      # left-skewed (m >> n)
    (32, 256, 512),      # right-skewed (m << n)
    (100, 300, 200),     # unaligned everything
]


@pytest.mark.parametrize("schedule", ["k_inner", "a_resident", "b_resident"])
@pytest.mark.parametrize("epilogue", [None, "bias", "gelu", "silu_residual",
                                      "bias_gelu_residual"])
@pytest.mark.parametrize("mkn", _SCHED_SHAPES)
def test_schedule_epilogue_matches_oracle(schedule, epilogue, mkn):
    m, k, n = mkn
    a, b = _arr((m, k), scale=0.3), _arr((k, n), scale=0.3)
    bias, res = _arr((n,)), _arr((m, n))
    plan = BlockPlan(32, 128, 128, schedule=schedule)
    got = ops.skew_matmul(a, b, plan=plan, epilogue=epilogue, bias=bias,
                          residual=res)
    want = ref.matmul_epilogue_ref(a, b, bias=bias, residual=res,
                                   epilogue=epilogue)
    assert got.dtype == want.dtype and got.shape == (m, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=1e-4)


@pytest.mark.parametrize("schedule", ["a_resident", "b_resident"])
def test_resident_single_k_block(schedule):
    """gk == 1: the resident schedules' no-revisit fast path."""
    a, b = _arr((64, 200), scale=0.3), _arr((200, 96), scale=0.3)
    plan = BlockPlan(32, 256, 32, schedule=schedule)
    got = ops.skew_matmul(a, b, plan=plan, epilogue="gelu")
    want = ref.matmul_epilogue_ref(a, b, epilogue="gelu")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=1e-4)


@pytest.mark.parametrize("inner_pos,n_inner", [(1, 3), (0, 2), (1, 1)])
def test_resident_output_blocks_written_once(inner_pos, n_inner):
    """gk > 1: on the TPU an output block is written back when its index
    changes and never read back, so every output block's visits must be
    one consecutive run that ends on the last k step."""
    gk, n_outer = 4, 2
    if inner_pos == 1:     # a_resident: grid (m, k, n)
        base = lambda i, kk, j: (i, j)  # noqa: E731
    else:                  # b_resident: grid (n, k, m)
        base = lambda j, kk, i: (i, j)  # noqa: E731
    held = skew_matmul.held_until_last(base, gk, inner_pos)
    visits = []
    for outer in range(n_outer):
        for kk in range(gk):
            for inner in range(n_inner):
                blk = tuple(int(x) for x in held(outer, kk, inner))
                visits.append((blk, kk))
    runs = []
    for blk, kk in visits:
        if runs and runs[-1][0] == blk:
            runs[-1][1].append(kk)
        else:
            runs.append((blk, [kk]))
    blocks = [blk for blk, _ in runs]
    assert len(blocks) == len(set(blocks)) == n_outer * n_inner
    assert all(ks[-1] == gk - 1 for _, ks in runs)


def test_compiler_params_cover_buffers_and_capacity():
    # the planner's k_inner (1024, 1536, 2048) bf16 plan: the cost model
    # claims 26 MiB, the kernel double-buffers its output too
    blocks = [((1024, 1536), jnp.bfloat16), ((1536, 2048), jnp.bfloat16),
              ((1024, 2048), jnp.bfloat16)]
    acc = ((1024, 2048), jnp.float32)
    params = skew_matmul.compiler_params(
        ("parallel", "parallel", "arbitrary"), pipelined=blocks,
        resident=[acc, acc])
    assert params.vmem_limit_bytes >= (2 * (3 + 6 + 4) + 8 + 8) * 2**20
    assert params.vmem_limit_bytes <= skew_matmul.VMEM_CAPACITY_BYTES
    assert skew_matmul.vmem_buffer_bytes((1, 2048), jnp.bfloat16) == \
        16 * 2048 * 2      # a row pads to the bf16 (16, 128) tile
    huge = [((8192, 4096), jnp.float32)]
    with pytest.raises(ValueError, match="VMEM"):
        skew_matmul.compiler_params(("parallel",), pipelined=huge)


@pytest.mark.parametrize("schedule", ["k_inner", "a_resident", "b_resident"])
def test_schedule_bf16_epilogue(schedule):
    a = _arr((64, 256), jnp.bfloat16, 0.3)
    b = _arr((256, 128), jnp.bfloat16, 0.3)
    res = _arr((64, 128), jnp.bfloat16)
    plan = BlockPlan(32, 128, 128, schedule=schedule)
    got = ops.skew_matmul(a, b, plan=plan, epilogue="silu_residual",
                          residual=res)
    want = ref.matmul_epilogue_ref(a, b, residual=res,
                                   epilogue="silu_residual")
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(a.dtype))


@pytest.mark.parametrize("epilogue", [None, "bias_silu_residual"])
def test_batched_grid_matches_oracle(epilogue):
    nb, m, k, n = 3, 50, 300, 200
    a, b = _arr((nb, m, k), scale=0.3), _arr((k, n), scale=0.3)
    bias, res = _arr((n,)), _arr((nb, m, n))
    plan = BlockPlan(16, 128, 128, batch_grid=True)
    got = ops.skew_matmul_batched(a, b, plan=plan, epilogue=epilogue,
                                  bias=bias, residual=res)
    want = ref.matmul_epilogue_ref(a, b, bias=bias, residual=res,
                                   epilogue=epilogue)
    assert got.shape == (nb, m, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=1e-4)


def test_epilogue_spec_validation():
    a, b = _arr((32, 128)), _arr((128, 32))
    with pytest.raises(ValueError):
        ops.skew_matmul(a, b, plan=BlockPlan(32, 128, 32),
                        epilogue="gelu_silu")
    with pytest.raises(ValueError):
        ops.skew_matmul(a, b, plan=BlockPlan(32, 128, 32),
                        epilogue="tanh")


@pytest.mark.parametrize("schedule", ["k_inner", "a_resident", "b_resident"])
def test_structured_epilogue_matches_oracle(schedule):
    """The Epilogue-object surface: operands ride on the spec, and the
    static `scale` op fuses without new operand plumbing."""
    m, k, n = 100, 300, 200
    a, b = _arr((m, k), scale=0.3), _arr((k, n), scale=0.3)
    ep = Epilogue(act="silu", scale=0.5, bias=_arr((n,)),
                  residual=_arr((m, n)))
    plan = BlockPlan(32, 128, 128, schedule=schedule)
    got = ops.skew_matmul(a, b, plan=plan, epilogue=ep)
    want = ref.matmul_epilogue_ref(a, b, epilogue=ep)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=1e-4)


def test_structured_epilogue_batched_grid():
    nb, m, k, n = 2, 40, 256, 96
    a, b = _arr((nb, m, k), scale=0.3), _arr((k, n), scale=0.3)
    ep = Epilogue(act="gelu", bias=_arr((n,)), residual=_arr((nb, m, n)))
    plan = BlockPlan(16, 128, 96, batch_grid=True)
    got = ops.skew_matmul_batched(a, b, plan=plan, epilogue=ep)
    want = ref.matmul_epilogue_ref(a, b, bias=ep.bias, residual=ep.residual,
                                   epilogue="bias_gelu_residual")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=1e-4)


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kw", [
    dict(causal=True),
    dict(causal=False),
    dict(causal=True, window=64),
    dict(causal=True, window=100),          # non-block-aligned window
    dict(causal=True, softcap=30.0),        # gemma2 logit soft-cap
    dict(causal=True, window=128, softcap=50.0),
])
def test_flash_attention_matches_oracle(kw, dtype):
    q = _arr((2, 4, 256, 64), dtype, 0.3)
    k = _arr((2, 2, 256, 64), dtype, 0.3)   # GQA group=2
    v = _arr((2, 2, 256, 64), dtype)
    got = ops.flash_attention(q, k, v, bq=64, bkv=64, **kw)
    want = ref.attention_ref(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("heads", [(8, 1), (8, 8), (6, 2)])
def test_flash_attention_gqa_groups(heads):
    hq, hkv = heads
    q = _arr((1, hq, 128, 32), scale=0.3)
    k = _arr((1, hkv, 128, 32), scale=0.3)
    v = _arr((1, hkv, 128, 32))
    got = ops.flash_attention(q, k, v, bq=64, bkv=64)
    want = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4)


def test_flash_attention_block_shapes_sweep():
    q = _arr((1, 2, 256, 64), scale=0.3)
    k = _arr((1, 2, 256, 64), scale=0.3)
    v = _arr((1, 2, 256, 64))
    want = ref.attention_ref(q, k, v, causal=True, window=96)
    for bq, bkv in [(32, 32), (64, 128), (128, 64), (256, 256)]:
        got = ops.flash_attention(q, k, v, bq=bq, bkv=bkv, causal=True,
                                  window=96)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4,
                                   err_msg=f"bq={bq} bkv={bkv}")


# -------------------------------------------------------------------- SSD
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("chunk", [32, 64, 128])
def test_ssd_scan_matches_oracle(chunk, dtype):
    B, L, H, P, G, S = 2, 256, 4, 64, 2, 32
    x = _arr((B, L, H, P), dtype)
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, size=(B, L, H)), dtype)
    a_log = jnp.asarray(RNG.uniform(-0.5, 1.0, size=(H,)), jnp.float32)
    bm = _arr((B, L, G, S), dtype, 0.5)
    cm = _arr((B, L, G, S), dtype, 0.5)
    got = ops.ssd_scan(x, dt, a_log, bm, cm, chunk=chunk)
    want = ref.ssd_ref(x, dt, a_log, bm, cm)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2 if dtype == jnp.bfloat16 else 2e-3,
                               atol=3e-2 if dtype == jnp.bfloat16 else 2e-3)


def test_ssd_scan_mqa_style_groups():
    # G=1 (all heads share B/C), mamba2 default
    B, L, H, P, G, S = 1, 128, 8, 32, 1, 16
    x = _arr((B, L, H, P))
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, size=(B, L, H)), jnp.float32)
    a_log = jnp.asarray(RNG.uniform(-0.5, 0.5, size=(H,)), jnp.float32)
    bm, cm = _arr((B, L, G, S), scale=0.5), _arr((B, L, G, S), scale=0.5)
    got = ops.ssd_scan(x, dt, a_log, bm, cm, chunk=64)
    want = ref.ssd_ref(x, dt, a_log, bm, cm)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


# -------------------------------------------------------------------- RG-LRU
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("chunk", [32, 64, 256])
def test_rglru_scan_matches_oracle(chunk, dtype):
    B, L, D = 2, 256, 32
    x = _arr((B, L, D), dtype)
    r = _arr((B, L, D), dtype)
    i = _arr((B, L, D), dtype)
    lam = jnp.asarray(RNG.uniform(-2, 2, size=(D,)), jnp.float32)
    got = ops.rglru_scan(x, r, i, lam, chunk=chunk)
    want = ref.rglru_ref(x, r, i, lam)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2 if dtype == jnp.bfloat16 else 2e-3,
                               atol=3e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_rglru_strong_decay_stability():
    """The regime that breaks the naive exp-prefix formulation."""
    B, L, D = 1, 128, 16
    x = _arr((B, L, D))
    r = jnp.full((B, L, D), 5.0)            # sigmoid ~ 1: max decay
    i = _arr((B, L, D))
    lam = jnp.full((D,), 4.0)               # softplus(4) ~ 4: a ~ e^-32
    got = ops.rglru_scan(x, r, i, lam, chunk=64)
    want = ref.rglru_ref(x, r, i, lam)
    assert not np.any(np.isnan(np.asarray(got)))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=1e-5)
