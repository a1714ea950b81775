"""Property-based tests (hypothesis) on system invariants."""

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro import guard
from repro.core import hw
from repro.core.costmodel import BlockPlan
from repro.core.planner import plan_matmul
from repro.kernels import ops, ref
from repro.models import layers
from repro.optim import compression
from repro.sparse import BlockSparseLayout
from repro.tune import calibrate
from repro.tune.shapeclass import ShapeClass, bucket_dim

SET = settings(max_examples=25, deadline=None)

dims = st.integers(min_value=1, max_value=4096)


@SET
@given(m=dims, k=dims, n=dims,
       amp=st.floats(min_value=0.05, max_value=0.95))
def test_planner_always_returns_valid_plan(m, k, n, amp):
    c = plan_matmul(m, k, n, amp=amp)
    d = c.dims
    gm, gn, gk = c.plan.grid(d)
    # full coverage
    assert gm * c.plan.bm >= m and gn * c.plan.bn >= n and gk * c.plan.bk >= k
    # costs are positive and finite
    assert 0 < c.total_s < float("inf")
    # fraction can never exceed 1
    assert c.roofline_fraction(hw.TPU_V5E) <= 1.0 + 1e-9


@SET
@given(m=st.integers(1, 300), k=st.integers(1, 300), n=st.integers(1, 300),
       seed=st.integers(0, 2 ** 16))
def test_skew_matmul_property(m, k, n, seed):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.normal(size=(m, k)) * 0.5, jnp.float32)
    b = jnp.asarray(rng.normal(size=(k, n)) * 0.5, jnp.float32)
    got = ops.skew_matmul(a, b)
    np.testing.assert_allclose(got, ref.matmul_ref(a, b),
                               rtol=5e-3, atol=5e-4)


@SET
@given(m=st.integers(1, 160), k=st.integers(1, 300), n=st.integers(1, 200),
       schedule=st.sampled_from(["k_inner", "a_resident", "b_resident"]),
       epilogue=st.sampled_from([None, "bias", "silu_residual"]),
       seed=st.integers(0, 2 ** 16))
def test_block_sparse_density_one_bitwise_dense_parity(m, k, n, schedule,
                                                       epilogue, seed):
    """A fully-dense block structure must reproduce the dense kernel
    BIT-FOR-BIT across schedules, epilogues and non-multiple-of-block
    shapes (same blocks, same accumulation order, same flush)."""
    rng = np.random.default_rng(seed)
    bm = min(32, -(-m // 8) * 8)
    bk = min(128, -(-k // 128) * 128)
    bn = min(128, -(-n // 128) * 128)
    a = jnp.asarray(rng.normal(size=(m, k)) * 0.4, jnp.float32)
    b = jnp.asarray(rng.normal(size=(k, n)) * 0.4, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    res = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    layout = BlockSparseLayout.dense(m, k, (bm, bk))
    plan = BlockPlan(bm, bk, bn, schedule=schedule)
    got = ops.sparse_matmul(a, b, layout, plan=plan, epilogue=epilogue,
                            bias=bias, residual=res)
    want = ops.skew_matmul(a, b, plan=plan, epilogue=epilogue, bias=bias,
                           residual=res)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@SET
@given(m=st.integers(1, 160), k=st.integers(1, 300), n=st.integers(1, 160),
       density=st.floats(min_value=0.05, max_value=1.0),
       seed=st.integers(0, 2 ** 16))
def test_block_sparse_matmul_property(m, k, n, density, seed):
    """Planned block-sparse matmul matches the masked dense oracle at any
    structure density (zero blocks are exact zeros, never read)."""
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.normal(size=(m, k)) * 0.5, jnp.float32)
    b = jnp.asarray(rng.normal(size=(k, n)) * 0.5, jnp.float32)
    layout = BlockSparseLayout.random(m, k, (32, 128), density, seed=seed)
    got = ops.sparse_matmul(a, b, layout)
    want = ref.block_sparse_matmul_ref(a, b, layout)
    np.testing.assert_allclose(got, want, rtol=5e-3, atol=5e-4)


@SET
@given(m=st.integers(1, 1 << 20), k=st.integers(1, 1 << 20),
       n=st.integers(1, 1 << 20), batch=st.integers(1, 256))
def test_shape_class_bucketing_is_a_partition(m, k, n, batch):
    """Autotuner bucketing (repro.tune): every (m, k, n) maps to exactly
    one shape class, and class representatives map to themselves."""
    cls = ShapeClass.of(m, k, n, batch)
    for dim, rep in zip((m, k, n, batch),
                        (cls.m, cls.k, cls.n, cls.batch)):
        # dim lies in the unique half-open dyadic bucket [rep, 2*rep):
        # buckets tile the positive integers, so membership in exactly
        # one bucket follows.
        assert rep <= dim < 2 * rep
        # the representative is a fixed point of the bucketing
        assert bucket_dim(rep) == rep
    # idempotence: bucketing a representative shape is the identity
    assert ShapeClass.of(cls.m, cls.k, cls.n, cls.batch) == cls
    # and the cache-key fragment is a pure function of the class
    assert cls.token == ShapeClass.of(m, k, n, batch).token


@SET
@given(measured=st.floats(min_value=1e-12, max_value=1e12),
       modeled=st.floats(min_value=1e-12, max_value=1e12))
def test_correction_factor_stays_in_unit_interval(measured, modeled):
    """Calibration (repro.tune): a fitted efficiency is always in (0, 1]
    whatever the measured/modeled ratio — a host may be arbitrarily
    slower than the model but is never credited as beating the roofline."""
    f = calibrate.correction_factor(measured, modeled)
    assert 0.0 < f <= 1.0


@SET
@given(base=st.floats(min_value=1e-9, max_value=1.0),
       ratios=st.lists(st.floats(min_value=1e-12, max_value=1e12),
                       max_size=8))
def test_fitted_gather_frac_stays_in_unit_interval(base, ratios):
    f = calibrate.fit_gather_frac(base, ratios)
    assert 0.0 < f <= 1.0


@SET
@given(b=st.integers(1, 3), s=st.sampled_from([17, 64, 130]),
       d=st.sampled_from([8, 32]), seed=st.integers(0, 2 ** 16))
def test_rmsnorm_scale_invariant_direction(b, s, d, seed):
    """rmsnorm(c*x) == rmsnorm(x) for any positive scalar c (fp32)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(b, s, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(d,)) * 0.1, jnp.float32)
    y1 = layers.rmsnorm(x, w)
    y2 = layers.rmsnorm(3.7 * x, w)
    np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-5)


@SET
@given(s=st.integers(2, 64), d=st.sampled_from([16, 64]),
       theta=st.sampled_from([1e4, 5e5]), seed=st.integers(0, 2 ** 16))
def test_rope_preserves_norm_and_relativity(s, d, theta, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(1, s, 1, d)), jnp.float32)
    pos = jnp.arange(s, dtype=jnp.int32)
    cos, sin = layers.rope_freqs(pos, d, theta)
    y = layers.apply_rope(x, cos, sin)
    # rotation preserves per-vector norms
    np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1),
                               jnp.linalg.norm(x, axis=-1),
                               rtol=1e-4, atol=1e-5)
    # dot(q_i, k_j) depends only on i - j: shift both by 1
    q = jnp.asarray(rng.normal(size=(d,)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(d,)), jnp.float32)

    def rot(v, p):
        c, s_ = layers.rope_freqs(jnp.asarray([p], jnp.int32), d, theta)
        return layers.apply_rope(v[None, None, None, :], c, s_)[0, 0, 0]

    d1 = jnp.dot(rot(q, 5), rot(k, 3))
    d2 = jnp.dot(rot(q, 9), rot(k, 7))
    np.testing.assert_allclose(d1, d2, rtol=1e-3, atol=1e-4)


@SET
@given(sq=st.sampled_from([33, 64, 127]), skv=st.sampled_from([64, 128]),
       window=st.one_of(st.none(), st.integers(4, 64)),
       seed=st.integers(0, 2 ** 16))
def test_blockwise_attention_property(sq, skv, window, seed):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(1, 2, sq, 16)) * 0.4, jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 1, sq, 16)) * 0.4, jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 1, sq, 16)), jnp.float32)
    got = layers.blockwise_attention(q, k, v, causal=True, window=window,
                                     q_chunk=32, kv_chunk=48)
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4)


@SET
@given(n=st.integers(1, 2048), seed=st.integers(0, 2 ** 16),
       scale=st.floats(1e-6, 1e3))
def test_quantize_error_bounded_by_half_step(n, seed, scale):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n,)) * scale, jnp.float32)
    q, s = compression.quantize(x)
    err = jnp.max(jnp.abs(compression.dequantize(q, s) - x))
    assert float(err) <= float(s) * 0.5 + 1e-12


@SET
@given(seed=st.integers(0, 2 ** 16), steps=st.integers(1, 8))
def test_error_feedback_residual_bounded(seed, steps):
    rng = np.random.default_rng(seed)
    g0 = {"w": jnp.asarray(rng.normal(size=(32,)), jnp.float32)}
    ef = compression.init_error_feedback(g0)
    for _ in range(steps):
        g = {"w": jnp.asarray(rng.normal(size=(32,)), jnp.float32)}
        _, ef = compression.compress_grads(g, ef)
        # residual can never exceed one quantization step of the carried sum
        assert float(jnp.max(jnp.abs(ef.residual["w"]))) < 1.0


@SET
@given(b=st.integers(1, 2), length=st.sampled_from([32, 96]),
       seed=st.integers(0, 2 ** 16))
def test_ssd_state_decomposition(b, length, seed):
    """SSD over [x1; x2] == SSD(x2) seeded with state(x1) — the chunked
    algorithm's core invariant."""
    from repro.models.ssm import ssd_chunked
    rng = np.random.default_rng(seed)
    H, P, G, S = 2, 8, 1, 4
    half = length // 2
    x = jnp.asarray(rng.normal(size=(b, length, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.1, (b, length, H)), jnp.float32)
    a_log = jnp.asarray(rng.uniform(-0.5, 0.5, (H,)), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(b, length, G, S)) * 0.5, jnp.float32)
    cm = jnp.asarray(rng.normal(size=(b, length, G, S)) * 0.5, jnp.float32)
    y_full = ssd_chunked(x, dt, a_log, bm, cm, chunk=16)
    _, st1 = ssd_chunked(x[:, :half], dt[:, :half], a_log, bm[:, :half],
                         cm[:, :half], chunk=16, return_state=True)
    y2 = ssd_chunked(x[:, half:], dt[:, half:], a_log, bm[:, half:],
                     cm[:, half:], chunk=16, init_state=st1)
    np.testing.assert_allclose(y2, y_full[:, half:], rtol=2e-3, atol=2e-3)


@SET
@given(kinds=st.lists(st.sampled_from(guard.FAULT_KINDS), min_size=1,
                      unique=True).map(lambda ks: tuple(sorted(ks))),
       fault_seed=st.integers(0, 2 ** 16),
       rate=st.floats(min_value=0.1, max_value=1.0),
       m=st.integers(1, 200), k=st.integers(1, 200), n=st.integers(1, 200),
       data_seed=st.integers(0, 2 ** 16))
def test_guarded_matmul_never_escapes_silently(kinds, fault_seed, rate,
                                               m, k, n, data_seed):
    """Under ANY fault combination at ANY seed, a guarded matmul either
    returns oracle-matching output (possibly from a lower ladder level)
    or raises a typed GuardError — never a silent NaN/Inf — and the
    injection ledger stays balanced (every fault accounted for)."""
    rng = np.random.default_rng(data_seed)
    a = jnp.asarray(rng.normal(size=(m, k)) * 0.5, jnp.float32)
    b = jnp.asarray(rng.normal(size=(k, n)) * 0.5, jnp.float32)
    guard.reset()
    try:
        with guard.fault_scope(kinds=kinds, seed=fault_seed, rate=rate):
            try:
                got = ops.skew_matmul(a, b)
            except guard.GuardError:
                got = None  # typed refusal is an allowed outcome
        if got is not None:
            assert bool(jnp.isfinite(got).all())
            np.testing.assert_allclose(got, ref.matmul_ref(a, b),
                                       rtol=5e-3, atol=5e-4)
        assert guard.health.get("faults_caught") == \
            guard.health.get("faults_injected")
    finally:
        guard.reset()


# ------------------------------------------------------ sharding-rule props
from jax.sharding import AbstractMesh, PartitionSpec as P  # noqa: E402

from repro.distributed import sharding as shd  # noqa: E402

PROP_MESH = AbstractMesh((4, 8), ("data", "model"))

_axis_entries = st.sampled_from([None, "data", "model", ("data", "model")])
_shapes = st.lists(st.integers(1, 512), min_size=1, max_size=4)


def _size(axes) -> int:
    return shd._axis_size(PROP_MESH, axes)


@SET
@given(shape=_shapes, entries=st.lists(_axis_entries, max_size=5))
def test_guard_spec_invariants(shape, entries):
    """_guard never emits a spec that outranks the value or asks for an
    indivisible split — and an overlong spec raises instead of silently
    truncating."""
    shape = tuple(shape)
    spec = P(*entries)
    if len(entries) > len(shape):
        with pytest.raises(ValueError):
            shd._guard(spec, shape, PROP_MESH)
        return
    out = tuple(shd._guard(spec, shape, PROP_MESH))
    assert len(out) == len(shape)
    for dim, axes in zip(shape, out):
        size = _size(axes)
        assert dim % size == 0
        # sharded -> gathered round-trip preserves the dim
        assert (dim // size) * size == dim


_param_names = st.sampled_from(
    ["wq", "wo", "embed", "unembed", "w_gate", "mystery", "conv_w", "bq"])


@SET
@given(name=_param_names,
       shape=st.lists(st.sampled_from([1, 8, 16, 64, 128, 256, 31]),
                      min_size=1, max_size=4))
def test_param_spec_invariants(name, shape):
    """Every rule output matches the leaf's rank and only asks for
    divisible splits, whatever the name/rank combination."""
    import jax

    shape = tuple(shape)
    # abstract leaf: param_spec only reads .shape, and materializing a
    # (256, 256, 256, 256) zeros array would be 17 GB
    leaf = jax.ShapeDtypeStruct(shape, jnp.float32)
    spec = shd.param_spec([name], leaf, PROP_MESH)
    out = tuple(spec)
    assert len(out) == len(shape)
    for dim, axes in zip(shape, out):
        assert dim % _size(axes) == 0


@SET
@given(shape=st.lists(st.sampled_from([4, 8, 64, 128, 31, 256]),
                      min_size=1, max_size=4),
       model_on=st.integers(-1, 3))
def test_zero1_spec_invariants(shape, model_on):
    """ZeRO-1 only ever adds a divisible "data" split on a replicated dim
    and never touches dims the param spec already sharded."""
    shape = tuple(shape)
    entries = [None] * len(shape)
    if 0 <= model_on < len(shape) and shape[model_on] % 8 == 0:
        entries[model_on] = "model"
    spec = P(*entries)
    out = tuple(shd.zero1_spec(spec, shape, PROP_MESH))
    assert len(out) == len(shape)
    for dim, before, after in zip(shape, entries, out):
        if before is not None:
            assert after == before        # pre-sharded dims untouched
        assert dim % _size(after) == 0
        assert (dim // _size(after)) * _size(after) == dim
    # at most one data axis added
    added = [a for b, a in zip(entries, out) if b is None and a is not None]
    assert len(added) <= 1 and all(a == "data" for a in added)
