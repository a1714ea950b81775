"""Sharding-aware joint planning: ShardSpec, collective terms, joint search.

Everything here is pure cost-model arithmetic — no device mesh is created
— so the tests pin exact byte counts and invariants, not tolerances.
"""

import pytest
from jax.sharding import AbstractMesh

from repro.core import hw
from repro.core.config import mm_config, parse_mesh
from repro.core.costmodel import (
    OVERLAP_EFFICIENCY,
    BlockPlan,
    MatmulDims,
    ShardSpec,
    collective_terms,
    cost_matmul,
    cost_sharded_matmul,
)
from repro.core.planner import plan_matmul, shard_candidates
from repro.distributed import sharding as shd

GC200 = hw.get_chip("ipu_gc200")
V5E = hw.get_chip("tpu_v5e")
RTX = hw.get_chip("gpu_rtx2080ti")


# ------------------------------------------------------------- ShardSpec
def test_shardspec_validation():
    with pytest.raises(ValueError):
        ShardSpec(m=0)
    with pytest.raises(ValueError):
        ShardSpec(k=-2)
    with pytest.raises(ValueError):
        ShardSpec(n=2.0)
    with pytest.raises(ValueError):
        ShardSpec(k=2, partials="ring")


def test_shardspec_devices_and_local_dims():
    spec = ShardSpec(m=2, k=4, n=2, batch=2)
    assert spec.devices == 32
    d = MatmulDims(4096, 4096, 4096, batch=4)
    ld = spec.local_dims(d)
    assert (ld.m, ld.k, ld.n, ld.batch) == (2048, 1024, 2048, 2)
    # ceil-div keeps tiny shapes valid
    ld = ShardSpec(m=64).local_dims(MatmulDims(100, 8, 8))
    assert ld.m == 2


def test_shardspec_describe():
    assert ShardSpec().describe() == "m1k1n1b1"
    assert ShardSpec(k=4).describe() == "m1k4n1b1/all_reduce"
    s = ShardSpec(m=2, k=2, partials="reduce_scatter", zero3=True)
    assert s.describe() == "m2k2n1b1/reduce_scatter/zero3"


# ----------------------------------------------------- collective arithmetic
def test_gather_a_bytes_exact():
    """n-sharding all-gathers A: (n-1)/n x local A bytes on the wire."""
    d = MatmulDims(1024, 2048, 4096, dtype_bytes=2)
    p = BlockPlan(256, 256, 256)
    spec = ShardSpec(n=4)
    t = collective_terms(d, p, GC200, spec)
    a_local = 1024 * 2048 * 2          # A is not n-sharded: full local A
    assert t.gather_a_bytes == 3 * a_local // 4
    assert t.gather_b_bytes == 0
    assert t.partials_bytes == 0


def test_partials_all_reduce_vs_reduce_scatter_exact():
    """all-reduce moves 2x the ring bytes of reduce-scatter, acc width."""
    d = MatmulDims(1024, 4096, 2048, dtype_bytes=2, acc_bytes=4)
    p = BlockPlan(256, 256, 256)
    ar = collective_terms(d, p, V5E, ShardSpec(k=4, partials="all_reduce"))
    rs = collective_terms(d, p, V5E, ShardSpec(k=4, partials="reduce_scatter"))
    c_partial = 1024 * 2048 * 4        # local C partial at acc width
    assert rs.partials_bytes == 3 * c_partial // 4
    assert ar.partials_bytes == 2 * rs.partials_bytes


def test_zero3_gathers_b_over_data_group():
    d = MatmulDims(4096, 4096, 4096, dtype_bytes=2)
    p = BlockPlan(512, 512, 512)
    spec = ShardSpec(m=4, zero3=True)
    t = collective_terms(d, p, V5E, spec)
    b_local = 4096 * 4096 * 2
    assert t.gather_b_bytes == 3 * b_local // 4
    # without zero3 the m-group holds B resident: no traffic at all
    t0 = collective_terms(d, p, V5E, ShardSpec(m=4))
    assert t0.total_bytes == 0


def test_wire_seconds_priced_against_aggregate_links():
    """Collective seconds = bytes / (per-link bw x link count)."""
    d = MatmulDims(2048, 2048, 2048, dtype_bytes=2)
    p = BlockPlan(256, 256, 256)
    spec = ShardSpec(n=2)
    for chip in (GC200, V5E, RTX):
        t = collective_terms(d, p, chip, spec)
        agg = chip.ici_bw_per_link * chip.ici_links
        assert t.total_s == pytest.approx(t.total_bytes / agg)


def test_overlap_hideability_is_schedule_dependent():
    """gather-A hides behind k_inner (m blocked, not innermost) but not
    behind b_resident (m innermost) — the windowed-einsum condition."""
    d = MatmulDims(4096, 4096, 4096, dtype_bytes=2)
    spec = ShardSpec(n=4)
    hide = collective_terms(d, BlockPlan(512, 512, 512), GC200, spec)
    assert hide.hideable_s == pytest.approx(hide.total_s)
    noh = collective_terms(
        d, BlockPlan(512, 512, 512, schedule="b_resident"), GC200, spec)
    assert noh.hideable_s == 0.0
    # all-reduce partials are a barrier: never hideable
    ar = collective_terms(d, BlockPlan(512, 512, 512), GC200,
                          ShardSpec(k=4, partials="all_reduce"))
    assert ar.hideable_s == 0.0
    rs = collective_terms(d, BlockPlan(512, 512, 512), GC200,
                          ShardSpec(k=4, partials="reduce_scatter"))
    assert rs.hideable_s > 0.0


def test_sharded_cost_floor_invariant():
    """Exposed collectives only add: sharded total >= same-plan local."""
    d = MatmulDims(4096, 4096, 4096, dtype_bytes=2)
    p = BlockPlan(512, 512, 512)
    for spec in (ShardSpec(m=4), ShardSpec(k=4), ShardSpec(n=4),
                 ShardSpec(m=2, k=2, n=2, partials="reduce_scatter"),
                 ShardSpec(m=2, n=2, zero3=True)):
        for chip in (GC200, V5E, RTX):
            local = cost_matmul(spec.local_dims(d), p, chip)
            c = cost_sharded_matmul(d, p, chip, spec, local=local)
            assert c.total_s >= local.total_s - 1e-18, (spec, chip.name)
            assert c.collective_s >= 0.0
            assert c.dims == local.dims          # local shard dims
            assert c.global_dims == d


def test_hidden_collective_bounded_by_busy_and_efficiency():
    d = MatmulDims(4096, 4096, 4096, dtype_bytes=2)
    p = BlockPlan(512, 512, 512)
    spec = ShardSpec(n=4)
    local = cost_matmul(spec.local_dims(d), p, GC200)
    c = cost_sharded_matmul(d, p, GC200, spec, local=local)
    busy = max(local.compute_s, local.memory_s)
    t = collective_terms(d, p, GC200, spec)
    assert c.hidden_collective_s == pytest.approx(
        min(t.hideable_s, busy) * OVERLAP_EFFICIENCY)
    assert c.collective_s == pytest.approx(t.total_s - c.hidden_collective_s)


# ------------------------------------------------------------ joint search
def test_shard_candidates_cover_device_count():
    specs = shard_candidates(16, 4096, 4096, 4096, 1)
    assert all(s.devices == 16 for s in specs)
    assert len(set(specs)) == len(specs)
    # factors never exceed the dim they split
    small = shard_candidates(64, 8, 4096, 4096, 1)
    assert all(s.m <= 8 for s in small)
    # indivisible pool falls back to replication rather than dying
    assert shard_candidates(64, 1, 1, 1, 1) == (ShardSpec(),)


def test_joint_plan_picks_a_sharding():
    c = plan_matmul(4096, 4096, 4096, mesh_shape=(16,), sharding="auto")
    assert c.sharding is not None and c.sharding.devices == 16
    assert c.global_dims.m == 4096
    assert c.dims.m == 4096 // c.sharding.m or c.sharding.m == 1
    # faster than one chip, never faster than perfect scaling
    single = plan_matmul(4096, 4096, 4096)
    assert c.total_s < single.total_s
    assert c.total_s >= single.total_s / 16 - 1e-18


def test_joint_plan_respects_explicit_spec():
    spec = ShardSpec(k=4, partials="reduce_scatter")
    c = plan_matmul(4096, 4096, 4096, mesh_shape=(4,), sharding=spec)
    assert c.sharding == spec
    assert c.dims.k == 1024


def test_joint_plan_floor_invariant_across_skew():
    """The acceptance gate: no sharded plan prices below its local cost."""
    for pod in (4, 16, 64):
        for ratio in (2.0 ** -8, 1.0, 2.0 ** 8):
            m = max(1, int(round((4096 * 4096 * ratio) ** 0.5)))
            k = max(1, int(round((4096 * 4096 / ratio) ** 0.5)))
            for chip in (GC200, RTX):
                c = plan_matmul(m, k, 4096, chip=chip,
                                mesh_shape=(pod,), sharding="auto")
                local_s = max(c.compute_s, c.memory_s) + c.overhead_s
                assert c.total_s >= local_s - 1e-18, (pod, ratio, chip.name)


def test_pod16_skew_spread_verdict():
    """fig5 at pod scale: gc200's 10-link pods stay flatter across skew
    than the 2-link rtx2080ti at >=16 chips."""
    spreads = {}
    for chip in (GC200, RTX):
        fracs = []
        for ratio in (2.0 ** -8, 2.0 ** -4, 1.0, 2.0 ** 4, 2.0 ** 8):
            m = max(1, int(round((4096 * 4096 * ratio) ** 0.5)))
            k = max(1, int(round((4096 * 4096 / ratio) ** 0.5)))
            c = plan_matmul(m, k, 4096, chip=chip,
                            mesh_shape=(16,), sharding="auto")
            fracs.append(c.roofline_fraction(chip))
        spreads[chip.name] = max(fracs) - min(fracs)
    assert spreads["ipu_gc200"] < spreads["gpu_rtx2080ti"]


def test_single_chip_planning_unchanged():
    c = plan_matmul(4096, 4096, 4096)
    assert c.sharding is None
    assert c.collective_s == 0.0
    assert c.global_dims is None
    # mesh of one device is the unsharded path too
    c1 = plan_matmul(4096, 4096, 4096, mesh_shape=(1,), sharding="auto")
    assert c1.sharding is None


def test_mesh_context_resolution():
    with mm_config(mesh_shape=(4, 2), sharding="auto", chip="ipu_gc200"):
        c = plan_matmul(2048, 2048, 2048)
    assert c.sharding is not None and c.sharding.devices == 8
    assert "shard=" in c.explain()


def test_naive_sharding_is_fixed_dp():
    c = plan_matmul(4096, 4096, 4096, mesh_shape=(8,), sharding="auto",
                    mode="naive")
    assert c.sharding is not None
    assert c.sharding.k == 1 and c.sharding.n == 1
    planned = plan_matmul(4096, 4096, 4096, mesh_shape=(8,),
                          sharding="auto")
    assert planned.total_s <= c.total_s


def test_parse_mesh():
    assert parse_mesh(None) is None
    assert parse_mesh("") is None
    assert parse_mesh("8") == (8,)
    assert parse_mesh("4,2") == (4, 2)
    with pytest.raises(ValueError):
        parse_mesh("4,x")


# --------------------------------------------------------------- ici_links
def test_chip_link_counts_are_honest():
    assert GC200.ici_links == 10 and GC200.ici_bw_per_link == 32e9
    assert GC200.ici_bw == pytest.approx(320e9)
    assert RTX.ici_links == 2
    assert V5E.ici_links == 4


def test_roofline_defaults_to_chip_links():
    """roofline.analyze prices collectives against ChipSpec.ici_links."""
    from repro.core import roofline

    class _Compiled:
        def memory_analysis(self):
            class MA:
                argument_size_in_bytes = 0
                output_size_in_bytes = 0
                alias_size_in_bytes = 0
                temp_size_in_bytes = 0
            return MA()

        def cost_analysis(self):
            return {"flops": 0.0, "bytes accessed": 0.0}

    hlo = "%ag = bf16[1024,1024]{1,0} all-gather(%x)"
    rep = roofline.analyze(_Compiled(), hlo, arch="t", shape="s", mesh="m",
                           chips=2, model_flops=0.0, chip=GC200)
    wire = 1024 * 1024 * 2
    assert rep.collective_s == pytest.approx(wire / (32e9 * 10))
    # an explicit override still wins
    rep4 = roofline.analyze(_Compiled(), hlo, arch="t", shape="s", mesh="m",
                            chips=2, model_flops=0.0, chip=GC200,
                            ici_links=4)
    assert rep4.collective_s == pytest.approx(wire / (32e9 * 4))


# ------------------------------------------------------- mesh-axis bridge
def test_matmul_shard_spec_from_mesh_axes():
    mesh = AbstractMesh((4, 2), ("data", "model"))
    spec = shd.matmul_shard_spec(mesh, batch_axes="data", n_axes="model")
    assert spec == ShardSpec(batch=4, n=2)
    col = shd.tp_matmul_spec(mesh, "col")
    assert col.n == 2 and col.batch == 4 and col.k == 1
    row = shd.tp_matmul_spec(mesh, "row", dp=False)
    assert row.k == 2 and row.partials == "all_reduce" and row.batch == 1
    with pytest.raises(ValueError):
        shd.tp_matmul_spec(mesh, "diag")
    # model-only mesh: dp finds no data axes and stays unsharded on batch
    tponly = shd.tp_matmul_spec(AbstractMesh((8,), ("model",)), "col")
    assert tponly.n == 8 and tponly.batch == 1
