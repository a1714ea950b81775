"""Analytic cost model for a blocked matmul plan on a tiled accelerator.

This is the quantitative core of the reproduction.  The paper observes that on
the IPU, achieved matmul throughput is governed by the *work-decomposition
plan* the compiler chooses (its "vertex count"), under a hard fast-memory
budget (AMP knob).  We model exactly those effects for TPU:

  time(plan) = max(compute_term, memory_term) + grid_overhead_term

  compute_term  — MAC throughput over *padded* block volumes (MXU granularity)
  memory_term   — HBM traffic implied by the block re-visit pattern, which is
                  now *schedule-dependent*: the grid loop order decides which
                  operand is re-streamed how many times (see SCHEDULES)
  grid_overhead — per-grid-step cost; blows up for pathological plans, which is
                  the TPU analogue of the paper's right-skew vertex explosion.

Schedules (the loop-order family `kernels.skew_matmul` implements):

  "k_inner"    — grid (m, n, k), K innermost, output-stationary fp32
                 accumulator.  A re-streamed per n-block (x gn), B per m-block
                 (x gm), C written once.  The classic safe choice.
  "a_resident" — grid (m, k, n), N innermost.  Each A block stays pinned in
                 VMEM across the whole n sweep, so A is streamed exactly once;
                 B per m-block; C is revisited per k-block (read+write at
                 accumulator width when gk > 1).  Wins for right-skewed
                 (m << n) shapes, where re-streaming A per n-block is the
                 dominant waste (the LM-head / vocab-projection shape class).
  "b_resident" — grid (n, k, m), M innermost; mirror image of "a_resident".
                 B streamed once, A per n-block, C revisited per k-block.
                 Wins for left-skewed (m >> n) shapes.

These are modeled charges.  The kernels, since they first ran on a TPU,
differ: the resident schedules at gk > 1 keep their partial sums in an
fp32 VMEM strip of the whole inner extent and write C once, and every
kernel double-buffers its output block, so `BlockPlan.vmem_bytes`
undercounts what a kernel allocates (kernels.skew_matmul.compiler_params
sizes the real allocation).

The GEMV family (`GEMV_SCHEDULES`) covers the decode regime — the paper's
right-skew limit, m a handful of rows against tens of thousands of cache
columns — where no dense loop order can feed the matrix engine:

  "splitk"     — two-pass split-K: grid (k_splits, n) computes fp32 partial
                 products in parallel over K *and* N, then a second (n,)-grid
                 pass tree-reduces the k_splits partials and applies the
                 structured epilogue once after the final reduce.  A is read
                 per n-block, B exactly once, plus one write + one read of
                 the (k_splits, m, n) fp32 partial accumulator.  Compute runs
                 at `chip.gemv_splitk_frac * gs/(gs+1)` of peak — the
                 K-parallel vertex tree substitutes for MXU row fill, with an
                 Amdahl-style discount for the serial reduce.

A plan may additionally put a leading batch dimension in the grid
(`batch_grid=True`) instead of folding it into m — worthwhile when folding
would straddle batch boundaries with a badly padded bm.

All quantities are derived with napkin-math-auditable formulas so that the
planner's choices can be inspected (see `MatmulCost.explain()`).
"""

from __future__ import annotations

import dataclasses
import math

from repro.core import hw

SCHEDULES = ("k_inner", "a_resident", "b_resident")
# The split-K / tree-reduction GEMV family: searched alongside SCHEDULES
# when m (after batch folding) is below the MXU row granularity, priced by
# the same cost_matmul so family switching is a pure argmin.
GEMV_SCHEDULES = ("splitk",)
ALL_SCHEDULES = SCHEDULES + GEMV_SCHEDULES


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _round_up(a: int, b: int) -> int:
    return _ceil_div(a, b) * b


@dataclasses.dataclass(frozen=True)
class MatmulDims:
    """Problem A[batch, m, k] @ B[k, n] = C[batch, m, n].

    (paper notation: A[m,n] x B[n,k]; batch defaults to 1 = the plain 2-D
    case.  batch > 1 models a shared-weight bmm whose leading dim either
    folds into m or rides in the grid, depending on the plan.)
    """

    m: int
    k: int
    n: int
    dtype_bytes: int = 2          # operand/output element width
    acc_bytes: int = 4            # accumulator width (fp32 accumulation)
    batch: int = 1

    @property
    def flops(self) -> int:
        return 2 * self.batch * self.m * self.k * self.n

    @property
    def skew(self) -> float:
        """Paper-style skew: log2(rows/n). <0 right-skewed, >0 left-skewed.

        Rows include the batch dim — the shape class of the contraction is
        the same whether the batch folds into m or rides in the grid.
        """
        return math.log2(self.batch * self.m / self.n)


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    """A work-decomposition plan: block shape + grid loop order (schedule).

    `schedule` is one of SCHEDULES and decides the traffic pattern (which
    operand is re-streamed) as well as the kernel loop order.  `batch_grid`
    puts a leading batch dim in the grid instead of folding it into m.
    """

    bm: int
    bk: int
    bn: int
    schedule: str = "k_inner"
    batch_grid: bool = False

    def grid(self, d: MatmulDims) -> tuple[int, int, int]:
        m = d.m if self.batch_grid else d.m * d.batch
        return (_ceil_div(m, self.bm), _ceil_div(d.n, self.bn),
                _ceil_div(d.k, self.bk))

    def grid_steps(self, d: MatmulDims) -> int:
        gm, gn, gk = self.grid(d)
        steps = gm * gn * gk
        if self.schedule == "splitk":
            # The second (reduction) pass visits every output block once.
            steps += gm * gn
        return steps * d.batch if self.batch_grid else steps

    def vmem_bytes(self, d: MatmulDims) -> int:
        """Working set per grid step, with double-buffered streamed blocks.

        This is the TPU translation of the paper's "all operands must fit
        In-Processor memory".  k_inner holds the C block as an fp32 VMEM
        scratch accumulator; the resident schedules accumulate through the
        revisited output block itself (fp32-wide while gk > 1, output width
        when the contraction fits a single k block).
        """
        gk = _ceil_div(d.k, self.bk)
        a = self.bm * self.bk * d.dtype_bytes
        b = self.bk * self.bn * d.dtype_bytes
        if self.schedule == "splitk":
            # Pass 1 streams (A, B) blocks and writes one fp32 partial block;
            # pass 2 holds the whole (gk, bm, bn) partial slab for the tree
            # reduce plus the double-buffered output block.  The AMP budget
            # must cover whichever pass is wider.
            pass1 = 2 * (a + b) + self.bm * self.bn * d.acc_bytes
            pass2 = (gk * self.bm * self.bn * d.acc_bytes
                     + 2 * self.bm * self.bn * d.dtype_bytes)
            return max(pass1, pass2)
        if self.schedule == "k_inner":
            c = self.bm * self.bn * d.acc_bytes
        else:
            c_width = d.acc_bytes if gk > 1 else d.dtype_bytes
            c = 2 * self.bm * self.bn * c_width
        return 2 * (a + b) + c


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """How one matmul's dims are split across a device mesh.

    `m`/`k`/`n`/`batch` are shard counts per logical dim; their product is
    the device count the spec occupies.  Per-device dims are the ceil-div
    shards (`local_dims`), so a spec stays valid for tiny smoke shapes.

    Collective semantics (the standard SPMD reading, sequence-parallel /
    Megatron conventions):

      n > 1      — A is stored sharded across the n-group (sequence /
                   row parallel) and must be all-gathered before the
                   column-parallel matmul: ring all-gather, wire bytes
                   (n-1)/n x local A per device.
      zero3      — B is stored ZeRO-3/FSDP-sharded over the (m x batch)
                   data group and all-gathered per use.  Off by default:
                   serving keeps weights resident.
      k > 1      — each device holds a partial C over its k-shard;
                   `partials` picks the combining collective: "all_reduce"
                   (2x wire at accumulator width, output replicated in the
                   k-group) or "reduce_scatter" (1x wire, output stays
                   sharded — the windowed-einsum serving convention).

    Hashable (frozen, all-int/str fields) so it can ride in `mm_config`
    layers and the planner's lru_cache keys.
    """

    m: int = 1
    k: int = 1
    n: int = 1
    batch: int = 1
    partials: str = "all_reduce"
    zero3: bool = False

    def __post_init__(self):
        for f in ("m", "k", "n", "batch"):
            v = getattr(self, f)
            if not isinstance(v, int) or v < 1:
                raise ValueError(f"ShardSpec.{f} must be a positive int, "
                                 f"got {v!r}")
        if self.partials not in ("all_reduce", "reduce_scatter"):
            raise ValueError(f"ShardSpec.partials must be 'all_reduce' or "
                             f"'reduce_scatter', got {self.partials!r}")

    @property
    def devices(self) -> int:
        return self.m * self.k * self.n * self.batch

    def local_dims(self, d: MatmulDims) -> MatmulDims:
        """The per-device shard of the problem (ceil-div per dim)."""
        return dataclasses.replace(
            d, m=_ceil_div(d.m, self.m), k=_ceil_div(d.k, self.k),
            n=_ceil_div(d.n, self.n), batch=_ceil_div(d.batch, self.batch))

    def describe(self) -> str:
        s = f"m{self.m}k{self.k}n{self.n}b{self.batch}"
        if self.k > 1:
            s += f"/{self.partials}"
        if self.zero3:
            s += "/zero3"
        return s


@dataclasses.dataclass(frozen=True)
class MatmulCost:
    dims: MatmulDims
    plan: BlockPlan
    compute_s: float
    memory_s: float
    overhead_s: float
    hbm_bytes: int
    vmem_bytes: int
    grid_steps: int
    mxu_utilization: float        # useful / padded FLOPs
    # Sharded-execution terms (single-chip costs leave these at their
    # defaults, so every pre-sharding construction site and committed
    # baseline is unchanged).  `dims` is always the *per-device* problem;
    # `global_dims` carries the unsharded dims when a ShardSpec applies.
    sharding: "ShardSpec | None" = None
    global_dims: "MatmulDims | None" = None
    collective_bytes: int = 0     # total wire bytes per device
    collective_s: float = 0.0     # exposed (un-hidden) collective seconds
    hidden_collective_s: float = 0.0  # wire time overlapped with compute

    @property
    def total_s(self) -> float:
        return (max(self.compute_s, self.memory_s) + self.overhead_s
                + self.collective_s)

    @property
    def achieved_flops(self) -> float:
        return self.dims.flops / self.total_s

    def roofline_fraction(self, chip: hw.ChipSpec) -> float:
        return self.achieved_flops / hw.peak_flops(chip, self.dims.dtype_bytes)

    @property
    def bound(self) -> str:
        busy = max(self.compute_s, self.memory_s)
        if self.collective_s > busy and self.collective_s > self.overhead_s:
            return "collective"
        if self.overhead_s > busy:
            return "grid-overhead"
        return "compute" if self.compute_s >= self.memory_s else "memory"

    def plan_provenance(self) -> dict:
        """The chosen plan as a flat record-friendly dict.

        This is the provenance surface benchmark records carry (see
        repro.bench.record.Provenance): enough to answer "which schedule
        and blocks produced this number" without re-running the planner.
        Sharded plans additionally name the chosen ShardSpec.
        """
        p = self.plan
        out = {"schedule": p.schedule, "blocks": (p.bm, p.bk, p.bn),
               "batch_grid": p.batch_grid, "grid_steps": self.grid_steps}
        if self.sharding is not None:
            out["sharding"] = self.sharding.describe()
        return out

    def explain(self) -> str:
        d, p = self.dims, self.plan
        batch = f" batch={d.batch}{'(grid)' if p.batch_grid else '(fold)'}" \
            if d.batch > 1 else ""
        shard = ""
        if self.sharding is not None:
            shard = (f" shard={self.sharding.describe()} "
                     f"coll={self.collective_s * 1e6:.1f}us"
                     f"(+{self.hidden_collective_s * 1e6:.1f}us hidden)")
        return (
            f"mm {d.m}x{d.k}x{d.n}{batch} plan ({p.bm},{p.bk},{p.bn}) "
            f"sched={p.schedule} "
            f"grid={self.grid_steps} vmem={self.vmem_bytes / 2**20:.2f}MiB "
            f"compute={self.compute_s * 1e6:.1f}us memory={self.memory_s * 1e6:.1f}us "
            f"overhead={self.overhead_s * 1e6:.1f}us bound={self.bound} "
            f"mxu_util={self.mxu_utilization:.3f}{shard}"
        )


def _schedule_traffic(d: MatmulDims, p: BlockPlan,
                      gm: int, gn: int, gk: int) -> int:
    """HBM bytes implied by the schedule's block re-visit pattern.

    Per-operand revisit counts (nb = batch copies sharing B):

      k_inner:    A x gn,  B x gm*nb,  C written once at output width.
      a_resident: A x 1,   B x gm*nb,  C revisited gk times (fp32
                  read-modify-write; single output-width write when gk == 1).
      b_resident: A x gn,  B x 1,      C as in a_resident.
    """
    nb = d.batch
    a_elems = nb * d.m * d.k
    b_elems = d.k * d.n
    c_elems = nb * d.m * d.n
    dt = d.dtype_bytes
    if p.schedule == "splitk":
        # A's k-slices are re-read per n-block; B exactly once; the fp32
        # partial accumulator (gk, m, n) is written by pass 1 and read back
        # by the reduction pass, then C written once at output width.
        a_bytes = a_elems * gn * dt
        b_bytes = b_elems * dt
        c_bytes = 2 * gk * c_elems * d.acc_bytes + c_elems * dt
        return a_bytes + b_bytes + c_bytes
    if p.schedule == "a_resident":
        a_bytes = a_elems * dt
        b_bytes = b_elems * gm * nb * dt
    elif p.schedule == "b_resident":
        a_bytes = a_elems * gn * dt
        b_bytes = b_elems * dt
    else:  # k_inner
        a_bytes = a_elems * gn * dt
        b_bytes = b_elems * gm * nb * dt
    if p.schedule == "k_inner" or gk == 1:
        c_bytes = c_elems * dt
    else:
        # first visit writes, each later visit reads + writes, all fp32-wide
        # ((2*gk - 1) acc-width passes), plus the cast back to output width
        # outside the kernel: one fp32 read + one output-width write.
        c_bytes = 2 * gk * c_elems * d.acc_bytes + c_elems * dt
    return a_bytes + b_bytes + c_bytes


def cost_matmul(d: MatmulDims, p: BlockPlan,
                chip: hw.ChipSpec = hw.TPU_V5E) -> MatmulCost:
    """Evaluate a block plan against the chip model."""
    gm, gn, gk = p.grid(d)
    nb = d.batch if p.batch_grid else 1
    m_eff = d.m if p.batch_grid else d.m * d.batch

    # ---- compute term: the MXU processes padded blocks. Pad each block dim to
    # the hardware granule (lanes on the minor dims, sublanes on m).
    pbm = _round_up(p.bm, chip.mxu_sublanes)
    pbk = _round_up(p.bk, chip.mxu_lanes)
    pbn = _round_up(p.bn, chip.mxu_lanes)
    padded_flops = 2 * nb * (gm * pbm) * (gk * pbk) * (gn * pbn)
    # GEMV-shaped blocks (bm << lanes) cannot fill the systolic array rows:
    # the MXU issues a full 128-row pass regardless, so row-underfill is an
    # additional multiplicative loss.
    row_fill = min(1.0, pbm / chip.mxu_lanes)
    if p.schedule == "splitk":
        # K-parallelism substitutes for row fill: gk partial products run
        # concurrently across the tile fabric at the chip's GEMV efficiency,
        # discounted Amdahl-style for the serial tree reduce.  (The reduce
        # adds (gk-1)*m*n flops — negligible against 2*m*k*n for k >> gk.)
        frac = min(1.0, chip.gemv_splitk_frac * gk / (gk + 1))
        eff_peak = hw.peak_flops(chip, d.dtype_bytes) * frac
    else:
        eff_peak = hw.peak_flops(chip, d.dtype_bytes) * max(
            row_fill, 1.0 / chip.mxu_lanes * 8)
    compute_s = padded_flops / eff_peak
    mxu_utilization = d.flops / padded_flops

    # ---- memory term: schedule-dependent block re-visit traffic.
    deff = dataclasses.replace(d, m=m_eff, batch=nb)
    hbm_bytes = _schedule_traffic(deff, p, gm, gn, gk)
    memory_s = hbm_bytes / chip.hbm_bw

    # ---- grid overhead: the "vertex count" term.  splitk pays the partial
    # pass plus one reduce step per output block.
    steps = nb * gm * gn * gk
    if p.schedule == "splitk":
        steps += nb * gm * gn
    overhead_s = steps * chip.grid_step_overhead_s

    return MatmulCost(
        dims=d, plan=p,
        compute_s=compute_s, memory_s=memory_s, overhead_s=overhead_s,
        hbm_bytes=hbm_bytes, vmem_bytes=p.vmem_bytes(d), grid_steps=steps,
        mxu_utilization=mxu_utilization,
    )


# ------------------------------------------------------- sharded execution
# Fraction of hideable wire time the async-collective pipeline actually
# hides (windowed einsum is not perfectly overlapped: the first window's
# transfer and the per-window collective-permute issue cost stay exposed).
OVERLAP_EFFICIENCY = 0.8


@dataclasses.dataclass(frozen=True)
class CollectiveTerms:
    """Per-device wire traffic for one sharded matmul, term by term."""

    gather_a_bytes: int           # ring all-gather of A over the n-group
    gather_b_bytes: int           # ZeRO-3 all-gather of B over (m x batch)
    partials_bytes: int           # reduce-scatter / all-reduce of partial C
    hideable_s: float             # wire seconds the schedule can overlap
    total_s: float                # wire seconds before any overlap

    @property
    def total_bytes(self) -> int:
        return self.gather_a_bytes + self.gather_b_bytes + self.partials_bytes


def _ring_wire(local_bytes: int, group: int, factor: float = 1.0) -> int:
    """Per-device wire bytes of a ring collective over `group` devices.

    all-gather / reduce-scatter move (group-1)/group of the local payload
    per device (factor 1); all-reduce is reduce-scatter + all-gather
    (factor 2).  Matches roofline._WIRE_FACTOR's large-N ring accounting.
    """
    if group <= 1:
        return 0
    return int(factor * (group - 1) * local_bytes // group)


def collective_terms(d: MatmulDims, p: BlockPlan, chip: hw.ChipSpec,
                     spec: ShardSpec) -> CollectiveTerms:
    """Wire traffic + overlap potential for plan `p` under sharding `spec`.

    `d` is the *global* problem; payloads are the post-gather per-device
    shards.  Whether a transfer is hideable is schedule-dependent — the
    windowed-einsum condition is that the kernel's grid makes progress on
    chunks of the gathered operand as they arrive, i.e. the gathered dim
    is blocked (>1 grid step) and is not swept by the innermost loop:

      gather A (chunks along m) — hidden unless the schedule sweeps m
        innermost (b_resident) or doesn't block m at all (splitk, gm==1).
      gather B (chunks along n) — hidden unless n is innermost
        (a_resident) or unblocked (gn==1).
      partials — reduce-scatter streams per k-shard behind the next
        window's compute; all-reduce is a barrier after the last partial
        and is never hidden.
    """
    ld = spec.local_dims(d)
    gm, gn, gk = p.grid(ld)
    dt, acc = d.dtype_bytes, d.acc_bytes
    ici_bw = chip.ici_bw_per_link * chip.ici_links

    a_local = ld.batch * ld.m * ld.k * dt
    gather_a = _ring_wire(a_local, spec.n)
    b_local = ld.k * ld.n * dt
    data_group = spec.m * spec.batch
    gather_b = _ring_wire(b_local, data_group) if spec.zero3 else 0
    c_partial = ld.batch * ld.m * ld.n * acc
    factor = 2.0 if spec.partials == "all_reduce" else 1.0
    partials = _ring_wire(c_partial, spec.k, factor)

    gather_a_s = gather_a / ici_bw
    gather_b_s = gather_b / ici_bw
    partials_s = partials / ici_bw
    hideable = 0.0
    if gm > 1 and p.schedule not in ("b_resident", "splitk"):
        hideable += gather_a_s
    if gn > 1 and p.schedule != "a_resident":
        hideable += gather_b_s
    if gk > 1 and spec.partials == "reduce_scatter":
        hideable += partials_s
    return CollectiveTerms(
        gather_a_bytes=gather_a, gather_b_bytes=gather_b,
        partials_bytes=partials, hideable_s=hideable,
        total_s=gather_a_s + gather_b_s + partials_s)


def cost_sharded_matmul(d: MatmulDims, p: BlockPlan, chip: hw.ChipSpec,
                        spec: ShardSpec, *,
                        local: MatmulCost | None = None) -> MatmulCost:
    """Evaluate plan `p` for the per-device shard of `d` under `spec`.

    The returned cost's `dims` are the local shard (so roofline fractions
    stay per-chip numbers comparable to fig5), `global_dims` the unsharded
    problem.  Exposed collective time is total wire time minus the part
    the schedule hides behind its own busy time (never below zero), so a
    sharded plan never prices below the same plan on its local shard —
    the planner's floor invariant.  `local` lets the planner's joint
    search pass the already-priced local cost instead of re-deriving it.
    """
    if local is None:
        local = cost_matmul(spec.local_dims(d), p, chip)
    coll = collective_terms(d, p, chip, spec)
    busy = max(local.compute_s, local.memory_s)
    hidden = min(coll.hideable_s, busy) * OVERLAP_EFFICIENCY
    return dataclasses.replace(
        local, sharding=spec, global_dims=d,
        collective_bytes=coll.total_bytes,
        collective_s=coll.total_s - hidden,
        hidden_collective_s=hidden)
