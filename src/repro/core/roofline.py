"""Roofline-term extraction from compiled XLA artifacts.

Per the reproduction brief:

    compute term    = HLO_FLOPs / (chips * peak_FLOP/s)
    memory term     = HLO_bytes / (chips * HBM_bw)
    collective term = collective_bytes / (chips * link_bw)

`compiled.cost_analysis()` reports the cost of the *per-device SPMD module*
(verified empirically in tests/test_roofline.py), so HLO_FLOPs for the global
step = per_device_flops * chips; the two normalizations cancel and the
compute term is simply per_device_flops / peak.  Same for bytes.

collective_bytes is parsed from the HLO text: we sum the output operand sizes
of all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
ops, weighted by the bytes-on-wire factor of a ring implementation of each.
"""

from __future__ import annotations

import dataclasses
import json
import re

from repro.core import hw

# bytes-on-wire multiplier per collective, ring algorithm, large-N limit:
#   all-gather: each device sends its shard N-1 times -> (N-1)/N ~ 1x output
#   all-reduce: reduce-scatter + all-gather -> 2x
#   reduce-scatter: 1x input shard traffic ~ 1x
#   all-to-all: (N-1)/N ~ 1x
#   collective-permute: 1x
_WIRE_FACTOR = {
    "all-gather": 1.0,
    "all-reduce": 2.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "c128": 16, "s4": 1, "u4": 1,
}

# e.g. "bf16[256,4096,7168]{2,1,0}"  or  "f32[]"
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# an HLO instruction line: "%name = <shape-or-tuple> opcode(...)"
_INSTR_RE = re.compile(
    r"=\s+(.+?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")


def _shape_bytes(shape_text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_text):
        if dt not in _DTYPE_BYTES:
            continue
        elems = 1
        if dims:
            for d in dims.split(","):
                elems *= int(d)
        total += elems * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    counts: dict[str, int]
    bytes_by_kind: dict[str, float]   # wire bytes per device

    @property
    def total_bytes(self) -> float:
        return sum(self.bytes_by_kind.values())


def collective_stats(hlo_text: str) -> CollectiveStats:
    """Sum per-device wire bytes of every collective in an HLO module.

    `-done` ops are skipped so async (start/done) pairs count once.
    """
    counts: dict[str, int] = {}
    by_kind: dict[str, float] = {}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        m = _INSTR_RE.search(line)
        if not m:
            continue
        shape_text, kind = m.group(1), m.group(2)
        nbytes = _shape_bytes(shape_text) * _WIRE_FACTOR[kind]
        counts[kind] = counts.get(kind, 0) + 1
        by_kind[kind] = by_kind.get(kind, 0.0) + nbytes
    return CollectiveStats(counts=counts, bytes_by_kind=by_kind)


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    # per-device quantities from the SPMD module
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    # roofline terms, seconds
    compute_s: float
    memory_s: float
    collective_s: float
    # bookkeeping
    model_flops: float            # 6*N*D (or 6*N_active*D) for the step
    peak_flops: float
    bytes_per_device: int
    collective_counts: dict[str, int]

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """useful-model-FLOPs MFU at the roofline-limited step time."""
        if self.step_s == 0:
            return 0.0
        return (self.model_flops / self.chips / self.step_s) / self.peak_flops

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs*chips): remat/redundancy waste detector."""
        total_hlo = self.hlo_flops * self.chips
        return self.model_flops / total_hlo if total_hlo else 0.0

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["dominant"] = self.dominant
        d["step_s"] = self.step_s
        d["roofline_fraction"] = self.roofline_fraction
        d["useful_ratio"] = self.useful_ratio
        return d

    def row(self) -> str:
        return (f"{self.arch:<24}{self.shape:<13}{self.mesh:<10}"
                f"compute={self.compute_s * 1e3:9.2f}ms "
                f"memory={self.memory_s * 1e3:9.2f}ms "
                f"coll={self.collective_s * 1e3:9.2f}ms "
                f"dom={self.dominant:<10} useful={self.useful_ratio:5.2f} "
                f"frac={self.roofline_fraction:5.3f}")


def analyze(compiled, hlo_text: str, *, arch: str, shape: str, mesh: str,
            chips: int, model_flops: float,
            dtype_bytes: int = 2, ici_links: int | None = None,
            chip: hw.ChipSpec = hw.TPU_V5E) -> RooflineReport:
    """Build a RooflineReport from a compiled executable + its HLO text.

    `ici_links` defaults to the chip's own link count (`ChipSpec.ici_links`
    — e.g. 10 IPU-Links on the GC200, not the 4 the old hardcoded default
    assumed); pass it only to model a deliberately reduced topology.
    """
    if ici_links is None:
        ici_links = chip.ici_links
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    hbm_bytes = float(ca.get("bytes accessed", 0.0))
    coll = collective_stats(hlo_text)
    peak = hw.peak_flops(chip, dtype_bytes)
    ma = compiled.memory_analysis()
    bytes_per_device = int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                           - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh, chips=chips,
        hlo_flops=flops, hlo_bytes=hbm_bytes,
        collective_bytes=coll.total_bytes,
        compute_s=flops / peak,
        memory_s=hbm_bytes / chip.hbm_bw,
        collective_s=coll.total_bytes / (chip.ici_bw_per_link * ici_links),
        model_flops=model_flops,
        peak_flops=peak,
        bytes_per_device=bytes_per_device,
        collective_counts=coll.counts,
    )


def save_report(report: RooflineReport, path: str) -> None:
    with open(path, "w") as f:
        json.dump(report.to_json(), f, indent=2, default=float)
