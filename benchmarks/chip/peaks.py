"""Published peaks of the chips the benchmark runs on, keyed by the
`device_kind` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float      # FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: float


_V5E = Peaks(flops_bf16=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9)

TABLE = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def for_kind(device_kind: str) -> Peaks:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(TABLE)}") from None
