"""Share of the roofline reached by the traced pure-decode ticks: their
least time (the larger of FLOPs over peak and bytes over bandwidth, with
bytes = every weight once plus the K/V of the live rows' real positions)
over their device-busy time."""

from chip import flops
from chip.stats import traced_steps


def read(rec):
    least = busy = 0.0
    pk = rec["peaks"]
    for s, _, b in traced_steps(rec):
        if s["admit"] or not s["positions"]:
            continue
        t, _ = flops.least_seconds(
            flops.decode_flops(rec["dims"], s["positions"]),
            flops.decode_bytes(rec["dims"], s["positions"]),
            pk.flops_bf16, pk.hbm_bytes_per_s)
        least += t
        busy += b
    return 100.0 * least / busy if busy else None
