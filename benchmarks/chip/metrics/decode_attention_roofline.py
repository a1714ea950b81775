"""Share of its roofline reached by the decode attention: over the
traced pure-decode ticks, the least time of the `attention` scope's work
(the larger of its FLOPs over peak and the K/V bytes of each live row's
real positions over bandwidth, as the configuration's dims count them)
over the device time under `attention`."""

from chip import flops
from chip.stats import traced_steps


def read(rec):
    attention = ((rec.get("trace") or {}).get("decode_scope_s") or {}).get(
        "attention")
    if not attention:
        return None
    pk, least = rec["peaks"], 0.0
    for s, _, _ in traced_steps(rec):
        if s["admit"] or not s["positions"]:
            continue
        t, _ = flops.least_seconds(
            flops.attention_flops(rec["dims"], s["positions"]),
            flops.attention_bytes(rec["dims"], s["positions"]),
            pk.flops_bf16, pk.hbm_bytes_per_s)
        least += t
    return 100.0 * least / attention
