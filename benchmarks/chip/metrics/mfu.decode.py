"""Decode FLOPs of the ticks that admitted nothing (every live row's
token, its attention over its real positions, the LM head) over those
ticks' wall time times the chip's peak: the whole decode step's share,
beside `decode_roofline`."""


def read(rec):
    dec = [s for s in rec["steps"] if not s["admit"] and s["positions"]]
    wall = sum(s["t1"] - s["t0"] for s in dec)
    if not wall:
        return None
    work = sum(s["decode_flops"] for s in dec)
    return 100.0 * work / (wall * rec["peaks"].flops_bf16)
