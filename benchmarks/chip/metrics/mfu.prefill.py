"""Prompt FLOPs admitted in the window over the wall time of the ticks
that admitted them times the chip's peak."""


def read(rec):
    adm = [s for s in rec["steps"] if s["admit"]]
    wall = sum(s["t1"] - s["t0"] for s in adm)
    if not wall:
        return None
    work = sum(s["prefill_flops"] for s in adm)
    return 100.0 * work / (wall * rec["peaks"].flops_bf16)
