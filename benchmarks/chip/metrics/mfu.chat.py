"""Model FLOPs of every prompt and output token processed in the traced
window (prefills of the admitted prompts, decode of every live row) over
the window's length times the chip's peak."""


def read(rec):
    tr = rec.get("trace")
    if not tr:
        return None
    work = sum(s["prefill_flops"] + s["decode_flops"] for s in rec["steps"])
    return 100.0 * work / (tr["window_s"] * rec["peaks"].flops_bf16)
