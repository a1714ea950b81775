"""A count that only this configuration's dims object gives, read by a
reader of its own: the MLP's share of the window's decode FLOPs."""


def read(rec):
    steps = [s for s in rec["steps"] if s["positions"]]
    total = sum(s["decode_flops"] for s in steps)
    if not total:
        return None
    mlp = sum(len(s["positions"]) for s in steps) \
        * rec["dims"].mlp_flops_per_token()
    return 100.0 * mlp / total
