"""Device time of the collectives (all-reduce, all-gather,
reduce-scatter, all-to-all, collective-permute) in the traced window, on
the chip that spends most on them, per training step."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not rec["steps"]:
        return None
    return tr["collective_s_most"] / len(rec["steps"]) * 1e3
