"""Device-busy time of the traced ticks that admitted nothing (pure
decode steps), averaged over them."""

from chip.stats import traced_steps


def read(rec):
    busy = [b for s, _, b in traced_steps(rec) if not s["admit"]
            and s["positions"]]
    return sum(busy) / len(busy) * 1e3 if busy else None
