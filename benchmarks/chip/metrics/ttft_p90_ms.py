"""90th percentile, over all requests whose first token came in the
window, of the time from when the request was due (open loop) or sent
(closed loop) to its first token."""

from chip.stats import percentile


def read(rec):
    p = percentile(rec.get("ttft_s", []), 90)
    return None if p is None else p * 1e3
