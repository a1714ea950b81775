"""Median of all gaps between consecutive output tokens of a request,
both inside the window, over all requests."""

from chip.stats import percentile


def read(rec):
    p = percentile(rec.get("itl_s", []), 50)
    return None if p is None else p * 1e3
