"""95th percentile (nearest rank) of every gap between consecutive output
tokens of the requests due in the window (client side)."""

from bench.stats import pct


def value(rec):
    return pct(rec["client"]["itl_s"], 0.95)
