"""95th percentile (nearest rank) of time to first token over every
request due in the window, from its due time (open loop) or send time
(closed loop). An unanswered request counts until the drain ended."""

from bench.stats import pct


def value(rec):
    return pct(rec["client"]["ttft_s"], 0.95)
