"""Server decode loop: 95th percentile (nearest rank) of the time from a
request's cache row to its first token (``first_token - started``), the
teacher-forced prefill, over every request due in the window."""

from bench.stats import pct


def value(rec):
    return pct(rec["server"]["prefill_s"], 0.95)
