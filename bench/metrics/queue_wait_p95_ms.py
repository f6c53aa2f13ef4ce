"""Server admission: 95th percentile (nearest rank) of the wait from a
request's due time to its cache row (``started - arrival``), over every
request due in the window, in ms."""

from bench.stats import pct


def value(rec):
    v = pct(rec["server"]["queue_wait_s"], 0.95)
    return None if v is None else 1e3 * v
