"""Seconds from the benchmark's start to the first request it is ready to
send: imports, weights, server start, warm-up (and compilation on a cold
cache)."""


def value(rec):
    return rec["setup_s"]
