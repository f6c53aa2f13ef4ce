"""Server decode loop: cache rows in use per engine step over the rows a
step decodes, ``rows / (steps x max_batch)`` from the window deltas of
the servers' ``InferenceServer.stats()`` counters, in %."""


def value(rec):
    c = rec["counters"]
    if not c["steps"]:
        return None
    return 100.0 * c["rows"] / (c["steps"] * rec["max_batch"])
