"""Server decode loop: host time per engine step outside the device wait
and outside parks (``host_s``: admission, dispatch, fetch, bookkeeping),
window delta over steps, in ms."""


def value(rec):
    c = rec["counters"]
    if not c["steps"]:
        return None
    return 1e3 * c["host_s"] / c["steps"]
