"""Scheduler layer: mean time the servers' decode workers waited ready
for a slot, per dispatch, over the window (``TaskStats.wait_time`` and
``dispatches`` deltas)."""


def value(rec):
    c = rec["counters"]
    if not c["dispatches"]:
        return None
    return 1e3 * c["wait_s"] / c["dispatches"]
