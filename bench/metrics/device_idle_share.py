"""Device: share of the traced window in which no operation ran, in %."""


def value(rec):
    t = rec.get("trace")
    if not t or not t["devices"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
