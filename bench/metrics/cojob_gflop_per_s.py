"""Work the co-located host BLAS job completed in the window, per second
of window: its finished matrix products times their operations."""


def value(rec):
    if rec.get("cojob_flop") is None:
        return None
    return rec["counters"]["cojob_done"] * rec["cojob_flop"] / \
        rec["client"]["seconds"] / 1e9
