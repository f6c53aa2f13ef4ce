"""Decode step: the least time one serve-step call could take on this
chip, ``max(flops / peak FLOP/s, bytes / HBM bandwidth)`` from
``bench/work.py``, over its mean device time in the trace, in %."""


def value(rec):
    t = rec.get("trace")
    if not t or not t["steps_s"]:
        return None
    w, p = rec["work"], rec["peak"]
    bound = max(w["flops"] / p["bf16_flop_per_s"],
                w["bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * bound / (sum(t["steps_s"]) / len(t["steps_s"]))
