"""Server decode loop: mean device-idle time between consecutive
serve-step executions in the traced window."""


def value(rec):
    t = rec.get("trace")
    if not t or not t["step_gaps_s"]:
        return None
    return 1e3 * sum(t["step_gaps_s"]) / len(t["step_gaps_s"])
