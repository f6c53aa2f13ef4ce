"""Decode step: operations of the serve-step executions in the traced
window over what the chip's peak bf16 rate gives in that window, in %."""


def value(rec):
    t = rec.get("trace")
    if not t or not t["steps_s"]:
        return None
    flops = len(t["steps_s"]) * rec["work"]["flops"]
    return 100.0 * flops / (t["window_s"] * t["devices"]
                            * rec["peak"]["bf16_flop_per_s"])
