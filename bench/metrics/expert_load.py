"""Expert layer: picks landing on this chip's held experts per held
expert and step, ``expert_picks / (steps x expert_layers x
experts_held)``, from the window deltas of the servers'
``InferenceServer.stats()`` counters and the family's ``serve_step``
count. Only the rows in use are counted, so a share of 8 of 64 experts
top-6 reads about 192 x 6 / 64 = 18 x the batch's occupancy."""


def value(rec):
    c, w = rec["counters"], rec["work"]
    if "expert_picks" not in c or not c["steps"]:
        return None
    return c["expert_picks"] / (c["steps"] * w["expert_layers"]
                                * w["experts_held"])
