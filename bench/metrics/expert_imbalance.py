"""Expert layer: the busiest held expert's picks over the mean held
expert's, ``expert_peak_picks / (expert_picks / experts_held)``, each
summed over the expert layers and the window's steps, of the rows in
use, in %. 100 is an even load."""


def value(rec):
    c = rec["counters"]
    if "expert_picks" not in c or not c["expert_picks"]:
        return None
    return 100.0 * c["expert_peak_picks"] / (
        c["expert_picks"] / rec["work"]["experts_held"])
