"""The busiest held expert of any expert layer against the mean held
expert, over the window: the largest of the counts behind
``serving.moe.assignments`` (one per layer and held expert, kept on the
device by the programs and read at the window's two ends) over their mean.
1.0 is even routing; the grouped products wait for the busiest group."""


def read(name, obs, cell, cfg, peak):
    moe = obs.get("moe")
    if not moe or not moe["assignments"]:
        return None
    per = moe["per_expert"]
    return float(per.max() / per.mean())
