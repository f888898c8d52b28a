"""Median host time of one ``engine.step()``."""

import statistics


def read(name, obs, cell, cfg, peak):
    upto = obs.get("untraced_s", obs["seconds"])
    steps = [s["end"] - s["start"] for s in obs["steps"]
             if s["start"] < upto]
    return statistics.median(steps) * 1e3 if steps else None
