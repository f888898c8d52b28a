"""Median time from one step seen complete to the next, in the traced
part of the window.  Host clock."""

import statistics


def read(name, obs, cell, cfg, peak):
    steps = obs.get("step_s") or []
    return statistics.median(steps) * 1e3 if steps else None
