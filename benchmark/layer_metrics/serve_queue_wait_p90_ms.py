"""From the instant a request was due to the return of the step in which
it got a slot, as the benchmark sees it between ``step()`` calls."""

from benchmark import compare


def read(name, obs, cell, cfg, peak):
    upto = obs.get("untraced_s", obs["seconds"])
    waits = [(r["admitted_s"] - r["due_s"]) * 1e3 for r in obs["requests"]
             if r["admitted_s"] is not None and r["due_s"] < upto]
    return compare.percentile(waits, 90) if waits else None
