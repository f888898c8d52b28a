"""How late the load generator submitted each request after it was due
(a starved generator must not be read as a fast server).  Host clock."""

from benchmark import compare


def read(name, obs, cell, cfg, peak):
    upto = obs.get("untraced_s", obs["seconds"])
    late = [(r["submit_s"] - r["due_s"]) * 1e3 for r in obs["requests"]
            if r["submit_s"] is not None and r["due_s"] < upto]
    return compare.percentile(late, 95) if late else None
