"""Mean first-token time over the finished requests, from the due time: the
steadier companion of ``ttft_p90_ms`` (one order statistic of 72 requests
moves by a whole engine step; the mean of all does not).  Host clock."""


def read(name, obs, cell, cfg, peak):
    upto = obs.get("untraced_s", obs["seconds"])
    ttft = [(r["token_s"][0] - r["due_s"]) * 1e3 for r in obs["requests"]
            if r["token_s"] and r["due_s"] < upto]
    return sum(ttft) / len(ttft) if ttft else None
