"""Highest share of the KV pool's blocks in use, from ``engine.stats()``
after each step."""


def read(name, obs, cell, cfg, peak):
    shares = [s["blocks_used"] / s["blocks_total"] for s in obs["steps"]
              if s.get("blocks_total")]
    return 100.0 * max(shares) if shares else None
