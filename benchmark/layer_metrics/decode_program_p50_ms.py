"""Median device time of one launch of the decode program (the XLA module
of ``serving/paged.py``'s jitted ``decode``), from the device trace."""

import statistics

PROGRAM = "jit_decode"


def launches(obs):
    return [s for prog, xs in obs["trace"]["programs"].items()
            if prog.startswith(PROGRAM) for s in xs]


def read(name, obs, cell, cfg, peak):
    xs = launches(obs)
    return statistics.median(xs) * 1e3 if xs else None
