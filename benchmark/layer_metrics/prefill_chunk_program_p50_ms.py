"""Median device time of one launch of a prefill-chunk program (the XLA
modules of ``serving/paged.py``'s jitted ``pchunk``, every bucket
together), from the device trace."""

import statistics

PROGRAM = "jit_pchunk"


def read(name, obs, cell, cfg, peak):
    if not obs.get("trace"):
        return None
    xs = [s for prog, launches in obs["trace"]["programs"].items()
          if prog.startswith(PROGRAM) for s in launches]
    return statistics.median(xs) * 1e3 if xs else None
