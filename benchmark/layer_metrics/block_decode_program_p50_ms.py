"""Median device time of one launch of the block-decoding engine's decode
program (the XLA module of ``serving/block_decode.py``'s jitted
``decode``: one denoising or commit pass of every running row's block),
from the device trace.  Nothing where the program counted no row pass (a
family whose ``jit_decode`` is one token a row)."""

import statistics

from benchmark.layer_metrics import decode_program_p50_ms


def launches(obs):
    if not (obs.get("counters") or {}).get("serving.diffusion.row_passes"):
        return []
    return decode_program_p50_ms.launches(obs) if obs.get("trace") else []


def read(name, obs, cell, cfg, peak):
    xs = launches(obs)
    return statistics.median(xs) * 1e3 if xs else None
