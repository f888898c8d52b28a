"""Median host time from the device draining to the engine's next launch,
over the traced seconds: what the device waits for the scheduler.

The engine stamps the instant a blocking read-back returns (the device
then holds nothing it queued) and opens the ``serving.*.dispatch`` span of
the first launch after it with ``gap_ns``, the host time since the stamp.
A gap is that count plus the dispatch span's own length (the launch is
enqueued when the span closes).  Only gaps that began inside the traced
seconds are read: a launch whose gap began before them, and a gap with no
launch after it in them, are left out.  A program whose dispatches carry
no ``gap_ns`` (any commit before PR 36) gives ``None``."""

import statistics

from benchmark.layer_metrics import step_spans


def launch_gaps(obs):
    """``[(start_s, length_s)]`` of the gaps that began in the traced
    seconds, on the window's clock; the length ends at the traced seconds'
    end."""
    if "traced" not in obs:
        return []
    lo, hi = obs["traced"]
    out = []
    for n, _, s, e, counts in step_spans.traced_spans(obs):
        if not (n.startswith("serving.") and n.endswith(".dispatch")
                and counts and "gap_ns" in counts):
            continue
        start = s - counts["gap_ns"] / 1e9
        if lo <= start < hi:
            out.append((start, min(e, hi) - start))
    return out


def read(name, obs, cell, cfg, peak):
    gaps = [g for _, g in launch_gaps(obs)]
    return statistics.median(gaps) * 1e3 if gaps else None
