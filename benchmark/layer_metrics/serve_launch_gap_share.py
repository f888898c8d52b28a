"""Share of the traced seconds in which the device waited for the engine's
next launch: the gaps of ``serve_launch_gap_p50_ms`` summed, over those
seconds.  Gaps in which the engine had nothing to launch count too (the
device idles then as well), so this is the engine's own account of the
device's idle share; where it reads less than the trace's, the engine
cannot see the rest."""

from benchmark.layer_metrics.serve_launch_gap_p50_ms import launch_gaps


def read(name, obs, cell, cfg, peak):
    gaps = launch_gaps(obs)
    if not gaps:
        return None
    lo, hi = obs["traced"]
    return 100.0 * sum(g for _, g in gaps) / (hi - lo)
