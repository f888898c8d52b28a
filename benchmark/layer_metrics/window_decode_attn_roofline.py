"""The banded walk's share of its roofline: for each traced step's decode
the larger of what the walk has to read (every running row's K/V rows in
its bands: all ``c`` of the full layers', ``min(c, W)`` of each window
layer's, 4,096 bytes a row at the published widths) over the HBM bandwidth
and what it has to compute (scores and weighted sums of 48 heads) over the
bf16 peak, summed, over the kernel's device time.  Six queries a K/V row
are 3 operations a byte, far under the v5e's ridge of 240: bytes bound
it.  The rows a step decoded stand for the launch it read back (one
launch a step; the traced seconds' two ends differ by a launch)."""

from benchmark import flops as gpt_flops
from benchmark import trinity_flops as flops
from benchmark.layer_metrics import window_decode_attn_ms
from benchmark.layer_metrics.serve_step_mfu import traced_steps


def read(name, obs, cell, cfg, peak):
    if not obs.get("trace") or "traced" not in obs:
        return None
    spent = window_decode_attn_ms.kernel_seconds(obs)
    least, bounds = 0.0, set()
    for s in traced_steps(obs):
        if not s["decode_live"]:
            continue
        t, bound = gpt_flops.roofline_seconds(
            *flops.window_decode_attn_cost(cfg, s["decode_live"]), peak)
        least += t
        bounds.add(bound)
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent, "bound: " + "/".join(sorted(bounds))
