"""The selective scan's share of its roofline in the decode launches: for
each traced step's decode the least bytes its scans move (each RUNNING
row's float32 state read and written, and its token's inputs and output,
every Mamba layer: ``jamba_flops.scan_cost``) over the HBM bandwidth, or
what they compute over the bf16 peak if that is longer, summed, over the
kernel's device time.  Rows that are not running count nothing, so a
kernel that stops moving their state raises the share.  The rows a step
decoded stand for the launch it read back (one launch a step; the traced
seconds' two ends differ by a launch)."""

from benchmark import flops as gpt_flops
from benchmark import jamba_flops as flops
from benchmark.layer_metrics import ssm_scan_decode_ms
from benchmark.layer_metrics.serve_step_mfu import traced_steps


def read(name, obs, cell, cfg, peak):
    if not obs.get("trace") or "traced" not in obs:
        return None
    spent = ssm_scan_decode_ms.kernel_seconds(obs)
    least, bounds = 0.0, set()
    for s in traced_steps(obs):
        rows = len(s["decode_live"])
        if not rows:
            continue
        t, bound = gpt_flops.roofline_seconds(
            *flops.scan_cost(cfg, rows, rows), peak)
        least += t
        bounds.add(bound)
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent, "bound: " + "/".join(sorted(bounds))
