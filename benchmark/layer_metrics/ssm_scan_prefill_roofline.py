"""The selective scan's share of its roofline in the prefill chunks: for
each chunk of the traced steps the larger of what its scans need to
compute over the bf16 peak (``7 E N`` a live token and Mamba layer) and
what they need to move over the HBM bandwidth (the live tokens' float32
inputs and outputs, the row's state read and written once a layer:
``jamba_flops.scan_cost``), summed, over the kernel's device time.  The
chunk's padded positions are not counted, so the share cannot pass 100 %.
The scan is elementwise work on the vector units, and ``peaks.json`` holds
the matrix units' peak alone: where operations bound it, the share reads
low.  Nothing where the steps carry no chunks (a kind that does not place
them) or the trace holds no such kernel."""

from benchmark import flops as gpt_flops
from benchmark import jamba_flops as flops
from benchmark.layer_metrics import ssm_scan_prefill_ms
from benchmark.layer_metrics.serve_step_mfu import traced_steps


def read(name, obs, cell, cfg, peak):
    if not obs.get("trace") or "traced" not in obs:
        return None
    steps = traced_steps(obs)
    if any("chunks" not in s for s in steps):
        return None
    spent = ssm_scan_prefill_ms.kernel_seconds(obs)
    least, bounds = 0.0, set()
    for s in steps:
        for _, take in s["chunks"]:
            t, bound = gpt_flops.roofline_seconds(
                *flops.scan_cost(cfg, take, 1), peak)
            least += t
            bounds.add(bound)
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent, "bound: " + "/".join(sorted(bounds))
