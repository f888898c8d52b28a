"""The latent decode-attention kernel's share of its roofline: for each
traced decode step the larger of what it has to read (the live latent rows
of every layer, 1,152 bytes each at the published widths) over the HBM
bandwidth and what it has to compute (``H (2 R + d_r)`` multiply-adds a
row, the absorbed form) over the bf16 peak, summed, over the kernel's
device time.  The two are within a hundredth of each other on a v5e
(242 FLOP/byte against a ridge of 240): the note says which bounds."""

from benchmark import deepseek_v2_flops as flops
from benchmark import flops as gpt_flops
from benchmark.layer_metrics import mla_decode_attn_ms
from benchmark.layer_metrics.serve_step_mfu import traced_steps


def read(name, obs, cell, cfg, peak):
    if not obs.get("trace"):
        return None
    spent = mla_decode_attn_ms.kernel_seconds(obs)
    least, bounds = 0.0, set()
    for s in traced_steps(obs):
        if s["decode_live"]:
            t, bound = gpt_flops.roofline_seconds(
                *flops.mla_decode_attn_cost(cfg, s["decode_live"]), peak)
            least += t
            bounds.add(bound)
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent, "bound: " + "/".join(sorted(bounds))
