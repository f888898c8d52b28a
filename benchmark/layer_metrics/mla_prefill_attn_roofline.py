"""The latent prefill-attention kernel's share of its roofline: for each
traced step's chunks the larger of what materialised attention has to
compute (``H (d_n + d_r + d_v)`` multiply-adds a query and key it may
see, the causal count) over the bf16 peak and what it has to move (the
queries in, the outputs out, the latent rows a query sees) over the HBM
bandwidth, summed, over the kernel's device time.  The kernel folds whole
tiles of keys, the masked pairs of a chunk's own tile too, so a chunk at
the start of a prompt reads lowest."""

from benchmark import deepseek_v2_flops as flops
from benchmark import flops as gpt_flops
from benchmark.layer_metrics import mla_prefill_attn_ms
from benchmark.layer_metrics.serve_step_mfu import traced_steps


def read(name, obs, cell, cfg, peak):
    if not obs.get("trace"):
        return None
    spent = mla_prefill_attn_ms.kernel_seconds(obs)
    least, bounds = 0.0, set()
    for s in traced_steps(obs):
        if s["prefill"][0]:
            t, bound = gpt_flops.roofline_seconds(
                *flops.mla_prefill_attn_cost(cfg, *s["prefill"]), peak)
            least += t
            bounds.add(bound)
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent, "bound: " + "/".join(sorted(bounds))
