"""``serve_step_mfu`` for the ``sdar_moe`` family: required operations
(``sdar_flops.serve_flops``) of every token prefilled or EMITTED by the
steps of the traced seconds, one pass each, over their length times the
chip's bf16 peak.  A block of 4 tokens costs 2 to 5 passes by the
denoising schedule; the mathematics of the tokens needs one, so fewer
passes a token raise this share and more lower it."""

from benchmark import sdar_flops as flops
from benchmark.layer_metrics.serve_step_mfu import traced_steps


def read(name, obs, cell, cfg, peak):
    steps = traced_steps(obs) if "traced" in obs else []
    if not steps:
        return None
    need = sum(flops.serve_flops(cfg, s["prefill"], s["decode_live"])
               for s in steps)
    t0, t1 = obs["traced"]
    return 100.0 * need / ((t1 - t0) * peak["bf16_flops_per_s"])
