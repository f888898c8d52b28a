"""``serve_step_mfu`` for the ``jamba`` family: required operations
(``jamba_flops.serve_flops``: 2 per matmul parameter and token, 2 per head
parameter and row of logits read, attention over the live keys in the
attention layers, the selective scan's 7 E N and the convolution's 2 W E
per token in the Mamba layers) of every token
prefilled or decoded by the steps of the traced seconds, over their length
times the chip's bf16 peak."""

from benchmark import jamba_flops as flops
from benchmark.layer_metrics.serve_step_mfu import traced_steps


def read(name, obs, cell, cfg, peak):
    steps = traced_steps(obs) if "traced" in obs else []
    if not steps:
        return None
    need = sum(flops.serve_flops(cfg, *s["prefill"], len(s["chunks"]))
               + flops.serve_tokens_flops(cfg, s["decode_live"])
               for s in steps)
    t0, t1 = obs["traced"]
    return 100.0 * need / ((t1 - t0) * peak["bf16_flops_per_s"])
