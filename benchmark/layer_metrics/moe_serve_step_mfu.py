"""``serve_step_mfu`` for the ``deepseek_v2`` family: required operations
(``deepseek_v2_flops.serve_flops``: 2 per parameter every token passes and
token; the routed experts by the program's own counts, ``serving.moe.
assignments`` over ``serving.moe.tokens`` as the window read them;
materialised attention over the live keys for a prefilled token, absorbed
for a decoded one) of every token prefilled or decoded by the steps of the
traced seconds, over their length times the chip's bf16 peak."""

from benchmark import deepseek_v2_flops as flops
from benchmark.layer_metrics.serve_step_mfu import traced_steps


def held_per_token_layer(obs, cfg):
    """Mean number of held experts a token's choices fell on in one
    expert layer, over the window; nothing where the program counted
    none."""
    moe = obs.get("moe")
    if not moe or not moe["tokens"]:
        return None
    return moe["assignments"] / (moe["tokens"] * flops.layer_counts(cfg)[1])


def read(name, obs, cell, cfg, peak):
    held = held_per_token_layer(obs, cfg)
    steps = traced_steps(obs) if "traced" in obs else []
    if held is None or not steps:
        return None
    need = sum(flops.serve_flops(cfg, s["prefill"], s["decode_live"], held)
               for s in steps)
    t0, t1 = obs["traced"]
    return 100.0 * need / ((t1 - t0) * peak["bf16_flops_per_s"])
