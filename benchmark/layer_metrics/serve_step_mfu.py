"""The serving steps' share of the chip's bf16 peak: required operations
(``flops.serve_tokens_flops``: 2 per matmul parameter and token, plus
attention over the live length) of every token prefilled or decoded by the
steps of the traced part of the window, over its length."""

from benchmark import flops


def traced_steps(obs):
    t0, t1 = obs["traced"]
    return [s for s in obs["steps"] if t0 <= s["start"] < t1]


def read(name, obs, cell, cfg, peak):
    steps = traced_steps(obs)
    if not steps:
        return None
    need = 0
    for s in steps:
        need += flops.serve_flops(cfg, *s["prefill"])
        need += flops.serve_tokens_flops(cfg, s["decode_live"])
    t0, t1 = obs["traced"]
    return 100.0 * need / ((t1 - t0) * peak["bf16_flops_per_s"])
