"""The whole training step's share of the chip's bf16 peak: required
forward+backward operations (``flops.train_step_flops``; recomputation is
not counted) of the steps completed in the traced part of the window, over
its length."""


def read(name, obs, cell, cfg, peak):
    if not obs.get("traced_steps"):
        return None
    done = obs["traced_steps"] * obs["train_flops_per_step"]
    return 100.0 * done / (obs["traced_s"] * peak["bf16_flops_per_s"])
