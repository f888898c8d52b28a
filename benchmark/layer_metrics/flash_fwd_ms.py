"""Device milliseconds a traced training step spends in the flash-attention
forward kernel (``pallas_call(name="flash_fwd")``): the forward pass and,
under selective recomputation, its replay in the backward pass."""

from benchmark.layer_metrics import step_spans


def read(name, obs, cell, cfg, peak):
    return step_spans.kernel_ms_per_step(obs, ["flash_fwd"])
