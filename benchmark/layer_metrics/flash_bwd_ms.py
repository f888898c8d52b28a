"""Device milliseconds a traced training step spends in the two
flash-attention backward kernels (``flash_dkv`` and ``flash_dq``)."""

from benchmark.layer_metrics import step_spans


def read(name, obs, cell, cfg, peak):
    return step_spans.kernel_ms_per_step(obs, ["flash_dkv", "flash_dq"])
