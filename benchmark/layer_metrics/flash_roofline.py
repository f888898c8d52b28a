"""The flash-attention kernels' share of their roofline: the least time
the chip could take for the causal attention of the traced steps
(``flops.flash_flops`` / ``flops.flash_bytes`` at the chip's peaks) over
the device time of the forward and backward Mosaic calls in the trace.

The trace names a Pallas kernel only as a ``tpu_custom_call`` (PR 24: the
train step has four, all of them flash: forward, the forward replayed by
recomputation, dK/dV and dQ), so every Mosaic call of the step counts as
flash time.  A later kernel of another kind in the step would make this
share read lower, never higher, until the program names its kernels."""

from benchmark import flops, trace_reduce

KERNELS = r"tpu_custom_call"


def read(name, obs, cell, cfg, peak):
    spent, n_ops = trace_reduce.op_seconds(obs["trace"], KERNELS)
    if not n_ops or spent <= 0 or not obs.get("traced_steps"):
        return None
    least, bound = flops.roofline_seconds(
        flops.flash_flops(cfg, cell["batch"], cell["seq"]),
        flops.flash_bytes(cfg, cell["batch"], cell["seq"]), peak)
    return 100.0 * obs["traced_steps"] * least / spent, f"bound: {bound}"
