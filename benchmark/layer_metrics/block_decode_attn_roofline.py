"""The block-attention kernel's share of its roofline: for each traced
decode launch the larger of what it has to read (the committed K/V rows of
every running row, 2,048 bytes a token and layer at the published widths)
over the HBM bandwidth and what it has to compute (scores and weighted
sums of 4 positions x 32 heads a row) over the bf16 peak, summed, over the
kernel's device time.  With 4 queries a K/V row the kernel does 64
operations a byte, under the v5e's ridge of 240: the bytes bound it."""

from benchmark import flops as gpt_flops
from benchmark import sdar_flops as flops
from benchmark.layer_metrics import block_decode_attn_ms, sdar_decode_roofline


def read(name, obs, cell, cfg, peak):
    if not obs.get("trace"):
        return None
    spent = block_decode_attn_ms.kernel_seconds(obs)
    least, bounds = 0.0, set()
    for starts in sdar_decode_roofline.passed(obs):
        t, bound = gpt_flops.roofline_seconds(
            *flops.block_decode_attn_cost(cfg, starts), peak)
        least += t
        bounds.add(bound)
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent, "bound: " + "/".join(sorted(bounds))
