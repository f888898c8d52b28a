"""``serve_step_mfu`` for the ``afmoe`` family: required operations
(``trinity_flops.serve_flops``: 2 per parameter every token passes and
token; the routed experts by the program's own counts, ``serving.moe.
assignments`` over ``serving.moe.tokens`` as the window read them;
attention over ``min(position + 1, W)`` keys in a window layer and every
earlier key in a full one) of every token prefilled or decoded by the
steps of the traced seconds, over their length times the chip's bf16
peak.  Nothing where the steps carry no chunks (a kind that does not place
them) or the program counted no routed token."""

from benchmark import trinity_flops as flops
from benchmark.layer_metrics.serve_step_mfu import traced_steps


def read(name, obs, cell, cfg, peak):
    moe = obs.get("moe")
    steps = traced_steps(obs) if "traced" in obs else []
    if not moe or not moe["tokens"] or not steps or any(
            "chunks" not in s for s in steps):
        return None
    held = moe["assignments"] / (moe["tokens"] * flops.layer_counts(cfg)[1])
    need = sum(flops.serve_flops(cfg, s["chunks"], s["decode_live"], held)
               for s in steps)
    t0, t1 = obs["traced"]
    return 100.0 * need / ((t1 - t0) * peak["bf16_flops_per_s"])
