"""``decode_roofline`` for the ``sdar_moe`` family: the least time for what
each traced decode launch HAS to move and compute (the weights every
position passes, of each layer's experts those that some position chose in
expectation at the window's own routing counts, the committed K/V of the
rows it passed, the operations of the positions passed:
``sdar_flops``) over the decode program's device time.  The passes a
launch ran are placed on it from the blocks' commits
(``serve_open_loop_sdar.place``); a block still in its passes when the
run ended is not counted, which can only lower the share."""

from benchmark import flops as gpt_flops
from benchmark import sdar_flops as flops
from benchmark.layer_metrics import block_decode_program_p50_ms
from benchmark.layer_metrics.serve_step_mfu import traced_steps


def load(obs):
    """``[layers, experts]``: the share of routed tokens that chose each
    expert, over the window."""
    moe = obs.get("moe")
    if not moe or not moe["tokens"]:
        return None
    return (moe["per_expert"] / moe["tokens"]).tolist()


def passed(obs):
    """The traced steps that launched the decode program: their
    ``passes``."""
    return [s["passes"] for s in traced_steps(obs) if s.get("passes")]


def read(name, obs, cell, cfg, peak):
    spent = sum(block_decode_program_p50_ms.launches(obs))
    shares = load(obs)
    if spent <= 0 or shares is None:
        return None
    least, bounds = 0.0, set()
    for starts in passed(obs):
        t, bound = gpt_flops.roofline_seconds(
            flops.decode_step_flops(cfg, starts),
            flops.decode_step_bytes(cfg, starts, shares), peak)
        least += t
        bounds.add(bound)
    if least <= 0:
        return None
    return 100.0 * least / spent, "bound: " + "/".join(sorted(bounds))
