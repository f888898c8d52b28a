"""The decode step's share of its roofline: the least time for what each
traced decode step HAS to move and compute — every weight once, the live
K/V of the running rows, the step's operations — over the decode program's
device time.  Counted from shapes and live lengths, so it reads the same
work whatever implements the step."""

from benchmark import flops
from benchmark.layer_metrics import decode_program_p50_ms, serve_step_mfu


def read(name, obs, cell, cfg, peak):
    spent = sum(decode_program_p50_ms.launches(obs))
    least, bounds = 0.0, set()
    for s in serve_step_mfu.traced_steps(obs):
        if s["decode_live"]:
            t, bound = flops.roofline_seconds(
                flops.serve_tokens_flops(cfg, s["decode_live"]),
                flops.decode_step_bytes(cfg, s["decode_live"]), peak)
            least += t
            bounds.add(bound)
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent, "bound: " + "/".join(sorted(bounds))
