"""``decode_roofline`` for the ``olmo_hybrid`` family: the least time for
what each traced decode step HAS to move and compute — every weight once,
the live K/V of the full layers, each running row's recurrent state read
and written, the step's operations (``olmo_hybrid_flops``) — over the
decode program's device time."""

from benchmark import flops as gpt_flops
from benchmark import olmo_hybrid_flops as flops
from benchmark.layer_metrics import decode_program_p50_ms
from benchmark.layer_metrics.serve_step_mfu import traced_steps


def read(name, obs, cell, cfg, peak):
    if not obs.get("trace"):
        return None
    spent = sum(decode_program_p50_ms.launches(obs))
    least, bounds = 0.0, set()
    for s in traced_steps(obs):
        if s["decode_live"]:
            t, bound = gpt_flops.roofline_seconds(
                flops.serve_tokens_flops(cfg, s["decode_live"]),
                flops.decode_step_bytes(cfg, s["decode_live"]), peak)
            least += t
            bounds.add(bound)
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent, "bound: " + "/".join(sorted(bounds))
