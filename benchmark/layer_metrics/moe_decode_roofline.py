"""``decode_roofline`` for the ``deepseek_v2`` family: the least time for
what each traced decode step HAS to move and compute (every weight that
every token passes once, of each expert layer's held experts those that
some row chose in expectation at the window's own mean of held choices,
the live latent rows of every layer, the step's operations in the absorbed
form: ``deepseek_v2_flops``) over the decode program's device time."""

from benchmark import deepseek_v2_flops as flops
from benchmark import flops as gpt_flops
from benchmark.layer_metrics import decode_program_p50_ms
from benchmark.layer_metrics.moe_serve_step_mfu import held_per_token_layer
from benchmark.layer_metrics.serve_step_mfu import traced_steps


def read(name, obs, cell, cfg, peak):
    held = held_per_token_layer(obs, cfg)
    if not obs.get("trace") or held is None:
        return None
    spent = sum(decode_program_p50_ms.launches(obs))
    least, bounds = 0.0, set()
    for s in traced_steps(obs):
        if s["decode_live"]:
            t, bound = gpt_flops.roofline_seconds(
                flops.serve_flops(cfg, (0, 0), s["decode_live"], held),
                flops.decode_step_bytes(cfg, s["decode_live"], held), peak)
            least += t
            bounds.add(bound)
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent, "bound: " + "/".join(sorted(bounds))
