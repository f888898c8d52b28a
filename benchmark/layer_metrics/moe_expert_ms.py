"""Device milliseconds a launch of the decode program spends in the routed
experts' grouped products: the ops of ``jit_decode`` that the trace prints
as ``ragged-dot*`` (what the TPU compiler makes of ``jax.lax.ragged_dot``:
one Mosaic grouped matmul for the gate beside the up projection and one for
the down projection, a layer), all expert layers of one step together."""

from benchmark.layer_metrics import decode_program_p50_ms


def read(name, obs, cell, cfg, peak):
    if not obs.get("trace"):
        return None
    n = len(decode_program_p50_ms.launches(obs))
    spent = sum(s for (prog, op), s in obs["trace"]["ops"].items()
                if prog.startswith(decode_program_p50_ms.PROGRAM)
                and op.lstrip("%").startswith("ragged-dot"))
    return spent * 1e3 / n if n and spent > 0 else None
