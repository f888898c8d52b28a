"""Device milliseconds a launch of the decode program spends in the paged
decode-attention kernel (``pallas_call(name="paged_decode_attn")``): the
block-table walk over the live K/V, all layers of one step together."""

from benchmark.layer_metrics import decode_program_p50_ms


def read(name, obs, cell, cfg, peak):
    if not obs.get("trace"):
        return None
    n = len(decode_program_p50_ms.launches(obs))
    spent = sum(s for (prog, op), s in obs["trace"]["ops"].items()
                if prog.startswith(decode_program_p50_ms.PROGRAM)
                and op.endswith("[mosaic]") and "paged_decode_attn" in op)
    return spent * 1e3 / n if n and spent > 0 else None
