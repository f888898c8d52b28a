"""Device milliseconds a launch of the decode program spends in the
block-attention kernel (``pallas_call(name="block_decode_attn")``): the
block-table walk over every running row's committed K/V and the fold of
the block's own lines, all layers of one launch together."""

from benchmark.layer_metrics import decode_program_p50_ms


def kernel_seconds(obs):
    return sum(s for (prog, op), s in obs["trace"]["ops"].items()
               if prog.startswith(decode_program_p50_ms.PROGRAM)
               and op.endswith("[mosaic]") and "block_decode_attn" in op)


def read(name, obs, cell, cfg, peak):
    if not obs.get("trace"):
        return None
    n = len(decode_program_p50_ms.launches(obs))
    spent = kernel_seconds(obs)
    return spent * 1e3 / n if n and spent > 0 else None
