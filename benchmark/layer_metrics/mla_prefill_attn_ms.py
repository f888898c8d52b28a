"""Device milliseconds a launch of a prefill-chunk program spends in the
latent prefill-attention kernel (``pallas_call(name="mla_prefill_attn")``):
the fold of one up-projected tile of keys and values into a chunk's online
softmax, every live tile and all layers of one chunk together (the tiles'
up-projections are XLA products beside it and not counted here)."""

from benchmark.layer_metrics import prefill_chunk_program_p50_ms


def launches(obs):
    return sum(len(xs) for prog, xs in obs["trace"]["programs"].items()
               if prog.startswith(prefill_chunk_program_p50_ms.PROGRAM))


def kernel_seconds(obs):
    return sum(s for (prog, op), s in obs["trace"]["ops"].items()
               if prog.startswith(prefill_chunk_program_p50_ms.PROGRAM)
               and op.endswith("[mosaic]") and "mla_prefill_attn" in op)


def read(name, obs, cell, cfg, peak):
    if not obs.get("trace"):
        return None
    n, spent = launches(obs), kernel_seconds(obs)
    return spent * 1e3 / n if n and spent > 0 else None
