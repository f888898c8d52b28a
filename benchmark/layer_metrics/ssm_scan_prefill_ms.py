"""Device milliseconds a launch of a prefill-chunk program spends in the
selective scan (``pallas_call(name="selective_scan")``): one chunk's scan
of one row through every Mamba layer, all layers of the launch together.
Nothing on a commit whose chunks run no such kernel."""

from benchmark.layer_metrics import (mla_prefill_attn_ms,
                                     prefill_chunk_program_p50_ms)


def kernel_seconds(obs):
    return sum(s for (prog, op), s in obs["trace"]["ops"].items()
               if prog.startswith(prefill_chunk_program_p50_ms.PROGRAM)
               and op.endswith("[mosaic]") and "selective_scan" in op)


def read(name, obs, cell, cfg, peak):
    if not obs.get("trace"):
        return None
    n, spent = mla_prefill_attn_ms.launches(obs), kernel_seconds(obs)
    return spent * 1e3 / n if n and spent > 0 else None
