"""Device milliseconds a launch of a prefill-chunk program spends in the
routed experts' grouped products: the ops of ``jit_pchunk`` that the trace
prints as ``ragged-dot*`` (``moe_expert_ms`` reads the same ops of
``jit_decode``), all expert layers of one chunk together."""

from benchmark.layer_metrics import mla_prefill_attn_ms
from benchmark.layer_metrics import prefill_chunk_program_p50_ms


def read(name, obs, cell, cfg, peak):
    if not obs.get("trace"):
        return None
    n = mla_prefill_attn_ms.launches(obs)
    spent = sum(s for (prog, op), s in obs["trace"]["ops"].items()
                if prog.startswith(prefill_chunk_program_p50_ms.PROGRAM)
                and op.lstrip("%").startswith("ragged-dot"))
    return spent * 1e3 / n if n and spent > 0 else None
