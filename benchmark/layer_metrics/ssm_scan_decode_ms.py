"""Device milliseconds a launch of the decode program spends in the
selective scan (``pallas_call(name="selective_scan")``): one token of
every slot row through every Mamba layer, all layers of one launch
together.  Nothing on a commit whose decode runs no such kernel."""

from benchmark.layer_metrics import decode_program_p50_ms


def kernel_seconds(obs):
    return sum(s for (prog, op), s in obs["trace"]["ops"].items()
               if prog.startswith(decode_program_p50_ms.PROGRAM)
               and op.endswith("[mosaic]") and "selective_scan" in op)


def read(name, obs, cell, cfg, peak):
    if not obs.get("trace"):
        return None
    n = len(decode_program_p50_ms.launches(obs))
    spent = kernel_seconds(obs)
    return spent * 1e3 / n if n and spent > 0 else None
