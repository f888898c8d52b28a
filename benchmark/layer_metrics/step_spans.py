"""What the readers of the program's own spans share.  Not a metric.

The program (``paddle_tpu/profiler/host_tracer.py``) keeps every span that
completed under a profiler session as ``(name, tid, start_ns, end_ns,
depth, counts)`` on ``perf_counter_ns``, the clock of the benchmark's own
stamps, and a ``--trace 1`` run takes its trace inside the window, so the
spans of the traced seconds are the ones between the two stamps of
``obs["traced"]``.  A program without such spans (any commit before PR 26)
gives an empty list, and every reader then returns ``None``."""

from paddle_tpu.profiler import host_tracer


def traced_spans(obs):
    """``[(name, tid, start_s, end_s, counts)]`` of the spans that began in
    the traced part of the window, in seconds on the window's clock."""
    if "traced" not in obs:
        return []
    lo, hi = obs["traced"]
    out = []
    for ev in host_tracer.events():
        if len(ev) < 6:          # the five-field events of an older program
            continue
        name, tid, t0, t1, _depth, counts = ev
        s, e = t0 / 1e9 - obs["t_start"], t1 / 1e9 - obs["t_start"]
        if lo <= s < hi:
            out.append((name, tid, s, e, counts))
    return out


def kernel_ms_per_step(obs, kernels):
    """Device milliseconds a traced training step spends in the Mosaic
    kernels whose instruction name carries one of ``kernels`` (the
    ``name=`` of their ``pallas_call``)."""
    red, steps = obs.get("trace"), obs.get("traced_steps")
    if not red or not steps:
        return None
    spent = sum(s for (_, op), s in red["ops"].items()
                if op.endswith("[mosaic]") and any(k in op for k in kernels))
    return spent * 1e3 / steps if spent > 0 else None
