"""Device milliseconds a launch of the decode program spends sorting: the
ops of ``jit_decode`` whose HLO name is ``sort*``, which are the top-k and
top-p filters of the sampling tail (``serving/sampling.py``), each over the
whole vocabulary.  Reads 0.0, not nothing, where the program launched and
no sort ran: a batch of greedy rows on a program whose tail branches on
``any(do_sample)``."""

from benchmark.layer_metrics import decode_program_p50_ms


def read(name, obs, cell, cfg, peak):
    if not obs.get("trace"):
        return None
    n = len(decode_program_p50_ms.launches(obs))
    if not n:
        return None
    spent = sum(s for (prog, op), s in obs["trace"]["ops"].items()
                if prog.startswith(decode_program_p50_ms.PROGRAM)
                and op.lstrip("%").startswith("sort"))
    return spent * 1e3 / n
