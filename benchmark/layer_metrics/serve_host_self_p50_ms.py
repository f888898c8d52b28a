"""Median host time of one ``engine.step()`` that the device does not
cover: the ``serving.step`` span less the ``serving.*.wait`` spans inside
it (the blocking read-backs), over the traced seconds.  What is left is
the sweep, the admission, the operand builds and uploads, the dispatches
and the emit loop: the scheduler's own cost per step."""

import statistics

from benchmark.layer_metrics import step_spans


def read(name, obs, cell, cfg, peak):
    spans = step_spans.traced_spans(obs)
    waits = [(tid, s, e) for n, tid, s, e, _ in spans
             if n.startswith("serving.") and n.endswith(".wait")]
    own = [(e - s) - sum(we - ws for wt, ws, we in waits
                         if wt == tid and s <= ws and we <= e)
           for n, tid, s, e, _ in spans if n == "serving.step"]
    return statistics.median(own) * 1e3 if own else None
