"""Median host time of building and uploading the decode launch's operands
(the ``serving.decode.operands`` span: the masked block tables and the
eight ``arena.operand`` uploads), over the traced seconds."""

import statistics

from benchmark.layer_metrics import step_spans


def read(name, obs, cell, cfg, peak):
    xs = [e - s for n, _, s, e, _ in step_spans.traced_spans(obs)
          if n == "serving.decode.operands"]
    return statistics.median(xs) * 1e3 if xs else None
