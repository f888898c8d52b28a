"""Highest share of the KV pool's blocks that sit in the tables of requests
holding a slot, from the counts the engine leaves on each ``serving.step``
span in the traced seconds.  Unlike ``kv_blocks_peak_share`` it leaves out
the blocks the prefix tree retains after a request has finished."""

from benchmark.layer_metrics import step_spans


def read(name, obs, cell, cfg, peak):
    shares = [c["blocks_live"] / c["blocks_total"]
              for n, _, _, _, c in step_spans.traced_spans(obs)
              if n == "serving.step" and c and c.get("blocks_total")]
    return 100.0 * max(shares) if shares else None
