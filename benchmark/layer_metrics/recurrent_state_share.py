"""Share of the cache manager's live bytes that is recurrent state, at the
fullest traced step: ``state_bytes / (state_bytes + kv_live_bytes)`` from
the counts the engine leaves on each ``serving.step`` span.  The state is
fixed per slot; the K/V beside it grows with the requests' lengths, so the
share falls as documents get longer.  A program whose spans carry no such
counts (a model without recurrent layers leaves ``state_bytes`` 0; a
commit before the counts existed leaves none) gives nothing."""

from benchmark.layer_metrics import step_spans


def read(name, obs, cell, cfg, peak):
    counts = [c for n, _, _, _, c in step_spans.traced_spans(obs)
              if n == "serving.step" and c and c.get("state_bytes")]
    if not counts:
        return None
    c = max(counts, key=lambda c: c.get("kv_live_bytes", 0))
    return 100.0 * c["state_bytes"] / (c["state_bytes"]
                                       + c.get("kv_live_bytes", 0))
