"""The banded prefill kernel's share of its roofline: for each chunk of
the traced steps the larger of what its attention needs to compute over
the bf16 peak and what it needs to move over the HBM bandwidth
(:func:`window_prefill_attn_cost`), summed, over the kernel's device time.
Only the query-key pairs a query sees are counted (a window layer's last
``W`` keys, a full layer's every earlier one), never the masked pairs of
the whole steps of blocks the kernel folds, so the share cannot pass
100 %.  Nothing where the steps carry no chunks (a kind that does not
place them) or the trace holds no such kernel."""

from benchmark import flops as gpt_flops
from benchmark import trinity_flops as flops
from benchmark.layer_metrics import window_prefill_attn_ms
from benchmark.layer_metrics.serve_step_mfu import traced_steps


def window_prefill_attn_cost(cfg, start, take, itemsize=2):
    """``(flops, bytes)`` a chunk of ``take`` queries at positions
    ``start ..`` needs: ``4 H hd`` operations a query and key it sees
    (``trinity_flops.keys_full`` / ``keys_window``); each layer's queries
    in (the pool's dtype), its float32 outputs out, and the rows of its
    band once (a full layer's ``start + take``, a window layer's from
    ``start - W + 1``), ``kv_bytes_per_token_layer`` each."""
    W = cfg["sliding_window"]
    _, _, nW, nF = flops.layer_counts(cfg)
    ops = flops.attention_flops(cfg, flops.keys_full(start, take),
                                flops.keys_window(start, take, W))
    band = nF * (start + take) + nW * (start + take - max(start - W + 1, 0))
    queries = take * cfg["num_attention_heads"] * cfg["head_dim"]
    return ops, ((nF + nW) * queries * (itemsize + 4)
                 + flops.kv_bytes_per_token_layer(cfg, itemsize) * band)


def read(name, obs, cell, cfg, peak):
    if not obs.get("trace") or "traced" not in obs:
        return None
    steps = traced_steps(obs)
    if any("chunks" not in s for s in steps):
        return None
    spent = window_prefill_attn_ms.kernel_seconds(obs)
    least, bounds = 0.0, set()
    for s in steps:
        for start, take in s["chunks"]:
            t, bound = gpt_flops.roofline_seconds(
                *window_prefill_attn_cost(cfg, start, take), peak)
            least += t
            bounds.add(bound)
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent, "bound: " + "/".join(sorted(bounds))
