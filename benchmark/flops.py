"""Required operations and bytes, from shapes and live lengths alone.

Every share of a peak or of a roofline that the benchmark reports divides
one of these counts by a time.  They count what the mathematics of the
configuration needs — never what an implementation happens to execute
(recomputation, padding to ``S_max``, a gather over dead blocks), so the
same work reads the same whatever implements it.

A multiply-add is two operations.  ``cfg`` is a configuration file's
dictionary (``n_layers``, ``d_model``, ``n_heads``, ``d_ff``,
``vocab_size``, ``n_ctx``).
"""

from __future__ import annotations


def matmul_params(cfg):
    """Parameters that take part in a matrix product for every token: the
    four attention projections and the two FFN matrices of each layer, and
    the output projection (the tied embedding, used once as a product; the
    embedding look-ups and the biases/norms multiply nothing)."""
    d, f = cfg["d_model"], cfg["d_ff"]
    return cfg["n_layers"] * (4 * d * d + 2 * d * f) + cfg["vocab_size"] * d


def n_params(cfg):
    """All parameters (the count bench.py's ``6N`` used)."""
    d, f, L = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    per_layer = 4 * d * d + 2 * d * f + 4 * d + 3 * d + d + f + d
    return (L * per_layer + cfg["vocab_size"] * d + cfg["n_ctx"] * d + 2 * d)


def attention_flops(cfg, q_tokens_times_keys):
    """Forward operations of softmax attention given the number of
    (query, live key) pairs: QK^T and PV, two operations per pair and
    channel, over all layers (``n_heads * d_head == d_model``)."""
    return 4 * cfg["n_layers"] * cfg["d_model"] * q_tokens_times_keys


def causal_pairs(seq):
    """(query, key) pairs of one causal sequence of ``seq`` tokens."""
    return seq * (seq + 1) // 2


def train_step_flops(cfg, batch, seq):
    """Forward plus backward of one step: the backward pass costs twice the
    forward, so 6 operations per matmul parameter and token, and three
    times the forward attention.  Recomputation is not counted."""
    tokens = batch * seq
    return (6 * matmul_params(cfg) * tokens
            + 3 * attention_flops(cfg, batch * causal_pairs(seq)))


def flash_flops(cfg, batch, seq):
    """What the attention kernels of one training step have to compute:
    forward (QK^T, PV) and backward (dV, dP, dQ, dK and the recomputed
    QK^T is NOT counted): 2 products forward + 4 backward = 3x forward."""
    return 3 * attention_flops(cfg, batch * causal_pairs(seq))


def flash_bytes(cfg, batch, seq, itemsize=2):
    """Least HBM traffic of those kernels in one step: forward reads q, k,
    v and writes o; backward reads q, k, v, o, do and writes dq, dk, dv
    (the per-row statistics are 1/d_head of that and left out)."""
    one = cfg["n_layers"] * batch * seq * cfg["d_model"] * itemsize
    return (4 + 8) * one


def serve_flops(cfg, n_tokens, live_sum):
    """Forward operations for ``n_tokens`` tokens that attend over
    ``live_sum`` keys between them (each token's own position included):
    2 per matmul parameter and token, plus attention over the live keys."""
    return (2 * matmul_params(cfg) * n_tokens
            + attention_flops(cfg, int(live_sum)))


def serve_tokens_flops(cfg, live_lengths):
    """``serve_flops`` for tokens given by their live lengths."""
    return serve_flops(cfg, len(live_lengths), sum(live_lengths))


def weight_bytes(cfg, itemsize=2):
    """Bytes of every weight a decode step has to read once (the position
    table is read one row per token and left out)."""
    return (n_params(cfg) - cfg["n_ctx"] * cfg["d_model"]) * itemsize


def decode_step_bytes(cfg, live_lengths, itemsize=2, kv_itemsize=2):
    """Least HBM traffic of one decode step over rows with those live
    lengths: every weight once, and the live K and V of each row."""
    kv = 2 * cfg["n_layers"] * cfg["d_model"] * int(sum(live_lengths))
    return weight_bytes(cfg, itemsize) + kv * kv_itemsize


def roofline_seconds(flops, nbytes, peak):
    """Least time the chip could take and which bound sets it."""
    t_f = flops / peak["bf16_flops_per_s"]
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
