"""Required operations and bytes of the ``olmo_hybrid`` family, from shapes
and live lengths alone (``flops.py`` is GPT's; the contract is the same:
what the mathematics needs, never what an implementation executes).

``cfg`` is a configuration file's dictionary under the published keys
(``hidden_size``, ``intermediate_size``, ``layer_types``, ``vocab_size``,
``linear_num_value_heads``, ``linear_key_head_dim``,
``linear_value_head_dim``, ``linear_conv_kernel_dim``).  A multiply-add is
two operations.
"""

from __future__ import annotations

LINEAR, FULL = "linear_attention", "full_attention"


def _counts(cfg):
    lt = cfg["layer_types"]
    return lt.count(LINEAR), lt.count(FULL)


def _lin(cfg):
    return (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"])


def linear_mixer_params(cfg):
    """The five projections of one Gated DeltaNet mixer: q, k (D x H d_k),
    v, the output gate (D x H d_v) and the output (H d_v x D)."""
    H, dk, dv = _lin(cfg)
    return cfg["hidden_size"] * (2 * H * dk + 3 * H * dv)


def full_mixer_params(cfg):
    return 4 * cfg["hidden_size"] ** 2


def mlp_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def matmul_params(cfg):
    """Parameters in a matrix product for every token: the mixer's
    projections, the three MLP matrices, and the head (the embedding is a
    look-up; the decay's two D x H projections, 0.1 % of a mixer, the
    filters and the gains are left out)."""
    n_lin, n_full = _counts(cfg)
    return (n_lin * linear_mixer_params(cfg)
            + n_full * full_mixer_params(cfg)
            + (n_lin + n_full) * mlp_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def n_params(cfg):
    """Every parameter the program holds."""
    D, W = cfg["hidden_size"], cfg["linear_conv_kernel_dim"]
    H, dk, dv = _lin(cfg)
    n_lin, n_full = _counts(cfg)
    small_lin = 2 * D * H + 2 * H + W * H * (2 * dk + dv) + dv
    return (matmul_params(cfg) + cfg["vocab_size"] * D + D
            + n_lin * small_lin + n_full * 2 * D + (n_lin + n_full) * 2 * D)


def attention_flops(cfg, q_tokens_times_keys):
    """QK^T and PV of the full-attention layers only: two operations per
    (query, live key) pair and channel, twice."""
    return 4 * _counts(cfg)[1] * cfg["hidden_size"] * q_tokens_times_keys


def delta_rule_flops(cfg, n_tokens):
    """The recurrence of the linear layers: per token and head the decay
    of the state (d_k d_v), the read-out S'^T k, the rank-one update and
    the read-out S^T q (2 d_k d_v each): 7 d_k d_v."""
    H, dk, dv = _lin(cfg)
    return _counts(cfg)[0] * n_tokens * H * 7 * dk * dv


def serve_flops(cfg, n_tokens, live_sum):
    """Forward operations for ``n_tokens`` tokens that attend over
    ``live_sum`` keys between them (each token's own position included)."""
    return (2 * matmul_params(cfg) * n_tokens
            + attention_flops(cfg, int(live_sum))
            + delta_rule_flops(cfg, n_tokens))


def serve_tokens_flops(cfg, live_lengths):
    return serve_flops(cfg, len(live_lengths), sum(live_lengths))


def weight_bytes(cfg, itemsize=2):
    """Every weight a decode step has to read once: all but the embedding
    table, which is read one row per token."""
    return (n_params(cfg)
            - cfg["vocab_size"] * cfg["hidden_size"]) * itemsize


def state_bytes_per_row(cfg, state_itemsize=4, conv_itemsize=2):
    """One request's recurrent state over all linear layers: the matrix
    ``[H, d_k, d_v]`` and the convolution's ``W - 1`` last inputs."""
    H, dk, dv = _lin(cfg)
    tail = (cfg["linear_conv_kernel_dim"] - 1) * H * (2 * dk + dv)
    return _counts(cfg)[0] * (H * dk * dv * state_itemsize
                              + tail * conv_itemsize)


def decode_step_bytes(cfg, live_lengths, itemsize=2, kv_itemsize=2):
    """Least HBM traffic of one decode step over rows with those live
    lengths: every weight once, the live K and V of the full layers, and
    each running row's recurrent state read and written."""
    kv = 2 * _counts(cfg)[1] * cfg["hidden_size"] * int(sum(live_lengths))
    return (weight_bytes(cfg, itemsize) + kv * kv_itemsize
            + 2 * len(live_lengths) * state_bytes_per_row(cfg))
