"""Operations and bytes of the ``jamba`` family's serving work, from a
configuration file's sizes (the published keys) and live lengths alone
(``flops.py`` is GPT's; the contract is the same: what the mathematics
needs, never what an implementation executes).

A token passes, in every layer, the gated MLP (``3 D F`` parameters); in an
attention layer ``W_q``, ``W_k``, ``W_v`` and ``W_o``; in a Mamba layer
``W_in`` (``D x 2E``), ``W_x`` (``E x (R + 2N)``), ``W_dt`` (``R x E``) and
``W_out`` (``E x D``).  The tied head (``V x D``) is counted once a row
whose logits are read: each decoded row, and a prefill chunk's last row
alone.  A multiply-add is two operations.  Attention over ``n`` keys costs ``4 n H
hd`` operations a token and layer (scores and weighted sum).  The selective
scan costs ``7 E N`` a token and layer (``dt A``, its exponential, the
decay of the state, ``dt x B`` and its sum into the state, ``C h`` and its
sum over ``N``), the convolution ``2 W E``.
"""

from __future__ import annotations

from benchmark import jamba_weights as jw

F32 = 4


def _sizes(cfg):
    """``(D, E, N, R, W)``."""
    return (cfg["hidden_size"], cfg["mamba_expand"] * cfg["hidden_size"],
            cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["mamba_d_conv"])


def layer_counts(cfg):
    """``(attention layers, Mamba layers)``."""
    kinds = jw.layer_types(cfg)
    return kinds.count(jw.ATTN), kinds.count(jw.MAMBA)


def matmul_params(cfg):
    """Parameters in a matrix product for every token: each layer's MLP and
    mixer projections (the head is ``head_params``)."""
    D, E, N, R, _ = _sizes(cfg)
    nA, nM = layer_counts(cfg)
    hd = D // cfg["num_attention_heads"]
    attn = D * (cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"]
                ) * hd + cfg["num_attention_heads"] * hd * D
    mamba = D * 2 * E + E * (R + 2 * N) + R * E + E * D
    return ((nA + nM) * 3 * D * cfg["intermediate_size"] + nA * attn
            + nM * mamba)


def head_params(cfg):
    """Parameters of the tied head, in a product for every row whose
    logits are read."""
    return cfg["vocab_size"] * cfg["hidden_size"]


def attention_flops(cfg, keys):
    """Scores and weighted sums of the attention layers over ``keys``
    (query, live key) pairs, each token's own position included."""
    return 4 * layer_counts(cfg)[0] * cfg["hidden_size"] * int(keys)


def scan_flops(cfg, n_tokens):
    """The selective scans of every Mamba layer over ``n_tokens``."""
    _, E, N, _, _ = _sizes(cfg)
    return layer_counts(cfg)[1] * n_tokens * 7 * E * N


def serve_flops(cfg, n_tokens, live_sum, head_rows):
    """Forward operations for ``n_tokens`` tokens that attend over
    ``live_sum`` keys between them and read ``head_rows`` rows of logits:
    products, attention, scans and convolutions."""
    _, E, _, _, W = _sizes(cfg)
    return (2 * (matmul_params(cfg) * n_tokens + head_params(cfg) * head_rows)
            + attention_flops(cfg, live_sum) + scan_flops(cfg, n_tokens)
            + layer_counts(cfg)[1] * n_tokens * 2 * W * E)


def serve_tokens_flops(cfg, live_lengths):
    """Forward operations for one decoded token a row, each row's logits
    read."""
    n = len(live_lengths)
    return serve_flops(cfg, n, sum(live_lengths), n)


def scan_cost(cfg, n_tokens, n_rows):
    """``(flops, bytes)`` the selective scans of every Mamba layer need for
    ``n_rows`` rows advancing ``n_tokens`` positions between them: each
    token's float32 inputs ``x``, ``dt`` (``E`` each), ``B``, ``C`` (``N``
    each) in and ``y`` (``E``) out, and each row's float32 state ``[N, E]``
    read and written once."""
    _, E, N, _, _ = _sizes(cfg)
    nM = layer_counts(cfg)[1]
    per_token = (3 * E + 2 * N) * F32
    per_row = 2 * N * E * F32
    return (scan_flops(cfg, n_tokens),
            nM * (n_tokens * per_token + n_rows * per_row))
