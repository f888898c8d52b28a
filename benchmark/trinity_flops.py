"""Operations and bytes of the ``afmoe`` family's serving work, from a
configuration file's sizes (the published keys, cut as the file states).

A token passes, in every layer, attention's four projections and ``W_o``
(``q``, ``k``, ``v``, the gate, the output: 62.9 M parameters a layer at
the published widths), a dense layer's SwiGLU or an expert layer's router,
shared expert and the routed experts held here that it chose; the head
once a row whose logits are read.  Attention over ``n`` keys costs ``4 n H
d`` operations a token and layer (scores and weighted sum): ``n`` is the
token's position plus one in a full layer and ``min(position + 1, W)`` in
a window layer.  A row of the cache is ``[k ; v]`` of every K/V head, 4,096
bytes a token and layer in bfloat16.
"""

from __future__ import annotations

from benchmark import trinity_weights as tw


def layer_counts(cfg):
    """``(dense layers, expert layers, window layers, full layers)``."""
    nD = cfg["num_dense_layers"]
    L = cfg["num_hidden_layers"]
    nF = cfg["layer_types"].count("full_attention")
    return nD, L - nD, L - nF, nF


def kv_bytes_per_token_layer(cfg, itemsize=2):
    """One cached row: ``[k ; v]`` of every K/V head."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def token_params(cfg):
    """Matrix parameters every token passes, the routed experts and the
    head aside: attention in every layer, the dense layers' MLP, the
    expert layers' routers and shared experts."""
    p = tw.parameters(cfg)
    nD, nM, _, _ = layer_counts(cfg)
    D = cfg["hidden_size"]
    attn = p["attn"] - 4 * D - 2 * cfg["head_dim"]          # less the gains
    moe = p["moe"] - tw.share(cfg)[0]                       # less the bias
    return (nD + nM) * attn + nD * p["dense"] + nM * moe


def keys_window(start, take, window):
    """``sum over positions p in [start, start + take) of min(p + 1, W)``."""
    def upto(n):                        # sum_{k=1}^{n} min(k, W)
        m = min(n, window)
        return m * (m + 1) // 2 + max(n - window, 0) * window
    return upto(start + take) - upto(start)


def keys_full(start, take):
    """``sum over positions p in [start, start + take) of (p + 1)``."""
    return take * start + take * (take + 1) // 2


def attention_flops(cfg, full_keys, window_keys):
    """Scores and weighted sums over the keys counted: ``full_keys``
    summed over the tokens of each full layer, ``window_keys`` of each
    window layer."""
    _, _, nW, nF = layer_counts(cfg)
    per_key = 4 * cfg["num_attention_heads"] * cfg["head_dim"]
    return per_key * (nF * full_keys + nW * window_keys)


def serve_flops(cfg, chunks, decode_live, held_per_token_layer):
    """Forward operations of a step: ``chunks`` the ``(start, tokens)`` it
    prefilled, ``decode_live`` the live lengths (keys, the token's own
    included) of the rows it decoded, ``held_per_token_layer`` the mean
    number of held experts a token's choices fall on in one expert layer
    (from the program's counts).  The head runs once a chunk (its last
    token's logits) and once a decoded row."""
    W = cfg["sliding_window"]
    n_tok = sum(t for _, t in chunks) + len(decode_live)
    _, nM, _, _ = layer_counts(cfg)
    expert = tw.parameters(cfg)["expert"]
    head = cfg["hidden_size"] * cfg["vocab_size"]
    full = (sum(keys_full(s, t) for s, t in chunks)
            + sum(int(c) for c in decode_live))
    win = (sum(keys_window(s, t, W) for s, t in chunks)
           + sum(min(int(c), W) for c in decode_live))
    return (2 * token_params(cfg) * n_tok
            + 2 * expert * held_per_token_layer * nM * n_tok
            + 2 * head * (len(chunks) + len(decode_live))
            + attention_flops(cfg, full, win))


def window_decode_attn_cost(cfg, live_lengths, itemsize=2):
    """``(flops, bytes)`` the decode walk needs over rows of these live
    lengths ``c`` (keys, the token's own included): every full layer reads
    all ``c`` rows, every window layer ``min(c, W)``; a row of
    ``kv_bytes_per_token_layer`` each, and ``4 H d`` operations each.  The
    queries and outputs are left out (a few KB a row)."""
    W = cfg["sliding_window"]
    _, _, nW, nF = layer_counts(cfg)
    keys = sum(nF * int(c) + nW * min(int(c), W) for c in live_lengths)
    return (4 * cfg["num_attention_heads"] * cfg["head_dim"] * keys,
            kv_bytes_per_token_layer(cfg, itemsize) * keys)


def window_entries(cell, cfg):
    """Blocks of a row's window ring: ``ceil((W + prefill_chunk) / bs) +
    1`` (``serving/paged.py``)."""
    e = cell["engine"]
    return -(-(cfg["sliding_window"] + e["prefill_chunk"])
             // e["block_size"]) + 1


def pool_bytes(cell, cfg, itemsize=2):
    """``(full pool, window pool)`` bytes of a cell's engine: ``n_blocks``
    blocks over the full layers, every slot's ring and the trash block
    over the window layers, each block ``block_size`` rows."""
    e = cell["engine"]
    _, _, nW, nF = layer_counts(cfg)
    row = kv_bytes_per_token_layer(cfg, itemsize) * e["block_size"]
    window_blocks = e["max_slots"] * window_entries(cell, cfg) + 1
    return nF * e["n_blocks"] * row, nW * window_blocks * row
