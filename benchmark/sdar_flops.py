"""Required operations and bytes of the ``sdar_moe`` family, from shapes,
live lengths and the program's own routing counts.

The same contract as ``flops.py``: what the mathematics of the
configuration needs, never what an implementation happens to execute.  A
multiply-add is two operations.  ``cfg`` is a configuration file's
dictionary (the published ``config.json`` keys).

A *pass* is one run of a block of ``block_length`` positions through the
model (``serving/block_decode.py``): each position attends to the row's
committed prefix and to the whole block.  A token costs one pass by the
mathematics of a model that emits a token a position; what the denoising
schedule runs beyond that (5 passes a block of 4 at the cell's settings)
is the schedule's price, which ``sdar_serve_step_mfu`` shows by counting
EMITTED tokens once and ``sdar_decode_roofline`` leaves in by counting the
positions passed.
"""

from __future__ import annotations


def layer_params(cfg):
    """``{"attention", "router", "expert", "gains"}``: parameters of one
    layer's attention projections, its router, ONE expert, its norm
    gains."""
    D, hd = cfg["hidden_size"], cfg["head_dim"]
    H, N = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"attention": D * H * hd + 2 * D * N * hd + H * hd * D,
            "router": D * cfg["num_experts"],
            "expert": 3 * D * cfg["moe_intermediate_size"],
            "gains": 2 * D + 2 * hd}


def parameters(cfg):
    """``{"layer", "top", "total"}``: all parameters of one layer (every
    expert), of the embedding, the head and the final norm, and of the
    model at the file's depth."""
    p = layer_params(cfg)
    layer = (p["attention"] + p["router"] + p["gains"]
             + cfg["num_experts"] * p["expert"])
    top = 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
    return {"layer": layer, "top": top,
            "total": cfg["num_hidden_layers"] * layer + top}


def kv_bytes_per_token_layer(cfg, itemsize=2):
    """Bytes one token's keys and values take in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def block_end(cfg, position):
    """Keys the token at ``position`` attends to: everything up to the
    end of its own block."""
    B = cfg["block_length"]
    return position // B * B + B


def attention_flops(cfg, pairs):
    """QK^T and PV over ``pairs`` (query, live key) pairs: two operations
    a pair and channel of every query head, all layers."""
    return (4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"] * int(pairs))


def token_matmul_params(cfg, with_head):
    """Parameters a token multiplies: each layer's attention, router and
    its ``num_experts_per_tok`` experts; the head where logits are made (a
    prefilled token predicts nothing: no chunk projects onto the
    vocabulary)."""
    p = layer_params(cfg)
    per_layer = (p["attention"] + p["router"]
                 + cfg["num_experts_per_tok"] * p["expert"])
    return (cfg["num_hidden_layers"] * per_layer
            + (cfg["vocab_size"] * cfg["hidden_size"] if with_head else 0))


def serve_flops(cfg, prefill, decode_keys):
    """Forward operations of ``prefill = (tokens, sum of the keys they
    attend to)`` prefilled tokens and of decoded positions given by the
    keys each attends to (``decode_keys``: one entry a position)."""
    n, pairs = prefill
    return (2 * token_matmul_params(cfg, False) * n
            + 2 * token_matmul_params(cfg, True) * len(decode_keys)
            + attention_flops(cfg, pairs + sum(decode_keys)))


def experts_touched(load, n_positions):
    """Experts of each layer that at least one of ``n_positions`` positions
    chooses, in expectation at the window's own routing: ``load [layers,
    experts]`` is the share of tokens that chose each expert (a row sums
    to ``num_experts_per_tok``)."""
    return sum(float((1.0 - (1.0 - min(f, 1.0)) ** n_positions))
               for row in load for f in row)


def dense_bytes(cfg, itemsize=2):
    """Bytes of every weight that every position of a decode pass
    multiplies: the layers without their experts, the head and the final
    norm (the embedding is read a row a position and left out)."""
    p = layer_params(cfg)
    return itemsize * (
        cfg["num_hidden_layers"] * (p["attention"] + p["router"]
                                    + p["gains"])
        + cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"])


def decode_step_bytes(cfg, pass_starts, load, itemsize=2):
    """Least HBM traffic of one decode launch over blocks that start at
    ``pass_starts``: the dense weights once, the experts some position
    chose (``experts_touched`` at the window's own ``load``), and the
    committed K/V of every row."""
    B = cfg["block_length"]
    touched = experts_touched(load, B * len(pass_starts))
    return (dense_bytes(cfg, itemsize)
            + itemsize * layer_params(cfg)["expert"] * touched
            + cfg["num_hidden_layers"] * kv_bytes_per_token_layer(cfg, itemsize)
            * int(sum(pass_starts)))


def decode_step_flops(cfg, pass_starts):
    """Operations of one decode launch: every position of every block
    passed, the head too."""
    B = cfg["block_length"]
    return serve_flops(cfg, (0, 0),
                       [s + B for s in pass_starts for _ in range(B)])


def block_decode_attn_cost(cfg, pass_starts, itemsize=2):
    """``(operations, bytes)`` the block-attention kernel of one launch
    has to do over all layers: scores and weighted sums of ``B`` queries a
    row over its prefix and its own block; the prefix's K/V rows read
    once, the queries and the block's own lines read and the outputs
    written."""
    B, L = cfg["block_length"], cfg["num_hidden_layers"]
    H, hd = cfg["num_attention_heads"], cfg["head_dim"]
    flops = attention_flops(cfg, sum(B * (s + B) for s in pass_starts))
    row = kv_bytes_per_token_layer(cfg, itemsize)
    nbytes = L * (row * int(sum(pass_starts))
                  + len(pass_starts) * B * (row + H * hd * (itemsize + 4)))
    return flops, nbytes
