"""Required operations and bytes of the ``deepseek_v2`` family, from shapes,
live lengths and the routers' counts alone (``flops.py`` is GPT's; the
contract is the same: what the mathematics needs, never what an
implementation executes).

``cfg`` is a configuration file's dictionary under the published keys,
cut to one chip's share as ``deepseek_v2_weights.share`` reads it.  A
multiply-add is two operations.  Attention is counted in the absorbed
form for a decoded token (``H (2 R + d_r)`` multiply-adds a cached row:
scores over ``R + d_r``, values over ``R``) and in the materialised form
for a prefilled one (``H (d_n + d_r + d_v)`` a key, the prefix's
up-projection left out: it is an implementation's choice to redo it per
chunk).
"""

from __future__ import annotations


def _h(cfg):
    return (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def attention_params(cfg):
    """``W_DQ``, ``W_UQ`` with ``W_QR``, ``W_DKV`` with ``W_KR``, ``W_UK``
    with ``W_UV``, ``W_O`` of one layer."""
    D, Rq, R = cfg["hidden_size"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    H, dn, dr, dv = _h(cfg)
    return (D * Rq + Rq * H * (dn + dr) + D * (R + dr) + R * H * (dn + dv)
            + H * dv * D)


def dense_mlp_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg):
    """One routed expert."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg):
    return cfg["n_shared_experts"] * expert_params(cfg)


def router_params(cfg):
    held = cfg["n_routed_experts"]
    return cfg["hidden_size"] * cfg.get("published", {}).get(
        "n_routed_experts", held)


def layer_counts(cfg):
    """``(dense layers, expert layers)``."""
    nD = cfg["first_k_dense_replace"]
    return nD, cfg["num_hidden_layers"] - nD


def expert_layer_params(cfg):
    """What an expert layer holds beside its attention: the shared
    experts, the router, and the routed experts held here."""
    return (shared_params(cfg) + router_params(cfg)
            + cfg["n_routed_experts"] * expert_params(cfg))


def n_params(cfg, norms=False):
    """Every matrix parameter the program holds; with ``norms`` the gains
    too (two a layer of ``D``, ``q_lora_rank`` and ``kv_lora_rank`` a
    layer, the final ``D``)."""
    nD, nM = layer_counts(cfg)
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    n = ((nD + nM) * attention_params(cfg) + nD * dense_mlp_params(cfg)
         + nM * expert_layer_params(cfg) + 2 * V * D)
    if norms:
        n += (nD + nM) * (2 * D + cfg["q_lora_rank"]
                          + cfg["kv_lora_rank"]) + D
    return n


def latent_bytes_per_token_layer(cfg, itemsize=2):
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize


def dense_token_params(cfg):
    """Parameters in a matrix product for EVERY token: attention, the
    dense MLPs, the shared experts and the routers, and the head (the
    embedding is a look-up)."""
    nD, nM = layer_counts(cfg)
    return ((nD + nM) * attention_params(cfg) + nD * dense_mlp_params(cfg)
            + nM * (shared_params(cfg) + router_params(cfg))
            + cfg["hidden_size"] * cfg["vocab_size"])


def decode_attention_flops(cfg, live_sum):
    """Absorbed: per cached row and layer ``H (R + d_r)`` multiply-adds of
    scores and ``H R`` of values."""
    H, _, dr, _ = _h(cfg)
    R = cfg["kv_lora_rank"]
    return 2 * sum(layer_counts(cfg)) * H * (2 * R + dr) * int(live_sum)


def prefill_attention_flops(cfg, q_tokens_times_keys):
    H, dn, dr, dv = _h(cfg)
    return (2 * sum(layer_counts(cfg)) * H * (dn + dr + dv)
            * int(q_tokens_times_keys))


def routed_flops(cfg, assignments):
    """``assignments`` (token, held expert) pairs, over all layers."""
    return 2 * expert_params(cfg) * int(assignments)


def serve_flops(cfg, prefill, decode_live, held_per_token_layer):
    """Forward operations of a step: ``prefill = (tokens, sum of the keys
    they attend to)``, ``decode_live`` the live lengths of the rows
    decoded, ``held_per_token_layer`` the mean number of held experts a
    token's choice falls on in one layer (from the program's counts; 6 x
    the held share of the experts if routing is even)."""
    n_tok = prefill[0] + len(decode_live)
    _, nM = layer_counts(cfg)
    return (2 * dense_token_params(cfg) * n_tok
            + routed_flops(cfg, held_per_token_layer * nM * n_tok)
            + prefill_attention_flops(cfg, prefill[1])
            + decode_attention_flops(cfg, sum(decode_live)))


def weight_bytes_touched(cfg, n_rows, held_per_token_layer, itemsize=2):
    """Least weight bytes of one decode step over ``n_rows`` rows: every
    weight but the embedding and the routed experts once, and of each
    expert layer's held experts those that some row chose, in expectation
    under even routing over the held: ``E (1 - (1 - 1/E)^(rows x mean
    held choices))``."""
    _, nM = layer_counts(cfg)
    E = cfg["n_routed_experts"]
    touched = E * (1.0 - (1.0 - 1.0 / E) ** (n_rows * held_per_token_layer))
    return itemsize * (dense_token_params(cfg)
                       + nM * touched * expert_params(cfg))


def decode_step_bytes(cfg, live_lengths, held_per_token_layer, itemsize=2):
    """Least HBM traffic of one decode step: the weights touched and the
    live latent rows of every layer."""
    rows = sum(layer_counts(cfg)) * int(sum(live_lengths))
    return (weight_bytes_touched(cfg, len(live_lengths),
                                 held_per_token_layer, itemsize)
            + rows * latent_bytes_per_token_layer(cfg, itemsize))


def mla_decode_attn_cost(cfg, live_lengths, itemsize=2):
    """``(operations, bytes)`` of the absorbed attention alone over rows
    with those live lengths, all layers: what ``mla_decode_attn`` has to
    compute and read."""
    rows = sum(layer_counts(cfg)) * int(sum(live_lengths))
    return (decode_attention_flops(cfg, sum(live_lengths)),
            rows * latent_bytes_per_token_layer(cfg, itemsize))


def mla_prefill_attn_cost(cfg, q_tokens, q_tokens_times_keys, itemsize=2):
    """``(operations, bytes)`` of materialised attention alone for chunks
    of ``q_tokens`` queries that may see ``q_tokens_times_keys`` keys
    between them, all layers: what ``mla_prefill_attn`` has to compute,
    and the least it has to move (each query's heads in and out, and as
    many latent rows as the mean query sees: no chunk reads fewer)."""
    H, dn, dr, dv = _h(cfg)
    rows = q_tokens_times_keys / max(q_tokens, 1)
    return (prefill_attention_flops(cfg, q_tokens_times_keys),
            sum(layer_counts(cfg)) * itemsize * (
                q_tokens * H * (dn + dr + dv)
                + rows * (cfg["kv_lora_rank"] + dr)))
