"""Seeded weights of the ``deepseek_v2`` family, for program and reference.

The same contract as ``weights.py`` and ``olmo_hybrid_weights.py``: neither
side's weights come from the program's constructor; both are drawn on the
device from ``--seed``, in the type they are served in, and a leaf's key
depends on the seed, the leaf, the layer and (for a routed expert) the
expert alone, so the reference can make ONE layer's weights at a time and
its experts a few at a time (a layer's 40 held experts are 3.8 GB in
float32) and get exactly what the program holds.

Leaves carry the paper's names (arXiv:2405.04434, section 2.1):
``w_dq``, ``w_uq``, ``w_qr``, ``w_dkv``, ``w_kr``, ``w_uk``, ``w_uv``,
``w_o``.  ``program_tensor`` re-deals them into the tensors
``paddle_tpu/models/deepseek_v2.py`` holds: stacked over the layers of one
kind, ``q_b_w = [w_uq | w_qr]``, ``kv_a_w = [w_dkv | w_kr]``, every gate
beside its up projection, the experts stacked over layers and experts.

How a leaf is drawn (``assumed`` in the configuration file): matrices
N(0, ``initializer_range``); norm gains 1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key


def _d(c):
    H = c["num_attention_heads"]
    return (c["hidden_size"], H * c["qk_nope_head_dim"],
            H * c["qk_rope_head_dim"], H * c["v_head_dim"])


#: leaf -> (shape from the sizes, how it is drawn); one list for all
#: groups, so a leaf's index (part of its key) is its place here
_TOP = (
    ("wte", lambda c: (c["vocab_size"], c["hidden_size"]), "normal"),
    ("lnf_g", lambda c: (c["hidden_size"],), "ones"),
    ("head", lambda c: (c["hidden_size"], c["vocab_size"]), "normal"),
)
_ATTN = (
    ("attn_g", lambda c: (_d(c)[0],), "ones"),
    ("ffn_g", lambda c: (_d(c)[0],), "ones"),
    ("w_dq", lambda c: (_d(c)[0], c["q_lora_rank"]), "normal"),
    ("q_g", lambda c: (c["q_lora_rank"],), "ones"),
    ("w_uq", lambda c: (c["q_lora_rank"], _d(c)[1]), "normal"),
    ("w_qr", lambda c: (c["q_lora_rank"], _d(c)[2]), "normal"),
    ("w_dkv", lambda c: (_d(c)[0], c["kv_lora_rank"]), "normal"),
    ("w_kr", lambda c: (_d(c)[0], c["qk_rope_head_dim"]), "normal"),
    ("kv_g", lambda c: (c["kv_lora_rank"],), "ones"),
    ("w_uk", lambda c: (c["kv_lora_rank"], _d(c)[1]), "normal"),
    ("w_uv", lambda c: (c["kv_lora_rank"], _d(c)[3]), "normal"),
    ("w_o", lambda c: (_d(c)[3], _d(c)[0]), "normal"),
)
_DENSE = (
    ("w_gate", lambda c: (_d(c)[0], c["intermediate_size"]), "normal"),
    ("w_up", lambda c: (_d(c)[0], c["intermediate_size"]), "normal"),
    ("w_down", lambda c: (c["intermediate_size"], _d(c)[0]), "normal"),
)
_FS = lambda c: c["n_shared_experts"] * c["moe_intermediate_size"]  # noqa: E731
_MOE = (
    ("w_router", lambda c: (_d(c)[0], c["router_width"]), "normal"),
    ("sh_gate", lambda c: (_d(c)[0], _FS(c)), "normal"),
    ("sh_up", lambda c: (_d(c)[0], _FS(c)), "normal"),
    ("sh_down", lambda c: (_FS(c), _d(c)[0]), "normal"),
)
_EXPERT = (
    ("ex_gate", lambda c: (_d(c)[0], c["moe_intermediate_size"]), "normal"),
    ("ex_up", lambda c: (_d(c)[0], c["moe_intermediate_size"]), "normal"),
    ("ex_down", lambda c: (c["moe_intermediate_size"], _d(c)[0]), "normal"),
)
_GROUPS = {"top": _TOP, "attn": _ATTN, "dense": _DENSE, "moe": _MOE,
           "expert": _EXPERT}
_INDEX = {(g, n): i for i, (g, n) in enumerate(
    (g, n) for g, leaves in _GROUPS.items() for n, _, _ in leaves)}

#: program tensor -> (group, the leaves joined along the last axis)
PROGRAM_TENSORS = {
    "wte": ("top", ("wte",)), "lnf_w": ("top", ("lnf_g",)),
    "lm_head": ("top", ("head",)),
    "attn_norm_w": ("attn", ("attn_g",)), "ffn_norm_w": ("attn", ("ffn_g",)),
    "q_a_w": ("attn", ("w_dq",)), "q_a_norm_w": ("attn", ("q_g",)),
    "q_b_w": ("attn", ("w_uq", "w_qr")),
    "kv_a_w": ("attn", ("w_dkv", "w_kr")),
    "kv_a_norm_w": ("attn", ("kv_g",)),
    "kv_b_k_w": ("attn", ("w_uk",)), "kv_b_v_w": ("attn", ("w_uv",)),
    "o_w": ("attn", ("w_o",)),
    "mlp_gu_w": ("dense", ("w_gate", "w_up")),
    "mlp_down_w": ("dense", ("w_down",)),
    "router_w": ("moe", ("w_router",)),
    "shared_gu_w": ("moe", ("sh_gate", "sh_up")),
    "shared_down_w": ("moe", ("sh_down",)),
    "expert_gu_w": ("expert", ("ex_gate", "ex_up")),
    "expert_down_w": ("expert", ("ex_down",)),
}


def share(cfg):
    """``(published experts, first held, held)`` of a configuration file:
    a file cut to one chip's share states the experts held under
    ``n_routed_experts``, the first of them under ``experts_held_first``
    and the router's width under ``published``."""
    held = int(cfg["n_routed_experts"])
    width = int(cfg.get("published", {}).get("n_routed_experts", held))
    return width, int(cfg.get("experts_held_first", 0)), held


def sizes(cfg):
    """The sizes a configuration file states, hashable (the static
    argument of the jitted makers)."""
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "n_shared_experts", "first_k_dense_replace")
    width, first, held = share(cfg)
    return tuple((k, int(cfg[k])) for k in keys) + (
        ("initializer_range", float(cfg["initializer_range"])),
        ("router_width", width), ("experts_first", first),
        ("experts_held", held))


def _draw(key, c, group, name, layer, dtype, expert=None):
    _, shape_of, how = next(x for x in _GROUPS[group] if x[0] == name)
    shape = shape_of(c)
    if how == "ones":
        return jnp.ones(shape, dtype)
    key = jax.random.fold_in(jax.random.fold_in(key, _INDEX[group, name]),
                             layer)
    if expert is not None:
        key = jax.random.fold_in(key, expert)
    x = jax.random.normal(key, shape, jnp.float32)
    return (x * c["initializer_range"]).astype(dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 4))
def _group(key, sz, group, layer, dtype):
    c = dict(sz)
    return {n: _draw(key, c, group, n, layer, dtype)
            for n, _, _ in _GROUPS[group]}


@functools.partial(jax.jit, static_argnums=(1, 4))
def _one_expert(key, sz, layer, expert, dtype):
    """One routed expert's three matrices; ``layer`` and ``expert`` (its
    index over ALL the routed experts) are traced, so one compile makes
    them all."""
    c = dict(sz)
    return {n: _draw(key, c, "expert", n, layer, dtype, expert)
            for n, _, _ in _EXPERT}


def top(cfg, seed, dtype):
    """``wte``, ``lnf_g``, ``head`` for the reference."""
    return _group(seed_key(seed), sizes(cfg), "top", 0, jnp.dtype(dtype))


def layer(cfg, seed, l, dtype):
    """Layer ``l``'s leaves for the reference, without its routed
    experts: attention, and the dense MLP or the router and the shared
    experts."""
    sz, key, dt = sizes(cfg), seed_key(seed), jnp.dtype(dtype)
    kind = "dense" if l < cfg["first_k_dense_replace"] else "moe"
    return {**_group(key, sz, "attn", l, dt), **_group(key, sz, kind, l, dt)}


def expert(cfg, seed, l, e, dtype):
    """Routed expert ``e`` (over all the routed experts) of layer ``l``:
    ``ex_gate``, ``ex_up``, ``ex_down``."""
    return _one_expert(seed_key(seed), sizes(cfg), jnp.int32(l),
                       jnp.int32(e), jnp.dtype(dtype))


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _program_tensor(key, sz, tensor, dtype):
    c = dict(sz)
    group, leaves = PROGRAM_TENSORS[tensor]
    join = lambda l: jnp.concatenate(                          # noqa: E731
        [_draw(key, c, group, n, l, dtype) for n in leaves], -1)
    if group == "top":
        return join(0)
    nD, L = c["first_k_dense_replace"], c["num_hidden_layers"]
    layers = {"attn": range(L), "dense": range(nD), "moe": range(nD, L)}
    return jnp.stack([join(l) for l in layers[group]])


@functools.partial(jax.jit, donate_argnums=0)
def _put_expert(stacked, one, j, e):
    return jax.lax.dynamic_update_slice(
        stacked, one[None, None], (j, e) + (0,) * one.ndim)


def program_tensor(cfg, seed, tensor, dtype):
    """One tensor the program holds, in its layout.  The experts' two are
    filled one expert at a time into a buffer that is handed on (5 GB in
    bfloat16 would need 10 GB more as one float32 draw)."""
    sz, dt = sizes(cfg), jnp.dtype(dtype)
    group, leaves = PROGRAM_TENSORS[tensor]
    if group != "expert":
        return _program_tensor(seed_key(seed), sz, tensor, dt)
    c = dict(sz)
    nD, L = c["first_k_dense_replace"], c["num_hidden_layers"]
    out = None
    for j, l in enumerate(range(nD, L)):
        for i in range(c["experts_held"]):
            ex = expert(cfg, seed, l, c["experts_first"] + i, dt)
            one = jnp.concatenate([ex[n] for n in leaves], -1)
            if out is None:
                out = jnp.zeros((L - nD, c["experts_held"]) + one.shape, dt)
            out = _put_expert(out, one, jnp.int32(j), jnp.int32(i))
    return out


def program(cfg, seed, dtype):
    """``(tensor, array)`` for every tensor the program holds."""
    for tensor in PROGRAM_TENSORS:
        yield tensor, program_tensor(cfg, seed, tensor, dtype)
