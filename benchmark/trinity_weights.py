"""Seeded weights of the ``afmoe`` family, for program and reference.

The same contract as ``weights.py`` and ``deepseek_v2_weights.py``: neither
side's weights come from the program's constructor; both are drawn on the
device from ``--seed``, in the type they are served in, and a leaf's key
depends on the seed, the leaf, the layer and (for a routed expert) the
expert alone, so the reference can make ONE layer's weights at a time and
its experts one at a time and get exactly what the program holds.

Leaves: the sandwich's four gains ``attn_in_g``, ``attn_post_g``,
``ffn_pre_g``, ``ffn_post_g``; ``w_q``, ``w_k``, ``w_v``, ``w_g`` (the
output gate), ``q_g``, ``k_g`` (the per-head QK-norm gains), ``w_o``; a dense
layer's ``w_gate``, ``w_up``, ``w_down``; an expert layer's ``w_router``,
``bias`` (the router's expert bias, float32), ``sh_gate``, ``sh_up``,
``sh_down``; a routed expert's ``ex_gate``, ``ex_up``, ``ex_down``; on top
``wte``, ``lnf_g``, ``head``.  ``program_tensor`` re-deals them into the
tensors ``paddle_tpu/models/trinity.py`` holds: stacked over the layers of
one kind, ``qkvg_w = [w_q | w_k | w_v | w_g]``, every gate beside its up
projection, the experts stacked over layers and experts.

How a leaf is drawn (``assumed`` in the configuration file): matrices
N(0, ``initializer_range``); norm gains 1; the expert bias N(0,
``expert_bias_std``), so that choosing by score plus bias and weighing by
the score part ways on a share of the tokens.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.deepseek_v2_weights import _put_expert
from benchmark.weights import seed_key

_D = lambda c: c["hidden_size"]                            # noqa: E731
_Q = lambda c: c["num_attention_heads"] * c["head_dim"]    # noqa: E731
_K = lambda c: c["num_key_value_heads"] * c["head_dim"]    # noqa: E731
_F = lambda c: c["intermediate_size"]                      # noqa: E731
_FM = lambda c: c["moe_intermediate_size"]                 # noqa: E731
_FS = lambda c: c["num_shared_experts"] * c["moe_intermediate_size"]  # noqa

#: leaf -> (shape from the sizes, how it is drawn); one list for all
#: groups, so a leaf's index (part of its key) is its place here
_TOP = (
    ("wte", lambda c: (c["vocab_size"], _D(c)), "normal"),
    ("lnf_g", lambda c: (_D(c),), "ones"),
    ("head", lambda c: (_D(c), c["vocab_size"]), "normal"),
)
_ATTN = (
    ("attn_in_g", lambda c: (_D(c),), "ones"),
    ("attn_post_g", lambda c: (_D(c),), "ones"),
    ("ffn_pre_g", lambda c: (_D(c),), "ones"),
    ("ffn_post_g", lambda c: (_D(c),), "ones"),
    ("w_q", lambda c: (_D(c), _Q(c)), "normal"),
    ("w_k", lambda c: (_D(c), _K(c)), "normal"),
    ("w_v", lambda c: (_D(c), _K(c)), "normal"),
    ("w_g", lambda c: (_D(c), _Q(c)), "normal"),
    ("q_g", lambda c: (c["head_dim"],), "ones"),
    ("k_g", lambda c: (c["head_dim"],), "ones"),
    ("w_o", lambda c: (_Q(c), _D(c)), "normal"),
)
_DENSE = (
    ("w_gate", lambda c: (_D(c), _F(c)), "normal"),
    ("w_up", lambda c: (_D(c), _F(c)), "normal"),
    ("w_down", lambda c: (_F(c), _D(c)), "normal"),
)
_MOE = (
    ("w_router", lambda c: (_D(c), c["router_width"]), "normal"),
    ("bias", lambda c: (c["router_width"],), "bias"),
    ("sh_gate", lambda c: (_D(c), _FS(c)), "normal"),
    ("sh_up", lambda c: (_D(c), _FS(c)), "normal"),
    ("sh_down", lambda c: (_FS(c), _D(c)), "normal"),
)
_EXPERT = (
    ("ex_gate", lambda c: (_D(c), _FM(c)), "normal"),
    ("ex_up", lambda c: (_D(c), _FM(c)), "normal"),
    ("ex_down", lambda c: (_FM(c), _D(c)), "normal"),
)
_GROUPS = {"top": _TOP, "attn": _ATTN, "dense": _DENSE, "moe": _MOE,
           "expert": _EXPERT}
_INDEX = {(g, n): i for i, (g, n) in enumerate(
    (g, n) for g, leaves in _GROUPS.items() for n, _, _ in leaves)}

#: program tensor -> (group, the leaves joined along the last axis)
PROGRAM_TENSORS = {
    "wte": ("top", ("wte",)), "lnf_w": ("top", ("lnf_g",)),
    "lm_head": ("top", ("head",)),
    "attn_in_w": ("attn", ("attn_in_g",)),
    "attn_post_w": ("attn", ("attn_post_g",)),
    "ffn_pre_w": ("attn", ("ffn_pre_g",)),
    "ffn_post_w": ("attn", ("ffn_post_g",)),
    "qkvg_w": ("attn", ("w_q", "w_k", "w_v", "w_g")),
    "q_norm_w": ("attn", ("q_g",)), "k_norm_w": ("attn", ("k_g",)),
    "o_w": ("attn", ("w_o",)),
    "mlp_gu_w": ("dense", ("w_gate", "w_up")),
    "mlp_down_w": ("dense", ("w_down",)),
    "router_w": ("moe", ("w_router",)),
    "expert_bias": ("moe", ("bias",)),
    "shared_gu_w": ("moe", ("sh_gate", "sh_up")),
    "shared_down_w": ("moe", ("sh_down",)),
    "expert_gu_w": ("expert", ("ex_gate", "ex_up")),
    "expert_down_w": ("expert", ("ex_down",)),
}


def share(cfg):
    """``(published experts, first held, held)`` of a configuration file: a
    file cut to one chip's share states the experts held under
    ``num_experts``, the first of them under ``experts_held_first`` and the
    router's width under ``published``."""
    held = int(cfg["num_experts"])
    width = int(cfg.get("published", {}).get("num_experts", held))
    return width, int(cfg.get("experts_held_first", 0)), held


def sizes(cfg):
    """The sizes a configuration file states, hashable (the static
    argument of the jitted makers)."""
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "num_shared_experts", "num_dense_layers")
    return tuple((k, int(cfg[k])) for k in keys) + (
        ("initializer_range", float(cfg["initializer_range"])),
        ("expert_bias_std", float(cfg["expert_bias_std"])),
        ("router_width", share(cfg)[0]))


def parameters(cfg):
    """How many parameters the configuration's program holds: ``{"attn"
    (a layer's attention and its norms), "dense" (a dense layer's MLP),
    "moe" (an expert layer's router, bias and shared experts), "expert"
    (one routed expert), "top", "total"}``."""
    c = dict(sizes(cfg))
    count = lambda leaves: sum(                            # noqa: E731
        math.prod(shape(c)) for _, shape, _ in leaves)
    out = {g: count(leaves) for g, leaves in _GROUPS.items()}
    L, nD = c["num_hidden_layers"], c["num_dense_layers"]
    out["total"] = (out["top"] + L * out["attn"] + nD * out["dense"]
                    + (L - nD) * (out["moe"] + share(cfg)[2] * out["expert"]))
    return out


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
    if how == "bias":          # chooses only: float32 in every program
        return x * c["expert_bias_std"]
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
    """Layer ``l``'s leaves for the reference, without its routed experts:
    attention and its norms, and the dense MLP or the router, its bias and
    the shared experts."""
    sz, key, dt = sizes(cfg), seed_key(seed), jnp.dtype(dtype)
    kind = "dense" if l < cfg["num_dense_layers"] else "moe"
    return {**_group(key, sz, "attn", l, dt), **_group(key, sz, kind, l, dt)}


def expert(cfg, seed, l, e, dtype):
    """Routed expert ``e`` (over all the routed experts) of layer ``l``:
    ``ex_gate``, ``ex_up``, ``ex_down``."""
    return _one_expert(seed_key(seed), sizes(cfg), jnp.int32(l),
                       jnp.int32(e), jnp.dtype(dtype))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 5))
def _leaf(key, sz, group, name, layer, dtype):
    """One leaf of one layer (``layer`` traced: one compile makes them
    all).  A tensor of several leaves is joined outside the jit: the TPU
    compiler did not finish a jitted join of two normal draws."""
    return _draw(key, dict(sz), group, name, layer, dtype)


def _layer_tensor(key, sz, tensor, l, dtype):
    group, leaves = PROGRAM_TENSORS[tensor]
    parts = [_leaf(key, sz, group, n, jnp.int32(l), dtype) for n in leaves]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, -1)


@functools.partial(jax.jit, donate_argnums=0)
def _put_layer(stacked, one, j):
    return jax.lax.dynamic_update_index_in_dim(stacked, one, j, 0)


def program_tensor(cfg, seed, tensor, dtype):
    """One tensor the program holds, in its layout, made one layer (and
    one expert) at a time into a buffer that is handed on: a jitted draw of
    a whole stack would need its float32 size again beside it."""
    sz, dt = sizes(cfg), jnp.dtype(dtype)
    c = dict(sz)
    _, first, held = share(cfg)
    group, leaves = PROGRAM_TENSORS[tensor]
    key = seed_key(seed)
    if group == "top":
        return _layer_tensor(key, sz, tensor, 0, dt)
    nD, L = c["num_dense_layers"], c["num_hidden_layers"]
    layers = {"attn": range(L), "dense": range(nD), "moe": range(nD, L),
              "expert": range(nD, L)}[group]
    out = None
    for j, l in enumerate(layers):
        if group != "expert":
            one = _layer_tensor(key, sz, tensor, l, dt)
            if out is None:
                out = jnp.zeros((len(layers),) + one.shape, one.dtype)
            out = _put_layer(out, one, jnp.int32(j))
            continue
        for i in range(held):
            ex = expert(cfg, seed, l, first + i, dt)
            one = jnp.concatenate([ex[n] for n in leaves], -1)
            if out is None:
                out = jnp.zeros((len(layers), held) + one.shape, dt)
            out = _put_expert(out, one, jnp.int32(j), jnp.int32(i))
    return out
