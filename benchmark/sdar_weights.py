"""Seeded weights of the ``sdar_moe`` family, for program and reference.

The same contract as ``weights.py`` and ``deepseek_v2_weights.py``:
neither side's weights come from the program's constructor; both are drawn
on the device from ``--seed``, in the type they are served in, and a
leaf's key depends on the seed, the leaf, the layer and (for an expert) the
expert alone, so the reference can make ONE layer's weights at a time and
its experts one at a time (a layer's 128 experts are 2.4 GB in float32)
and get exactly what the program holds.

Leaves: ``attn_g``, ``ffn_g`` (the block's two RMSNorm gains), ``w_q``,
``w_k``, ``w_v``, ``w_o``, ``q_g``, ``k_g`` (the per-head QK-norm gains),
``w_router``; an expert's ``ex_gate``, ``ex_up``, ``ex_down``; on top
``wte``, ``lnf_g``, ``head``.  ``program_tensor`` re-deals them into the
tensors ``paddle_tpu/models/sdar.py`` holds: stacked over the layers,
``kv_w = [w_k | w_v]``, every gate beside its up projection, the experts
stacked over layers and experts.

How a leaf is drawn (``assumed`` in the configuration file): matrices
N(0, ``initializer_range``); norm gains 1.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.deepseek_v2_weights import _put_expert
from benchmark.weights import seed_key


def _qd(c):
    return c["num_attention_heads"] * c["head_dim"]


def _kd(c):
    return c["num_key_value_heads"] * c["head_dim"]


_D = lambda c: c["hidden_size"]                            # noqa: E731
_F = lambda c: c["moe_intermediate_size"]                  # noqa: E731

#: leaf -> (shape from the sizes, how it is drawn); one list for all
#: groups, so a leaf's index (part of its key) is its place here
_TOP = (
    ("wte", lambda c: (c["vocab_size"], _D(c)), "normal"),
    ("lnf_g", lambda c: (_D(c),), "ones"),
    ("head", lambda c: (_D(c), c["vocab_size"]), "normal"),
)
_LAYER = (
    ("attn_g", lambda c: (_D(c),), "ones"),
    ("ffn_g", lambda c: (_D(c),), "ones"),
    ("w_q", lambda c: (_D(c), _qd(c)), "normal"),
    ("w_k", lambda c: (_D(c), _kd(c)), "normal"),
    ("w_v", lambda c: (_D(c), _kd(c)), "normal"),
    ("q_g", lambda c: (c["head_dim"],), "ones"),
    ("k_g", lambda c: (c["head_dim"],), "ones"),
    ("w_o", lambda c: (_qd(c), _D(c)), "normal"),
    ("w_router", lambda c: (_D(c), c["num_experts"]), "normal"),
)
_EXPERT = (
    ("ex_gate", lambda c: (_D(c), _F(c)), "normal"),
    ("ex_up", lambda c: (_D(c), _F(c)), "normal"),
    ("ex_down", lambda c: (_F(c), _D(c)), "normal"),
)
_GROUPS = {"top": _TOP, "layer": _LAYER, "expert": _EXPERT}
_INDEX = {(g, n): i for i, (g, n) in enumerate(
    (g, n) for g, leaves in _GROUPS.items() for n, _, _ in leaves)}

#: program tensor -> (group, the leaves joined along the last axis)
PROGRAM_TENSORS = {
    "wte": ("top", ("wte",)), "lnf_w": ("top", ("lnf_g",)),
    "lm_head": ("top", ("head",)),
    "attn_norm_w": ("layer", ("attn_g",)),
    "ffn_norm_w": ("layer", ("ffn_g",)),
    "q_w": ("layer", ("w_q",)), "kv_w": ("layer", ("w_k", "w_v")),
    "q_norm_w": ("layer", ("q_g",)), "k_norm_w": ("layer", ("k_g",)),
    "o_w": ("layer", ("w_o",)), "router_w": ("layer", ("w_router",)),
    "expert_gu_w": ("expert", ("ex_gate", "ex_up")),
    "expert_down_w": ("expert", ("ex_down",)),
}

_SIZE_KEYS = ("vocab_size", "hidden_size", "moe_intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "num_experts")


def sizes(cfg):
    """The sizes a configuration file states, hashable (the static
    argument of the jitted makers)."""
    return tuple((k, int(cfg[k])) for k in _SIZE_KEYS) + (
        ("initializer_range", float(cfg["initializer_range"])),)


def parameters(cfg):
    """How many parameters the configuration's model has: ``{"layer",
    "top", "total"}``."""
    c = dict(sizes(cfg))
    count = lambda leaves: sum(                            # noqa: E731
        math.prod(shape(c)) for _, shape, _ in leaves)
    layer = count(_LAYER) + c["num_experts"] * count(_EXPERT)
    top = count(_TOP)
    return {"layer": layer, "top": top,
            "total": c["num_hidden_layers"] * layer + top}


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
    """One expert's three matrices; ``layer`` and ``expert`` are traced,
    so one compile makes them all."""
    c = dict(sz)
    return {n: _draw(key, c, "expert", n, layer, dtype, expert)
            for n, _, _ in _EXPERT}


def top(cfg, seed, dtype):
    """``wte``, ``lnf_g``, ``head`` for the reference."""
    return _group(seed_key(seed), sizes(cfg), "top", 0, jnp.dtype(dtype))


def layer(cfg, seed, l, dtype):
    """Layer ``l``'s leaves for the reference, without its experts."""
    return _group(seed_key(seed), sizes(cfg), "layer", l, jnp.dtype(dtype))


def expert(cfg, seed, l, e, dtype):
    """Expert ``e`` of layer ``l``: ``ex_gate``, ``ex_up``, ``ex_down``."""
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
    return jnp.stack([join(l) for l in range(c["num_hidden_layers"])])


def program_tensor(cfg, seed, tensor, dtype):
    """One tensor the program holds, in its layout.  The experts' two are
    filled one expert at a time into a buffer that is handed on (4.8 GB in
    bfloat16 would need 9.7 GB more as one float32 draw)."""
    sz, dt = sizes(cfg), jnp.dtype(dtype)
    group, leaves = PROGRAM_TENSORS[tensor]
    if group != "expert":
        return _program_tensor(seed_key(seed), sz, tensor, dt)
    c = dict(sz)
    out = None
    for l in range(c["num_hidden_layers"]):
        for e in range(c["num_experts"]):
            ex = expert(cfg, seed, l, e, dt)
            one = jnp.concatenate([ex[n] for n in leaves], -1)
            if out is None:
                out = jnp.zeros((c["num_hidden_layers"], c["num_experts"])
                                + one.shape, dt)
            out = _put_expert(out, one, jnp.int32(l), jnp.int32(e))
    return out


def program(cfg, seed, dtype):
    """``(tensor, array)`` for every tensor the program holds."""
    for tensor in PROGRAM_TENSORS:
        yield tensor, program_tensor(cfg, seed, tensor, dtype)
