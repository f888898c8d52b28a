"""Seeded weights, made by the benchmark and handed to both sides.

The reference may take nothing the program has made, so neither side's
weights come from the program's constructor: this module draws them on the
device from ``--seed`` in one jitted call, in the type they are trained or
served in, and the program's parameters are overwritten with them before
its first program is built.  The reference regenerates the same leaves from
the same seed (a leaf's key depends on the seed and the leaf alone, so one
leaf drawn alone equals that leaf drawn with all the others).

Leaves carry the paper's names, stacked over layers: ``wq [L, D, D]`` ...
``program_layout`` re-deals them into the tensors ``models/gpt.py`` holds
(``qkv_w = [wq | wk | wv]`` along the output axis, which is how its
``jnp.split(qkv, 3, -1)`` reads them).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: leaf -> (shape as a function of the sizes, how it is drawn)
_LEAVES = (
    ("wte", lambda c: (c["vocab_size"], c["d_model"]), "normal"),
    ("wpe", lambda c: (c["n_ctx"], c["d_model"]), "normal"),
    ("ln1_g", lambda c: (c["n_layers"], c["d_model"]), "ones"),
    ("ln1_b", lambda c: (c["n_layers"], c["d_model"]), "zeros"),
    ("wq", lambda c: (c["n_layers"], c["d_model"], c["d_model"]), "normal"),
    ("wk", lambda c: (c["n_layers"], c["d_model"], c["d_model"]), "normal"),
    ("wv", lambda c: (c["n_layers"], c["d_model"], c["d_model"]), "normal"),
    ("bq", lambda c: (c["n_layers"], c["d_model"]), "zeros"),
    ("bk", lambda c: (c["n_layers"], c["d_model"]), "zeros"),
    ("bv", lambda c: (c["n_layers"], c["d_model"]), "zeros"),
    ("wo", lambda c: (c["n_layers"], c["d_model"], c["d_model"]), "normal"),
    ("bo", lambda c: (c["n_layers"], c["d_model"]), "zeros"),
    ("ln2_g", lambda c: (c["n_layers"], c["d_model"]), "ones"),
    ("ln2_b", lambda c: (c["n_layers"], c["d_model"]), "zeros"),
    ("w1", lambda c: (c["n_layers"], c["d_model"], c["d_ff"]), "normal"),
    ("b1", lambda c: (c["n_layers"], c["d_ff"]), "zeros"),
    ("w2", lambda c: (c["n_layers"], c["d_ff"], c["d_model"]), "normal"),
    ("b2", lambda c: (c["n_layers"], c["d_model"]), "zeros"),
    ("lnf_g", lambda c: (c["d_model"],), "ones"),
    ("lnf_b", lambda c: (c["d_model"],), "zeros"),
)
LEAF_NAMES = tuple(n for n, _, _ in _LEAVES)
#: leaves with a layer axis in front
LAYER_LEAVES = tuple(n for n in LEAF_NAMES
                     if n not in ("wte", "wpe", "lnf_g", "lnf_b"))

#: program tensor -> the leaves it is made of (joined along the last axis)
PROGRAM_TENSORS = {
    "wte": ("wte",), "wpe": ("wpe",),
    "ln1_w": ("ln1_g",), "ln1_b": ("ln1_b",),
    "qkv_w": ("wq", "wk", "wv"), "qkv_b": ("bq", "bk", "bv"),
    "proj_w": ("wo",), "proj_b": ("bo",),
    "ln2_w": ("ln2_g",), "ln2_b": ("ln2_b",),
    "fc1_w": ("w1",), "fc1_b": ("b1",),
    "fc2_w": ("w2",), "fc2_b": ("b2",),
    "lnf_w": ("lnf_g",), "lnf_b": ("lnf_b",),
}


def sizes(cfg):
    """The sizes a configuration file states, as a hashable tuple (the
    static argument of the jitted makers)."""
    keys = ("n_layers", "d_model", "n_heads", "d_ff", "n_ctx", "vocab_size")
    return tuple((k, int(cfg[k])) for k in keys) + (
        ("initializer_range", float(cfg["initializer_range"])),)


def seed_key(seed):
    """A key from any whole number up to and beyond 2**31 (``jax.random.key``
    alone takes 32 signed bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def _draw(key, sz, name, dtype):
    c = dict(sz)
    idx = LEAF_NAMES.index(name)
    _, shape_of, how = _LEAVES[idx]
    shape = shape_of(c)
    if how == "ones":
        return jnp.ones(shape, dtype)
    if how == "zeros":
        return jnp.zeros(shape, dtype)
    x = jax.random.normal(jax.random.fold_in(key, idx), shape, jnp.float32)
    return (x * c["initializer_range"]).astype(dtype)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, sz, names, dtype):
    return {n: _draw(key, sz, n, dtype) for n in names}


def make(cfg, seed, dtype, names=LEAF_NAMES):
    """``{leaf: array}`` for ``names``, drawn on the device in one call."""
    return _make(seed_key(seed), sizes(cfg), tuple(names), jnp.dtype(dtype))


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make_program(key, sz, names, dtype):
    return {t: jnp.concatenate(
        [_draw(key, sz, n, dtype) for n in PROGRAM_TENSORS[t]], -1)
        for t in names}


def make_program(cfg, seed, dtype, names=tuple(PROGRAM_TENSORS)):
    """The same leaves in the program's layout, ``{tensor: array}``."""
    return _make_program(seed_key(seed), sizes(cfg), tuple(names),
                         jnp.dtype(dtype))


def split_program(name, x):
    """A program tensor (or anything shaped like it, such as its gradient)
    back into ``{leaf: array}``."""
    leaves = PROGRAM_TENSORS[name]
    if len(leaves) == 1:
        return {leaves[0]: x}
    return dict(zip(leaves, jnp.split(x, len(leaves), axis=-1)))
