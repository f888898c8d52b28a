"""Seeded weights of the ``olmo_hybrid`` family, for program and reference.

The same contract as ``weights.py`` (which is GPT's): neither side's
weights come from the program's constructor; both are drawn on the device
from ``--seed``, in the type they are served in, and a leaf's key depends
on the seed, the leaf and the layer alone, so the reference can make ONE
layer's weights at a time (4.1 B float32 parameters would be 16.4 GB) and
get exactly what the program holds.

Leaves carry the names of ``reference/olmo_hybrid.py``.  ``program``
re-deals them into the tensors ``paddle_tpu/models/olmo_hybrid.py`` holds:
stacked over the layers of one kind, ``[q | k | v]`` joined along the
output axis (and the three convolution filters alike), ``[a | b]`` joined.

How a leaf is drawn (``assumed`` in the configuration file): matrices
N(0, ``initializer_range``); norm gains 1; the convolution filters
U(-W^-1/2, W^-1/2), torch's Conv1d default; ``A_log = log A`` with
``A ~ U(0, 16]`` and ``dt_bias`` the inverse softplus of ``dt``
log-uniform in [1e-3, 1e-1], as flash-linear-attention's GatedDeltaNet
initialises them (a decay that is neither 0 nor 1, or the state would
test nothing).  ``A_log`` and ``dt_bias`` stay float32 on both sides.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key

LINEAR, FULL = "linear_attention", "full_attention"


def _dims(c):
    H, dk, dv = (c["linear_num_value_heads"], c["linear_key_head_dim"],
                 c["linear_value_head_dim"])
    return c["hidden_size"], c["intermediate_size"], H * dk, H * dv, H, dv


#: leaf -> (shape from the sizes, how it is drawn); one list for the three
#: groups, so a leaf's index (part of its key) is its place here
_TOP = (
    ("wte", lambda c: (c["vocab_size"], c["hidden_size"]), "normal"),
    ("lnf_g", lambda c: (c["hidden_size"],), "ones"),
    ("head", lambda c: (c["hidden_size"], c["vocab_size"]), "normal"),
)
_COMMON = (
    ("post_mixer_g", lambda c: (_dims(c)[0],), "ones"),
    ("post_mlp_g", lambda c: (_dims(c)[0],), "ones"),
    ("w_gate", lambda c: _dims(c)[:2], "normal"),
    ("w_up", lambda c: _dims(c)[:2], "normal"),
    ("w_down", lambda c: _dims(c)[1::-1], "normal"),
)
_FULL = (
    ("wq", lambda c: (_dims(c)[0],) * 2, "normal"),
    ("wk", lambda c: (_dims(c)[0],) * 2, "normal"),
    ("wv", lambda c: (_dims(c)[0],) * 2, "normal"),
    ("wo", lambda c: (_dims(c)[0],) * 2, "normal"),
    ("q_g", lambda c: (_dims(c)[0],), "ones"),
    ("k_g", lambda c: (_dims(c)[0],), "ones"),
)
_W = "linear_conv_kernel_dim"
_LINEAR = (
    ("wq", lambda c: (_dims(c)[0], _dims(c)[2]), "normal"),
    ("wk", lambda c: (_dims(c)[0], _dims(c)[2]), "normal"),
    ("wv", lambda c: (_dims(c)[0], _dims(c)[3]), "normal"),
    ("wg", lambda c: (_dims(c)[0], _dims(c)[3]), "normal"),
    ("wo", lambda c: (_dims(c)[3], _dims(c)[0]), "normal"),
    ("wa", lambda c: (_dims(c)[0], _dims(c)[4]), "normal"),
    ("wb", lambda c: (_dims(c)[0], _dims(c)[4]), "normal"),
    ("A_log", lambda c: (_dims(c)[4],), "A_log"),
    ("dt_bias", lambda c: (_dims(c)[4],), "dt_bias"),
    ("conv_q", lambda c: (c[_W], _dims(c)[2]), "conv"),
    ("conv_k", lambda c: (c[_W], _dims(c)[2]), "conv"),
    ("conv_v", lambda c: (c[_W], _dims(c)[3]), "conv"),
    ("o_g", lambda c: (_dims(c)[5],), "ones"),
)
_GROUPS = {"top": _TOP, "common": _COMMON, FULL: _FULL, LINEAR: _LINEAR}
_INDEX = {(g, n): i for i, (g, n) in enumerate(
    (g, n) for g, leaves in _GROUPS.items() for n, _, _ in leaves)}

#: program tensor -> (group, the leaves joined along the last axis)
PROGRAM_TENSORS = {
    "wte": ("top", ("wte",)), "lnf_w": ("top", ("lnf_g",)),
    "lm_head": ("top", ("head",)),
    "mixer_norm_w": ("common", ("post_mixer_g",)),
    "mlp_norm_w": ("common", ("post_mlp_g",)),
    "gate_w": ("common", ("w_gate",)), "up_w": ("common", ("w_up",)),
    "down_w": ("common", ("w_down",)),
    "att_qkv_w": (FULL, ("wq", "wk", "wv")), "att_o_w": (FULL, ("wo",)),
    "att_qnorm_w": (FULL, ("q_g",)), "att_knorm_w": (FULL, ("k_g",)),
    "lin_qkv_w": (LINEAR, ("wq", "wk", "wv")),
    "lin_g_w": (LINEAR, ("wg",)), "lin_ab_w": (LINEAR, ("wa", "wb")),
    "lin_o_w": (LINEAR, ("wo",)),
    "lin_conv_w": (LINEAR, ("conv_q", "conv_k", "conv_v")),
    "lin_A_log": (LINEAR, ("A_log",)), "lin_dt_bias": (LINEAR, ("dt_bias",)),
    "lin_norm_w": (LINEAR, ("o_g",)),
}


def sizes(cfg):
    """The sizes a configuration file states, hashable (the static
    argument of the jitted makers)."""
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "linear_num_value_heads", "linear_key_head_dim",
            "linear_value_head_dim", _W)
    return tuple((k, int(cfg[k])) for k in keys) + (
        ("initializer_range", float(cfg["initializer_range"])),
        ("layer_types", tuple(cfg["layer_types"])))


def _draw(key, c, group, name, layer, dtype):
    _, shape_of, how = next(x for x in _GROUPS[group] if x[0] == name)
    shape = shape_of(c)
    key = jax.random.fold_in(jax.random.fold_in(key, _INDEX[group, name]),
                             layer)
    if how == "ones":
        return jnp.ones(shape, dtype)
    if how == "normal":
        x = jax.random.normal(key, shape, jnp.float32)
        return (x * c["initializer_range"]).astype(dtype)
    u = jax.random.uniform(key, shape, jnp.float32)
    if how == "conv":
        bound = c[_W] ** -0.5
        return ((2.0 * u - 1.0) * bound).astype(dtype)
    if how == "A_log":
        return jnp.log(16.0 * (1.0 - u))
    if how == "dt_bias":
        dt = jnp.exp(u * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(how)


@functools.partial(jax.jit, static_argnums=(1, 2, 4))
def _group(key, sz, group, layer, dtype):
    c = dict(sz)
    return {n: _draw(key, c, group, n, layer, dtype)
            for n, _, _ in _GROUPS[group]}


def top(cfg, seed, dtype):
    """``wte``, ``lnf_g``, ``head`` for the reference."""
    return _group(seed_key(seed), sizes(cfg), "top", 0, jnp.dtype(dtype))


def layer(cfg, seed, l, dtype):
    """Layer ``l``'s leaves for the reference, of the kind the
    configuration's ``layer_types`` gives it."""
    sz, key, dt = sizes(cfg), seed_key(seed), jnp.dtype(dtype)
    return {**_group(key, sz, "common", l, dt),
            **_group(key, sz, cfg["layer_types"][l], l, dt)}


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _program_tensor(key, sz, tensor, dtype):
    c = dict(sz)
    group, leaves = PROGRAM_TENSORS[tensor]
    join = lambda l: jnp.concatenate(                          # noqa: E731
        [_draw(key, c, group, n, l, dtype) for n in leaves], -1)
    if group == "top":
        return join(0)
    return jnp.stack([join(l) for l, kind in enumerate(c["layer_types"])
                      if group in ("common", kind)])


def program_tensor(cfg, seed, tensor, dtype):
    """One tensor the program holds, in its layout."""
    return _program_tensor(seed_key(seed), sizes(cfg), tensor,
                           jnp.dtype(dtype))


def program(cfg, seed, dtype):
    """``(tensor, array)`` for every tensor the program holds."""
    for tensor in PROGRAM_TENSORS:
        yield tensor, program_tensor(cfg, seed, tensor, dtype)
