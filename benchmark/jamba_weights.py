"""Seeded weights of the ``jamba`` family, for program and reference.

The same contract as ``weights.py`` and ``trinity_weights.py``: neither
side's weights come from the program's constructor; both are drawn on the
device from ``--seed``, in the type they are served in, and a leaf's key
depends on the seed, the leaf and the layer alone, so the reference can
make ONE layer's weights at a time and get exactly what the program holds.

Leaves: every layer's ``in_norm_g``, ``ff_norm_g`` (the two pre-norms'
gains) and gated MLP ``w_gate``, ``w_up``, ``w_down``; an attention
layer's ``wq``, ``wk``, ``wv``, ``wo``; a Mamba layer's ``w_in_x``,
``w_in_z`` (the two halves of ``W_in``), ``conv_w``, ``conv_b``, ``w_x``
(``E -> R + 2N``), ``dt_g``, ``b_g``, ``c_g`` (the inner norms' gains),
``w_dt``, ``b_dt``, ``A_log`` (``[N, E]``, channels last as the program
holds it), ``D``, ``w_out``; on top ``wte`` (the tied head) and
``lnf_g``.  ``program_tensor`` re-deals them into the tensors
``paddle_tpu/models/jamba.py`` holds: stacked over the layers of one kind,
``gu_w = [w_gate | w_up]``, ``qkv_w = [wq | wk | wv]``, ``in_w = [w_in_x |
w_in_z]``.

How a leaf is drawn (``assumed`` in the configuration file): matrices
N(0, ``initializer_range``); norm gains 1; the convolution's filter and
bias U(-k, k) with k = ``mamba_d_conv``^-1/2, torch's Conv1d default for a
depthwise filter; Mamba's own ``A_log[n, e] = log(n + 1)``, ``D = 1`` and
``b_dt`` the inverse softplus of ``dt`` log-uniform in [1e-3, 1e-1].
``A_log``, ``b_dt`` and ``D`` stay float32 on both sides.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key

MAMBA, ATTN = "mamba", "attention"

_D = lambda c: c["hidden_size"]                            # noqa: E731
_E = lambda c: c["mamba_expand"] * c["hidden_size"]        # noqa: E731
_N = lambda c: c["mamba_d_state"]                          # noqa: E731
_R = lambda c: c["mamba_dt_rank"]                          # noqa: E731
_F = lambda c: c["intermediate_size"]                      # noqa: E731
_HD = lambda c: c["hidden_size"] // c["num_attention_heads"]  # noqa: E731

#: leaf -> (shape from the sizes, how it is drawn); one list for all
#: groups, so a leaf's index (part of its key) is its place here
_TOP = (
    ("wte", lambda c: (c["vocab_size"], _D(c)), "normal"),
    ("lnf_g", lambda c: (_D(c),), "ones"),
)
_COMMON = (
    ("in_norm_g", lambda c: (_D(c),), "ones"),
    ("ff_norm_g", lambda c: (_D(c),), "ones"),
    ("w_gate", lambda c: (_D(c), _F(c)), "normal"),
    ("w_up", lambda c: (_D(c), _F(c)), "normal"),
    ("w_down", lambda c: (_F(c), _D(c)), "normal"),
)
_ATTN = (
    ("wq", lambda c: (_D(c), c["num_attention_heads"] * _HD(c)), "normal"),
    ("wk", lambda c: (_D(c), c["num_key_value_heads"] * _HD(c)), "normal"),
    ("wv", lambda c: (_D(c), c["num_key_value_heads"] * _HD(c)), "normal"),
    ("wo", lambda c: (c["num_attention_heads"] * _HD(c), _D(c)), "normal"),
)
_MAMBA = (
    ("w_in_x", lambda c: (_D(c), _E(c)), "normal"),
    ("w_in_z", lambda c: (_D(c), _E(c)), "normal"),
    ("conv_w", lambda c: (c["mamba_d_conv"], _E(c)), "conv"),
    ("conv_b", lambda c: (_E(c),), "conv"),
    ("w_x", lambda c: (_E(c), _R(c) + 2 * _N(c)), "normal"),
    ("dt_g", lambda c: (_R(c),), "ones"),
    ("b_g", lambda c: (_N(c),), "ones"),
    ("c_g", lambda c: (_N(c),), "ones"),
    ("w_dt", lambda c: (_R(c), _E(c)), "normal"),
    ("b_dt", lambda c: (_E(c),), "dt_bias"),
    ("A_log", lambda c: (_N(c), _E(c)), "A_log"),
    ("D", lambda c: (_E(c),), "skip"),
    ("w_out", lambda c: (_E(c), _D(c)), "normal"),
)
_GROUPS = {"top": _TOP, "common": _COMMON, ATTN: _ATTN, MAMBA: _MAMBA}
_INDEX = {(g, n): i for i, (g, n) in enumerate(
    (g, n) for g, leaves in _GROUPS.items() for n, _, _ in leaves)}
#: drawn float32 whatever the served type
_FLOAT32 = ("dt_bias", "A_log", "skip")

#: program tensor -> (group, the leaves joined along the last axis)
PROGRAM_TENSORS = {
    "wte": ("top", ("wte",)), "lnf_w": ("top", ("lnf_g",)),
    "mixer_norm_w": ("common", ("in_norm_g",)),
    "mlp_norm_w": ("common", ("ff_norm_g",)),
    "gu_w": ("common", ("w_gate", "w_up")),
    "down_w": ("common", ("w_down",)),
    "qkv_w": (ATTN, ("wq", "wk", "wv")), "o_w": (ATTN, ("wo",)),
    "in_w": (MAMBA, ("w_in_x", "w_in_z")),
    "conv_w": (MAMBA, ("conv_w",)), "conv_b": (MAMBA, ("conv_b",)),
    "x_w": (MAMBA, ("w_x",)), "dt_norm_w": (MAMBA, ("dt_g",)),
    "b_norm_w": (MAMBA, ("b_g",)), "c_norm_w": (MAMBA, ("c_g",)),
    "dt_w": (MAMBA, ("w_dt",)), "dt_b": (MAMBA, ("b_dt",)),
    "A_log": (MAMBA, ("A_log",)), "D": (MAMBA, ("D",)),
    "out_w": (MAMBA, ("w_out",)),
}


def layer_types(cfg):
    """Each layer's kind, from the offset and period of the attention
    layers (``assumed`` in the configuration file)."""
    off, per = cfg["attn_layer_offset"], cfg["attn_layer_period"]
    return tuple(ATTN if i % per == off else MAMBA
                 for i in range(cfg["num_hidden_layers"]))


def sizes(cfg):
    """The sizes a configuration file states, hashable (the static
    argument of the jitted makers)."""
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_attention_heads", "num_key_value_heads", "mamba_d_state",
            "mamba_d_conv", "mamba_expand", "mamba_dt_rank")
    return tuple((k, int(cfg[k])) for k in keys) + (
        ("initializer_range", float(cfg["initializer_range"])),)


def parameters(cfg):
    """How many parameters the configuration's program holds: ``{"top",
    "common" (a layer's norms and MLP), "attention" (an attention
    mixer), "mamba" (a Mamba mixer), "total"}``."""
    c = dict(sizes(cfg))
    count = lambda leaves: sum(                            # noqa: E731
        math.prod(shape(c)) for _, shape, _ in leaves)
    out = {g: count(leaves) for g, leaves in _GROUPS.items()}
    kinds = layer_types(cfg)
    out["total"] = (out["top"] + len(kinds) * out["common"]
                    + kinds.count(ATTN) * out[ATTN]
                    + kinds.count(MAMBA) * out[MAMBA])
    return out


def _draw(key, c, group, name, layer, dtype):
    _, shape_of, how = next(x for x in _GROUPS[group] if x[0] == name)
    shape = shape_of(c)
    if how == "ones":
        return jnp.ones(shape, dtype)
    if how == "skip":
        return jnp.ones(shape, jnp.float32)
    if how == "A_log":
        n = jnp.arange(1, shape[0] + 1, dtype=jnp.float32)[:, None]
        return jnp.broadcast_to(jnp.log(n), shape)
    key = jax.random.fold_in(jax.random.fold_in(key, _INDEX[group, name]),
                             layer)
    if how == "normal":
        x = jax.random.normal(key, shape, jnp.float32)
        return (x * c["initializer_range"]).astype(dtype)
    u = jax.random.uniform(key, shape, jnp.float32)
    if how == "conv":
        bound = c["mamba_d_conv"] ** -0.5
        return ((2.0 * u - 1.0) * bound).astype(dtype)
    if how == "dt_bias":
        dt = jnp.exp(u * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(how)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 5))
def _leaf(key, sz, group, name, layer, dtype):
    """One leaf of one layer (``layer`` traced: one compile makes them
    all)."""
    return _draw(key, dict(sz), group, name, layer, dtype)


def _group(cfg, seed, group, l, dtype):
    sz, key = sizes(cfg), seed_key(seed)
    return {n: _leaf(key, sz, group, n, jnp.int32(l), jnp.dtype(dtype))
            for n, _, _ in _GROUPS[group]}


def top(cfg, seed, dtype):
    """``wte`` and ``lnf_g`` for the reference."""
    return _group(cfg, seed, "top", 0, dtype)


def layer(cfg, seed, l, dtype):
    """Layer ``l``'s leaves for the reference: its norms and MLP, and the
    mixer of the kind the offset and period give it."""
    return {**_group(cfg, seed, "common", l, dtype),
            **_group(cfg, seed, layer_types(cfg)[l], l, dtype)}


@functools.partial(jax.jit, donate_argnums=0)
def _put_layer(stacked, one, j):
    return jax.lax.dynamic_update_index_in_dim(stacked, one, j, 0)


def program_tensor(cfg, seed, tensor, dtype):
    """One tensor the program holds, in its layout, made one layer at a
    time into a buffer that is handed on (a jitted draw of a whole stack
    would need its float32 size again beside it); a tensor of several
    leaves is joined outside the jit."""
    sz, dt, key = sizes(cfg), jnp.dtype(dtype), seed_key(seed)
    group, leaves = PROGRAM_TENSORS[tensor]

    def one(l):
        parts = [_leaf(key, sz, group, n, jnp.int32(l), dt) for n in leaves]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, -1)

    if group == "top":
        return one(0)
    layers = [l for l, kind in enumerate(layer_types(cfg))
              if group in ("common", kind)]
    out = None
    for j, l in enumerate(layers):
        x = one(l)
        if out is None:
            out = jnp.zeros((len(layers),) + x.shape, x.dtype)
        out = _put_layer(out, x, jnp.int32(j))
    return out
