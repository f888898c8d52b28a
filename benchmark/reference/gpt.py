"""Plain GPT-3 (Brown et al. 2020, arXiv:2005.14165, section 2.1) in float32.

Dense pre-LayerNorm decoder as GPT-2 released it and GPT-3 reuses it:
token + learned position embedding; per layer ``h += Attn(LN(h))`` then
``h += W2 gelu(W1 LN(h))`` with causal softmax attention over ``n_heads``
heads and the tanh GELU; a final LayerNorm; logits through the transposed
token embedding.  Training loss is the mean next-token cross-entropy, and
the optimizer is AdamW (Loshchilov & Hutter 2019) with bias correction.

Nothing here comes from ``paddle_tpu``: no kernel, no cache, no batching,
no weight.  Departures from a textbook listing, all for memory on a 16 GB
chip and none in the mathematics: the training step walks the layers one
at a time (forward keeps each layer's input, backward takes each layer's
``jax.vjp`` and applies AdamW to that layer at once, so no second copy of
the gradients exists), and the loss head runs one sequence at a time.

``prec`` is the precision of every matrix product:

* ``"f32"`` — float32 at ``highest`` (on a TPU a float32 product is
  otherwise computed in fewer passes).  This is the reference.
* ``"fp8"`` — the control: both operands rounded to 8-bit floats (e4m3:
  three bits of mantissa), one scale per row of the contraction (dynamic
  per-token activations, per-channel weights), straight-through in the
  backward pass.  The rounding is written out in float32 arithmetic, so it
  is the same on every backend.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("ln1_g", "ln1_b", "wq", "wk", "wv", "bq", "bk", "bv", "wo",
                "bo", "ln2_g", "ln2_b", "w1", "b1", "w2", "b2")
_HI = jax.lax.Precision.HIGHEST


def _fp8(x, axis):
    """Round to e4m3 (largest value 448, three bits of mantissa, smallest
    normal 2**-6) after scaling each row of the contraction ``axis`` so
    that its largest magnitude is 448."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    y = x / s
    step = jnp.exp2(jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 2.0 ** -6)))
                    - 3.0)
    return x + jax.lax.stop_gradient(jnp.round(y / step) * step * s - x)


def _einsum(spec, a, b, prec, axes):
    """``jnp.einsum`` of two operands whose contraction axes are ``axes``
    (one per operand), in precision ``prec``."""
    if prec == "fp8":
        a, b = _fp8(a, axes[0]), _fp8(b, axes[1])
    elif prec != "f32":
        raise ValueError(f"unknown precision {prec!r}")
    return jnp.einsum(spec, a, b, precision=_HI)


def _mm(x, w, prec):
    return _einsum("...i,io->...o", x, w, prec, (-1, 0))


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(p, h, n_heads, eps, prec):
    """One decoder layer; ``p`` holds that layer's leaves, ``h [B, S, D]``."""
    B, S, D = h.shape
    hd = D // n_heads
    x = layer_norm(h, p["ln1_g"], p["ln1_b"], eps)

    def heads(w, b):
        return (_mm(x, w, prec) + b).reshape(B, S, n_heads, hd)

    q, k, v = heads(p["wq"], p["bq"]), heads(p["wk"], p["bk"]), heads(
        p["wv"], p["bv"])
    s = _einsum("bqhd,bkhd->bhqk", q, k, prec, (-1, -1)) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = _einsum("bhqk,bkhd->bqhd", a, v, prec, (-1, 1)).reshape(B, S, D)
    h = h + _mm(o, p["wo"], prec) + p["bo"]
    x = layer_norm(h, p["ln2_g"], p["ln2_b"], eps)
    return h + _mm(gelu(_mm(x, p["w1"], prec) + p["b1"]), p["w2"],
                   prec) + p["b2"]


def embed(wte, wpe, ids):
    return wte[ids] + wpe[: ids.shape[1]]


def head(wte, lnf_g, lnf_b, h, eps, prec):
    """Final norm and the tied output projection: ``[.., D] -> [.., V]``."""
    return _einsum("...d,vd->...v", layer_norm(h, lnf_g, lnf_b, eps), wte,
                   prec, (-1, -1))


def _layer(params, l):
    return {n: params[n][l] for n in LAYER_LEAVES}


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _block_fwd(p, h, n_heads, eps, prec):
    return block(p, h, n_heads, eps, prec)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _block_bwd(p, h, dh, n_heads, eps, prec):
    _, vjp = jax.vjp(lambda pp, hh: block(pp, hh, n_heads, eps, prec), p, h)
    return vjp(dh)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def logits_rows(params, ids, first_row, n_rows, n_heads, eps, prec):
    """Logits ``[n_rows, V]`` of one sequence ``ids [T]`` from position
    ``first_row`` on; row ``i`` predicts the token at ``first_row + i + 1``.
    Other rows are not projected (the serving check needs the generated
    positions only)."""
    layers = {n: params[n] for n in LAYER_LEAVES}
    h = embed(params["wte"], params["wpe"], ids[None])
    h, _ = jax.lax.scan(
        lambda hh, p: (block(p, hh, n_heads, eps, prec), None), h, layers)
    rows = jax.lax.dynamic_slice_in_dim(h[0], first_row, n_rows, axis=0)
    return head(params["wte"], params["lnf_g"], params["lnf_b"], rows, eps,
                prec)


# ---------------------------------------------------------------------------
# training: loss, gradients and AdamW, one layer at a time
# ---------------------------------------------------------------------------
def _seq_loss(top, h, labels, eps, prec, n_tokens):
    """One sequence's share of the mean cross-entropy."""
    lg = head(top["wte"], top["lnf_g"], top["lnf_b"], h, eps, prec)
    picked = jnp.take_along_axis(lg, labels[:, None], -1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(lg, -1) - picked) / n_tokens


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _seq_loss_grad(top, h, labels, eps, prec, n_tokens):
    return jax.value_and_grad(_seq_loss, argnums=(0, 1))(
        top, h, labels, eps, prec, n_tokens)


@jax.jit
def _embed_fwd(wte, wpe, ids):
    return embed(wte, wpe, ids)


@jax.jit
def _embed_bwd(g_wte, dh, ids):
    """Adds the embedding's gradient to the head's (the matrix is tied)."""
    return (g_wte.at[ids.reshape(-1)].add(dh.reshape(-1, dh.shape[-1])),
            jnp.sum(dh, 0))


def _adamw(p, g, m, v, t, o):
    m = o["beta1"] * m + (1 - o["beta1"]) * g
    v = o["beta2"] * v + (1 - o["beta2"]) * g * g
    mhat = m / (1 - o["beta1"] ** t)
    vhat = v / (1 - o["beta2"] ** t)
    p = p * (1 - o["learning_rate"] * o["weight_decay"]) \
        - o["learning_rate"] * mhat / (jnp.sqrt(vhat) + o["epsilon"])
    return p, m, v


@functools.partial(jax.jit, static_argnums=(5,), donate_argnums=(0, 1, 2))
def _adamw_layer(params, m, v, g, lt, opt):
    """AdamW on layer ``lt[0]``'s slice of every stacked leaf, in place."""
    l, t = lt
    opt = dict(opt)
    for n, gl in g.items():
        pn, mn, vn = _adamw(params[n][l], gl, m[n][l], v[n][l], t, opt)
        params[n] = params[n].at[l].set(pn)
        m[n] = m[n].at[l].set(mn)
        v[n] = v[n].at[l].set(vn)
    return params, m, v


@functools.partial(jax.jit, static_argnums=(5,), donate_argnums=(0, 1, 2))
def _adamw_top(params, m, v, g, t, opt):
    opt = dict(opt)
    for n, gn in g.items():
        params[n], m[n], v[n] = _adamw(params[n], gn, m[n], v[n], t, opt)
    return params, m, v


def _norms(tree):
    return {n: jnp.sqrt(jnp.sum(x.astype(jnp.float32) ** 2))
            for n, x in tree.items()}


class Trainer:
    """The reference training run: float32 parameters and AdamW moments,
    stepped on the batches it is given.  ``grad_norms[i]`` is
    ``{leaf: norm}`` of step ``i``'s gradient, a layer leaf's norm being
    the vector of its per-layer norms; ``losses[i]`` its loss."""

    def __init__(self, params, n_heads, eps, opt, prec="f32"):
        self.params = {n: jnp.asarray(x, jnp.float32)
                       for n, x in params.items()}
        self.m = {n: jnp.zeros_like(x) for n, x in self.params.items()}
        self.v = {n: jnp.zeros_like(x) for n, x in self.params.items()}
        self.n_heads, self.eps, self.prec = int(n_heads), float(eps), prec
        self.opt = tuple(sorted((k, float(x)) for k, x in opt.items()
                                if k != "name"))
        self.t = 0
        self.losses, self.grad_norms = [], []

    def step(self, ids, labels):
        ids, labels = jnp.asarray(ids), jnp.asarray(labels)
        args = (self.n_heads, self.eps, self.prec)
        p = self.params
        n_layers = p["wq"].shape[0]
        self.t += 1
        hs = [_embed_fwd(p["wte"], p["wpe"], ids)]
        for l in range(n_layers):
            hs.append(_block_fwd(_layer(p, l), hs[-1], *args))
        top = {n: p[n] for n in ("wte", "lnf_g", "lnf_b")}
        loss, g_top, dh = 0.0, None, []
        for b in range(ids.shape[0]):
            lb, (gt, dhb) = _seq_loss_grad(top, hs[-1][b], labels[b],
                                           self.eps, self.prec, ids.size)
            loss = loss + lb
            g_top = gt if g_top is None else jax.tree_util.tree_map(
                jnp.add, g_top, gt)
            dh.append(dhb)
        dh = jnp.stack(dh)
        hs.pop()
        norms = {n: [None] * n_layers for n in LAYER_LEAVES}
        for l in reversed(range(n_layers)):
            g, dh = _block_bwd(_layer(self.params, l), hs.pop(), dh, *args)
            for n, x in _norms(g).items():
                norms[n][l] = x
            self.params, self.m, self.v = _adamw_layer(
                self.params, self.m, self.v, g,
                (jnp.int32(l), jnp.float32(self.t)), self.opt)
        g_top["wte"], g_top["wpe"] = _embed_bwd(g_top["wte"], dh, ids)
        norms = {n: jnp.stack(x) for n, x in norms.items()}
        norms.update(_norms(g_top))
        self.params, self.m, self.v = _adamw_top(
            self.params, self.m, self.v, g_top, jnp.float32(self.t),
            self.opt)
        self.losses.append(float(loss))
        self.grad_norms.append(jax.device_get(norms))
        return self.losses[-1]
