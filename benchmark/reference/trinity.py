"""Plain Trinity (``afmoe``) in float32: window and full attention, a shared
expert and routed experts chosen by sigmoid scores with a bias.

Source of the sizes: ``https://huggingface.co/arcee-ai/Trinity-Large-
Preview`` (``model_type: afmoe``); of the block: transformers'
``modeling_afmoe`` (the placement of the rotary turn, the gate and the four
norms, which the config has no key for, are listed under ``assumed``).
``eps`` and every count are the configuration file's.

* ``x_0 = sqrt(D) Emb[ids]`` where ``mup_enabled``; each layer ``h = x +
  N_pa(Attn(N_in(x)))``, ``x' = h + N_pm(FFN(N_pre(h)))``, every ``N`` an
  RMSNorm with its own gain; a final RMSNorm and an untied head;
* attention, ``H`` query heads over ``n_kv`` K/V heads of ``d``: ``q = W_q
  u``, ``k = W_k u``, ``v = W_v u``, ``g = W_g u``; an RMSNorm over each
  head's ``d`` dims of ``q`` and of ``k`` (one gain each); a WINDOW layer
  turns ``q`` and ``k`` by RoPE (theta, each head's dims as two halves, at
  absolute positions), a FULL layer does not; query head ``h`` reads K/V
  head ``h // (H / n_kv)``; ``s_ij = q_i . k_j / sqrt(d)`` under the mask
  ``j <= i`` (full) or ``i - W < j <= i`` (window); ``a = softmax(s) v``,
  the heads side by side, times ``sigmoid(g)``; ``Attn = W_o a``;
* dense FFN (the first ``num_dense_layers``): ``W_down (silu(W_gate z) *
  W_up z)``;
* expert FFN: ``sigma = sigmoid(W_r z)`` over all routed experts; the top
  ``k`` of ``sigma + b`` chosen (ties to the lower index), each weighed
  ``route_scale sigma_e / (sum of the chosen sigma + 1e-20)``; ``FFN(z) =
  Shared(z) + sum_e w_e E_e(z)``, ``Shared`` one SwiGLU of the shared
  experts' joint width.  Given a share ``(first, held)`` of the experts, the
  router runs over all of them and the sum over the chosen experts in
  ``first .. first + held - 1`` (``model-configs`` guide, section 4).

Nothing here comes from ``paddle_tpu``: no kernel, no cache, no ring, no
batching.  Departures from a textbook listing, for memory and time on a
16 GB chip and none in the mathematics: one sequence at a time; the caller
hands the weights over one layer at a time and the routed experts one at a
time; attention runs by K/V head and blocks of query rows, each block
against the keys its mask can reach (for a window layer the ``W - 1``
before the block's first row and the block itself; for a full layer every
key from the first to the last row of its group of 32 blocks), with an
explicit mask matrix over them; an expert is applied to the rows that
chose it, gathered 2,048 at a time; only the rows asked for are
projected onto the vocabulary.

``prec`` is the precision of every matrix product, as in ``gpt.py``:
``"f32"`` (float32 at ``highest``: the reference) or ``"fp8"`` (the
control: both operands rounded to e4m3 with one scale per row of the
contraction; the router's product too).  ``window=False`` is the window
control: the window layers attend the whole prefix (what a program that
ignored the window would compute).  ``forced`` hands the routers another
run's choices.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import gpt as _gpt

#: query rows per block of the attention
_Q_BLOCK = 256
#: blocks a full layer takes against one span of keys
_GROUP = 32
#: rows an expert is applied to at a time
_ROWS = 2048


def _einsum(spec, a, b, prec, axes):
    return _gpt._einsum(spec, a, b, prec, axes)


def _mm(x, w, prec):
    return _einsum("...i,io->...o", x, w, prec, (-1, 0))


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, theta):
    """``x [T, heads, d]`` at positions ``0 .. T - 1``."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def attention(p, u, rotary, window, sizes, prec):
    """``u [T, D]`` (already normalised) -> ``W_o (softmax(s) v *
    sigmoid(g))``.  ``rotary``: whether queries and keys are turned;
    ``window``: the number of keys a query sees, or ``None`` for causal
    attention over the whole prefix."""
    cfg = dict(sizes)
    T = u.shape[0]
    H, N, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    G, eps = H // N, cfg["rms_norm_eps"]
    q = rms_norm(_mm(u, p["w_q"], prec).reshape(T, H, d), p["q_g"], eps)
    k = rms_norm(_mm(u, p["w_k"], prec).reshape(T, N, d), p["k_g"], eps)
    v = _mm(u, p["w_v"], prec).reshape(T, N, d)
    if rotary:
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    q = q / math.sqrt(d)
    nb = -(-T // _Q_BLOCK)
    Tp = nb * _Q_BLOCK
    # a window layer's keys are padded at the front by the ``window - 1``
    # a block's first row can reach back
    lead = 0 if window is None else window - 1
    pad = lambda t: jnp.pad(                                   # noqa: E731
        t, ((lead, Tp - T), (0, 0), (0, 0)))
    # by K/V head: [N, Tp + lead, d] keys, [N, nb, Q, G, d] queries
    kp, vp = jnp.moveaxis(pad(k), 1, 0), jnp.moveaxis(pad(v), 1, 0)
    qb = jnp.moveaxis(jnp.pad(q, ((0, Tp - T), (0, 0), (0, 0))).reshape(
        nb, _Q_BLOCK, N, G, d), 2, 0)

    def blocks(lo, hi, span):
        """Query blocks ``lo .. hi - 1`` of every K/V head, each against
        ``span`` keys: from its first row's ``window - 1`` before it (a
        window layer), or from the first key (a full layer)."""
        def head(args):
            qh, kh, vh = args

            def rows(args):
                qq, first = args                           # [Q, G, d]
                k0 = 0 if window is None else first
                kk = jax.lax.dynamic_slice_in_dim(kh, k0, span, 0)
                vv = jax.lax.dynamic_slice_in_dim(vh, k0, span, 0)
                qpos = first + jnp.arange(_Q_BLOCK)
                kpos = k0 - lead + jnp.arange(span)
                mask = ((kpos[None, :] <= qpos[:, None])
                        & (kpos[None, :] >= 0))
                if window is not None:
                    mask = mask & (kpos[None, :] > qpos[:, None] - window)
                s = _einsum("qgd,kd->gqk", qq, kk, prec, (-1, -1))
                s = jnp.where(mask, s, -jnp.inf)
                return _einsum("gqk,kd->qgd", jax.nn.softmax(s, -1), vv,
                               prec, (-1, 0))

            return jax.lax.map(rows, (qh[lo:hi],
                                      jnp.arange(lo, hi) * _Q_BLOCK))

        return jax.lax.map(head, (qb, kp, vp))         # [N, n, Q, G, d]

    if window is None:
        # a full layer's block reaches no key past its own last row: the
        # blocks go in groups, each group against the keys up to its end
        a = jnp.concatenate(
            [blocks(lo, min(lo + _GROUP, nb),
                    min(lo + _GROUP, nb) * _Q_BLOCK)
             for lo in range(0, nb, _GROUP)], 1)
    else:
        a = blocks(0, nb, window - 1 + _Q_BLOCK)
    a = jnp.moveaxis(a, 0, 2).reshape(Tp, H * d)[:T]
    a = a * jax.nn.sigmoid(_mm(u, p["w_g"], prec))
    return _mm(a, p["w_o"], prec)


def swiglu(z, w_gate, w_up, w_down, prec):
    return _mm(jax.nn.silu(_mm(z, w_gate, prec)) * _mm(z, w_up, prec),
               w_down, prec)


def route(p, z, sizes, prec, forced=None):
    """``(expert [T, k] int32, weight [T, k])`` over ALL the routed
    experts.  With ``forced [T, k]`` those experts, weighed by this
    router's own scores."""
    cfg = dict(sizes)
    sigma = jax.nn.sigmoid(_mm(z, p["w_router"], prec))
    if forced is None:
        _, expert = jax.lax.top_k(sigma + p["bias"],
                                  cfg["num_experts_per_tok"])
    else:
        expert = forced
    chosen = jnp.take_along_axis(sigma, expert, -1)
    return expert, (cfg["route_scale"] * chosen
                    / (chosen.sum(-1, keepdims=True) + 1e-20))


_SIZES = ("rms_norm_eps", "num_attention_heads", "num_key_value_heads",
          "head_dim", "rope_theta", "num_experts_per_tok", "route_scale")


def _sizes(cfg):
    return tuple((k, cfg[k]) for k in _SIZES)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _attention_jit(p, h, rotary, window, sizes, prec):
    eps = dict(sizes)["rms_norm_eps"]
    h = h + rms_norm(attention(p, rms_norm(h, p["attn_in_g"], eps), rotary,
                               window, sizes, prec), p["attn_post_g"], eps)
    return h, rms_norm(h, p["ffn_pre_g"], eps)


@functools.partial(jax.jit, static_argnums=(2,))
def _dense_jit(p, z, prec):
    return swiglu(z, p["w_gate"], p["w_up"], p["w_down"], prec)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _shared_jit(p, z, sizes, prec, forced=None):
    expert, weight = route(p, z, sizes, prec, forced)
    return (swiglu(z, p["sh_gate"], p["sh_up"], p["sh_down"], prec),
            expert, weight)


@functools.partial(jax.jit, static_argnums=(7,), donate_argnums=(1,))
def _expert_jit(ex, f, z, expert, weight, e, at, prec):
    """``f + w_e E_e(z)`` over the ``_ROWS`` rows that chose expert ``e``
    from the ``at``-th of them on (places past the last weigh 0)."""
    hit = expert == e
    w = jnp.sum(jnp.where(hit, weight, 0.0), -1)
    chose = hit.any(-1)
    place = at + jnp.arange(_ROWS)
    rows = jnp.argsort(~chose, stable=True)[
        jnp.minimum(place, z.shape[0] - 1)]
    w = jnp.where(place < chose.sum(), w[rows], 0.0)
    y = swiglu(z[rows], ex["ex_gate"], ex["ex_up"], ex["ex_down"], prec)
    return f.at[rows].add(w[:, None] * y)


@functools.partial(jax.jit, static_argnums=(3,))
def _post_jit(p, h, f, eps):
    return h + rms_norm(f, p["ffn_post_g"], eps)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _head_jit(lnf_g, head, h, first_row, n_rows, eps, prec):
    rows = jax.lax.dynamic_slice_in_dim(h, first_row, n_rows, axis=0)
    return _mm(rms_norm(rows, lnf_g, eps), head, prec)


def layer(p, experts, h, l, cfg, held, prec, window=True, forced=None):
    """One block over ``h [T, D]``.  ``experts(e)`` makes routed expert
    ``e``'s three matrices; ``held = (first, count)`` is the share whose
    part of the sum is computed; ``window=False`` lets a window layer see
    the whole prefix; ``forced [T, k]`` takes the router's place.  Returns
    ``(h, expert [T, k] or None)``: the choices over all the experts."""
    sizes = _sizes(cfg)
    eps = cfg["rms_norm_eps"]
    sliding = cfg["layer_types"][l] == "sliding_attention"
    h, z = _attention_jit(p, h, sliding, cfg["sliding_window"]
                          if sliding and window else None, sizes, prec)
    if l < cfg["num_dense_layers"]:
        return _post_jit(p, h, _dense_jit(p, z, prec), eps), None
    f, expert, weight = _shared_jit(p, z, sizes, prec, forced)
    chose = np.asarray(expert)
    for e in range(held[0], held[0] + held[1]):
        n = int((chose == e).any(-1).sum())
        ex = experts(e) if n else None
        for at in range(0, n, _ROWS):
            f = _expert_jit(ex, f, z, expert, weight, jnp.int32(e),
                            jnp.int32(at), prec)
    return _post_jit(p, h, f, eps), expert


def logits_rows(top, layer_params, expert_params, cfg, held, ids, first_row,
                n_rows, prec, forced=None, window=True):
    """Logits ``[n_rows, V]`` of one sequence ``ids [T]`` from position
    ``first_row`` on (row ``i`` predicts the token at ``first_row + i +
    1``), and the routers' choices ``[expert layers, T, k]``.  ``top``
    holds ``wte``, ``lnf_g`` and ``head``; ``layer_params(l)`` makes layer
    ``l``'s weights without its routed experts and ``expert_params(l, e)``
    one of those; each is dropped before the next is made.  ``window=False``
    is the window control; ``forced`` (another run's choices) takes the
    routers' place."""
    h = top["wte"][jnp.asarray(ids)]
    if cfg.get("mup_enabled"):
        h = h * math.sqrt(cfg["hidden_size"])
    chosen = []
    for l in range(cfg["num_hidden_layers"]):
        h, expert = layer(layer_params(l),
                          functools.partial(expert_params, l), h, l, cfg,
                          held, prec, window,
                          None if forced is None else forced[len(chosen)])
        if expert is not None:
            chosen.append(expert)
    return (_head_jit(top["lnf_g"], top["head"], h, first_row, n_rows,
                      cfg["rms_norm_eps"], prec), jnp.stack(chosen))
