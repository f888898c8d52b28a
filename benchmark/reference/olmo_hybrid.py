"""Plain Olmo-Hybrid in float32: Gated DeltaNet layers beside full attention.

Source of the sizes: ``https://huggingface.co/allenai/Olmo-Hybrid-7B``
(``model_type: olmo_hybrid``).  Its keys ``linear_num_key_heads``,
``linear_key_head_dim``, ``linear_value_head_dim``,
``linear_conv_kernel_dim`` and ``linear_allow_neg_eigval`` are those of the
Gated DeltaNet layer (Yang, Kautz, Hatamizadeh, arXiv:2412.06464; the
``GatedDeltaNet`` layer of flash-linear-attention), whose equations this
file follows.  What the config does not state is taken from OLMo 2
(arXiv:2501.00656) and listed under ``assumed`` in the configuration file:

* the block: ``h = x + RMSNorm(Mixer(x))``, ``y = h + RMSNorm(MLP(h))``,
  ``MLP(x) = W_down (silu(W_gate x) * W_up x)``; a final RMSNorm and an
  untied head;
* full attention: ``q, k, v = W_q x, W_k x, W_v x``, RMSNorm with a gain
  over the whole width of ``q`` and of ``k`` before the split into heads,
  NO rotary embedding (``rope_theta: null``), causal softmax, ``W_o``;
* Gated DeltaNet, ``H`` heads of ``d_k``/``d_v``: ``q, k, v`` each through a
  depthwise causal convolution of width 4 without bias and SiLU; per head
  ``q <- q / |q| * d_k^-1/2``, ``k <- k / |k|`` (eps 1e-6 under the root);
  ``beta = 2 sigmoid(W_b x)``; ``g = -exp(A_log) softplus(W_a x +
  dt_bias)``, ``alpha = exp(g)``; state ``S [d_k, d_v]`` from zero:
  ``S' = alpha S``, ``u = beta (v - S'^T k)``, ``S = S' + k u^T``,
  ``o = S^T q``; ``y = W_o [RMSNorm_{d_v}(o_h) * silu((W_g x)_h)]_h`` with
  one gain of size ``d_v`` shared by the heads.

Nothing here comes from ``paddle_tpu``: no kernel, no cache, no chunking
of the recurrence (it runs token by token under ``lax.scan``), no
batching, no weight.  Departures from a textbook listing, for memory on a
16 GB chip and none in the mathematics: one sequence at a time; the
caller hands the weights over one layer at a time (``layer_params(l)``),
since 4.1 B float32 parameters are 16.4 GB; attention runs by blocks of
query rows; only the rows asked for are projected onto the vocabulary.

``prec`` is the precision of every matrix product, as in ``gpt.py``:
``"f32"`` (float32 at ``highest``: the reference) or ``"fp8"`` (the
control: both operands rounded to e4m3 with one scale per row of the
contraction; the recurrent state itself stays float32, its two read-outs
``S'^T k`` and ``S^T q`` are rounded like any other product).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.gpt import _einsum, _mm

LINEAR, FULL = "linear_attention", "full_attention"
#: query rows per block of the attention
_Q_BLOCK = 256


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def mlp(p, h, prec):
    return _mm(jax.nn.silu(_mm(h, p["w_gate"], prec)) * _mm(h, p["w_up"],
                                                           prec),
               p["w_down"], prec)


def full_attention(p, x, n_heads, eps, prec):
    """``x [T, D]``; causal softmax attention with QK-norm, no positions."""
    T, D = x.shape
    hd = D // n_heads
    q = rms_norm(_mm(x, p["wq"], prec), p["q_g"], eps)
    k = rms_norm(_mm(x, p["wk"], prec), p["k_g"], eps)
    v = _mm(x, p["wv"], prec)
    q, k, v = (t.reshape(T, n_heads, hd) for t in (q, k, v))
    nb = -(-T // _Q_BLOCK)
    qb = jnp.pad(q, ((0, nb * _Q_BLOCK - T), (0, 0), (0, 0))).reshape(
        nb, _Q_BLOCK, n_heads, hd)
    kpos = jnp.arange(T)

    def rows(args):
        qi, first = args
        s = _einsum("qhd,khd->hqk", qi, k, prec, (-1, -1)) / math.sqrt(hd)
        qpos = first + jnp.arange(_Q_BLOCK)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s,
                      -jnp.inf)
        return _einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, prec,
                       (-1, 0))

    o = jax.lax.map(rows, (qb, jnp.arange(nb) * _Q_BLOCK))
    return _mm(o.reshape(nb * _Q_BLOCK, D)[:T], p["wo"], prec)


def causal_conv(x, c):
    """``y_t = sum_i c_i * x_{t-W+1+i}`` per channel, zeros before the
    start: ``x [T, C]``, ``c [W, C]``."""
    W, T = c.shape[0], x.shape[0]
    xp = jnp.pad(x, ((W - 1, 0), (0, 0)))
    return sum(c[i] * xp[i:i + T] for i in range(W))


def gated_delta_net(p, x, H, dk, dv, eps, prec, state_dtype=jnp.float32):
    """``x [T, D]``; the recurrence token by token.  ``state_dtype`` is
    the type the state is kept in between tokens (float32; the tests'
    control passes bfloat16 to show that the tolerance notices)."""
    T = x.shape[0]
    q = jax.nn.silu(causal_conv(_mm(x, p["wq"], prec), p["conv_q"]))
    k = jax.nn.silu(causal_conv(_mm(x, p["wk"], prec), p["conv_k"]))
    v = jax.nn.silu(causal_conv(_mm(x, p["wv"], prec), p["conv_v"]))
    q, k, v = q.reshape(T, H, dk), k.reshape(T, H, dk), v.reshape(T, H, dv)
    unit = lambda t: t * jax.lax.rsqrt(                        # noqa: E731
        jnp.sum(t * t, -1, keepdims=True) + 1e-6)
    q, k = unit(q) * dk ** -0.5, unit(k)
    beta = 2.0 * jax.nn.sigmoid(_mm(x, p["wb"], prec))          # [T, H]
    alpha = jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(
        _mm(x, p["wa"], prec) + p["dt_bias"]))

    def token(S, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        S = a_t[:, None, None] * S.astype(jnp.float32)
        u = b_t[:, None] * (v_t - _einsum("hkv,hk->hv", S, k_t, prec,
                                          (1, -1)))
        S = S + k_t[:, :, None] * u[:, None, :]
        o = _einsum("hkv,hk->hv", S, q_t, prec, (1, -1))
        return S.astype(state_dtype), o

    _, o = jax.lax.scan(token, jnp.zeros((H, dk, dv), state_dtype),
                        (q, k, v, alpha, beta))
    gate = jax.nn.silu(_mm(x, p["wg"], prec)).reshape(T, H, dv)
    o = rms_norm(o, p["o_g"], eps) * gate
    return _mm(o.reshape(T, H * dv), p["wo"], prec)


def layer(p, h, kind, cfg, prec, state_dtype=jnp.float32):
    """One block over ``h [T, D]``; ``cfg`` is the configuration file's
    dictionary (the published keys)."""
    eps = cfg["rms_norm_eps"]
    if kind == FULL:
        a = full_attention(p, h, cfg["num_attention_heads"], eps, prec)
    elif kind == LINEAR:
        a = gated_delta_net(p, h, cfg["linear_num_value_heads"],
                            cfg["linear_key_head_dim"],
                            cfg["linear_value_head_dim"], eps, prec,
                            state_dtype)
    else:
        raise ValueError(f"unknown layer type {kind!r}")
    h = h + rms_norm(a, p["post_mixer_g"], eps)
    return h + rms_norm(mlp(p, h, prec), p["post_mlp_g"], eps)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _layer_jit(p, h, kind, sizes, prec, state_dtype):
    return layer(p, h, kind, dict(sizes), prec, state_dtype)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _head_jit(lnf_g, head, h, first_row, n_rows, eps, prec):
    rows = jax.lax.dynamic_slice_in_dim(h, first_row, n_rows, axis=0)
    return _mm(rms_norm(rows, lnf_g, eps), head, prec)


_SIZES = ("rms_norm_eps", "num_attention_heads", "linear_num_value_heads",
          "linear_key_head_dim", "linear_value_head_dim")


def logits_rows(top, layer_params, cfg, ids, first_row, n_rows, prec,
                state_dtype=jnp.float32):
    """Logits ``[n_rows, V]`` of one sequence ``ids [T]`` from position
    ``first_row`` on; row ``i`` predicts the token at ``first_row + i +
    1``.  ``top`` holds ``wte``, ``lnf_g`` and ``head``; ``layer_params(l)``
    makes layer ``l``'s weights, which are dropped before the next
    layer's are made."""
    sizes = tuple((k, cfg[k]) for k in _SIZES)
    h = top["wte"][jnp.asarray(ids)]
    for l, kind in enumerate(cfg["layer_types"]):
        h = _layer_jit(layer_params(l), h, kind, sizes, prec, state_dtype)
    return _head_jit(top["lnf_g"], top["head"], h, first_row, n_rows,
                     cfg["rms_norm_eps"], prec)
