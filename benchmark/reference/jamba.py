"""Plain Jamba in float32: Mamba-1 layers beside attention layers with one
K/V head.

Source of the sizes: ``https://huggingface.co/ai21labs/AI21-Jamba2-3B``
(``model_type: jamba``).  Its Mamba keys (``mamba_d_state``,
``mamba_d_conv``, ``mamba_expand``, ``mamba_dt_rank``, ``mamba_conv_bias``,
``mamba_proj_bias``) are those of the Mamba-1 layer (Gu and Dao,
arXiv:2312.00752) with Jamba's inner RMSNorms on ``dt``, ``B`` and ``C``
(Lieber et al., arXiv:2403.19887; the ``JambaMambaMixer`` of Hugging Face
transformers), whose equations this file follows; what the config does not
state is listed under ``assumed`` in the configuration file:

* the block: ``r = x + Mixer(RMSNorm(x))``, ``y = r + MLP(RMSNorm(r))``,
  ``MLP(h) = (silu(h W_gate) * h W_up) W_down``; a final RMSNorm; logits
  ``h wte^T`` (the head tied to the embedding);
* layer ``i`` is attention where ``i % attn_layer_period ==
  attn_layer_offset``, Mamba otherwise; ``num_experts`` 1 is a dense MLP
  in every layer;
* attention: ``q = x W_q`` (``H`` heads), ``k = x W_k``, ``v = x W_v``
  (``n_kv`` heads; query head ``h`` reads K/V head ``h // (H / n_kv)``),
  NO positional encoding, causal softmax of ``q k^T / sqrt(hd)``, ``W_o``;
* Mamba: ``x = u W_in_x``, ``z = u W_in_z``; ``x <- silu(conv_b + sum_i
  conv_w[i] x_{t-W+1+i})`` (depthwise, causal, zeros before the start);
  ``[d ; B ; C] = x W_x``, each through an RMSNorm with its own gain;
  ``dt = softplus(d W_dt + b_dt)``; ``A = -exp(A_log)``; from a zero state
  ``h_t[n, e] = exp(dt_t[e] A[n, e]) h_{t-1}[n, e] + dt_t[e] x_t[e]
  B_t[n]``, ``y_t[e] = sum_n C_t[n] h_t[n, e] + D[e] x_t[e]``; ``(y *
  silu(z)) W_out``.

Nothing here comes from ``paddle_tpu``: no kernel, no cache, no chunking,
no batching, no weight; the recurrence runs token by token under
``lax.scan``.  Departures from a textbook listing, for memory on a 16 GB
chip and none in the mathematics: one sequence at a time; the caller hands
the weights over one layer at a time (``layer_params(l)``); attention runs
by blocks of query rows; only the rows asked for are projected onto the
vocabulary; ``A_log`` is laid out ``[N, E]`` (the published parameter is
``[E, N]``; the same numbers).

``prec`` is the precision of every matrix product, as in ``gpt.py``:
``"f32"`` (float32 at ``highest``: the reference) or ``"fp8"`` (the
control: both operands rounded to e4m3 with one scale per row of the
contraction).  ``state_dtype`` is the type the scan keeps its state in
between tokens (float32; ``bfloat16`` is the control a state stored in
half the bytes would serve).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.jamba_weights import ATTN, MAMBA, layer_types
from benchmark.reference.gpt import _einsum, _mm

#: query rows per block of the attention
_Q_BLOCK = 256


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def mlp(p, h, prec):
    return _mm(jax.nn.silu(_mm(h, p["w_gate"], prec)) * _mm(h, p["w_up"],
                                                           prec),
               p["w_down"], prec)


def attention(p, x, n_heads, n_kv, prec):
    """``x [T, D]``; causal softmax attention, no positions."""
    T, D = x.shape
    hd = D // n_heads
    G = n_heads // n_kv
    q = _mm(x, p["wq"], prec).reshape(T, n_kv, G, hd)
    k = _mm(x, p["wk"], prec).reshape(T, n_kv, hd)
    v = _mm(x, p["wv"], prec).reshape(T, n_kv, hd)
    nb = -(-T // _Q_BLOCK)
    qb = jnp.pad(q, ((0, nb * _Q_BLOCK - T),) + ((0, 0),) * 3).reshape(
        nb, _Q_BLOCK, n_kv, G, hd)
    kpos = jnp.arange(T)

    def rows(args):
        qi, first = args
        s = _einsum("qngd,knd->ngqk", qi, k, prec, (-1, -1)) / math.sqrt(hd)
        qpos = first + jnp.arange(_Q_BLOCK)
        s = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        return _einsum("ngqk,knd->qngd", jax.nn.softmax(s, axis=-1), v, prec,
                       (-1, 0))

    o = jax.lax.map(rows, (qb, jnp.arange(nb) * _Q_BLOCK))
    return _mm(o.reshape(nb * _Q_BLOCK, n_heads * hd)[:T], p["wo"], prec)


def causal_conv(x, w, b):
    """``y_t = b + sum_i w_i * x_{t-W+1+i}`` per channel, zeros before the
    start: ``x [T, E]``, ``w [W, E]``, ``b [E]``."""
    W, T = w.shape[0], x.shape[0]
    xp = jnp.pad(x, ((W - 1, 0), (0, 0)))
    return b + sum(w[i] * xp[i:i + T] for i in range(W))


def mamba(p, u, R, N, eps, prec, state_dtype=jnp.float32):
    """``u [T, D]``; the selective scan token by token."""
    x = jax.nn.silu(causal_conv(_mm(u, p["w_in_x"], prec), p["conv_w"],
                                p["conv_b"]))
    z = _mm(u, p["w_in_z"], prec)
    dbc = _mm(x, p["w_x"], prec)
    d, B, C = dbc[:, :R], dbc[:, R:R + N], dbc[:, R + N:]
    d = rms_norm(d, p["dt_g"], eps)
    B, C = rms_norm(B, p["b_g"], eps), rms_norm(C, p["c_g"], eps)
    dt = jax.nn.softplus(_mm(d, p["w_dt"], prec) + p["b_dt"])      # [T, E]
    A = -jnp.exp(p["A_log"])                                        # [N, E]

    def token(h, xs):
        x_t, dt_t, b_t, c_t = xs
        h = (jnp.exp(dt_t[None, :] * A) * h.astype(jnp.float32)
             + b_t[:, None] * (dt_t * x_t)[None, :])
        y = jnp.sum(c_t[:, None] * h, axis=0) + p["D"] * x_t
        return h.astype(state_dtype), y

    _, y = jax.lax.scan(token, jnp.zeros(A.shape, state_dtype),
                        (x, dt, B, C))
    return _mm(y * jax.nn.silu(z), p["w_out"], prec)


def layer(p, h, kind, cfg, prec, state_dtype=jnp.float32):
    """One block over ``h [T, D]``; ``cfg`` is the configuration file's
    dictionary (the published keys)."""
    eps = cfg["rms_norm_eps"]
    x = rms_norm(h, p["in_norm_g"], eps)
    if kind == ATTN:
        a = attention(p, x, cfg["num_attention_heads"],
                      cfg["num_key_value_heads"], prec)
    elif kind == MAMBA:
        a = mamba(p, x, cfg["mamba_dt_rank"], cfg["mamba_d_state"], eps,
                  prec, state_dtype)
    else:
        raise ValueError(f"unknown layer type {kind!r}")
    h = h + a
    return h + mlp(p, rms_norm(h, p["ff_norm_g"], eps), prec)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _layer_jit(p, h, kind, sizes, prec, state_dtype):
    return layer(p, h, kind, dict(sizes), prec, state_dtype)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _head_jit(lnf_g, wte, h, first_row, n_rows, eps, prec):
    rows = jax.lax.dynamic_slice_in_dim(h, first_row, n_rows, axis=0)
    return _einsum("td,vd->tv", rms_norm(rows, lnf_g, eps), wte, prec,
                   (-1, -1))


_SIZES = ("rms_norm_eps", "num_attention_heads", "num_key_value_heads",
          "mamba_dt_rank", "mamba_d_state")


def logits_rows(top, layer_params, cfg, ids, first_row, n_rows, prec,
                state_dtype=jnp.float32):
    """Logits ``[n_rows, V]`` of one sequence ``ids [T]`` from position
    ``first_row`` on; row ``i`` predicts the token at ``first_row + i +
    1``.  ``top`` holds ``wte`` and ``lnf_g``; ``layer_params(l)`` makes
    layer ``l``'s weights, which are dropped before the next layer's are
    made."""
    sizes = tuple((k, cfg[k]) for k in _SIZES)
    h = top["wte"][jnp.asarray(ids)]
    for l, kind in enumerate(layer_types(cfg)):
        h = _layer_jit(layer_params(l), h, kind, sizes, prec,
                       jnp.dtype(state_dtype))
    return _head_jit(top["lnf_g"], top["wte"], h, first_row, n_rows,
                     cfg["rms_norm_eps"], prec)
