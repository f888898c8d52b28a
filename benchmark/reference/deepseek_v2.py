"""Plain DeepSeek-V2 in float32: latent attention, shared and routed experts.

Source of the sizes: ``https://huggingface.co/deepseek-ai/DeepSeek-V2``
(``model_type: deepseek_v2``); of the equations: DeepSeek-V2,
arXiv:2405.04434, sections 2.1 (multi-head latent attention) and 2.2
(DeepSeekMoE with device-limited, here group-limited, routing), with YaRN
(arXiv:2309.00071) on the rotary dimensions as the public implementation
applies it.  ``eps`` and every count are the configuration file's.

* the block: ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; a
  final RMSNorm and an untied head;
* attention, ``H`` heads: ``c_q = RMSNorm(W_DQ u)``; ``q_nope = W_UQ c_q``
  (``d_n`` a head), ``q_pe = RoPE(W_QR c_q)`` (``d_r`` a head); ``c_kv =
  RMSNorm(W_DKV u)``; ``k_pe = RoPE(W_KR u)``, ONE per token, shared by the
  heads; ``k_nope = W_UK c_kv``, ``v = W_UV c_kv`` (``d_n``, ``d_v`` a
  head); ``score = (q_nope . k_nope + q_pe . k_pe) s``, causal softmax,
  ``W_O`` of the heads' outputs side by side; ``s = (d_n + d_r)^-1/2 m^2``,
  ``m = 0.1 mscale_all_dim ln(factor) + 1``;
* RoPE over the ``d_r`` rotary dims, each head's as two halves (dim ``i``
  turns with dim ``i + d_r/2``: the layout the public implementation
  reaches after regrouping its interleaved pairs; with random weights the
  regrouping is a permutation of ``W_QR``'s and ``W_KR``'s columns, listed
  under ``assumed``), at YaRN's frequencies: ``f_i = theta^(-2i/d_r)``,
  ``f_i / factor`` blended in by a ramp over ``[low, high]``, the
  correction range of ``(beta_fast, beta_slow)`` turns over the original
  length; cos and sin times ``mscale(factor, mscale) / mscale(factor,
  mscale_all_dim)``;
* dense FFN (the first ``first_k_dense_replace`` layers): ``W_down
  (silu(W_gate z) * W_up z)``;
* expert FFN: ``p = softmax(W_r z)`` over all routed experts; the score of
  a group is its largest ``p``; the best ``topk_group`` groups stay, the
  rest are set to 0; the top ``num_experts_per_tok`` of what is left, with
  weights ``g_i = routed_scaling_factor p_i`` (not renormalised); ``FFN(z)
  = Shared(z) + sum_i g_i E_i(z)``, ``Shared`` one SwiGLU of the shared
  experts' joint width.  Given a share ``(first, held)`` of the experts,
  the router, the groups and the top-k run over all of them and the sum
  over the chosen experts in ``first .. first + held - 1``: what the
  absent ones would add is left out (``model-configs`` guide, section 4).

Nothing here comes from ``paddle_tpu``: no kernel, no cache, no absorbed
form, no sorting of tokens (every expert is applied to every token and
weighted, 0 where it was not chosen), no batching, no weight.  Departures
from a textbook listing, for memory on a 16 GB chip and none in the
mathematics: one sequence at a time; the caller hands the weights over one
layer at a time and the routed experts one at a time; attention runs by
groups of heads and blocks of query rows; only the rows asked for are
projected onto the vocabulary.

``prec`` is the precision of every matrix product, as in ``gpt.py``:
``"f32"`` (float32 at ``highest``: the reference) or ``"fp8"`` (the
control: both operands rounded to e4m3 with one scale per row of the
contraction; the router's product too); and, to show what a program's
own precision does to this model, ``"bf16"`` (both operands rounded to
bfloat16, the sums in float32).  ``forced`` hands the routers another
run's choices: the gates are this run's own probabilities of them.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference import gpt as _gpt

#: query rows per block, and heads per group, of the attention
_Q_BLOCK = 256
_HEAD_GROUP = 16


def _einsum(spec, a, b, prec, axes):
    if prec == "bf16":
        a, b = (t.astype(jnp.bfloat16).astype(jnp.float32) for t in (a, b))
        prec = "f32"
    return _gpt._einsum(spec, a, b, prec, axes)


def _mm(x, w, prec):
    return _einsum("...i,io->...o", x, w, prec, (-1, 0))


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg):
    """``d_r / 2`` frequencies (radians a position)."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    i = jnp.arange(0, d, 2, dtype=jnp.float32)
    extra = 1.0 / theta ** (i / d)
    rs = cfg.get("rope_scaling")
    if not rs:
        return extra

    def dim_of(turns):
        return d * math.log(rs["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rs["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    mask = 1.0 - jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                          / (high - low), 0, 1)
    return extra / rs["factor"] * (1.0 - mask) + extra * mask


def rope(x, cfg):
    """``x [T, ..., d_r]`` at positions ``0 .. T - 1``."""
    rs = cfg.get("rope_scaling")
    m = (yarn_mscale(rs["factor"], rs["mscale"])
         / yarn_mscale(rs["factor"], rs["mscale_all_dim"])) if rs else 1.0
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * yarn_inv_freq(cfg)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def softmax_scale(cfg):
    rs = cfg.get("rope_scaling")
    m = yarn_mscale(rs["factor"], rs["mscale_all_dim"]) if rs else 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def latent_attention(p, x, cfg, prec):
    """``x [T, D]`` (already normalised); materialised keys and values."""
    T = x.shape[0]
    H = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    eps, s = cfg["rms_norm_eps"], softmax_scale(cfg)
    c_q = rms_norm(_mm(x, p["w_dq"], prec), p["q_g"], eps)
    c_kv = rms_norm(_mm(x, p["w_dkv"], prec), p["kv_g"], eps)
    k_pe = rope(_mm(x, p["w_kr"], prec), cfg)                    # [T, d_r]
    Hg = min(_HEAD_GROUP, H)
    G = H // Hg
    cols = lambda w, d: jnp.moveaxis(                          # noqa: E731
        w.reshape(w.shape[0], G, Hg * d), 1, 0)
    nb = -(-T // _Q_BLOCK)
    kpos = jnp.arange(T)

    def group(acc, ws):
        w_uq, w_qr, w_uk, w_uv, w_o = ws
        q_nope = _mm(c_q, w_uq, prec).reshape(T, Hg, dn)
        q_pe = rope(_mm(c_q, w_qr, prec).reshape(T, Hg, dr), cfg)
        k_nope = _mm(c_kv, w_uk, prec).reshape(T, Hg, dn)
        v = _mm(c_kv, w_uv, prec).reshape(T, Hg, dv)
        block = lambda t: jnp.pad(                             # noqa: E731
            t, ((0, nb * _Q_BLOCK - T), (0, 0), (0, 0))).reshape(
                (nb, _Q_BLOCK) + t.shape[1:])

        def rows(args):
            qn, qr, first = args
            sc = (_einsum("qhd,khd->hqk", qn, k_nope, prec, (-1, -1))
                  + _einsum("qhd,kd->hqk", qr, k_pe, prec, (-1, -1))) * s
            qpos = first + jnp.arange(_Q_BLOCK)
            sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc,
                           -jnp.inf)
            return _einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v,
                           prec, (-1, 0))

        o = jax.lax.map(rows, (block(q_nope), block(q_pe),
                               jnp.arange(nb) * _Q_BLOCK))
        o = o.reshape(nb * _Q_BLOCK, Hg * dv)[:T]
        return acc + _mm(o, w_o, prec), None

    out, _ = jax.lax.scan(
        group, jnp.zeros_like(x),
        (cols(p["w_uq"], dn), cols(p["w_qr"], dr), cols(p["w_uk"], dn),
         cols(p["w_uv"], dv),
         p["w_o"].reshape(G, Hg * dv, p["w_o"].shape[1])))
    return out


def swiglu(z, w_gate, w_up, w_down, prec):
    return _mm(jax.nn.silu(_mm(z, w_gate, prec)) * _mm(z, w_up, prec),
               w_down, prec)


def route(w_router, z, cfg, prec, forced=None):
    """``(expert [T, k] int32, gate [T, k])`` over ALL the routed
    experts; ties go to the lower index.  With ``forced [T, k]`` those
    experts, each at this router's own probability."""
    p = jax.nn.softmax(_mm(z, w_router, prec), axis=-1)
    if forced is not None:
        return forced, (jnp.take_along_axis(p, forced, -1)
                        * cfg["routed_scaling_factor"])
    T, E = p.shape
    n_group = cfg["n_group"]
    best = p.reshape(T, n_group, E // n_group).max(-1)
    _, groups = jax.lax.top_k(best, cfg["topk_group"])
    kept = jnp.zeros((T, n_group), bool).at[
        jnp.arange(T)[:, None], groups].set(True)
    p = jnp.where(jnp.repeat(kept, E // n_group, axis=1), p, 0.0)
    gate, expert = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    return expert, gate * cfg["routed_scaling_factor"]


@functools.partial(jax.jit, static_argnums=(2, 3))
def _attention_jit(p, h, sizes, prec):
    cfg = _cfg(sizes)
    eps = cfg["rms_norm_eps"]
    h = h + latent_attention(p, rms_norm(h, p["attn_g"], eps), cfg, prec)
    return h, rms_norm(h, p["ffn_g"], eps)


@functools.partial(jax.jit, static_argnums=(3,))
def _dense_jit(p, h, z, prec):
    return h + swiglu(z, p["w_gate"], p["w_up"], p["w_down"], prec)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _shared_jit(p, h, z, sizes, prec, forced=None):
    expert, gate = route(p["w_router"], z, _cfg(sizes), prec, forced)
    return (h + swiglu(z, p["sh_gate"], p["sh_up"], p["sh_down"], prec),
            expert, gate)


@functools.partial(jax.jit, static_argnums=(6,), donate_argnums=(1,))
def _expert_jit(ex, h, z, expert, gate, e, prec):
    g = jnp.sum(jnp.where(expert == e, gate, 0.0), -1, keepdims=True)
    return h + g * swiglu(z, ex["ex_gate"], ex["ex_up"], ex["ex_down"], prec)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _head_jit(lnf_g, head, h, first_row, n_rows, eps, prec):
    rows = jax.lax.dynamic_slice_in_dim(h, first_row, n_rows, axis=0)
    return _mm(rms_norm(rows, lnf_g, eps), head, prec)


_SIZES = ("rms_norm_eps", "num_attention_heads", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "rope_theta", "n_group",
          "topk_group", "num_experts_per_tok", "routed_scaling_factor")


def _sizes(cfg):
    rs = cfg.get("rope_scaling")
    return tuple((k, cfg[k]) for k in _SIZES) + (
        ("rope_scaling", tuple(sorted(rs.items())) if rs else None),)


def _cfg(sizes):
    cfg = dict(sizes)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"] or ()) or None
    return cfg


def layer(p, experts, h, l, cfg, held, prec, forced=None):
    """One block over ``h [T, D]``.  ``experts(e)`` makes routed expert
    ``e``'s three matrices; ``held = (first, count)`` is the share of the
    experts whose part of the sum is computed; ``forced [T, k]`` takes
    the router's place.  Returns ``(h, expert [T, k] or None)``: the
    choices over all the experts."""
    sizes = _sizes(cfg)
    h, z = _attention_jit(p, h, sizes, prec)
    if l < cfg["first_k_dense_replace"]:
        return _dense_jit(p, h, z, prec), None
    h, expert, gate = _shared_jit(p, h, z, sizes, prec, forced)
    for e in range(held[0], held[0] + held[1]):
        h = _expert_jit(experts(e), h, z, expert, gate, jnp.int32(e), prec)
    return h, expert


def logits_rows(top, layer_params, expert_params, cfg, held, ids, first_row,
                n_rows, prec, forced=None):
    """Logits ``[n_rows, V]`` of one sequence ``ids [T]`` from position
    ``first_row`` on (row ``i`` predicts the token at ``first_row + i +
    1``), and the routers' choices ``[expert layers, T, k]``.  ``top``
    holds ``wte``, ``lnf_g`` and ``head``; ``layer_params(l)`` makes layer
    ``l``'s weights without its routed experts and ``expert_params(l, e)``
    one of those; each is dropped before the next is made.  ``forced``
    (another run's choices, shaped as they are returned) takes the
    routers' place."""
    h = top["wte"][jnp.asarray(ids)]
    chosen = []
    for l in range(cfg["num_hidden_layers"]):
        h, expert = layer(layer_params(l),
                          functools.partial(expert_params, l), h, l, cfg,
                          held, prec,
                          None if forced is None else forced[len(chosen)])
        if expert is not None:
            chosen.append(expert)
    return (_head_jit(top["lnf_g"], top["head"], h, first_row, n_rows,
                      cfg["rms_norm_eps"], prec), jnp.stack(chosen))
