"""Plain SDAR-MoE in float32: a Qwen3-MoE block under a block-causal mask,
and generation by diffusion over blocks.

Source of the sizes: ``https://huggingface.co/JetLM/SDAR-30B-A3B-Chat``
(``model_type: sdar_moe``); of the equations: SDAR, arXiv:2510.06303, whose
layer is the Qwen3-MoE block and whose sampler is the repository's
``block_diffusion_generate``.  ``eps`` and every count are the
configuration file's; what the config has no key for (QK-norm, the rotary
layout, the block length, the mask token) is the family's convention and
is listed under ``assumed`` there.

* the block: ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``; a
  final RMSNorm and an untied head;
* attention: ``q = W_q u`` as ``H`` heads of ``hd``, ``k = W_k u`` and ``v
  = W_v u`` as ``N`` heads of ``hd``; ``q <- RMSNorm(q) g_q``, ``k <-
  RMSNorm(k) g_k`` over each head's ``hd`` dims (one gain each, shared by
  the heads); RoPE over all ``hd`` dims as two halves (dim ``i`` turns
  with dim ``i + hd/2``), ``theta`` as given, at the token's position;
  query head ``h`` reads K/V head ``h // (H / N)``; ``score = q . k /
  sqrt(hd)``; position ``i`` sees ``j`` where ``mask[i, j]``; softmax;
  ``W_o`` of the heads' outputs side by side;
* experts: ``p = softmax(W_r z)`` over all experts; the top ``k`` (ties to
  the lower index); ``g_i = p_i / sum_topk p``; ``MoE(z) = sum_i g_i
  W_down,i (silu(W_gate,i z) * W_up,i z)``;
* the mask of a sequence: ``i`` sees ``j`` iff ``j // B <= i // B``
  (:func:`block_causal`); the logit at a position predicts THAT position's
  token (no shift);
* generation (:func:`generate`): the sequence is laid out in blocks of
  ``B``; a block starts as the prompt's remainder followed by mask tokens;
  up to ``S`` times: if nothing is masked, the block is final (the program
  caches its K/V there: a pass the reference has no need of); else one
  pass over the sequence so far, at each masked position the candidate
  ``x0`` (arg-max) and its confidence (the softmax probability of ``x0``),
  and the ``n_s`` most confident masked positions are revealed (``n_s = B
  // S``, one more for the first ``B mod S`` passes), or with a threshold
  ``tau`` every masked position above it when there are at least ``n_s``.

Nothing here comes from ``paddle_tpu``: no kernel, no cache, no sorting of
tokens (every expert is applied to every token and weighted, 0 where it
was not chosen), no batching, no weight.  Departures from a textbook
listing, for memory on a 16 GB chip and none in the mathematics: one
sequence at a time; the caller hands the weights over one layer at a time
and the experts one at a time (``params["layer"](l)``,
``params["expert"](l, e)``); attention runs by K/V head and blocks of
query rows; only the rows asked for are projected onto the vocabulary.

``prec`` is the precision of every matrix product, as in ``gpt.py``:
``"f32"`` (float32 at ``highest``: the reference) or ``"fp8"`` (the
control: both operands rounded to e4m3, the router's product too), and
``"bf16"`` (operands rounded to bfloat16, sums in float32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import gpt as _gpt

#: query rows per block of the attention
_Q_BLOCK = 256

#: a position's reveal pass when it was given / while it is masked
GIVEN, MASKED = -1, -2


def _einsum(spec, a, b, prec, axes):
    if prec == "bf16":
        a, b = (t.astype(jnp.bfloat16).astype(jnp.float32) for t in (a, b))
        prec = "f32"
    return _gpt._einsum(spec, a, b, prec, axes)


def _mm(x, w, prec):
    return _einsum("...i,io->...o", x, w, prec, (-1, 0))


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    """``x [T, heads, hd]`` at positions ``pos [T]``."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block_causal(n, block_length):
    """``[n, n]`` bool: ``i`` sees ``j`` iff ``j``'s block is not after
    ``i``'s."""
    blk = np.arange(n) // block_length
    return blk[None, :] <= blk[:, None]


def attention(p, x, mask, pos, cfg, prec):
    """``x [T, D]`` (already normalised), ``mask [T, T]`` bool."""
    T = x.shape[0]
    H, N, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    G = H // N
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    q = rms_norm(_mm(x, p["w_q"], prec).reshape(T, H, hd), p["q_g"], eps)
    k = rms_norm(_mm(x, p["w_k"], prec).reshape(T, N, hd), p["k_g"], eps)
    v = _mm(x, p["w_v"], prec).reshape(T, N, hd)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    nb = -(-T // _Q_BLOCK)
    pad = nb * _Q_BLOCK - T
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        nb, _Q_BLOCK, N, G, hd)
    mb = jnp.pad(mask, ((0, pad), (0, 0)), constant_values=True).reshape(
        nb, _Q_BLOCK, T)

    def rows(args):
        qs, ms = args
        s = _einsum("qngd,knd->ngqk", qs, k, prec, (-1, -1)) / (hd ** 0.5)
        a = jax.nn.softmax(jnp.where(ms[None, None], s, -jnp.inf), axis=-1)
        return _einsum("ngqk,knd->qngd", a, v, prec, (-1, 0))

    o = jax.lax.map(rows, (qb, mb)).reshape(nb * _Q_BLOCK, H * hd)[:T]
    return _mm(o, p["w_o"], prec)


def route(w_router, z, cfg, prec, forced=None):
    """``(expert [T, k] int32, gate [T, k])``: the top ``k`` of the
    softmax over all experts (ties to the lower index), renormalised.
    With ``forced [T, k]`` those experts at this router's own
    probabilities."""
    p = jax.nn.softmax(_mm(z, w_router, prec), axis=-1)
    if forced is None:
        gate, expert = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    else:
        expert, gate = forced, jnp.take_along_axis(p, forced, -1)
    return expert, gate / gate.sum(-1, keepdims=True)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _attention_jit(p, h, mask, pos, sizes, prec, forced=None):
    cfg = dict(sizes)
    eps = cfg["rms_norm_eps"]
    h = h + attention(p, rms_norm(h, p["attn_g"], eps), mask, pos, cfg, prec)
    z = rms_norm(h, p["ffn_g"], eps)
    expert, gate = route(p["w_router"], z, cfg, prec, forced)
    return h, z, expert, gate


@functools.partial(jax.jit, static_argnums=(6,), donate_argnums=(1,))
def _expert_jit(ex, h, z, expert, gate, e, prec):
    g = jnp.sum(jnp.where(expert == e, gate, 0.0), -1, keepdims=True)
    y = _mm(jax.nn.silu(_mm(z, ex["ex_gate"], prec))
            * _mm(z, ex["ex_up"], prec), ex["ex_down"], prec)
    return h + g * y


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head_jit(lnf_g, head, h, rows, eps, prec):
    return _mm(rms_norm(h[rows], lnf_g, eps), head, prec)


_SIZES = ("rms_norm_eps", "num_attention_heads", "num_key_value_heads",
          "head_dim", "rope_theta", "num_experts_per_tok")


def hidden(params, ids, mask, pos=None, prec="f32", forced=None):
    """The residual stream after the last layer, ``[T, D]``, of one
    sequence ``ids [T]`` under ``mask [T, T]``, and the routers' choices
    ``[layers, T, k]``.  ``pos [T]`` are the tokens' positions where they
    are not ``0 .. T - 1`` (rows that stand for another state of the same
    positions).  ``params``: ``"config"`` (the configuration file's
    sizes), ``"top"`` (``wte``, ``lnf_g``, ``head``), ``"layer"(l)``
    (layer ``l``'s weights without its experts) and ``"expert"(l, e)``
    (one expert's); each is dropped before the next is made.  ``forced``
    (another run's choices, shaped as they are returned) takes the
    routers' place."""
    cfg = params["config"]
    sizes = tuple((k, cfg[k]) for k in _SIZES)
    ids = jnp.asarray(ids)
    pos = jnp.arange(ids.shape[0]) if pos is None else jnp.asarray(pos)
    mask = jnp.asarray(mask, bool)
    h = params["top"]["wte"][ids]
    chosen = []
    for l in range(cfg["num_hidden_layers"]):
        h, z, expert, gate = _attention_jit(
            params["layer"](l), h, mask, pos, sizes, prec,
            None if forced is None else forced[l])
        for e in range(cfg["num_experts"]):
            h = _expert_jit(params["expert"](l, e), h, z, expert, gate,
                            jnp.int32(e), prec)
        chosen.append(expert)
    return h, jnp.stack(chosen)


def project(params, h, rows, prec="f32"):
    """Logits ``[len(rows), V]`` of the rows ``rows`` of ``h``: the final
    norm and the head.  Row ``i`` is the model's reading of position
    ``i`` (no shift)."""
    top = params["top"]
    return _head_jit(top["lnf_g"], top["head"], h, jnp.asarray(rows),
                     params["config"]["rms_norm_eps"], prec)


def logits(params, ids, mask, pos=None, rows=None, prec="f32", forced=None):
    """:func:`hidden` then :func:`project` (all rows by default):
    ``(logits, the routers' choices)``."""
    h, chosen = hidden(params, ids, mask, pos, prec, forced)
    rows = np.arange(len(ids)) if rows is None else rows
    return project(params, h, rows, prec), chosen


def reveal_counts(block_length, steps):
    """The static schedule: what each of ``steps`` passes reveals of a
    block of ``block_length`` positions."""
    return [block_length // steps + (i < block_length % steps)
            for i in range(steps)]


def reveal(conf, masked, n_s, tau=None):
    """The positions one pass reveals: ``conf [B]`` the candidates'
    confidences, ``masked [B]`` bool.  The ``n_s`` most confident masked
    positions (ties to the lower index; all of them where fewer are
    left), or with ``tau`` every masked position above it when there are
    at least ``n_s``."""
    conf, masked = np.asarray(conf, np.float64), np.asarray(masked, bool)
    high = masked & (conf > tau) if tau is not None else None
    if high is not None and high.sum() >= n_s:
        return high
    order = sorted(np.flatnonzero(masked), key=lambda i: (-conf[i], i))
    out = np.zeros(len(conf), bool)
    out[order[:n_s]] = True
    return out


def generate(params, prompt, max_new_tokens, block_length, steps,
             mask_token_id, tau=None, eos_token_id=None, prec="f32",
             trace=None, width=None):
    """The published loop over :func:`logits`, greedy: ``(tokens,
    reveal_steps)`` of the generated positions, cut at ``max_new_tokens``
    or after an EOS inside a finished block.  Every pass recomputes the
    whole sequence so far (no cache).  ``trace``, a list, takes one entry
    a denoising pass: ``{"start", "pass", "ids" (the block as the pass saw
    it), "logits" [B, V]}``.  ``width`` pads every pass's sequence with
    mask tokens to one length (whole blocks after the current one, which
    no position before them sees), so that the passes compile once."""
    B = block_length
    seq = [int(t) for t in prompt]
    T = len(seq)
    counts = reveal_counts(B, steps)
    out, out_steps = [], []
    while len(out) < max_new_tokens:
        start = len(seq) // B * B
        block = seq[start:] + [mask_token_id] * (B - (len(seq) - start))
        rstep = [GIVEN] * (len(seq) - start) + [MASKED] * (
            B - (len(seq) - start))
        seq = seq[:start]
        for step in range(steps):
            masked = np.array([r == MASKED for r in rstep])
            if not masked.any():
                break
            ids = seq + block
            ids = np.asarray(ids + [mask_token_id] * max(
                0, (width or 0) - len(ids)), np.int32)
            lg, _ = logits(params, ids, block_causal(len(ids), B),
                           rows=np.arange(start, start + B), prec=prec)
            lg = np.asarray(lg, np.float64)
            if trace is not None:
                trace.append({"start": start, "pass": step,
                              "ids": list(block), "logits": lg})
            x0 = lg.argmax(-1)
            conf = np.exp(lg.max(-1) - _logsumexp(lg))
            for i in np.flatnonzero(reveal(conf, masked, counts[step], tau)):
                block[i], rstep[i] = int(x0[i]), step
        seq = seq + block
        for t, r in zip(block, rstep):
            if r == GIVEN or len(out) >= max_new_tokens:
                continue
            out.append(t)
            out_steps.append(r)
            if eos_token_id is not None and t == eos_token_id:
                return out, out_steps
    return out, out_steps


def _logsumexp(x):
    m = x.max(-1)
    return m + np.log(np.exp(x - m[..., None]).sum(-1))
