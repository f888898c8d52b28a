"""Shared decode-time sampling (GPT.generate + serving.LLMEngine).

ONE implementation of the temperature / top-k / top-p logits transform and
the token draw, traced by BOTH ``GPTForCausalLM.generate`` (python-scalar
knobs, one PRNG key per step over [B, V] logits) and the serving engines'
programs (per-slot knob ARRAYS, one key per slot).

The serving programs' whole sampling tail lives here too, once:
``next_tokens`` is what ``LLMEngine``'s decode program, every prefill
chunk program's first-token draw (a batch of one) and
the speculative drafter's proposal draw all trace.  It branches ON THE
DEVICE on what the batch's own ``do_sample`` row shows: a batch with no
sampling row runs ``argmax`` alone and never the two vocabulary-wide
sorts of ``filter_logits``.

Knob semantics at neutral values: python scalars (``top_k=0``,
``top_p=1.0``) skip the work statically.  TRACED per-slot values apply
it, and it is NOT the identity: the top-k threshold does degenerate to
the row minimum, but the nucleus at a traced ``top_p = 1.0`` masks tail
tokens once the float32 ``cumsum`` of the sorted probabilities rounds up
to 1.0 (one entry of a [4, 50304] batch at N(0, 1) logits, tens of
thousands at N(0, 5)).  So a neutral-knob slot inside an engine program
and a ``generate`` trace that never emitted the transform agree on the
TOKENS of the tests' draws (the masked tail holds next to no mass), not
on the logits bit for bit.  For the same reason ``next_tokens`` has one
predicate, ``any(do_sample)``, and no second one on the filter knobs:
skipping the filters when every sampling row is neutral would make a
neutral row's support depend on whether a neighbour asked for a filter.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _is_traced(x):
    return isinstance(x, (jax.Array, jax.core.Tracer))


def filter_logits(lg, temperature=1.0, top_k=0, top_p=1.0):
    """Temperature scaling, then top-k, then top-p (nucleus) masking over
    fp32 logits ``lg[..., V]``.  Masked entries become -1e30 (exp == 0
    exactly under softmax).  Knobs may be python scalars or traced values
    broadcastable against ``lg[..., 0]``."""
    V = lg.shape[-1]
    lg = lg / jnp.maximum(temperature, 1e-6)
    if _is_traced(top_k):
        srt = jnp.sort(lg, axis=-1)  # ascending
        k = jnp.clip(top_k, 0, V)
        # k <= 0 disables: threshold at the row min masks nothing
        idx = jnp.where(k <= 0, 0, V - jnp.maximum(k, 1)).astype(jnp.int32)
        idx = jnp.broadcast_to(idx, lg.shape[:-1])[..., None]
        kth = jnp.take_along_axis(srt, idx, axis=-1)
        lg = jnp.where(lg < kth, -1e30, lg)
    # ptlint: disable=PT001 reason="a python scalar here: the traced case took the branch above"
    elif top_k and int(top_k) > 0:
        # ptlint: disable=PT001 reason="a python scalar here: the traced case took the branch above"
        kth = jnp.sort(lg, axis=-1)[..., -min(int(top_k), V)][..., None]
        lg = jnp.where(lg < kth, -1e30, lg)
    # ptlint: disable=PT001 reason="float() runs only where _is_traced ruled a traced value out"
    if _is_traced(top_p) or float(top_p) < 1.0:
        s = -jnp.sort(-lg, axis=-1)  # descending
        probs = jax.nn.softmax(s, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep a token while the mass strictly BEFORE it is < p; the top
        # token is always kept (0 < p)
        keep = (cum - probs) < top_p
        cnt = jnp.maximum(jnp.sum(keep, axis=-1), 1)
        cutoff = jnp.take_along_axis(
            s, (cnt - 1)[..., None].astype(jnp.int32), axis=-1)
        lg = jnp.where(lg < cutoff, -1e30, lg)
    return lg


def sample_tokens(lg, key, *, do_sample=True, temperature=1.0, top_k=0,
                  top_p=1.0, out_dtype=jnp.int32):
    """Next tokens from fp32 logits ``lg[..., V]``.  Static
    ``do_sample=False`` is pure argmax (no PRNG traced); otherwise a
    categorical draw over the filtered distribution."""
    if do_sample is False:
        return jnp.argmax(lg, axis=-1).astype(out_dtype)
    flg = filter_logits(lg, temperature, top_k, top_p)
    return jax.random.categorical(key, flg, axis=-1).astype(out_dtype)


def next_tokens(logits, keys_data, do_sample, temp, top_k, top_p, *,
                with_dist=False):
    """The serving programs' sampling tail: next token ids ``[B]`` from
    fp32 ``logits[B, V]`` under per-row knob arrays, and the rows' new
    key data.  Every row's key is split OUTSIDE the branch, so the key
    chains evolve the same whichever side runs.  Then one scalar
    predicate over the batch picks the side: with no sampling row the
    tail is ``argmax`` alone; otherwise each sampling row draws
    ``categorical(filter_logits(...))`` over its own ``[1, V]`` row with
    its own key (exactly ``generate``'s draw for a batch-1 request) and
    each greedy row keeps its ``argmax``.  The ``cond`` sits outside the
    per-row ``vmap``: under it a ``cond`` lowers to a select that runs
    both sides.  Callers upload ``do_sample`` masked to running rows.

    ``with_dist=True`` also returns the ``[B, V]`` distribution each
    token was drawn from (the speculative drafter's ``q``); a greedy
    row's is never read, so the all-greedy side returns zeros."""
    keys = jax.random.wrap_key_data(keys_data)  # [B] typed
    pair = jax.vmap(jax.random.split)(keys)     # [B, 2]
    new_keys, kstep = pair[:, 0], pair[:, 1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sampled_tail():
        flg = jax.vmap(lambda lg, t, tk, tp: filter_logits(
            lg[None], t, tk, tp))(logits, temp, top_k, top_p)  # [B, 1, V]
        sampled = jax.vmap(lambda k, f: jax.random.categorical(
            k, f, axis=-1)[0])(kstep, flg)
        nxt = jnp.where(do_sample, sampled, greedy).astype(jnp.int32)
        if with_dist:
            return nxt, jax.nn.softmax(flg[:, 0], axis=-1)
        return (nxt,)

    def greedy_tail():
        return (greedy, jnp.zeros_like(logits)) if with_dist else (greedy,)

    return (*jax.lax.cond(jnp.any(do_sample), sampled_tail, greedy_tail),
            jax.random.key_data(new_keys))


def residual_sample(p, q, key, out_dtype=jnp.int32):
    """Draw from the speculative-decoding residual distribution
    ``norm(max(0, p - q))`` (Leviathan et al., ICML 2023, eq. for the
    rejection fallback).  ``p`` is the target model's probability row(s)
    ``[..., V]``, ``q`` the draft's; when a drafted token is rejected the
    correction draw from this residual keeps the OVERALL output
    distribution exactly equal to sampling from ``p`` alone.

    Degenerate rows where ``q >= p`` everywhere (residual mass 0, only
    possible up to float rounding since both sum to 1) fall back to
    sampling from ``p`` itself — a measure-zero guard, not a bias."""
    res = jnp.maximum(p - q, 0.0)
    mass = jnp.sum(res, axis=-1, keepdims=True)
    safe = res / jnp.maximum(mass, 1e-20)
    dist = jnp.where(mass > 0.0, safe, p)
    lg = jnp.log(jnp.maximum(dist, 1e-30))
    return jax.random.categorical(key, lg, axis=-1).astype(out_dtype)
