"""A routed-expert layer for one chip's share of the experts.

The layer is told which experts it holds (``first`` and the leading axis of
the expert weights), routes over ALL the router's outputs, and computes the
part of the result that its own experts give; what the absent experts
would add is left out, and no code stands in for their chips or for the
exchange with them (expert parallelism without its all-to-all is exactly
this layer on every chip).

No token is dropped and there is no capacity: the (token, expert) pairs
held here are ordered by expert with a counting sort (a cumulative sum
over a one-hot, no ``sort`` op), the tokens gathered in that order, and the
experts applied as two grouped products (``jax.lax.ragged_dot``, which the
TPU compiler lowers to one Mosaic grouped matmul that visits only the
tiles of rows that belong to a group).  Shapes are static: ``N * top_k``
rows, the bound when every choice of every token is held here; the rows
past the last group belong to pairs held elsewhere and are discarded.

Routing is DeepSeek-V2's group-limited greedy top-k (arXiv:2405.04434,
section 2.2.2): softmax over the experts in float32, the best
``topk_group`` of ``n_group`` groups by their largest probability, the top
``top_k`` probabilities of what is left, not renormalised.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..profiler import counters


def _top_k(x, k):
    """``jax.lax.top_k`` for a small ``k`` as ``k`` arg-max passes (ties to
    the lower index, as ``top_k`` breaks them): a handful of reductions
    over a short row, and no sort in the program."""
    vals, idxs = [], []
    cols = jnp.arange(x.shape[-1])
    for _ in range(k):
        i = jnp.argmax(x, axis=-1)
        vals.append(jnp.take_along_axis(x, i[..., None], -1)[..., 0])
        idxs.append(i.astype(jnp.int32))
        x = jnp.where(cols == i[..., None], -jnp.inf, x)
    return jnp.stack(vals, -1), jnp.stack(idxs, -1)


def group_limited_top_k(logits, n_group, topk_group, top_k):
    """``logits [N, E]`` -> ``(p [N, top_k] float32, expert [N, top_k]
    int32)``: the router probabilities of the chosen experts and their
    indices over all ``E``."""
    N, E = logits.shape
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    best = p.reshape(N, n_group, E // n_group).max(-1)
    _, groups = _top_k(best, topk_group)
    kept = (groups[:, :, None] == jnp.arange(n_group)).any(1)
    p = jnp.where(jnp.repeat(kept, E // n_group, axis=1), p, 0.0)
    return _top_k(p, top_k)


def held_expert_ffn(x, weight, expert, gu_w, down_w, first, layer=0,
                    live=None):
    """``sum_i weight_i E_i(x)`` over the chosen experts that are held
    here.  ``x [N, D]``; ``weight``/``expert [N, k]`` from the router, the
    indices over all experts; ``gu_w [L, E, D, 2F]`` (gate beside up) and
    ``down_w [L, E, F, D]`` the experts ``first .. first + E - 1`` of
    every expert layer, of which ``layer`` (it may be traced) is applied:
    the products run over all ``L * E`` groups with the other layers'
    empty, so that the stacked weights are read where they lie and no
    layer's gigabyte is sliced out first.  ``live [N]`` bool leaves rows
    out (padding, idle slots).  Returns ``(y [N, D] float32, count [E]
    int32)``: the held part of the routed sum, and how many live tokens
    each held expert took."""
    N, k = expert.shape
    L, E, _, F2 = gu_w.shape
    e = expert - first
    held = (e >= 0) & (e < E)
    if live is not None:
        held = held & live[:, None]
    key = jnp.where(held, e, E).reshape(N * k)    # absent pairs go last
    # counting sort: a pair's place is its group's offset plus the number
    # of earlier pairs of the same group
    hot = key[:, None] == jnp.arange(E + 1)
    within = jnp.take_along_axis(jnp.cumsum(hot, 0, dtype=jnp.int32),
                                 key[:, None], 1)[:, 0] - 1
    sizes = hot.sum(0, dtype=jnp.int32)
    place = (jnp.cumsum(sizes) - sizes)[key] + within
    order = jnp.zeros(N * k, jnp.int32).at[place].set(
        jnp.arange(N * k, dtype=jnp.int32))
    count = sizes[:E]
    groups = jax.lax.dynamic_update_slice(
        jnp.zeros(L * E, jnp.int32), count, (layer * E,))
    xs = x[order // k]
    gu = jax.lax.ragged_dot(xs, gu_w.reshape((L * E,) + gu_w.shape[2:]),
                            groups)
    h = jax.nn.silu(gu[:, :F2 // 2]) * gu[:, F2 // 2:]
    ys = jax.lax.ragged_dot(h, down_w.reshape((L * E,) + down_w.shape[2:]),
                            groups, preferred_element_type=jnp.float32)
    # back in (token, choice) order; rows past the last group hold nothing
    # that was computed
    y = jnp.where(held.reshape(N * k, 1),
                  ys[place] * weight.reshape(N * k, 1), 0.0)
    return y.reshape(N, k, -1).sum(1), count


def publish_load(state, seen):
    """The expert layers' load so far from an engine's ``step_state()``
    reading (``moe_assignments [layers, held]``, ``moe_tokens``):
    ``assignments`` ((token, held expert) pairs computed), ``tokens``
    (tokens routed), ``per_expert`` and ``load_max_over_mean`` (the
    busiest held expert of any layer against the mean; 1.0 is even
    routing).  Publishes what was added since the last call (``seen``, the
    caller's running totals, updated in place) as the counters
    ``serving.moe.assignments`` and ``serving.moe.tokens``, and the gauge
    ``serving.moe.load_max_over_mean``."""
    per = state["moe_assignments"]
    load = {"assignments": int(per.sum()),
            "tokens": int(state["moe_tokens"]), "per_expert": per,
            "load_max_over_mean": (float(per.max() / per.mean())
                                   if per.any() else 0.0)}
    for k in ("assignments", "tokens"):
        counters.inc("serving.moe." + k, load[k] - seen[k])
        seen[k] = load[k]
    counters.set_gauge("serving.moe.load_max_over_mean",
                       load["load_max_over_mean"])
    return load
