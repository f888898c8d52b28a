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
experts applied as two grouped products (:func:`grouped_mm`: gate beside
up, then down).  Shapes are static: ``N * top_k`` rows, the bound when
every choice of every token is held here (plus, for the kernel, each
group's start rounded up to a row tile); the rows past the last group
belong to pairs held elsewhere and are discarded.

A grouped product has two bodies, and :func:`kernel_mode` chooses between
them from what the code can observe (the house pattern of
``block_attention``, under the interpret hook the walks share):

* :func:`grouped_mm_pallas` (``name="ragged-dot-held"``: the trace still
  says what the op is) is weight-stationary.  Its grid walks (held expert
  of the applied layer, tile of output columns); the stacked weights stay
  where they lie and each weight tile crosses HBM once a launch, fetched
  by the pipeline while the tile before it is used; a group without rows
  fetches nothing.  Inside a step the group's rows are taken a row tile at
  a time (the next tile's rows copied in while this one is multiplied), so
  the MXU's work follows ``round_up(rows of the group, tile)``.  At 1-40
  rows a group, as in serving, the product is bound by the bytes of the
  experts some row chose (on a v5e 720-750 GB/s of them: PERF.md, section
  5).
* ``jax.lax.ragged_dot`` is the twin: the CPU path, the tests' reference
  and every width that is not whole 128-lane tiles.  The TPU compiler
  lowers it to a Mosaic grouped matmul of its own that walks (row tile,
  group) visits with row tiles of up to 512 rows and does a whole tile's
  MXU work a visit: as good as the kernel at a row a group, a third of
  the bandwidth from 4 rows a group on.

Routing is DeepSeek-V2's group-limited greedy top-k (arXiv:2405.04434,
section 2.2.2): softmax over the experts in float32, the best
``topk_group`` of ``n_group`` groups by their largest probability, the top
``top_k`` probabilities of what is left, not renormalised; or sigmoid
scores with a per-expert bias that chooses and does not weigh
(:func:`biased_sigmoid_top_k`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..device import on_tpu
from ..profiler import counters
from ._shapes import LANE, min_sublane
from .paged_attention import _INTERPRET
from .paged_attention import preload as _preload

#: the most bytes of one weight tile ``[K, columns]``; the pipeline holds two
_WEIGHT_TILE_BYTES = 8 * 2 ** 20
#: the most rows of a row tile: a 64-row product hides under the copy of
#: its weight tile, and a chip's share of the experts holds a fraction of
#: the pairs the mean is taken over, so a larger tile only pads (a 1,024-token
#: chunk of DeepSeek-V2 at 38 rows a group: 10.5 ms at 64, 11.9 at 128)
_MAX_ROW_TILE = 64


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


def biased_sigmoid_top_k(logits, bias, top_k, scale):
    """``logits [N, E]``, ``bias [E]`` -> ``(weight [N, top_k] float32,
    expert [N, top_k] int32)``: sigmoid scores in float32, the experts
    CHOSEN by the top ``top_k`` of score plus bias (ties to the lower
    index) and WEIGHED by their scores alone, renormalised over the chosen
    and times ``scale`` (auxiliary-loss-free balancing, arXiv:2408.15664:
    the bias steers the choice and never the sum)."""
    sigma = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, expert = _top_k(sigma + bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(sigma, expert, -1)
    return (scale * chosen / (chosen.sum(-1, keepdims=True) + 1e-20),
            expert)


def kernel_mode(D, F, dtype):
    """``"pallas"`` where the weight-stationary kernel can run both
    products of experts ``[D, 2F]`` and ``[F, D]`` (the tests' interpret
    hook, or a TPU with ``D`` and ``F`` whole 128-lane tiles), in bfloat16
    or float32; else ``"off"``, the ``ragged_dot`` twin."""
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float32)):
        return "off"
    if _INTERPRET[0] or (on_tpu() and D % LANE == 0 and F % LANE == 0):
        return "pallas"
    return "off"


def preload(D, F, dtype):
    """Start importing Pallas on a background thread
    (``paged_attention.preload``) if experts of these widths will run
    through the kernel: a model calls this before it draws its weights, so
    the import (1.5 s on a v5e's host) is over when the first program is
    traced."""
    if kernel_mode(D, F, dtype) == "pallas":
        _preload()


def row_tile(rows, E, dtype):
    """The kernel's static row tile for ``rows`` (token, expert) pairs over
    ``E`` held experts: the mean rows a group rounded up to a power of
    two, at least a sublane tile of ``dtype`` and at most ``_MAX_ROW_TILE``
    (a larger group is taken in several)."""
    mean = -(-rows // E)
    return max(min_sublane(dtype),
               min(_MAX_ROW_TILE, 1 << (mean - 1).bit_length()))


def group_starts(sizes, tile):
    """Where each group's rows begin when every group but the last starts
    on a whole ``tile`` of rows: ``sizes [G]`` -> ``[G]`` int32.  The last
    group (the pairs held elsewhere) is not padded."""
    padded = -(-sizes[:-1] // tile) * tile
    return jnp.concatenate([jnp.zeros(1, jnp.int32),
                            jnp.cumsum(padded, dtype=jnp.int32)])


def _col_tile(K, N, itemsize):
    """The widest tile of output columns (whole lanes, a divisor of ``N``)
    whose ``[K, columns]`` weights are at most ``_WEIGHT_TILE_BYTES``."""
    if N % LANE:
        return N                  # the interpret hook at a test's widths
    fits = [c for c in range(LANE, N + 1, LANE)
            if N % c == 0 and K * c * itemsize <= _WEIGHT_TILE_BYTES]
    return max(fits, default=LANE)


def _kernel(layer_ref, before_ref, src_ref, col_ref, next_ref, x_hbm, w_ref,
            o_hbm, xbuf, obuf, xsem, osem, *, tm, tn, J):
    """One grid step = one (held expert, tile of columns): the weight tile
    is in ``w_ref``.  ``before_ref [E + 1]`` counts the row tiles of the
    groups before each, so group ``e`` has ``before[e + 1] - before[e]``
    of them from row ``before[e] * tm`` on, and they are items ``c =
    before[e] * J + j * tiles + t`` of one sequence over the whole grid,
    item ``c`` using slot ``c % 2`` of both buffers.  An item starts the
    copy of the next item's rows (the next row tile of this group, or the
    first of the next step that has rows) before it waits for its own, and
    waits for the result copy of the item before it after starting its
    own: both copies have a whole product to hide under.  The first item
    of all starts its own rows (row 0: the first group that has rows
    starts there), the last waits for its own result."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del layer_ref, src_ref, col_ref           # the index maps' operands
    e, j, E = pl.program_id(0), pl.program_id(1), pl.num_programs(0)
    n = before_ref[e + 1] - before_ref[e]
    row0 = before_ref[e] * tm
    c0 = before_ref[e] * J + j * n
    total = before_ref[E] * J
    # the step after this one that has rows: this group's next tile of
    # columns, or the next group that is not empty (``E``: none)
    follows = jnp.logical_or(j + 1 < J, next_ref[e] < E)
    then = jnp.where(j + 1 < J, row0, before_ref[next_ref[e]] * tm)
    cols = (slice(None) if J == 1
            else pl.ds(pl.multiple_of(j * tn, tn), tn))

    def rows_in(row, slot):
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(pl.multiple_of(row, tm), tm)], xbuf.at[slot],
            xsem.at[slot])

    def rows_out(row, slot):
        return pltpu.make_async_copy(
            obuf.at[slot], o_hbm.at[pl.ds(pl.multiple_of(row, tm), tm), cols],
            osem.at[slot])

    @pl.when(jnp.logical_and(e + j == 0, total > 0))
    def _():
        rows_in(0, 0).start()

    def item(t, _):
        c = c0 + t
        slot = jax.lax.rem(c, 2)
        row = row0 + t * tm
        more = t + 1 < n

        @pl.when(jnp.logical_or(more, follows))
        def _():
            rows_in(jnp.where(more, row + tm, then), 1 - slot).start()

        rows_in(row, slot).wait()
        obuf[slot] = jnp.dot(xbuf[slot], w_ref[...],
                             preferred_element_type=jnp.float32
                             ).astype(obuf.dtype)
        rows_out(row, slot).start()

        @pl.when(c > 0)
        def _():
            rows_out(row, 1 - slot).wait()

        @pl.when(c + 1 == total)
        def _():
            rows_out(row, slot).wait()

        return 0

    jax.lax.fori_loop(0, n, item, 0)


def grouped_mm_pallas(xs, w, count, layer, tile, out_dtype):
    """The weight-stationary kernel.  ``xs [R, K]`` rows ordered by group,
    group ``e`` of the applied layer at rows ``group_starts(count, tile)[e]
    ...`` (so every group starts a whole ``tile`` of rows; ``R`` bounds
    them); ``w [L, E, K, N]`` the stacked weights, of which ``layer`` (it
    may be traced) is applied; ``count [E]`` int32 the groups' sizes.
    Returns ``[R, N]`` in ``out_dtype`` (float32 accumulation): every row
    of a tile some group reaches is computed, the other rows are left as
    they were allocated."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, E, K, N = w.shape
    R, tm = xs.shape[0], tile
    tn = _col_tile(K, N, w.dtype.itemsize)
    J = N // tn
    tiles = -(-count // tm)
    # a group without rows repeats the weight tile that was fetched last
    # (or will be fetched first), so the pipeline copies nothing for it
    idx = jnp.arange(E, dtype=jnp.int32)
    full = tiles > 0
    last = jax.lax.cummax(jnp.where(full, idx, -1))
    nxt = jax.lax.cummin(jnp.where(full, idx, E), reverse=True)
    src = jnp.where(last >= 0, last, nxt[0] % E)
    col = jnp.where(full, -1, jnp.where(last >= 0, J - 1, 0))

    def w_map(e, j, layer_ref, before_ref, src_ref, col_ref, next_ref):
        c = col_ref[e]
        return (layer_ref[0] * E + src_ref[e], 0, jnp.where(c < 0, j, c))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(E, J),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec((None, K, tn), w_map)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((2, tm, K), xs.dtype),
                        pltpu.VMEM((2, tm, tn), out_dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,))])
    need = (2 * K * tn * w.dtype.itemsize + 2 * tm * K * xs.dtype.itemsize
            + tm * tn * (2 * jnp.dtype(out_dtype).itemsize + 4))
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, tn=tn, J=J),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=need + 8 * 2 ** 20),
        interpret=_INTERPRET[0],
        name="ragged-dot-held",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      jnp.cumsum(jnp.append(0, tiles), dtype=jnp.int32), src, col,
      jnp.append(nxt[1:], E), xs, w.reshape(L * E, K, N))


def grouped_mm(xs, w, count, layer, tile=1, out_dtype=None):
    """``xs``'s rows of group ``e`` times expert ``e`` of layer ``layer``
    of ``w [L, E, K, N]``, for the ``count [E]`` rows a group that lie at
    ``group_starts(count, tile)``.  ``tile`` > 1 is the Pallas kernel's
    row tile (:func:`grouped_mm_pallas`); 1 is ``jax.lax.ragged_dot`` over
    all ``L * E`` groups with the other layers' empty, so that in both the
    stacked weights are read where they lie and no layer's gigabyte is
    sliced out first."""
    out_dtype = xs.dtype if out_dtype is None else out_dtype
    if tile > 1:
        counters.inc("kernels.moe.grouped_mm.pallas")
        return grouped_mm_pallas(xs, w, count, layer, tile, out_dtype)
    counters.inc("kernels.moe.grouped_mm.xla")
    L, E = w.shape[:2]
    groups = jax.lax.dynamic_update_slice(
        jnp.zeros(L * E, jnp.int32), count, (layer * E,))
    return jax.lax.ragged_dot(xs, w.reshape((L * E,) + w.shape[2:]), groups,
                              preferred_element_type=out_dtype)


def held_expert_ffn(x, weight, expert, gu_w, down_w, first, layer=0,
                    live=None):
    """``sum_i weight_i E_i(x)`` over the chosen experts that are held
    here.  ``x [N, D]``; ``weight``/``expert [N, k]`` from the router, the
    indices over all experts; ``gu_w [L, E, D, 2F]`` (gate beside up) and
    ``down_w [L, E, F, D]`` the experts ``first .. first + E - 1`` of
    every expert layer, of which ``layer`` (it may be traced) is applied
    (:func:`grouped_mm`: the stacked weights are read where they lie and
    no layer's gigabyte is sliced out first).  ``live [N]`` bool leaves rows
    out (padding, idle slots).  Returns ``(y [N, D] float32, count [E]
    int32)``: the held part of the routed sum, and how many live tokens
    each held expert took."""
    N, k = expert.shape
    E, D, F2 = gu_w.shape[1:]
    # the kernel wants every group to start a whole tile of rows; the twin
    # packs them (a tile of 1)
    tile = (row_tile(N * k, E, x.dtype)
            if kernel_mode(D, F2 // 2, x.dtype) == "pallas" else 1)
    R = -(-(N * k + E * (tile - 1)) // tile) * tile
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
    place = group_starts(sizes, tile)[key] + within
    order = jnp.zeros(R, jnp.int32).at[place].set(
        jnp.arange(N * k, dtype=jnp.int32))
    count = sizes[:E]
    xs = x[order // k]
    gu = grouped_mm(xs, gu_w, count, layer, tile)
    h = jax.nn.silu(gu[:, :F2 // 2]) * gu[:, F2 // 2:]
    ys = grouped_mm(h, down_w, count, layer, tile, jnp.float32)
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
