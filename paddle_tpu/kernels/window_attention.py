"""Attention over a band of a row's paged K/V, with grouped query heads: a
decode step's one token a row, and a prefill chunk's queries of one row.

A model that mixes window layers and full layers (``models/trinity.py``)
caches, for every layer, one row ``[k (n_kv x hd) ; v (n_kv x hd)]`` a token
(the ``kv_row`` seam of ``block_attention``: a block of ``bs`` tokens is one
contiguous slab and one DMA).  A full layer's row is tabled as usual: logical
block ``b`` at entry ``b`` of the row's table.  A window layer's row holds at
most the last few thousand positions, in a RING of ``n`` table entries:
logical block ``b`` at entry ``b mod n``.  Both are one walk: a query at
position ``pos`` reads positions ``lo .. pos`` of its row, logical blocks
``lo // bs .. pos // bs`` at entries ``b mod n``; for a full layer ``lo`` is 0
and ``n`` the table's width (no block wraps), for a window layer of ``W``
keys ``lo = max(0, pos - W + 1)``.  Out-of-band positions in the first and
last block are masked.  The token's own K/V are written to the pool before
the walk, so ``pos`` is inclusive.

``G = n_heads // n_kv`` query heads share a K/V head: per K/V head the query
tile is those ``G`` rows (padded to whole sublanes), one ``[G, hd] x [hd,
T]`` product a chunk of ``T`` positions.

:func:`window_decode_attn` is the Pallas walk (``name=
"window_decode_attn"``): grid ``(rows,)``, the layer, tables, positions and
lower bounds as scalar prefetch, the pool left in HBM, the band's blocks
fetched ``_BLOCKS_PER_STEP`` at a time by the kernel's own double-buffered
copies and folded into an online softmax per K/V head.  Only blocks of the
band are fetched, so its time follows the band's length: ``min(pos + 1, W)``
for a window layer, whatever ``max_seq_len`` and however long the row.
:func:`window_decode_attn_xla` is the gather twin (the CPU path and the
tests' reference).  :func:`kernel_mode` chooses between them from what the
code can observe, under the interpret hook the other walks share.

A prefill chunk of ``C`` queries at positions ``start ..`` (its rows
written to the pool first) is the same walk a block of queries at a time:
:func:`window_prefill_attn` (``name="window_prefill_attn"``) takes ``bq``
query positions a program, the band of that block (``max(a - W + 1, 0)``
for the block's first position ``a``, to its last live query) fetched like
the decode walk's and folded into an online softmax kept in VMEM, so no
score reaches HBM; only the steps that hold the band's edges are masked.
:func:`window_prefill_attn_xla` is its twin, a fold over key tiles of
``_KEY_TILE`` positions whose scores cross HBM.  The prefill entry points
count the traced calls of each form (``kernels.window_attention.prefill.
pallas`` / ``.xla``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..device import on_tpu
from ..profiler import counters
from ._shapes import LANE, NEG_INF, check_equal, min_sublane
from .block_attention import _split
from .paged_attention import _INTERPRET

#: physical blocks folded per inner step (32 blocks of 16 tokens = 512
#: positions: 2 MB of bf16 rows of 2,048)
_BLOCKS_PER_STEP = 32

#: query positions a program of the prefill kernel takes (a power of two;
#: a shorter chunk takes one block of its length)
_BLOCK_Q = 512

#: cached positions the prefill twin folds a step (a whole number of blocks)
_KEY_TILE = 1024


def kernel_mode(head_dim, row):
    """``"pallas"`` where the walk can run (the tests' interpret hook, or a
    TPU with heads of whole 128-lane tiles and a row of whole tiles), else
    ``"off"``, the XLA gather twin."""
    if _INTERPRET[0] or (on_tpu() and head_dim % LANE == 0
                         and row % LANE == 0):
        return "pallas"
    return "off"


def band(pos, window):
    """The first position a query at ``pos`` reads: ``pos - window + 1``
    (not below 0) for a window of ``window`` keys, 0 for ``None``."""
    if window is None:
        return jnp.zeros_like(pos)
    return jnp.maximum(pos - window + 1, 0)


def window_decode_attn_xla(q, pool, layer, table, pos, lo, n_kv):
    """The twin: gathers every entry of each row's table.  ``q [S, n_kv, G,
    hd]`` (scaled), ``pool [L, n_blocks, bs, row]``, ``table [S, n]`` (a
    ring of ``n`` entries), ``pos [S]`` the queries' positions, ``lo [S]``
    the bands' first positions.  Entry ``e`` holds the latest logical block
    ``b <= pos // bs`` with ``b mod n == e``.  Returns float32 ``[S, n_kv,
    G, hd]``."""
    S, _, _, hd = q.shape
    bs = pool.shape[2]
    n = table.shape[1]
    rows = pool[layer, table].reshape(S, n * bs, -1)
    k, v = _split(rows, n_kv, hd)
    cur = (pos // bs)[:, None]
    blk = cur - jnp.mod(cur - jnp.arange(n)[None, :], n)        # [S, n]
    kpos = (blk[:, :, None] * bs + jnp.arange(bs)).reshape(S, n * bs)
    seen = (kpos >= lo[:, None]) & (kpos <= pos[:, None])
    s = jnp.einsum("bngd,bknd->bngk", q.astype(rows.dtype), k,
                   preferred_element_type=jnp.float32)
    p = jax.nn.softmax(jnp.where(seen[:, None, None, :], s, NEG_INF), -1)
    return jnp.einsum("bngk,bknd->bngd", p.astype(rows.dtype), v,
                      preferred_element_type=jnp.float32)


def _kernel(layer_ref, table_ref, pos_ref, lo_ref, q_ref, pool_hbm, o_ref,
            buf, m_ref, l_ref, acc_ref, sem, *, bs, G, n, n_kv, hd):
    """One grid step = one row.  Every chunk copies ``G`` whole blocks (a
    chunk's tail past the band copies whatever its entries hold, finite
    rows that the mask removes) and waits for them with one wait of the
    chunk's size."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    layer = layer_ref[0]
    pos = pos_ref[b]
    lo = lo_ref[b]
    first = lo // bs
    T = G * bs
    nchunks = (pos // bs - first + G) // G

    def start(i, slot):
        for g in range(G):
            entry = jax.lax.rem(first + i * G + g, n)
            pltpu.make_async_copy(
                pool_hbm.at[layer, table_ref[b, entry]],
                buf.at[slot, pl.ds(g * bs, bs)], sem.at[slot]).start()

    def wait(slot):
        # the G copies signal one semaphore: wait for their sum at once
        pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                              sem.at[slot]).wait()

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    nq = q_ref.shape[2]
    col = jax.lax.broadcasted_iota(jnp.int32, (nq, T), 1)

    def fold(rows, seen):
        for h in range(n_kv):
            k = rows[:, h * hd:(h + 1) * hd]
            v = rows[:, (n_kv + h) * hd:(n_kv + h + 1) * hd]
            s = jax.lax.dot_general(q_ref[0, h], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(seen, s, NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # every chunk holds a position of the band (the first holds
            # ``lo``, a later one begins inside it), so a masked score never
            # meets a running maximum that is itself the mask
            p = jnp.exp(s - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, -1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p.astype(rows.dtype), v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    def body(i, _):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < nchunks)
        def _():
            start(i + 1, 1 - slot)

        wait(slot)
        kpos = (first + i * G) * bs + col
        fold(buf[slot], (kpos >= lo) & (kpos <= pos))
        return 0

    start(0, 0)
    jax.lax.fori_loop(0, nchunks, body, 0)
    for h in range(n_kv):
        o_ref[0, h] = acc_ref[h] / l_ref[h]


def window_decode_attn(q, pool, layer, table, pos, lo, n_kv):
    """The walk; arguments and result as :func:`window_decode_attn_xla`.
    The contraction operands are in the pool's dtype, the softmax and both
    accumulations in float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, _, Gq, hd = q.shape
    bs, row = pool.shape[2], pool.shape[3]
    n = table.shape[1]
    check_equal("window_decode_attn", kv_heads=(q.shape[1], n_kv),
                table_rows=(table.shape[0], S), pos_rows=(pos.shape[0], S),
                lo_rows=(lo.shape[0], S))
    G = min(_BLOCKS_PER_STEP, n)
    # the query tile as whole sublane tiles of the pool's dtype
    nq = -(-Gq // min_sublane(pool.dtype)) * min_sublane(pool.dtype)
    qp = jnp.pad(q.astype(pool.dtype), ((0, 0), (0, 0), (0, nq - Gq), (0, 0)))
    tile = lambda b, *_: (b, 0, 0, 0)                      # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(S,),
        in_specs=[pl.BlockSpec((1, n_kv, nq, hd), tile),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, n_kv, nq, hd), tile),
        scratch_shapes=[pltpu.VMEM((2, G * bs, row), pool.dtype),
                        pltpu.VMEM((n_kv, nq, 1), jnp.float32),
                        pltpu.VMEM((n_kv, nq, 1), jnp.float32),
                        pltpu.VMEM((n_kv, nq, hd), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))])
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, G=G, n=n, n_kv=n_kv, hd=hd),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, n_kv, nq, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_INTERPRET[0],
        name="window_decode_attn",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), table.astype(jnp.int32),
      pos.astype(jnp.int32), lo.astype(jnp.int32), qp, pool)
    return out[:, :, :Gq]


# ---------------------------------------------------------------------------
# a prefill chunk's queries over the band of its row
# ---------------------------------------------------------------------------
def window_prefill_attn_xla(qg, pool, layer, table, start, length, window):
    """The twin.  ``qg [n_kv, G, C, hd]`` (scaled, in the pool's dtype) are
    the queries of a chunk at positions ``start ..``, ``length`` of them
    live, whose rows ``pool [L, n_blocks, bs, row]`` already holds;
    ``table [n]`` is the row's table (a ring: logical block ``b`` at entry
    ``b mod n``), ``window`` the keys a query sees (``None``: every earlier
    one).  A key tile of ``_KEY_TILE`` positions at a time is folded into
    an online softmax, from the tile of the band's first position to that
    of the last live query.  Returns float32 ``[n_kv, G, C, hd]``."""
    counters.inc("kernels.window_attention.prefill.xla")
    N, G, C, hd = qg.shape
    bs, row = pool.shape[2], pool.shape[3]
    n = table.shape[0]
    tile = min(_KEY_TILE, n * bs)
    nb_tile = tile // bs
    tokpos = start + jnp.arange(C)
    first_tile = band(start, window) // tile
    end_tile = (start + length + tile - 1) // tile

    def fold(t, st):
        m, l, acc = st
        lb = t * nb_tile + jnp.arange(nb_tile)
        blocks = (jnp.take(table, lb, mode="fill", fill_value=0)
                  if window is None else table[lb % n])
        kt, vt = _split(pool[layer, blocks].reshape(tile, row), N, hd)
        s = jnp.einsum("ngqd,knd->ngqk", qg, kt,
                       preferred_element_type=jnp.float32)
        kpos = t * tile + jnp.arange(tile)
        seen = kpos[None, :] <= tokpos[:, None]
        if window is not None:
            seen = seen & (kpos[None, :] > tokpos[:, None] - window)
        s = jnp.where(seen, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        e = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        acc = acc * alpha + jnp.einsum(
            "ngqk,knd->ngqd", e.astype(vt.dtype), vt,
            preferred_element_type=jnp.float32)
        return m_new, l * alpha + e.sum(-1, keepdims=True), acc

    _, l, acc = jax.lax.fori_loop(
        first_tile, end_tile, fold,
        (jnp.full((N, G, C, 1), NEG_INF, jnp.float32),
         jnp.zeros((N, G, C, 1), jnp.float32),
         jnp.zeros((N, G, C, hd), jnp.float32)))
    return acc / l


def _prefill_kernel(layer_ref, table_ref, span_ref, q_ref, pool_hbm, o_ref,
                    buf, m_ref, l_ref, acc_ref, sem, *, bs, K, n, n_kv, hd,
                    window):
    """One grid step = one block of ``bq`` query positions, every head.
    Rows are ``(query head of the group, position)``: ``G * bq`` of them a
    K/V head, one ``[G * bq, hd] x [hd, K * bs]`` product a step.  A block
    past the chunk's live queries folds nothing and writes zeros."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    G, bq = q_ref.shape[1], q_ref.shape[2]
    R, T = G * bq, K * bs
    layer = layer_ref[0]
    start, length = span_ref[0], span_ref[1]
    a = start + pl.program_id(0) * bq
    last = jnp.minimum(a + bq, start + length) - 1
    first = band(a, window) // bs
    nsteps = jnp.where(last >= a, (last // bs - first + K) // K, 0)

    def fetch(i, slot):
        # a loop, not K unrolled copies: the program's trace and lowering,
        # paid by every chunk bucket at set-up, shrink with it
        def one(g, _):
            entry = jax.lax.rem(first + i * K + g, n)
            pltpu.make_async_copy(
                pool_hbm.at[layer, table_ref[entry]],
                buf.at[slot, pl.ds(pl.multiple_of(g * bs, bs), bs)],
                sem.at[slot]).start()
            return 0

        jax.lax.fori_loop(0, K, one, 0)

    def wait(slot):
        # the K copies signal one semaphore: wait for their sum at once
        pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                              sem.at[slot]).wait()

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def fold(slot, k0, masked):
        if masked:
            # a row is (head of the group, position): its query sits at
            # ``a + row mod bq``
            row = jax.lax.broadcasted_iota(jnp.int32, (R, T), 0)
            qpos = a + jnp.bitwise_and(row, bq - 1)
            kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (R, T), 1)
            seen = kpos <= qpos
            if window is not None:
                seen = seen & (kpos > qpos - window)

        def head(h, _):
            q = q_ref[h].reshape(R, hd)
            k = buf[slot, :, pl.ds(pl.multiple_of(h * hd, hd), hd)]
            v = buf[slot, :, pl.ds(pl.multiple_of((n_kv + h) * hd, hd),
                                   hd)]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if masked:
                s = jnp.where(seen, s, NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # a row that has seen no key yet takes exp(0) for each masked
            # score here; the first key it sees sets a maximum above the
            # mask, and alpha = 0 then clears what it took
            p = jnp.exp(s - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, -1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new
            return 0

        jax.lax.fori_loop(0, n_kv, head, 0)

    def body(i, _):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < nsteps)
        def _():
            fetch(i + 1, 1 - slot)

        wait(slot)
        k0 = (first + i * K) * bs
        # a step holds an edge of the band: a key past the block's first
        # query, or one before its last query's window
        edge = k0 + T - 1 > a
        if window is not None:
            edge = edge | (k0 < a + bq - window)

        @pl.when(edge)
        def _():
            fold(slot, k0, True)

        @pl.when(jnp.logical_not(edge))
        def _():
            fold(slot, k0, False)

        return 0

    @pl.when(nsteps > 0)
    def _():
        fetch(0, 0)

    jax.lax.fori_loop(0, nsteps, body, 0)
    for h in range(n_kv):
        l = l_ref[h]
        o_ref[h] = (acc_ref[h] / jnp.where(l > 0, l, 1.0)).reshape(G, bq, hd)


def window_prefill_attn(qg, pool, layer, table, start, length, window):
    """The kernel; arguments and result as :func:`window_prefill_attn_xla`.
    Grid: the chunk's blocks of ``bq`` positions (``_BLOCK_Q``, or the
    chunk's length rounded up to a power of two and a sublane tile); the
    layer, table, ``start`` and ``length`` as scalar prefetch; the pool
    left in HBM, the band's blocks fetched ``_BLOCKS_PER_STEP`` at a time by
    the kernel's own double-buffered copies of whole ``[bs, row]`` slabs,
    each K/V head's lanes sliced out of VMEM.  Operands in the pool's
    dtype, scores, softmax and accumulation in float32."""
    counters.inc("kernels.window_attention.prefill.pallas")
    C = qg.shape[2]
    bq = min(_BLOCK_Q,
             1 << (max(C, min_sublane(pool.dtype)) - 1).bit_length())
    return _prefill_call(qg, pool, jnp.asarray(layer, jnp.int32), table,
                         start, length, window=window, bq=bq,
                         K=min(_BLOCKS_PER_STEP, table.shape[0]),
                         interpret=_INTERPRET[0])


@functools.partial(jax.jit,
                   static_argnames=("window", "bq", "K", "interpret"))
def _prefill_call(qg, pool, layer, table, start, length, *, window, bq, K,
                  interpret):
    # jitted so that the calls of one program at the same shapes (a chunk
    # program's window layers) trace and lower the kernel once: each chunk
    # bucket pays that at set-up
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, G, C, hd = qg.shape
    bs, row = pool.shape[2], pool.shape[3]
    n = table.shape[0]
    Cp = -(-C // bq) * bq
    qp = jnp.pad(qg.astype(pool.dtype),
                 ((0, 0), (0, 0), (0, Cp - C), (0, 0)))
    R, T = G * bq, K * bs
    blk = lambda i, *_: (0, 0, i, 0)                       # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(Cp // bq,),
        in_specs=[pl.BlockSpec((N, G, bq, hd), blk),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((N, G, bq, hd), blk),
        scratch_shapes=[pltpu.VMEM((2, T, row), pool.dtype),
                        pltpu.VMEM((N, R, 1), jnp.float32),
                        pltpu.VMEM((N, R, 1), jnp.float32),
                        pltpu.VMEM((N, R, hd), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))])
    # the pipeline's two query and output blocks, the steps' buffers, the
    # softmax state (m and l padded to whole lanes) and a step's scores
    item = jnp.dtype(pool.dtype).itemsize
    need = (2 * N * R * hd * (item + 4) + 2 * T * row * item
            + N * R * (2 * LANE + hd) * 4 + 4 * R * T * 4)
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, bs=bs, K=K, n=n, n_kv=N, hd=hd,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, G, Cp, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=need + 8 * 2 ** 20),
        interpret=interpret,
        name="window_prefill_attn",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), table.astype(jnp.int32),
      jnp.stack([start, length]).astype(jnp.int32), qp, pool)
    return out[:, :, :C]
