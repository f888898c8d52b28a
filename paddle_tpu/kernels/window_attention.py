"""One-token decode attention over a band of a row's paged K/V, with grouped
query heads.

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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..device import on_tpu
from ._shapes import LANE, NEG_INF, check_equal, min_sublane
from .block_attention import _split
from .paged_attention import _INTERPRET

#: physical blocks folded per inner step (32 blocks of 16 tokens = 512
#: positions: 2 MB of bf16 rows of 2,048)
_BLOCKS_PER_STEP = 32


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
