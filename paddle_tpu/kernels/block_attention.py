"""Attention of one block of queries against a row's paged prefix and the
block's own fresh K/V, with grouped query heads.

A model that decodes by blocks (``models/sdar.py``: diffusion over blocks
of ``B`` tokens) runs, each launch, the ``B`` positions of every running
row's current block.  A position sees the row's committed prefix (every
position below the block's first, cached in the pool) and ALL ``B``
positions of its own block, whose keys and values are this launch's own
and are not in the pool: a block's K/V change from pass to pass until its
last token is revealed, and only the pass after that writes them.

The pool is the ``kv_row`` seam's: ``[L, n_blocks, bs, row]`` with one
row a token, ``[k (n_kv x hd) ; v (n_kv x hd)]`` side by side and zeros up
to whole 128-lane tiles (``mla_attention.pool_row``), so a block of
``bs`` tokens is one contiguous slab and one DMA whatever the number of
K/V heads (4 heads of 128 are 1,024 lanes, whole tiles; stored by head
they would pad to 8 sublanes, twice the bytes).

``G = n_heads // n_kv`` query heads share a K/V head, so per K/V head the
query tile is ``G * B`` rows (8 heads x 4 positions = 32 rows of 128
lanes): one ``[32, 128] x [128, T]`` product a chunk of ``T`` cached
positions in place of ``paged_decode_attn``'s one row a head.

:func:`block_decode_attn` is the Pallas walk (``name=
"block_decode_attn"``): grid ``(rows,)``, the layer, block tables and
first positions as scalar prefetch, the pool left in HBM, each row's live
blocks fetched ``_BLOCKS_PER_STEP`` at a time by the kernel's own
double-buffered copies and folded into an online softmax per K/V head;
the block's own K/V are folded in last.  A block past the row's live
length costs nothing, so the time follows the live lengths, not
``max_seq_len``.  :func:`block_decode_attn_xla` is the gather twin: the
CPU path and the tests' reference.  :func:`kernel_mode` chooses between
them from what the code can observe, under the interpret hook the other
walks share (``paged_attention._INTERPRET``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..device import on_tpu
from ._shapes import LANE, NEG_INF, check_equal, min_sublane
from .paged_attention import _INTERPRET

#: physical blocks folded per inner step (32 blocks of 16 tokens = 512
#: positions: 1 MB of bf16 rows of 1,024)
_BLOCKS_PER_STEP = 32


def kernel_mode(n_heads, n_kv, head_dim, block_length):
    """``"pallas"`` where the walk can run (the tests' interpret hook, or
    a TPU with whole tiles: heads of whole 128-lane tiles, a query tile
    of whole sublanes), else ``"off"``, the XLA gather twin."""
    if _INTERPRET[0] or (on_tpu() and head_dim % LANE == 0
                         and (n_heads // n_kv * block_length) % 8 == 0):
        return "pallas"
    return "off"


def _split(rows, n_kv, hd):
    """``rows [..., row]`` -> ``(k, v)``, each ``[..., n_kv, hd]``."""
    lead = rows.shape[:-1]
    return (rows[..., :n_kv * hd].reshape(lead + (n_kv, hd)),
            rows[..., n_kv * hd:2 * n_kv * hd].reshape(lead + (n_kv, hd)))


def block_decode_attn_xla(q, new, pool, layer, bt, pos, n_kv):
    """The twin: gathers each row's whole logical sequence.  ``q [S, n_kv,
    G * B, hd]`` (scaled and turned; row ``g * B + p`` of a tile is query
    head ``g`` of its group at block position ``p``), ``new [S, B, row]``
    the block's own lines, ``pool [L, n_blocks, bs, row]``, ``bt [S,
    max_blocks]``, ``pos [S]`` the blocks' first positions.  Returns
    float32 ``[S, n_kv, G * B, hd]``."""
    S, _, _, hd = q.shape
    T = bt.shape[1] * pool.shape[2]
    rows = pool[layer, bt].reshape(S, T, -1)
    k, v = _split(jnp.concatenate([rows, new.astype(rows.dtype)], 1),
                  n_kv, hd)
    s = jnp.einsum("bnqd,bknd->bnqk", q.astype(rows.dtype), k,
                   preferred_element_type=jnp.float32)
    live = jnp.concatenate(
        [jnp.arange(T)[None, :] < pos[:, None],
         jnp.ones((S, new.shape[1]), bool)], 1)
    p = jax.nn.softmax(jnp.where(live[:, None, None, :], s, NEG_INF), -1)
    return jnp.einsum("bnqk,bknd->bnqd", p.astype(rows.dtype), v,
                      preferred_element_type=jnp.float32)


def _kernel(layer_ref, bt_ref, pos_ref, q_ref, new_ref, pool_hbm, o_ref, buf,
            m_ref, l_ref, acc_ref, sem, *, bs, G, n_kv, hd, n_new):
    """One grid step = one row.  Every chunk copies ``G`` whole blocks
    (the table is padded with the trash block, so a chunk's dead tail is
    a copy of finite rows that the mask removes) and waits for them with
    one wait of the chunk's size."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    layer = layer_ref[0]
    pos = pos_ref[b]                  # cached positions: 0 .. pos - 1
    T = G * bs
    nchunks = (pos + T - 1) // T

    def start(i, slot):
        for g in range(G):
            pltpu.make_async_copy(
                pool_hbm.at[layer, bt_ref[b, i * G + g]],
                buf.at[slot, pl.ds(g * bs, bs)], sem.at[slot]).start()

    def wait(slot):
        # the G copies signal one semaphore: wait for their sum at once
        pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                              sem.at[slot]).wait()

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    nq = q_ref.shape[2]

    def fold(rows, seen):
        """``rows [K, row]`` of which ``seen [nq, K]`` bool count."""
        for h in range(n_kv):
            k = rows[:, h * hd:(h + 1) * hd]
            v = rows[:, (n_kv + h) * hd:(n_kv + h + 1) * hd]
            s = jax.lax.dot_general(q_ref[0, h], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(seen, s, NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # every fold has a key that every query sees (a live chunk's
            # first position, the block's own first line), so a masked
            # score never meets a running maximum that is itself the mask
            p = jnp.exp(s - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, -1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p.astype(rows.dtype), v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    col = jax.lax.broadcasted_iota(jnp.int32, (nq, T), 1)

    def body(i, _):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < nchunks)
        def _():
            start(i + 1, 1 - slot)

        wait(slot)
        fold(buf[slot], col < pos - i * T)
        return 0

    @pl.when(nchunks > 0)
    def _():
        start(0, 0)

    jax.lax.fori_loop(0, nchunks, body, 0)
    # the block's own lines, last: every position sees all of them (the
    # lines past ``n_new`` pad the tile to whole sublanes)
    new = new_ref[0]
    fold(new, jax.lax.broadcasted_iota(
        jnp.int32, (nq, new.shape[0]), 1) < n_new)
    for h in range(n_kv):
        o_ref[0, h] = acc_ref[h] / l_ref[h]


def block_decode_attn(q, new, pool, layer, bt, pos, n_kv):
    """The walk; arguments and result as :func:`block_decode_attn_xla`.
    The contraction operands are in the pool's dtype, the softmax and both
    accumulations in float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, _, nq, hd = q.shape
    bs, row = pool.shape[2], pool.shape[3]
    n_new = new.shape[1]
    check_equal("block_decode_attn", line=(new.shape[2], row),
                kv_heads=(q.shape[1], n_kv), table_rows=(bt.shape[0], S),
                pos_rows=(pos.shape[0], S))
    G = min(_BLOCKS_PER_STEP, bt.shape[1])
    # whole chunks of G table entries: what is appended is the trash block
    bt = jnp.pad(bt, ((0, 0), (0, -bt.shape[1] % G)))
    # the block's own lines as whole sublane tiles of the pool's dtype
    new = jnp.pad(new.astype(pool.dtype),
                  ((0, 0), (0, -n_new % min_sublane(pool.dtype)), (0, 0)))
    tile = lambda b, *_: (b, 0, 0, 0)                      # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[pl.BlockSpec((1, n_kv, nq, hd), tile),
                  pl.BlockSpec((1,) + new.shape[1:], lambda b, *_: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, n_kv, nq, hd), tile),
        scratch_shapes=[pltpu.VMEM((2, G * bs, row), pool.dtype),
                        pltpu.VMEM((n_kv, nq, 1), jnp.float32),
                        pltpu.VMEM((n_kv, nq, 1), jnp.float32),
                        pltpu.VMEM((n_kv, nq, hd), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))])
    return pl.pallas_call(
        functools.partial(_kernel, bs=bs, G=G, n_kv=n_kv, hd=hd,
                          n_new=n_new),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, n_kv, nq, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_INTERPRET[0],
        name="block_decode_attn",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), bt, pos,
      q.astype(pool.dtype), new, pool)
