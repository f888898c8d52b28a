"""Absorbed latent-attention (MLA) decode over a paged latent pool.

Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434, section 2.1)
caches ONE row per token and layer, ``[c_kv (R) ; k_pe (d_r)]``, shared by
every query head: there is no head axis to page.  In the absorbed form a
decode step never rebuilds per-head keys or values: with ``q_abs[h] =
W_UK[h]^T q_nope[h]`` the score of head ``h`` against a cached row is
``q_abs[h] . c_kv + q_pe[h] . k_pe``, and the head's output is ``W_UV[h]``
applied to ``sum_t p_t c_kv_t``.  Both ends are ordinary matmuls outside
this file; what is here is the middle: ``H`` query rows of width ``R +
d_r`` against the rows of one request, weighted sums of their first ``R``
values back.

A prefill chunk attends in the materialised form: many queries against
per-head keys and values that XLA up-projects from a tile of cached rows.
Left to XLA, each tile's ``[H, C, tile]`` float32 scores cross HBM
several times (three fusions of 30 ms each a 1,024-token chunk over an 8k
prefix; ``PERF.md``, PR 32); :func:`mla_prefill_fold` folds one tile into
an online-softmax state that is handed from tile to tile, with the scores
in VMEM only (``name="mla_prefill_attn"``); :func:`mla_prefill_fold_xla`
is its twin.

The pool is ``[L, n_blocks, bs, row]`` with ``row`` the latent width
rounded up to whole 128-lane tiles (:func:`pool_row`: 576 is stored as
640, the tail zeros), so a block is one contiguous slab and one DMA.

:func:`mla_decode_attn` is the Pallas walk (``name="mla_decode_attn"``):
grid ``(B,)``, the layer, block tables and positions as scalar prefetch,
the pool left in HBM, each row's live blocks fetched ``G`` at a time by the
kernel's own double-buffered copies and folded into an online softmax, as
``paged_attention``'s walk does for per-head K/V: a block past ``pos //
bs`` costs nothing, so the time follows the live lengths, not
``max_seq_len``.  :func:`mla_decode_attn_xla` is the gather twin: the CPU
path and the tests' reference.  :func:`kernel_mode` chooses between them
from what the code can observe, like ``paged_attention.kernel_mode``, and
under the same interpret hook (``paged_attention._INTERPRET``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..device import on_tpu
from ._shapes import LANE, NEG_INF, check_equal
from .paged_attention import _INTERPRET

#: physical blocks folded per inner step (32 blocks of 16 tokens = 512
#: positions: 640 KB of bf16 rows of 640)
_BLOCKS_PER_STEP = 32


def pool_row(width):
    """Values a latent row takes in the pool: ``width`` rounded up to
    whole lane tiles."""
    return -(-int(width) // LANE) * LANE


def kernel_mode(n_heads, row):
    """``"pallas"`` where the walk can run (the tests' interpret hook, or
    a TPU with whole tiles: ``row`` a multiple of 128 lanes, ``n_heads``
    of 8 sublanes), else ``"off"``, the XLA gather twin."""
    if _INTERPRET[0] or (on_tpu() and row % LANE == 0 and n_heads % 8 == 0):
        return "pallas"
    return "off"


def mla_decode_attn_xla(q, pool, layer, bt, pos, rank):
    """The twin: gathers each row's whole logical sequence.  ``q [B, H,
    row]`` (scaled; zeros past the latent width), ``pool [L, n_blocks, bs,
    row]``, ``bt [B, max_blocks]``, ``pos [B]``; returns float32 ``[B, H,
    rank]``, the softmax-weighted sums of the rows' first ``rank``
    values."""
    B = q.shape[0]
    S = bt.shape[1] * pool.shape[2]
    rows = pool[layer, bt].reshape(B, S, -1)
    s = jnp.einsum("bhw,bkw->bhk", q.astype(rows.dtype), rows,
                   preferred_element_type=jnp.float32)
    live = jnp.arange(S)[None, :] <= pos[:, None]
    p = jax.nn.softmax(jnp.where(live[:, None, :], s, NEG_INF), axis=-1)
    return jnp.einsum("bhk,bkc->bhc", p.astype(rows.dtype),
                      rows[..., :rank], preferred_element_type=jnp.float32)


def _decode_kernel(layer_ref, bt_ref, pos_ref, q_ref, pool_hbm, o_ref, buf,
                   m_ref, l_ref, acc_ref, sem, *, bs, G, rank):
    """One grid step = one row.  Every chunk copies ``G`` whole blocks
    (the table is padded with the trash block, so a chunk's dead tail is
    a copy of finite rows that the mask removes) and waits for them with
    one wait of the chunk's size."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    layer = layer_ref[0]
    pos = pos_ref[b]
    T = G * bs
    nchunks = (pos // bs + G) // G          # chunks holding live positions

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
    q = q_ref[0]                                              # [H, row]
    col = jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], T), 1)

    def body(i, _):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < nchunks)
        def _():
            start(i + 1, 1 - slot)

        wait(slot)
        rows = buf[slot]                                      # [T, row]
        s = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(col <= pos - i * T, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(rows.dtype), rows[:, :rank],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return 0

    start(0, 0)
    jax.lax.fori_loop(0, nchunks, body, 0)
    o_ref[0] = acc_ref[...] / l_ref[...]


def mla_decode_attn(q, pool, layer, bt, pos, rank):
    """The walk; arguments and result as :func:`mla_decode_attn_xla`.  The
    contraction operands are in the pool's dtype, the softmax and both
    accumulations in float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, row = q.shape
    bs = pool.shape[2]
    check_equal("mla_decode_attn", pool_row=(pool.shape[3], row),
                table_rows=(bt.shape[0], B), pos_rows=(pos.shape[0], B))
    G = min(_BLOCKS_PER_STEP, bt.shape[1])
    # whole chunks of G table entries: what is appended is the trash block
    bt = jnp.pad(bt, ((0, 0), (0, -bt.shape[1] % G)))
    line = lambda b, *_: (b, 0, 0)                         # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, H, row), line),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, rank), line),
        scratch_shapes=[pltpu.VMEM((2, G * bs, row), pool.dtype),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, rank), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))])
    return pl.pallas_call(
        functools.partial(_decode_kernel, bs=bs, G=G, rank=rank),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_INTERPRET[0],
        name="mla_decode_attn",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), bt, pos,
      q.astype(pool.dtype), pool)


# ---------------------------------------------------------------------------
# the prefill chunk's fold of one tile of keys
# ---------------------------------------------------------------------------
#: query rows and keys per step of the prefill kernel
_BLOCK_Q = 512
_BLOCK_K = 512


def mla_prefill_fold_xla(q, k, v, q0, key0, state):
    """Fold ``K`` keys into the online-softmax ``state = (m [H, C, 1], l
    [H, C, 1], acc [H, C, d_v])`` of ``C`` queries: ``q [H, C, d]``
    (scaled) at positions ``q0 ..``, ``k [H, K, d]`` and ``v [H, K, d_v]``
    at positions ``key0 ..``; a query sees the keys at or before its own
    position.  The twin: the scores cross HBM."""
    m, l, acc = state
    s = jnp.einsum("hqd,hkd->hqk", q, k, preferred_element_type=jnp.float32)
    qpos = q0 + jnp.arange(q.shape[1])
    kpos = key0 + jnp.arange(k.shape[1])
    s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, NEG_INF)
    m_new = jnp.maximum(m, s.max(-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    acc = acc * alpha + jnp.einsum("hqk,hkd->hqd", p.astype(v.dtype), v,
                                   preferred_element_type=jnp.float32)
    return m_new, l * alpha + p.sum(-1, keepdims=True), acc


def _prefill_kernel(off_ref, q_ref, k_ref, v_ref, m_in, l_in, acc_in,
                    m_out, l_out, acc_out, *, bk):
    from jax.experimental import pallas as pl

    bq = q_ref.shape[1]
    q0 = off_ref[0] + pl.program_id(1) * bq
    key0 = off_ref[1]
    # key blocks that hold a position at or before this block's last query
    nk = jnp.clip((q0 + bq - 1 - key0) // bk + 1, 0, k_ref.shape[1] // bk)
    q = q_ref[0]
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    def body(j, carry):
        m, l, acc = carry
        rows = pl.ds(pl.multiple_of(j * bk, bk), bk)
        k, v = k_ref[0, rows], v_ref[0, rows]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(key0 + j * bk + col <= qpos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, -1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        return (m_new, l * alpha + jnp.sum(p, -1, keepdims=True),
                acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                      preferred_element_type=jnp.float32))

    m, l, acc = jax.lax.fori_loop(0, nk, body,
                                  (m_in[0], l_in[0], acc_in[0]))
    m_out[0], l_out[0], acc_out[0] = m, l, acc


def mla_prefill_fold(q, k, v, q0, key0, state):
    """The kernel; arguments and result as :func:`mla_prefill_fold_xla`.
    Grid ``(H, C / block)``; a program keeps one head's keys of the tile
    in VMEM and walks the blocks its queries can see; the state is
    updated in place.  Every query has seen the key at position 0 before
    any block that it sees nothing of (tiles are folded in order), so a
    masked score never meets a running maximum that is itself the mask."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    H, C, d = q.shape
    K, dv = k.shape[1], v.shape[2]
    bq = min(_BLOCK_Q, C)
    bk = next(b for b in range(min(_BLOCK_K, K), 0, -1) if K % b == 0)
    check_equal("mla_prefill_attn", key_heads=(k.shape[0], H),
                value_heads=(v.shape[0], H), value_keys=(v.shape[1], K),
                query_blocks=(C % bq, 0))
    qb = lambda h, i, *_: (h, i, 0)                        # noqa: E731
    kb = lambda h, i, *_: (h, 0, 0)                        # noqa: E731
    carry = [pl.BlockSpec((1, bq, 1), qb), pl.BlockSpec((1, bq, 1), qb),
             pl.BlockSpec((1, bq, dv), qb)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(H, C // bq),
        in_specs=[pl.BlockSpec((1, bq, d), qb), pl.BlockSpec((1, K, d), kb),
                  pl.BlockSpec((1, K, dv), kb)] + carry,
        out_specs=carry)
    return pl.pallas_call(
        functools.partial(_prefill_kernel, bk=bk),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in state],
        input_output_aliases={4: 0, 5: 1, 6: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=_INTERPRET[0],
        name="mla_prefill_attn",
    )(jnp.stack([q0, key0]).astype(jnp.int32), q, k, v, *state)
