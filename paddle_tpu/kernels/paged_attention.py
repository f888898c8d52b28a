"""Fused paged-attention decode kernel + quantized-KV helpers.

The plain-XLA paged decode (``GPT.decode_paged``) gathers every row's
logical sequence ``pool[l, bt] -> [B, S_max, nh, hd]`` per layer before
the attention einsum — O(B * S_max) HBM traffic per step however short
the sequences actually are.  The Pallas kernel here walks the int32
block tables **directly over the stacked block-pool arena** (vLLM's
PagedAttention shape, Kwon et al. SOSP '23): the grid is ``(B,)``, the
layer index, block tables and positions ride as scalar-prefetch
operands, the pool stays in HBM, and each row loops over its LIVE
blocks only, several per step, fetched by the kernel's own
double-buffered async copies and folded into an online-softmax
accumulator (flash-attention style) — the ``[B, S_max]`` gathered cache
is never materialized, and a block past ``pos // bs`` costs neither a
loop step nor a DMA.

Quantized KV (int8 / fp8-e4m3) stores the arena 1 byte/value with one
fp32 scale per (layer, block, position) — per-token symmetric absmax,
quantized on insert by prefill/decode (see ``quantize_kv``).  Because
the scale is a per-key-token scalar it commutes with both attention
contractions, so the kernel dequantizes **in-register** by scaling the
``[1, bs]`` logit/probability rows — the int8 tiles themselves are never
expanded in HBM.

Backend selection is :func:`kernel_mode`, from what the code can
observe and with no switch to set:

* ``pallas`` — this kernel, on a TPU whose compiler can tile the pool's
  ``[nh, hd]`` slabs (``hd`` a multiple of 128 lanes, ``nh`` of 8
  sublanes: the kernel's own DMAs move whole tiles), and under the
  tests' interpret hook.
* ``off`` — the plain-XLA gather math in ``GPT.decode_paged`` everywhere
  else: the CPU path (tier-1 never needs a TPU) and the tests' reference.

The kernel is trace-time transparent to the serving invariants: block
tables stay int32 OPERANDS, one compiled decode program serves every
table content, and ``kernels.paged.*`` counters only move when a program
is traced — steady-state windows stay counter-silent.
"""

from __future__ import annotations

import functools
import importlib
import threading

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..device import on_tpu
from ..profiler import counters
from ._shapes import LANE, check_divides, check_equal, neg_inf

_INTERPRET = [False]  # tests flip this on CPU

#: serving ``kv_dtype`` string -> arena storage dtype.
KV_DTYPES = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}

#: symmetric quantization range per kv_dtype (int8 integer grid; fp8
#: e4m3 max finite value).
KV_QMAX = {"int8": 127.0, "fp8": 448.0}


def kernel_mode(nh, hd):
    """The decode attention a program over ``nh`` heads of ``hd``
    (per chip) compiles with: ``"pallas"`` where the kernel can run —
    under the tests' interpret hook, or on a TPU when the pool's
    ``[nh, hd]`` slabs are whole (8, 128) tiles, which the kernel's
    block DMAs need — else ``"off"``, the XLA gather twin."""
    if _INTERPRET[0] or (on_tpu() and hd % LANE == 0 and nh % 8 == 0):
        return "pallas"
    return "off"


def pool_heads(nh, hd):
    """Heads along a single chip's pool for ``nh`` heads of ``hd``: ``nh``
    rounded up to whole sublane tiles where the lanes are whole
    (``hd % 128 == 0``), so that the kernel runs whatever the head count
    (30 heads of 128 are stored as 32); ``nh`` elsewhere.  The padded
    heads hold zeros and never reach an output (:func:`pad_heads`)."""
    return -(-nh // 8) * 8 if hd % LANE == 0 else nh


def pad_heads(x, nhp):
    """``x [..., nh, hd]`` with zero heads appended up to ``nhp``; ``x``
    itself when there is nothing to append."""
    nh = x.shape[-2]
    if nhp == nh:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, nhp - nh), (0, 0)])


def head_padding(pool, nh):
    """``(pad, unpad)`` for a model of ``nh`` heads over ``pool [..., nhp,
    hd]``, which may store more heads than the model has (whole tiles:
    :func:`pool_heads`): ``pad`` appends the zero heads to what is written
    or asked, ``unpad`` drops them from what is read.  Both are the
    identity where the counts agree."""
    nhp = pool.shape[-2]
    return (lambda t: pad_heads(t, nhp),
            lambda t: t if nhp == nh else t[..., :nh, :])


_PRELOADED = [False]


def preload():
    """Start importing Pallas on a background thread.  The import pulls
    in every Mosaic dialect (0.8 s on a desktop core, 1.5 s on a v5e's
    host) and would otherwise sit inside the decode program's first
    trace, in front of the first token.  An engine that resolved to the
    kernel calls this at construction; the prefill programs it builds
    first leave the GIL often enough to hide about a third of it
    (``PERF.md``, PR 27).  A model whose experts run through a kernel
    (``kernels.moe.preload``) calls it before it draws its weights, which
    hides the rest.  The kernel's own import then finds the module loaded
    or waits on its lock; a second call does nothing."""
    if _PRELOADED[0]:
        return
    _PRELOADED[0] = True
    threading.Thread(target=importlib.import_module,
                     args=("jax.experimental.pallas.tpu",),
                     name="pallas-import", daemon=True).start()


# ---------------------------------------------------------------------------
# quantized-KV insert/load helpers (shared by prefill, decode, and the
# plain-XLA reference twin)
# ---------------------------------------------------------------------------
def quantize_kv(x, kv_dtype):
    """Per-token symmetric quantization of ``x[..., nh, hd]``: returns
    ``(q[..., nh, hd] in KV_DTYPES[kv_dtype], scale[...] fp32)`` where
    ``scale`` is one absmax-derived scalar per leading index (token).
    All-zero tokens (padded prefill tail) quantize to zeros with a unit
    epsilon scale."""
    qmax = KV_QMAX[kv_dtype]
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=(-2, -1))
    scale = jnp.maximum(amax, 1e-8) / qmax
    y = xf / scale[..., None, None]
    if kv_dtype == "int8":
        q = jnp.clip(jnp.round(y), -qmax, qmax).astype(jnp.int8)
    else:
        q = jnp.clip(y, -qmax, qmax).astype(KV_DTYPES[kv_dtype])
    return q, scale


def kv_dtype_of(dtype):
    """Map an arena storage dtype back to its ``kv_dtype`` name (None for
    unquantized full/half-precision pools)."""
    dt = jnp.dtype(dtype)
    for name, d in KV_DTYPES.items():
        if jnp.dtype(d) == dt:
            return name
    return None


def dequantize_kv(q, scale):
    """Inverse of :func:`quantize_kv`: fp32 values from quantized tiles
    ``q[..., nh, hd]`` and per-token scales ``scale[...]``."""
    return q.astype(jnp.float32) * scale[..., None, None]


# ---------------------------------------------------------------------------
# the fused decode kernel
# ---------------------------------------------------------------------------
#: physical blocks folded per inner step (8 blocks of 16 tokens = 128
#: positions: 512 KB of bf16 K and of V at 16 heads of 128)
_BLOCKS_PER_STEP = 8


def _decode_kernel(layer_ref, bt_ref, pos_ref, q_ref, k_hbm, v_hbm, *rest,
                   bs, nh, G, quant):
    """One grid step = one row: walk the row's live blocks ``0 .. pos //
    bs`` in chunks of ``G``, each chunk fetched from the stacked pool
    (left in HBM) by the kernel's own double-buffered async copies and
    folded into an online-softmax state carried in registers.  A dead
    block costs neither a loop step nor a DMA.

    All heads share one contraction: the chunk read as ``[T * nh, hd]``
    (free: nh fills whole sublane tiles) against q gives every (head,
    head') pair; the own-head diagonal is selected before the softmax
    and is all that P.V sums, so both products are plain ``[nh, .]``
    matmuls with K/V in the dtype they are stored in."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if quant:
        sk_ref, sv_ref, o_ref, kbuf, vbuf, sem = rest
    else:
        o_ref, kbuf, vbuf, sem = rest
    b = pl.program_id(0)
    layer = layer_ref[0]
    pos = pos_ref[b]
    nb = pos // bs + 1              # blocks holding live positions
    nchunks = (nb + G - 1) // G
    hd = q_ref.shape[-1]
    W = G * bs * nh                 # logit columns a chunk: (position, head)
    cd = q_ref.dtype                # contraction operand dtype
    fmin = neg_inf(jnp.float32)

    # stale rows of a partly filled chunk meet p == 0 in P.V: they must
    # be finite, which old K/V is and never-written VMEM need not be
    @pl.when(b == 0)
    def _zero():
        vbuf[...] = jnp.zeros_like(vbuf)

    def dma(i, slot, start):
        """Start, or wait for, the copies of chunk ``i``'s live blocks."""
        def block(g, _):
            blk = bt_ref[b, i * G + g]
            for s, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                cp = pltpu.make_async_copy(
                    hbm.at[layer, blk],
                    buf.at[slot, pl.ds(pl.multiple_of(g * bs, bs), bs)],
                    sem.at[s, slot])
                cp.start() if start else cp.wait()
            return 0

        jax.lax.fori_loop(0, jnp.minimum(G, nb - i * G), block, 0)

    q = q_ref[0]                                              # [nh, hd]
    col = jax.lax.broadcasted_iota(jnp.int32, (nh, W), 1)
    own = jax.lax.rem(col, nh) == jax.lax.broadcasted_iota(
        jnp.int32, (nh, W), 0)

    def body(i, carry):
        m_prev, l_prev, acc = carry
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < nchunks)
        def _():
            dma(i + 1, 1 - slot, True)

        dma(i, slot, False)
        s = jax.lax.dot_general(
            q, kbuf[slot].astype(cd).reshape(W, hd),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if quant:
            # a per-key-token scale commutes with both contractions:
            # dequantization is a row multiply of logits / probabilities
            cols = pl.ds(pl.multiple_of(i * W, W), W)
            s = s * sk_ref[0, :, cols]
        live = own & (col < (pos + 1 - i * G * bs) * nh)
        s = jnp.where(live, s, fmin)
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, -1, keepdims=True)
        if quant:
            p = p * sv_ref[0, :, cols]
        pv = jnp.dot(p.astype(cd), vbuf[slot].astype(cd).reshape(W, hd),
                     preferred_element_type=jnp.float32)
        return m_new, l_new, acc * alpha + pv

    dma(0, 0, True)
    _, l_fin, acc = jax.lax.fori_loop(
        0, nchunks, body,
        (jnp.full((nh, 1), fmin, jnp.float32),
         jnp.zeros((nh, 1), jnp.float32),
         jnp.zeros((nh, hd), jnp.float32)))
    o_ref[0] = acc / l_fin


def paged_decode_attention(q, pool_k, pool_v, layer, bt, pos, scale_k=None,
                           scale_v=None, *, scale):
    """Fused paged decode attention for B rows over the stacked arena.

    q ``[B, nh, hd]`` (the rows' single query tokens), pool_k/pool_v
    ``[L, n_blocks, bs, nh, hd]`` (the whole arena, layer ``layer``
    already holding each row's newly scattered K/V at ``pos``; it stays
    in HBM and only the live blocks of that layer are read), layer int32
    scalar, bt ``[B, max_blocks]`` int32, pos ``[B]`` int32.  With
    quantized pools, scale_k/scale_v ``[L, n_blocks, bs]`` fp32 are the
    per-token scales and dequantization happens in-register.  Contraction
    operands are in the pool's dtype (q's for a quantized pool), the
    accumulation and the softmax in fp32.  Returns fp32 ``[B, nh, hd]``.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, nh, hd = q.shape
    n_blocks, bs = pool_k.shape[1], pool_k.shape[2]
    max_blocks = bt.shape[1]
    quant = scale_k is not None
    check_equal(
        "paged_attention",
        pool_v_layers=(pool_v.shape[0], pool_k.shape[0]),
        pool_v_blocks=(pool_v.shape[1], n_blocks),
        pool_k_heads=(pool_k.shape[3], nh),
        pool_k_head_dim=(pool_k.shape[4], hd),
        table_rows=(bt.shape[0], B),
        pos_rows=(pos.shape[0], B),
        **({"scale_k_blocks": (scale_k.shape[1], n_blocks),
            "scale_k_positions": (scale_k.shape[2], bs)} if quant else {}))
    check_divides("paged_attention", block_size=(bs, 1))

    cd = q.dtype if quant else pool_k.dtype
    G = min(_BLOCKS_PER_STEP, max_blocks)
    kernel = functools.partial(_decode_kernel, bs=bs, nh=nh, G=G,
                               quant=quant)
    row = lambda b, *_: (b, 0, 0)                          # noqa: E731
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, nh, hd), row), hbm, hbm]
    args = [(q * scale).astype(cd), pool_k, pool_v]
    if quant:
        # the rows' scales in logical order, one column per column of
        # the kernel's logits: [B, 1, cols] rows sliced chunk by chunk
        # (B * S fp32 a layer, next to B * S * nh * hd of tiles)
        cols = -(-max_blocks // G) * G * bs * nh
        for sc in (scale_k, scale_v):
            r = jnp.repeat(sc[layer, bt].reshape(B, -1), nh, axis=1)
            args.append(jnp.pad(r, ((0, 0), (0, cols - r.shape[1])))
                        [:, None, :])
        in_specs += [pl.BlockSpec((1, 1, cols), row)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nh, hd), row),
        scratch_shapes=[pltpu.VMEM((2, G * bs, nh, hd), pool_k.dtype),
                        pltpu.VMEM((2, G * bs, nh, hd), pool_v.dtype),
                        pltpu.SemaphoreType.DMA((2, 2))])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nh, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_INTERPRET[0],
        name="paged_decode_attn",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), bt, pos, *args)


def sharded_paged_decode_attention(mesh, axis, q, pool_k, pool_v, layer, bt,
                                   pos, scale_k=None, scale_v=None, *,
                                   scale):
    """Head-sharded twin of :func:`paged_decode_attention`.

    The kernel's heads are fully independent, so a pool whose head axis
    is sharded over ``axis`` (``[L, n_blocks, bs, nh/mp, hd]`` per chip)
    decodes with one ``shard_map`` over the heads: each chip runs the
    unmodified kernel on its head slice against the replicated block
    tables/positions/scales, and the concatenated ``[B, nh, hd]`` output
    needs no collective at all — the TP all-reduce happens later, at the
    projection contraction GSPMD partitions.
    """
    hspec = P(None, axis, None)                 # q / output: heads on dim 1
    pspec = P(None, None, None, axis, None)     # pools: heads on dim 3
    in_specs = [hspec, pspec, pspec, P(), P(), P()]
    args = [q, pool_k, pool_v, layer, bt, pos]
    if scale_k is not None:
        in_specs += [P(), P()]                  # per-token scales replicate
        args += [scale_k, scale_v]

    def _local(q_, pk_, pv_, layer_, bt_, pos_, *scales):
        sk_, sv_ = scales if scales else (None, None)
        return paged_decode_attention(q_, pk_, pv_, layer_, bt_, pos_, sk_,
                                      sv_, scale=scale)

    fn = jax.shard_map(_local, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=hspec, check_vma=False)
    return fn(*args)


def note_program(backend):
    """Trace-time breadcrumb: which backend a paged decode program was
    compiled with (never moves in a steady-state window)."""
    if backend == "pallas":
        counters.inc("kernels.paged.pallas_programs")
    else:
        counters.inc("kernels.paged.xla_fallbacks")
