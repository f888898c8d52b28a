"""Fused paged-attention decode kernel + quantized-KV helpers.

The plain-XLA paged decode (``GPT.decode_paged``) gathers every row's
logical sequence ``pool[bt] -> [B, S_max, nh, hd]`` per layer before the
attention einsum — O(B * S_max) HBM traffic per step however short the
sequences actually are.  The Pallas kernel here walks the int32 block
tables **directly over the block-pool arena** (vLLM's PagedAttention
shape, Kwon et al. SOSP '23): the grid is ``(B, max_blocks)``, the block
tables + positions ride as scalar-prefetch operands so each grid step
DMAs exactly ONE physical block ``pool[bt[row, j]]`` into VMEM, and an
online-softmax accumulator (flash-attention style) folds the block in —
the ``[B, S_max]`` gathered cache is never materialized, and blocks past
``ceil((pos+1)/bs)`` are skipped.

Quantized KV (int8 / fp8-e4m3) stores the arena 1 byte/value with one
fp32 scale per (layer, block, position) — per-token symmetric absmax,
quantized on insert by prefill/decode (see ``quantize_kv``).  Because
the scale is a per-key-token scalar it commutes with both attention
contractions, so the kernel dequantizes **in-register** by scaling the
``[1, bs]`` logit/probability rows — the int8 tiles themselves are never
expanded in HBM.

Backend selection is ``FLAGS_paged_kernel``:

* ``off`` (default) — the plain-XLA gather math in ``GPT.decode_paged``
  (the reference twin; also the CPU path, so tier-1 never needs a TPU).
* ``pallas`` — this kernel on TPU (or under interpret mode in tests).
  Off-TPU without interpret mode :func:`kernel_mode` raises: a requested
  backend that cannot run is an error, never a quiet switch to the twin.

The kernel is trace-time transparent to the serving invariants: block
tables stay int32 OPERANDS, one compiled decode program serves every
table content, and ``kernels.paged.*`` counters only move when a program
is traced — steady-state windows stay counter-silent.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.flags import define_flag, flag
from ..device import on_tpu
from ..profiler import counters
from ._shapes import check_divides, check_equal, neg_inf

_INTERPRET = [False]  # tests flip this on CPU

define_flag("FLAGS_paged_kernel", "off",
            "paged-attention decode backend: 'off' keeps the plain-XLA "
            "gather twin (reference; CPU default), 'pallas' fuses the "
            "block-table walk into one Pallas kernel on TPU")

#: serving ``kv_dtype`` string -> arena storage dtype.
KV_DTYPES = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}

#: symmetric quantization range per kv_dtype (int8 integer grid; fp8
#: e4m3 max finite value).
KV_QMAX = {"int8": 127.0, "fp8": 448.0}


def kernel_mode():
    """Validate ``FLAGS_paged_kernel`` against the platform and return
    it: the mode the decode program compiles with.  ``pallas`` without a
    TPU (and without the tests' interpret hook) raises."""
    mode = flag("FLAGS_paged_kernel")
    if mode not in ("off", "pallas"):
        raise ValueError(f"FLAGS_paged_kernel={mode!r}: want 'off' or "
                         "'pallas'")
    if mode == "pallas" and not (on_tpu() or _INTERPRET[0]):
        raise RuntimeError(
            "FLAGS_paged_kernel='pallas' needs a TPU backend, found "
            f"{jax.devices()[0].platform!r}; the Pallas decode kernel "
            "does not fall back to the XLA twin")
    return mode


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
def _dot32(a, b, tb=False):
    """Tiny fp32-accumulating dot for the per-head [1, hd] x [hd, bs]
    contractions (operands stay in their input dtype; the MXU/VPU
    accumulates fp32)."""
    cb = (1 if tb else 0,)
    return jax.lax.dot_general(a.astype(jnp.float32),
                               b.astype(jnp.float32),
                               (((1,), cb), ((), ())),
                               preferred_element_type=jnp.float32)


def _decode_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, *rest, bs, nh,
                   scale, max_blocks, quant):
    """One grid step: fold physical block ``bt[b, j]`` into row ``b``'s
    online-softmax state.  Scratch (m, l, acc) persists across the
    ``j`` (arbitrary-semantics) grid dim; the output row is written at
    the last block."""
    from jax.experimental import pallas as pl

    if quant:
        sk_ref, sv_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
        sk_ref = sv_ref = None
    b = pl.program_id(0)
    j = pl.program_id(1)
    pos = pos_ref[b]
    nb = pos // bs + 1          # blocks holding live positions

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, neg_inf(jnp.float32))
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < nb)
    def _fold():
        # key positions this block covers, vs the row's live horizon
        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        live = kpos <= pos                                   # [1, bs]
        skrow = sk_ref[0] if quant else None                 # [1, bs] f32
        svrow = sv_ref[0] if quant else None
        # per-head tiny matmuls, python-unrolled (nh is static + small);
        # a per-key-token scale commutes with the contraction, so the
        # quantized dequant is a [1, bs] row multiply — int8/fp8 tiles
        # are never expanded
        rows = []
        for hh in range(nh):
            qh = q_ref[0, hh:hh + 1]                          # [1, hd]
            kh = k_ref[0, :, hh, :]                           # [bs, hd]
            s_h = _dot32(qh, kh, tb=True) * scale             # [1, bs]
            if quant:
                s_h = s_h * skrow
            rows.append(jnp.where(live, s_h, neg_inf(jnp.float32)))
        s = jnp.concatenate(rows, axis=0)                     # [nh, bs]
        m_prev, l_prev = m_ref[...], l_ref[...]               # [nh, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                                # [nh, bs]
        l_ref[...] = l_prev * alpha + jnp.sum(p, -1, keepdims=True)
        m_ref[...] = m_new
        if quant:
            p = p * svrow
        prows = [_dot32(p[hh:hh + 1], v_ref[0, :, hh, :])     # [1, hd]
                 for hh in range(nh)]
        acc_ref[...] = acc_ref[...] * alpha + jnp.concatenate(prows, 0)

    @pl.when(j == max_blocks - 1)
    def _emit():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def paged_decode_attention(q, pool_k, pool_v, bt, pos, scale_k=None,
                           scale_v=None, *, scale):
    """Fused paged decode attention for B rows over the shared arena.

    q ``[B, nh, hd]`` (the rows' single query tokens, any float dtype),
    pool_k/pool_v ``[n_blocks, bs, nh, hd]`` (one layer's arena, already
    holding each row's newly scattered K/V at ``pos``), bt ``[B,
    max_blocks]`` int32, pos ``[B]`` int32.  With quantized pools,
    scale_k/scale_v ``[n_blocks, bs]`` fp32 are the per-token scales and
    dequantization happens in-register.  Returns fp32 ``[B, nh, hd]``.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, nh, hd = q.shape
    n_blocks, bs = pool_k.shape[0], pool_k.shape[1]
    max_blocks = bt.shape[1]
    quant = scale_k is not None
    check_equal(
        "paged_attention",
        pool_v_blocks=(pool_v.shape[0], n_blocks),
        pool_k_heads=(pool_k.shape[2], nh),
        pool_k_head_dim=(pool_k.shape[3], hd),
        table_rows=(bt.shape[0], B),
        pos_rows=(pos.shape[0], B),
        **({"scale_k_blocks": (scale_k.shape[0], n_blocks),
            "scale_k_positions": (scale_k.shape[1], bs)} if quant else {}))
    check_divides("paged_attention", block_size=(bs, 1))

    kernel = functools.partial(_decode_kernel, bs=bs, nh=nh, scale=scale,
                               max_blocks=max_blocks, quant=quant)
    blk = lambda b, j, bt_s, pos_s: (bt_s[b, j], 0, 0, 0)  # noqa: E731
    row = lambda b, j, bt_s, pos_s: (b, 0, 0)              # noqa: E731
    in_specs = [
        pl.BlockSpec((1, nh, hd), row),
        pl.BlockSpec((1, bs, nh, hd), blk),
        pl.BlockSpec((1, bs, nh, hd), blk),
    ]
    args = [q, pool_k, pool_v]
    if quant:
        # Mosaic wants a block's last two dims divisible by (8, 128) or
        # equal to the array's: a (1, bs) row of [n_blocks, bs] is
        # neither, a (1, 1, bs) slab of [n_blocks, 1, bs] is the latter
        srow = lambda b, j, bt_s, pos_s: (bt_s[b, j], 0, 0)  # noqa: E731
        in_specs += [pl.BlockSpec((1, 1, bs), srow),
                     pl.BlockSpec((1, 1, bs), srow)]
        args += [scale_k[:, None, :], scale_v[:, None, :]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nh, hd), row),
        scratch_shapes=[pltpu.VMEM((nh, 1), jnp.float32),
                        pltpu.VMEM((nh, 1), jnp.float32),
                        pltpu.VMEM((nh, hd), jnp.float32)])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nh, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=_INTERPRET[0],
        name="paged_decode_attn",
    )(bt, pos, *args)


def sharded_paged_decode_attention(mesh, axis, q, pool_k, pool_v, bt, pos,
                                   scale_k=None, scale_v=None, *, scale):
    """Head-sharded twin of :func:`paged_decode_attention`.

    The kernel's per-head matmuls are fully independent, so a pool whose
    head axis is sharded over ``axis`` (``[n_blocks, bs, nh/mp, hd]`` per
    chip) decodes with one ``shard_map`` over the heads: each chip runs
    the unmodified kernel on its head slice against the replicated block
    tables/positions/scales, and the concatenated ``[B, nh, hd]`` output
    needs no collective at all — the TP all-reduce happens later, at the
    projection contraction GSPMD partitions.
    """
    hspec = P(None, axis, None)                 # q / output: heads on dim 1
    pspec = P(None, None, axis, None)           # pools: heads on dim 2
    in_specs = [hspec, pspec, pspec, P(), P()]
    args = [q, pool_k, pool_v, bt, pos]
    if scale_k is not None:
        in_specs += [P(), P()]                  # per-token scales replicate
        args += [scale_k, scale_v]

    def _local(q_, pk_, pv_, bt_, pos_, *scales):
        sk_, sv_ = scales if scales else (None, None)
        return paged_decode_attention(q_, pk_, pv_, bt_, pos_, sk_, sv_,
                                      scale=scale)

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
