"""Flash attention — Pallas TPU kernel with custom VJP.

Reference analogue: phi/kernels/gpu/flash_attn_kernel.cu (wrapping the
flash-attn CUDA lib).  TPU-native design: online-softmax tiled attention where
q/k/v blocks stream HBM→VMEM and the two matmuls per tile hit the MXU;
backward recomputes attention probabilities per tile (flash-attention-2
style), avoiding O(S^2) residuals.

Perf notes (v5e measurements): Mosaic grid-step overhead is ~2.4us/program,
so at short sequence lengths a naive (b, h, s/128) grid is overhead-bound —
attention at GPT-125M shapes was ~65% of forward wall-clock for ~6% of the
FLOPs.  The kernels therefore process BH heads per grid step (python-unrolled
head loop) with adaptive q/k block sizes, cutting the program count ~16x.

Layout: [B, S, H, D] (paddle convention) — internally [B, H, S, D].
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from ..device import on_tpu
from ..profiler import counters
from ._shapes import NEG_INF, check_divides

_INTERPRET = [False]  # tests flip this on CPU


def reference_attention(q, k, v, causal=False, scale=None):
    """jnp reference ([B, S, H, D]); also the off-TPU fallback."""
    d = q.shape[-1]
    sc = scale if scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32) * sc,
                        k.astype(jnp.float32))
    if causal:
        s, t = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((s, t), dtype=bool), t - s)
        logits = jnp.where(mask, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhst,bthd->bshd", p.astype(v.dtype), v)


def _round_to_divisor(block, s):
    """Largest multiple of 128 that is <= block and divides s (s % 128 == 0,
    so 128 always qualifies) — blocks that don't divide s would silently skip
    key blocks / leave query rows unwritten."""
    block = max(128, min(block, s))
    block -= block % 128
    while s % block:
        block -= 128
    return block


def _env_block(name, default):
    """Read a block-size override env var; fail loudly on junk values."""
    import os
    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        # ptlint: disable=PT001 reason="raw is an environment string read at trace time, never a traced value"
        val = int(raw)
    except ValueError:
        raise ValueError(
            f"{name}={raw!r} is not an integer; set it to a multiple of 128"
            " (e.g. 512) or unset it") from None
    if val < 128 or val % 128:
        raise ValueError(
            f"{name}={val} must be a multiple of 128 and >= 128 (TPU lane"
            " alignment)")
    return val


def _pick_blocks(h, s, d, itemsize):
    """(bh, block_q, block_k): heads per program + q/k tile sizes.

    Keeps resident VMEM for bh heads under budget while minimising the
    program count.  Worst case is the dkv kernel, which holds TWO full-seq
    arrays (q, do) plus k/v tiles per head group; `itemsize` is the input
    dtype width (fp32 attention is supported and doubles the footprint).
    """
    # 512/512 measured best on v5e for the GPT legs (r5 sweep,
    # scripts/PERF_NOTES.md): 760M batch8 0.474 vs 0.465 at 1024/512;
    # 1024/256 and 512/256 are 3-5% worse — don't shrink block_k
    block_q = _round_to_divisor(_env_block("PTPU_FA_BQ", 512), s)
    block_k = _round_to_divisor(_env_block("PTPU_FA_BK", 512), s)
    bh = 1
    for cand in (8, 4, 2):
        if h % cand == 0 and cand * (2 * s * d * itemsize) <= 6 * 1024 * 1024:
            bh = cand
            break
    return bh, block_q, block_k



def _dot_f32(a, b, ta=False, tb=False):
    """MXU matmul with fp32 accumulate.  When either operand is 16-bit the
    other is cast to bf16 too: bf16 x bf16 -> fp32 runs at full MXU rate
    (fp32 x fp32 runs at ~1/8).  Pure-fp32 inputs keep fp32 operands so
    fp32 attention stays fp32-accurate."""
    if a.dtype.itemsize <= 2 or b.dtype.itemsize <= 2:
        a = a.astype(jnp.bfloat16)
        b = b.astype(jnp.bfloat16)
    ca = (1 if not ta else 0,)
    cb = (0 if not tb else 1,)
    return jax.lax.dot_general(a, b, ((ca, cb), ((), ())),
                               preferred_element_type=jnp.float32)

# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_k, seq_len, bh):
    from jax.experimental import pallas as pl

    block_q = q_ref.shape[2]
    d = q_ref.shape[-1]
    qi = pl.program_id(2)
    num_k = seq_len // block_k
    if causal:
        num_k_run = jnp.minimum(num_k, pl.cdiv((qi + 1) * block_q, block_k))
    else:
        num_k_run = num_k

    for hh in range(bh):
        q = q_ref[0, hh]  # [block_q, d] bf16

        def body(start_k, carry):
            acc, m_prev, l_prev = carry
            k = k_ref[0, hh, pl.dslice(start_k * block_k, block_k)]
            v = v_ref[0, hh, pl.dslice(start_k * block_k, block_k)]
            s = _dot_f32(q, k, tb=True) * scale  # [block_q, block_k] — MXU
            if causal:
                q_pos = qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                k_pos = start_k * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                s = jnp.where(q_pos >= k_pos, s, NEG_INF)
            m_cur = jnp.max(s, axis=-1)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, None])
            l_new = l_prev * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[:, None] + _dot_f32(p, v)
            return acc, m_new, l_new

        acc0 = jnp.zeros((block_q, d), jnp.float32)
        m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
        l0 = jnp.zeros((block_q,), jnp.float32)
        acc, m, l = jax.lax.fori_loop(0, num_k_run, body, (acc0, m0, l0))
        o_ref[0, hh] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(
            o_ref.dtype)
        # LSE materialised as [b, h, s, 1]: trailing singleton lane dim keeps
        # the Mosaic block shape (block_q, 1) legal.
        lse_ref[0, hh] = (m + jnp.log(jnp.maximum(l, 1e-30)))[:, None]


def _flash_fwd(q, k, v, causal, scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = q.shape
    bh, block_q, block_k = _pick_blocks(h, s, d, q.dtype.itemsize)
    check_divides("flash_attention_fwd", heads=(h, bh),
                  seq_len_q=(s, block_q), seq_len_k=(s, block_k))
    grid = (b, h // bh, s // block_q)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_k=block_k, seq_len=s, bh=bh)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bh, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, bh, s, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, bh, s, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bh, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, bh, block_q, 1),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, s, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=_INTERPRET[0],
        name="flash_fwd",
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
                   scale, causal, block_k, seq_len, bh):
    from jax.experimental import pallas as pl

    block_q = q_ref.shape[2]
    d = q_ref.shape[-1]
    qi = pl.program_id(2)
    num_k = seq_len // block_k
    if causal:
        num_k_run = jnp.minimum(num_k, pl.cdiv((qi + 1) * block_q, block_k))
    else:
        num_k_run = num_k

    for hh in range(bh):
        q = q_ref[0, hh]
        do = do_ref[0, hh]
        lse = lse_ref[0, hh, :, 0]
        delta = delta_ref[0, hh, :, 0]

        def body(start_k, dq):
            k = k_ref[0, hh, pl.dslice(start_k * block_k, block_k)]
            v = v_ref[0, hh, pl.dslice(start_k * block_k, block_k)]
            s = _dot_f32(q, k, tb=True) * scale
            if causal:
                q_pos = qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                k_pos = start_k * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                s = jnp.where(q_pos >= k_pos, s, NEG_INF)
            p = jnp.exp(s - lse[:, None])
            dp = _dot_f32(do, v, tb=True)
            ds = p * (dp - delta[:, None])
            return dq + _dot_f32(ds, k)

        dq = jax.lax.fori_loop(0, num_k_run, body,
                               jnp.zeros((block_q, d), jnp.float32))
        dq_ref[0, hh] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                    dv_ref, *, scale, causal, block_q, seq_len, bh):
    from jax.experimental import pallas as pl

    block_k = k_ref.shape[2]
    d = k_ref.shape[-1]
    ki = pl.program_id(2)
    num_q = seq_len // block_q
    start = (ki * block_k) // block_q if causal else 0

    for hh in range(bh):
        k = k_ref[0, hh]
        v = v_ref[0, hh]

        def body(start_q, carry):
            dk, dv = carry
            q = q_ref[0, hh, pl.dslice(start_q * block_q, block_q)]
            do = do_ref[0, hh, pl.dslice(start_q * block_q, block_q)]
            lse = lse_ref[0, hh, pl.dslice(start_q * block_q, block_q), 0]
            delta = delta_ref[0, hh,
                              pl.dslice(start_q * block_q, block_q), 0]
            s = _dot_f32(q, k, tb=True) * scale  # [block_q, block_k]
            if causal:
                q_pos = start_q * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                k_pos = ki * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                s = jnp.where(q_pos >= k_pos, s, NEG_INF)
            p = jnp.exp(s - lse[:, None])
            dv = dv + _dot_f32(p, do, ta=True)
            dp = _dot_f32(do, v, tb=True)
            ds = p * (dp - delta[:, None])
            dk = dk + _dot_f32(ds, q, ta=True) * scale
            return dk, dv

        dk0 = jnp.zeros((block_k, d), jnp.float32)
        dv0 = jnp.zeros((block_k, d), jnp.float32)
        dk, dv = jax.lax.fori_loop(start, num_q, body, (dk0, dv0))
        dk_ref[0, hh] = dk.astype(dk_ref.dtype)
        dv_ref[0, hh] = dv.astype(dv_ref.dtype)


def _flash_bwd(q, k, v, lse, delta, do, causal, scale):
    """(dq, dk, dv), [b, h, s, d]; ``lse`` and ``delta`` are [b, h, s] fp32
    and enter the kernels as [b, h, s, 1]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = q.shape
    bh, block_q, block_k = _pick_blocks(h, s, d, q.dtype.itemsize)
    check_divides("flash_attention_bwd", heads=(h, bh),
                  seq_len_q=(s, block_q), seq_len_k=(s, block_k))
    lse = lse[..., None]
    delta = delta[..., None]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_k=block_k, seq_len=s, bh=bh),
        grid=(b, h // bh, s // block_q),
        in_specs=[
            pl.BlockSpec((1, bh, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, bh, s, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, bh, s, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, bh, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, bh, block_q, 1),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, bh, block_q, 1),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, bh, block_q, d),
                               lambda bi, hi, qi: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=_INTERPRET[0],
        name="flash_dq",
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, seq_len=s, bh=bh),
        grid=(b, h // bh, s // block_k),
        in_specs=[
            pl.BlockSpec((1, bh, s, d), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, bh, block_k, d),
                         lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, bh, block_k, d),
                         lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, bh, s, d), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, bh, s, 1), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, bh, s, 1), lambda bi, hi, ki: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bh, block_k, d),
                         lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, bh, block_k, d),
                         lambda bi, hi, ki: (bi, hi, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=_INTERPRET[0],
        name="flash_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _swap(x):
    """[B, S, H, D] <-> [B, H, S, D]."""
    return jnp.swapaxes(x, 1, 2)


def _saveable(out, lse):
    """The kernel's output and its log-sum-exp (squeezed to a lane-dense
    [b, h, s]: kept as [b, h, s, 1] fp32 it would be stored padded to 128
    lanes) under the names a remat policy keeps them by.  A backward that
    finds both saved reads them back and does not replay the forward
    kernel (``models/gpt.py`` ``_sel_policy``)."""
    from jax.ad_checkpoint import checkpoint_name
    return (checkpoint_name(out, "flash_out"),
            checkpoint_name(lse[..., 0], "flash_lse"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_bshd(q, k, v, causal, scale):
    out, _ = _flash_fwd(_swap(q), _swap(k), _swap(v), causal, scale)
    return _swap(out)


def _flash_vjp_fwd(q, k, v, causal, scale):
    # The output is named with its heads merged, [B, S, H*D]: no layout
    # of that pads a lane, where one with D minor stores a head size that
    # is not whole 128-lane tiles padded (96 as 128).  What the caller
    # goes on with is a reshape of the named array, so a policy that keeps
    # "flash_out" keeps this one copy, and nothing downstream (the model's
    # projection) needs the kernel again.
    b, s, h, d = q.shape
    out, lse = _flash_fwd(_swap(q), _swap(k), _swap(v), causal, scale)
    out, lse = _saveable(_swap(out).reshape(b, s, h * d), lse)
    return out.reshape(b, s, h, d), (q, k, v, out, lse)


def _flash_vjp_bwd(causal, scale, res, do):
    q, k, v, out, lse = res
    b, s, h, d = q.shape
    # delta, rowsum(out * dO) over each head, taken in the layout the
    # output is kept in (heads merged): no relayout of the kept copy
    prod = (out.astype(jnp.float32)
            * do.reshape(out.shape).astype(jnp.float32))
    delta = _swap(prod.reshape(b, s, h, d).sum(-1))
    dq, dk, dv = _flash_bwd(_swap(q), _swap(k), _swap(v), lse, delta,
                            _swap(do), causal, scale)
    return _swap(dq), _swap(dk), _swap(dv)


_flash_bshd.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_with_lse(q, k, v, causal, scale):
    """(out, lse) flash attention, [B, H, S, D] layout, differentiable.

    lse is [B, H, S] fp32.  Used by ring attention (kernels/ring_attention.py)
    whose online-softmax merge needs the per-chunk LSE *and* gradients through
    both outputs — the lse cotangent folds into the flash backward via the
    delta term (see _flash_lse_vjp_bwd)."""
    out, lse = _flash_fwd(q, k, v, causal, scale)
    return out, lse[..., 0]


def _flash_lse_vjp_fwd(q, k, v, causal, scale):
    out, lse = _saveable(*_flash_fwd(q, k, v, causal, scale))
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_vjp_bwd(causal, scale, res, cot):
    do, dlse = cot
    q, k, v, out, lse = res
    # A cotangent g on lse enters as ds_ij += g_i * p_ij (because
    # d lse_i / d s_ij = p_ij); the kernels compute ds = p*(dp - delta),
    # so folding it in as delta' = delta - g gives p*(dp - delta + g).
    delta = (jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32), -1)
             - dlse.astype(jnp.float32))
    return _flash_bwd(q, k, v, lse, delta, do, causal, scale)


flash_attention_with_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


_warned_fallback = [False]


def flash_attention_fwd(q, k, v, causal=False, scale=None):
    """Public entry, [B, S, H, D] layout; differentiable (custom VJP)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    tpu = on_tpu()
    if not (tpu or _INTERPRET[0]):
        # CPU tests only: chip_smoke.py asserts this counter stays 0 on
        # the chip, so the kernel can never give way unnoticed there
        counters.inc("kernels.flash.reference_calls")  # trace-time only
        return reference_attention(q, k, v, causal, scale)
    s = q.shape[1]
    if s % 128 != 0:
        counters.inc("kernels.flash.reference_calls")  # trace-time only
        if tpu and not _warned_fallback[0]:
            _warned_fallback[0] = True
            import warnings
            warnings.warn(
                f"flash_attention: seq_len={s} is not a multiple of 128;"
                " falling back to O(S^2) reference attention on TPU. Pad the"
                " sequence to a 128 multiple for the Pallas kernel.",
                RuntimeWarning, stacklevel=2)
        return reference_attention(q, k, v, causal, scale)
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty and mesh.size > 1 and not mesh.manual_axes:
        # (inside somebody's shard_map the caller has done the splitting)
        return _sharded_flash(mesh, q, k, v, causal, scale)
    return _flash_bshd(q, k, v, causal, scale)


def _sharded_flash(mesh, q, k, v, causal, scale):
    """The kernel under an ambient mesh (``CompiledTrainStep(mesh=)``
    publishes its own with ``jax.set_mesh``).  GSPMD cannot partition a
    Mosaic kernel — lowering one in a multi-device program raises — so it
    runs per shard through a fully-manual ``shard_map``: batch over the
    data axes, heads over ``"mp"``.  Attention is independent across both,
    so the wrap adds no collective; a dim its axes do not divide stays
    replicated (correct, redundant)."""
    from jax.sharding import PartitionSpec as P

    from ..distributed.env import DATA_AXES
    b, _, h, _ = q.shape
    batch = tuple(a for a in DATA_AXES if mesh.shape.get(a, 1) > 1)
    if b % math.prod(mesh.shape[a] for a in batch):
        batch = ()
    head = "mp" if (mesh.shape.get("mp", 1) > 1
                    and h % mesh.shape["mp"] == 0) else None
    spec = P(batch or None, None, head, None)
    fn = jax.shard_map(
        lambda ql, kl, vl: _flash_bshd(ql, kl, vl, causal, scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)
