"""Fused RMSNorm Pallas kernel (reference CUDA:
phi/kernels/fusion/gpu/fused_rms_norm kernels / incubate fused_rms_norm).

Forward computes mean-square + normalize in one VMEM pass; backward is left
to XLA (the jnp reference) — the op is bandwidth-bound and XLA's fusion of
the backward chain is already optimal, so the kernel exists to guarantee a
single-pass forward on the inference/serving path."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..device import on_tpu

_INTERPRET = [False]


def rms_norm_reference(x, w, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(
        x.dtype) * w


def _rms_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)).astype(o_ref.dtype) * w_ref[...]


def rms_norm(x, w, eps=1e-6, block_rows=256):
    """x: [..., H]; w: [H]."""
    if not (on_tpu() or _INTERPRET[0]):
        return rms_norm_reference(x, w, eps)
    return _rms_norm_pallas(x, w, eps, block_rows)


def _rms_norm_pallas(x, w, eps=1e-6, block_rows=256):
    """The kernel behind :func:`rms_norm`'s platform gate (the AOT
    compile test in tests/test_tpu_compile.py enters here)."""
    from jax.experimental import pallas as pl

    orig_shape = x.shape
    h = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, h)
    if rows % block_rows != 0:
        block_rows = rows if rows < block_rows else 1
        while rows % block_rows != 0:
            block_rows -= 1
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
            pl.BlockSpec((h,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, h), x.dtype),
        interpret=_INTERPRET[0],
        name="rms_norm",
    )(x2, w)
    return out.reshape(orig_shape)
