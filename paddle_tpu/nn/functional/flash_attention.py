"""Attention functionals (reference: python/paddle/nn/functional/flash_attention.py
— flash_attention:147, flash_attn_unpadded:455, scaled_dot_product_attention:722;
CUDA kernel: phi/kernels/gpu/flash_attn_kernel.cu wrapping third_party flashattn).

TPU-native: routes to the Pallas flash-attention kernel
(paddle_tpu/kernels/flash_attention.py) on TPU, with an XLA reference path
(jnp einsum softmax chain — XLA fuses it) elsewhere or when shapes are
unsuitable for the kernel tiling."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...core.dispatch import apply_op
from ...core.tensor import Tensor
from ...device import on_tpu


def _t(x):
    return x if isinstance(x, Tensor) else Tensor(x)


def _use_pallas(q_data):
    """The Pallas kernel takes every [B, S, H, D] call on TPU whose
    sequence tiles by 128 — at any head dim (D=96, the GPT-760M width,
    compiles like 64/128/256: tests/test_tpu_compile.py)."""
    if q_data.ndim != 4 or not on_tpu():
        return False
    s = q_data.shape[1]
    return s >= 128 and s % 128 == 0


def _sdpa_reference(q, k, v, mask, causal, dropout_p, scale=None):
    """[B, S, H, D] layout (paddle convention)."""
    d = q.shape[-1]
    sc = scale if scale is not None else 1.0 / math.sqrt(d)
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    logits = jnp.einsum("bshd,bthd->bhst", qf * sc, kf)
    if causal:
        s, t = logits.shape[-2], logits.shape[-1]
        cmask = jnp.tril(jnp.ones((s, t), dtype=bool), t - s)
        logits = jnp.where(cmask, logits, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -1e30)
        else:
            logits = logits + mask.astype(logits.dtype)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", p.astype(v.dtype), v)
    return out


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """reference surface: nn/functional/flash_attention.py:722."""
    q, k, v = _t(query), _t(key), _t(value)
    # the kernel tiles K/V by the query's sequence length: self-attention
    # shapes only (cross-attention with another K length takes the einsum)
    if (_use_pallas(q._data) and k._data.shape == q._data.shape
            and attn_mask is None and dropout_p == 0.0):
        from ...kernels.flash_attention import flash_attention_fwd
        return apply_op("flash_attention",
                        lambda a, b, c: flash_attention_fwd(a, b, c,
                                                            causal=is_causal),
                        q, k, v)
    m = attn_mask._data if isinstance(attn_mask, Tensor) else attn_mask
    return apply_op("sdpa",
                    lambda a, b, c: _sdpa_reference(a, b, c, m, is_causal,
                                                    dropout_p), q, k, v)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None):
    """reference surface: nn/functional/flash_attention.py:147.
    Returns (out, softmax_lse-like None) tuple for compat."""
    out = scaled_dot_product_attention(query, key, value, None, dropout,
                                       causal, training)
    return out, None


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale, dropout=0.0,
                        causal=False, return_softmax=False, name=None):
    """Varlen attention (reference :455). Implemented by segment-masked dense
    attention — ragged batches become one padded batch with a block-diagonal
    mask (TPU prefers static shapes over ragged kernels)."""
    q, k, v = _t(query), _t(key), _t(value)

    def fn(qd, kd, vd, cq, ck):
        total_q = qd.shape[0]
        total_k = kd.shape[0]
        seg_q = jnp.cumsum(
            jnp.zeros(total_q, jnp.int32).at[cq[1:-1]].add(1))
        seg_k = jnp.cumsum(
            jnp.zeros(total_k, jnp.int32).at[ck[1:-1]].add(1))
        logits = jnp.einsum("qhd,khd->hqk", qd.astype(jnp.float32) * scale,
                            kd.astype(jnp.float32))
        mask = seg_q[:, None] == seg_k[None, :]
        if causal:
            pos_q = jnp.arange(total_q) - jnp.take(cq, seg_q)
            pos_k = jnp.arange(total_k) - jnp.take(ck, seg_k)
            mask = mask & (pos_q[:, None] >= pos_k[None, :])
        logits = jnp.where(mask[None], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p.astype(vd.dtype), vd)
    out = apply_op("flash_attn_unpadded", fn, q, k, v, _t(cu_seqlens_q),
                   _t(cu_seqlens_k))
    return out, None


def sparse_attention(query, key, value, sparse_csr_offset, sparse_csr_columns,
                     key_padding_mask=None, attn_mask=None, name=None):
    """CSR-masked attention (reference kernel:
    phi/kernels/gpu/sparse_attention... via paddle.nn.functional
    .sparse_attention): each query row attends only to the key columns
    listed in its CSR row.

    TPU-native realisation: the CSR pattern becomes a dense boolean mask
    (one scatter) and the masked softmax-attention runs as ordinary MXU
    matmuls — XLA has no gather-attention primitive that beats the dense
    path until sparsity is extreme, and the mask build is O(nnz).
    query/key/value: [B, H, S, D]; csr offset [B, H, S+1], columns
    [B, H, nnz].  Returns [B, H, S, D].
    """
    import numpy as np

    if key_padding_mask is not None or attn_mask is not None:
        raise NotImplementedError(
            "sparse_attention: key_padding_mask/attn_mask are not applied "
            "on the TPU path — fold them into the CSR pattern instead")
    off = np.asarray((sparse_csr_offset._data
                      if isinstance(sparse_csr_offset, Tensor)
                      else sparse_csr_offset)).astype(np.int64)
    col = np.asarray((sparse_csr_columns._data
                      if isinstance(sparse_csr_columns, Tensor)
                      else sparse_csr_columns)).astype(np.int64)
    B, H, S = off.shape[0], off.shape[1], off.shape[2] - 1
    mask = np.zeros((B, H, S, S), bool)
    for b in range(B):
        for h in range(H):
            nnz = off[b, h, -1]
            rows = np.repeat(np.arange(S), np.diff(off[b, h]))
            mask[b, h, rows, col[b, h, :nnz]] = True  # one scatter per head
    mask_j = jnp.asarray(mask)

    def fn(qd, kd, vd):
        d = qd.shape[-1]
        # fp32 logits/softmax regardless of input dtype (matches
        # _sdpa_reference above; also keeps the -inf fill safe under fp16)
        logits = jnp.einsum("bhqd,bhkd->bhqk", qd.astype(jnp.float32),
                            kd.astype(jnp.float32)) / jnp.sqrt(float(d))
        logits = jnp.where(mask_j, logits, jnp.finfo(jnp.float32).min)
        p = jax.nn.softmax(logits, axis=-1)
        # fully-masked rows (empty CSR row) output zeros, not nan
        p = jnp.where(mask_j.any(-1, keepdims=True), p, 0.0)
        return jnp.einsum("bhqk,bhkd->bhqd", p.astype(vd.dtype), vd)

    return apply_op("sparse_attention", fn, _t(query), _t(key), _t(value))
