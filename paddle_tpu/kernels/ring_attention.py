"""Ring attention — context parallelism over the 'sep' mesh axis.

Reference capability anchor: the sep (segment-parallel) axis of the hybrid
topology (fleet/base/topology.py:68,240; meta_parallel/segment_parallel.py)
— the reference scales sequence length across ranks.  SURVEY §5 requires a
ring/flash composition to match that capability on TPU.

TPU-native design: Q/K/V are sequence-sharded over 'sep'.  K/V chunks
rotate around the ring with lax.ppermute (ICI neighbor exchange); each step
computes the local-Q x visiting-KV partial attention with the Pallas flash
kernel (kernels/flash_attention.py) and merges it into a running
(acc, m, l) online-softmax state using the chunk LSE — the same merge the
flash kernel does across key blocks, lifted one level up the memory
hierarchy (VMEM tiles -> per-device sequence chunks).

Causality by global chunk position: a visiting chunk strictly older than
the local Q chunk attends in full (non-causal kernel), the diagonal chunk
attends causally, newer chunks are skipped via a lax.switch branch that
returns lse = NEG_INF (zero weight in the merge, and XLA executes only the
taken branch, so skipped pairs cost nothing — the causal ring saves ~half
the FLOPs).

Gradients flow through jax's scan/ppermute transposes + the flash kernel's
custom VJP — no hand-written backward needed.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..device import on_tpu
from ..profiler import counters
from .flash_attention import _INTERPRET
from ._shapes import NEG_INF, check_divides, check_equal


def _chunk_attention(q, k, v, causal, scale):
    """(out, lse) for one q-chunk x kv-chunk pair, [B, S, H, D] layout.
    lse is [B, S, H] (fp32)."""
    if (on_tpu() or _INTERPRET[0]) and q.shape[1] % 128 == 0 \
            and k.shape[1] % 128 == 0:
        from .flash_attention import flash_attention_with_lse
        qt = jnp.swapaxes(q, 1, 2)
        kt = jnp.swapaxes(k, 1, 2)
        vt = jnp.swapaxes(v, 1, 2)
        out, lse = flash_attention_with_lse(qt, kt, vt, causal, scale)
        return jnp.swapaxes(out, 1, 2), jnp.swapaxes(lse, 1, 2)
    # jnp fallback (CPU tests / odd chunk sizes), counted like the flash
    # entry point's so a chip run can assert it never happened
    counters.inc("kernels.flash.reference_calls")  # trace-time only
    logits = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
    if causal:
        s, t = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((s, t), dtype=bool), t - s)
        logits = jnp.where(mask, logits, NEG_INF)
    m = jnp.max(logits, -1)
    p = jnp.exp(logits - m[..., None])
    l = jnp.sum(p, -1)
    o = jnp.einsum("bhst,bthd->bshd", p.astype(v.dtype), v)
    o = o / jnp.maximum(l, 1e-30).astype(o.dtype)[
        ..., None].swapaxes(1, 2)
    lse = (m + jnp.log(jnp.maximum(l, 1e-30))).swapaxes(1, 2)  # [B, S, H]
    return o, lse


def _ring_body(q, k, v, axis, axis_size, causal, scale):
    """Per-device ring loop over sequence-sharded q/k/v ([B, Sloc, H, D])."""
    my = jax.lax.axis_index(axis)
    B, Sloc, H, D = q.shape

    def full_fn(kv):
        return _chunk_attention(q, kv[0], kv[1], False, scale)

    def diag_fn(kv):
        return _chunk_attention(q, kv[0], kv[1], True, scale)

    def skip_fn(kv):
        return (jnp.zeros_like(q),
                jnp.full((B, Sloc, H), NEG_INF, jnp.float32))

    def step(carry, s):
        kc, vc, acc, m_run, l_run = carry
        src = (my - s) % axis_size  # global chunk index of the visiting KV
        if causal:
            case = jnp.where(src == my, 1, jnp.where(src < my, 0, 2))
            o_s, lse_s = jax.lax.switch(case, [full_fn, diag_fn, skip_fn],
                                        (kc, vc))
        else:
            o_s, lse_s = full_fn((kc, vc))
        m_new = jnp.maximum(m_run, lse_s)
        keep = jnp.exp(m_run - m_new)
        w = jnp.exp(lse_s - m_new)
        acc = acc * keep[..., None] + o_s.astype(jnp.float32) * w[..., None]
        l_new = l_run * keep + w
        # rotate kv to the next device (collective OUTSIDE the switch)
        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
        kc = jax.lax.ppermute(kc, axis, perm)
        vc = jax.lax.ppermute(vc, axis, perm)
        return (kc, vc, acc, m_new, l_new), None

    acc0 = jnp.zeros(q.shape, jnp.float32)
    m0 = jnp.full((B, Sloc, H), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sloc, H), jnp.float32)
    (_, _, acc, m_run, l_run), _ = jax.lax.scan(
        step, (k, v, acc0, m0, l0), jnp.arange(axis_size))
    return (acc / jnp.maximum(l_run, 1e-30)[..., None]).astype(q.dtype)


def ring_attention(q, k, v, causal=True, scale=None, axis="sep", mesh=None):
    """Context-parallel attention, [B, S, H, D] with S sharded over `axis`.

    Must run inside jit; the sequence axis S is the GLOBAL length and must
    divide by the axis size.  Other mesh axes stay GSPMD-auto.
    """
    from ..distributed.env import get_mesh
    mesh = mesh or get_mesh()
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if mesh is None or mesh.shape.get(axis, 1) == 1:
        from .flash_attention import flash_attention_fwd
        return flash_attention_fwd(q, k, v, causal=causal, scale=scale)
    n = mesh.shape[axis]
    check_equal("ring_attention",
                k_seq_len=(k.shape[1], q.shape[1]),
                v_seq_len=(v.shape[1], q.shape[1]))
    check_divides("ring_attention", seq_len=(q.shape[1], n))
    spec = P(None, axis, None, None)

    def body(ql, kl, vl):
        return _ring_body(ql, kl, vl, axis, n, causal, scale)

    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, axis_names={axis},
                         check_vma=False)(q, k, v)
