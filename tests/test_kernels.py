"""Pallas kernel tests (interpret mode on CPU; reference pattern:
test/legacy_test/test_flash_attention.py comparing against naive math)."""

import numpy as np
import pytest

import paddle_tpu as paddle


def naive_attention(q, k, v, causal=False):
    d = q.shape[-1]
    logits = np.einsum("bshd,bthd->bhst", q, k) / np.sqrt(d)
    if causal:
        s, t = logits.shape[-2], logits.shape[-1]
        mask = np.tril(np.ones((s, t), bool))
        logits = np.where(mask, logits, -1e30)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhst,bthd->bshd", p, v)


@pytest.fixture()
def interpret_mode():
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.kernels import rms_norm as rn
    fa._INTERPRET[0] = True
    rn._INTERPRET[0] = True
    yield
    fa._INTERPRET[0] = False
    rn._INTERPRET[0] = False


class TestFlashAttentionKernel:
    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_naive(self, interpret_mode, causal):
        import jax.numpy as jnp
        from paddle_tpu.kernels.flash_attention import flash_attention_fwd
        rng = np.random.RandomState(0)
        q = rng.randn(1, 256, 2, 64).astype(np.float32)
        k = rng.randn(1, 256, 2, 64).astype(np.float32)
        v = rng.randn(1, 256, 2, 64).astype(np.float32)
        out = np.asarray(flash_attention_fwd(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal))
        ref = naive_attention(q, k, v, causal)
        assert np.allclose(out, ref, atol=2e-3), np.abs(out - ref).max()

    @pytest.mark.parametrize("causal", [False, True])
    def test_backward_matches_jax_grad(self, interpret_mode, causal):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.kernels.flash_attention import (
            flash_attention_fwd, reference_attention)
        rng = np.random.RandomState(1)
        q = jnp.asarray(rng.randn(1, 128, 1, 64).astype(np.float32))
        k = jnp.asarray(rng.randn(1, 128, 1, 64).astype(np.float32))
        v = jnp.asarray(rng.randn(1, 128, 1, 64).astype(np.float32))

        def loss_kernel(q, k, v):
            return jnp.sum(flash_attention_fwd(q, k, v, causal=causal) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)

        gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gk, gr, "qkv"):
            assert np.allclose(np.asarray(a), np.asarray(b), atol=5e-2), (
                name, np.abs(np.asarray(a) - np.asarray(b)).max())


class TestFlashAttentionTPULowering:
    """Round-1 regression: the kernel passed interpret mode but failed Mosaic
    lowering on real TPU (illegal LSE BlockSpec).  Cross-lower for the TPU
    target from the CPU host via jax.export so CI catches lowering errors."""

    def test_kernel_lowers_for_tpu(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.kernels.flash_attention import _flash_bshd

        b, s, h, d = 2, 1024, 12, 64
        q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)

        def fwd_bwd(q, k, v):
            out, vjp = jax.vjp(
                lambda q, k, v: _flash_bshd(q, k, v, True, 0.125),
                q, k, v)
            return out, vjp(out)

        exported = jax.export.export(jax.jit(fwd_bwd), platforms=["tpu"])(
            q, q, q)
        assert "tpu" in exported.platforms


class TestRMSNormKernel:
    def test_matches_reference(self, interpret_mode):
        import jax.numpy as jnp
        from paddle_tpu.kernels.rms_norm import rms_norm, rms_norm_reference
        x = jnp.asarray(np.random.randn(8, 128).astype(np.float32))
        w = jnp.asarray(np.random.randn(128).astype(np.float32))
        out = rms_norm(x, w)
        ref = rms_norm_reference(x, w)
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


class TestRope:
    def test_rope_properties(self):
        import jax.numpy as jnp
        from paddle_tpu.kernels.rope import apply_rope
        x = jnp.asarray(np.random.randn(1, 16, 2, 32).astype(np.float32))
        out = apply_rope(x)
        # norm-preserving per pair
        assert np.allclose(np.linalg.norm(np.asarray(out), axis=-1),
                           np.linalg.norm(np.asarray(x), axis=-1), atol=1e-4)
        # position 0 unchanged
        assert np.allclose(np.asarray(out)[:, 0], np.asarray(x)[:, 0],
                           atol=1e-6)
