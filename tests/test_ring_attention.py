"""Ring attention (context parallel over 'sep') parity tests.

Reference capability: segment-parallel sequence scaling
(fleet/base/topology.py:240, meta_parallel/segment_parallel.py); SURVEY §5
long-context requirement."""

import numpy as np
import pytest

import paddle_tpu as paddle


@pytest.fixture(scope="module")
def mesh_sep4():
    import jax
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    from paddle_tpu.distributed import fleet
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 1,
                               "pp_degree": 1, "sep_degree": 4}
    hcg = fleet.init(is_collective=True, strategy=strategy)
    yield hcg
    fleet._reset()


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_single_device(self, mesh_sep4, causal):
        """sep=4 ring attention == single-device reference attention,
        forward and gradients."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.distributed import get_mesh
        from paddle_tpu.kernels.flash_attention import reference_attention
        from paddle_tpu.kernels.ring_attention import ring_attention

        rng = np.random.default_rng(0)
        B, S, H, D = 2, 32, 2, 8
        q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        mesh = get_mesh()

        def ring_loss(q, k, v):
            o = ring_attention(q, k, v, causal=causal, mesh=mesh)
            return jnp.sum(o.astype(jnp.float32) ** 2), o

        def ref_loss(q, k, v):
            o = reference_attention(q, k, v, causal=causal)
            return jnp.sum(o.astype(jnp.float32) ** 2), o

        with mesh:
            (l1, o1), g1 = jax.jit(jax.value_and_grad(
                ring_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        (l2, o2), g2 = jax.value_and_grad(
            ref_loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)

        assert np.allclose(np.asarray(o1), np.asarray(o2), atol=2e-5), \
            np.abs(np.asarray(o1) - np.asarray(o2)).max()
        assert np.allclose(float(l1), float(l2), rtol=1e-5)
        for a, b, n in zip(g1, g2, "qkv"):
            assert np.allclose(np.asarray(a), np.asarray(b), atol=2e-4), \
                (n, np.abs(np.asarray(a) - np.asarray(b)).max())

    @pytest.mark.parametrize("causal", [True, False])
    def test_pallas_chunk_path_with_grads(self, mesh_sep4, causal):
        """S=1024/sep=4 -> 256-token chunks (%128==0): the Pallas _flash_fwd
        path actually runs inside the shard_map ring (interpret mode), and
        gradients flow through flash_attention_with_lse's custom VJP —
        regression for the round-4 advisor finding that raw _flash_fwd had no
        VJP and jax.grad crashed on exactly this path."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.distributed import get_mesh
        from paddle_tpu.kernels import flash_attention as fa
        from paddle_tpu.kernels.flash_attention import reference_attention
        from paddle_tpu.kernels.ring_attention import ring_attention

        rng = np.random.default_rng(3)
        B, S, H, D = 1, 1024, 2, 64
        q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        mesh = get_mesh()

        def ring_loss(q, k, v):
            o = ring_attention(q, k, v, causal=causal, mesh=mesh)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        def ref_loss(q, k, v):
            o = reference_attention(q, k, v, causal=causal)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        fa._INTERPRET[0] = True
        try:
            with mesh:
                l1, g1 = jax.jit(jax.value_and_grad(
                    ring_loss, argnums=(0, 1, 2)))(q, k, v)
        finally:
            fa._INTERPRET[0] = False
        l2, g2 = jax.value_and_grad(ref_loss, argnums=(0, 1, 2))(q, k, v)

        assert np.allclose(float(l1), float(l2), rtol=1e-4)
        for a, b, n in zip(g1, g2, "qkv"):
            assert np.allclose(np.asarray(a), np.asarray(b), atol=5e-2), \
                (n, np.abs(np.asarray(a) - np.asarray(b)).max())

    def test_kept_flash_residuals_give_the_replays_grads(self, mesh_sep4):
        """Each ring step's flash call names its output and log-sum-exp
        (``flash_out``, ``flash_lse``).  Under a policy that keeps them the
        backward reads them back; its gradients are the replay's (nothing
        kept, the kernels run again) to float32 rounding."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.distributed import get_mesh
        from paddle_tpu.kernels import flash_attention as fa
        from paddle_tpu.kernels.ring_attention import ring_attention

        rng = np.random.default_rng(5)
        B, S, H, D = 1, 512, 2, 64
        q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
                   for _ in range(3))
        mesh = get_mesh()

        def loss(q, k, v):
            o = ring_attention(q, k, v, causal=True, mesh=mesh)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        pol = jax.checkpoint_policies
        keep = pol.save_only_these_names("flash_out", "flash_lse")
        grads = []
        fa._INTERPRET[0] = True
        try:
            with mesh:
                for policy in (keep, pol.nothing_saveable):
                    grads.append(jax.jit(jax.grad(
                        jax.checkpoint(loss, policy=policy),
                        argnums=(0, 1, 2)))(q, k, v))
        finally:
            fa._INTERPRET[0] = False
        for a, b, n in zip(*grads, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6, err_msg=n)

    def test_gpt_context_parallel_trains(self, mesh_sep4):
        """GPT with context_parallel=True trains on a sep=4 mesh."""
        from paddle_tpu.distributed import DistributedTrainStep
        from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                       GPTPretrainingCriterion)

        cfg = GPTConfig(vocab_size=64, hidden_size=16, num_layers=2,
                        num_heads=2, max_seq_len=32,
                        use_flash_attention=False, context_parallel=True,
                        sequence_parallel=False)
        paddle.seed(5)
        model = GPTForCausalLM(cfg)
        crit = GPTPretrainingCriterion()
        opt = paddle.optimizer.AdamW(1e-2, parameters=model.parameters())
        ids = paddle.randint(0, 64, [4, 32])
        lab = paddle.randint(0, 64, [4, 32])

        def loss_fn(m, x, l):
            return crit(m(x), l)

        step = DistributedTrainStep(model, loss_fn, opt)
        losses = [float(step(ids, lab).numpy()) for _ in range(4)]
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], losses
