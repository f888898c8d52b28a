"""Quantization: observers, fake-quant STE, and int8 weight-only PTQ.

Contracts: (1) observers track the right statistic (absmax running max;
percentile clips outliers below the absmax); (2) ``fake_quant`` is a
straight-through estimator — values snap to the 8-bit grid forward,
gradients pass through untouched; (3) ``channel_scales`` /
``quantize_weight_int8`` produce per-output-channel ``[L, 1, out]``
scales whose roundtrip error is bounded by half a quantization step;
(4) ``ptq_int8_decode_state`` swaps exactly the stacked matmul weights
for int8+scale pairs and the quantized serving logits stay within the
documented tolerance of fp32 on the tiny GPT.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.quantization import (PTQ_WEIGHTS, AbsmaxObserver,
                                     PercentileObserver, channel_scales,
                                     fake_quant, ptq_int8_decode_state,
                                     quantize_weight_int8)

_MODEL = None


def _model():
    global _MODEL
    if _MODEL is None:
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=4, max_seq_len=32,
                        use_flash_attention=False)
        paddle.seed(55)
        _MODEL = GPTForCausalLM(cfg)
        _MODEL.eval()
    return _MODEL


class TestObservers:
    def test_absmax_tracks_running_max(self):
        obs = AbsmaxObserver()
        obs(paddle.to_tensor(np.asarray([1.0, -3.0], np.float32)))
        assert float(obs.scales().numpy()) == 3.0
        obs(paddle.to_tensor(np.asarray([0.5], np.float32)))
        assert float(obs.scales().numpy()) == 3.0      # max never decays
        obs(paddle.to_tensor(np.asarray([-7.0], np.float32)))
        assert float(obs.scales().numpy()) == 7.0

    def test_percentile_clips_outliers(self):
        rng = np.random.default_rng(0)
        x = rng.standard_normal(4096).astype(np.float32)
        x[0] = 1000.0                                   # one outlier
        t = paddle.to_tensor(x)
        a, p = AbsmaxObserver(), PercentileObserver(percentile=99.0)
        a(t)
        p(t)
        assert float(a.scales().numpy()) == 1000.0      # absmax blown up
        assert float(p.scales().numpy()) < 5.0          # percentile is not

    def test_percentile_validation(self):
        with pytest.raises(ValueError, match="percentile"):
            PercentileObserver(percentile=0.0)
        with pytest.raises(ValueError, match="percentile"):
            PercentileObserver(percentile=101.0)


class TestFakeQuant:
    def test_forward_snaps_to_grid(self):
        x = np.linspace(-2.0, 2.0, 9).astype(np.float32)
        s = np.asarray(1.5, np.float32)
        y = fake_quant(paddle.to_tensor(x), paddle.to_tensor(s)).numpy()
        ref = np.round(np.clip(x / 1.5 * 127, -127, 127)) * 1.5 / 127
        assert np.allclose(np.asarray(y), ref, atol=1e-6)

    def test_straight_through_gradient(self):
        x = paddle.to_tensor(np.linspace(-1.0, 1.0, 8).astype(np.float32),
                             stop_gradient=False)
        s = paddle.to_tensor(np.asarray(1.0, np.float32))
        fake_quant(x, s).sum().backward()
        # STE: d(fake_quant)/dx == 1 everywhere inside the clip range
        assert np.allclose(np.asarray(x.grad.numpy()), np.ones(8))


class TestChannelScales:
    def test_shapes_and_absmax_values(self):
        rng = np.random.default_rng(1)
        w = rng.standard_normal((3, 16, 8)).astype(np.float32)
        s = np.asarray(channel_scales(w))
        assert s.shape == (3, 1, 8) and s.dtype == np.float32
        expect = np.abs(w).max(axis=1, keepdims=True) / 127.0
        assert np.allclose(s, expect, atol=1e-7)

    def test_percentile_observer_below_absmax_on_outliers(self):
        rng = np.random.default_rng(2)
        w = rng.standard_normal((2, 256, 4)).astype(np.float32)
        w[:, 0, :] = 50.0                               # outlier row
        sa = np.asarray(channel_scales(w, observer="absmax"))
        sp = np.asarray(channel_scales(w, observer="percentile",
                                       percentile=99.0))
        assert np.all(sp < sa)

    def test_invalid_observer_raises(self):
        with pytest.raises(ValueError, match="observer"):
            channel_scales(np.zeros((1, 2, 2), np.float32), observer="kl")

    def test_quantize_roundtrip_bound(self):
        rng = np.random.default_rng(3)
        w = (rng.standard_normal((2, 32, 16)) * 0.3).astype(np.float32)
        q, s = quantize_weight_int8(w)
        assert np.asarray(q).dtype == np.int8
        dq = np.asarray(q, np.float32) * np.asarray(s)
        # symmetric rounding: per-element error <= scale / 2
        assert np.all(np.abs(dq - w) <= np.asarray(s) / 2 + 1e-7)


def _prompt_logits(m, w, ids):
    """The logits after ``ids[1, T]``: one prefill chunk over a pool that
    holds this one request (blocks of 4, none shared)."""
    import jax.numpy as jnp
    c = m.config
    T = ids.shape[1]
    pool = jnp.zeros((c.num_layers, T // 4, 4, c.num_heads,
                      c.hidden_size // c.num_heads), jnp.dtype(c.dtype))
    return np.asarray(m.prefill_paged(
        w, ids, 0, T, jnp.arange(T // 4, dtype=jnp.int32), pool, pool)[-1])


class TestPTQDecodeState:
    def test_swaps_exactly_the_matmul_weights(self):
        m = _model()
        w = ptq_int8_decode_state(m)
        raw = m.decode_state()
        for name in PTQ_WEIGHTS:
            assert np.asarray(w["lws"][name]).dtype == np.int8
            scale = np.asarray(w["lws"][name + "__scale"])
            L, _, out = raw["lws"][name].shape
            assert scale.shape == (L, 1, out)
        # everything else untouched (embeddings, biases, norms, head)
        assert w["wte"] is raw["wte"] or np.array_equal(
            np.asarray(w["wte"]), np.asarray(raw["wte"]))
        for name in ("qkv_b", "proj_b", "fc1_b", "fc2_b", "ln1_w", "ln2_w"):
            if name in raw["lws"]:
                assert np.asarray(w["lws"][name]).dtype != np.int8

    def test_logit_tolerance_vs_fp32(self):
        # the documented PTQ gate: max |logit drift| <= 5% of the fp32
        # logit magnitude on the tiny model (same gate check_counters
        # enforces)
        import jax.numpy as jnp
        m = _model()
        w_fp = m.decode_state()
        w_q = ptq_int8_decode_state(m)
        ids = jnp.asarray(np.arange(16)[None, :] % 64, jnp.int32)
        ref, got = _prompt_logits(m, w_fp, ids), _prompt_logits(m, w_q, ids)
        drift = np.abs(got - ref).max()
        assert drift <= 0.05 * np.abs(ref).max(), drift

    def test_percentile_variant_also_within_tolerance(self):
        import jax.numpy as jnp
        m = _model()
        w_fp = m.decode_state()
        w_q = ptq_int8_decode_state(m, observer="percentile",
                                    percentile=99.9)
        ids = jnp.asarray(np.arange(12)[None, :] % 64, jnp.int32)
        ref, got = _prompt_logits(m, w_fp, ids), _prompt_logits(m, w_q, ids)
        assert np.abs(got - ref).max() <= 0.05 * np.abs(ref).max()
