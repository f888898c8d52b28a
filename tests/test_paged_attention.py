"""Fused paged-attention decode kernel + quantized KV arena.

Contracts: (1) the Pallas kernel (interpret mode on CPU) matches a
materialized gather-softmax reference to float epsilon — the block-table
walk and online softmax are invisible in the math; (2) int8/fp8 pools
dequantized in-register match the explicitly dequantized reference
exactly (same fp32 ops, reordered by a commuting per-token scale);
(3) engines running kv_dtype / the Pallas kernel / weight-only
PTQ stay token-identical to the plain-XLA bf16 baseline on the tiny
model; (4) the shared ``kernels._shapes`` preflight validators fail
loudly, naming the offending dimension.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.kernels._shapes import (LANE, NEG_INF, check_divides,
                                        check_equal, check_min_tile,
                                        min_sublane, neg_inf)

_MODEL = None


def _model():
    global _MODEL
    if _MODEL is None:
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=4, max_seq_len=32,
                        use_flash_attention=False)
        paddle.seed(77)
        _MODEL = GPTForCausalLM(cfg)
        _MODEL.eval()
    return _MODEL


def _paged(m, **kw):
    from paddle_tpu.serving import LLMEngine
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_seq_len", 32)
    # min_bucket == prefill_chunk keeps every chunk in ONE bucket, so each
    # engine config compiles a single prefill program (suite-time budget).
    kw.setdefault("min_bucket", 8)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk", 8)
    return LLMEngine(m, **kw)


def _run(eng, handles, limit=300):
    n = 0
    while not all(h.is_finished for h in handles):
        eng.step()
        n += 1
        assert n < limit, "engine did not converge"
    return n


@pytest.fixture()
def interpret_mode():
    pa._INTERPRET[0] = True
    yield
    pa._INTERPRET[0] = False


@pytest.fixture()
def twin_then_kernel():
    """For engine-against-engine tests: call it once the twin baselines
    are built; engines built after that run the kernel."""
    def switch():
        pa._INTERPRET[0] = True
    yield switch
    pa._INTERPRET[0] = False


def _ref_paged(q, pool_k, pool_v, bt, pos, scale, sk=None, sv=None,
               layer=0):
    """Materialized gather + softmax reference (the XLA-twin math in
    numpy): pool[layer, bt] -> [B, S, nh, hd], causal-mask to pos,
    softmax."""
    B, nh, hd = q.shape
    pool_k, pool_v = pool_k[layer], pool_v[layer]
    if sk is not None:
        sk, sv = sk[layer], sv[layer]
    bs = pool_k.shape[1]
    S = bt.shape[1] * bs
    k = pool_k[bt].reshape(B, S, nh, hd).astype(np.float32)
    v = pool_v[bt].reshape(B, S, nh, hd).astype(np.float32)
    if sk is not None:
        k = k * sk[bt].reshape(B, S)[:, :, None, None]
        v = v * sv[bt].reshape(B, S)[:, :, None, None]
    logits = np.einsum("bhd,bshd->bhs", q.astype(np.float32), k) * scale
    live = (np.arange(S)[None, :] <= pos[:, None])[:, None, :]
    logits = np.where(live, logits, NEG_INF)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhs,bshd->bhd", p, v)


def _random_case(rng, B=3, nh=2, hd=8, n_blocks=16, bs=4, max_blocks=5,
                 dtype=np.float32, L=1):
    q = rng.standard_normal((B, nh, hd)).astype(dtype)
    pool_k = rng.standard_normal((L, n_blocks, bs, nh, hd)).astype(dtype)
    pool_v = rng.standard_normal((L, n_blocks, bs, nh, hd)).astype(dtype)
    # distinct physical blocks per row, deliberately out of order
    perm = rng.permutation(n_blocks)[:B * max_blocks]
    bt = perm.reshape(B, max_blocks).astype(np.int32)
    pos = rng.integers(0, max_blocks * bs, size=B).astype(np.int32)
    return q, pool_k, pool_v, bt, pos


class TestPagedDecodeKernel:
    def test_matches_gather_reference(self, interpret_mode):
        import jax.numpy as jnp
        rng = np.random.default_rng(0)
        q, pk, pv, bt, pos = _random_case(rng)
        scale = 1.0 / np.sqrt(q.shape[-1])
        out = np.asarray(pa.paged_decode_attention(
            jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv), 0,
            jnp.asarray(bt), jnp.asarray(pos), scale=scale))
        ref = _ref_paged(q, pk, pv, bt, pos, scale)
        assert np.allclose(out, ref, atol=1e-5), np.abs(out - ref).max()

    def test_single_live_token(self, interpret_mode):
        # pos=0: only one key is live; attention must return exactly v[0]
        import jax.numpy as jnp
        rng = np.random.default_rng(1)
        q, pk, pv, bt, pos = _random_case(rng, B=2)
        pos[:] = 0
        out = np.asarray(pa.paged_decode_attention(
            jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv), 0,
            jnp.asarray(bt), jnp.asarray(pos), scale=0.5))
        ref = pv[0, bt[:, 0], 0]                    # [B, nh, hd]
        assert np.allclose(out, ref, atol=1e-6)

    def test_bf16_pool(self, interpret_mode):
        import jax.numpy as jnp
        rng = np.random.default_rng(2)
        q, pk, pv, bt, pos = _random_case(rng)
        scale = 0.35
        out = np.asarray(pa.paged_decode_attention(
            jnp.asarray(q, jnp.bfloat16), jnp.asarray(pk, jnp.bfloat16),
            jnp.asarray(pv, jnp.bfloat16), 0, jnp.asarray(bt),
            jnp.asarray(pos), scale=scale))
        ref = _ref_paged(
            np.asarray(jnp.asarray(q, jnp.bfloat16), np.float32),
            np.asarray(jnp.asarray(pk, jnp.bfloat16), np.float32),
            np.asarray(jnp.asarray(pv, jnp.bfloat16), np.float32),
            bt, pos, scale)
        assert np.allclose(out, ref, atol=2e-2), np.abs(out - ref).max()

    @pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
    def test_quantized_pool_matches_dequantized_reference(
            self, interpret_mode, kv_dtype):
        import jax.numpy as jnp
        rng = np.random.default_rng(3)
        q, pk, pv, bt, pos = _random_case(rng)
        qk, sk = pa.quantize_kv(jnp.asarray(pk), kv_dtype)
        qv, sv = pa.quantize_kv(jnp.asarray(pv), kv_dtype)
        scale = 1.0 / np.sqrt(q.shape[-1])
        out = np.asarray(pa.paged_decode_attention(
            jnp.asarray(q), qk, qv, 0, jnp.asarray(bt), jnp.asarray(pos),
            sk, sv, scale=scale))
        # in-register dequant must equal the explicitly dequantized pool
        dk = np.asarray(pa.dequantize_kv(qk, sk))
        dv = np.asarray(pa.dequantize_kv(qv, sv))
        ref = _ref_paged(q, dk, dv, bt, pos, scale)
        assert np.allclose(out, ref, atol=1e-5), np.abs(out - ref).max()
        # and stay near the unquantized fp32 answer
        full = _ref_paged(q, pk, pv, bt, pos, scale)
        tol = 0.05 if kv_dtype == "int8" else 0.2
        assert np.abs(out - full).max() <= tol

    def test_jit_with_donated_pools(self, interpret_mode):
        import jax
        import jax.numpy as jnp
        rng = np.random.default_rng(4)
        q, pk, pv, bt, pos = _random_case(rng, B=2, max_blocks=3,
                                          n_blocks=8)

        @jax.jit
        def step(q, pk, pv, bt, pos):
            return pa.paged_decode_attention(q, pk, pv, 0, bt, pos,
                                             scale=0.5)

        out = np.asarray(step(jnp.asarray(q), jnp.asarray(pk),
                              jnp.asarray(pv), jnp.asarray(bt),
                              jnp.asarray(pos)))
        ref = _ref_paged(q, pk, pv, bt, pos, 0.5)
        assert np.allclose(out, ref, atol=1e-5)

    @pytest.mark.parametrize("kv", ["bf16", "int8", "fp8"])
    @pytest.mark.parametrize("nh,hd", [(16, 128), (16, 96)])
    def test_stacked_pool_layer_index_and_positions(self, interpret_mode,
                                                    nh, hd, kv):
        # the whole [L, ...] arena plus a layer index, 16-token blocks,
        # rows at pos 0, bs - 1, bs, a multiple of the inner step (8
        # blocks = 128 positions) and S_max - 1, all in one batch
        import jax.numpy as jnp
        rng = np.random.default_rng(8)
        bs, max_blocks, L = 16, 24, 3
        S = bs * max_blocks
        q, pk, pv, bt, _ = _random_case(
            rng, B=5, nh=nh, hd=hd, n_blocks=5 * max_blocks + 1, bs=bs,
            max_blocks=max_blocks, L=L)
        pos = np.array([0, bs - 1, bs, 128, S - 1], np.int32)
        scale = 1.0 / np.sqrt(hd)
        if kv == "bf16":
            qj, pkj, pvj = (jnp.asarray(x, jnp.bfloat16) for x in (q, pk, pv))
            sk = sv = None
            q, pk, pv = (np.asarray(x, np.float32) for x in (qj, pkj, pvj))
        else:
            qj = jnp.asarray(q)
            pkj, sk = pa.quantize_kv(jnp.asarray(pk), kv)
            pvj, sv = pa.quantize_kv(jnp.asarray(pv), kv)
            pk = np.asarray(pa.dequantize_kv(pkj, sk))
            pv = np.asarray(pa.dequantize_kv(pvj, sv))
        for layer in (0, L - 1):
            out = np.asarray(pa.paged_decode_attention(
                qj, pkj, pvj, jnp.int32(layer), jnp.asarray(bt),
                jnp.asarray(pos), sk, sv, scale=scale))
            ref = _ref_paged(q, pk, pv, bt, pos, scale, layer=layer)
            # bf16: q * scale and p are rounded to the pool's dtype, as
            # the twin rounds them; quantized pools compute in fp32 here
            tol = 3e-2 if kv == "bf16" else 2e-5
            assert np.allclose(out, ref, atol=tol), np.abs(out - ref).max()

    @pytest.mark.parametrize("kv", ["bf16", "int8", "fp8"])
    def test_dead_blocks_are_never_read(self, interpret_mode, kv):
        # every block a row does not hold live tokens in — the other
        # layers, the unlisted blocks, and the row's own table beyond
        # pos // bs — is poisoned with NaN (Inf scales for quantized
        # pools): one read of one of them and the output is not finite
        import jax.numpy as jnp
        rng = np.random.default_rng(9)
        bs, max_blocks, L, layer = 4, 20, 2, 1
        q, pk, pv, bt, _ = _random_case(
            rng, B=4, nh=8, hd=16, n_blocks=4 * max_blocks + 1, bs=bs,
            max_blocks=max_blocks, L=L)
        pos = np.array([0, bs - 1, 8 * bs, 11 * bs + 1], np.int32)
        livemask = np.zeros(pk.shape[:2], bool)
        for b, p in enumerate(pos):
            livemask[layer, bt[b, :p // bs + 1]] = True
        scale = 1.0 / np.sqrt(q.shape[-1])
        if kv == "bf16":
            ref = _ref_paged(q, pk, pv, bt, pos, scale, layer=layer)
            pk[~livemask] = np.nan
            pv[~livemask] = np.nan
            args = (jnp.asarray(pk), jnp.asarray(pv))
            scales = ()
            tol = 1e-5
        else:
            pkj, sk = pa.quantize_kv(jnp.asarray(pk), kv)
            pvj, sv = pa.quantize_kv(jnp.asarray(pv), kv)
            ref = _ref_paged(q, np.asarray(pa.dequantize_kv(pkj, sk)),
                             np.asarray(pa.dequantize_kv(pvj, sv)), bt, pos,
                             scale, layer=layer)
            # the table's dead entries index the scale arena too: its
            # gather is masked, so poison only what no table names
            named = np.zeros(livemask.shape, bool)
            named[layer, bt.reshape(-1)] = True
            sk = jnp.where(jnp.asarray(named | livemask)[..., None], sk,
                           jnp.inf)
            sv = jnp.where(jnp.asarray(named | livemask)[..., None], sv,
                           jnp.inf)
            poison = jnp.asarray(~livemask)[..., None, None, None]
            fill = 127 if kv == "int8" else jnp.nan
            args = (jnp.where(poison, jnp.asarray(fill, pkj.dtype), pkj),
                    jnp.where(poison, jnp.asarray(fill, pvj.dtype), pvj))
            scales = (sk, sv)
            tol = 1e-5
        out = np.asarray(pa.paged_decode_attention(
            jnp.asarray(q), *args, jnp.int32(layer), jnp.asarray(bt),
            jnp.asarray(pos), *scales, scale=scale))
        assert np.isfinite(out).all()
        assert np.allclose(out, ref, atol=tol), np.abs(out - ref).max()

    def test_shape_mismatch_fails_preflight(self, interpret_mode):
        import jax.numpy as jnp
        rng = np.random.default_rng(5)
        q, pk, pv, bt, pos = _random_case(rng)
        with pytest.raises(ValueError, match="table_rows"):
            pa.paged_decode_attention(
                jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv), 0,
                jnp.asarray(bt[:-1]), jnp.asarray(pos), scale=0.5)


class TestQuantizeKV:
    @pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
    def test_roundtrip(self, kv_dtype):
        import jax.numpy as jnp
        rng = np.random.default_rng(6)
        x = rng.standard_normal((5, 4, 2, 8)).astype(np.float32) * 3.0
        q, s = pa.quantize_kv(jnp.asarray(x), kv_dtype)
        assert q.dtype == pa.KV_DTYPES[kv_dtype]
        assert s.shape == (5, 4) and s.dtype == jnp.float32
        dq = np.asarray(pa.dequantize_kv(q, s))
        amax = np.abs(x).max(axis=(-2, -1), keepdims=True)
        if kv_dtype == "int8":
            # uniform grid: per-element error <= half a step of the
            # per-token absmax scale
            bound = amax / pa.KV_QMAX[kv_dtype] * 0.5
        else:
            # fp8-e4m3 is floating point: 3 mantissa bits -> relative
            # half-ulp error 2^-4, plus a denormal floor near zero
            bound = np.abs(x) * 2.0 ** -4 + amax / pa.KV_QMAX[kv_dtype]
        assert np.all(np.abs(dq - x) <= bound + 1e-6)

    def test_zero_token_quantizes_to_zero(self):
        import jax.numpy as jnp
        x = jnp.zeros((3, 2, 4))
        q, s = pa.quantize_kv(x, "int8")
        assert np.all(np.asarray(q) == 0)
        assert np.all(np.asarray(pa.dequantize_kv(q, s)) == 0)

    def test_kv_dtype_of(self):
        import jax.numpy as jnp
        assert pa.kv_dtype_of(jnp.int8) == "int8"
        assert pa.kv_dtype_of(jnp.float8_e4m3fn) == "fp8"
        assert pa.kv_dtype_of(jnp.bfloat16) is None
        assert pa.kv_dtype_of(jnp.float32) is None


class TestKernelMode:
    """The platform rule that replaced ``FLAGS_paged_kernel``: nothing
    to set, the code observes where it runs."""

    def test_twin_off_tpu(self):
        # was test_default_off: on the CPU the XLA twin, whatever the
        # head shape, and an engine reports it
        assert not pa.on_tpu()
        assert pa.kernel_mode(16, 128) == "off"
        assert _paged(_model()).stats()["kv_kernel"] == "off"

    def test_kernel_on_tpu_for_whole_tiles(self, monkeypatch):
        # was test_pallas_raises_off_tpu: a TPU runs the kernel where
        # its block DMAs move whole (8, 128) tiles, the twin elsewhere;
        # nothing raises and nothing is requested
        monkeypatch.setattr(pa, "on_tpu", lambda: True)
        assert pa.kernel_mode(16, 128) == "pallas"
        assert pa.kernel_mode(8, 256) == "pallas"
        assert pa.kernel_mode(16, 96) == "off"      # 96 lanes of 128
        assert pa.kernel_mode(12, 128) == "off"     # 12 sublanes of 8
        assert pa.kernel_mode(4, 128) == "off"      # 16 heads over mp4

    def test_interpret_hook_runs_kernel_anywhere(self, interpret_mode):
        # was test_invalid_mode_raises: no mode is left to get wrong;
        # the tests' hook runs the kernel at any shape, and the program
        # cache keys on the resolved mode
        assert pa.kernel_mode(4, 8) == "pallas"
        eng = _paged(_model())
        assert eng.stats()["kv_kernel"] == "pallas"
        assert eng._prog_key("decode_paged") == "decode_paged@pallas:raw"
        pa._INTERPRET[0] = False
        assert _paged(_model())._prog_key("decode_paged") == "decode_paged"


def test_preload_imports_pallas_off_the_main_thread():
    # an engine that resolved to the kernel starts this at construction;
    # an engine on the twin (every CPU engine) never does
    import sys
    import threading

    def importers():
        return [t for t in threading.enumerate() if t.name == "pallas-import"]

    for t in importers():
        t.join(60)
    _paged(_model())
    assert not importers()
    pa.preload()
    started = importers()
    assert all(t.daemon for t in started)
    for t in started:
        t.join(60)
    assert "jax.experimental.pallas.tpu" in sys.modules


class TestShapesPreflight:
    def test_check_divides_names_offender(self):
        check_divides("k", seq=(256, 128))           # fine
        with pytest.raises(ValueError) as ei:
            check_divides("flash_attention_fwd", heads=(2, 2),
                          seq_len_q=(100, 64))
        msg = str(ei.value)
        assert "flash_attention_fwd" in msg and "seq_len_q" in msg
        assert "ragged tail" in msg
        with pytest.raises(ValueError, match="must be >= 1"):
            check_divides("k", seq=(256, 0))

    def test_check_equal_names_offender(self):
        check_equal("k", rows=(3, 3))
        with pytest.raises(ValueError) as ei:
            check_equal("paged_attention", table_rows=(2, 3))
        assert "paged_attention" in str(ei.value)
        assert "table_rows" in str(ei.value)

    def test_check_min_tile(self):
        import jax.numpy as jnp
        check_min_tile("k", jnp.float32, sublane=8, lane=LANE)
        with pytest.raises(ValueError, match="lane"):
            check_min_tile("k", jnp.float32, lane=100)
        with pytest.raises(ValueError, match="sublane"):
            check_min_tile("k", jnp.bfloat16, sublane=8)   # bf16 needs 16
        assert min_sublane(jnp.float32) == 8
        assert min_sublane(jnp.bfloat16) == 16
        assert min_sublane(jnp.int8) == 32

    def test_neg_inf_is_finite_and_underflows(self):
        import jax.numpy as jnp
        assert NEG_INF == float(jnp.finfo(jnp.float32).min)
        assert np.isfinite(NEG_INF)
        assert np.isfinite(neg_inf(jnp.bfloat16))
        # the property the mask fill relies on: exp underflows to exactly 0
        assert np.exp(np.float32(NEG_INF)) == 0.0

    def test_neg_inf_softmax_parity_with_legacy_fill(self):
        # swapping -1e30 for finfo.min must not change any masked softmax
        rng = np.random.default_rng(7)
        logits = rng.standard_normal((4, 16)).astype(np.float32)
        mask = rng.random((4, 16)) < 0.5
        mask[:, 0] = True                            # keep one live key

        def sm(fill):
            z = np.where(mask, logits, fill)
            p = np.exp(z - z.max(-1, keepdims=True))
            return p / p.sum(-1, keepdims=True)

        assert np.array_equal(sm(np.float32(-1e30)), sm(np.float32(NEG_INF)))


class TestQuantizedEngines:
    def _baseline(self, m, prompts, seeds, max_new=6, **kw):
        eng = _paged(m)
        hs = [eng.add_request(p, max_new_tokens=max_new, seed=s, **kw)
              for p, s in zip(prompts, seeds)]
        _run(eng, hs)
        return [h.tokens for h in hs]

    def _prompts(self, seed=20):
        rng = np.random.default_rng(seed)
        return [rng.integers(0, 64, size=n).tolist() for n in (5, 9, 3)]

    # int8 engine identity also rides in the cheaper COW/counters tests
    # below and is gated end-to-end by scripts/check_counters.py; keep
    # only the fp8 variant in the tier-1 time budget.
    @pytest.mark.parametrize(
        "kv_dtype",
        [pytest.param("int8", marks=pytest.mark.slow), "fp8"])
    def test_kv_dtype_token_identity(self, kv_dtype):
        m = _model()
        prompts, seeds = self._prompts(), [0, 1, 2]
        refs = self._baseline(m, prompts, seeds)
        eng = _paged(m, kv_dtype=kv_dtype)
        assert eng.stats()["kv_dtype"] == kv_dtype
        hs = [eng.add_request(p, max_new_tokens=6, seed=s)
              for p, s in zip(prompts, seeds)]
        _run(eng, hs)
        for h, r in zip(hs, refs):
            assert h.tokens == r

    def test_pallas_greedy_and_sampled_identity(self, twin_then_kernel):
        m = _model()
        prompts, seeds = self._prompts(21), [3, 4, 5]
        kw = dict(do_sample=True, temperature=0.9, top_k=8)
        greedy_ref = self._baseline(m, prompts, seeds)
        sampled_ref = self._baseline(m, prompts, seeds, **kw)
        twin_then_kernel()
        eng = _paged(m)
        assert eng.stats()["kv_kernel"] == "pallas"
        hs = [eng.add_request(p, max_new_tokens=6, seed=s)
              for p, s in zip(prompts, seeds)]
        _run(eng, hs)
        for h, r in zip(hs, greedy_ref):
            assert h.tokens == r
        eng2 = _paged(m)
        hs2 = [eng2.add_request(p, max_new_tokens=6, seed=s, **kw)
               for p, s in zip(prompts, seeds)]
        _run(eng2, hs2)
        for h, r in zip(hs2, sampled_ref):
            assert h.tokens == r

    def test_pallas_int8_identity(self, twin_then_kernel):
        m = _model()
        prompts, seeds = self._prompts(22), [6, 7, 8]
        refs = self._baseline(m, prompts, seeds)
        twin_then_kernel()
        eng = _paged(m, kv_dtype="int8")
        hs = [eng.add_request(p, max_new_tokens=6, seed=s)
              for p, s in zip(prompts, seeds)]
        _run(eng, hs)
        for h, r in zip(hs, refs):
            assert h.tokens == r

    # PTQ identity is also gated by check_counters.py's direct
    # prefill logit-drift check; full-suite only.
    @pytest.mark.slow
    def test_ptq_weights_token_identity(self):
        m = _model()
        prompts, seeds = self._prompts(23), [9, 10, 11]
        refs = self._baseline(m, prompts, seeds)
        eng = _paged(m, weight_dtype="int8")
        assert eng.stats()["weight_dtype"] == "int8"
        hs = [eng.add_request(p, max_new_tokens=6, seed=s)
              for p, s in zip(prompts, seeds)]
        _run(eng, hs)
        for h, r in zip(hs, refs):
            assert h.tokens == r

    def test_quant_cow_and_prefix_identity(self):
        # COW with scale-row cloning + prefix sharing on a quantized arena
        m = _model()
        rng = np.random.default_rng(24)
        p1 = rng.integers(0, 64, size=10).tolist()
        eng = _paged(m, kv_dtype="int8")
        h1 = eng.add_request(p1, max_new_tokens=6, seed=12)
        _run(eng, [h1])
        base = self._baseline(m, [p1], [12])[0]
        assert h1.tokens == base
        seq1 = p1 + h1.tokens
        p2 = seq1[:15] + rng.integers(0, 64, size=4).tolist()
        h2 = eng.add_request(p2, max_new_tokens=5, seed=13)
        _run(eng, [h2])
        assert eng.stats()["cow_copies"] >= 1
        assert h2.tokens == self._baseline(m, [p2], [13], max_new=5)[0]

    def test_quant_counters_and_bytes_saved(self):
        from paddle_tpu.profiler import counters
        m = _model()
        before = counters.snapshot()
        eng = _paged(m, kv_dtype="int8")
        h = eng.add_request(list(range(8)), max_new_tokens=4, seed=0)
        _run(eng, [h])
        d = counters.delta(before)
        assert d.get("serving.kv.quant.prefill_tokens", 0) > 0
        assert d.get("serving.kv.quant.decode_tokens", 0) > 0
        assert counters.get("serving.kv.quant.bytes_saved") > 0

    def test_kv_dtype_validation(self):
        from paddle_tpu.serving import LLMEngine
        m = _model()
        with pytest.raises(ValueError, match="kv_dtype"):
            _paged(m, kv_dtype="int4")
        # no keyword needed: the one layout is the one that quantizes
        assert LLMEngine(m, max_slots=2, max_seq_len=32, min_bucket=4,
                         kv_dtype="int8").stats()["kv_dtype"] == "int8"
        with pytest.raises(ValueError, match="weight_dtype"):
            _paged(m, weight_dtype="fp4")
        from paddle_tpu.serving.kvcache import BlockPool
        with pytest.raises(ValueError, match="kv_dtype"):
            BlockPool(4, 4, kv_dtype="int4")
