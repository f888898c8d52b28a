"""Jamba (Mamba-1 layers beside attention layers of one K/V head) against
the benchmark's plain reference, at a small size on the CPU.

Tolerances.  Program and reference both compute in float32 here; they
differ in the ORDER of the sums (the scan's read-out, attention's online
softmax over tiles of keys).  On logits of magnitude 3 that reads 4e-6 at
most, so ``TOL = 1e-4`` leaves twenty times of room, and the reference
whose scans keep their state in bfloat16 between tokens reads 1e-2, a
hundred times ``TOL``."""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import jamba_weights as jw                        # noqa: E402
from benchmark.reference import jamba as ref                     # noqa: E402
from paddle_tpu.kernels import gated_delta as gd                 # noqa: E402
from paddle_tpu.kernels import paged_attention as pa             # noqa: E402
from paddle_tpu.models import jamba                              # noqa: E402
from paddle_tpu.profiler import counters                         # noqa: E402
from paddle_tpu.serving import (LLMEngine,                       # noqa: E402
                                RecurrentStateUnsupported)

TOL = 1e-4
SEED = 7
V = 256
WIDTH = 40


def _cfg(**over):
    """Tiny widths, the published pattern's rule at a period of 3: layers
    1 and 4 of 6 attention, the others Mamba (two whole periods)."""
    cfg = {"vocab_size": V, "hidden_size": 64, "intermediate_size": 96,
           "num_hidden_layers": 6, "num_attention_heads": 4,
           "num_key_value_heads": 1, "attn_layer_offset": 1,
           "attn_layer_period": 3, "mamba_d_state": 16, "mamba_d_conv": 4,
           "mamba_expand": 2, "mamba_dt_rank": 8, "mamba_conv_bias": True,
           "mamba_proj_bias": False, "rms_norm_eps": 1e-6,
           "max_position_embeddings": 512, "num_experts": 1,
           "sliding_window": None, "tie_word_embeddings": True,
           "hidden_act": "silu", "initializer_range": 0.1}
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def cfg():
    return _cfg()


@pytest.fixture(scope="module")
def model(cfg):
    config = jamba.JambaConfig.from_hf(cfg, initializer_range=0.1,
                                       dtype="float32")
    assert set(jamba.param_shapes(config)) == set(jw.PROGRAM_TENSORS)
    m = jamba.JambaForCausalLM(config, tensors=lambda name: (
        jw.program_tensor(cfg, SEED, name, "float32")))
    m.eval()
    return m


def _reference(cfg, ids, state=jnp.float32):
    """The reference's logits at every position of ``ids``, which are
    padded to ``WIDTH`` (causal: what follows a position changes nothing
    before it), so that the reference compiles once."""
    padded = np.pad(np.asarray(ids), (0, WIDTH - len(ids)))
    return np.asarray(ref.logits_rows(
        jw.top(cfg, SEED, "float32"),
        lambda l: jw.layer(cfg, SEED, l, "float32"), cfg,
        jnp.asarray(padded), 0, WIDTH, "f32", state))[:len(ids)]


@pytest.fixture()
def interpret_mode():
    pa._INTERPRET[0] = True
    yield
    pa._INTERPRET[0] = False


def _engine(model, **kw):
    args = dict(block_size=4, max_slots=3, max_seq_len=128, n_blocks=97,
                prefill_chunk=16, min_bucket=1)
    args.update(kw)
    return LLMEngine(model, **args)


# ---------------------------------------------------------------------------
# the configuration
# ---------------------------------------------------------------------------
def test_the_published_pattern_puts_attention_at_layers_7_and_21():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "jamba2-3b.json")) as f:
        hf = json.load(f)
    c = jamba.JambaConfig.from_hf(hf)
    assert [i for i, k in enumerate(c.layer_types)
            if k == jamba.ATTN] == [7, 21]
    assert (c.num_layers, c.inner, c.head_dim, c.kv_row) == (28, 5120, 128,
                                                             256)
    assert jw.layer_types(hf) == tuple(c.layer_types)


@pytest.mark.parametrize("key,value,says", [
    ("num_experts", 16, "expert layers are not implemented"),
    ("sliding_window", 4096, "window attention is not implemented")])
def test_from_hf_refuses_what_is_not_implemented(key, value, says):
    with pytest.raises(ValueError, match=says):
        jamba.JambaConfig.from_hf(_cfg(**{key: value}))


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------
def test_forward_is_the_reference_and_a_bf16_state_is_not(cfg, model):
    ids = np.random.default_rng(0).integers(0, V, 40).astype(np.int32)
    got = np.asarray(model.forward_logits(model.decode_state(), ids[None]))
    want = _reference(cfg, ids)
    np.testing.assert_allclose(got[0], want, atol=TOL, rtol=TOL)
    assert np.abs(_reference(cfg, ids, jnp.bfloat16) - want).max() > 20 * TOL


def _served_logits(eng, prompt, n_new, chunk):
    """Drive the engine's model calls for one request: admission through
    the engine, then prefill chunks of ``chunk`` tokens and ``n_new``
    decode launches fed the reference's greedy tokens, every launch's
    logits at the row's position."""
    m = eng.model
    req = eng.add_request(prompt, max_new_tokens=n_new + 1)
    eng._admit([])
    slot = req.slot
    bt = jnp.asarray(eng._bt[slot])
    prefill = jax.jit(functools.partial(m.prefill_paged,
                                        kernel=eng.kv_kernel),
                      static_argnums=8)
    decode = jax.jit(functools.partial(m.decode_paged, kernel=eng.kv_kernel))
    pk, pv, st = eng._pk, eng._pv, eng._st
    out, T = [], len(prompt)
    for start in range(0, T, chunk):
        take = min(chunk, T - start)
        ids = np.zeros((1, chunk), np.int32)
        ids[0, :take] = prompt[start:start + take]
        pk, pv, st, logits = prefill(
            eng._w, jnp.asarray(ids), jnp.int32(start), jnp.int32(take), bt,
            pk, pv, st, slot)
        out.append((start + take - 1, np.asarray(logits[0])))
    seq = list(prompt)
    B = eng.max_slots
    bts = jnp.zeros((B, bt.shape[0]), jnp.int32).at[slot].set(bt)
    running = jnp.zeros(B, bool).at[slot].set(True)
    for _ in range(n_new):
        seq.append(int(np.argmax(out[-1][1])))
        pos = jnp.zeros(B, jnp.int32).at[slot].set(len(seq) - 1)
        tok = jnp.zeros(B, jnp.int32).at[slot].set(seq[-1])
        logits, pk, pv, st = decode(
            eng._w, tok, pos, jnp.where(running[:, None], bts, 0), pk, pv,
            st, running)
        out.append((len(seq) - 1, np.asarray(logits[slot])))
    return np.asarray(seq), out


@pytest.mark.parametrize("chunk", [1, 3, 16])
def test_chunked_prefill_then_decode_is_the_reference(cfg, model, chunk):
    """A 19-token prompt in chunks of 1, 3 (every chunk shorter than the
    convolution's window) or 16 (a short padded last chunk), then 4 decode
    steps through the cache."""
    eng = _engine(model)
    assert eng.kv_kernel == "off" and not eng.stats()["prefix_cache"]
    prompt = np.random.default_rng(4).integers(0, V, 19).astype(np.int32)
    seq, out = _served_logits(eng, prompt, 4, chunk)
    want = _reference(cfg, seq)
    for p, logits in out:
        np.testing.assert_allclose(logits, want[p], atol=TOL, rtol=TOL)


def test_served_tokens_through_the_kernels_in_reused_slots(cfg, model,
                                                           interpret_mode):
    """Through ``LLMEngine.step`` with the Pallas kernels (interpret mode):
    three requests on two slots, so the third takes a slot its last owner
    left, and rows between chunks or free sit out decode launches; every
    served token is the reference's first choice for its own sequence."""
    before = counters.snapshot()
    eng = _engine(model, max_slots=2, prefill_chunk=8, min_bucket=8)
    assert eng.kv_kernel == "pallas"
    r = np.random.default_rng(5)
    prompts = [r.integers(0, V, n).astype(np.int32) for n in (13, 6, 9)]
    reqs = [eng.add_request(p, max_new_tokens=m)
            for p, m in zip(prompts, (5, 3, 4))]
    while eng.has_work():
        eng.step()
    took = counters.delta(before)
    assert took.get("kernels.selective_scan.pallas", 0) > 0
    assert not took.get("kernels.selective_scan.xla")
    for p, req in zip(prompts, reqs):
        served = np.asarray(req.tokens, np.int32)
        assert req.finish_reason == "length", req.finish_reason
        logits = _reference(cfg, np.concatenate([p, served[:-1]]))
        rows = logits[len(p) - 1:]
        gap = rows.max(-1) - rows[np.arange(len(served)), served]
        assert gap.max() < TOL


# ---------------------------------------------------------------------------
# what the engine holds
# ---------------------------------------------------------------------------
def test_the_engine_holds_the_state_beside_the_row_pool(model):
    eng = _engine(model)
    c = model.config
    st = eng.stats()
    assert st["state_bytes"] == 4 * 3 * (16 * 128 * 4 + 3 * 128 * 4)
    assert eng._pv is None and eng._pk.shape == (2, 97, 4, 128)
    assert eng._st["ssm_state"].shape == (4, 3, c.d_state, c.inner)
    assert eng._st["ssm_conv"].shape == (4, 3, 8, 48)     # 3 x 128 as 8 x 48
    with pytest.raises(RecurrentStateUnsupported):
        _engine(model, kv_dtype="int8")


def test_the_conv_bias_leaves_the_bias_free_form_bit_for_bit():
    """``causal_conv`` without a bias is the plain sum of taps it was
    before the bias came (what Olmo-Hybrid runs), and with one adds it."""
    r = np.random.default_rng(3)
    x = jnp.asarray(r.normal(size=(2, 9, 6)), jnp.float32)
    w = jnp.asarray(r.normal(size=(4, 6)), jnp.float32)
    tail = jnp.asarray(r.normal(size=(2, 3, 6)), jnp.float32)
    b = jnp.asarray(r.normal(size=(6,)), jnp.float32)
    xin = jnp.concatenate([tail, x], 1)
    plain = sum(w[i] * xin[:, i:i + 9] for i in range(4))
    y, new = gd.causal_conv(x, w, tail)
    assert np.array_equal(np.asarray(y), np.asarray(plain))
    assert np.array_equal(np.asarray(new), np.asarray(xin[:, 9:]))
    yb, _ = gd.causal_conv(x, w, tail, bias=b)
    assert np.array_equal(np.asarray(yb), np.asarray(plain + b))
