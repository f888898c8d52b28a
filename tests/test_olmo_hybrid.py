"""Olmo-Hybrid (Gated DeltaNet layers beside full attention) against the
benchmark's plain reference, at a small size on the CPU.

Tolerances.  Program and reference both compute in float32 here; they
differ in the ORDER of the sums (the chunked rule solves 64 positions at
once, the reference walks token by token; attention folds key tiles).  On
logits of magnitude 4 that reads 1e-4 at most, so ``TOL = 1e-3`` leaves
ten times of room, and the control below (the same comparison with the
recurrent state held in bfloat16 between tokens) reads over 1e-2 and
fails it."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare                                    # noqa: E402
from benchmark import olmo_hybrid_weights as hweights            # noqa: E402
from benchmark.reference import olmo_hybrid as ref               # noqa: E402
from paddle_tpu.kernels import gated_delta as gd                 # noqa: E402
from paddle_tpu.kernels import paged_attention as pa             # noqa: E402
from paddle_tpu.models.olmo_hybrid import (OlmoHybridConfig,     # noqa: E402
                                           OlmoHybridForCausalLM)
from paddle_tpu.serving import (LLMEngine,                       # noqa: E402
                                RecurrentStateUnsupported, bucket_length)

TOL = 1e-3
SEED = 7


def _cfg(heads=2, head_dim=32, layers=8):
    return {"vocab_size": 512, "hidden_size": heads * head_dim,
            "intermediate_size": 128, "num_hidden_layers": layers,
            "num_attention_heads": heads, "num_key_value_heads": heads,
            "max_position_embeddings": 512, "rms_norm_eps": 1e-6,
            "layer_types": (["linear_attention"] * 3
                            + ["full_attention"]) * (layers // 4),
            "linear_num_key_heads": 2, "linear_num_value_heads": 2,
            "linear_key_head_dim": 16, "linear_value_head_dim": 32,
            "linear_conv_kernel_dim": 4, "initializer_range": 0.1}


def _model(cfg):
    model = OlmoHybridForCausalLM(OlmoHybridConfig.from_hf(
        cfg, initializer_range=cfg["initializer_range"], dtype="float32"))
    named = dict(model.named_parameters())
    assert set(named) == set(hweights.PROGRAM_TENSORS)
    for name, made in hweights.program(cfg, SEED, "float32"):
        assert tuple(named[name].shape) == made.shape, name
        named[name]._data = made
    model.eval()
    return model


def _ref_rows(cfg, ids, first, n, **kw):
    """Rows ``first .. first + n - 1`` of the reference's logits.  The ids
    are padded to one width (causal: what follows changes nothing before
    it), so the reference compiles once per kind of layer."""
    top = hweights.top(cfg, SEED, "float32")
    ids = np.pad(np.asarray(ids), (0, 256 - len(ids)))
    return np.asarray(ref.logits_rows(
        top, lambda l: hweights.layer(cfg, SEED, l, "float32"), cfg,
        ids, np.int32(first), 16, "f32", **kw))[:n] if n <= 16 else \
        np.asarray(ref.logits_rows(
            top, lambda l: hweights.layer(cfg, SEED, l, "float32"), cfg,
            ids, 0, 256, "f32", **kw))[first:first + n]


@pytest.fixture(scope="module")
def cfg():
    return _cfg()


@pytest.fixture(scope="module")
def model(cfg):
    return _model(cfg)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(0, 512, 170).astype(np.int32)


# ---------------------------------------------------------------------------
# the mixer: chunked form and one-token form against the recurrence
# ---------------------------------------------------------------------------
def _rule_inputs(T, B=2, H=2, K=16, V=32, seed=0):
    r = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(r.standard_normal((B, T, H, K))) * K ** -0.5
    k = unit(r.standard_normal((B, T, H, K)))
    v = r.standard_normal((B, T, H, V))
    g = -r.uniform(0.0, 1.5, (B, T, H))
    beta = r.uniform(0.0, 2.0, (B, T, H))        # up to 2: negative eigenvalues
    s0 = r.standard_normal((B, H, K, V))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta, s0)]


def _token_by_token(q, k, v, g, beta, s, upto=None):
    xs = [jnp.moveaxis(x, 1, 0)[:upto] for x in (q, k, v, g, beta)]
    s, o = jax.lax.scan(
        lambda s, x: gd.gdn_step(*x, s)[::-1], s, tuple(xs))
    return jnp.moveaxis(o, 0, 1), s


@pytest.mark.parametrize("T", [16, 64, 192, 512, 8])
def test_chunked_rule_is_the_recurrence(T):
    q, k, v, g, beta, s0 = _rule_inputs(T)
    o, s = gd.gdn_chunk(q, k, v, g, beta, s0)
    o_ref, s_ref = _token_by_token(q, k, v, g, beta, s0)
    np.testing.assert_allclose(o, o_ref, atol=2e-4)
    np.testing.assert_allclose(s, s_ref, atol=2e-4)


@pytest.mark.parametrize("T,live", [(128, 77), (512, 300), (8, 5)])
def test_padded_bucket_leaves_the_state_where_the_tokens_end(T, live):
    q, k, v, g, beta, s0 = _rule_inputs(T, seed=1)
    mask = (jnp.arange(T) < live)[None, :, None]
    o, s = gd.gdn_chunk(q, k, v, jnp.where(mask, g, 0.0),
                        jnp.where(mask, beta, 0.0), s0)
    o_ref, s_ref = _token_by_token(q, k, v, g, beta, s0, upto=live)
    np.testing.assert_allclose(o[:, :live], o_ref, atol=2e-4)
    np.testing.assert_allclose(s, s_ref, atol=2e-4)


def _hard_systems(c, shared, N=24, K=96, R=288):
    """``N`` systems ``(I + A) X = rhs`` of the chunked rule at its hard
    corner: ``beta`` in 1.8-2.0 and ``g`` near 0, so nothing damps the
    entries under the diagonal, and keys that share ``shared`` of their
    norm, so those entries are all of one sign and near ``2 shared^2``."""
    r = np.random.default_rng(c + int(10 * shared))
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    k = unit(shared * unit(r.standard_normal((N, 1, K)))
             + (1 - shared ** 2) ** 0.5 * unit(r.standard_normal((N, c, K))))
    beta = r.uniform(1.8, 2.0, (N, c))
    G = np.cumsum(-r.uniform(0.0, 1e-3, (N, c)), -1)
    decay = np.exp(np.tril(G[:, :, None] - G[:, None, :]))
    A = np.tril(beta[..., None] * decay * (k @ k.transpose(0, 2, 1)), -1)
    return A.astype(np.float32), r.standard_normal((N, c, R)).astype(
        np.float32)


@pytest.mark.parametrize("shared", [0.0, 0.5, 0.9])
@pytest.mark.parametrize("c", [8, 16, 32, 37, 64])
def test_blocked_solve_is_forward_substitution(c, shared):
    """The solve alone against row-by-row substitution in float64; 37 rows
    are not whole blocks (the eager forward pass of a short sequence)."""
    A, rhs = _hard_systems(c, shared)
    want = rhs.astype(np.float64)
    for t in range(1, c):
        want[:, t] -= np.einsum("ns,nsr->nr", A[:, t, :t].astype(np.float64),
                                want[:, :t])
    got = np.asarray(gd._solve_unit_lower(jnp.asarray(A), jnp.asarray(rhs)))
    assert got.shape == rhs.shape and got.dtype == np.float32
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()


def test_no_triangular_solve_is_left(model):
    """The chunk's system goes through products on every backend: neither
    the rule at the docqa cell's chunk nor the model's chunk program lowers
    to a ``triangular_solve``, and the file no longer reaches for one."""
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    text = jax.jit(gd.gdn_chunk).lower(
        f32(1, 512, 30, 96), f32(1, 512, 30, 96), f32(1, 512, 30, 192),
        f32(1, 512, 30), f32(1, 512, 30), f32(1, 30, 96, 192)).as_text()
    assert "dot_general" in text and "triangular" not in text.lower()
    pk, pv, st = _pools(model, max_blocks=4, B=2)
    text = jax.jit(model.prefill_paged).lower(
        model.decode_state(), np.zeros((1, 64), np.int32), np.int32(0),
        np.int32(64), np.arange(1, 5, dtype=np.int32), pk, pv, st,
        np.int32(0)).as_text()
    assert "dot_general" in text and "triangular" not in text.lower()
    with open(gd.__file__) as f:
        assert "jax.scipy" not in f.read()


def test_conv_carries_its_tail_across_chunks():
    r = np.random.default_rng(2)
    x = jnp.asarray(r.standard_normal((1, 40, 6)), jnp.float32)
    w = jnp.asarray(r.standard_normal((4, 6)), jnp.float32)
    whole, _ = gd.causal_conv(x, w, jnp.zeros((1, 3, 6)))
    np.testing.assert_allclose(whole[0], ref.causal_conv(x[0], w), atol=1e-6)
    # 25 tokens in a bucket of 32, then the rest
    first = jnp.pad(x[:, :25], ((0, 0), (0, 7), (0, 0)))
    y1, tail = gd.causal_conv(first, w, jnp.zeros((1, 3, 6)), 25)
    y2, _ = gd.causal_conv(x[:, 25:], w, tail)
    np.testing.assert_allclose(
        jnp.concatenate([y1[:, :25], y2], 1), whole, atol=1e-6)


def test_forward_is_the_reference(model, cfg, ids):
    """A length that is not a multiple of the chunk (150 = 2 x 64 + 22)."""
    got = np.asarray(model.forward_logits(model.decode_state(),
                                          jnp.asarray(ids[:150])[None]))[0]
    want = _ref_rows(cfg, ids[:150], 0, 150)
    assert np.abs(got - want).max() < TOL


# ---------------------------------------------------------------------------
# chunked prefill, then decode, through the paged cache: on logits
# ---------------------------------------------------------------------------
def _pools(model, max_blocks, B, bs=16, state_dtype="float32",
           heads_in_pool=None):
    """Empty K/V pools of ``max_blocks + 1`` blocks and the per-slot state
    of ``B`` rows, the state filled with garbage (a last owner's)."""
    spec = model.cache_spec()
    nh, hd = spec["kv_heads"], spec["head_dim"]
    nhp = heads_in_pool or pa.pool_heads(nh, hd)
    pk = jnp.zeros((spec["kv_layers"], max_blocks + 1, bs, nhp, hd),
                   jnp.float32)
    st = {name: jnp.full(tuple(lead) + (B,) + tuple(per), 7.0,
                         state_dtype if name == "gdn_state" else dt)
          for name, (lead, per, dt) in spec["slot_state"].items()}
    return pk, pk, st


def _serve_logits(model, ids, T, n, chunk=64, B=4, slot=2, bs=16,
                  state_dtype="float32", heads_in_pool=None, kernel=None):
    """Logits at positions ``T - 1 .. T + n - 2``: ``ids[:T]`` prefilled in
    chunks into slot ``slot``, then ``ids[T:]`` decoded one by one.  The
    slot's state starts as garbage (a last owner's), the other rows' too."""
    max_blocks = -(-(T + n) // bs)
    pk, pv, st = _pools(model, max_blocks, B, bs, state_dtype, heads_in_pool)
    before = jax.tree_util.tree_map(np.asarray, st)
    w = model.decode_state()
    row = np.arange(1, max_blocks + 1, dtype=np.int32)
    prefill = jax.jit(model.prefill_paged)
    decode = jax.jit(lambda *a: model.decode_paged(*a, kernel=kernel))
    for start in range(0, T, chunk):
        take = min(chunk, T - start)
        C = bucket_length(take, 8, chunk)
        buf = np.zeros((1, C), np.int32)
        buf[0, :take] = ids[start:start + take]
        pk, pv, st, logits = prefill(w, buf, np.int32(start), np.int32(take),
                                     row, pk, pv, st, np.int32(slot))
    out = [np.asarray(logits)[0]]
    bt = np.zeros((B, max_blocks), np.int32)
    bt[slot] = row
    running = np.arange(B) == slot
    for j in range(n - 1):
        tok = np.zeros(B, np.int32)
        pos = np.zeros(B, np.int32)
        tok[slot], pos[slot] = ids[T + j], T + j
        logits, pk, pv, st = decode(w, tok, pos, bt, pk, pv, st, running)
        out.append(np.asarray(logits)[slot])
    return np.stack(out), before, jax.tree_util.tree_map(np.asarray, st)


def test_chunked_prefill_then_decode_is_the_reference(model, cfg, ids):
    T, n = 150, 12
    got, before, after = _serve_logits(model, ids, T, n)
    want = _ref_rows(cfg, ids[:T + n - 1], T - 1, n)
    assert np.abs(got - want).max() < TOL
    # rows that were not running kept their state bit for bit through the
    # other row's chunks and decode launches; the served row did not
    for name in after:
        others = [b for b in range(4) if b != 2]
        assert np.array_equal(after[name][:, others], before[name][:, others])
        assert not np.array_equal(after[name][:, 2], before[name][:, 2])


def test_state_in_bfloat16_fails_the_tolerance(model, cfg, ids):
    """The control: the same comparison with the recurrent state rounded
    to bfloat16 between tokens and chunks, on either side."""
    T, n = 150, 12
    want = _ref_rows(cfg, ids[:T + n - 1], T - 1, n)
    got, _, _ = _serve_logits(model, ids, T, n, state_dtype="bfloat16")
    assert np.abs(got - want).max() > 5 * TOL
    low = _ref_rows(cfg, ids[:T + n - 1], T - 1, n,
                    state_dtype=jnp.bfloat16)
    assert np.abs(low - want).max() > 5 * TOL


# ---------------------------------------------------------------------------
# through LLMEngine
# ---------------------------------------------------------------------------
def _engine(model, **kw):
    args = dict(block_size=16, max_slots=2,
                max_seq_len=256, n_blocks=40, prefill_chunk=64)
    args.update(kw)
    return LLMEngine(model, **args)


def _drain(eng, limit=2000):
    for _ in range(limit):
        if not eng.has_work():
            return
        eng.step()
    raise AssertionError("engine did not converge")


def _gap(cfg, prompt, tokens):
    served = np.asarray(tokens, np.int32)
    rows = _ref_rows(cfg, np.concatenate([prompt, served[:-1]]),
                     len(prompt) - 1, len(served))
    return compare.token_gaps(rows, served).max()


def test_engine_serves_what_the_reference_puts_first(model, cfg):
    """Six requests over two slots, so every slot is reused and prompts of
    several chunks prefill between other rows' decode launches.  A served
    token whose reference logit lay below the reference's best would show
    a state that leaked from the slot's last owner, or one that a decode
    launch moved between two chunks."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 512, n).astype(np.int32)
               for n in (150, 37, 200, 64, 90, 129)]
    eng = _engine(model)
    st = eng.stats()
    assert st["prefix_cache"] is False and st["kv_kernel"] == "off"
    assert st["state_bytes"] == 2 * 6 * (2 * 16 * 32 * 4 + 3 * 128 * 4)
    handles = [eng.add_request(p, max_new_tokens=10, seed=0)
               for p in prompts]
    eng.step()
    assert eng.stats()["kv_live_bytes"] == (
        eng.stats()["blocks_live"] * 2 * 2 * 16 * 2 * 32 * 4)
    _drain(eng)
    for p, h in zip(prompts, handles):
        assert h.finish_reason == "length"
        assert _gap(cfg, p, h.tokens) < TOL


def test_engine_without_a_keyword_serves_a_model_with_state(model):
    """No second layout to refuse: ``LLMEngine(model)`` builds the one
    engine, which holds the recurrent state beside the pool."""
    eng = LLMEngine(model, max_slots=2, max_seq_len=64)
    assert type(eng) is LLMEngine
    st = eng.stats()
    assert st["state_bytes"] > 0 and st["blocks_total"] == 2 * 4
    assert "kv_layout='slots'" not in RecurrentStateUnsupported.__doc__


def test_prefix_cache_is_resolved_off_and_says_so(model):
    eng = _engine(model, prefix_cache=True)
    assert eng.prefix is None and eng.stats()["prefix_cache"] is False
    # a GPT engine keeps it
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    gpt = GPTForCausalLM(GPTConfig(vocab_size=64, hidden_size=32,
                                   num_layers=2, num_heads=4, max_seq_len=64,
                                   use_flash_attention=False))
    st = LLMEngine(gpt, max_slots=2).stats()
    assert st["prefix_cache"] is True and st["state_bytes"] == 0


def _gpt(vocab=512):
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    return GPTForCausalLM(GPTConfig(
        vocab_size=vocab, hidden_size=32, num_layers=1, num_heads=2,
        max_seq_len=256, use_flash_attention=False))


@pytest.mark.parametrize("how", [
    "kv_dtype", "host_kv_blocks", "adapter_slots", "mesh",
    "draft_model", "hybrid_draft", "export_request", "adopt_migration"])
def test_what_would_lose_the_state_is_refused(model, how):
    if how == "mesh":
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("mp",))
    build = {
        "kv_dtype": lambda: _engine(model, kv_dtype="int8"),
        "host_kv_blocks": lambda: _engine(model, host_kv_blocks=8),
        "adapter_slots": lambda: _engine(model, adapter_slots=2),
        "mesh": lambda: _engine(model, mesh=mesh),
        "draft_model": lambda: _engine(model, draft_model=_gpt()),
        "hybrid_draft": lambda: _engine(_gpt(), draft_model=model),
    }
    if how in build:
        with pytest.raises(RecurrentStateUnsupported):
            build[how]()
        return
    eng = _engine(model)
    req = eng.add_request(np.arange(20, dtype=np.int32), max_new_tokens=4,
                          hold_after_prefill=True)
    _drain_until_held(eng, req)
    with pytest.raises(RecurrentStateUnsupported):
        if how == "export_request":
            eng.export_request(req)
        else:
            eng.adopt_migration({"block_size": 16, "kv_dtype": None}, eng)


def _drain_until_held(eng, req):
    for _ in range(50):
        if req.state == "held":
            return
        eng.step()
    raise AssertionError(req.state)


def test_step_span_counts_both_kinds_of_cache(model):
    from paddle_tpu.profiler import host_tracer
    eng = _engine(model)
    eng.add_request(np.arange(40, dtype=np.int32), max_new_tokens=3)
    host_tracer.start()
    try:
        _drain(eng)
    finally:
        host_tracer.stop()
    counts = [ev[5] for ev in host_tracer.events()
              if ev[0] == "serving.step" and ev[5]]
    assert counts and all(c["state_bytes"] == eng.stats()["state_bytes"]
                          for c in counts)
    assert max(c["kv_live_bytes"] for c in counts) == (
        3 * 2 * 2 * 16 * 2 * 32 * 4)      # 3 blocks of 40 + 2 positions


# ---------------------------------------------------------------------------
# the pool's head axis padded to whole tiles
# ---------------------------------------------------------------------------
@pytest.fixture()
def interpret_mode():
    pa._INTERPRET[0] = True
    yield
    pa._INTERPRET[0] = False


def test_pool_heads_pads_whole_lanes_only():
    assert pa.pool_heads(30, 128) == 32 and pa.pool_heads(16, 128) == 16
    assert pa.pool_heads(3, 128) == 8
    assert pa.pool_heads(16, 96) == 16 and pa.pool_heads(3, 32) == 3
    x = jnp.ones((5, 3, 128))
    assert pa.pad_heads(x, 3) is x
    padded = pa.pad_heads(x, 8)
    assert padded.shape == (5, 8, 128) and float(padded[:, 3:].sum()) == 0


def test_padded_heads_never_reach_the_output(interpret_mode):
    """3 heads of 128 stored as 8: the kernel's walk over the padded pool
    gives what the twin gives over a pool of 3."""
    cfg = _cfg(heads=3, head_dim=128, layers=4)
    model = _model(cfg)
    ids = np.random.default_rng(4).integers(0, 512, 60).astype(np.int32)
    padded, _, _ = _serve_logits(model, ids, 40, 6, kernel="pallas")
    plain, _, _ = _serve_logits(model, ids, 40, 6, heads_in_pool=3)
    assert np.abs(padded - plain).max() < TOL
    eng = _engine(model, max_seq_len=128, n_blocks=20)
    assert eng.stats()["kv_kernel"] == "pallas"
    assert eng._pk.shape[3] == 8
    h = eng.add_request(ids[:40], max_new_tokens=5, seed=0)
    _drain(eng)
    assert _gap(cfg, ids[:40], h.tokens) < TOL


def test_gpt_pool_pads_too(interpret_mode):
    """The open fault of head counts that are not whole tiles: a GPT of 3
    heads of 128 now decodes through the kernel over a pool of 8, and
    serves what ``generate`` does over its unpadded cache."""
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    gpt = GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=384, num_layers=2, num_heads=3,
        max_seq_len=64, use_flash_attention=False))
    gpt.eval()
    prompt = np.arange(3, 30, dtype=np.int32)
    eng = LLMEngine(gpt, max_slots=2, prefill_chunk=16)
    assert eng.stats()["kv_kernel"] == "pallas" and eng._pk.shape[3] == 8
    a = eng.add_request(prompt, max_new_tokens=6, seed=0)
    _drain(eng)
    import paddle_tpu as paddle
    want = np.asarray(gpt.generate(paddle.to_tensor(prompt[None]),
                                   max_new_tokens=6).numpy())[0, len(prompt):]
    assert a.tokens == want.tolist()
