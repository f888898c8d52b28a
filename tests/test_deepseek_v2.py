"""DeepSeek-V2 (latent attention, shared and routed experts) against the
benchmark's plain reference, at a small size on the CPU.

Tolerances.  Program and reference both compute in float32 here; they
differ in the ORDER of the sums (attention folds key tiles, the absorbed
form contracts over the latent width instead of the head's, the experts
run as grouped products over sorted rows).  On logits of magnitude 3 that
reads 2e-5 at most, so ``TOL = 1e-3`` leaves fifty times of room, and the
controls (an expert left out, the rotary layout swapped) read over 1e-2."""

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
from benchmark import deepseek_v2_weights as dweights            # noqa: E402
from benchmark.reference import deepseek_v2 as ref               # noqa: E402
from paddle_tpu.kernels import mla_attention as mla              # noqa: E402
from paddle_tpu.kernels import moe                               # noqa: E402
from paddle_tpu.kernels import paged_attention as pa             # noqa: E402
from paddle_tpu.models import deepseek_v2 as ds                  # noqa: E402
from paddle_tpu.profiler import counters                         # noqa: E402
from paddle_tpu.serving import (LatentCacheUnsupported,          # noqa: E402
                                LLMEngine, bucket_length)

TOL = 1e-3
SEED = 11
YARN = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 64,
        "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
        "mscale_all_dim": 0.707}


def _cfg(held=(0, 16), **over):
    """Tiny widths; ``held`` is the share of the 16 routed experts."""
    cfg = {"vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
           "moe_intermediate_size": 32, "num_hidden_layers": 3,
           "num_attention_heads": 4, "num_key_value_heads": 4,
           "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
           "qk_rope_head_dim": 8, "v_head_dim": 16, "n_shared_experts": 2,
           "n_routed_experts": held[1], "experts_held_first": held[0],
           "published": {"n_routed_experts": 16}, "n_group": 4,
           "topk_group": 2, "num_experts_per_tok": 3,
           "routed_scaling_factor": 16, "first_k_dense_replace": 1,
           "rms_norm_eps": 1e-6, "rope_theta": 10000, "rope_scaling": YARN,
           "max_position_embeddings": 512, "initializer_range": 0.1}
    cfg.update(over)
    return cfg


def _model(cfg):
    width, first, held = dweights.share(cfg)
    model = ds.DeepseekV2ForCausalLM(ds.DeepseekV2Config.from_hf(
        cfg, experts_held=(first, held), n_routed_experts=width,
        initializer_range=cfg["initializer_range"], dtype="float32"))
    named = dict(model.named_parameters())
    assert set(named) == set(dweights.PROGRAM_TENSORS)
    for name, made in dweights.program(cfg, SEED, "float32"):
        assert tuple(named[name].shape) == made.shape, name
        named[name]._data = made
    model.eval()
    return model


def _ref(cfg, ids, first, n):
    """Rows ``first .. first + n - 1`` of the reference's logits, and the
    routers' choices ``[expert layers, len(ids), k]``.  The ids are padded
    to one width (causal: what follows changes nothing before it), so the
    reference compiles once."""
    T = len(ids)
    padded = np.pad(np.asarray(ids), (0, 256 - T))
    _, e0, held = dweights.share(cfg)
    rows, chosen = ref.logits_rows(
        dweights.top(cfg, SEED, "float32"),
        lambda l: dweights.layer(cfg, SEED, l, "float32"),
        lambda l, e: dweights.expert(cfg, SEED, l, e, "float32"),
        cfg, (e0, held), padded, 0, 256, "f32")
    return np.asarray(rows)[first:first + n], np.asarray(chosen)[:, :T]


@pytest.fixture(scope="module")
def cfg():
    return _cfg(held=(4, 8))      # a share: experts 4-11 of 16


@pytest.fixture(scope="module")
def model(cfg):
    return _model(cfg)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(0, 512, 170).astype(np.int32)


@pytest.fixture()
def interpret_mode():
    pa._INTERPRET[0] = True
    yield
    pa._INTERPRET[0] = False


# ---------------------------------------------------------------------------
# positions
# ---------------------------------------------------------------------------
def test_yarn_tables_are_the_formula():
    """The published numbers: 64 rotary dims, theta 1e4, factor 40 over an
    original length of 4096, beta 32 and 1.  The correction range is dims
    10 to 23: the model's own frequency up to 10, a fortieth of it from 23
    on, a straight ramp between; cos and sin are not rescaled (mscale ==
    mscale_all_dim) and the softmax scale carries 1.2608 squared."""
    c = ds.DeepseekV2Config(rope_scaling=dict(
        YARN, original_max_position_embeddings=4096))
    i = np.arange(32)
    extra = 10000.0 ** (-2.0 * i / 64)
    ramp = np.clip((i - 10) / (23 - 10), 0, 1)
    want = extra / 40 * ramp + extra * (1 - ramp)
    got = np.asarray(c.inv_freq)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got[:11], extra[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], extra[23:] / 40, rtol=1e-6)
    assert c.rope_mscale == 1.0
    m = 0.1 * 0.707 * np.log(40.0) + 1.0
    assert abs(m - 1.2608) < 1e-4
    assert abs(c.softmax_scale - 192 ** -0.5 * m * m) < 1e-9
    np.testing.assert_allclose(
        got, np.asarray(ref.yarn_inv_freq(
            {"qk_rope_head_dim": 64, "rope_theta": 10000,
             "rope_scaling": c.rope_scaling})), rtol=1e-6)
    # no scaling: plain rotary frequencies
    np.testing.assert_allclose(
        np.asarray(ds.DeepseekV2Config().inv_freq), extra, rtol=1e-6)


# ---------------------------------------------------------------------------
# the forward pass, and the two forms of attention
# ---------------------------------------------------------------------------
def test_forward_is_the_reference(model, cfg, ids):
    got = np.asarray(model.forward_logits(model.decode_state(),
                                          jnp.asarray(ids[:150])[None]))[0]
    want, _ = _ref(cfg, ids[:150], 0, 150)
    assert np.abs(got - want).max() < TOL


def test_absorbed_attention_is_materialised_attention(model, ids):
    """The decode step (absorbed, the XLA twin of the walk) against the
    plain forward pass (materialised) at the same positions."""
    T, n = 60, 30
    plain = np.asarray(model.forward_logits(
        model.decode_state(), jnp.asarray(ids[:T + n - 1])[None]))[0]
    absorbed, _ = _serve_logits(model, ids, T, n)
    assert np.abs(plain[T - 1:] - absorbed).max() < 1e-4


def test_rotary_layout_matters(model, cfg, ids):
    """The control of the tolerance: the same weights read with each
    head's rotary dims as interleaved pairs instead of two halves."""
    want, _ = _ref(cfg, ids[:60], 0, 60)
    old = ref.rope

    def interleaved(x, cfg):
        d = x.shape[-1]
        perm = np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])
        return old(x[..., perm], cfg)[..., np.argsort(perm)]

    ref.rope = interleaved
    try:
        jax.clear_caches()
        other, _ = _ref(cfg, ids[:60], 0, 60)
    finally:
        ref.rope = old
        jax.clear_caches()
    assert np.abs(other - want).max() > 10 * TOL


# ---------------------------------------------------------------------------
# routing and the expert layer
# ---------------------------------------------------------------------------
def test_group_limited_routing_is_the_reference():
    r = np.random.default_rng(1)
    D, E = 24, 16
    z = jnp.asarray(r.standard_normal((200, D)), jnp.float32)
    w_r = jnp.asarray(r.standard_normal((D, E)), jnp.float32)
    knobs = {"n_group": 4, "topk_group": 2, "num_experts_per_tok": 3,
             "routed_scaling_factor": 16}
    want_e, want_g = ref.route(w_r, z, knobs, "f32")
    p, got_e = moe.group_limited_top_k(z @ w_r, 4, 2, 3)
    assert np.array_equal(np.asarray(got_e), np.asarray(want_e))
    np.testing.assert_allclose(np.asarray(p) * 16, np.asarray(want_g),
                               rtol=1e-5)
    # every choice lies in one of the token's two best groups
    groups = np.asarray(got_e) // 4
    assert all(len(set(g)) <= 2 for g in groups)


def _dense_experts(x, weight, expert, gu_w, down_w, first):
    """Every held expert applied to every token, weighted (0 where it was
    not chosen): what the grouped products have to equal."""
    y = jnp.zeros_like(x)
    for e in range(gu_w.shape[0]):
        g = jnp.sum(jnp.where(expert == first + e, weight, 0.0), -1)
        gu = x @ gu_w[e]
        F = gu.shape[-1] // 2
        y = y + g[:, None] * ((jax.nn.silu(gu[:, :F]) * gu[:, F:])
                              @ down_w[e])
    return y


@pytest.mark.parametrize("how", ["spread", "all_to_one", "none_held"])
def test_no_token_is_dropped(how):
    """There is no capacity: 96 tokens that all choose expert 5 first are
    all computed by it; a batch none of whose choices is held gives 0."""
    r = np.random.default_rng(2)
    N, D, F, E, first = 96, 16, 8, 4, 4          # experts 4-7 of 16 held
    x = jnp.asarray(r.standard_normal((N, D)), jnp.float32)
    gu_w = jnp.asarray(r.standard_normal((2, E, D, 2 * F)), jnp.float32)
    down_w = jnp.asarray(r.standard_normal((2, E, F, D)), jnp.float32)
    weight = jnp.asarray(r.uniform(0.1, 1.0, (N, 3)), jnp.float32)
    expert = np.stack([r.permutation(16)[:3] for _ in range(N)])
    if how == "all_to_one":
        expert[:, 1] = np.where(expert[:, 1] == 5, 0, expert[:, 1])
        expert[:, 2] = np.where(expert[:, 2] == 5, 1, expert[:, 2])
        expert[:, 0] = 5
    elif how == "none_held":
        expert = expert % 4
    expert = jnp.asarray(expert, jnp.int32)
    live = jnp.asarray(r.uniform(size=N) < 0.8)
    for layer in (0, 1):
        y, count = moe.held_expert_ffn(x, weight, expert, gu_w, down_w,
                                       first, layer, live)
        want = _dense_experts(x, weight, expert, gu_w[layer],
                              down_w[layer], first)
        want = jnp.where(live[:, None], want, 0.0)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   atol=1e-4)
        held = (np.asarray(expert)[np.asarray(live)][..., None]
                == first + np.arange(E)).sum((0, 1))
        assert np.array_equal(np.asarray(count), held)
    if how == "all_to_one":
        assert int(count[1]) == int(live.sum())
    if how == "none_held":
        assert float(jnp.abs(y).max()) == 0.0 and int(count.sum()) == 0


def test_the_shares_add_up():
    """Four chips holding experts 0-3, 4-7, 8-11, 12-15 of 16: the routed
    parts they compute, summed, with the shared experts counted once,
    equal the uncut reference layer's FFN (``model-configs``, section
    4)."""
    r = np.random.default_rng(3)
    z = jnp.asarray(r.standard_normal((80, 64)), jnp.float32)
    whole = _cfg(held=(0, 16))
    p = dweights.layer(whole, SEED, 2, "float32")
    h0 = jnp.zeros_like(z)
    shared, expert, gate = ref._shared_jit(p, h0, z, ref._sizes(whole),
                                           "f32")
    want = shared + 0.0          # the expert step donates what it adds to
    for e in range(16):
        want = ref._expert_jit(dweights.expert(whole, SEED, 2, e, "float32"),
                               want, z, expert, gate, jnp.int32(e), "f32")
    total, counted = shared, 0
    for first in (0, 4, 8, 12):
        m = _model(_cfg(held=(first, 4)))
        f, count = m._expert_ffn(m.decode_state(), 1, z,
                                 jnp.ones(80, bool))
        total = total + (f - shared)
        counted += int(count.sum())
    assert np.abs(np.asarray(total - want)).max() < TOL
    assert counted == 80 * 3      # every (token, choice) pair, once
    # the control: three of the four shares are not the layer
    assert np.abs(np.asarray(total - (f - shared) - want)).max() > 10 * TOL


# ---------------------------------------------------------------------------
# the decode kernel against its twin
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("max_blocks", [5, 40])
def test_mla_decode_attn_walk_is_the_twin(interpret_mode, max_blocks):
    """More blocks than one chunk holds (40 > 32), rows that end inside a
    block, at a block's end, at position 0, and an idle row on the trash
    block."""
    r = np.random.default_rng(4)
    B, H, R, row, bs, L = 5, 4, 128, 256, 16, 2
    n_blocks = B * max_blocks + 1
    pool = jnp.asarray(r.standard_normal((L, n_blocks, bs, row)),
                       jnp.float32)
    q = jnp.asarray(r.standard_normal((B, H, row)), jnp.float32) * 0.2
    bt = jnp.asarray(1 + r.permutation(n_blocks - 1)[:B * max_blocks]
                     .reshape(B, max_blocks), jnp.int32)
    pos = jnp.asarray([max_blocks * bs - 1, 37, 15, 0, 0], jnp.int32)
    bt = bt.at[4].set(0)
    for layer in (0, 1):
        got = mla.mla_decode_attn(q, pool, jnp.int32(layer), bt, pos, R)
        want = mla.mla_decode_attn_xla(q, pool, jnp.int32(layer), bt, pos, R)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)
    assert mla.pool_row(576) == 640 and mla.pool_row(40) == 128
    assert mla.kernel_mode(128, 640) == "pallas"
    pa._INTERPRET[0] = False
    assert mla.kernel_mode(128, 640) == "off"      # no TPU here


# ---------------------------------------------------------------------------
# chunked prefill, then decode, through the paged latent cache: on logits
# ---------------------------------------------------------------------------
def _serve_logits(model, ids, T, n, chunk=64, B=4, slot=2, bs=16,
                  kernel=None):
    """Logits at positions ``T - 1 .. T + n - 2``: ``ids[:T]`` prefilled in
    chunks, then ``ids[T:]`` decoded one by one in row ``slot`` of ``B``."""
    spec = model.cache_spec()
    max_blocks = -(-(T + n) // bs)
    pool = jnp.zeros((spec["kv_layers"], max_blocks + 1, bs,
                      mla.pool_row(spec["kv_row"])), jnp.float32)
    st = {name: jnp.zeros(shape, dt)
          for name, (shape, dt) in spec["step_state"].items()}
    w = model.decode_state()
    row = np.arange(1, max_blocks + 1, dtype=np.int32)
    prefill = jax.jit(lambda *a: model.prefill_paged(*a, kernel=kernel))
    decode = jax.jit(lambda *a: model.decode_paged(*a, kernel=kernel))
    for start in range(0, T, chunk):
        take = min(chunk, T - start)
        buf = np.zeros((1, bucket_length(take, 8, chunk)), np.int32)
        buf[0, :take] = ids[start:start + take]
        pool, _, st, logits = prefill(w, buf, np.int32(start),
                                      np.int32(take), row, pool, None, st,
                                      np.int32(slot))
    out = [np.asarray(logits)[0]]
    bt = np.zeros((B, max_blocks), np.int32)
    bt[slot] = row
    running = np.arange(B) == slot
    for j in range(n - 1):
        tok = np.zeros(B, np.int32)
        pos = np.zeros(B, np.int32)
        tok[slot], pos[slot] = ids[T + j], T + j
        logits, pool, _, st = decode(w, tok, pos, bt, pool, None, st,
                                     running)
        out.append(np.asarray(logits)[slot])
    return np.stack(out), jax.tree_util.tree_map(np.asarray, st)


def _held_histogram(cfg, chosen):
    """``[expert layers, held]``: how often each held expert was chosen."""
    _, first, held = dweights.share(cfg)
    return (chosen[..., None] == first + np.arange(held)).sum((1, 2))


@pytest.mark.parametrize("kernel", [None, "pallas"])
def test_chunked_prefill_then_decode_is_the_reference(model, cfg, ids,
                                                      kernel):
    T, n = 150, 10
    pa._INTERPRET[0] = kernel == "pallas"
    try:
        got, st = _serve_logits(model, ids, T, n, kernel=kernel)
    finally:
        pa._INTERPRET[0] = False
    want, chosen = _ref(cfg, ids[:T + n - 1], T - 1, n)
    assert np.abs(got - want).max() < TOL
    # the counts the programs carried: every token once, and each held
    # expert as often as the reference's routers chose it
    assert int(st["moe_tokens"]) == T + n - 1
    assert np.array_equal(st["moe_assignments"], _held_histogram(cfg, chosen))


# ---------------------------------------------------------------------------
# through LLMEngine
# ---------------------------------------------------------------------------
def _engine(model, **kw):
    args = dict(block_size=16, max_slots=2, max_seq_len=256, n_blocks=40,
                prefill_chunk=64)
    args.update(kw)
    return LLMEngine(model, **args)


def _drain(eng, limit=2000):
    for _ in range(limit):
        if not eng.has_work():
            return
        eng.step()
    raise AssertionError("engine did not converge")


def test_engine_serves_what_the_reference_puts_first(model, cfg):
    """Five requests over two slots: every slot is reused, prompts of
    several chunks prefill between other rows' decode launches, and idle
    rows sit on the trash block.  On logits: a served token's reference
    logit is the reference's best."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 512, n).astype(np.int32)
               for n in (150, 37, 200, 64, 129)]
    eng = _engine(model)
    assert type(eng) is LLMEngine
    st = eng.stats()
    assert st["prefix_cache"] is False and st["kv_kernel"] == "off"
    assert st["state_bytes"] == 0
    assert eng._pv is None and eng._pk.shape == (3, 40, 16, 128)
    # a row of 32 + 8 values is stored as one lane tile of 128, and counted
    assert st["kv_pool_bytes_per_chip"] == 3 * 40 * 16 * 128 * 4
    handles = [eng.add_request(p, max_new_tokens=8, seed=0) for p in prompts]
    eng.step()
    live = eng.stats()
    assert live["kv_live_bytes"] == live["blocks_live"] * 3 * 16 * 128 * 4
    _drain(eng)
    hist, tokens = 0, 0
    for p, h in zip(prompts, handles):
        assert h.finish_reason == "length"
        served = np.asarray(h.tokens, np.int32)
        rows, chosen = _ref(cfg, np.concatenate([p, served[:-1]]),
                            len(p) - 1, len(served))
        assert compare.token_gaps(rows, served).max() < TOL
        hist = hist + _held_histogram(cfg, chosen)
        tokens += len(p) + len(served) - 1
    before = counters.snapshot()
    load = model.moe_load(eng.step_state())
    assert load["tokens"] == tokens
    assert np.array_equal(load["per_expert"], hist)
    assert load["load_max_over_mean"] == hist.max() / hist.mean()
    # what the model had published before this engine's tokens
    seen = dict(model._moe_seen)
    moved = counters.delta(before)
    assert seen == {"tokens": tokens, "assignments": hist.sum()}
    assert 0 < moved["serving.moe.tokens"] <= tokens
    # nothing new: a second read publishes no more
    before = counters.snapshot()
    model.moe_load(eng.step_state())
    assert not counters.delta(before).get("serving.moe.tokens")


def test_engine_decodes_through_the_walk(model, cfg, ids, interpret_mode):
    eng = _engine(model)
    assert eng.stats()["kv_kernel"] == "pallas"
    h = eng.add_request(ids[:70], max_new_tokens=6, seed=0)
    _drain(eng)
    served = np.asarray(h.tokens, np.int32)
    rows, _ = _ref(cfg, np.concatenate([ids[:70], served[:-1]]), 69, 6)
    assert compare.token_gaps(rows, served).max() < TOL


def test_step_span_counts_the_latent_pool(model):
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
    assert counts and all(c["state_bytes"] == 0 for c in counts)
    assert max(c["kv_live_bytes"] for c in counts) == (
        3 * 3 * 16 * 128 * 4)            # 3 blocks of 40 + 2 positions


def test_prefix_cache_is_resolved_off_and_says_so(model):
    eng = _engine(model, prefix_cache=True)
    assert eng.prefix is None and eng.stats()["prefix_cache"] is False


def _gpt(vocab=512):
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    return GPTForCausalLM(GPTConfig(
        vocab_size=vocab, hidden_size=32, num_layers=1, num_heads=2,
        max_seq_len=256, use_flash_attention=False))


@pytest.mark.parametrize("how", [
    "kv_dtype", "host_kv_blocks", "adapter_slots", "mesh",
    "draft_model", "latent_draft", "export_request", "adopt_migration"])
def test_what_cannot_carry_a_latent_cache_is_refused(model, how):
    if how == "mesh":
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("mp",))
    build = {
        "kv_dtype": lambda: _engine(model, kv_dtype="int8"),
        "host_kv_blocks": lambda: _engine(model, host_kv_blocks=8),
        "adapter_slots": lambda: _engine(model, adapter_slots=2),
        "mesh": lambda: _engine(model, mesh=mesh),
        "draft_model": lambda: _engine(model, draft_model=_gpt()),
        "latent_draft": lambda: _engine(_gpt(), draft_model=model),
    }
    if how in build:
        with pytest.raises(LatentCacheUnsupported):
            build[how]()
        return
    eng = _engine(model)
    req = eng.add_request(np.arange(20, dtype=np.int32), max_new_tokens=4,
                          hold_after_prefill=True)
    for _ in range(50):
        if req.state == "held":
            break
        eng.step()
    with pytest.raises(LatentCacheUnsupported):
        if how == "export_request":
            eng.export_request(req)
        else:
            eng.adopt_migration({"block_size": 16, "kv_dtype": None}, eng)


def test_config_refuses_what_is_not_implemented(cfg):
    for key, value in (("topk_method", "greedy"), ("scoring_func", "sigmoid"),
                       ("norm_topk_prob", True), ("moe_layer_freq", 2),
                       ("num_key_value_heads", 2)):
        with pytest.raises(ValueError):
            ds.DeepseekV2Config.from_hf(dict(cfg, **{key: value}))
    with pytest.raises(ValueError):
        ds.DeepseekV2Config(n_routed_experts=16, n_group=4,
                            experts_held=(12, 8))


# ---------------------------------------------------------------------------
# the other families build the programs they built
# ---------------------------------------------------------------------------
def test_other_families_take_the_branches_they_took():
    from paddle_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                               OlmoHybridForCausalLM)
    gpt = LLMEngine(_gpt(), max_slots=2, max_seq_len=64)
    assert gpt._state_names == () and gpt.kv_row == 0
    assert gpt._prog_key("decode_paged") == "decode_paged"
    assert gpt._pk.ndim == 5 and gpt._pv.shape == gpt._pk.shape
    assert gpt.step_state() == {}
    hyb = LLMEngine(OlmoHybridForCausalLM(OlmoHybridConfig(
        vocab_size=64, hidden_size=64, intermediate_size=64, num_layers=4,
        num_heads=2, linear_num_heads=2, linear_key_head_dim=16,
        linear_value_head_dim=32)), max_slots=2, max_seq_len=64)
    assert hyb._state_names == ("gdn_conv", "gdn_state")
    assert hyb.step_state() == {}
    assert hyb.stats()["state_bytes"] > 0
    before = counters.snapshot()
    for eng in (gpt, hyb):
        eng.add_request(np.arange(9, dtype=np.int32), max_new_tokens=3)
        _drain(eng)
    assert not [k for k in counters.delta(before) if "moe" in k]


@pytest.mark.parametrize("C,K,q0,key0", [(64, 128, 128, 0), (64, 128, 128, 128),
                                         (8, 48, 0, 0), (512, 1024, 1024, 1024)])
def test_mla_prefill_fold_is_the_twin(interpret_mode, C, K, q0, key0):
    """A tile before the chunk (every key seen), the chunk's own tile
    (causal), a short bucket over a tile that no block size divides, and
    two query blocks over two key blocks (the first sees half)."""
    r = np.random.default_rng(6)
    H, d, dv = 3, 24, 16
    q = jnp.asarray(r.standard_normal((H, C, d)), jnp.float32) * 0.3
    k = jnp.asarray(r.standard_normal((H, K, d)), jnp.float32)
    v = jnp.asarray(r.standard_normal((H, K, dv)), jnp.float32)
    state = (jnp.asarray(r.standard_normal((H, C, 1)), jnp.float32),
             jnp.asarray(r.uniform(1, 2, (H, C, 1)), jnp.float32),
             jnp.asarray(r.standard_normal((H, C, dv)), jnp.float32))
    args = (q, k, v, jnp.int32(q0), jnp.int32(key0))
    got = mla.mla_prefill_fold(*args, state)
    want = mla.mla_prefill_fold_xla(*args, state)
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w_), atol=2e-5)
