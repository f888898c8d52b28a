"""Trinity (``afmoe``: window layers beside a full one, a window pool whose
blocks a row reuses as a ring, sigmoid routing with an expert bias) against
the benchmark's plain reference, at a small size on the CPU.

Tolerances.  Program and reference both compute in float32 here; they
differ in the ORDER of the sums (attention folds key tiles into an online
softmax, the experts run as grouped products over sorted rows).  On logits
of magnitude 1 that reads 1e-5 at most, so ``TOL = 1e-3`` leaves a hundred
times of room, and the window control (the window layers attending the whole
prefix) reads over 1e-2 past the window."""

import functools
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trinity_weights as tw                      # noqa: E402
from benchmark.reference import trinity as ref                   # noqa: E402
from paddle_tpu.kernels import moe                               # noqa: E402
from paddle_tpu.kernels import paged_attention as pa             # noqa: E402
from paddle_tpu.kernels import window_attention as wa            # noqa: E402
from paddle_tpu.models import trinity                            # noqa: E402
from paddle_tpu.profiler import counters                         # noqa: E402
from paddle_tpu.serving import LLMEngine, WindowCacheUnsupported  # noqa: E402

TOL = 1e-3
SEED = 11
W = 16


def _cfg(held=(0, 16), **over):
    """Tiny widths, the published pattern cut as the cell cuts it: one
    dense window layer, then one period (window, full, window, window);
    ``held`` is the share of the 16 routed experts."""
    cfg = {"vocab_size": 512, "hidden_size": 64, "intermediate_size": 96,
           "moe_intermediate_size": 32, "num_hidden_layers": 5,
           "num_dense_layers": 1, "num_attention_heads": 12,
           "num_key_value_heads": 2, "head_dim": 16,
           "layer_types": ["sliding_attention", "sliding_attention",
                           "full_attention", "sliding_attention",
                           "sliding_attention"],
           "global_attn_every_n_layers": 4, "sliding_window": W,
           "num_experts": held[1], "experts_held_first": held[0],
           "published": {"num_experts": 16}, "num_experts_per_tok": 4,
           "num_shared_experts": 1, "route_scale": 2.448, "route_norm": True,
           "score_func": "sigmoid", "n_group": 1, "topk_group": 1,
           "rms_norm_eps": 1e-5, "rope_theta": 10000, "rope_scaling": None,
           "mup_enabled": True, "max_position_embeddings": 512,
           "tie_word_embeddings": False, "hidden_act": "silu",
           "initializer_range": 0.1, "expert_bias_std": 0.1}
    cfg.update(over)
    return cfg


def _config(cfg):
    width, first, held = tw.share(cfg)
    return trinity.TrinityConfig.from_hf(
        cfg, experts_held=(first, held), num_experts=width,
        initializer_range=cfg["initializer_range"], dtype="float32")


def _model(cfg):
    config = _config(cfg)
    assert set(trinity.param_shapes(config)) == set(tw.PROGRAM_TENSORS)
    model = trinity.TrinityForCausalLM(config, tensors=lambda name: (
        tw.program_tensor(cfg, SEED, name, "float32")))
    model.eval()
    return model


def _reference(cfg, ids, window=True):
    """The reference's logits at every position of ``ids``."""
    _, first, held = tw.share(cfg)
    top = tw.top(cfg, SEED, "float32")
    out, _ = ref.logits_rows(
        top, lambda l: tw.layer(cfg, SEED, l, "float32"),
        lambda l, e: tw.expert(cfg, SEED, l, e, "float32"), cfg,
        (first, held), jnp.asarray(ids), 0, len(ids), "f32",
        window=window)
    return np.asarray(out)


@pytest.fixture(scope="module")
def cfg():
    return _cfg()


@pytest.fixture(scope="module")
def model(cfg):
    return _model(cfg)


@pytest.fixture()
def interpret_mode():
    pa._INTERPRET[0] = True
    yield
    pa._INTERPRET[0] = False


def _engine(model, **kw):
    args = dict(block_size=4, max_slots=3, max_seq_len=128, n_blocks=97,
                prefill_chunk=8)
    args.update(kw)
    return LLMEngine(model, **args)


def _drain(eng, limit=3000):
    events = []
    for _ in range(limit):
        if not eng.has_work():
            return events
        events += eng.step()
    raise AssertionError("engine did not converge")


# ---------------------------------------------------------------------------
# the plain forward pass, the window, the routing
# ---------------------------------------------------------------------------
def test_forward_is_the_reference_and_the_window_matters(cfg, model):
    ids = np.random.default_rng(0).integers(0, 512, 48).astype(np.int32)
    got = np.asarray(model.forward_logits(model.decode_state(), ids[None]))
    want = _reference(cfg, ids)
    np.testing.assert_allclose(got[0], want, atol=TOL, rtol=TOL)
    # the window control: the same up to the window, apart past it
    whole = _reference(cfg, ids, window=False)
    np.testing.assert_allclose(whole[:W], want[:W], atol=TOL, rtol=TOL)
    assert np.abs(whole[W + 4:] - want[W + 4:]).max() > 10 * TOL


@pytest.mark.parametrize("window", [W, None])
def test_the_references_blocks_and_groups_are_one_masked_softmax(
        cfg, monkeypatch, window):
    """Blocks of 4 query rows, a full layer's in groups of 2 blocks, give
    what one block over the whole sequence gives."""
    p = tw.layer(cfg, SEED, 2, "float32")
    u = jax.random.normal(jax.random.key(1), (42, cfg["hidden_size"]))
    args = (p, u, window is not None, window, ref._sizes(cfg), "f32")
    monkeypatch.setattr(ref, "_Q_BLOCK", 64)
    whole = np.asarray(ref.attention(*args))
    monkeypatch.setattr(ref, "_Q_BLOCK", 4)
    monkeypatch.setattr(ref, "_GROUP", 2)
    np.testing.assert_allclose(np.asarray(ref.attention(*args)), whole,
                               atol=1e-5, rtol=1e-5)


def test_the_reference_applies_an_expert_to_its_rows_in_pieces(
        cfg, monkeypatch):
    """Rows of an expert taken 3 at a time give what all at once give."""
    ids = np.random.default_rng(2).integers(0, 512, 40).astype(np.int32)
    whole = _reference(cfg, ids)
    monkeypatch.setattr(ref, "_ROWS", 3)
    ref._expert_jit.clear_cache()
    try:
        np.testing.assert_allclose(_reference(cfg, ids), whole, atol=1e-5,
                                   rtol=1e-5)
    finally:
        monkeypatch.undo()
        ref._expert_jit.clear_cache()


def test_biased_top_k_chooses_by_score_and_bias_and_weighs_by_score():
    logits = jnp.asarray([[0.0, 1.0, 2.0, 3.0]])
    bias = jnp.asarray([5.0, 0.0, 0.0, -5.0])
    weight, expert = moe.biased_sigmoid_top_k(logits, bias, 2, 2.0)
    assert expert.tolist() == [[0, 2]]       # 3 scores best, the bias drops it
    sig = jax.nn.sigmoid(jnp.asarray([0.0, 2.0]))
    np.testing.assert_allclose(np.asarray(weight[0]),
                               2.0 * np.asarray(sig / sig.sum()), rtol=1e-6)
    # ties go to the lower index
    _, tied = moe.biased_sigmoid_top_k(jnp.zeros((1, 4)), jnp.zeros(4), 2, 1.0)
    assert tied.tolist() == [[0, 1]]


def test_the_bias_parts_choice_from_weight_on_a_share_of_tokens(cfg, model):
    """With the drawn bias the experts chosen by score plus bias are not
    those the score alone would choose, for a share of the tokens."""
    z = jax.random.normal(jax.random.key(0), (256, cfg["hidden_size"]))
    logits = z @ model.router_w._data[0]
    _, by_bias = moe.biased_sigmoid_top_k(logits, model.expert_bias._data[0],
                                          4, 1.0)
    _, by_score = moe.biased_sigmoid_top_k(logits, jnp.zeros(16), 4, 1.0)
    differ = np.mean(np.any(np.sort(by_bias, -1) != np.sort(by_score, -1),
                            -1))
    assert 0.05 < differ < 1.0


def test_the_held_shares_of_eight_chips_add_up_to_the_layer(cfg):
    """Each chip holds 2 of the 16 experts; its layer is the shared expert
    plus its share of the routed sum.  The eight outputs, with the shared
    expert counted once, are the uncut layer's."""
    z = jax.random.normal(jax.random.key(1), (24, cfg["hidden_size"])) * 0.5
    live = jnp.ones(24, bool)

    def layer(c):
        # expert layer 1 of a model holding the share of ``c``: its
        # router, shared expert and held experts, nothing else drawn
        w = {n: tw.program_tensor(c, SEED, n, "float32") for n in (
            "router_w", "expert_bias", "shared_gu_w", "shared_down_w",
            "expert_gu_w", "expert_down_w")}
        holder = types.SimpleNamespace(config=_config(c))
        f, _ = trinity.TrinityForCausalLM._expert_ffn(holder, w, 1, z, live)
        return f, w

    want, w = layer(cfg)
    shared = trinity._swiglu(z, w["shared_gu_w"][1], w["shared_down_w"][1])
    got = sum(layer(_cfg(held=(2 * chip, 2)))[0]
              for chip in range(8)) - 7 * shared
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# the walk
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,window", [(7, 16), (12, None)])
def test_the_banded_walk_is_the_twin(interpret_mode, n, window):
    """A ring of 7 entries past its end (rows at positions that wrap it
    several times, one inside its first block) and a full table from 0."""
    r = np.random.default_rng(3)
    bs, N, G, d = 4, 2, 6, 16
    pool = jnp.asarray(r.standard_normal((2, 40, bs, 2 * N * d)),
                       jnp.float32)
    table = jnp.asarray(r.permutation(np.arange(1, 40))[:3 * n].reshape(
        3, n), jnp.int32)
    pos = jnp.asarray([2, 9, 45] if window else [2, 17, 47], jnp.int32)
    lo = wa.band(pos, window)
    q = jnp.asarray(r.standard_normal((3, N, G, d)), jnp.float32) * 0.3
    got = wa.window_decode_attn(q, pool, 1, table, pos, lo, N)
    want = wa.window_decode_attn_xla(q, pool, 1, table, pos, lo, N)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------------------
# the engine against the reference
# ---------------------------------------------------------------------------
def _served_logits(eng, prompt, n_new):
    """Drive the engine's own programs' model calls for one request:
    admission through the engine, then each prefill chunk's logits and
    ``n_new`` decode launches fed the reference's greedy tokens, every
    launch's logits at the row's position."""
    m = eng.model
    req = eng.add_request(prompt, max_new_tokens=n_new + 1)
    eng._admit([])
    slot = req.slot
    bt = jnp.asarray(eng._bt[slot])
    kw = {"kernel": eng.kv_kernel, "window_entries": eng.window_entries}
    prefill = jax.jit(functools.partial(m.prefill_paged, **kw),
                      static_argnums=8)
    decode = jax.jit(functools.partial(m.decode_paged, **kw))
    pk, pv, st = eng._pk, eng._pv, eng._st
    out, T, C = [], len(prompt), eng.prefill_chunk
    for start in range(0, T, C):
        take = min(C, T - start)
        ids = np.zeros((1, C), np.int32)
        ids[0, :take] = prompt[start:start + take]
        pk, pv, st, logits = prefill(
            eng._w, jnp.asarray(ids), jnp.int32(start), jnp.int32(take), bt,
            pk, pv, st, slot)
        out.append((start + take - 1, np.asarray(logits[0])))
    seq = list(prompt)
    B = eng.max_slots
    bts = jnp.zeros((B, bt.shape[0]), jnp.int32).at[slot].set(bt)
    running = jnp.zeros(B, bool).at[slot].set(True)
    for t in range(n_new):
        seq.append(int(np.argmax(out[-1][1])))
        pos = jnp.zeros(B, jnp.int32).at[slot].set(len(seq) - 1)
        tok = jnp.zeros(B, jnp.int32).at[slot].set(seq[-1])
        logits, pk, pv, st = decode(
            eng._w, tok, pos, jnp.where(running[:, None], bts, 0), pk, pv,
            st, running)
        out.append((len(seq) - 1, np.asarray(logits[slot])))
    eng._pk, eng._pv, eng._st = pk, pv, st
    return np.asarray(seq), out


@pytest.mark.parametrize("walk", ["twin", "pallas"])
def test_prefill_then_decode_is_the_reference_across_the_window(
        cfg, model, walk, request):
    """A 42-token prompt (five chunks, past the window of 16 and past the
    ring of 7 blocks), then 50 decode steps: three windows past it, the
    ring wrapping again and again."""
    if walk == "pallas":
        request.getfixturevalue("interpret_mode")
    eng = _engine(model)
    assert eng.kv_kernel == ("pallas" if walk == "pallas" else "off")
    assert eng.window_entries == 7              # ceil((16 + 8) / 4) + 1
    prompt = np.random.default_rng(4).integers(0, 512, 42).astype(np.int32)
    seq, out = _served_logits(eng, prompt, 50)
    want = _reference(cfg, seq)
    for p, logits in out:
        np.testing.assert_allclose(logits, want[p], atol=TOL, rtol=TOL)


def test_the_prefill_kernel_serves_the_twins_tokens(cfg):
    """An engine whose chunks attend through the Pallas kernel (interpret
    mode) serves the greedy tokens an engine through the XLA twin serves:
    chunks of two bucket widths (16 and a short last one of 8), rings
    wrapped.  A fresh model, so that both engines trace their programs and
    count the form they took."""
    m = _model(cfg)
    r = np.random.default_rng(6)
    prompts = [r.integers(0, 512, n).astype(np.int32) for n in (37, 12, 26)]

    def serve(interpret):
        pa._INTERPRET[0] = interpret
        try:
            before = counters.snapshot()
            eng = _engine(m, prefill_chunk=16)
            reqs = [eng.add_request(p, max_new_tokens=12) for p in prompts]
            _drain(eng)
            return [list(q.tokens) for q in reqs], counters.delta(before)
        finally:
            pa._INTERPRET[0] = False

    twin, by_twin = serve(False)
    kernel, by_kernel = serve(True)
    assert kernel == twin
    assert by_twin.get("kernels.window_attention.prefill.xla", 0) > 0
    assert not by_twin.get("kernels.window_attention.prefill.pallas")
    assert by_kernel.get("kernels.window_attention.prefill.pallas", 0) > 0
    assert not by_kernel.get("kernels.window_attention.prefill.xla")


def test_served_tokens_are_the_references_first_choices(cfg, model,
                                                        interpret_mode):
    eng = _engine(model)
    r = np.random.default_rng(5)
    prompts = [r.integers(0, 512, n).astype(np.int32) for n in (30, 9, 21)]
    reqs = [eng.add_request(p, max_new_tokens=40) for p in prompts]
    _drain(eng)
    for p, req in zip(prompts, reqs):
        served = np.asarray(req.tokens, np.int32)
        logits = _reference(cfg, np.concatenate([p, served[:-1]]))
        rows = logits[len(p) - 1:]
        gap = rows.max(-1) - rows[np.arange(len(served)), served]
        assert gap.max() < TOL


# ---------------------------------------------------------------------------
# the window pool
# ---------------------------------------------------------------------------
def test_window_blocks_are_bounded_recycled_and_never_leak(model):
    eng = _engine(model)
    bound = eng.window_entries
    before = counters.snapshot()
    r = np.random.default_rng(6)
    reqs = [eng.add_request(r.integers(0, 512, n).astype(np.int32),
                            max_new_tokens=m)
            for n, m in ((50, 30), (6, 3), (20, 40))]
    peak = 0
    while eng.has_work():
        eng.step()
        for s in range(eng.max_slots):
            ring = eng._bt[s, eng.max_blocks:]
            assert np.count_nonzero(ring) <= bound
        peak = max(peak, eng.stats()["window_blocks_live"])
    assert all(q.is_finished for q in reqs)
    # the short request holds 2 blocks (6 + 3 - 1 positions), the others
    # the whole ring
    assert peak == 2 * bound + 2
    recycled = counters.delta(before).get(
        "serving.kv.window_blocks_recycled", 0)
    # blocks 7.. of rows reaching positions 78 and 58: 13 + 8 entries
    assert recycled == (78 // 4 - 7 + 1) + (58 // 4 - 7 + 1)
    st = eng.stats()
    assert st["blocks_free"] == st["blocks_total"]
    assert st["blocks_live"] == 0 and st["window_blocks_live"] == 0
    # a finished row's table, ring included, points at the trash block
    assert not eng._bt.any()


def test_each_slot_owns_its_ring_and_only_the_full_pool_refuses(model):
    # the rings are disjoint, fixed, and fill the window pool beside the
    # trash block: no request waits for it
    eng = _engine(model)
    n = eng.window_entries
    assert sorted(eng._ring.ravel()) == list(range(1, eng.max_slots * n + 1))
    assert eng._pv.shape[1] == eng.max_slots * n + 1
    r = np.random.default_rng(7)
    reqs = [eng.add_request(r.integers(0, 512, 30).astype(np.int32),
                            max_new_tokens=5) for _ in range(eng.max_slots)]
    eng.step()
    for q in reqs:
        assert q.slot is not None
        assert list(eng._bt[q.slot, eng.max_blocks:]) == list(
            eng._ring[q.slot])
    _drain(eng)
    # a full pool of 10 blocks: the second long request waits for it
    eng = _engine(model, n_blocks=11)
    a = eng.add_request(r.integers(0, 512, 30).astype(np.int32),
                        max_new_tokens=5)
    b = eng.add_request(r.integers(0, 512, 30).astype(np.int32),
                        max_new_tokens=5)
    eng.step()
    assert a.slot is not None and b.slot is None
    assert eng.stats()["pool_exhausted"] >= 1
    _drain(eng)
    assert a.is_finished and b.is_finished


def test_the_step_span_counts_both_pools(model):
    from paddle_tpu.profiler import host_tracer
    eng = _engine(model)
    eng.add_request(np.arange(60, dtype=np.int32) % 512, max_new_tokens=4)
    host_tracer.start()
    try:
        _drain(eng)
    finally:
        host_tracer.stop()
    counts = [ev[5] for ev in host_tracer.events()
              if ev[0] == "serving.step" and ev[5]]
    assert counts
    live = max(counts, key=lambda c: c["window_kv_live_bytes"])
    # a block: 4 positions of [k ; v] (2 x 16 each, as one 128-lane
    # tile) in float32
    block = 4 * 128 * 4
    assert live["window_blocks_total"] == eng.max_slots * eng.window_entries
    assert live["window_kv_live_bytes"] == 7 * 4 * block   # 4 window layers
    # the whole sequence of 63 positions, 16 blocks, in 4 window layers
    assert live["window_kv_unbounded_bytes"] == 16 * 4 * block
    assert live["kv_live_bytes"] == (16 * 1 * block
                                     + live["window_kv_live_bytes"])


def test_prefix_cache_is_resolved_off_and_says_so(model):
    eng = _engine(model, prefix_cache=True)
    assert eng.prefix is None and eng.stats()["prefix_cache"] is False


def _gpt(vocab=512):
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    return GPTForCausalLM(GPTConfig(
        vocab_size=vocab, hidden_size=32, num_layers=1, num_heads=2,
        max_seq_len=256, use_flash_attention=False))


@pytest.mark.parametrize("how", [
    "kv_dtype", "host_kv_blocks", "adapter_slots", "mesh", "draft_model",
    "window_draft", "export_request", "adopt_migration"])
def test_what_cannot_carry_a_window_pool_is_refused(model, how):
    if how == "mesh":
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("mp",))
    build = {
        "kv_dtype": lambda: _engine(model, kv_dtype="int8"),
        "host_kv_blocks": lambda: _engine(model, host_kv_blocks=8),
        "adapter_slots": lambda: _engine(model, adapter_slots=2),
        "mesh": lambda: _engine(model, mesh=mesh),
        "draft_model": lambda: _engine(model, draft_model=_gpt()),
        "window_draft": lambda: _engine(_gpt(), draft_model=model),
    }
    if how in build:
        with pytest.raises(WindowCacheUnsupported):
            build[how]()
        return
    eng = _engine(model)
    req = eng.add_request(np.arange(20, dtype=np.int32), max_new_tokens=4,
                          hold_after_prefill=True)
    for _ in range(50):
        if req.state == "held":
            break
        eng.step()
    with pytest.raises(WindowCacheUnsupported):
        if how == "export_request":
            eng.export_request(req)
        else:
            eng.adopt_migration({"block_size": 4, "kv_dtype": None}, eng)


def test_config_refuses_what_is_not_implemented(cfg):
    for key, value in (("score_func", "softmax"), ("n_group", 2),
                       ("topk_group", 2), ("rope_scaling", {"type": "yarn"}),
                       ("route_norm", False), ("tie_word_embeddings", True)):
        with pytest.raises(ValueError):
            trinity.TrinityConfig.from_hf(dict(cfg, **{key: value}))


def test_other_families_take_the_branches_they_took():
    """A model without window layers keeps one table of its own width, no
    window pool, no window counts, and the programs it had."""
    from paddle_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                               OlmoHybridForCausalLM)
    gpt = LLMEngine(_gpt(), max_slots=2, max_seq_len=64)
    hyb = LLMEngine(OlmoHybridForCausalLM(OlmoHybridConfig(
        vocab_size=64, hidden_size=64, intermediate_size=64, num_layers=4,
        num_heads=2, linear_num_heads=2, linear_key_head_dim=16,
        linear_value_head_dim=32)), max_slots=2, max_seq_len=64)
    before = counters.snapshot()
    for eng in (gpt, hyb):
        assert eng.window_entries == 0 and not eng.window
        assert eng._bt.shape == (2, eng.max_blocks)
        assert eng._prog_key("decode_paged") == "decode_paged"
        eng.add_request(np.arange(9, dtype=np.int32), max_new_tokens=3)
        _drain(eng)
        assert "window_blocks_total" not in eng.stats()
    assert not [k for k in counters.delta(before) if "window" in k]
