"""SDAR-MoE (generation by diffusion over blocks, grouped-query attention,
128-way experts) against the benchmark's plain reference, at a small size
on the CPU.

Tolerances.  Program and reference both compute in float32 here; they
differ in the ORDER of the sums (attention folds key tiles and the block's
own lines one after the other, the experts run as grouped products over
sorted rows).  On logits of magnitude 1 that reads 1e-5 at most, so ``TOL =
1e-3`` leaves a hundred times of room, and the controls (the causal mask
in place of the block-causal one, an expert left out) read over 1e-2."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import sdar_weights as sweights                   # noqa: E402
from benchmark.reference import sdar as ref                      # noqa: E402
from paddle_tpu.kernels import block_attention as ba             # noqa: E402
from paddle_tpu.kernels import mla_attention as mla              # noqa: E402
from paddle_tpu.kernels import paged_attention as pa             # noqa: E402
from paddle_tpu.models import sdar                               # noqa: E402
from paddle_tpu.profiler import counters                         # noqa: E402
from paddle_tpu.serving import (BlockDecodeUnsupported,          # noqa: E402
                                LLMEngine)
from paddle_tpu.serving import block_decode as bd                # noqa: E402

TOL = 1e-3
SEED = 7
MASK = 500
WIDTH = 96          # the reference's passes are padded to one length


def _cfg(**over):
    cfg = {"vocab_size": 512, "hidden_size": 64, "moe_intermediate_size": 32,
           "num_hidden_layers": 2, "num_attention_heads": 8,
           "num_key_value_heads": 2, "head_dim": 16, "num_experts": 16,
           "num_experts_per_tok": 8, "norm_topk_prob": True,
           "rms_norm_eps": 1e-6, "rope_theta": 1000000,
           "max_position_embeddings": 512, "initializer_range": 0.1}
    cfg.update(over)
    return cfg


def _config(cfg, block_length=4, steps=4):
    return sdar.SdarConfig.from_hf(
        cfg, initializer_range=cfg["initializer_range"], dtype="float32",
        block_length=block_length, denoising_steps=steps,
        mask_token_id=MASK)


def _model(cfg, block_length=4, steps=4):
    config = _config(cfg, block_length, steps)
    assert set(sdar.param_shapes(config)) == set(sweights.PROGRAM_TENSORS)
    model = sdar.SdarMoeForCausalLM(config, tensors=lambda name: (
        sweights.program_tensor(cfg, SEED, name, "float32")))
    model.eval()
    return model


def _params(cfg):
    return {"config": cfg, "top": sweights.top(cfg, SEED, "float32"),
            "layer": lambda l: sweights.layer(cfg, SEED, l, "float32"),
            "expert": lambda l, e: sweights.expert(cfg, SEED, l, e,
                                                   "float32")}


def _generate(cfg, prompt, n, B=4, steps=4, **kw):
    return ref.generate(_params(cfg), prompt, n, B, steps, MASK,
                        width=WIDTH, **kw)


@pytest.fixture(scope="module")
def cfg():
    return _cfg()


@pytest.fixture(scope="module")
def model(cfg):
    return _model(cfg)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(0, 500, 96).astype(np.int32)


@pytest.fixture()
def interpret_mode():
    pa._INTERPRET[0] = True
    yield
    pa._INTERPRET[0] = False


def _engine(model, **kw):
    args = dict(block_size=16, max_slots=3, max_seq_len=128, n_blocks=25,
                prefill_chunk=32)
    args.update(kw)
    return LLMEngine(model, **args)


def _drain(eng, limit=2000):
    events = []
    for _ in range(limit):
        if not eng.has_work():
            return events
        events += eng.step()
    raise AssertionError("engine did not converge")


# ---------------------------------------------------------------------------
# the plain forward pass, the mask, the experts
# ---------------------------------------------------------------------------
def test_forward_is_the_reference_under_the_block_causal_mask(model, cfg,
                                                              ids):
    got = np.asarray(model.forward_logits(model.decode_state(),
                                          jnp.asarray(ids[None, :70])))[0]
    want, _ = ref.logits(_params(cfg), ids[:70], ref.block_causal(70, 4))
    assert np.abs(got - np.asarray(want)).max() < TOL
    # the control: under the causal mask the reference reads otherwise
    causal, _ = ref.logits(_params(cfg), ids[:70],
                           np.tril(np.ones((70, 70), bool)))
    assert np.abs(got - np.asarray(causal)).max() > 1e-2
    # a position sees its whole block: the last token of a block moves the
    # first one's logits, the first of the next block does not
    moved = ids[:70].copy()
    moved[7] = (moved[7] + 1) % 500
    moved[8] = (moved[8] + 1) % 500
    other = np.asarray(model.forward_logits(model.decode_state(),
                                            jnp.asarray(moved[None])))[0]
    assert np.abs(other[4] - got[4]).max() > 1e-4
    assert np.abs(other[:4] - got[:4]).max() == 0


def test_the_constructor_takes_a_loaders_tensors(model, cfg):
    """With ``tensors=`` the model holds what the loader gave, array for
    array, and a tensor of another shape or type is refused; without it
    the constructor draws every parameter of ``param_shapes`` itself."""
    table = sdar.param_shapes(model.config)
    for name, p in model.named_parameters():
        want = sweights.program_tensor(cfg, SEED, name, "float32")
        assert tuple(p.shape) == table[name][0]
        np.testing.assert_array_equal(np.asarray(p._data), np.asarray(want))
    with pytest.raises(ValueError, match="router_w"):
        sdar.SdarMoeForCausalLM(_config(cfg), tensors=lambda name: (
            sweights.program_tensor(cfg, SEED, name, "float32")[..., :-1]
            if name == "router_w"
            else sweights.program_tensor(cfg, SEED, name, "float32")))
    with pytest.raises(ValueError, match="float32"):
        sdar.SdarMoeForCausalLM(_config(cfg), tensors=lambda name: (
            sweights.program_tensor(cfg, SEED, name, "bfloat16")))
    drawn = dict(sdar.SdarMoeForCausalLM(_config(cfg)).named_parameters())
    assert set(drawn) == set(table)
    for name, (shape, how, _) in table.items():
        x = np.asarray(drawn[name]._data)
        assert x.shape == shape
        if how == "ones":
            assert (x == 1).all()
        else:
            assert 0.5 < x.std() / cfg["initializer_range"] < 1.5


def test_grouped_heads_read_their_own_kv_head():
    """4 K/V heads under 32 query heads, as published."""
    cfg = _cfg(num_attention_heads=32, num_key_value_heads=4,
               num_hidden_layers=1)
    m = _model(cfg)
    ids = np.random.default_rng(1).integers(0, 500, 24).astype(np.int32)
    got = np.asarray(m.forward_logits(m.decode_state(),
                                      jnp.asarray(ids[None])))[0]
    want, _ = ref.logits(_params(cfg), ids, ref.block_causal(24, 4))
    assert np.abs(got - np.asarray(want)).max() < TOL
    assert m.cache_spec()["kv_row"] == 2 * 4 * 16
    with pytest.raises(ValueError):
        sdar.SdarConfig(num_heads=32, num_kv_heads=5)


def test_renormalised_top_8_is_the_reference(model, cfg):
    r = np.random.default_rng(2)
    z = jnp.asarray(r.standard_normal((40, 64)), jnp.float32)
    w = model.decode_state()
    lw = {"router_w": w["router_w"][1]}
    f, count = model._expert_ffn(w, lw, jnp.int32(1), z, jnp.ones(40, bool))
    expert, gate = ref.route(w["router_w"][1], z, cfg, "f32")
    assert np.allclose(np.asarray(gate).sum(-1), 1.0, atol=1e-6)
    # every token goes to 8 experts, none is dropped
    assert int(count.sum()) == 40 * 8
    hist = (np.asarray(expert)[..., None] == np.arange(16)).sum((0, 1))
    assert np.array_equal(np.asarray(count), hist)
    want = 0
    for e in range(16):
        ex = sweights.expert(cfg, SEED, 1, e, "float32")
        g = jnp.where(expert == e, gate, 0.0).sum(-1, keepdims=True)
        want = want + g * ((jax.nn.silu(z @ ex["ex_gate"]) * (z @ ex["ex_up"]))
                           @ ex["ex_down"])
    assert np.abs(np.asarray(f) - np.asarray(want)).max() < 1e-5


def test_config_refuses_what_is_not_implemented(cfg):
    for key, value in (("decoder_sparse_step", 2), ("mlp_only_layers", [0]),
                       ("norm_topk_prob", False), ("attention_bias", True),
                       ("tie_word_embeddings", True),
                       ("use_sliding_window", True)):
        with pytest.raises(ValueError):
            sdar.SdarConfig.from_hf(dict(cfg, **{key: value}))
    with pytest.raises(ValueError):
        sdar.SdarConfig(block_length=4, denoising_steps=5)
    with pytest.raises(ValueError):
        sdar.SdarConfig(vocab_size=100, mask_token_id=100)


# ---------------------------------------------------------------------------
# the schedule
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,want", [(4, 4, [1, 1, 1, 1]), (4, 2, [2, 2]),
                                      (4, 3, [2, 1, 1]), (8, 3, [3, 3, 2])])
def test_static_schedule(B, S, want):
    assert ref.reveal_counts(B, S) == want
    # the program's tail reveals that many a pass, the most confident
    # first, ties to the lower index
    r = np.random.default_rng(B * 10 + S)
    V = 32
    tok = jnp.full((1, B), MASK % V, jnp.int32)
    rstep = jnp.full((1, B), bd.MASKED, jnp.int32)
    keys = jax.random.key_data(jax.random.key(0))[None]
    for step in range(S):
        logits = jnp.asarray(r.standard_normal((1, B, V)), jnp.float32)
        masked = np.asarray(rstep[0]) == bd.MASKED
        lg = np.asarray(logits[0], np.float64)
        conf = np.exp(lg.max(-1) - ref._logsumexp(lg))
        expect = ref.reveal(conf, masked, want[step])
        tok, rstep, _ = bd._reveal(
            logits, tok, rstep, jnp.full(1, step, jnp.int32),
            jnp.full(1, S, jnp.int32), jnp.full(1, jnp.inf), keys,
            jnp.zeros(1, bool), jnp.ones(1), jnp.zeros(1, jnp.int32),
            jnp.ones(1))
        now = (np.asarray(rstep[0]) == step)
        assert np.array_equal(now, expect) and now.sum() == want[step]
        assert np.array_equal(np.asarray(tok[0])[now], lg.argmax(-1)[now])
    assert not (np.asarray(rstep) == bd.MASKED).any()


def test_dynamic_reveal_by_threshold(model, cfg, ids):
    """``tau`` 0: every masked position is above it, so one pass reveals
    the whole block and the next commits; ``tau`` above 1: nothing is, so
    the rule falls back to the static one on every pass."""
    prompt = ids[:21]
    static = _drain_one(model, prompt, 11)
    eng = _engine(model)
    h = eng.add_request(prompt, max_new_tokens=11, seed=0,
                        reveal_threshold=0.0)
    events = _drain(eng)
    blocks = [ev for ev in events if ev["type"] == "block"]
    assert [b["passes"] for b in blocks] == [2] * len(blocks)
    assert all(r in (bd.GIVEN, 0) for b in blocks for r in b["reveal_steps"])
    want, steps = _generate(cfg, prompt, 11, tau=0.0)
    assert h.tokens == want and set(steps) == {0}
    eng = _engine(model)
    h = eng.add_request(prompt, max_new_tokens=11, seed=0,
                        reveal_threshold=1.5)
    _drain(eng)
    assert h.tokens == static.tokens


class _Served:
    """What ``_drain_one`` hands back (a ``Request`` has ``__slots__``)."""


def _drain_one(model, prompt, n, **kw):
    eng = _engine(model)
    h = eng.add_request(prompt, max_new_tokens=n, seed=0, **kw)
    out = _Served()
    out.events = _drain(eng)
    out.tokens, out.request, out.engine = list(h.tokens), h, eng
    return out


# ---------------------------------------------------------------------------
# the walk against its twin
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("max_blocks", [7, 40])
def test_block_decode_attn_walk_is_the_twin(interpret_mode, max_blocks):
    """The published tile: 8 query heads x 4 positions a K/V head, 4 K/V
    heads of 128 in rows of 1,024.  More blocks than one chunk holds (40 >
    32), prefixes that end inside a K/V block, at its end, an empty prefix,
    and an idle row on the trash block."""
    r = np.random.default_rng(4)
    S, N, G, Bl, hd, bs, L = 5, 4, 8, 4, 128, 16, 2
    row = 2 * N * hd
    n_blocks = S * max_blocks + 1
    pool = jnp.asarray(r.standard_normal((L, n_blocks, bs, row)),
                       jnp.float32)
    q = jnp.asarray(r.standard_normal((S, N, G * Bl, hd)), jnp.float32) * 0.1
    new = jnp.asarray(r.standard_normal((S, Bl, row)), jnp.float32)
    bt = jnp.asarray(1 + r.permutation(n_blocks - 1)[:S * max_blocks]
                     .reshape(S, max_blocks), jnp.int32)
    pos = jnp.asarray([max_blocks * bs - 4, 36, 16, 0, 0], jnp.int32)
    bt = bt.at[4].set(0)
    for layer in (0, 1):
        got = ba.block_decode_attn(q, new, pool, jnp.int32(layer), bt, pos, N)
        want = ba.block_decode_attn_xla(q, new, pool, jnp.int32(layer), bt,
                                        pos, N)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)
    assert ba.kernel_mode(32, 4, 128, 4) == "pallas"
    pa._INTERPRET[0] = False
    assert ba.kernel_mode(32, 4, 128, 4) == "off"      # no TPU here


# ---------------------------------------------------------------------------
# chunked prefill, then block decode, through LLMEngine: on logits
# ---------------------------------------------------------------------------
def _watched(cfg, seen):
    """A model of its own (the programs are cached by model) whose decode
    pass also hands its logits to ``seen``."""
    m = _model(cfg)
    inner = m.decode_paged

    def decode_paged(w, tok, pos, *a, **kw):
        logits, pool, st = inner(w, tok, pos, *a, **kw)
        jax.debug.callback(
            lambda t, p, lg: seen.append((np.asarray(t), np.asarray(p),
                                          np.asarray(lg))), tok, pos, logits)
        return logits, pool, st

    m.decode_paged = decode_paged
    return m


@pytest.mark.parametrize("kernel", ["off", "pallas"])
def test_every_denoising_pass_is_the_reference(cfg, ids, kernel):
    """A prompt of two chunks and a remainder of 3, 13 new tokens: at
    EVERY pass the logits of the block as the program held it are the
    reference's full forward pass over the committed tokens and that
    block; the tokens and the passes that revealed them are the
    reference's ``generate``."""
    seen = []
    pa._INTERPRET[0] = kernel == "pallas"
    try:
        m = _watched(cfg, seen)
        eng = _engine(m)
        assert eng.stats()["kv_kernel"] == kernel
        prompt = ids[:43]
        h = eng.add_request(prompt, max_new_tokens=13, seed=0)
        events = _drain(eng)
        jax.effects_barrier()
    finally:
        pa._INTERPRET[0] = False
    trace = []
    want, want_steps = _generate(cfg, prompt, 13, trace=trace)
    assert h.tokens == want and h.finish_reason == "length"
    got_steps = [ev["reveal_step"] for ev in events if ev["type"] == "token"]
    assert got_steps == want_steps
    slot = 0
    denoise = [(t[slot], int(p[slot]), lg[slot]) for t, p, lg in seen
               if (t[slot] == MASK).any()]
    assert len(denoise) == len(trace)
    for (tok, pos, lg), tr in zip(denoise, trace):
        assert pos == tr["start"] and tok.tolist() == tr["ids"]
        assert np.abs(lg - tr["logits"]).max() < TOL
    # the commit passes: the block's final tokens, under the same mask
    final = np.concatenate([prompt, np.asarray(
        [t for ev in events if ev["type"] == "block"
         for t in ev["tokens"]][3:], np.int32)])
    full, _ = ref.logits(_params(cfg), np.pad(final, (0, WIDTH - len(final)),
                                              constant_values=MASK),
                         ref.block_causal(WIDTH, 4))
    commits = [(int(p[slot]), lg[slot]) for t, p, lg in seen
               if not (t[slot] == MASK).any()]
    assert len(commits) == 4
    for pos, lg in commits:
        assert np.abs(lg - np.asarray(full)[pos:pos + 4]).max() < TOL


def test_only_commit_passes_write_the_pool(model, cfg, ids):
    """After a request the pool holds the reference's K/V of the final
    tokens; a denoising pass leaves the row's blocks untouched."""
    prompt = ids[:22]
    eng = _engine(model)
    h = eng.add_request(prompt, max_new_tokens=10, seed=0)
    table = None
    snaps = []
    for _ in range(200):
        if not eng.has_work():
            break
        if h.slot is not None and table is None:
            table = list(eng._slot_blocks[h.slot])
        before = np.asarray(eng._pk)
        events = eng.step()
        if table is not None:
            mine = np.asarray(eng._pk)[:, table]
            snaps.append((any(ev["type"] == "block" for ev in events),
                          np.array_equal(mine, before[:, table])))
    # a step that committed no block (and prefilled nothing: the prompt is
    # one chunk, run on the first step) changed nothing in the row's blocks
    assert all(same for committed, same in snaps[1:] if not committed)
    assert sum(committed for committed, _ in snaps) == 3
    assert all(not same for committed, same in snaps if committed)
    # what it holds: the reference's keys and values of the final tokens
    blocks = [ev for ev in _replay(model, prompt, 10) if ev["type"] == "block"]
    final = np.concatenate([prompt[:20]] + [np.asarray(b["tokens"], np.int32)
                                            for b in blocks])
    assert len(final) == 32
    p = _params(cfg)
    layer0 = p["layer"](0)
    x = ref.rms_norm(p["top"]["wte"][final], layer0["attn_g"], 1e-6)
    k = ref.rope(ref.rms_norm((x @ layer0["w_k"]).reshape(32, 2, 16),
                              layer0["k_g"], 1e-6), jnp.arange(32), 1e6)
    v = (x @ layer0["w_v"]).reshape(32, 2, 16)
    rows = np.asarray(eng._pk)[0, table[:2]].reshape(32, -1)
    assert rows.shape[1] == mla.pool_row(64) == 128
    assert np.abs(rows[:, :32] - np.asarray(k).reshape(32, 32)).max() < 1e-5
    assert np.abs(rows[:, 32:64] - np.asarray(v).reshape(32, 32)).max() < 1e-5
    assert not rows[:, 64:].any()


def _replay(model, prompt, n):
    return _drain_one(model, prompt, n).events


@pytest.mark.parametrize("T", [20, 21, 22, 23, 3])
@pytest.mark.parametrize("n", [8, 9, 10, 11])
def test_every_remainder_of_prompt_and_output(model, cfg, ids, T, n):
    """Prompts and outputs of every length mod 4 (a prompt shorter than a
    block is never prefilled): the reference's tokens and reveal passes."""
    got = _drain_one(model, ids[:T], n)
    want, steps = _generate(cfg, ids[:T], n)
    assert got.tokens == want
    assert [ev["reveal_step"] for ev in got.events
            if ev["type"] == "token"] == steps
    assert [ev["index"] for ev in got.events
            if ev["type"] == "token"] == list(range(n))
    # the blocks reserved: whole blocks of 4, the last one too
    assert got.engine._blocks_needed(T, n) == -(-(-(-(T + n) // 4) * 4) // 16)
    st = got.engine.stats()
    assert st["blocks_live"] == 0 and st["blocks_free"] == st["blocks_total"]


def test_an_eos_inside_a_block_ends_the_request(model, cfg, ids):
    free = _drain_one(model, ids[:21], 12)
    eos = free.tokens[5]
    first = free.tokens.index(eos)
    got = _drain_one(model, ids[:21], 12, eos_token_id=eos)
    assert got.tokens == free.tokens[:first + 1]
    assert got.request.finish_reason == "eos"
    want, _ = _generate(cfg, ids[:21], 12, eos_token_id=eos)
    assert got.tokens == want


def test_rows_out_of_phase_serve_what_each_serves_alone(model, ids):
    """Two requests admitted a step apart (their blocks commit on
    different launches), a third that reuses a slot."""
    prompts = [ids[:26], ids[30:53], ids[60:69]]
    alone = [_drain_one(model, p, 9).tokens for p in prompts]
    eng = _engine(model, max_slots=2)
    handles = [eng.add_request(prompts[0], max_new_tokens=9, seed=0)]
    eng.step()
    handles.append(eng.add_request(prompts[1], max_new_tokens=9, seed=0))
    handles.append(eng.add_request(prompts[2], max_new_tokens=9, seed=0))
    before = counters.snapshot()
    events = _drain(eng)
    moved = counters.delta(before)
    assert [h.tokens for h in handles] == alone
    commits = {}
    for i, ev in enumerate(events):
        if ev["type"] == "block":
            commits.setdefault(id(ev["request"]), []).append(ev["passes"])
    # first blocks of 2, 1 and 3 masked positions (26, 23 and 9 tokens)
    assert sorted(commits.values()) == [[2, 5, 5], [3, 5, 5], [4, 5, 5]]
    # the records: tokens emitted, not launches; a launch counts its rows
    assert moved["serving.decode_tokens"] == 27
    assert moved["serving.diffusion.commits"] == 9
    assert moved["serving.diffusion.row_passes"] == sum(
        sum(p) for p in commits.values()) - 1     # one pass before the read
    hist = eng.hists["serving.diffusion.passes_per_block"]
    assert hist.count == 9


def test_one_read_back_and_no_retrace(model, ids):
    eng = _engine(model)
    h = eng.add_request(ids[:24], max_new_tokens=8, seed=0)
    _drain(eng)                                   # the programs exist now
    before = counters.snapshot()
    h = eng.add_request(ids[24:48], max_new_tokens=8, seed=0)
    _drain(eng)
    moved = counters.delta(before)
    assert not moved.get("serving.retraces")
    assert moved["serving.decode_steps"] == 10    # two blocks of 5 passes
    assert moved["serving.decode_tokens"] == 8
    assert moved["serving.diffusion.row_passes"] == 10
    assert moved["serving.diffusion.revealed"] == 8
    # the first launch uploads what admission wrote, no other does
    assert moved["serving.decode.upload_steps"] == 1
    assert len(h.tokens) == 8


def test_sampling_rows_draw_from_their_own_keys(model, ids):
    a = _drain_one(model, ids[:20], 8, do_sample=True, temperature=1.0)
    b = _drain_one(model, ids[:20], 8, do_sample=True, temperature=1.0)
    greedy = _drain_one(model, ids[:20], 8)
    assert a.tokens == b.tokens and a.tokens != greedy.tokens


def test_moe_counts_and_step_span(model, cfg, ids):
    from paddle_tpu.profiler import host_tracer
    eng = _engine(model)
    eng.add_request(ids[:40], max_new_tokens=4, seed=0)
    host_tracer.start()
    try:
        _drain(eng)
    finally:
        host_tracer.stop()
    load = model.moe_load(eng.step_state())
    # the prompt's 40 tokens once, then 5 passes of a block of 4
    assert load["tokens"] == 40 + 5 * 4
    assert load["assignments"] == load["tokens"] * 8 * 2
    assert load["per_expert"].shape == (2, 16)
    counts = [ev[5] for ev in host_tracer.events()
              if ev[0] == "serving.step" and ev[5]]
    assert max(c["kv_live_bytes"] for c in counts) == 3 * 2 * 16 * 128 * 4
    st = eng.stats()
    assert st["prefix_cache"] is False and st["kv_kernel"] == "off"
    assert st["kv_pool_bytes_per_chip"] == 2 * 25 * 16 * 128 * 4


# ---------------------------------------------------------------------------
# refusals
# ---------------------------------------------------------------------------
def _gpt(vocab=512):
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    return GPTForCausalLM(GPTConfig(
        vocab_size=vocab, hidden_size=32, num_layers=1, num_heads=2,
        max_seq_len=256, use_flash_attention=False))


@pytest.mark.parametrize("how", [
    "draft_model", "block_draft", "kv_dtype", "host_kv_blocks",
    "adapter_slots", "mesh", "hold", "export_request", "adopt_migration"])
def test_what_cannot_carry_a_block_decoding_model_is_refused(model, how):
    if how == "mesh":
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("mp",))
    build = {
        "draft_model": lambda: _engine(model, draft_model=_gpt()),
        "block_draft": lambda: LLMEngine(_gpt(), max_slots=2,
                                         max_seq_len=64, draft_model=model),
        "kv_dtype": lambda: _engine(model, kv_dtype="int8"),
        "host_kv_blocks": lambda: _engine(model, host_kv_blocks=8),
        "adapter_slots": lambda: _engine(model, adapter_slots=2),
        "mesh": lambda: _engine(model, mesh=mesh),
    }
    if how in build:
        with pytest.raises(BlockDecodeUnsupported):
            build[how]()
        return
    eng = _engine(model)
    assert type(eng) is bd.BlockDecodeLLMEngine
    with pytest.raises(BlockDecodeUnsupported):
        if how == "hold":
            eng.add_request(np.arange(20, dtype=np.int32), max_new_tokens=4,
                            hold_after_prefill=True)
        elif how == "export_request":
            eng.export_request(eng.add_request(np.arange(20, dtype=np.int32)))
        else:
            eng.adopt_migration({"block_size": 16, "kv_dtype": None}, eng)


def test_sizes_that_split_a_block_are_refused(model):
    for kw in ({"prefill_chunk": 30}, {"block_size": 6},
               {"max_seq_len": 126}, {"min_bucket": 2}):
        with pytest.raises(ValueError):
            _engine(model, **kw)
    eng = _engine(model, prefix_cache=True)
    assert eng.prefix is None and eng.stats()["prefix_cache"] is False
    with pytest.raises(ValueError):
        eng.add_request(np.arange(8, dtype=np.int32), denoise_steps=5)


# ---------------------------------------------------------------------------
# the other families build the programs they built
# ---------------------------------------------------------------------------
def test_other_families_take_the_branches_they_took():
    from paddle_tpu.models import deepseek_v2 as ds
    from paddle_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                               OlmoHybridForCausalLM)
    gpt = LLMEngine(_gpt(), max_slots=2, max_seq_len=64)
    hyb = LLMEngine(OlmoHybridForCausalLM(OlmoHybridConfig(
        vocab_size=64, hidden_size=64, intermediate_size=64, num_layers=4,
        num_heads=2, linear_num_heads=2, linear_key_head_dim=16,
        linear_value_head_dim=32)), max_slots=2, max_seq_len=64)
    lat = LLMEngine(ds.DeepseekV2ForCausalLM(ds.DeepseekV2Config(
        vocab_size=64, hidden_size=32, intermediate_size=32,
        moe_intermediate_size=16, num_layers=2, num_heads=2, q_lora_rank=16,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, n_routed_experts=4, n_group=2, topk_group=1,
        num_experts_per_tok=2, max_seq_len=64)), max_slots=2, max_seq_len=64)
    before = counters.snapshot()
    for eng in (gpt, hyb, lat):
        assert type(eng) is LLMEngine
        assert eng._operand_names[:3] == ("bt", "tok", "pos")
        h = eng.add_request(np.arange(9, dtype=np.int32), max_new_tokens=3)
        events = _drain(eng)
        assert len(h.tokens) == 3
        toks = [ev for ev in events if ev["type"] == "token"]
        assert all("reveal_step" not in ev for ev in toks)
        assert not [ev for ev in events if ev["type"] == "block"]
    assert gpt._prog_key("decode_paged") == "decode_paged"
    assert not [k for k in counters.delta(before) if "diffusion" in k]
