"""The paged engine enqueues decode launch N+1 before it reads launch N back.

Every token stream is the one the engine gives when each launch is read
back in the step that made it (``_sync_step``: the engine as it was before
the overlap), and for a GPT ``generate``'s.  The families that run the base engine's
decode step are cases of each test: a GPT, an Olmo-Hybrid (a recurrent
state per slot beside the pools) and a DeepSeek-V2 (a latent pool, and the
experts' counts every program carries), tiny, in interpret mode on the CPU
so that their engines decode through the Pallas walks.  The overlap is
decided by what the engine sees (whether the next launch uploads an
operand, whether a row gets its last token from the launch in flight): no
option turns it on or off.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.profiler import counters

FAMILIES = ("gpt", "olmo_hybrid", "deepseek_v2")
STEPS = "serving.decode_steps"
OVERLAPPED = "serving.decode.overlapped_steps"
S = 32                                    # every engine's max_seq_len

_MODELS = {}


def _model(family):
    if family not in _MODELS:
        paddle.seed(23)
        if family == "gpt":
            from paddle_tpu.models import GPTConfig, GPTForCausalLM
            m = GPTForCausalLM(GPTConfig(
                vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                max_seq_len=S, use_flash_attention=False))
        elif family == "olmo_hybrid":
            from paddle_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                                       OlmoHybridForCausalLM)
            m = OlmoHybridForCausalLM(OlmoHybridConfig.from_hf({
                "vocab_size": 64, "hidden_size": 32,
                "intermediate_size": 64, "num_hidden_layers": 4,
                "num_attention_heads": 2, "num_key_value_heads": 2,
                "max_position_embeddings": 64, "rms_norm_eps": 1e-6,
                "layer_types": ["linear_attention"] * 3 + ["full_attention"],
                "linear_num_key_heads": 2, "linear_num_value_heads": 2,
                "linear_key_head_dim": 8, "linear_value_head_dim": 16,
                "linear_conv_kernel_dim": 4},
                initializer_range=0.1, dtype="float32"))
        else:
            from paddle_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                                       DeepseekV2ForCausalLM)
            m = DeepseekV2ForCausalLM(DeepseekV2Config(
                vocab_size=64, hidden_size=32, intermediate_size=64,
                moe_intermediate_size=16, num_layers=3, num_heads=2,
                q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=8,
                qk_rope_head_dim=4, v_head_dim=8, n_shared_experts=1,
                n_routed_experts=8, n_group=4, topk_group=2,
                num_experts_per_tok=2, max_seq_len=64))
        m.eval()
        _MODELS[family] = m
    return _MODELS[family]


@pytest.fixture()
def interpret_mode():
    pa._INTERPRET[0] = True
    yield
    pa._INTERPRET[0] = False


def _engine(family, **kw):
    from paddle_tpu.serving import LLMEngine
    args = dict(max_slots=3, max_seq_len=S, min_bucket=4, block_size=4,
                prefill_chunk=8)
    args.update(kw)
    return LLMEngine(_model(family), **args)


def _sync_step(eng):
    """A step of the engine read synchronously: the launch it made is read
    back before it returns, so that none is in flight at the next."""
    events = eng.step()
    eng._settle(events)
    return events


def _prompt(rng, n):
    return rng.integers(1, 64, size=n).tolist()


def _mid_stream(tokens):
    """A token that first appears at the third place of ``tokens`` or
    later: as an end-of-sequence id it ends the stream mid-decode."""
    return next(t for i, t in enumerate(tokens)
                if i >= 2 and t not in tokens[:i])


def _alone(family, prompt, **kw):
    """The request's tokens with an engine to itself, read synchronously."""
    eng = _engine(family)
    h = eng.add_request(prompt, **kw)
    while not h.is_finished:
        _sync_step(eng)
    return list(h.tokens)


# each case: ``(requests, actions)``; a request is (name, prompt length,
# keywords, the step before which it is added); an action is (step, what,
# name) applied after that step: "cancel" or "deadline"
def _case(name):
    if name == "greedy":
        return [("a", 5, dict(max_new_tokens=9), 0),
                ("b", 7, dict(max_new_tokens=6), 0),
                ("c", 4, dict(max_new_tokens=7), 3)], []
    if name == "sampling":
        return [("a", 6, dict(max_new_tokens=8, do_sample=True, seed=11,
                              temperature=0.8, top_k=5), 0),
                ("b", 5, dict(max_new_tokens=6, do_sample=True, seed=12,
                              top_p=0.9), 0),
                ("c", 4, dict(max_new_tokens=6), 2)], []
    if name == "chunked":                  # prompts of 2-3 chunks of 8
        return [("a", 19, dict(max_new_tokens=6), 0),
                ("b", 5, dict(max_new_tokens=10), 0),
                ("c", 13, dict(max_new_tokens=5, do_sample=True, seed=3), 2)
                ], []
    if name == "eos":                      # set from the stream, below
        return [("a", 6, dict(max_new_tokens=10, do_sample=True, seed=5,
                              temperature=1.5), 0),
                ("b", 5, dict(max_new_tokens=9), 0),
                ("c", 7, dict(max_new_tokens=4), 1)], []
    if name == "max_seq_len":              # a and c end at position S
        return [("a", 9, dict(max_new_tokens=S - 9), 0),
                ("b", 5, dict(max_new_tokens=6), 0),
                ("c", 12, dict(max_new_tokens=S - 12), 4)], []
    if name == "cancel":
        return [("a", 5, dict(max_new_tokens=9), 0),
                ("b", 6, dict(max_new_tokens=7), 0)], [(4, "cancel", "a")]
    if name == "deadline":
        return [("a", 5, dict(max_new_tokens=9, deadline_s=600.0), 0),
                ("b", 6, dict(max_new_tokens=7), 0)], [(3, "deadline", "a")]
    if name == "readmit":                  # a's slot goes to d in one step
        return [("a", 5, dict(max_new_tokens=4), 0),
                ("b", 6, dict(max_new_tokens=9), 0),
                ("c", 7, dict(max_new_tokens=5), 1),
                ("d", 4, dict(max_new_tokens=5), 1)], []
    raise KeyError(name)


CASES = ("greedy", "sampling", "chunked", "eos", "max_seq_len", "cancel",
         "deadline", "readmit")


def _serve(eng, step, reqs, actions, prompts):
    hs, n = {}, 0
    while len(hs) < len(reqs) or not all(h.is_finished for h in hs.values()):
        for name, _, kw, at in reqs:
            if at == n and name not in hs:
                hs[name] = eng.add_request(prompts[name], **kw)
        step(eng)
        n += 1
        for at, what, name in actions:
            if at == n:
                if what == "cancel":
                    hs[name].cancel()
                else:
                    hs[name].deadline = 0.0
        assert n < 200, "engine did not converge"
    return hs


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("family", FAMILIES)
def test_streams_equal_the_synchronous_engine(family, case, interpret_mode):
    reqs, actions = _case(case)
    rng = np.random.default_rng(len(case) * 7 + FAMILIES.index(family))
    prompts = {name: _prompt(rng, n) for name, n, _, _ in reqs}
    if case == "eos":
        # a token of a's own stream ends it, mid-stream
        full = _alone(family, prompts["a"], **reqs[0][2])
        eos = _mid_stream(full)
        reqs[0][2]["eos_token_id"] = eos
        want_a = full[:full.index(eos) + 1]
    got = _serve(_engine(family), lambda e: e.step(), reqs, actions, prompts)
    ref = _serve(_engine(family), _sync_step, reqs, actions, prompts)
    for name, _, kw, _ in reqs:
        assert got[name].tokens == ref[name].tokens, name
        assert got[name].finish_reason == ref[name].finish_reason, name
    for name, _, kw, _ in reqs:
        if name in {name for _, _, name in actions}:
            assert got[name].finish_reason in ("cancelled", "deadline")
            assert 1 < len(got[name].tokens) < kw["max_new_tokens"]
        elif family == "gpt" and "eos_token_id" not in kw:
            knobs = {k: v for k, v in kw.items() if k != "max_new_tokens"}
            out = np.asarray(_model(family).generate(
                paddle.to_tensor(np.asarray([prompts[name]])),
                max_new_tokens=kw["max_new_tokens"], **knobs).numpy())[0]
            assert got[name].tokens == out[len(prompts[name]):].tolist()
    if case == "eos":
        assert got["a"].tokens == want_a and got["a"].finish_reason == "eos"
    if case == "max_seq_len":
        assert got["a"].finish_reason == "length"
        assert len(prompts["a"]) + len(got["a"].tokens) == S


@pytest.mark.parametrize("family", FAMILIES)
def test_a_steady_window_overlaps_every_launch(family):
    """With no slot changing hands every launch is enqueued behind the one
    in flight; the first after an admission is not."""
    rng = np.random.default_rng(61)
    eng = _engine(family)
    hs = [eng.add_request(_prompt(rng, n), max_new_tokens=14)
          for n in (5, 6)]
    before = counters.snapshot()
    eng.step()                    # admitted, prefilled, the first launch
    first = counters.delta(before)
    assert (first[STEPS], first.get(OVERLAPPED, 0)) == (1, 0)
    before = counters.snapshot()
    for _ in range(6):
        eng.step()
    d = counters.delta(before)
    assert d[STEPS] == d[OVERLAPPED] == 6
    assert eng._inflight is not None
    while not all(h.is_finished for h in hs):
        eng.step()
    assert eng._inflight is None         # the last token ends the stream
    assert all(len(h.tokens) == 14 for h in hs)


@pytest.mark.parametrize("family", FAMILIES)
def test_drain_and_generate_end_with_a_launch_in_flight(family):
    """``drain`` and ``generate`` terminate when a launch is in flight, and
    leave the pool whole: every block free or held by the prefix tree,
    none in a table."""
    rng = np.random.default_rng(62)
    eng = _engine(family)
    first = eng.add_request(_prompt(rng, 6), max_new_tokens=10)
    for _ in range(3):
        eng.step()
    assert eng._inflight is not None
    out = eng.generate([_prompt(rng, 5), _prompt(rng, 7)], max_new_tokens=5)
    assert [len(o) for o in out] == [10, 12]
    eng.add_request(_prompt(rng, 4), max_new_tokens=8)
    for _ in range(3):
        eng.step()
    assert eng._inflight is not None
    done = eng.drain()
    assert first.is_finished and len(first.tokens) == 10
    assert all(r.is_finished for r in done)
    assert eng._inflight is None and not eng.has_work()
    assert eng.stats()["blocks_live"] == 0
    if eng.prefix is not None:
        eng.prefix.clear()             # what the tree kept goes back too
    assert eng.pool.free_blocks == eng.pool.capacity


def test_an_end_of_sequence_row_runs_once_more_and_is_dropped():
    """A row that ends by an end-of-sequence token at launch N ran in N+1,
    which was enqueued before N was read: its token there is dropped, its
    slot is handed on, and the next request there is served as alone."""
    rng = np.random.default_rng(63)
    p = _prompt(rng, 6)
    kw = dict(max_new_tokens=10, do_sample=True, seed=5, temperature=1.5)
    full = _alone("gpt", p, **kw)
    eos = _mid_stream(full)
    eng = _engine("gpt", max_slots=2)
    a = eng.add_request(p, eos_token_id=eos, **kw)
    b = eng.add_request(_prompt(rng, 5), max_new_tokens=14)
    before = counters.get("serving.decode_tokens")
    while not a.is_finished:
        eng.step()
    assert a.tokens == full[:full.index(eos) + 1]
    assert a.slot is None and eng._inflight is not None
    assert a in [r for _, r in eng._inflight.rows]   # the launch after it
    c_prompt = _prompt(rng, 7)
    c = eng.add_request(c_prompt, max_new_tokens=5)
    while not (b.is_finished and c.is_finished):
        eng.step()
    # tokens counted are tokens emitted: the dropped one is not among them
    emitted = len(a.tokens) + len(b.tokens) + len(c.tokens) - 3
    assert counters.get("serving.decode_tokens") - before == emitted
    assert c.tokens == _alone("gpt", c_prompt, max_new_tokens=5)


@pytest.mark.parametrize("engine", ("block_decode", "speculative"))
def test_the_engines_with_their_own_decode_step_never_overlap(engine):
    rng = np.random.default_rng(64)
    from paddle_tpu.serving import LLMEngine
    if engine == "block_decode":
        from paddle_tpu.models import sdar
        paddle.seed(7)
        model = sdar.SdarMoeForCausalLM(sdar.SdarConfig(
            vocab_size=512, hidden_size=64, moe_intermediate_size=32,
            num_layers=2, num_heads=8, num_kv_heads=2, head_dim=16,
            num_experts=16, num_experts_per_tok=8, max_seq_len=512,
            mask_token_id=500, initializer_range=0.1))
        model.eval()
        eng = LLMEngine(model, block_size=16, max_slots=2, max_seq_len=64,
                        n_blocks=12, prefill_chunk=16, min_bucket=16)
        prompts = [rng.integers(1, 400, size=n).tolist() for n in (6, 9)]
    else:
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        paddle.seed(9)
        draft = GPTForCausalLM(GPTConfig(
            vocab_size=64, hidden_size=16, num_layers=1, num_heads=2,
            max_seq_len=S, use_flash_attention=False))
        draft.eval()
        eng = _engine("gpt", draft_model=draft, spec_k=2)
        prompts = [_prompt(rng, n) for n in (5, 6)]
    before = counters.snapshot()
    hs = [eng.add_request(p, max_new_tokens=8) for p in prompts]
    while not all(h.is_finished for h in hs):
        eng.step()
        assert eng._inflight is None
    d = counters.delta(before)
    assert d[STEPS] > 0 and d.get(OVERLAPPED, 0) == 0
    assert OVERLAPPED in counters.snapshot()
