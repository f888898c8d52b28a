"""Continuous-batching serving engine (paddle_tpu.serving).

The load-bearing contract: LLMEngine output is TOKEN-IDENTICAL to running
each request alone through GPT.generate with the same seed — continuous
batching, slot placement, bucketed prefill, and staggered arrival must be
invisible in the tokens.  Plus the robustness surface: eviction/slot
reuse, EOS/deadline/cancel, backpressure, drain, and the O(log S_max)
prefill-program bound."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.profiler import counters


def _model(**kw):
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=32,
                    use_flash_attention=False, **kw)
    paddle.seed(31)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _engine(m, **kw):
    from paddle_tpu.serving import LLMEngine
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("min_bucket", 4)
    return LLMEngine(m, **kw)


def _ref_generate(m, prompt, max_new, **kw):
    """Sequential reference: the request alone through GPT.generate."""
    out = np.asarray(m.generate(paddle.to_tensor(np.asarray([prompt])),
                                max_new_tokens=max_new, **kw).numpy())[0]
    return out[len(prompt):]


def _run(eng, handles, limit=200):
    n = 0
    while not all(h.is_finished for h in handles):
        eng.step()
        n += 1
        assert n < limit, "engine did not converge"
    return n


class TestEngineMatchesGenerate:
    @pytest.mark.parametrize("use_rope", [False, True])
    def test_greedy_token_identical(self, use_rope):
        m = _model(use_rope=use_rope)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 64, size=n).tolist()
                   for n in (5, 3, 9, 6, 11)]
        refs = [_ref_generate(m, p, 6) for p in prompts]
        eng = _engine(m)
        hs = [eng.add_request(p, max_new_tokens=6) for p in prompts]
        _run(eng, hs)
        for h, r in zip(hs, refs):
            assert np.array_equal(h.tokens, r), (h.tokens, list(r))
            assert h.finish_reason == "length"

    def test_sampling_token_identical(self):
        """Per-slot temperature/top-k/top-p + per-request key chain
        reproduce generate's draws exactly (shared serving.sampling)."""
        m = _model()
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 64, size=n).tolist() for n in (4, 7, 11)]
        kw = dict(do_sample=True, temperature=0.8, top_k=8, top_p=0.9)
        refs = [_ref_generate(m, p, 5, seed=100 + i, **kw)
                for i, p in enumerate(prompts)]
        eng = _engine(m, max_slots=4)
        hs = [eng.add_request(p, max_new_tokens=5, seed=100 + i, **kw)
              for i, p in enumerate(prompts)]
        _run(eng, hs)
        for h, r in zip(hs, refs):
            assert np.array_equal(h.tokens, r), (h.tokens, list(r))

    def test_staggered_arrivals_identical(self):
        """Requests joining mid-flight decode next to half-finished ones
        and still match their solo trajectories."""
        m = _model()
        rng = np.random.default_rng(2)
        prompts = [rng.integers(0, 64, size=n).tolist()
                   for n in (6, 4, 8, 5)]
        refs = [_ref_generate(m, p, 6) for p in prompts]
        eng = _engine(m, max_slots=4)
        hs = [eng.add_request(prompts[0], max_new_tokens=6)]
        eng.step()
        eng.step()
        hs.append(eng.add_request(prompts[1], max_new_tokens=6))
        eng.step()
        hs += [eng.add_request(p, max_new_tokens=6) for p in prompts[2:]]
        _run(eng, hs)
        for h, r in zip(hs, refs):
            assert np.array_equal(h.tokens, r), (h.tokens, list(r))

    def test_mixed_greedy_and_sampled_slots(self):
        m = _model()
        rng = np.random.default_rng(3)
        pg = rng.integers(0, 64, size=5).tolist()
        ps = rng.integers(0, 64, size=7).tolist()
        ref_g = _ref_generate(m, pg, 5)
        ref_s = _ref_generate(m, ps, 5, do_sample=True, temperature=0.7,
                              top_k=6, seed=9)
        eng = _engine(m)
        hg = eng.add_request(pg, max_new_tokens=5)
        hsmp = eng.add_request(ps, max_new_tokens=5, do_sample=True,
                               temperature=0.7, top_k=6, seed=9)
        _run(eng, [hg, hsmp])
        assert np.array_equal(hg.tokens, ref_g)
        assert np.array_equal(hsmp.tokens, ref_s)


class TestSlots:
    def test_eviction_and_reuse(self):
        """5 requests through 2 slots: slots are freed on finish and
        rehanded; everyone completes with the solo trajectory."""
        m = _model()
        rng = np.random.default_rng(4)
        prompts = [rng.integers(0, 64, size=n).tolist()
                   for n in (5, 3, 7, 4, 6)]
        refs = [_ref_generate(m, p, 4) for p in prompts]
        before = counters.snapshot()
        eng = _engine(m, max_slots=2, queue_size=8)
        hs = [eng.add_request(p, max_new_tokens=4) for p in prompts]
        _run(eng, hs)
        delta = counters.delta(before)
        assert delta.get("serving.evictions", 0) == 5
        assert delta.get("serving.evictions.length", 0) == 5
        for h, r in zip(hs, refs):
            assert np.array_equal(h.tokens, r)
        # all slots free again, occupancy gauge settled at 0
        assert eng.stats()["free_slots"] == 2
        assert counters.get("serving.slot_occupancy") == 0.0

    def test_eos_evicts_early(self):
        m = _model()
        rng = np.random.default_rng(5)
        p = rng.integers(0, 64, size=4).tolist()
        # eos = the 2nd greedily generated token → finishes at its first
        # occurrence (which is index 0 if greedy repeats the token)
        ref = _ref_generate(m, p, 8)
        eos = int(ref[1])
        stop = int(np.flatnonzero(ref == eos)[0])
        eng = _engine(m)
        h = eng.add_request(p, max_new_tokens=8, eos_token_id=eos)
        _run(eng, [h])
        assert h.finish_reason == "eos"
        assert h.tokens == list(map(int, ref[: stop + 1]))
        assert eng.stats()["free_slots"] == eng.max_slots

    def test_deadline_expires_in_queue(self):
        """deadline_s=0 is already past at admission: the request is
        dropped from the queue without ever taking a slot."""
        m = _model()
        rng = np.random.default_rng(6)
        p = rng.integers(0, 64, size=4).tolist()
        eng = _engine(m)
        h = eng.add_request(p, max_new_tokens=20, deadline_s=0.0)
        _run(eng, [h])
        assert h.finish_reason == "deadline"
        assert h.tokens == []
        assert eng.stats()["free_slots"] == eng.max_slots

    def test_deadline_evicts_running_with_partial_output(self):
        m = _model()
        rng = np.random.default_rng(6)
        p = rng.integers(0, 64, size=4).tolist()
        eng = _engine(m)
        h = eng.add_request(p, max_new_tokens=20, deadline_s=60.0)
        eng.step()  # admitted; prefill emits the first token
        first = len(h.tokens)
        assert first >= 1 and h.state == "running"
        h.deadline = 0.0  # force expiry; next sweep evicts
        _run(eng, [h])
        assert h.finish_reason == "deadline"
        # sweep runs before decode: the one launch made before the
        # deadline is read back first, and no launch follows it
        assert len(h.tokens) == first + 1
        assert eng.stats()["free_slots"] == eng.max_slots

    def test_cancel_active_and_queued(self):
        m = _model()
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, 64, size=4).tolist() for _ in range(3)]
        eng = _engine(m, max_slots=1, queue_size=8)
        h0 = eng.add_request(prompts[0], max_new_tokens=20)
        h1 = eng.add_request(prompts[1], max_new_tokens=4)
        eng.step()
        assert h0.state == "running" and h1.state == "queued"
        h0.cancel()   # active
        h1.cancel()   # still queued
        h2 = eng.add_request(prompts[2], max_new_tokens=3)
        _run(eng, [h0, h1, h2])
        assert h0.finish_reason == "cancelled" and len(h0.tokens) >= 1
        assert h1.finish_reason == "cancelled" and h1.tokens == []
        assert h2.finish_reason == "length" and len(h2.tokens) == 3


class TestRobustness:
    def test_backpressure_nonblocking_raises(self):
        from paddle_tpu.serving import EngineBackpressure
        m = _model()
        eng = _engine(m, max_slots=1, queue_size=2)
        eng.add_request([1, 2, 3], max_new_tokens=4)
        eng.add_request([1, 2, 3], max_new_tokens=4)
        with pytest.raises(EngineBackpressure):
            eng.add_request([1, 2, 3], max_new_tokens=4, block=False)

    def test_backpressure_blocking_times_out(self):
        from paddle_tpu.serving import EngineBackpressure
        m = _model()
        eng = _engine(m, max_slots=1, queue_size=1)
        eng.add_request([1, 2, 3], max_new_tokens=4)
        with pytest.raises(EngineBackpressure, match="timed out"):
            eng.add_request([1, 2, 3], max_new_tokens=4, block=True,
                            timeout=0.05)

    def test_backpressure_releases_as_queue_drains(self):
        from paddle_tpu.serving import EngineBackpressure
        m = _model()
        eng = _engine(m, max_slots=1, queue_size=1)
        h0 = eng.add_request([1, 2, 3], max_new_tokens=2)  # fills queue
        with pytest.raises(EngineBackpressure):
            eng.add_request([2, 3, 4], max_new_tokens=2, block=False)
        eng.step()  # h0 admitted to the slot → queue has room again
        h1 = eng.add_request([2, 3, 4], max_new_tokens=2, block=False)
        _run(eng, [h0, h1])
        assert all(h.finish_reason == "length" for h in (h0, h1))

    def test_drain_finishes_everything_and_closes(self):
        from paddle_tpu.serving import EngineClosed
        m = _model()
        rng = np.random.default_rng(8)
        prompts = [rng.integers(0, 64, size=4).tolist() for _ in range(4)]
        eng = _engine(m, max_slots=2, queue_size=8)
        hs = [eng.add_request(p, max_new_tokens=3) for p in prompts]
        eng.step()
        done = eng.drain()
        assert all(h.is_finished for h in hs)
        assert {r.rid for r in done} | {h.rid for h in hs} \
            == {h.rid for h in hs}
        assert not eng.has_work()
        with pytest.raises(EngineClosed):
            eng.add_request([1, 2], max_new_tokens=2)

    def test_request_validation(self):
        m = _model()
        eng = _engine(m)
        with pytest.raises(ValueError, match="max_seq_len"):
            eng.add_request(list(range(20)), max_new_tokens=20)
        with pytest.raises(ValueError, match="empty"):
            eng.add_request([], max_new_tokens=2)

    def test_streaming_iterator(self):
        m = _model()
        rng = np.random.default_rng(9)
        p = rng.integers(0, 64, size=5).tolist()
        ref = _ref_generate(m, p, 6)
        eng = _engine(m)
        h = eng.add_request(p, max_new_tokens=6)
        streamed = list(h)  # pumps eng.step() internally
        assert np.array_equal(streamed, ref)
        assert np.array_equal(h.output_ids(), list(p) + list(ref))

    def test_queued_deadline_expiry_evicts_before_prefill(self):
        """An expired-deadline request is dropped from the QUEUE — counted
        under serving.deadline_expired, never reaching prefill — while a
        healthy request admitted in the same step is unaffected."""
        m = _model()
        rng = np.random.default_rng(12)
        p_live = rng.integers(0, 64, size=5).tolist()
        ref = _ref_generate(m, p_live, 4)
        eng = _engine(m)
        before = counters.snapshot()
        h_dead = eng.add_request(rng.integers(0, 64, size=4).tolist(),
                                 max_new_tokens=8, deadline_s=0.0)
        h_live = eng.add_request(p_live, max_new_tokens=4)
        _run(eng, [h_dead, h_live])
        d = counters.delta(before)
        assert h_dead.finish_reason == "deadline"
        assert h_dead.tokens == []
        assert h_live.finish_reason == "length"
        assert np.array_equal(h_live.tokens, ref)
        assert d.get("serving.deadline_expired", 0) == 1
        # only the live request ever prefilled (no slot/work for the dead)
        assert d.get("serving.prefill_batches", 0) == 1
        assert eng.stats()["free_slots"] == eng.max_slots

    def test_poisoned_request_contained_to_error(self):
        """A request whose prefill blows up finishes with
        finish_reason="error" (exception on .error) — the slot is returned
        and every OTHER request still matches sequential generate."""
        from paddle_tpu.resilience import faultinject
        m = _model()
        rng = np.random.default_rng(13)
        p_good = rng.integers(0, 64, size=6).tolist()
        ref = _ref_generate(m, p_good, 4)
        eng = _engine(m)
        h_bad = eng.add_request(rng.integers(0, 64, size=4).tolist(),
                                max_new_tokens=8)   # rid 0
        h_good = eng.add_request(p_good, max_new_tokens=4)  # rid 1
        before = counters.snapshot()
        with faultinject.fault_schedule(f"serving_prefill@{h_bad.rid}"):
            _run(eng, [h_bad, h_good])
            assert faultinject.fired == [("serving_prefill", h_bad.rid)]
        d = counters.delta(before)
        assert h_bad.finish_reason == "error"
        assert isinstance(h_bad.error, faultinject.InjectedFault)
        assert h_bad.tokens == []
        assert h_good.finish_reason == "length"
        assert np.array_equal(h_good.tokens, ref)
        assert d.get("serving.request_errors", 0) == 1
        assert eng.stats()["free_slots"] == eng.max_slots
        # the engine keeps serving after containment
        h_next = eng.add_request(p_good, max_new_tokens=4)
        _run(eng, [h_next])
        assert np.array_equal(h_next.tokens, ref)


class TestBuckets:
    def test_bucket_length(self):
        from paddle_tpu.serving import bucket_length
        assert bucket_length(1, min_bucket=4) == 4
        assert bucket_length(4, min_bucket=4) == 4
        assert bucket_length(5, min_bucket=4) == 8
        assert bucket_length(9, min_bucket=4) == 16
        assert bucket_length(9, min_bucket=4, max_len=12) == 12

    def test_prefill_programs_bounded_and_no_steady_retraces(self):
        """Many distinct prompt lengths → O(log S_max) prefill programs;
        once buckets are warm, new requests trace NOTHING."""
        m = _model()
        rng = np.random.default_rng(10)
        eng = _engine(m, max_slots=2, queue_size=32)
        lens = [3, 4, 5, 6, 7, 9, 11, 13, 15]  # buckets {4, 8, 16}
        hs = [eng.add_request(rng.integers(0, 64, size=n).tolist(),
                              max_new_tokens=2) for n in lens]
        _run(eng, hs)
        assert eng.stats()["prefill_programs"] == 3
        assert counters.get("serving.prefill_programs") == 3
        # steady state: same buckets again — zero serving retraces
        before = counters.snapshot()
        hs = [eng.add_request(rng.integers(0, 64, size=n).tolist(),
                              max_new_tokens=2) for n in (3, 6, 12)]
        _run(eng, hs)
        delta = counters.delta(before)
        assert delta.get("serving.retraces", 0) == 0, delta
        assert delta.get("jit.traces", 0) == 0
        assert eng.stats()["prefill_programs"] == 3


class TestGenerateExtensions:
    def test_engine_generate_blocking_api(self):
        m = _model()
        rng = np.random.default_rng(11)
        prompts = [rng.integers(0, 64, size=n).tolist() for n in (4, 6, 3)]
        refs = [_ref_generate(m, p, 4) for p in prompts]
        eng = _engine(m, max_slots=2, queue_size=2)  # oversubscribed
        outs = eng.generate(prompts, max_new_tokens=4)
        for o, p, r in zip(outs, prompts, refs):
            assert np.array_equal(o, list(p) + list(r))

    def test_generation_predictor_routes_through_engine(self):
        from paddle_tpu.inference import GenerationPredictor
        m = _model()
        rng = np.random.default_rng(12)
        prompts = [rng.integers(0, 64, size=n).tolist() for n in (5, 7)]
        refs = [_ref_generate(m, p, 4) for p in prompts]
        pred = GenerationPredictor(m, max_slots=2, max_seq_len=32,
                                   min_bucket=4)
        outs = pred.generate(prompts, max_new_tokens=4)
        for o, p, r in zip(outs, prompts, refs):
            assert np.array_equal(o, list(p) + list(r))
        streamed = list(pred.stream(prompts[0], max_new_tokens=4))
        assert np.array_equal(streamed, refs[0])
        pred.close()
        from paddle_tpu.serving import EngineClosed
        with pytest.raises(EngineClosed):
            pred.engine.add_request([1], max_new_tokens=1)

    def test_generate_top_p_reproducible_and_constraining(self):
        """top_p in GPT.generate: seeded reproducibility; p→0 degenerates
        to greedy (nucleus keeps only the top token)."""
        m = _model()
        ids = paddle.randint(0, 64, [2, 4])
        a = np.asarray(m.generate(ids, max_new_tokens=5, do_sample=True,
                                  top_p=0.7, seed=3).numpy())
        b = np.asarray(m.generate(ids, max_new_tokens=5, do_sample=True,
                                  top_p=0.7, seed=3).numpy())
        assert np.array_equal(a, b)
        greedy = np.asarray(m.generate(ids, max_new_tokens=5).numpy())
        tiny = np.asarray(m.generate(ids, max_new_tokens=5, do_sample=True,
                                     top_p=1e-6, seed=5).numpy())
        assert np.array_equal(tiny, greedy)

    def test_gen_cache_lru_bound(self):
        """_gen_cache is LRU-bounded: recently used shapes survive, the
        stalest executable is evicted."""
        m = _model()
        m._gen_cache_max = 2
        ids3 = paddle.randint(0, 64, [1, 3])
        ids4 = paddle.randint(0, 64, [1, 4])
        ids5 = paddle.randint(0, 64, [1, 5])
        m.generate(ids3, max_new_tokens=2)   # A
        m.generate(ids4, max_new_tokens=2)   # B
        assert len(m._gen_cache) == 2
        m.generate(ids3, max_new_tokens=2)   # hit A → B is now LRU
        m.generate(ids5, max_new_tokens=2)   # C evicts B
        keys = list(m._gen_cache)
        assert len(keys) == 2
        assert {k[1] for k in keys} == {3, 5}

    def test_moe_model_serves(self):
        m = _model(num_experts=2)
        eng = _engine(m)
        h = eng.add_request([1, 2, 3, 4], max_new_tokens=3)
        _run(eng, [h])
        assert len(h.tokens) == 3


class TestFleetSatellites:
    """Engine-level guarantees the elastic fleet layer builds on:
    finish-CAS idempotence, atomic stats with outstanding-token
    accounting, structured backpressure, and drain's pre-prefill sweep
    of deadline-expired queued requests."""

    def test_double_finish_is_idempotent_single_eviction(self):
        """The fleet reaps/cancels from a different thread than the
        replica's step loop: a racing double finish must transition once,
        keep the first reason, and never double-release the KV slot."""
        m = _model()
        eng = _engine(m)
        h = eng.add_request([1, 2, 3], max_new_tokens=4, block=False)
        eng.step()                      # admitted: slot assigned
        assert h.slot is not None
        before = counters.snapshot()
        events = []
        assert eng._finish(h, "cancelled", events) is True
        free0 = eng.stats()["free_slots"]
        assert eng._finish(h, "error", events) is False   # CAS loses
        assert h.finish_reason == "cancelled"             # first wins
        assert eng.stats()["free_slots"] == free0
        assert sorted(eng._free) == sorted(set(eng._free))
        d = counters.delta(before)
        assert d.get("serving.evictions", 0) == 1
        assert len(events) == 1
        eng.drain()

    def test_stats_outstanding_tokens_and_tps_ema(self):
        """stats() is one atomic snapshot; outstanding_tokens is the
        undelivered decode-token backlog (+max_new at admission, -1 per
        emitted token, -remainder at finish) and sums back to zero."""
        m = _model()
        eng = _engine(m)
        assert eng.stats()["outstanding_tokens"] == 0
        h1 = eng.add_request([1, 2, 3], max_new_tokens=6, block=False)
        h2 = eng.add_request([4, 5], max_new_tokens=3, block=False)
        assert eng.stats()["outstanding_tokens"] == 9
        eng.step()
        delivered = len(h1.tokens) + len(h2.tokens)
        assert eng.stats()["outstanding_tokens"] == 9 - delivered
        _run(eng, [h1, h2])
        st = eng.stats()
        assert st["outstanding_tokens"] == 0
        assert st["decode_tps_ema"] > 0        # decode launches ran
        # early finish returns the unspent budget, not just -1 per token
        h3 = eng.add_request([1, 2, 3], max_new_tokens=20, block=False)
        eng.step()
        h3.cancel()
        _run(eng, [h3])
        assert eng.stats()["outstanding_tokens"] == 0
        eng.drain()

    def test_backpressure_carries_depth_and_hint(self):
        from paddle_tpu.serving import EngineBackpressure
        m = _model()
        eng = _engine(m, max_slots=1, queue_size=2)
        hs = [eng.add_request([1, 2, 3], max_new_tokens=4, block=False)
              for _ in range(2)]
        with pytest.raises(EngineBackpressure) as ei:
            eng.add_request([1, 2, 3], max_new_tokens=4, block=False)
        assert ei.value.queue_depth == 2
        assert ei.value.retry_after_hint is None   # cold: no EMA yet
        _run(eng, hs)
        hs = [eng.add_request([1, 2, 3], max_new_tokens=4, block=False)
              for _ in range(2)]
        with pytest.raises(EngineBackpressure) as ei:
            eng.add_request([1, 2, 3], max_new_tokens=4, block=False)
        assert ei.value.queue_depth == 2
        assert ei.value.retry_after_hint is not None   # backlog / tps EMA
        assert ei.value.retry_after_hint > 0
        _run(eng, hs)
        eng.drain()

    def test_drain_sweeps_expired_queued_without_prefill(self):
        """drain() sweeps deadline-expired queued requests BEFORE the
        step loop: they terminate with reason='deadline' and zero tokens
        instead of spending a prefill launch each."""
        m = _model()
        eng = _engine(m, max_slots=1)
        h1 = eng.add_request([1, 2, 3], max_new_tokens=3, block=False)
        h2 = eng.add_request([4, 5, 6], max_new_tokens=3, block=False,
                             deadline_s=0.0)
        before = counters.snapshot()
        eng.drain()
        d = counters.delta(before)
        assert h1.finish_reason == "length"
        assert h2.finish_reason == "deadline"
        assert h2.tokens == []
        assert d.get("serving.deadline_expired", 0) == 1
        assert d.get("serving.prefill_batches", 0) == 1   # h1 only


# -- the one sampling tail (serving.sampling.next_tokens) --------------------
_V = 384
# (do_sample, temperature, top_k, top_p) per row of a batch of four
_KNOB_MIXES = {
    "all_greedy": [(False, 1.0, 0, 1.0)] * 4,
    "all_sampling_neutral": [(True, 1.0, 0, 1.0)] * 4,
    "top_k_only": [(True, 0.8, 5, 1.0), (True, 1.0, 40, 1.0),
                   (True, 1.3, 1, 1.0), (True, 1.0, 7, 1.0)],
    "top_p_only": [(True, 1.0, 0, 0.9), (True, 0.7, 0, 0.5),
                   (True, 1.0, 0, 0.99), (True, 1.2, 0, 0.8)],
    "greedy_and_filtered": [(False, 1.0, 0, 1.0), (True, 0.8, 8, 0.9),
                            (False, 0.7, 6, 0.5), (True, 1.0, 0, 0.95)],
}


def _tail_operands(mix, seed=0, scale=3.0):
    import jax
    import jax.numpy as jnp
    rows = _KNOB_MIXES[mix]
    rng = np.random.default_rng(seed)
    logits = jnp.asarray(rng.normal(0, scale, (len(rows), _V)), jnp.float32)
    keys = jax.vmap(jax.random.key_data)(
        jax.vmap(jax.random.key)(jnp.arange(len(rows)) + 11 * seed))
    ds, t, tk, tp = zip(*rows)
    return (logits, keys, jnp.asarray(ds, jnp.bool_),
            jnp.asarray(t, jnp.float32), jnp.asarray(tk, jnp.int32),
            jnp.asarray(tp, jnp.float32))


def _old_decode_tail(logits, keys_data, do_sample, temp, top_k, top_p):
    """The tail as ``sample_next`` / ``engine._decode`` wrote it out before
    ``next_tokens``: every row filtered and drawn, then selected."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving.sampling import filter_logits
    keys = jax.random.wrap_key_data(keys_data)
    pair = jax.vmap(jax.random.split)(keys)
    new_keys, kstep = pair[:, 0], pair[:, 1]
    sampled = jax.vmap(
        lambda k, lg, t, tk, tp: jax.random.categorical(
            k, filter_logits(lg[None], t, tk, tp), axis=-1)[0]
    )(kstep, logits, temp, top_k, top_p)
    greedy = jnp.argmax(logits, axis=-1)
    nxt = jnp.where(do_sample, sampled, greedy).astype(jnp.int32)
    return nxt, jax.random.key_data(new_keys)


def _old_first_token(logits, key_data, do_sample, temp, top_k, top_p):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving.sampling import filter_logits
    key, k0 = jax.random.split(jax.random.wrap_key_data(key_data))
    flg = filter_logits(logits, temp, top_k, top_p)
    sampled = jax.random.categorical(k0, flg, axis=-1)
    greedy = jnp.argmax(logits, axis=-1)
    tok = jnp.where(do_sample, sampled, greedy).astype(jnp.int32)
    return tok[0], jax.random.key_data(key)


def _old_draft_tail(logits, keys_data, do_sample, temp, top_k, top_p):
    """``speculative.sample_q`` as it was: the draw and the filtered
    distribution it was drawn from."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving.sampling import filter_logits
    keys = jax.random.wrap_key_data(keys_data)
    pair = jax.vmap(jax.random.split)(keys)
    new_keys, kstep = pair[:, 0], pair[:, 1]
    flg = jax.vmap(lambda lg, t, tk, tp: filter_logits(
        lg[None], t, tk, tp)[0])(logits, temp, top_k, top_p)
    sampled = jax.vmap(lambda kk, lg: jax.random.categorical(
        kk, lg, axis=-1))(kstep, flg)
    greedy = jnp.argmax(logits, axis=-1)
    nxt = jnp.where(do_sample, sampled, greedy).astype(jnp.int32)
    return nxt, jax.nn.softmax(flg, axis=-1), jax.random.key_data(new_keys)


def _sorts_outside_conditionals(hlo_text):
    """``(sorts, loose)``: how many ``sort`` instructions an HLO module
    has, and the computations holding one that ENTRY reaches WITHOUT
    passing through a ``conditional``'s branch."""
    import re
    comps, name = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$", line)
        if head and not line.startswith(" "):
            name = head.group(2)
            comps[name] = {"entry": bool(head.group(1)), "sorts": 0,
                           "calls": set()}
        elif line.startswith("}"):
            name = None
        elif name is not None and "=" in line:
            body = line.split("=", 1)[1]
            if re.search(r"\bsort\(", body):
                comps[name]["sorts"] += 1
            if not re.search(r"\bconditional\(", body):
                for called in re.findall(
                        r"(?:to_apply|calls|body|condition)=(\{[^}]*\}|\S+)",
                        body):
                    comps[name]["calls"] |= set(
                        re.findall(r"[\w.\-]+", called))
    entry = [n for n, c in comps.items() if c["entry"]]
    assert len(entry) == 1, entry
    seen, todo = set(), list(entry)
    while todo:
        n = todo.pop()
        if n in seen or n not in comps:
            continue
        seen.add(n)
        todo.extend(comps[n]["calls"])
    return (sum(c["sorts"] for c in comps.values()),
            [n for n in seen if comps[n]["sorts"]])


def _record_program(eng, attr, key=None):
    """Shim one of the engine's jitted programs so that its next launch
    leaves the abstract shapes of its operands in the returned dict."""
    import jax
    jits = getattr(eng, attr)
    fn = jits if key is None else jits[key]
    seen = {"fn": fn}

    def shim(*args):
        seen["shapes"] = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
        return fn(*args)
    if key is None:
        setattr(eng, attr, shim)
    else:
        jits[key] = shim
    return seen


# the engine's own geometry (one block and one chunk hold a whole prompt
# of these tests) and one where a prompt spans blocks and chunks
_GEOMETRIES = {"defaults": {},
               "small_blocks": dict(block_size=4, prefill_chunk=8)}


class TestSamplingTail:
    @pytest.mark.parametrize("mix", sorted(_KNOB_MIXES))
    def test_next_tokens_bitwise_old_decode_tail(self, mix):
        """Tokens AND new keys, bit for bit the tail every decode program
        wrote out before: over seeds and logit scales, jitted as the
        programs jit it."""
        import jax
        from paddle_tpu.serving.sampling import next_tokens
        new, old = jax.jit(next_tokens), jax.jit(_old_decode_tail)
        for seed, scale in ((0, 1.0), (1, 3.0), (2, 5.0)):
            ops = _tail_operands(mix, seed, scale)
            (n_tok, n_keys), (o_tok, o_keys) = new(*ops), old(*ops)
            assert n_tok.dtype == o_tok.dtype == np.int32
            assert np.array_equal(n_tok, o_tok), (mix, seed)
            assert np.array_equal(n_keys, o_keys), (mix, seed)

    @pytest.mark.parametrize("mix", sorted(_KNOB_MIXES))
    def test_first_token_bitwise_old_first_token(self, mix):
        """A prefill program's draw is the same tail over a batch of one:
        scalar knobs, one key, ``logits[1, V]``."""
        import jax
        from paddle_tpu.serving import LLMEngine
        new, old = jax.jit(LLMEngine._first_token), jax.jit(_old_first_token)
        logits, keys, ds, t, tk, tp = _tail_operands(mix, seed=4)
        for b in range(logits.shape[0]):
            ops = (logits[b:b + 1], keys[b], ds[b], t[b], tk[b], tp[b])
            (n_tok, n_key), (o_tok, o_key) = new(*ops), old(*ops)
            assert n_tok.shape == () and int(n_tok) == int(o_tok), (mix, b)
            assert np.array_equal(n_key, o_key), (mix, b)

    @pytest.mark.parametrize("mix", ["all_greedy", "greedy_and_filtered",
                                     "top_p_only"])
    def test_draft_tail_bitwise_old_sample_q(self, mix):
        """The speculative drafter's proposal and, for every sampling
        row, the distribution ``q`` the acceptance test divides by (a
        greedy row's is never read)."""
        import functools
        import jax
        from paddle_tpu.serving.sampling import next_tokens
        new = jax.jit(functools.partial(next_tokens, with_dist=True))
        ops = _tail_operands(mix, seed=5)
        (n_tok, n_q, n_keys) = new(*ops)
        (o_tok, o_q, o_keys) = jax.jit(_old_draft_tail)(*ops)
        assert np.array_equal(n_tok, o_tok)
        assert np.array_equal(n_keys, o_keys)
        ds = np.asarray(ops[2])
        assert n_q.shape == o_q.shape
        assert np.array_equal(np.asarray(n_q)[ds], np.asarray(o_q)[ds])

    def test_traced_neutral_nucleus_is_not_the_identity(self):
        """What the module docstring says of traced neutral knobs: the
        top-k threshold masks nothing, the ``top_p = 1.0`` nucleus masks
        tail tokens once the float32 cumsum rounds up to 1.0."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.serving.sampling import filter_logits
        lg = jnp.asarray(np.random.default_rng(0).normal(0, 5, (4, 50304)),
                         jnp.float32)
        only_k = jax.jit(lambda x, t, k: filter_logits(x, t, k, 1.0))(
            lg, jnp.float32(1.0), jnp.int32(0))
        assert np.array_equal(only_k, lg)
        both = jax.jit(filter_logits)(
            lg, jnp.float32(1.0), jnp.int32(0), jnp.float32(1.0))
        masked = np.asarray(both) != np.asarray(lg)
        assert masked.any()
        # next to no mass: the tokens of the identity tests still agree
        assert float(jnp.sum(jnp.where(masked, jax.nn.softmax(lg), 0))) < 1e-4

    def test_sorts_only_inside_a_conditional_branch(self):
        """The decode program's and a prefill chunk program's
        lowered HLO keep every ``sort`` inside a ``conditional``'s branch:
        the ``cond`` sits outside the per-row ``vmap`` and did not turn
        into a select that runs both sides."""
        m = _model()
        eng = _engine(m, block_size=4, prefill_chunk=8)
        h = eng.add_request([3, 1, 4, 1, 5], max_new_tokens=2)
        _run(eng, [h])                               # builds the programs
        bucket = next(iter(eng._pchunk_jits))
        progs = [_record_program(eng, "_pdecode_jit"),
                 _record_program(eng, "_pchunk_jits", bucket)]
        h = eng.add_request([2, 7, 1, 8, 2], max_new_tokens=3)
        _run(eng, [h])
        for seen in progs:
            text = seen["fn"].lower(*seen["shapes"]).as_text(dialect="hlo")
            sorts, loose = _sorts_outside_conditionals(text)
            assert sorts and "conditional(" in text
            assert loose == [], loose
        # the walker does tell a select from a branch
        import jax
        bad = jax.jit(_old_decode_tail).lower(
            *_tail_operands("all_greedy")).as_text(dialect="hlo")
        assert _sorts_outside_conditionals(bad)[1]

    @pytest.mark.parametrize("geometry", sorted(_GEOMETRIES))
    def test_neutral_row_ignores_a_filtering_neighbour(self, geometry):
        """A request's output does not depend on who shares its batch: a
        sampling row with neutral knobs draws the same tokens alone and
        beside a row that filters (the one predicate is any(do_sample),
        never "does any row filter")."""
        m = _model()
        rng = np.random.default_rng(12)
        pa = rng.integers(0, 64, size=6).tolist()
        pb = rng.integers(0, 64, size=5).tolist()
        kw = _GEOMETRIES[geometry]
        ref = _ref_generate(m, pa, 8, do_sample=True, seed=21)
        eng = _engine(m, **kw)
        alone = eng.add_request(pa, max_new_tokens=8, do_sample=True,
                                seed=21)
        _run(eng, [alone])
        eng = _engine(m, **kw)
        beside = eng.add_request(pa, max_new_tokens=8, do_sample=True,
                                 seed=21)
        other = eng.add_request(pb, max_new_tokens=8, do_sample=True,
                                top_p=0.9, temperature=0.7, seed=22)
        _run(eng, [beside, other])
        assert list(alone.tokens) == list(beside.tokens)
        assert np.array_equal(alone.tokens, ref)

    @pytest.mark.parametrize("geometry", sorted(_GEOMETRIES))
    def test_sampled_steps_counts_steps_with_a_sampling_row(self, geometry):
        """``serving.decode.sampled_steps`` beside ``serving.decode_steps``
        in the mixed batch of ``test_mixed_greedy_and_sampled_slots``: the
        sampling request decodes 3 times (its first token is the
        prefill's), the greedy one 7; once it has left, the launches take
        the short branch again."""
        m = _model()
        rng = np.random.default_rng(3)
        pg = rng.integers(0, 64, size=5).tolist()
        ps = rng.integers(0, 64, size=7).tolist()
        kw = _GEOMETRIES[geometry]
        eng = _engine(m, **kw)
        before = counters.snapshot()
        hg = eng.add_request(pg, max_new_tokens=8)
        hsmp = eng.add_request(ps, max_new_tokens=4, do_sample=True,
                               temperature=0.7, top_k=6, seed=9)
        _run(eng, [hg, hsmp])
        d = counters.delta(before)
        assert d["serving.decode_steps"] == 7
        assert d["serving.decode.sampled_steps"] == 3
        assert np.array_equal(hg.tokens, _ref_generate(m, pg, 8))
        assert np.array_equal(hsmp.tokens, _ref_generate(
            m, ps, 4, do_sample=True, temperature=0.7, top_k=6, seed=9))
        # an all-greedy engine registers the counter and leaves it at 0
        counters.reset("serving.decode.sampled_steps")
        h = eng.add_request(pg, max_new_tokens=3)
        _run(eng, [h])
        assert counters.snapshot()["serving.decode.sampled_steps"] == 0


# ---------------------------------------------------------------------------
# One KV layout (PR 30): ``LLMEngine(model)`` is the paged engine, and the
# switch that chose between two layouts is gone.  Every case fails at the
# parent commit.
# ---------------------------------------------------------------------------
def _draft():
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    d = GPTForCausalLM(GPTConfig(vocab_size=64, hidden_size=32,
                                 num_layers=1, num_heads=4, max_seq_len=32,
                                 use_flash_attention=False))
    d.eval()
    return d


class TestOneLayout:
    @pytest.mark.parametrize("key", ["blocks_total", "blocks_live",
                                     "prefix_cache", "kv_kernel"])
    def test_engine_without_a_keyword_reports_its_block_pool(self, key):
        st = _engine(_model()).stats()
        assert key in st and "kv_layout" not in st

    def test_engine_without_a_keyword_answers_a_prefix_probe(self):
        m = _model()
        eng = _engine(m)
        prompt = list(range(1, 21))
        assert eng.prefix_probe(prompt) == (0, 0)
        _run(eng, [eng.add_request(prompt, max_new_tokens=2)])
        # a whole block of 16 and a partial one of 3 (the last prompt
        # token is always prefilled), both on the device
        assert eng.prefix_probe(prompt) == (19, 0)
        assert eng.prefix_peek(prompt) == 19

    def test_defaults_hold_every_slot_at_full_length(self):
        """``LLMEngine(model, max_slots=B)`` keeps the memory it had:
        a pool of ``B * ceil(S_max / 16) + 1`` blocks, chunks of
        ``min(S_max, 128)``, the prefix cache on."""
        from paddle_tpu.serving import LLMEngine
        eng = LLMEngine(_model(), max_slots=5)
        assert (eng.n_blocks, eng.block_size) == (5 * 2 + 1, 16)
        assert eng.prefill_chunk == 32 and eng.prefix is not None
        assert eng._pk.shape[:3] == (2, 11, 16)

    @pytest.mark.parametrize("how", ["plain", "draft_model"])
    def test_the_class_built_is_the_public_one(self, how):
        from paddle_tpu import serving
        from paddle_tpu.serving import LLMEngine, SpeculativeLLMEngine
        if how == "plain":
            assert type(LLMEngine(_model(), max_slots=2)) is LLMEngine
        else:
            eng = LLMEngine(_model(), max_slots=2, draft_model=_draft())
            assert type(eng) is SpeculativeLLMEngine
            assert SpeculativeLLMEngine.__bases__ == (LLMEngine,)
        assert not hasattr(serving, "PagedLLMEngine")
        assert "PagedLLMEngine" not in serving.__all__

    @pytest.mark.parametrize("how", ["engine", "draft_model", "positional"])
    def test_any_other_layout_is_refused_by_name(self, how):
        from paddle_tpu.serving import LLMEngine
        kw = {"draft_model": _draft()} if how == "draft_model" else {}
        with pytest.raises(ValueError, match="removed in PR 30"):
            if how == "positional":
                LLMEngine(_model(), 2, None, 64, 4, None, "slots")
            else:
                LLMEngine(_model(), kv_layout="slots", **kw)
        # the one value the benchmark's cell files still pass
        assert LLMEngine(_model(), kv_layout="paged", max_slots=2,
                         **kw).stats()["blocks_total"] > 0

    def test_fleet_takes_no_layout(self):
        from paddle_tpu.serving import ServingFleet
        with pytest.raises(TypeError, match="kv_layout"):
            ServingFleet(_model(), replicas=1, threaded=False,
                         kv_layout="paged")

    @pytest.mark.parametrize("cls,n", [("LLMEngine", 20),
                                       ("ServingFleet", 32)])
    def test_constructor_parameter_counts(self, cls, n):
        """ROADMAP D7's counts (``self`` not counted)."""
        import inspect
        from paddle_tpu import serving
        params = inspect.signature(getattr(serving, cls).__init__).parameters
        assert len(params) - 1 == n, sorted(params)

    def test_lifecycle_module_imports_neither_engine_above_it(self):
        """``serving/engine.py`` (queue, deadlines, finish, drain) names
        ``paged`` only inside the function that resolves the class's old
        address, and ``speculative`` nowhere."""
        import ast
        import inspect
        from paddle_tpu.serving import engine
        tree = ast.parse(inspect.getsource(engine))
        top = [n for n in tree.body
               if isinstance(n, (ast.Import, ast.ImportFrom))]
        named = {a.name for n in top for a in n.names} | {
            n.module for n in top if isinstance(n, ast.ImportFrom)}
        assert not named & {"paged", "speculative"}
        lazy = [n.module for n in ast.walk(tree)
                if isinstance(n, ast.ImportFrom) and n not in top]
        assert lazy == ["paged"]

    def test_lifecycle_alone_cannot_be_constructed(self):
        from paddle_tpu.serving import LLMEngine, engine
        with pytest.raises(TypeError, match="LLMEngine"):
            engine._RequestLifecycle(2, 32, 4, None, 0, 8)
        assert not hasattr(engine._RequestLifecycle, "step")
        assert engine.LLMEngine is LLMEngine      # the old address

    @pytest.mark.parametrize("phase", ["prefill", "decode"])
    def test_model_serves_through_its_paged_method_only(self, phase):
        m = _model()
        assert hasattr(m, phase + "_paged")
        assert not [n for n in dir(m) if n.startswith(phase + "_slot")]
