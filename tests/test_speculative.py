"""Speculative decoding (paddle_tpu.serving.speculative / .sampling).

The load-bearing contracts: (1) greedy speculative output is
TOKEN-IDENTICAL to the non-speculative paged engine (and therefore to
sequential ``GPT.generate``) for ANY draft model; (2) seeded sampling is
DISTRIBUTION-preserving — the emitted-token distribution matches the
non-speculative engine's (modified rejection sampling, Leviathan et al.
ICML 2023), proven by a chi-squared test over a small vocab; (3) the
draft namespace shares the target's ``BlockPool`` with exact refcount
accounting — rejection rollback releases blocks by table truncation and
a finished/cancelled/expired request leaks nothing; (4) the fleet path
threads ``draft_model=`` through replicas and loses zero requests when a
replica dies mid-draft."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.profiler import counters
from paddle_tpu.resilience import faultinject
from paddle_tpu.serving.kvcache import blocks_for_tokens

_MODELS = None


def _models():
    """(target, draft) pair on a shared 64-token vocab.  Different seeds
    and depths so drafts genuinely disagree with the target (rejections
    and rollbacks happen) — the contracts must hold for ANY draft."""
    global _MODELS
    if _MODELS is None:
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=4, max_seq_len=32,
                        use_flash_attention=False)
        paddle.seed(31)
        target = GPTForCausalLM(cfg)
        target.eval()
        dcfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                         num_heads=4, max_seq_len=32,
                         use_flash_attention=False)
        paddle.seed(7)
        draft = GPTForCausalLM(dcfg)
        draft.eval()
        _MODELS = (target, draft)
    return _MODELS


def _nb(max_slots, max_seq_len=32, block_size=4):
    """Pool size covering BOTH namespaces at every slot's worst case."""
    return 2 * max_slots * blocks_for_tokens(max_seq_len, block_size) + 1


def _spec(target, draft, **kw):
    from paddle_tpu.serving import LLMEngine
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("min_bucket", 4)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("spec_k", 3)
    kw.setdefault("n_blocks", _nb(kw["max_slots"], kw["max_seq_len"],
                                  kw["block_size"]))
    return LLMEngine(target, draft_model=draft, **kw)


def _paged(target, **kw):
    from paddle_tpu.serving import LLMEngine
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("min_bucket", 4)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk", 8)
    return LLMEngine(target, **kw)


def _ref_generate(m, prompt, max_new, **kw):
    out = np.asarray(m.generate(paddle.to_tensor(np.asarray([prompt])),
                                max_new_tokens=max_new, **kw).numpy())[0]
    return out[len(prompt):].tolist()


def _run(eng, handles, limit=400):
    n = 0
    while not all(h.is_finished for h in handles):
        eng.step()
        n += 1
        assert n < limit, "engine did not converge"
    return n


class TestResidualSample:
    """Satellite unit tests for serving.sampling.residual_sample."""

    def _draw(self, p, q, n=4000, seed=0):
        import jax
        from paddle_tpu.serving.sampling import residual_sample
        keys = jax.random.split(jax.random.key(seed), n)
        toks = jax.vmap(lambda k: residual_sample(p, q, k))(keys)
        return np.asarray(toks)

    def test_matches_normalized_residual(self):
        import jax.numpy as jnp
        p = jnp.asarray([0.5, 0.3, 0.15, 0.05])
        q = jnp.asarray([0.1, 0.6, 0.25, 0.05])
        res = np.maximum(np.asarray(p) - np.asarray(q), 0.0)
        want = res / res.sum()
        toks = self._draw(p, q)
        freq = np.bincount(toks, minlength=4) / len(toks)
        # 4000 draws: binomial std <= 0.008 per bin — 0.03 is ~4 sigma
        assert np.abs(freq - want).max() < 0.03, (freq, want)

    def test_zero_residual_support_never_sampled(self):
        import jax.numpy as jnp
        p = jnp.asarray([0.5, 0.3, 0.15, 0.05])
        q = jnp.asarray([0.1, 0.6, 0.25, 0.05])
        toks = self._draw(p, q)
        # q >= p at indices 1, 2, 3: the residual there is exactly zero
        assert set(np.unique(toks)) == {0}

    def test_degenerate_equal_distributions_fall_back_to_p(self):
        import jax.numpy as jnp
        p = jnp.asarray([0.7, 0.2, 0.1, 0.0])
        toks = self._draw(p, p)          # residual mass exactly 0
        freq = np.bincount(toks, minlength=4) / len(toks)
        assert np.abs(freq - np.asarray(p)).max() < 0.03, freq
        assert 3 not in np.unique(toks)  # p(3)=0 stays unsampleable

    def test_batched_rows(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.serving.sampling import residual_sample
        p = jnp.asarray([[0.9, 0.1, 0.0], [0.0, 0.2, 0.8]])
        q = jnp.asarray([[0.1, 0.9, 0.0], [0.0, 0.8, 0.2]])
        keys = jax.random.split(jax.random.key(1), 2)
        toks = np.asarray(jax.vmap(residual_sample)(p, q, keys))
        assert toks[0] == 0 and toks[1] == 2   # only positive-residual bins


class TestGreedyIdentity:
    def test_token_identical_to_paged_engine_and_generate(self):
        target, draft = _models()
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 64, size=n).tolist() for n in (5, 3, 9)]
        base = _paged(target)
        bh = [base.add_request(p, max_new_tokens=10) for p in prompts]
        _run(base, bh)
        spec = _spec(target, draft)
        sh = [spec.add_request(p, max_new_tokens=10) for p in prompts]
        _run(spec, sh)
        for b, s, p in zip(bh, sh, prompts):
            assert s.tokens == b.tokens, (s.tokens, b.tokens)
            assert s.tokens == _ref_generate(target, p, 10)
            assert s.finish_reason == b.finish_reason

    def test_identity_for_every_spec_k(self):
        """The acceptance logic is K-invariant: any draft depth emits the
        target's own greedy chain."""
        target, draft = _models()
        prompt = [3, 1, 4, 1, 5]
        ref = _ref_generate(target, prompt, 8)
        for k in (1, 2, 4):
            spec = _spec(target, draft, spec_k=k, max_slots=2)
            h = spec.add_request(prompt, max_new_tokens=8)
            _run(spec, [h])
            assert h.tokens == ref, (k, h.tokens, ref)

    def test_eos_and_length_finish_reasons(self):
        target, draft = _models()
        prompt = [2, 7, 2]
        ref = _ref_generate(target, prompt, 12)
        eos = ref[3]
        # eos mid-draft-block: the engine must stop emitting at the eos
        # token even when the verify round accepted tokens past it — same
        # truncation point as the non-speculative engine
        base = _paged(target, max_slots=2)
        b_eos = base.add_request(prompt, max_new_tokens=12,
                                 eos_token_id=eos)
        _run(base, [b_eos])
        spec = _spec(target, draft, max_slots=2)
        h_eos = spec.add_request(prompt, max_new_tokens=12, eos_token_id=eos)
        h_len = spec.add_request(prompt, max_new_tokens=12)
        _run(spec, [h_eos, h_len])
        assert h_len.tokens == ref and h_len.finish_reason == "length"
        assert h_eos.tokens == b_eos.tokens
        assert h_eos.finish_reason == b_eos.finish_reason == "eos"
        assert len(h_eos.tokens) < 12 and h_eos.tokens[-1] == eos


class TestDistributionPreservation:
    def test_chi_squared_small_vocab(self):
        """Modified rejection sampling leaves the output distribution
        equal to the target's own: the emitted-token histogram over many
        seeded requests must be chi-squared-compatible with the
        non-speculative paged engine's over the same seeds.  Fully
        deterministic (fixed seeds on both sides)."""
        target, draft = _models()
        prompt = [5, 9, 2, 6]
        kw = dict(max_new_tokens=4, do_sample=True, temperature=1.1,
                  top_k=8)
        n = 120

        def harvest(eng):
            counts = np.zeros(64, np.int64)
            pending = list(range(n))
            live = []
            while pending or live:
                while pending and len(live) < 8:
                    live.append(eng.add_request(
                        prompt, seed=1000 + pending.pop(0), **kw))
                eng.step()
                done = [h for h in live if h.is_finished]
                live = [h for h in live if not h.is_finished]
                for h in done:
                    for t in h.tokens:
                        counts[t] += 1
            return counts

        o1 = harvest(_paged(target, max_slots=4))
        o2 = harvest(_spec(target, draft, max_slots=4, spec_k=2))
        assert o1.sum() == o2.sum() == n * 4
        both = o1 + o2
        live_bins = both > 0
        # two-sample chi-squared: sum (o1-o2)^2/(o1+o2) ~ chi2(df)
        stat = float((((o1 - o2) ** 2)[live_bins]
                      / both[live_bins]).sum())
        df = int(live_bins.sum()) - 1
        # p=0.001 critical value for df<=63 is < df + 3.1*sqrt(2*df) + 12
        crit = df + 3.1 * np.sqrt(2 * df) + 12
        assert stat < crit, (stat, crit, df)

    def test_truncated_round_final_token_samples_from_target(self):
        """Budget exhaustion is NOT rejection: a row whose round is
        truncated below K+1 considered proposals (nv=1 here — the final
        token of every sampled request, and draft-starved rows) must draw
        its token from the target distribution ``p``, not from the
        residual ``norm(max(0, p - q))``.  Regression: the old acceptance
        folded ``j < nv-1`` into the accept bit, which read as a
        rejection and made every token where ``q >= p`` unsampleable at
        truncated positions."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.serving.speculative import _acceptance

        B, K1, V = 4096, 4, 4
        p0 = np.asarray([0.5, 0.3, 0.15, 0.05])
        q0 = np.asarray([0.9, 0.05, 0.03, 0.02])   # q > p at token 0
        logits = jnp.broadcast_to(jnp.log(jnp.asarray(p0, jnp.float32)),
                                  (B, K1, V))
        q = jnp.broadcast_to(jnp.asarray(q0, jnp.float32),
                             (B, K1 - 1, V))
        toks = jnp.zeros((B, K1), jnp.int32)
        nv = jnp.ones(B, jnp.int32)               # zero considered drafts
        keys_data = jax.random.key_data(
            jax.random.split(jax.random.key(11), B))
        emit, n_emit, _ = _acceptance(
            logits, toks, q, nv, keys_data,
            jnp.ones(B, bool), jnp.ones(B, jnp.float32),
            jnp.zeros(B, jnp.int32), jnp.ones(B, jnp.float32))
        assert np.all(np.asarray(n_emit) == 1)
        freq = np.bincount(np.asarray(emit)[:, 0], minlength=V) / B
        # 4096 draws: binomial std <= 0.008 per bin — 0.04 is ~5 sigma.
        # Under the residual bug freq[0] would be ~0 (residual mass at
        # token 0 is exactly zero), not ~0.5.
        assert np.abs(freq - p0).max() < 0.04, (freq, p0)

    def test_sampled_run_completes_and_counts_balance(self):
        target, draft = _models()
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, 64, size=n).tolist() for n in (4, 7)]
        spec = _spec(target, draft)
        before = counters.snapshot()
        hs = [spec.add_request(p, max_new_tokens=8, seed=50 + i,
                               do_sample=True, temperature=0.8, top_k=8,
                               top_p=0.9)
              for i, p in enumerate(prompts)]
        _run(spec, hs)
        d = counters.delta(before)
        assert all(len(h.tokens) == 8 for h in hs)
        assert all(0 <= t < 64 for h in hs for t in h.tokens)
        assert (d.get("serving.spec.accepted", 0)
                + d.get("serving.spec.rejected", 0)
                == d.get("serving.spec.drafted", 0) > 0)


class TestKVRollbackAccounting:
    def test_no_block_leak_after_rejections(self):
        """Rejection rollback truncates draft block tables and releases
        refcounts; with the prefix cache off a drained engine must own
        ZERO pool blocks — target and draft namespaces both."""
        target, draft = _models()
        spec = _spec(target, draft, prefix_cache=False)
        rng = np.random.default_rng(4)
        before = counters.snapshot()
        for _ in range(2):   # two waves reuse the same freed blocks
            hs = [spec.add_request(rng.integers(0, 64, size=n).tolist(),
                                   max_new_tokens=10) for n in (5, 9, 3)]
            _run(spec, hs)
        d = counters.delta(before)
        assert spec.pool.used_blocks == 0
        assert spec.pool.free_blocks == spec.pool.capacity
        # the mismatched draft really did get rolled back along the way
        assert d.get("serving.spec.rejected", 0) > 0
        assert d.get("serving.spec.rollback_blocks", 0) >= 0

    def test_draft_blocks_not_donated_to_prefix_cache(self):
        """With the prefix cache ON, finished TARGET blocks may stay
        resident in the radix tree but draft blocks must all be freed:
        the draft namespace is per-request scratch, never shared."""
        target, draft = _models()
        spec = _spec(target, draft, max_slots=2)
        h = spec.add_request([1, 2, 3, 4, 5, 6], max_new_tokens=8)
        _run(spec, [h])
        # every surviving reference is target-side: the prefix tree can
        # hold at most the target blocks of the one finished sequence
        max_target = blocks_for_tokens(6 + 8, spec.pool.block_size)
        assert spec.pool.used_blocks <= max_target
        assert all(t is None for t in spec._dslot_blocks)
        assert not spec._dbt.any()

    def test_missing_draft_table_degrades_to_plain_decode(self):
        """A running row whose draft table is gone must be downgraded to
        ``nv=1`` (``serving.spec.draft_starved``) instead of verifying
        proposals drafted against the trash block — the round degrades to
        plain decode and the greedy chain stays token-identical."""
        target, draft = _models()
        spec = _spec(target, draft, max_slots=2, prefix_cache=False)
        prompt = [1, 2, 3, 4, 5]
        ref = _ref_generate(target, prompt, 10)
        h = spec.add_request(prompt, max_new_tokens=10)
        while not any(r is not None and r.state == "running"
                      for r in spec._slots):
            spec.step()
        s = next(i for i, r in enumerate(spec._slots)
                 if r is not None and r.state == "running")
        with spec._cond:
            dbl = spec._dslot_blocks[s]
            spec._dslot_blocks[s] = None
            spec._dbt[s] = 0
            for b in dbl:
                spec.pool.release(b)
        before = counters.snapshot()
        _run(spec, [h])
        d = counters.delta(before)
        assert d.get("serving.spec.draft_starved", 0) > 0
        assert h.tokens == ref and h.finish_reason == "length"
        assert spec.pool.used_blocks == 0

    def test_pool_exhaustion_defers_not_crashes(self):
        """A pool too small for two doubled-namespace residents admits
        one request at a time — backpressure, not a crash."""
        target, draft = _models()
        spec = _spec(target, draft, max_slots=2, prefix_cache=False,
                     n_blocks=2 * blocks_for_tokens(20, 4) + 3)
        hs = [spec.add_request([7] * 5, max_new_tokens=12),
              spec.add_request([9] * 5, max_new_tokens=12)]
        _run(spec, hs)
        assert all(h.finish_reason == "length" for h in hs)
        assert all(len(h.tokens) == 12 for h in hs)
        assert spec.pool.used_blocks == 0


class TestCancellationAndDeadline:
    def test_mid_draft_cancellation_releases_both_namespaces(self):
        target, draft = _models()
        spec = _spec(target, draft, max_slots=2, prefix_cache=False)
        h_live = spec.add_request([1, 2, 3], max_new_tokens=10)
        h_dead = spec.add_request([4, 5, 6, 7, 8], max_new_tokens=20)
        for _ in range(3):   # past prefill, into the draft/verify rounds
            spec.step()
        h_dead.cancel()
        _run(spec, [h_live, h_dead])
        assert h_dead.finish_reason == "cancelled"
        assert len(h_dead.tokens) < 20
        assert h_live.finish_reason == "length"
        assert h_live.tokens == _ref_generate(target, [1, 2, 3], 10)
        assert spec.pool.used_blocks == 0

    def test_deadline_mid_decode(self):
        import time
        target, draft = _models()
        spec = _spec(target, draft, max_slots=2, prefix_cache=False)
        h = spec.add_request([3, 1, 4], max_new_tokens=25, deadline_s=0.01)
        spec.step()          # admit + begin prefill
        time.sleep(0.05)     # budget lapses mid-flight
        _run(spec, [h])
        assert h.finish_reason == "deadline"
        assert spec.pool.used_blocks == 0


class TestAcceptanceCounters:
    def test_round_economics_and_stats(self):
        target, draft = _models()
        spec = _spec(target, draft, spec_k=3, max_slots=2)
        before = counters.snapshot()
        hs = [spec.add_request([2, 4, 6], max_new_tokens=9),
              spec.add_request([1, 3, 5, 7], max_new_tokens=9)]
        _run(spec, hs)
        d = counters.delta(before)
        drafted = d.get("serving.spec.drafted", 0)
        assert drafted > 0
        assert (d.get("serving.spec.accepted", 0)
                + d.get("serving.spec.rejected", 0)) == drafted
        # K+1 draft launches + ONE verify launch per scheduler round
        assert d.get("serving.spec.draft_steps", 0) == \
            4 * d.get("serving.spec.verify_steps", 0) > 0
        # satellite fix: decode tokens/s accounting counts EMITTED tokens
        # (variable per round), not dispatches — so decode_tokens must be
        # everything emitted past the prefill-produced first token, and
        # exceed the round count when drafts land
        decoded = sum(len(h.tokens) - 1 for h in hs)
        assert d.get("serving.decode_tokens", 0) == decoded
        assert d.get("serving.decode_steps", 0) < decoded
        st = spec.stats()
        assert st["speculative"] is True and st["spec_k"] == 3
        # per-engine tally == this run's global movement (sole spec
        # engine inside the delta window)
        assert st["spec_drafted"] == drafted
        assert 0.0 <= st["spec_acceptance_ema"] <= 1.0
        assert st["spec_yield_ema"] > 0
        assert st["decode_tps_ema"] > 0
        assert 0.0 <= counters.get("serving.spec.acceptance") <= 1.0

    def test_constructor_validation(self):
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        target, draft = _models()
        with pytest.raises(ValueError, match="spec_k"):
            _spec(target, draft, spec_k=0)
        paddle.seed(5)
        other = GPTForCausalLM(GPTConfig(
            vocab_size=32, hidden_size=32, num_layers=1, num_heads=4,
            max_seq_len=32, use_flash_attention=False))
        other.eval()
        with pytest.raises(ValueError, match="vocab"):
            _spec(target, other)


@pytest.mark.slow
class TestFleetChaos:
    def _fleet(self, target, draft, **kw):
        from paddle_tpu.serving import ServingFleet
        kw.setdefault("replicas", 2)
        kw.setdefault("threaded", False)
        kw.setdefault("max_slots", 2)
        kw.setdefault("max_seq_len", 32)
        kw.setdefault("min_bucket", 4)
        kw.setdefault("heartbeat_timeout_s", 30.0)
        return ServingFleet(target, draft_model=draft, spec_k=2,
                            block_size=4,
                            prefill_chunk=8, n_blocks=_nb(kw["max_slots"]),
                            **kw)

    def test_replica_kill_mid_draft_loses_nothing(self):
        """The durability contract survives speculation: a replica crash
        mid-draft replays the request onto a survivor and the delivered
        greedy tokens still match the sequential reference."""
        target, draft = _models()
        rng = np.random.default_rng(6)
        prompts = [rng.integers(0, 64, size=n).tolist() for n in (5, 3)]
        refs = [_ref_generate(target, p, 8) for p in prompts]
        fleet = self._fleet(target, draft)
        before = counters.snapshot()
        hs = [fleet.submit(p, max_new_tokens=8) for p in prompts]
        with faultinject.fault_schedule(f"replica_crash@{hs[0].rid}"):
            fleet.join(hs)
        d = counters.delta(before)
        for h, r in zip(hs, refs):
            assert list(h.tokens) == r, (list(h.tokens), r)
            assert h.finish_reason == "length"
        assert d.get("serving.fleet.lost", 0) == 0
        assert d.get("serving.fleet.respawns", 0) == 1
        assert d.get("serving.fleet.retried", 0) == 1
        # the fleet view rolls up speculative telemetry from the replicas
        st = fleet.stats()
        assert st["spec"]["spec_k"] == 2
        assert st["spec"]["drafted"] > 0
        assert 0.0 <= st["spec"]["acceptance"] <= 1.0
        assert 0.0 <= counters.get("serving.fleet.spec_acceptance") <= 1.0
        fleet.drain()

    def test_no_fault_fleet_identity(self):
        target, draft = _models()
        rng = np.random.default_rng(8)
        prompts = [rng.integers(0, 64, size=n).tolist() for n in (4, 6, 9)]
        refs = [_ref_generate(target, p, 6) for p in prompts]
        fleet = self._fleet(target, draft)
        hs = [fleet.submit(p, max_new_tokens=6) for p in prompts]
        fleet.join(hs)
        for h, r in zip(hs, refs):
            assert list(h.tokens) == r
        fleet.drain()
        assert counters.get("serving.fleet.lost") == 0
