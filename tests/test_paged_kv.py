"""Paged KV-cache subsystem (paddle_tpu.serving.kvcache / .paged).

The load-bearing contracts: (1) the engine is TOKEN-IDENTICAL to
sequential GPT.generate — block tables,
prefix sharing, copy-on-write, and chunked prefill must be invisible in
the tokens; (2) block accounting never tears — all-or-nothing
reservation, refcounted sharing, LRU eviction only of unreferenced
blocks; (3) exhaustion (real or injected) defers admission and surfaces
as backpressure, never a crash.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.profiler import counters
from paddle_tpu.resilience import faultinject
from paddle_tpu.serving.kvcache import (TRASH_BLOCK, BlockPool,
                                        BlockPoolExhausted, HostKVTier,
                                        PrefixCache, blocks_for_tokens)

_MODEL = None


def _model():
    global _MODEL
    if _MODEL is None:
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=4, max_seq_len=32,
                        use_flash_attention=False)
        paddle.seed(31)
        _MODEL = GPTForCausalLM(cfg)
        _MODEL.eval()
    return _MODEL


def _paged(m, **kw):
    from paddle_tpu.serving import LLMEngine
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("min_bucket", 4)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk", 8)
    return LLMEngine(m, **kw)


def _ref_generate(m, prompt, max_new, **kw):
    out = np.asarray(m.generate(paddle.to_tensor(np.asarray([prompt])),
                                max_new_tokens=max_new, **kw).numpy())[0]
    return out[len(prompt):].tolist()


def _run(eng, handles, limit=300):
    n = 0
    while not all(h.is_finished for h in handles):
        eng.step()
        n += 1
        assert n < limit, "engine did not converge"
    return n


class TestBlockPool:
    def test_alloc_free_refcount(self):
        pool = BlockPool(5, 4)
        assert pool.capacity == 4 and pool.free_blocks == 4
        a = pool.alloc()
        assert a != TRASH_BLOCK and pool.ref(a) == 1
        pool.retain(a)
        assert pool.ref(a) == 2
        assert pool.release(a) is False       # still held
        assert pool.release(a) is True        # freed
        assert pool.free_blocks == 4

    def test_alloc_n_all_or_nothing(self):
        pool = BlockPool(5, 4)
        got = pool.alloc_n(3)
        assert len(got) == 3 and pool.free_blocks == 1
        with pytest.raises(BlockPoolExhausted) as ei:
            pool.alloc_n(2)
        assert ei.value.needed == 2 and ei.value.free == 1
        assert pool.free_blocks == 1          # nothing torn off

    def test_trash_block_reserved(self):
        pool = BlockPool(3, 4)
        blocks = pool.alloc_n(2)
        assert TRASH_BLOCK not in blocks
        with pytest.raises(BlockPoolExhausted):
            pool.alloc()
        with pytest.raises(ValueError):
            pool.retain(TRASH_BLOCK)

    def test_release_free_block_raises(self):
        pool = BlockPool(3, 4)
        with pytest.raises(ValueError):
            pool.release(1)

    def test_blocks_for_tokens(self):
        assert blocks_for_tokens(1, 4) == 1
        assert blocks_for_tokens(4, 4) == 1
        assert blocks_for_tokens(5, 4) == 2
        assert blocks_for_tokens(16, 4) == 4


class TestPrefixCache:
    def test_match_full_and_partial(self):
        pool = BlockPool(9, 4)
        cache = PrefixCache(pool)
        seq = list(range(10))                       # 2 full blocks + 2 rest
        blocks = pool.alloc_n(3)
        assert cache.insert(seq, blocks) == 3
        for b in blocks:
            pool.release(b)                         # donor refs dropped
        assert all(pool.ref(b) == 1 for b in blocks)

        # full-block hit: first 8 tokens shared, partial [8,9] usable
        got, cached, pn, p = cache.match(seq + [42], limit=10)
        assert got == blocks[:2] and cached == 8
        assert pn is not None and pn.block == blocks[2] and p == 2
        assert pool.ref(blocks[0]) == 2             # retained for caller
        assert pool.ref(pn.block) == 2              # partial retained too
        for b in got:
            pool.release(b)
        pool.release(pn.block)

        # limit clips the partial
        got, cached, pn, p = cache.match(seq, limit=9)
        assert cached == 8 and p == 1
        for b in got:
            pool.release(b)
        pool.release(pn.block)

        # divergent second block: only the first is shared
        div = seq[:4] + [63, 62, 61, 60]
        got, cached, pn, p = cache.match(div, limit=8)
        assert got == blocks[:1] and cached == 4 and pn is None
        for b in got:
            pool.release(b)

    def test_partial_survives_repeated_cow_matches(self):
        """Regression: ``match`` retains the partial block for the
        caller, so the COW-side release (``_reserve`` drops it after the
        copy) does NOT strip the tree's own retain.  Without the
        caller-side retain the first COW adoption freed the partial's
        block under a live tree node — the next sharer matched a
        dangling node over a freed (or reused) block and the release
        blew up with "release of free block"."""
        pool = BlockPool(9, 4)
        cache = PrefixCache(pool)
        seq = list(range(6))                        # 1 full block + 2 rest
        blocks = pool.alloc_n(2)
        cache.insert(seq, blocks)
        for b in blocks:
            pool.release(b)
        for _ in range(3):                          # every sharer COWs
            got, cached, pn, p = cache.match(seq, limit=5)
            assert cached == 4 and pn is not None and p == 1
            for b in got:
                pool.release(b)                     # admission bookkeeping
            pool.release(pn.block)                  # post-COW release
            assert pool.ref(pn.block) == 1          # tree retain intact
        cache.clear()
        assert pool.free_blocks == pool.capacity

    def test_peek_is_read_only(self):
        pool = BlockPool(9, 4)
        cache = PrefixCache(pool)
        seq = list(range(10))
        blocks = pool.alloc_n(3)
        cache.insert(seq, blocks)
        for b in blocks:
            pool.release(b)
        assert cache.peek(seq, limit=10) == 10
        assert cache.peek(seq, limit=9) == 9
        assert cache.peek([59] * 10, limit=10) == 0
        assert all(pool.ref(b) == 1 for b in blocks)   # no refs taken

    def test_evict_lru_unreferenced_only(self):
        pool = BlockPool(9, 4)
        cache = PrefixCache(pool)
        s1, s2 = [1] * 4, [2] * 4
        b1 = pool.alloc_n(1)
        cache.insert(s1, b1)
        pool.release(b1[0])
        b2 = pool.alloc_n(1)
        cache.insert(s2, b2)
        pool.release(b2[0])
        # touch s1 so s2 is LRU
        got, *_ = cache.match(s1 + [0], limit=5)
        assert cache.evict(1) == 1                  # evicts s2, not held s1
        assert pool.ref(b2[0]) == 0
        assert cache.peek(s2, limit=4) == 0
        assert cache.peek(s1 + [0], limit=5) == 4   # s1 survives (referenced)
        for b in got:
            pool.release(b)

    def test_evict_parent_after_child(self):
        pool = BlockPool(9, 4)
        cache = PrefixCache(pool)
        seq = list(range(8))                        # chain of 2 full blocks
        blocks = pool.alloc_n(2)
        cache.insert(seq, blocks)
        for b in blocks:
            pool.release(b)
        assert cache.evict(2) == 2                  # leaf first, then parent
        assert cache.nodes == 0
        assert pool.free_blocks == pool.capacity

    def test_clear_releases_everything(self):
        pool = BlockPool(9, 4)
        cache = PrefixCache(pool)
        blocks = pool.alloc_n(3)
        cache.insert(list(range(10)), blocks)
        for b in blocks:
            pool.release(b)
        cache.clear()
        assert pool.free_blocks == pool.capacity and cache.nodes == 0


class TestPagedIdentity:
    def test_greedy_vs_generate_at_two_geometries(self):
        """The defaults (a prompt in one block and one chunk) and small
        blocks and chunks both serve what ``generate`` does."""
        m = _model()
        from paddle_tpu.serving import LLMEngine
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 64, size=n).tolist()
                   for n in (5, 3, 9, 6, 11)]
        refs = [_ref_generate(m, p, 6) for p in prompts]
        whole = LLMEngine(m, max_slots=3, max_seq_len=32, min_bucket=4)
        hs = [whole.add_request(p, max_new_tokens=6, seed=i)
              for i, p in enumerate(prompts)]
        _run(whole, hs)
        paged = _paged(m)
        hp = [paged.add_request(p, max_new_tokens=6, seed=i)
              for i, p in enumerate(prompts)]
        _run(paged, hp)
        for h, hq, r in zip(hs, hp, refs):
            assert h.tokens == r
            assert hq.tokens == r

    def test_sampled_identity(self):
        m = _model()
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, 64, size=n).tolist() for n in (7, 4, 10)]
        kw = dict(do_sample=True, temperature=0.8, top_k=8, top_p=0.9)
        refs = [_ref_generate(m, p, 6, seed=100 + i, **kw)
                for i, p in enumerate(prompts)]
        eng = _paged(m)
        hs = [eng.add_request(p, max_new_tokens=6, seed=100 + i, **kw)
              for i, p in enumerate(prompts)]
        _run(eng, hs)
        for h, r in zip(hs, refs):
            assert h.tokens == r

    def test_chunked_prefill_identity(self):
        m = _model()
        rng = np.random.default_rng(4)
        long_p = rng.integers(0, 64, size=26).tolist()
        eng = _paged(m, prefill_chunk=8)            # 26 tokens -> 4 chunks
        before = counters.snapshot().get("serving.kv.prefill_chunks", 0)
        h = eng.add_request(long_p, max_new_tokens=5, seed=9)
        _run(eng, [h])
        chunks = counters.snapshot().get("serving.kv.prefill_chunks",
                                         0) - before
        assert chunks == 4
        assert h.tokens == _ref_generate(m, long_p, 5)

    def test_shared_prefix_hit_identity(self):
        m = _model()
        rng = np.random.default_rng(5)
        sys_p = rng.integers(0, 64, size=12).tolist()
        eng = _paged(m)
        tails = [rng.integers(0, 64, size=4).tolist() for _ in range(3)]
        first = eng.add_request(sys_p + tails[0], max_new_tokens=4, seed=0)
        _run(eng, [first])
        assert first.tokens == _ref_generate(m, sys_p + tails[0], 4)
        st0 = eng.stats()
        hs = [eng.add_request(sys_p + t, max_new_tokens=4, seed=1 + i)
              for i, t in enumerate(tails[1:])]
        _run(eng, hs)
        st = eng.stats()
        assert st["prefix_hits"] - st0["prefix_hits"] == 2
        assert st["prefix_hit_tokens"] > st0["prefix_hit_tokens"]
        for h, t in zip(hs, tails[1:]):
            assert h.tokens == _ref_generate(m, sys_p + t, 4)

    def test_cow_partial_block_identity(self):
        m = _model()
        rng = np.random.default_rng(6)
        p1 = rng.integers(0, 64, size=10).tolist()
        eng = _paged(m)
        h1 = eng.add_request(p1, max_new_tokens=6, seed=2)
        _run(eng, [h1])
        # the finished sequence cached 15 KV positions: 3 full blocks + a
        # 3-token partial; extending past it forces a copy-on-write
        seq1 = p1 + h1.tokens
        p2 = seq1[:15] + rng.integers(0, 64, size=4).tolist()
        h2 = eng.add_request(p2, max_new_tokens=5, seed=3)
        _run(eng, [h2])
        st = eng.stats()
        assert st["cow_copies"] >= 1
        assert h2.tokens == _ref_generate(m, p2, 5)

    def test_repeated_cow_adoptions_of_one_partial(self):
        """Regression (engine level): several requests COW-adopting the
        SAME cached partial, one after another.  Each adoption must
        leave the tree's partial node alive over a still-referenced
        block; pre-fix the first COW freed it and the next admission
        crashed the engine on "release of free block"."""
        m = _model()
        rng = np.random.default_rng(8)
        eng = _paged(m)
        p1 = rng.integers(0, 64, size=10).tolist()
        h1 = eng.add_request(p1, max_new_tokens=6, seed=2)
        _run(eng, [h1])
        seq1 = p1 + h1.tokens
        for i in range(3):
            p2 = seq1[:15] + rng.integers(0, 64, size=4).tolist()
            h2 = eng.add_request(p2, max_new_tokens=4, seed=10 + i)
            _run(eng, [h2])
            assert h2.tokens == _ref_generate(m, p2, 4)
        assert eng.stats()["cow_copies"] >= 3
        pool = eng.pool
        live = sum(1 for b in range(1, len(pool._ref))
                   if pool._ref[b] > 0)
        assert len(pool._free) + live == pool.capacity


class TestSamplingTailPredicate:
    def test_parked_sampling_row_keeps_greedy_batch_on_short_branch(self):
        """A sampling request that holds a slot WITHOUT running (parked
        after prefill for migration) keeps its flag, on the host and in
        the launch's operand, but not its ``running`` bit, and the decode
        program masks the one by the other: the greedy rows decoding
        beside it never take the sampling tail's long branch
        (``serving.decode.sampled_steps`` stays 0), and their tokens are
        their own."""
        m = _model()
        rng = np.random.default_rng(40)
        ps = rng.integers(0, 64, size=6).tolist()
        pg = [rng.integers(0, 64, size=n).tolist() for n in (5, 7)]
        eng = _paged(m)
        before = counters.snapshot()
        held = eng.add_request(ps, max_new_tokens=6, do_sample=True,
                               top_p=0.9, seed=3, hold_after_prefill=True)
        while held.state != "held":
            eng.step()
        dec, uploads = eng._pdecode(), []

        def shim(*args):   # (w, pk, pv, bt, tok, pos, running, keys, do_sample, ..)
            uploads.append((np.asarray(args[8]).copy(),
                            np.asarray(args[6]).copy()))
            return dec(*args)
        eng._pdecode_jit = shim
        hs = [eng.add_request(p, max_new_tokens=5) for p in pg]
        _run(eng, hs)
        d = counters.delta(before)
        assert d["serving.decode_steps"] == len(uploads) == 4
        assert d.get("serving.decode.sampled_steps", 0) == 0
        assert "serving.decode.sampled_steps" in counters.snapshot()
        assert held.state == "held" and eng._dosample[held.slot]
        assert uploads[0][0].dtype == uploads[0][1].dtype == np.bool_
        assert all(ds[held.slot] and not run[held.slot]
                   for ds, run in uploads)
        assert not any((ds & run).any() for ds, run in uploads)
        for h, p in zip(hs, pg):
            assert list(h.tokens) == _ref_generate(m, p, 5)
        held.cancel()
        eng.step()
        assert held.is_finished and not eng._dosample.any()


class TestChunkedPrefillInterleaving:
    def test_decode_not_starved_by_long_prefill(self):
        m = _model()
        rng = np.random.default_rng(7)
        eng = _paged(m, prefill_chunk=8, prefix_cache=False)
        short = rng.integers(0, 64, size=4).tolist()
        long_p = rng.integers(0, 64, size=24).tolist()
        h_short = eng.add_request(short, max_new_tokens=10, seed=1)
        eng.step()                                   # short is now decoding
        h_long = eng.add_request(long_p, max_new_tokens=3, seed=2)
        # while the long prompt prefills chunk by chunk, the short request
        # must receive one token per step — chunked prefill never starves
        # inter-token latency
        while h_long.state != "running" and not h_long.is_finished:
            before = len(h_short.tokens)
            eng.step()
            if not h_short.is_finished:
                assert len(h_short.tokens) == before + 1
        _run(eng, [h_short, h_long])
        assert h_short.tokens == _ref_generate(m, short, 10)
        assert h_long.tokens == _ref_generate(m, long_p, 3)


class TestDeadlineAndRelease:
    def test_deadline_expiry_mid_chunked_prefill(self):
        m = _model()
        rng = np.random.default_rng(8)
        eng = _paged(m, prefill_chunk=8)
        long_p = rng.integers(0, 64, size=24).tolist()
        h = eng.add_request(long_p, max_new_tokens=4, seed=1,
                            deadline_s=0.0)
        eng.step()                                   # sweep reaps it
        assert h.is_finished and h.finish_reason == "deadline"
        st = eng.stats()
        assert st["blocks_used"] == 0                # every block released
        assert st["blocks_free"] == st["blocks_total"]

    def test_cancel_mid_prefill_releases_blocks(self):
        m = _model()
        rng = np.random.default_rng(9)
        eng = _paged(m, prefill_chunk=8, prefix_cache=False)
        h = eng.add_request(rng.integers(0, 64, size=24).tolist(),
                            max_new_tokens=4, seed=1)
        eng.step()                                   # admitted, 1 chunk in
        assert h.state == "prefilling"
        h.cancel()
        eng.step()
        assert h.finish_reason == "cancelled"
        assert eng.stats()["blocks_used"] == 0


class TestExhaustionBackpressure:
    def test_impossible_request_rejected(self):
        m = _model()
        eng = _paged(m, n_blocks=3)                  # 2 usable blocks
        with pytest.raises(ValueError):
            eng.add_request(list(range(12)), max_new_tokens=4)

    def test_real_exhaustion_defers_and_recovers(self):
        m = _model()
        # pool fits ~1 request at a time: 6 usable blocks of 4 tokens
        eng = _paged(m, n_blocks=7, max_slots=2, prefix_cache=False)
        p = list(range(10))
        h1 = eng.add_request(p, max_new_tokens=6, seed=0)      # 4 blocks
        h2 = eng.add_request(p[::-1], max_new_tokens=6, seed=1)
        _run(eng, [h1, h2])
        st = eng.stats()
        assert st["pool_exhausted"] >= 1             # h2 had to wait
        assert h1.tokens == _ref_generate(m, p, 6)
        assert h2.tokens == _ref_generate(m, p[::-1], 6)

    def test_injected_exhaustion_is_deterministic(self):
        m = _model()
        eng = _paged(m)
        h0 = eng.add_request([1, 2, 3], max_new_tokens=3, seed=0)
        rid = h0.rid + 1
        with faultinject.fault_schedule(f"kv_pool_exhausted@{rid}"):
            h1 = eng.add_request([4, 5, 6], max_new_tokens=3, seed=1)
            _run(eng, [h0, h1])
            assert ("kv_pool_exhausted", rid) in faultinject.fired
        assert h1.finish_reason == "length"          # deferred, not dropped
        assert h1.tokens == _ref_generate(m, [4, 5, 6], 3)
        assert eng.stats()["pool_exhausted"] == 1

    def test_backpressure_surfaces_when_queue_fills(self):
        from paddle_tpu.serving import EngineBackpressure
        m = _model()
        eng = _paged(m, max_slots=1, queue_size=1, n_blocks=9,
                     prefix_cache=False)
        h1 = eng.add_request(list(range(10)), max_new_tokens=6, seed=0)
        eng.step()                                   # h1 occupies the pool
        h2 = eng.add_request(list(range(8)), max_new_tokens=6, seed=1,
                             block=False)            # queued
        with pytest.raises(EngineBackpressure):
            eng.add_request(list(range(6)), max_new_tokens=4, seed=2,
                            block=False)             # queue full
        _run(eng, [h1, h2])
        assert h1.finish_reason == "length"
        assert h2.finish_reason == "length"


class TestRouterPrefixAware:
    def test_pick_prefers_warm_prefix(self):
        m = _model()
        from paddle_tpu.serving import Replica, Router
        rng = np.random.default_rng(10)
        sys_p = rng.integers(0, 64, size=12).tolist()
        warm = _paged(m)
        cold = _paged(m)
        h = warm.add_request(sys_p + [1, 2], max_new_tokens=4, seed=0)
        _run(warm, [h])
        reps = [Replica(0, cold), Replica(1, warm)]
        before = counters.snapshot().get("serving.fleet.prefix_routed", 0)
        picked = Router().pick(reps, est_tokens=16, prompt=sys_p + [3, 4])
        assert picked.engine is warm                 # despite higher idx
        got = counters.snapshot().get("serving.fleet.prefix_routed", 0)
        assert got == before + 1
        # without a prompt the tie breaks to the lowest index
        assert Router().pick(reps, est_tokens=16).engine is cold


class TestFleetPagedChaos:
    def test_fleet_kv_stats_and_injected_exhaustion(self):
        m = _model()
        from paddle_tpu.serving import ServingFleet
        rng = np.random.default_rng(11)
        sys_p = rng.integers(0, 64, size=8).tolist()
        with ServingFleet(m, replicas=2, max_slots=2, max_seq_len=32,
                          min_bucket=4, threaded=False,
                          block_size=4, prefill_chunk=8) as fleet:
            reqs = [fleet.submit(sys_p + rng.integers(0, 64, size=3).tolist(),
                                 max_new_tokens=4, seed=i)
                    for i in range(4)]
            # chaos leg: exhaust the pool at a specific engine-level
            # admission — the request must still finish
            victim = fleet.submit(sys_p + [7, 8, 9], max_new_tokens=4,
                                  seed=99)
            erid = victim._er.rid
            with faultinject.fault_schedule(f"kv_pool_exhausted@{erid}"):
                n = 0
                while any(not r.is_finished for r in reqs + [victim]):
                    fleet.pump()
                    n += 1
                    assert n < 500
                assert ("kv_pool_exhausted", erid) in faultinject.fired
            st = fleet.stats()
            assert st["kv"]["prefix_hits"] > 0
            assert st["kv"]["pool_exhausted"] >= 1
            assert st["kv"]["blocks_total"] > 0
            for r in reqs + [victim]:
                assert r.finish_reason in ("length", "eos")
                ref = _ref_generate(m, list(r.prompt), 4)
                assert r.tokens == ref


def _pool_reconciles(eng):
    pool = eng.pool
    live = sum(1 for b in range(1, len(pool._ref)) if pool._ref[b] > 0)
    return len(pool._free) + live == pool.capacity


class TestHostKVTierUnit:
    SPEC = (((2, 4, 2, 8), np.dtype(np.float32)),
            ((2, 4, 2, 8), np.dtype(np.float32)))

    def test_acquire_reuse_and_arena_gauge(self):
        before = counters.snapshot()
        tier = HostKVTier(4)
        bufs = tier.acquire(self.SPEC)
        assert len(bufs) == 2 and all(b.shape == (2, 4, 2, 8)
                                      for b in bufs)
        nbytes = sum(b.nbytes for b in bufs)
        assert tier.arena_bytes == nbytes
        # recycle via pop, then re-acquire: pool hit, no new bytes
        tier.put("a", bufs)
        assert tier.pop("a") is True
        again = tier.acquire(self.SPEC)
        assert tier.arena_bytes == nbytes                # flat once warm
        d = counters.delta(before)
        assert d.get("serving.kv.host_buf_reuse", 0) == 2
        # last-write-wins gauge: this tier published its arena total
        # (delta vs `before` would see other engines' tiers)
        assert counters.get("serving.kv.host_arena_bytes") == nbytes
        assert {id(b) for b in again} == {id(b) for b in bufs}

    def test_put_lru_overflow_returns_dropped_keys(self):
        tier = HostKVTier(2)
        for key in ("a", "b"):
            assert tier.put(key, tier.acquire(self.SPEC)) == []
        # touching "a" makes "b" the LRU victim of the next overflow
        assert tier.get("a") is not None
        dropped = tier.put("c", tier.acquire(self.SPEC))
        assert dropped == ["b"]
        assert tier.resident == 2
        assert tier.get("b") is None
        # the dropped entry's buffers were recycled, not leaked
        tier.put("d", tier.acquire(self.SPEC))
        bytes_before = tier.arena_bytes
        assert tier.arena_bytes == bytes_before

    def test_pop_is_tolerant_of_absent_keys(self):
        tier = HostKVTier(1)
        assert tier.pop("nope") is False
        with pytest.raises(ValueError):
            HostKVTier(0)


def _tiered(m, **kw):
    kw.setdefault("n_blocks", 10)
    kw.setdefault("host_kv_blocks", 32)
    kw.setdefault("max_slots", 2)
    return _paged(m, **kw)


class TestKVTiering:
    """Tentpole: cold KV spills to pinned host RAM and pages back on
    demand — token identity is preserved across the round-trip, the
    host reuse pool keeps steady-state traffic allocation-free, and a
    dropped host copy degrades to a deterministic cache-miss replay."""

    def test_oversubscribed_identity_greedy(self):
        m = _model()
        rng = np.random.default_rng(20)
        prompts = [rng.integers(0, 64, size=9).tolist() for _ in range(6)]
        refs = [_ref_generate(m, p, 4) for p in prompts]
        before = counters.snapshot()
        eng = _tiered(m)                 # 9 usable blocks, far too few
        for two_pass in range(2):        # pass 2 restores what 1 spilled
            for i, p in enumerate(prompts):
                h = eng.add_request(p, max_new_tokens=4, seed=i)
                _run(eng, [h])
                assert h.tokens == refs[i], \
                    f"pass {two_pass} prompt {i} diverged"
        d = counters.delta(before)
        assert d.get("serving.kv.tier.spilled_blocks", 0) > 0
        assert d.get("serving.kv.tier.restored_blocks", 0) > 0
        assert d.get("serving.kv.host_buf_reuse", 0) > 0
        assert _pool_reconciles(eng)
        eng.prefix.clear()
        assert eng.pool.free_blocks == eng.pool.capacity
        assert eng._host_tier.resident == 0

    def test_oversubscribed_identity_sampled(self):
        m = _model()
        rng = np.random.default_rng(21)
        prompts = [rng.integers(0, 64, size=9).tolist() for _ in range(5)]
        kw = dict(do_sample=True, temperature=0.8, top_k=8, top_p=0.9)
        ample = _paged(m, n_blocks=64, max_slots=2)
        refs = []
        for i, p in enumerate(prompts):
            h = ample.add_request(p, max_new_tokens=4, seed=50 + i, **kw)
            _run(ample, [h])
            refs.append(h.tokens)
        before = counters.snapshot()
        eng = _tiered(m)
        for _ in range(2):
            for i, p in enumerate(prompts):
                h = eng.add_request(p, max_new_tokens=4, seed=50 + i,
                                    **kw)
                _run(eng, [h])
                assert h.tokens == refs[i]
        d = counters.delta(before)
        assert d.get("serving.kv.tier.spilled_blocks", 0) > 0
        assert d.get("serving.kv.tier.restored_blocks", 0) > 0
        assert _pool_reconciles(eng)

    def test_steady_state_spill_restore_compiles_nothing(self):
        """After one warm cycle compiled the one-block gather/scatter
        programs, further spill/restore churn traces nothing and the
        host arena stays flat (the reuse pool covers every buffer)."""
        m = _model()
        rng = np.random.default_rng(22)
        prompts = [rng.integers(0, 64, size=9).tolist() for _ in range(6)]
        eng = _tiered(m)
        for p in prompts:                          # warm: compiles + fills
            _run(eng, [eng.add_request(p, max_new_tokens=4, seed=3)])
        before = counters.snapshot()
        for p in prompts:                          # measured churn
            _run(eng, [eng.add_request(p, max_new_tokens=4, seed=3)])
        d = counters.delta(before)
        assert d.get("serving.kv.tier.spilled_blocks", 0) > 0
        assert d.get("serving.kv.tier.restored_blocks", 0) > 0
        assert d.get("serving.retraces", 0) == 0
        assert d.get("serving.kv.host_arena_bytes", 0) == 0
        assert d.get("serving.kv.host_buf_reuse", 0) > 0

    def test_kv_spill_drop_degrades_to_cache_miss(self):
        """Chaos: the host copy vanishes mid-restore — the chain is
        dropped, admission proceeds as a plain prefix miss, and the
        replayed prefill is token-identical."""
        m = _model()
        rng = np.random.default_rng(23)
        p = rng.integers(0, 64, size=9).tolist()   # 9 + 4 - 1 = 3 blocks
        eng = _tiered(m)
        h1 = eng.add_request(p, max_new_tokens=4, seed=0)
        _run(eng, [h1])
        with eng._cond:
            assert eng._spill_cold(3) == 3         # whole chain to host
        assert eng._host_tier.resident == 3
        before = counters.snapshot()
        h2 = eng.add_request(p, max_new_tokens=4, seed=0)
        with faultinject.fault_schedule(f"kv_spill_drop@{h2.rid}"):
            _run(eng, [h2])
            assert ("kv_spill_drop", h2.rid) in faultinject.fired
        assert h2.tokens == h1.tokens == _ref_generate(m, p, 4)
        d = counters.delta(before)
        assert d.get("serving.kv.tier.spill_drops", 0) == 3
        assert d.get("serving.kv.tier.restored_blocks", 0) == 0
        assert d.get("resilience.faults_injected.kv_spill_drop", 0) == 1
        assert d.get("serving.kv.prefix_misses", 0) >= 1
        assert eng._host_tier.resident == 0
        assert _pool_reconciles(eng)

    def test_readoption_flips_host_node_back_for_free(self):
        """A donor inserting over a host-resident node re-adopts it to
        device residency without any host->device copy: the donor's
        live block simply replaces the spilled one."""
        m = _model()
        rng = np.random.default_rng(24)
        p = rng.integers(0, 64, size=9).tolist()
        eng = _tiered(m)
        h1 = eng.add_request(p, max_new_tokens=4, seed=0)
        _run(eng, [h1])
        with eng._cond:
            eng._spill_cold(3)
        before = counters.snapshot()
        # admission pages back only the first 2 blocks (the match limit
        # is prompt-1 = 8 tokens); the third host node is re-adopted at
        # donation time — the finishing request carries a live device
        # copy of the same tokens, so residency flips back for free
        h2 = eng.add_request(p, max_new_tokens=4, seed=0)
        _run(eng, [h2])
        d = counters.delta(before)
        assert d.get("serving.kv.tier.restored_blocks", 0) == 2
        assert d.get("serving.kv.tier.readopted", 0) == 1
        assert h2.tokens == h1.tokens
        assert eng._host_tier.resident == 0
        assert _pool_reconciles(eng)


class TestHostTierRouting:
    def test_probe_reports_host_tokens_and_router_prices_restore(self):
        m = _model()
        from paddle_tpu.serving import Replica, Router
        rng = np.random.default_rng(25)
        sys_p = rng.integers(0, 64, size=8).tolist()
        warm = _tiered(m)
        cold = _paged(m)
        h = warm.add_request(sys_p + [1, 2], max_new_tokens=3, seed=0)
        _run(warm, [h])                  # KV = 12 tokens = 3 full blocks
        with warm._cond:
            assert warm._spill_cold(3) == 3
        probe_p = np.asarray(sys_p + [9, 9], np.int32)
        dev, host = warm.prefix_probe(probe_p)
        assert dev == 0 and host == 8    # whole prefix is host-resident
        assert cold.prefix_probe(probe_p) == (0, 0)
        reps = [Replica(0, cold), Replica(1, warm)]
        before = counters.snapshot()
        picked = Router().pick(reps, est_tokens=16, prompt=probe_p)
        assert picked.engine is warm     # host tokens still win routing
        d = counters.delta(before)
        assert d.get("serving.fleet.prefix_routed", 0) == 1
        # restore_cost=1.0 prices paging at a full re-prefill: the
        # host-resident prefix carries no edge and the tie breaks cold
        router = Router(restore_cost=1.0)
        assert router.pick(reps, est_tokens=16,
                           prompt=probe_p).engine is cold

    def test_digest_short_circuits_cold_probes(self):
        pool = BlockPool(9, 4)
        cache = PrefixCache(pool)
        seq = list(range(8))
        blocks = pool.alloc_n(2)
        cache.insert(seq, blocks)
        for b in blocks:
            pool.release(b)
        assert cache.digest() == frozenset({hash(tuple(seq[:4]))})
        # digest miss: a full-block probe of unseen tokens never walks
        assert cache.probe([40] * 8, limit=8) == (0, 0)
        assert cache.probe(seq, limit=8) == (8, 0)
        cache.clear()
        assert cache.digest() == frozenset()


# ---------------------------------------------------------------------------
# the per-slot decode state lives on the device between launches (PR 31)
# ---------------------------------------------------------------------------
_KINDS = ("plain", "kv_dtype", "slot_state")
_HYBRID = None
_UPLOADS, _STEPS = "serving.decode.upload_steps", "serving.decode_steps"


def _hybrid():
    """A one-period Olmo-hybrid model: three delta-rule layers and one of
    full attention, so the engine's programs are the ``slot_state`` ones."""
    global _HYBRID
    if _HYBRID is None:
        from paddle_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                                   OlmoHybridForCausalLM)
        cfg = {"vocab_size": 64, "hidden_size": 32, "intermediate_size": 64,
               "num_hidden_layers": 4, "num_attention_heads": 2,
               "num_key_value_heads": 2, "max_position_embeddings": 64,
               "rms_norm_eps": 1e-6,
               "layer_types": ["linear_attention"] * 3 + ["full_attention"],
               "linear_num_key_heads": 2, "linear_num_value_heads": 2,
               "linear_key_head_dim": 8, "linear_value_head_dim": 16,
               "linear_conv_kernel_dim": 4}
        paddle.seed(17)
        _HYBRID = OlmoHybridForCausalLM(OlmoHybridConfig.from_hf(
            cfg, initializer_range=0.1, dtype="float32"))
        _HYBRID.eval()
    return _HYBRID


def _kind_engine(kind, **kw):
    if kind == "slot_state":
        return _paged(_hybrid(), **kw)
    if kind == "kv_dtype":
        kw["kv_dtype"] = "int8"
    return _paged(_model(), **kw)


def _alone(kind, prompt, **kw):
    """The request's tokens when it has an engine of that kind to itself."""
    eng = _kind_engine(kind)
    h = eng.add_request(prompt, **kw)
    _run(eng, [h])
    return list(h.tokens)


def _key_after(seed, splits):
    import jax
    key = jax.random.key(seed)
    for _ in range(splits):
        key = jax.random.split(key)[0]
    return np.asarray(jax.random.key_data(key))


def _device_is_the_host(eng):
    """Every per-slot operand that nothing has written since the last
    launch reads the same on the device as in its host mirror (the keys'
    mirror is fetched from the device, so it is compared by chain in the
    tests instead)."""
    for name in ("bt", "tok", "pos", "running", "dosample", "temp", "topk",
                 "topp"):
        if name in eng._dev and name not in eng._stale:
            assert np.array_equal(np.asarray(eng._dev[name]),
                                  getattr(eng, "_" + name)), name


class TestResidentDecodeState:
    @pytest.mark.parametrize("kind", _KINDS)
    def test_tokens_across_slot_turnover(self, kind):
        """A row joins while others decode, a finished row's slot is
        handed on, greedy and sampling rows with different knobs share
        launches: every request's tokens are the ones it gets with an
        engine to itself (for the plain engine: ``generate``'s)."""
        rng = np.random.default_rng(51)
        reqs = {
            "a": dict(max_new_tokens=9),
            "b": dict(max_new_tokens=4, do_sample=True, temperature=0.8,
                      top_k=5, seed=11),
            "c": dict(max_new_tokens=6, do_sample=True, top_p=0.9, seed=12),
            "d": dict(max_new_tokens=5),
            "e": dict(max_new_tokens=5, do_sample=True, temperature=1.3,
                      seed=13),
        }
        prompts = {n: rng.integers(0, 64, size=s).tolist()
                   for n, s in zip(reqs, (5, 7, 6, 4, 9))}
        eng = _kind_engine(kind)
        hs = {n: eng.add_request(prompts[n], **reqs[n]) for n in "ab"}
        for _ in range(2):
            eng.step()
            _device_is_the_host(eng)
        hs["c"] = eng.add_request(prompts["c"], **reqs["c"])   # joins late
        while not hs["b"].is_finished:
            eng.step()
            _device_is_the_host(eng)
        freed = {s for s, r in enumerate(eng._slots) if r is None}
        hs["d"] = eng.add_request(prompts["d"], **reqs["d"])
        hs["e"] = eng.add_request(prompts["e"], **reqs["e"])
        eng.step()
        assert hs["d"].slot in freed                # b's row, handed on
        while not all(h.is_finished for h in hs.values()):
            eng.step()
            _device_is_the_host(eng)
        for n, h in hs.items():
            assert list(h.tokens) == _alone(kind, prompts[n], **reqs[n]), n
            if kind == "plain":
                kw = {k: v for k, v in reqs[n].items()
                      if k != "max_new_tokens"}
                assert list(h.tokens) == _ref_generate(
                    _model(), prompts[n], reqs[n]["max_new_tokens"], **kw)
        assert not eng._running.any() and not eng._bt.any()

    @pytest.mark.parametrize("kind", _KINDS)
    def test_key_chain_is_one_split_a_launch(self, kind):
        """The chain the device carries is today's: the seed's key, one
        split by the last prefill chunk, one by every decode launch the
        row ran in, whatever joins beside it (a joining row makes the
        host upload ALL rows' keys: the running row's must be current)."""
        rng = np.random.default_rng(52)
        eng = _kind_engine(kind)
        h = eng.add_request(rng.integers(0, 64, size=6).tolist(),
                            max_new_tokens=12, do_sample=True, seed=77)
        other = None
        while len(h.tokens) < 9:
            if len(h.tokens) == 4 and other is None:
                other = eng.add_request(
                    rng.integers(0, 64, size=5).tolist(), max_new_tokens=3)
            eng.step()
            slot, n = h.slot, len(h.tokens)
            assert np.array_equal(eng._keys[slot], _key_after(77, n))
            assert eng._pos[slot] == 6 + n - 1
            assert eng._tok[slot] == h.tokens[-1]
            _device_is_the_host(eng)
        assert other is not None and other.is_finished

    @pytest.mark.parametrize("kind", ("plain", "kv_dtype"))
    def test_export_after_resident_steps_is_current(self, kind):
        """A parked row's token, position and key are what its last chunk
        left, however many launches its neighbours ran meanwhile (its
        ``running`` bit is off, so the program carries its row through),
        and the engine that adopts it continues the request's own chain."""
        rng = np.random.default_rng(53)
        ph = rng.integers(0, 64, size=7).tolist()
        kw = dict(max_new_tokens=6, do_sample=True, top_k=8, seed=31)
        src, dst = _kind_engine(kind), _kind_engine(kind)
        held = src.add_request(ph, hold_after_prefill=True, **kw)
        beside = src.add_request(rng.integers(0, 64, size=5).tolist(),
                                 max_new_tokens=8)
        _run(src, [beside])
        assert held.state == "held" and len(held.tokens) == 1
        mig = src.export_request(held)
        assert mig["tok"] == held.tokens[0] and mig["pos"] == len(ph)
        assert np.array_equal(mig["key"], _key_after(31, 1))
        busy = dst.add_request(rng.integers(0, 64, size=4).tolist(),
                               max_new_tokens=12)
        for _ in range(3):
            dst.step()
        before = counters.snapshot()
        new, _ = dst.adopt_migration(mig, src)
        src.finish_migrated(held)
        dst.step()                     # the launch that sees the adoption
        d = counters.delta(before)
        assert (d[_STEPS], d[_UPLOADS]) == (1, 1)
        assert len(new.tokens) == 1    # its token is the next step's
        _device_is_the_host(dst)
        _run(dst, [new, busy])
        assert list(new.tokens) == _alone(kind, ph, **kw)
        if kind == "plain":
            assert list(new.tokens) == _ref_generate(
                _model(), ph, 6, do_sample=True, top_k=8, seed=31)

    def test_spill_and_restore_reach_the_next_launch(self):
        """The host tier trashes a parked row's table entries and pages
        them back on export; each writes the table from outside the decode
        program, so the launch after each uploads it."""
        rng = np.random.default_rng(54)
        eng = _paged(_model(), host_kv_blocks=16, spill_idle_steps=2)
        held = eng.add_request(rng.integers(0, 64, size=11).tolist(),
                               max_new_tokens=4, seed=3,
                               hold_after_prefill=True)
        beside = eng.add_request(rng.integers(0, 64, size=5).tolist(),
                                 max_new_tokens=14)
        while held.state != "held":
            eng.step()
        before = counters.snapshot()
        row = eng._bt[held.slot].copy()
        while held.rid not in eng._req_host:
            eng.step()                                  # ...and it spills
        assert not eng._bt[held.slot, :2].any() and row[:2].all()
        eng.step()
        _device_is_the_host(eng)
        d = counters.delta(before)
        assert d[_UPLOADS] == 1 and d[_STEPS] >= 2
        mig = eng.export_request(held)                  # pages it back
        assert "bt" in eng._stale and eng._bt[held.slot, :2].all()
        eng.step()
        _device_is_the_host(eng)
        assert counters.delta(before)[_UPLOADS] == 2
        assert list(mig["table"]) == list(eng._slot_blocks[held.slot])
        assert not beside.is_finished


class TestUploadStepsCounter:
    @pytest.mark.parametrize("kind", _KINDS)
    def test_steady_steps_upload_nothing(self, kind):
        """K launches with no admission and no finish: ``decode_steps``
        rises by K and ``upload_steps`` stays, and the launches run with
        host-to-device transfers refused outright."""
        import jax
        rng = np.random.default_rng(55)
        eng = _kind_engine(kind)
        hs = [eng.add_request(rng.integers(0, 64, size=n).tolist(),
                              max_new_tokens=12, **kw)
              for n, kw in ((5, {}), (6, dict(do_sample=True, seed=4)))]
        for _ in range(3):
            eng.step()
        counters.reset(_UPLOADS)
        before = counters.snapshot()
        with jax.transfer_guard_host_to_device("disallow_explicit"):
            for _ in range(5):
                eng.step()
        d = counters.delta(before)
        assert d[_STEPS] == 5 and d.get(_UPLOADS, 0) == 0
        # registered all the same, at 0
        assert counters.snapshot()[_UPLOADS] == 0
        _run(eng, hs)
        for h in hs:
            assert len(h.tokens) == 12

    def test_an_upload_step_is_refused_under_the_guard(self):
        """The guard does bite on this backend: a launch after a write to
        a slot's state needs its upload."""
        import jax
        eng = _paged(_model())
        h = eng.add_request([1, 2, 3], max_new_tokens=8)
        for _ in range(2):
            eng.step()
        eng._write_slot(h.slot, temp=h.temperature)
        with jax.transfer_guard_host_to_device("disallow_explicit"):
            with pytest.raises(Exception, match="host-to-device"):
                eng.step()

    def test_admission_and_finish_each_add_one(self):
        rng = np.random.default_rng(56)
        eng = _paged(_model())
        long = eng.add_request(rng.integers(0, 64, size=5).tolist(),
                               max_new_tokens=16)
        for _ in range(3):
            eng.step()
        before = counters.snapshot()

        def ups():
            return counters.delta(before).get(_UPLOADS, 0)
        eng.step()
        assert ups() == 0
        short = eng.add_request(rng.integers(0, 64, size=4).tolist(),
                                max_new_tokens=3)
        eng.step()                      # admitted, prefilled, first launch
        assert ups() == 1 and len(short.tokens) == 1
        eng.step()                      # its second token
        assert ups() == 1 and len(short.tokens) == 2
        eng.step()                      # its last: the row finishes, and
        assert ups() == 2 and short.is_finished    # the launch is without it
        eng.step()
        assert ups() == 2 and not long.is_finished
        assert counters.delta(before)[_STEPS] == 5

    def test_speculative_round_counts_its_uploads(self):
        """The speculative engine shares the arrays and uploads them from
        the host mirrors every round; the counter says so."""
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        paddle.seed(9)
        draft = GPTForCausalLM(GPTConfig(
            vocab_size=64, hidden_size=16, num_layers=1, num_heads=2,
            max_seq_len=32, use_flash_attention=False))
        draft.eval()
        eng = _paged(_model(), draft_model=draft)
        before = counters.snapshot()
        h = eng.add_request([5, 6, 7, 8], max_new_tokens=6, do_sample=True,
                            seed=8)
        _run(eng, [h])
        d = counters.delta(before)
        assert d[_UPLOADS] == d[_STEPS] > 0 and len(h.tokens) == 6

