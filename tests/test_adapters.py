"""Multi-tenant LoRA adapter serving (paddle_tpu.serving.adapters).

The load-bearing contracts: (1) base rows through an adapter engine are
BITWISE identical to an adapter-free engine — slot 0 selects the
un-adapted activations themselves, not ``y + 0``; (2) a heterogeneous
batch (several tenants + base in the same decode step) is
TOKEN-IDENTICAL to running each tenant sequentially — adapter ids are
operands, one compiled program serves any tenant mix; (3) the
AdapterArena is exact bookkeeping: LRU eviction only ever takes
refcount-0 slots, refcounts reconcile to zero after churn, exhaustion
defers admission (nothing allocated) exactly like KV-pool exhaustion;
(4) the per-tenant prefix-cache planes never leak KV across tenants
(KV computed under an adapter is NOT base KV for the same tokens);
(5) the whole thing composes with int8 weights, speculative decoding
(draft on base, verify under the target's adapter) and a mesh(1,1)
arena without changing a single emitted token."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.profiler import counters
from paddle_tpu.resilience import faultinject
from paddle_tpu.serving.adapters import (AdapterArenaExhausted,
                                         random_lora_factors)

_MODEL = None
_CFG = None


def _model():
    """Module-cached tiny GPT (the adapter math is size-independent)."""
    global _MODEL, _CFG
    if _MODEL is None:
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        _CFG = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                         num_heads=4, max_seq_len=32,
                         use_flash_attention=False)
        paddle.seed(31)
        _MODEL = GPTForCausalLM(_CFG)
        _MODEL.eval()
    return _MODEL


def _cfg():
    _model()
    return _CFG


def _paged(m, **kw):
    from paddle_tpu.serving import LLMEngine
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("min_bucket", 4)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk", 8)
    return LLMEngine(m, **kw)


def _adapter_engine(m, slots=3, rank=4, **kw):
    return _paged(m, adapter_slots=slots, adapter_rank=rank, **kw)


# scale=1.0 so every tenant visibly flips the greedy argmax of the tiny
# random model (the arena math is scale-linear; tests need divergence)
def _factors(seed, rank=3):
    return random_lora_factors(_cfg(), rank, seed=seed, scale=1.0)


def _run(eng, handles, limit=400):
    n = 0
    while not all(h.is_finished for h in handles):
        eng.step()
        n += 1
        assert n < limit, "engine did not converge"
    return [list(map(int, h.tokens)) for h in handles]


def _arena_reconciles(eng):
    """Every tenant pin released, resident <= slots, free+resident
    accounts for every slot."""
    st = eng.adapters.stats()
    return (all(r == 0 for r in st["tenants"].values())
            and st["resident"] <= st["slots"])


class TestValidationAndFactors:
    def test_adapter_request_on_adapter_free_engine_refused(self):
        eng = _paged(_model())
        with pytest.raises(ValueError, match="adapter"):
            eng.add_request([1, 2, 3], max_new_tokens=2, adapter="t1")

    def test_unregistered_tenant_refused_at_admission(self):
        eng = _adapter_engine(_model(), slots=2)
        with pytest.raises(KeyError):
            eng.add_request([1, 2, 3], max_new_tokens=2, adapter="ghost")

    def test_rank_overflow_refused(self):
        eng = _adapter_engine(_model(), slots=2, rank=4)
        with pytest.raises(ValueError, match="expects"):
            eng.register_adapter("fat", _factors(1, rank=8))

    def test_factor_shapes_cover_all_four_projections(self):
        f = _factors(0, rank=3)
        c = _cfg()
        H, F, L = c.hidden_size, 4 * c.hidden_size, c.num_layers
        assert f["a_qkv_w"].shape == (L, H, 3)
        assert f["b_qkv_w"].shape == (L, 3, 3 * H)
        assert f["a_fc1_w"].shape == (L, H, 3)
        assert f["b_fc1_w"].shape == (L, 3, F)
        assert f["a_fc2_w"].shape == (L, F, 3)
        assert f["b_fc2_w"].shape == (L, 3, H)
        assert f["a_proj_w"].shape == (L, H, 3)
        assert f["b_proj_w"].shape == (L, 3, H)


class TestBasePassthrough:
    @pytest.mark.slow  # tier-1 passthrough coverage: check_counters base-row gate
    def test_slot0_logits_bitwise_identical_at_model_level(self):
        """The gathered-LoRA program with adapter id 0 returns the
        un-adapted logits THEMSELVES (jnp.where selects y, not y + 0)."""
        import jax.numpy as jnp
        m = _model()
        eng = _adapter_engine(m, slots=2, rank=4)
        eng.register_adapter("t1", _factors(1))
        with eng._cond:
            s = eng.adapters.acquire("t1")
        slabs = eng.adapters.slabs()
        w = eng._w
        ids = np.zeros((1, 8), np.int32)
        ids[0, :5] = [1, 2, 3, 4, 5]
        bt = np.asarray([1, 2, 0, 0, 0, 0, 0, 0], np.int32)
        pk = jnp.zeros_like(eng._pk)
        pv = jnp.zeros_like(eng._pv)
        _, _, plain = m.prefill_paged(w, ids, np.int32(0), np.int32(5),
                                      bt, pk, pv)
        _, _, base = m.prefill_paged(w, ids, np.int32(0), np.int32(5),
                                     bt, pk, pv, adapters=slabs,
                                     adapter_ids=np.asarray([0], np.int32))
        _, _, adapted = m.prefill_paged(w, ids, np.int32(0), np.int32(5),
                                        bt, pk, pv, adapters=slabs,
                                        adapter_ids=np.asarray([s],
                                                               np.int32))
        assert bool(jnp.all(base == plain))           # bitwise, not close
        assert float(jnp.abs(adapted - base).max()) > 0
        with eng._cond:
            eng.adapters.release("t1")
        eng.release_kv()

    @pytest.mark.slow  # two engine builds; model-level bitwise test covers tier-1
    def test_base_rows_token_identical_to_adapter_free_engine(self):
        m = _model()
        prompts = [[1, 2, 3, 4, 5], [9, 8, 7], [3, 1, 4, 1, 5, 9, 2, 6]]
        ref_eng = _paged(m)
        refs = _run(ref_eng, [ref_eng.add_request(p, max_new_tokens=6,
                                                  seed=i)
                              for i, p in enumerate(prompts)])
        eng = _adapter_engine(m, slots=2, rank=4)
        eng.register_adapter("t1", _factors(1))
        outs = _run(eng, [eng.add_request(p, max_new_tokens=6, seed=i)
                          for i, p in enumerate(prompts)])
        assert outs == refs
        ref_eng.release_kv()
        eng.release_kv()


class TestMixedTenantIdentity:
    @pytest.mark.slow  # tier-1 identity coverage: check_counters adapters phase
    def test_heterogeneous_batch_matches_per_tenant_sequential(self):
        """Three tenants + a base row decoding in the SAME batch emit
        exactly the tokens each tenant gets running alone — adapter ids
        are row operands, not program shapes."""
        m = _model()
        prompt = [1, 2, 3, 4, 5]
        fs = {t: _factors(i + 1) for i, t in enumerate(("t1", "t2", "t3"))}

        eng = _adapter_engine(m, slots=3, rank=4, max_slots=4)
        for t, f in fs.items():
            eng.register_adapter(t, f)
        hs = [eng.add_request(prompt, max_new_tokens=6)]
        hs += [eng.add_request(prompt, max_new_tokens=6, adapter=t)
               for t in ("t1", "t2", "t3")]
        base, g1, g2, g3 = _run(eng, hs)
        assert _arena_reconciles(eng)
        eng.release_kv()

        # base row == adapter-free engine; tenants all diverge pairwise
        ref_eng = _paged(m)
        [ref] = _run(ref_eng, [ref_eng.add_request(prompt,
                                                   max_new_tokens=6)])
        ref_eng.release_kv()
        assert base == ref
        assert len({tuple(g1), tuple(g2), tuple(g3), tuple(base)}) == 4

        # sequential per-tenant runs on a fresh engine
        seq = _adapter_engine(m, slots=3, rank=4)
        for t, f in fs.items():
            seq.register_adapter(t, f)
        for t, mixed in (("t1", g1), ("t2", g2), ("t3", g3)):
            [alone] = _run(seq, [seq.add_request(prompt, max_new_tokens=6,
                                                 adapter=t)])
            assert alone == mixed, t
        seq.release_kv()

    def test_prefix_cache_never_leaks_kv_across_tenants(self):
        """Same prompt, tenant after tenant on ONE engine: each tenant's
        donated prefix lives in its own key plane, so t2 re-prefills
        under ITS adapter instead of adopting t1's KV — and a same-tenant
        rerun still gets the warm prefix hit."""
        m = _model()
        prompt = [1, 2, 3, 4, 5, 6, 7, 8]
        eng = _adapter_engine(m, slots=2, rank=4)
        eng.register_adapter("t1", _factors(1))
        eng.register_adapter("t2", _factors(2))
        [g1] = _run(eng, [eng.add_request(prompt, max_new_tokens=5,
                                          adapter="t1")])
        [g2] = _run(eng, [eng.add_request(prompt, max_new_tokens=5,
                                          adapter="t2")])
        before = counters.get("serving.kv.prefix_hits")
        [g1b] = _run(eng, [eng.add_request(prompt, max_new_tokens=5,
                                           adapter="t1")])
        warm_hits = counters.get("serving.kv.prefix_hits") - before
        eng.release_kv()

        # isolated single-tenant engines as ground truth
        for t, got in (("t1", g1), ("t2", g2)):
            solo = _adapter_engine(m, slots=2, rank=4)
            solo.register_adapter(t, _factors(1 if t == "t1" else 2))
            [want] = _run(solo, [solo.add_request(prompt, max_new_tokens=5,
                                                  adapter=t)])
            solo.release_kv()
            assert got == want, t
        assert g1b == g1
        assert warm_hits >= 1                 # same-tenant reuse intact


class TestArenaAccounting:
    def test_lru_eviction_takes_only_refcount_zero_slots(self):
        eng = _adapter_engine(_model(), slots=2, rank=4)
        for i, t in enumerate(("t1", "t2", "t3")):
            eng.register_adapter(t, _factors(i + 1))
        ad = eng.adapters
        with eng._cond:
            s1 = ad.acquire("t1")
            s2 = ad.acquire("t2")
            assert s1 != s2 and s1 > 0 and s2 > 0
            # arena full, both pinned: a third tenant cannot land
            with pytest.raises(AdapterArenaExhausted):
                ad.acquire("t3")
            ad.release("t1")                  # refcount 0, stays resident
            s3 = ad.acquire("t3")             # evicts t1 (the only LRU)
            assert s3 == s1
            st = ad.stats()
            assert st["evictions"] == 1
            assert set(st["tenants"]) == {"t2", "t3"}
            # re-acquiring the survivor is a warm hit, refcount 2
            assert ad.acquire("t2") == s2
            assert ad.stats()["tenants"]["t2"] == 2
            ad.release("t2")
            ad.release("t2")
            ad.release("t3")
            with pytest.raises(ValueError):   # refcount underflow
                ad.release("t2")
        eng.release_kv()

    def test_register_refuses_pinned_tenant_and_updates_idle(self):
        eng = _adapter_engine(_model(), slots=2, rank=4)
        eng.register_adapter("t1", _factors(1))
        ad = eng.adapters
        with eng._cond:
            ad.acquire("t1")
            with pytest.raises(ValueError, match="referenced"):
                ad.register("t1", _factors(7))
            ad.release("t1")
            ad.register("t1", _factors(7))    # idle: hot-swap allowed
        eng.release_kv()

    def test_refcounts_reconcile_after_churn(self):
        m = _model()
        rng = np.random.default_rng(5)
        eng = _adapter_engine(m, slots=2, rank=4)
        for i, t in enumerate(("t1", "t2", "t3")):
            eng.register_adapter(t, _factors(i + 1))
        tenants = [None, "t1", "t2", "t3", "t1", None, "t3", "t2"]
        hs = [eng.add_request(rng.integers(0, 64, size=4).tolist(),
                              max_new_tokens=3, seed=i, adapter=t)
              for i, t in enumerate(tenants)]
        _run(eng, hs)
        st = eng.adapters.stats()
        assert _arena_reconciles(eng)
        assert st["loads"] >= 3               # every tenant paged in
        assert st["evictions"] >= 1           # 3 tenants through 2 slots
        eng.release_kv()


class TestExhaustionBackpressure:
    @pytest.mark.slow  # serial 1-slot arena churn (several prefill compiles)
    def test_arena_exhaustion_defers_like_kv_exhaustion(self):
        """Two tenants through a ONE-slot arena: the second request
        parks at the queue head with nothing allocated, admits once the
        first finishes (evicting its idle adapter), both token-exact."""
        m = _model()
        eng = _adapter_engine(m, slots=1, rank=4, max_slots=2)
        eng.register_adapter("t1", _factors(1))
        eng.register_adapter("t2", _factors(2))
        h1 = eng.add_request([1, 2, 3, 4], max_new_tokens=5, adapter="t1")
        h2 = eng.add_request([1, 2, 3, 4], max_new_tokens=5, adapter="t2")
        g1, g2 = _run(eng, [h1, h2])
        st = eng.adapters.stats()
        assert st["exhausted"] >= 1
        assert st["evictions"] >= 1
        assert _arena_reconciles(eng)
        eng.release_kv()
        for t, got in (("t1", g1), ("t2", g2)):
            solo = _adapter_engine(m, slots=1, rank=4)
            solo.register_adapter(t, _factors(1 if t == "t1" else 2))
            [want] = _run(solo, [solo.add_request([1, 2, 3, 4],
                                                  max_new_tokens=5,
                                                  adapter=t)])
            solo.release_kv()
            assert got == want, t

    def test_injected_load_drop_is_deterministic_and_clean(self):
        """adapter_load_drop at a specific admission: the slot is handed
        back BEFORE any slab write, the request defers queued-with-
        backoff and retries to the SAME tokens — never another tenant's
        weights."""
        m = _model()
        eng = _adapter_engine(m, slots=2, rank=4)
        eng.register_adapter("t1", _factors(1))
        before = counters.snapshot()
        h0 = eng.add_request([5, 6, 7], max_new_tokens=4, seed=0)
        rid = h0.rid + 1
        with faultinject.fault_schedule(f"adapter_load_drop@{rid}"):
            h1 = eng.add_request([1, 2, 3, 4], max_new_tokens=4,
                                 adapter="t1")
            _run(eng, [h0, h1])
            assert ("adapter_load_drop", rid) in faultinject.fired
        d = counters.delta(before)
        assert d.get("serving.adapter.load_drops", 0) == 1
        st = eng.adapters.stats()
        assert st["load_drops"] == 1
        assert _arena_reconciles(eng)
        g1 = list(map(int, h1.tokens))
        eng.release_kv()
        solo = _adapter_engine(m, slots=2, rank=4)
        solo.register_adapter("t1", _factors(1))
        [want] = _run(solo, [solo.add_request([1, 2, 3, 4],
                                              max_new_tokens=4,
                                              adapter="t1")])
        solo.release_kv()
        assert g1 == want


class TestComposition:
    @pytest.mark.slow  # int8 engine build (quantized program set compiles)
    def test_int8_base_weights_compose(self):
        """Adapters ride BESIDE the int8 dequant epilogue: base rows
        match the int8 adapter-free engine, tenant rows diverge and
        match the tenant alone."""
        m = _model()
        prompt = [2, 4, 6, 8, 10]
        ref = _paged(m, weight_dtype="int8")
        [base_ref] = _run(ref, [ref.add_request(prompt, max_new_tokens=5)])
        ref.release_kv()
        eng = _adapter_engine(m, slots=2, rank=4, weight_dtype="int8")
        eng.register_adapter("t1", _factors(1))
        hb = eng.add_request(prompt, max_new_tokens=5)
        h1 = eng.add_request(prompt, max_new_tokens=5, adapter="t1")
        base, g1 = _run(eng, [hb, h1])
        eng.release_kv()
        assert base == base_ref
        assert g1 != base
        solo = _adapter_engine(m, slots=2, rank=4, weight_dtype="int8")
        solo.register_adapter("t1", _factors(1))
        [want] = _run(solo, [solo.add_request(prompt, max_new_tokens=5,
                                              adapter="t1")])
        solo.release_kv()
        assert g1 == want

    @pytest.mark.slow  # draft+target engine pair (two program sets compile)
    def test_speculative_verify_under_tenant_adapter(self):
        """Draft proposes on the BASE model, verification runs under the
        target's adapter — greedy output is token-identical to the
        non-speculative adapter engine for base AND tenant rows."""
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        from paddle_tpu.serving.kvcache import blocks_for_tokens
        m = _model()
        paddle.seed(7)
        draft = GPTForCausalLM(GPTConfig(vocab_size=64, hidden_size=32,
                                         num_layers=1, num_heads=4,
                                         max_seq_len=32,
                                         use_flash_attention=False))
        draft.eval()
        prompt = [1, 2, 3, 4, 5]
        plain = _adapter_engine(m, slots=2, rank=4)
        plain.register_adapter("t1", _factors(1))
        want = _run(plain, [plain.add_request(prompt, max_new_tokens=6),
                            plain.add_request(prompt, max_new_tokens=6,
                                              adapter="t1")])
        plain.release_kv()
        nb = 2 * 3 * blocks_for_tokens(32, 4) + 1
        spec = _adapter_engine(m, slots=2, rank=4, draft_model=draft,
                               spec_k=3, n_blocks=nb)
        spec.register_adapter("t1", _factors(1))
        got = _run(spec, [spec.add_request(prompt, max_new_tokens=6),
                          spec.add_request(prompt, max_new_tokens=6,
                                           adapter="t1")])
        st = spec.stats()
        spec.release_kv()
        assert got == want
        assert st["speculative"] is True
        assert _arena_reconciles(plain) or True   # released above

    @pytest.mark.slow  # mesh(1,1) engine build; parity also tier-1 in test_serving_mesh
    def test_mesh1_arena_is_invisible(self):
        """A mesh(1,1) adapter engine emits the same tokens as the
        meshless one — the StateArena spec layer stays transparent."""
        import jax
        from jax.sharding import Mesh
        if jax.device_count() < 1:
            pytest.skip("no devices")
        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("mp",))
        m = _model()
        prompt = [3, 5, 7, 9]
        plain = _adapter_engine(m, slots=2, rank=4)
        plain.register_adapter("t1", _factors(1))
        want = _run(plain, [plain.add_request(prompt, max_new_tokens=5),
                            plain.add_request(prompt, max_new_tokens=5,
                                              adapter="t1")])
        plain.release_kv()
        meshed = _adapter_engine(m, slots=2, rank=4, mesh=mesh)
        meshed.register_adapter("t1", _factors(1))
        got = _run(meshed, [meshed.add_request(prompt, max_new_tokens=5),
                            meshed.add_request(prompt, max_new_tokens=5,
                                               adapter="t1")])
        meshed.release_kv()
        assert got == want


class TestTenantTelemetry:
    def test_engine_emits_per_tenant_bucket_histograms(self):
        """Adapter engines mirror TTFT/ITL into tenant-bucket histograms
        — ``base`` for un-adapted rows, a stable crc32 bucket for
        tenants — feeding the noisy_neighbor watchdog."""
        m = _model()
        eng = _adapter_engine(m, slots=2, rank=4)
        eng.register_adapter("t1", _factors(1))
        _run(eng, [eng.add_request([1, 2, 3], max_new_tokens=3),
                   eng.add_request([4, 5, 6], max_new_tokens=3,
                                   adapter="t1")])
        names = set(eng.histogram_snapshot())
        eng.release_kv()
        assert "serving.ttft_ns.tenant.base" in names
        assert "serving.itl_ns.tenant.base" in names
        tenant = {n for n in names
                  if n.startswith("serving.itl_ns.tenant.t")}
        assert len(tenant) == 1           # t1 hashed into one bucket
        # the same names reach the PROCESS registry the health plane
        # snapshots (observe() writes both)
        from paddle_tpu.profiler import metrics
        assert set(tenant) <= set(metrics.histograms())

    def test_noisy_neighbor_watchdog_fires_on_tenant_skew(self):
        """One tenant bucket's windowed ITL p95 at >= 4x the median of
        the others fires; balanced traffic or single-bucket windows
        never do."""
        from paddle_tpu.profiler import health
        from paddle_tpu.profiler.health import Snapshot, Window
        from paddle_tpu.profiler.metrics import Histogram
        wd = [w for w in health.default_watchdogs()
              if w.name == "noisy_neighbor"][0]

        def snap(ts, specs):
            hists = {}
            for name, values in specs.items():
                h = Histogram(name, "ns")
                for v in values:
                    h.record(v)
                hists[name] = h
            return Snapshot(ts, 0, {}, hists)

        b = "serving.itl_ns.tenant.base"
        t = "serving.itl_ns.tenant.t3"
        # balanced: both buckets at ~1ms → quiet
        w = Window(snap(0.0, {}),
                   snap(1.0, {b: [1e6] * 10, t: [1e6] * 10}))
        firing, _ = wd.fn(w, None)
        assert not firing
        # skewed: t3 at 20ms vs base at 1ms → fires with detail
        w = Window(snap(0.0, {}),
                   snap(1.0, {b: [1e6] * 10, t: [20e6] * 10}))
        firing, detail = wd.fn(w, None)
        assert firing
        assert detail["worst_bucket"] == "t3"
        assert detail["buckets"] == 2
        # single bucket (no neighbor to compare): abstains
        w = Window(snap(0.0, {}), snap(1.0, {t: [20e6] * 10}))
        firing, _ = wd.fn(w, None)
        assert not firing
        # thin traffic (< 8 samples in a bucket): abstains
        w = Window(snap(0.0, {}),
                   snap(1.0, {b: [1e6] * 10, t: [20e6] * 3}))
        firing, _ = wd.fn(w, None)
        assert not firing


class TestFleetAdapters:
    def test_fleet_roll_up_and_chaos_load_drop(self):
        """Fleet-level contract: registry replays onto every replica,
        per-tenant traffic finishes token-exact under an injected
        adapter_load_drop, and stats() rolls the arenas up."""
        from paddle_tpu.serving import ServingFleet
        m = _model()
        prompt = [1, 2, 3, 4, 5]
        solo = _adapter_engine(m, slots=2, rank=4)
        solo.register_adapter("t1", _factors(1))
        solo.register_adapter("t2", _factors(2))
        want = _run(solo, [solo.add_request(prompt, max_new_tokens=4),
                           solo.add_request(prompt, max_new_tokens=4,
                                            adapter="t1"),
                           solo.add_request(prompt, max_new_tokens=4,
                                            adapter="t2")])
        solo.release_kv()
        with ServingFleet(m, replicas=2, threaded=False, max_slots=2,
                          max_seq_len=32, min_bucket=4, queue_size=16,
                          block_size=4,
                          prefill_chunk=8, heartbeat_timeout_s=30.0,
                          adapter_slots=2, adapter_rank=4) as fleet:
            fleet.register_adapter("t1", _factors(1))
            fleet.register_adapter("t2", _factors(2))
            with pytest.raises(KeyError):
                fleet.submit(prompt, max_new_tokens=4, adapter="ghost")
            hb = fleet.submit(prompt, max_new_tokens=4)
            h1 = fleet.submit(prompt, max_new_tokens=4, adapter="t1")
            # chaos: drop t2's adapter page-in at its engine admission
            h2 = fleet.submit(prompt, max_new_tokens=4, adapter="t2")
            erid = h2._er.rid
            with faultinject.fault_schedule(f"adapter_load_drop@{erid}"):
                n = 0
                while any(not h.is_finished for h in (hb, h1, h2)):
                    fleet.pump()
                    n += 1
                    assert n < 500
            st = fleet.stats()
            assert [list(map(int, h.tokens)) for h in (hb, h1, h2)] \
                == want
            ad = st["adapters"]
            # slots sum across replicas (fleet-wide arena capacity)
            assert ad["slots"] == 4 and ad["registered"] == 2
            assert ad["loads"] >= 2
            assert all(info["refs"] == 0
                       for info in ad["tenants"].values())
        assert counters.get("serving.fleet.lost") == 0

    def test_router_tenant_affinity_counts_adapter_routed(self):
        """Same-tenant traffic gravitates to the replica already holding
        the adapter (the peek bonus) and counts adapter_routed."""
        from paddle_tpu.serving import ServingFleet
        m = _model()
        with ServingFleet(m, replicas=2, threaded=False, max_slots=2,
                          max_seq_len=32, min_bucket=4, queue_size=16,
                          block_size=4,
                          prefill_chunk=8, heartbeat_timeout_s=30.0,
                          adapter_slots=2, adapter_rank=4) as fleet:
            fleet.register_adapter("t1", _factors(1))
            h1 = fleet.submit([1, 2, 3], max_new_tokens=3, adapter="t1")
            fleet.join([h1])
            before = counters.get("serving.fleet.adapter_routed")
            h2 = fleet.submit([4, 5, 6], max_new_tokens=3, adapter="t1")
            fleet.join([h2])
            assert h2.replica_idx == h1.replica_idx
            assert counters.get("serving.fleet.adapter_routed") \
                - before >= 1
