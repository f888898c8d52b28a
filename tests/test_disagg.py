"""Disaggregated prefill/decode fleet (serving.fleet + serving.autoscale).

The load-bearing contracts:

  * block-granular KV migration — a request prefilled on a prefill
    replica continues decoding on a decode replica with TOKEN IDENTITY
    to the unified fleet (same id, same seed, same PRNG chain), and the
    hand-off copies exactly the blocks the request owns:
    ``blocks_copied == ceil(pos / block_size) - blocks_shared``, where
    prefix blocks already cached on the destination adopt by refcount
    transfer and are NEVER copied;
  * chaos — ``kv_migrate_drop`` severs the hand-off between export and
    adopt: both block pools reconcile (free + live == capacity), the
    request replays deterministically, zero lost requests; a replica
    killed mid-stream on a disaggregated fleet drains through the same
    zero-lost path;
  * backpressure — a migration that finds no decode slot is DEFERRED
    (the request stays held on its source, KV intact), not discarded
    into a replay;
  * router health actions — admission level ``degraded`` tightens the
    SLO shed margin, ``critical`` refuses new admissions
    (``serving.fleet.health_shed``) while ``shed=False`` replays pass;
  * autoscaler — ``itl_burn`` on a unified fleet triggers
    ``disaggregate`` (a replica flips to prefill), the alert resolves
    after the rebalance, and ``serving.autoscale.*`` counters prove the
    transition; with ``FLAGS_health`` off the autoscaler is inert.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import flags as core_flags
from paddle_tpu.profiler import counters
from paddle_tpu.resilience import faultinject
from paddle_tpu.serving import LLMEngine, RetryAfter, Router, ServingFleet
from paddle_tpu.serving.kvcache import (TRASH_BLOCK, BlockPoolExhausted,
                                        HostTierLost, blocks_for_tokens)


@pytest.fixture(scope="module")
def model():
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=64,
                    use_flash_attention=False)
    paddle.seed(31)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def draft_model():
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    cfg = GPTConfig(vocab_size=64, hidden_size=16, num_layers=1,
                    num_heads=2, max_seq_len=64,
                    use_flash_attention=False)
    paddle.seed(7)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


BS = 8


def _fleet(m, **kw):
    kw.setdefault("replicas", 2)
    kw.setdefault("threaded", False)
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("min_bucket", 4)
    kw.setdefault("queue_size", 16)
    kw.setdefault("heartbeat_timeout_s", 30.0)
    kw.setdefault("block_size", BS)
    kw.setdefault("n_blocks", 128)
    kw.setdefault("prefill_chunk", 16)
    return ServingFleet(m, **kw)


def _prompts(rng, sizes):
    return [rng.integers(1, 64, size=n).tolist() for n in sizes]


def _assert_pools_reconcile(fleet):
    """free + live-refcounted == capacity on every alive replica pool."""
    for rep in fleet._alive():
        pool = rep.engine.pool
        refs = list(pool._ref)
        live = sum(1 for b in range(1, len(refs)) if refs[b] > 0)
        assert len(pool._free) + live == pool.capacity, \
            f"replica {rep.idx}: pool leak"


# -- construction ------------------------------------------------------------
class TestConstruction:
    def test_requires_a_decode_replica(self, model):
        with pytest.raises(ValueError, match="decode"):
            _fleet(model, replicas=2, prefill_replicas=2)

    def test_roles_and_gauges(self, model):
        fleet = _fleet(model, replicas=3, prefill_replicas=1)
        st = fleet.stats()
        assert st["roles"] == {"prefill": 1, "decode": 2, "unified": 0}
        assert counters.get("serving.autoscale.prefill_replicas") == 1
        assert counters.get("serving.autoscale.decode_replicas") == 2
        fleet.drain()

    def test_unified_fleet_has_no_roles(self, model):
        fleet = _fleet(model)
        assert fleet.stats()["roles"] == \
            {"prefill": 0, "decode": 0, "unified": 2}
        fleet.drain()


# -- migration ---------------------------------------------------------------
class TestMigration:
    def test_token_identity_vs_unified_fleet(self, model):
        """The tentpole identity: disaggregated output is bitwise equal
        to the unified paged fleet's (itself gated against sequential
        generate), for greedy AND sampled requests."""
        rng = np.random.default_rng(0)
        prompts = _prompts(rng, (24, 9, 40, 17))
        seeds = list(range(4))
        uni = _fleet(model)
        ref = uni.generate(prompts, seeds=seeds, max_new_tokens=8,
                           do_sample=True)
        uni.drain()
        before = counters.snapshot()
        dis = _fleet(model, prefill_replicas=1)
        out = dis.generate(prompts, seeds=seeds, max_new_tokens=8,
                           do_sample=True)
        dis.drain()
        for i, (a, b) in enumerate(zip(ref, out)):
            assert np.array_equal(a, b), f"request {i} diverged"
        d = counters.delta(before)
        assert d.get("serving.fleet.migrate.requests", 0) == 4
        assert d.get("serving.fleet.lost", 0) == 0
        # every request decoded on the decode replica, so the source
        # finished each engine-attempt with reason "migrated"
        assert d.get("serving.evictions.migrated", 0) == 4

    def test_migrated_blocks_equal_owned_nonshared(self, model):
        """blocks_copied == ceil(pos/bs) for a cold destination: the
        request owns every data block and all of them move."""
        fleet = _fleet(model, prefill_replicas=1)
        prompt = _prompts(np.random.default_rng(1), (27,))[0]
        before = counters.snapshot()
        h = fleet.submit(prompt, seed=0, max_new_tokens=6)
        fleet.join([h])
        fleet.drain()
        d = counters.delta(before)
        # held at pos == len(prompt) with the first token emitted but
        # not yet inserted: KV covers exactly the prompt
        expect = blocks_for_tokens(len(prompt), BS)
        assert d.get("serving.fleet.migrate.blocks_copied", 0) == expect
        assert d.get("serving.fleet.migrate.blocks_shared", 0) == 0
        assert d.get("serving.fleet.migrate.tokens", 0) == len(prompt)

    def test_shared_prefix_blocks_never_copied_twice(self, model):
        """Two requests sharing a block-aligned prefix: the second
        migration adopts the prefix from the destination's radix tree
        (refcount transfer) and copies only its private tail."""
        rng = np.random.default_rng(2)
        shared = rng.integers(1, 64, size=2 * BS).tolist()
        p1 = shared + rng.integers(1, 64, size=8).tolist()
        p2 = shared + rng.integers(1, 64, size=9).tolist()
        fleet = _fleet(model, prefill_replicas=1)
        h1 = fleet.submit(p1, seed=1, max_new_tokens=4)
        fleet.join([h1])
        before = counters.snapshot()
        h2 = fleet.submit(p2, seed=2, max_new_tokens=4)
        fleet.join([h2])
        fleet.drain()
        d = counters.delta(before)
        n_data = blocks_for_tokens(len(p2), BS)
        assert d.get("serving.fleet.migrate.blocks_shared", 0) == 2
        assert d.get("serving.fleet.migrate.blocks_copied", 0) == \
            n_data - 2

    def test_decode_backpressure_defers_instead_of_replaying(self, model):
        """More prefilled requests than decode slots: the overflow
        hand-offs park on the source (KV intact) and complete when the
        decode side drains — no retry budget burned, nothing lost."""
        rng = np.random.default_rng(3)
        prompts = _prompts(rng, (24, 9, 40, 17, 12, 30))
        before = counters.snapshot()
        fleet = _fleet(model, prefill_replicas=1, max_slots=2)
        hs = [fleet.submit(p, seed=i, max_new_tokens=8)
              for i, p in enumerate(prompts)]
        fleet.join(hs)
        fleet.drain()
        d = counters.delta(before)
        assert all(h.finish_reason == "length" for h in hs)
        assert all(h.retries == 0 for h in hs)
        assert d.get("serving.fleet.migrate.requests", 0) == len(prompts)
        assert d.get("serving.fleet.migrate.deferred", 0) > 0
        assert d.get("serving.fleet.lost", 0) == 0

    def test_zero_steady_retraces_on_both_roles(self, model):
        """After one migration compiled the gather/scatter program, a
        steady stream of migrating requests compiles NOTHING on either
        role — the one-decode-program economics survive disaggregation."""
        rng = np.random.default_rng(4)
        fleet = _fleet(model, prefill_replicas=1,
                       warm_buckets=(16, 32, 48))
        warm = [fleet.submit(p, seed=9, max_new_tokens=4)
                for p in _prompts(rng, (24, 40))]
        fleet.join(warm)                       # compiles migrate program
        before = counters.snapshot()
        hs = [fleet.submit(p, seed=i, max_new_tokens=6)
              for i, p in enumerate(_prompts(rng, (24, 40, 9, 17)))]
        fleet.join(hs)
        d = counters.delta(before)
        assert d.get("serving.fleet.migrate.requests", 0) == 4
        assert d.get("serving.retraces", 0) == 0
        fleet.drain()


# -- engines with extra state on the decode side -----------------------------
class TestEngineVariants:
    def test_speculative_decode_replicas_token_identical(self, model,
                                                         draft_model):
        """Speculative engines on both roles: the draft namespace never
        migrates — the destination re-prefills its draft KV — and
        draft/verify acceptance stays distribution-preserving (token
        identity vs the unified speculative fleet)."""
        rng = np.random.default_rng(5)
        prompts = _prompts(rng, (24, 9, 40, 17))
        seeds = list(range(4))
        uni = _fleet(model, draft_model=draft_model, spec_k=3)
        ref = uni.generate(prompts, seeds=seeds, max_new_tokens=8,
                           do_sample=True)
        uni.drain()
        before = counters.snapshot()
        dis = _fleet(model, prefill_replicas=1,
                     draft_model=draft_model, spec_k=3)
        out = dis.generate(prompts, seeds=seeds, max_new_tokens=8,
                           do_sample=True)
        dis.drain()
        for i, (a, b) in enumerate(zip(ref, out)):
            assert np.array_equal(a, b), f"request {i} diverged"
        d = counters.delta(before)
        assert d.get("serving.fleet.migrate.requests", 0) == 4
        assert d.get("serving.spec.drafted", 0) > 0

    def test_quantized_kv_migration(self, model):
        """int8 KV arenas migrate scale rows along with the blocks; the
        stream completes with zero lost requests."""
        rng = np.random.default_rng(6)
        prompts = _prompts(rng, (24, 9, 40))
        before = counters.snapshot()
        fleet = _fleet(model, prefill_replicas=1, kv_dtype="int8")
        hs = [fleet.submit(p, seed=i, max_new_tokens=8)
              for i, p in enumerate(prompts)]
        fleet.join(hs)
        fleet.drain()
        d = counters.delta(before)
        assert all(h.finish_reason == "length" for h in hs)
        assert d.get("serving.fleet.migrate.requests", 0) == 3
        assert d.get("serving.fleet.lost", 0) == 0


# -- chaos -------------------------------------------------------------------
class TestMigrationChaos:
    def test_kv_migrate_drop_replays_with_identity(self, model):
        """The migration severed between export and adopt: refcounts on
        BOTH pools reconcile, the request replays (same id, same seed)
        and the delivered stream is identical to the unfaulted fleet."""
        rng = np.random.default_rng(7)
        prompts = _prompts(rng, (24, 9, 40, 17))
        seeds = list(range(4))
        uni = _fleet(model)
        ref = uni.generate(prompts, seeds=seeds, max_new_tokens=8,
                           do_sample=True)
        uni.drain()
        before = counters.snapshot()
        with faultinject.fault_schedule(
                "kv_migrate_drop@0,kv_migrate_drop@2"):
            dis = _fleet(model, prefill_replicas=1, max_retries=2)
            out = dis.generate(prompts, seeds=seeds, max_new_tokens=8,
                               do_sample=True)
            _assert_pools_reconcile(dis)
            dis.drain()
        for i, (a, b) in enumerate(zip(ref, out)):
            assert np.array_equal(a, b), f"request {i} diverged"
        d = counters.delta(before)
        assert d.get("serving.fleet.migrate.dropped", 0) == 2
        assert d.get("resilience.faults_injected.kv_migrate_drop", 0) == 2
        assert d.get("serving.fleet.retried", 0) == 2
        assert d.get("serving.fleet.lost", 0) == 0

    def test_replica_crash_on_disagg_fleet_loses_nothing(self, model):
        """A replica killed mid-stream on a disaggregated fleet drains
        through the normal death path: respawn inherits the role, every
        request reaches a terminal state, zero lost."""
        rng = np.random.default_rng(8)
        prompts = _prompts(rng, (24, 9, 40, 17))
        before = counters.snapshot()
        fleet = _fleet(model, prefill_replicas=1, max_retries=2)
        hs = [fleet.submit(p, seed=i, max_new_tokens=8)
              for i, p in enumerate(prompts)]
        with faultinject.fault_schedule(f"replica_crash@{hs[0].rid}"):
            fleet.join(hs)
        st = fleet.stats()
        fleet.drain()
        d = counters.delta(before)
        assert d.get("serving.fleet.replica_deaths", 0) == 1
        assert d.get("serving.fleet.lost", 0) == 0
        assert all(h.finish_reason is not None for h in hs)
        # the respawn preserved the role split
        assert st["roles"]["prefill"] == 1
        assert st["roles"]["decode"] == 1


# -- router acting on its health signal --------------------------------------
class _FakeHealth:
    def __init__(self, level):
        self.level = level

    def admission_level(self):
        return self.level


class _FakeEngine:
    queue_size = 16

    def stats(self):
        return {"closed": False, "queued": 0, "outstanding_tokens": 10,
                "decode_tps_ema": 1000.0}

    def prefix_peek(self, prompt):
        return 0


class _FakeReplica:
    def __init__(self, idx, role=None):
        self.idx = idx
        self.role = role
        self.engine = _FakeEngine()


@pytest.fixture
def health_on():
    core_flags.set_flags({"FLAGS_health": True,
                          "FLAGS_health_interval_s": 0.0})
    yield
    core_flags.set_flags({"FLAGS_health": False,
                          "FLAGS_health_interval_s": 1.0})


class TestRouterHealthActions:
    def test_critical_refuses_new_admissions(self, health_on):
        router = Router()
        router.health = _FakeHealth("critical")
        before = counters.snapshot()
        with pytest.raises(RetryAfter) as ei:
            router.pick([_FakeReplica(0)], est_tokens=4)
        assert ei.value.reason == "health"
        d = counters.delta(before)
        assert d.get("serving.fleet.health_shed", 0) == 1
        assert d.get("serving.fleet.shed", 0) == 1

    def test_critical_still_routes_replays(self, health_on):
        router = Router()
        router.health = _FakeHealth("critical")
        rep = _FakeReplica(0)
        assert router.pick([rep], est_tokens=4, shed=False) is rep

    def test_degraded_tightens_slo_margin(self, health_on):
        """deadline budget sits between the plain estimate and the
        degraded-factor estimate: ok-level admits, degraded sheds."""
        router = Router(slo_margin=1.0, degraded_factor=10.0)
        rep = _FakeReplica(0)
        # est_done = (10 + 10) / 1000 = 0.02s; budget 0.05s admits at
        # margin 1.0 but sheds at margin 10.0
        router.health = _FakeHealth("ok")
        assert router.pick([rep], est_tokens=10, deadline_s=0.05) is rep
        router.health = _FakeHealth("degraded")
        with pytest.raises(RetryAfter) as ei:
            router.pick([rep], est_tokens=10, deadline_s=0.05)
        assert ei.value.reason == "slo"

    def test_health_off_flag_disables_actions(self):
        """FLAGS_health off: a critical monitor changes nothing."""
        router = Router()
        router.health = _FakeHealth("critical")
        rep = _FakeReplica(0)
        assert router.pick([rep], est_tokens=4) is rep

    def test_role_filter_with_unified_fallback(self, health_on):
        router = Router()
        pre, dec = _FakeReplica(0, "prefill"), _FakeReplica(1, "decode")
        uni = _FakeReplica(2)
        assert router.pick([pre, dec], role="decode") is dec
        assert router.pick([pre, dec], role="prefill") is pre
        # no replica of the requested role → unified fallback
        assert router.pick([pre, uni], role="decode") is uni
        # nothing matching at all → degrade to the full list
        assert router.pick([pre], role="decode") is pre


# -- autoscaler --------------------------------------------------------------
class TestAutoscaler:
    def _burn_fleet(self, model, rules, **autoscale_kw):
        from paddle_tpu.profiler.health import SLO
        return _fleet(model, autoscale=True,
                      autoscale_kw=dict(cooldown_ticks=1, ok_streak=100,
                                        **autoscale_kw),
                      health_kw=dict(rules=rules, interval_s=0.0),
                      prefill_chunk=8)

    def test_disaggregate_on_itl_burn_then_resolve(self, model,
                                                   health_on):
        """The acceptance loop: mixed long/short traffic on a UNIFIED
        fleet fires itl_burn; the autoscaler flips the least-loaded
        replica to prefill (disaggregate); with prefill interference off
        the decode path, the burn alert resolves — all inside one test,
        with the serving.autoscale.* counters proving the transition."""
        import time
        from paddle_tpu.profiler.health import SLO
        rng = np.random.default_rng(9)
        rules = [SLO("itl_burn", ("hist_p95", "serving.itl_ns"), 2e6,
                     windows=((0.5, 1.0),), min_count=4)]
        before = counters.snapshot()
        fleet = self._burn_fleet(model, rules)

        def submit(n, mx):
            p = rng.integers(1, 64, size=n).tolist()
            while True:
                try:
                    return fleet.submit(p, seed=3, max_new_tokens=mx)
                except RetryAfter:
                    fleet.pump()

        hs, t0 = [], time.monotonic()
        while time.monotonic() - t0 < 60:
            hs.append(submit(48, 12))
            hs.append(submit(6, 12))
            for _ in range(4):
                fleet.pump()
            if counters.get("serving.autoscale.decisions.disaggregate") \
                    > before.get(
                        "serving.autoscale.decisions.disaggregate", 0):
                break
        d = counters.delta(before)
        assert d.get("health.alerts.fired.itl_burn", 0) >= 1
        assert d.get("serving.autoscale.decisions.disaggregate", 0) >= 1
        assert fleet.stats()["roles"]["prefill"] == 1
        t1 = time.monotonic()
        while time.monotonic() - t1 < 60:
            hs.append(submit(6, 12))
            for _ in range(6):
                fleet.pump()
            if counters.delta(before).get(
                    "health.alerts.resolved.itl_burn", 0):
                break
        fleet.join(hs)
        fleet.drain()
        d = counters.delta(before)
        assert d.get("health.alerts.resolved.itl_burn", 0) >= 1
        assert d.get("serving.autoscale.flips.to_prefill", 0) >= 1
        assert d.get("serving.fleet.migrate.requests", 0) > 0
        assert d.get("serving.fleet.lost", 0) == 0
        assert all(h.finish_reason == "length" for h in hs)

    def test_grow_prefill_spawns_then_retires(self, model, health_on):
        """ttft_burn on an already-disaggregated fleet grows the prefill
        pool (spawn: the single decode replica is at its floor); once
        the alert clears, the ok-streak retires the spawned replica."""
        import time
        from paddle_tpu.profiler.health import SLO
        rng = np.random.default_rng(10)
        rules = [SLO("ttft_burn", ("hist_p95", "serving.ttft_ns"), 1.0,
                     windows=((0.4, 1.0),), min_count=2)]
        before = counters.snapshot()
        fleet = _fleet(model, prefill_replicas=1, autoscale=True,
                       autoscale_kw=dict(cooldown_ticks=0, ok_streak=2,
                                         max_replicas=3),
                       health_kw=dict(rules=rules, interval_s=0.0))
        hs, t0 = [], time.monotonic()
        while time.monotonic() - t0 < 60:
            p = rng.integers(1, 64, size=24).tolist()
            try:
                hs.append(fleet.submit(p, seed=1, max_new_tokens=4))
            except RetryAfter:
                pass
            fleet.pump()
            if counters.delta(before).get(
                    "serving.autoscale.spawns", 0):
                break
        d = counters.delta(before)
        assert d.get("serving.autoscale.spawns", 0) >= 1
        assert d.get("serving.autoscale.decisions.grow_prefill", 0) >= 1
        assert fleet.stats()["roles"]["prefill"] == 2
        fleet.join(hs)
        # drain the burn: 1ns target can never resolve while samples
        # arrive, so stop traffic — the window empties, the rule
        # abstains, the alert resolves, and the ok-streak retires
        t1 = time.monotonic()
        while time.monotonic() - t1 < 60:
            fleet.pump()
            if counters.delta(before).get("serving.autoscale.retires", 0):
                break
        d = counters.delta(before)
        assert d.get("serving.autoscale.retires", 0) >= 1
        assert fleet.stats()["roles"]["prefill"] == 1
        fleet.drain()
        assert counters.delta(before).get("serving.fleet.lost", 0) == 0

    def test_kv_spill_burn_disaggregates_then_grows_decode(self, model):
        """Sustained spill-rate burn is a capacity signal: a unified
        fleet disaggregates (the split frees decode-side arena), an
        already-split fleet flips surplus prefill capacity to decode."""
        before = counters.snapshot()
        uni = _fleet(model, autoscale=True)
        uni.health.firing_names = lambda: {"kv_spill_burn"}
        assert uni.autoscaler._evaluate() == "disaggregate"
        assert uni.stats()["roles"]["prefill"] == 1
        uni.drain()
        dis = _fleet(model, replicas=3, prefill_replicas=2,
                     autoscale=True)
        dis.health.firing_names = lambda: {"kv_spill_burn"}
        assert dis.autoscaler._evaluate() == "grow_decode"
        assert dis.stats()["roles"] == \
            {"prefill": 1, "decode": 2, "unified": 0}
        dis.drain()
        d = counters.delta(before)
        assert d.get("serving.autoscale.decisions.disaggregate", 0) == 1
        assert d.get("serving.autoscale.decisions.grow_decode", 0) == 1
        assert d.get("serving.autoscale.flips.to_decode", 0) >= 2

    def test_inert_when_health_off(self, model):
        """FLAGS_health off: maybe_scale is a no-op and no autoscale
        counter moves (the zero-overhead-off gate)."""
        before = counters.snapshot()
        fleet = _fleet(model, autoscale=True)
        assert fleet.autoscaler.maybe_scale() is None
        hs = [fleet.submit([1, 2, 3], seed=0, max_new_tokens=4)]
        fleet.join(hs)
        fleet.drain()
        d = counters.delta(before)
        assert d.get("serving.autoscale.decisions", 0) == 0
        assert d.get("serving.autoscale.flips.to_prefill", 0) == 0


# -- host-RAM KV tier on the migration path ----------------------------------
def _engine(m, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("min_bucket", 4)
    kw.setdefault("block_size", BS)
    kw.setdefault("prefill_chunk", 16)
    return LLMEngine(m, **kw)


def _engine_reconciles(eng):
    pool = eng.pool
    live = sum(1 for b in range(1, len(pool._ref)) if pool._ref[b] > 0)
    return len(pool._free) + live == pool.capacity


def _ref_tokens(m, prompt, seed, max_new):
    eng = _engine(m)
    h = eng.add_request(prompt, max_new_tokens=max_new, seed=seed)
    while not h.is_finished:
        eng.step()
    return h.tokens


class TestHeldRequestSpill:
    """A request parked ``"held"`` past ``spill_idle_steps`` demotes its
    KV to the host tier (freeing device blocks for live traffic); the
    export that finally migrates it pages everything back first — or
    raises ``HostTierLost`` when the host copy is gone, with both tiers
    reconciled and nothing torn."""

    def test_idle_spill_then_export_restores_and_migrates(self, model):
        rng = np.random.default_rng(30)
        prompt = rng.integers(1, 64, size=27).tolist()  # 3 full + partial
        ref = _ref_tokens(model, prompt, seed=5, max_new=6)
        before = counters.snapshot()
        src = _engine(model, host_kv_blocks=16, spill_idle_steps=2)
        dst = _engine(model)
        req = src.add_request(prompt, max_new_tokens=6, seed=5,
                              hold_after_prefill=True)
        for _ in range(8):
            src.step()
        assert req.state == "held"
        d = counters.delta(before)
        n_data = blocks_for_tokens(len(prompt), BS)
        assert d.get("serving.kv.tier.spilled_blocks", 0) == n_data
        table = src._slot_blocks[req.slot]
        assert all(b == TRASH_BLOCK for b in table[:n_data])
        assert src._host_tier.resident == n_data
        mig = src.export_request(req)        # pages the KV back in
        assert all(b != TRASH_BLOCK for b in mig["table"][:n_data])
        assert src._host_tier.resident == 0
        d = counters.delta(before)
        assert d.get("serving.kv.tier.restored_blocks", 0) == n_data
        assert d.get("serving.kv.host_buf_reuse", 0) >= 0
        new_req, info = dst.adopt_migration(mig, src)
        src.finish_migrated(req)
        while not new_req.is_finished:
            dst.step()
        assert new_req.tokens == ref
        assert info["blocks_copied"] == n_data
        assert _engine_reconciles(src) and _engine_reconciles(dst)

    def test_kv_spill_drop_on_export_raises_hosttierlost(self, model):
        """Chaos: the spilled copy is dropped before the export can
        restore it.  ``HostTierLost`` surfaces (the fleet's replay
        signal), the tier empties, no device block was allocated for
        the lost data, and the pool reconciles after teardown."""
        rng = np.random.default_rng(31)
        prompt = rng.integers(1, 64, size=27).tolist()
        src = _engine(model, host_kv_blocks=16, spill_idle_steps=2)
        req = src.add_request(prompt, max_new_tokens=6, seed=5,
                              hold_after_prefill=True)
        for _ in range(8):
            src.step()
        assert src._host_tier.resident > 0
        before = counters.snapshot()
        free_before = src.pool.free_blocks
        with faultinject.fault_schedule(f"kv_spill_drop@{req.rid}"):
            with pytest.raises(HostTierLost):
                src.export_request(req)
            assert ("kv_spill_drop", req.rid) in faultinject.fired
        assert src._host_tier.resident == 0
        assert src.pool.free_blocks == free_before
        d = counters.delta(before)
        assert d.get("serving.kv.tier.spill_drops", 0) == \
            blocks_for_tokens(len(prompt), BS)
        assert d.get("serving.kv.tier.restored_blocks", 0) == 0
        src._finish(req, "dropped", [])
        src.prefix.clear()
        assert src.pool.free_blocks == src.pool.capacity

    def test_adopt_reenters_prefix_into_destination_tree(self, model):
        """Tentpole contract: a migrated prefix is shareable on the
        destination IMMEDIATELY after adopt — the next same-prefix
        prompt (or migration) resolves it from the radix tree without
        waiting for the request to finish and donate."""
        rng = np.random.default_rng(32)
        prompt = rng.integers(1, 64, size=27).tolist()
        src = _engine(model)
        dst = _engine(model)
        req = src.add_request(prompt, max_new_tokens=6, seed=5,
                              hold_after_prefill=True)
        while req.state != "held":
            src.step()
        new_req, _ = dst.adopt_migration(src.export_request(req), src)
        src.finish_migrated(req)
        n_full_tokens = (len(prompt) // BS) * BS
        # still mid-decode on dst, yet the full prompt blocks are shared
        assert new_req.state == "running"
        assert dst.prefix_peek(np.asarray(prompt, np.int32)) == \
            n_full_tokens
        while not new_req.is_finished:
            dst.step()
        assert new_req.tokens == _ref_tokens(model, prompt, 5, 6)
        assert _engine_reconciles(dst)

    def test_destination_exhausted_mid_adopt_tears_nothing(self, model):
        """Satellite: adopt against a pool that cannot host the table
        raises ``BlockPoolExhausted`` with NOTHING allocated on the
        destination and the source intact — the same payload then
        adopts cleanly elsewhere."""
        rng = np.random.default_rng(33)
        prompt = rng.integers(1, 64, size=27).tolist()
        src = _engine(model)
        tiny = _engine(model, n_blocks=3, prefix_cache=False)
        req = src.add_request(prompt, max_new_tokens=6, seed=5,
                              hold_after_prefill=True)
        while req.state != "held":
            src.step()
        mig = src.export_request(req)
        before = counters.snapshot()
        free_before = tiny.pool.free_blocks
        with pytest.raises(BlockPoolExhausted):
            tiny.adopt_migration(mig, src)
        assert tiny.pool.free_blocks == free_before
        assert all(r is None for r in tiny._slots)
        assert counters.delta(before).get(
            "serving.kv.pool_exhausted", 0) == 1
        # the source never moved: the same export adopts cleanly
        dst = _engine(model)
        new_req, _ = dst.adopt_migration(mig, src)
        src.finish_migrated(req)
        while not new_req.is_finished:
            dst.step()
        assert new_req.tokens == _ref_tokens(model, prompt, 5, 6)

    def test_int8_partial_block_scale_rows_survive_tier_roundtrip(
            self, model):
        """Satellite: an int8 arena spills fp32 scale rows alongside
        the quantised tiles.  A held request whose last block is
        partial round-trips through the host tier, migrates, and the
        destination's scale rows match the source bit for bit."""
        rng = np.random.default_rng(34)
        prompt = rng.integers(1, 64, size=27).tolist()  # partial of 3
        ref_eng = _engine(model, kv_dtype="int8")
        hr = ref_eng.add_request(prompt, max_new_tokens=6, seed=5)
        while not hr.is_finished:
            ref_eng.step()
        src = _engine(model, kv_dtype="int8", host_kv_blocks=16,
                      spill_idle_steps=2)
        dst = _engine(model, kv_dtype="int8")
        req = src.add_request(prompt, max_new_tokens=6, seed=5,
                              hold_after_prefill=True)
        for _ in range(8):
            src.step()
        n_data = blocks_for_tokens(len(prompt), BS)
        assert src._host_tier.resident == n_data       # scales spilled too
        mig = src.export_request(req)
        new_req, _ = dst.adopt_migration(mig, src)
        sk_src = np.asarray(src._sk)
        sk_dst = np.asarray(dst._sk)
        dtable = dst._slot_blocks[new_req.slot]
        pos = int(mig["pos"])
        for i in range(n_data):
            valid = min(BS, pos - i * BS)              # partial last block
            assert np.array_equal(sk_src[:, mig["table"][i], :valid],
                                  sk_dst[:, dtable[i], :valid]), \
                f"scale rows of block {i} diverged"
        src.finish_migrated(req)
        while not new_req.is_finished:
            dst.step()
        assert new_req.tokens == hr.tokens
        assert _engine_reconciles(src) and _engine_reconciles(dst)

    def test_fleet_rolls_up_tier_stats(self, model):
        """Fleet stats aggregate the per-engine tier view; a tiered
        disaggregated stream completes with zero lost requests."""
        rng = np.random.default_rng(35)
        prompts = _prompts(rng, (24, 9, 40, 17))
        before = counters.snapshot()
        fleet = _fleet(model, prefill_replicas=1, host_kv_blocks=16)
        hs = [fleet.submit(p, seed=i, max_new_tokens=6)
              for i, p in enumerate(prompts)]
        fleet.join(hs)
        st = fleet.stats()["kv"]
        fleet.drain()
        assert st["host_tier_capacity"] == 16 * 2      # both replicas
        assert st["host_tier_blocks"] >= 0
        assert {"host_arena_bytes", "tier_spilled",
                "tier_restored"} <= set(st)
        d = counters.delta(before)
        assert d.get("serving.fleet.lost", 0) == 0
        assert all(h.finish_reason == "length" for h in hs)
