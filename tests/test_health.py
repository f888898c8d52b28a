"""Health plane (profiler.health): windowed signals, SLO burn-rate
alerting, invariant watchdogs, alert lifecycle, and the live wiring.

The load-bearing contracts:

  * window math — snapshot deltas/rates are counter-reset safe and
    histogram windows are element-wise bucket subtraction
    (``Histogram.delta``), so a window percentile reflects ONLY the
    samples recorded inside the window;
  * burn-rate SLOs — an alert needs EVERY configured window burning
    (fast = still happening, slow = sustained), fires once (dedupe),
    writes one flight bundle naming the rule + window, and resolves when
    the measured burn drops;
  * watchdogs — each offline check_counters invariant promoted to a live
    rule fires on its violation and stays silent on a clean run;
  * chaos — ``slow_decode`` fires exactly ``itl_burn`` on a real fleet
    and ``kv_pool_exhausted`` fires exactly ``kv_backpressure`` on a
    paged engine, each leaving a postmortem dump naming the rule;
  * zero-overhead off — with ``FLAGS_health`` off (the default), ticks
    are no-ops and NO counter moves;
  * ops — ``/alerts``, ``/slo``, ``/signals`` serve live JSON and
    ``/healthz`` degrades while an alert fires.
"""

import json
import urllib.error
import urllib.request

import pytest

import paddle_tpu as paddle
from paddle_tpu.core import flags as core_flags
from paddle_tpu.profiler import counters, flight, health, metrics
from paddle_tpu.profiler.health import (SLO, HealthMonitor, Snapshot,
                                        Watchdog, Window)
from paddle_tpu.profiler.metrics import Histogram
from paddle_tpu.profiler.ops import OpsServer
from paddle_tpu.resilience import faultinject


@pytest.fixture(autouse=True)
def _health_flags(tmp_path):
    """Health ON with per-call ticks for these tests; flight dumps into
    the test's tmp dir; everything restored after."""
    core_flags.set_flags({"FLAGS_health": True,
                          "FLAGS_health_interval_s": 0.0})
    flight.configure(directory=str(tmp_path))
    yield
    core_flags.set_flags({"FLAGS_health": False,
                          "FLAGS_health_interval_s": 1.0})
    flight.clear()


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


def _fired(before):
    """health.alerts.fired.* movement since ``before`` (a counter
    snapshot)."""
    return {k: v for k, v in counters.delta(before).items()
            if k.startswith("health.alerts.fired.")}


# -- window math -------------------------------------------------------------
class TestWindowMath:
    def test_delta_and_rate(self):
        w = Window(Snapshot(10.0, 0, {"a": 5, "b": 2}, {}),
                   Snapshot(14.0, 1, {"a": 9, "b": 2, "c": 7}, {}))
        assert w.delta("a") == 4
        assert w.delta("b") == 0
        assert w.delta("c") == 7          # born inside the window
        assert w.delta("missing") == 0
        assert w.seconds == pytest.approx(4.0)
        assert w.rate("a") == pytest.approx(1.0)

    def test_counter_reset_restarts_from_zero(self):
        # counters.reset() between snapshots: the window must report the
        # post-reset value, never a negative delta
        w = Window(Snapshot(0.0, 0, {"c": 100}, {}),
                   Snapshot(5.0, 1, {"c": 3}, {}))
        assert w.delta("c") == 3
        assert w.rate("c") == pytest.approx(0.6)

    def test_gauge_reads_window_end(self):
        w = Window(Snapshot(0.0, 0, {"g": 1.0}, {}),
                   Snapshot(1.0, 1, {"g": 7.5}, {}))
        assert w.gauge("g") == 7.5
        assert w.gauge("absent", default=-1) == -1

    def test_histogram_bucket_delta(self):
        h = Histogram("t", "ns")
        for v in (1e6, 2e6, 4e6):
            h.record(v)
        prev = h.copy()
        for v in (32e6, 64e6):
            h.record(v)
        d = h.delta(prev)
        assert d.count == 2
        assert d.sum == pytest.approx(96e6)
        # the window p95 sees ONLY the new (slow) samples
        assert d.percentile(95) > 30e6
        # lifetime p95 would have been dragged down by the old fast ones
        assert h.percentile(50) < 10e6

    def test_histogram_delta_reset_safe(self):
        prev = Histogram("t", "ns")
        for _ in range(10):
            prev.record(5e6)
        cur = Histogram("t", "ns")     # registry was reset: fresh hist
        cur.record(1e6)
        d = cur.delta(prev)            # prev is not a prefix of cur
        assert d.count == 1            # full current state, not negative
        assert d.sum == pytest.approx(1e6)

    def test_window_hist_delta_and_percentile(self):
        h = Histogram("w", "ns")
        h.record(1e6)
        s1 = Snapshot(0.0, 0, {}, {"w": h.copy()})
        for _ in range(20):
            h.record(40e6)
        s2 = Snapshot(1.0, 1, {}, {"w": h.copy()})
        w = Window(s1, s2)
        assert w.hist_delta("w").count == 20
        assert w.percentile("w", 95) > 20e6
        assert w.hist_delta("missing") is None
        assert w.percentile("missing", 95) is None

    def test_monitor_window_spans(self):
        mon = HealthMonitor(rules=[])
        assert mon.window(5.0) is None           # <2 snapshots
        for t in range(8):
            mon.tick(now=float(t))
        w = mon.window(5.0)
        assert w.end.ts == 7.0
        assert w.seconds >= 5.0
        # wider than the ring: degrade to the widest available span
        w = mon.window(1000.0)
        assert w.start.ts == 0.0


# -- SLO burn-rate lifecycle -------------------------------------------------
def _lat_slo(name="lat_burn", target=10e6, windows=((5.0, 1.0),
                                                    (30.0, 1.0))):
    return SLO(name, ("hist_p95", "test.health.lat_ns"), target,
               windows=windows)


class TestBurnRate:
    def test_fires_then_resolves_across_synthetic_windows(self):
        h = metrics.get_histogram("test.health.lat_ns", "ns")
        mon = HealthMonitor(rules=[_lat_slo()])
        before = counters.snapshot()
        mon.tick(now=0.0)
        for _ in range(20):
            h.record(50e6)             # 5x the 10ms objective
        mon.tick(now=1.0)
        assert [a.name for a in mon.firing()] == ["lat_burn"]
        assert _fired(before) == {"health.alerts.fired.lat_burn": 1}
        assert mon.admission_level() == "critical"
        # healthy traffic; the slow samples age out of the fast window
        t = 1.0
        for _ in range(12):
            t += 1.0
            for _ in range(30):
                h.record(1e6)
            mon.tick(now=t)
        assert mon.firing() == []
        assert mon.admission_level() == "ok"
        d = counters.delta(before)
        assert d.get("health.alerts.resolved.lat_burn") == 1
        assert d.get("health.alerts.fired.lat_burn") == 1   # no refire

    def test_needs_every_window_burning(self):
        # slow burn only in the fast window -> once the ring spans the
        # slow window, the alert must NOT fire on a short blip
        h = metrics.get_histogram("test.health.blip_ns", "ns")
        slo = SLO("blip_burn", ("hist_p95", "test.health.blip_ns"), 10e6,
                  windows=((2.0, 1.0), (30.0, 4.0)))
        mon = HealthMonitor(rules=[slo])
        t = 0.0
        for _ in range(35):            # ring spans > 30s of clean history
            t += 1.0
            for _ in range(5):
                h.record(1e6)
            mon.tick(now=t)
        for _ in range(5):
            h.record(30e6)             # blip: burn 3 in the fast window
        mon.tick(now=t + 1.0)
        st = [s for s in mon.slo_status() if s["name"] == "blip_burn"][0]
        assert st["windows"][0]["burning"] is True
        assert st["windows"][1]["burning"] is False
        assert mon.firing() == []

    def test_abstains_below_min_count(self):
        h = metrics.get_histogram("test.health.sparse_ns", "ns")
        slo = SLO("sparse_burn", ("hist_p95", "test.health.sparse_ns"),
                  1e6, min_count=8)
        mon = HealthMonitor(rules=[slo])
        mon.tick(now=0.0)
        for _ in range(3):             # violating, but too few samples
            h.record(100e6)
        mon.tick(now=1.0)
        assert mon.firing() == []
        st = mon.slo_status()[0]
        assert st["windows"][0]["value"] is None

    def test_ratio_signal(self):
        slo = SLO("err_ratio", ("ratio", "test.health.errs",
                                "test.health.reqs"), 0.01,
                  windows=((5.0, 1.0),))
        mon = HealthMonitor(rules=[slo])
        mon.tick(now=0.0)
        counters.inc("test.health.reqs", 100)
        counters.inc("test.health.errs", 7)
        mon.tick(now=1.0)
        assert [a.name for a in mon.firing()] == ["err_ratio"]
        st = mon.slo_status()[0]
        assert st["windows"][0]["value"] == pytest.approx(0.07)


# -- watchdogs ---------------------------------------------------------------
class TestWatchdogs:
    def _mon_with(self, wd, **kw):
        return HealthMonitor(rules=[wd], **kw)

    def test_retrace_storm(self):
        wd = [w for w in health.default_watchdogs()
              if w.name == "retrace_storm"][0]
        mon = self._mon_with(wd)
        mon.tick(now=0.0)
        mon.tick(now=1.0)
        assert mon.firing() == []                    # clean: no retrace
        counters.inc("serving.retraces")
        mon.tick(now=2.0)
        assert [a.name for a in mon.firing()] == ["retrace_storm"]

    def test_kv_conservation(self):
        from paddle_tpu.serving.kvcache import BlockPool
        wd = [w for w in health.default_watchdogs()
              if w.name == "kv_conservation"][0]
        pool = BlockPool(n_blocks=8, block_size=4)
        holder = type("Eng", (), {})()
        holder.pool = pool
        mon = self._mon_with(wd).attach(holder)
        mon.tick(now=0.0)
        b = pool.alloc()
        mon.tick(now=1.0)
        assert mon.firing() == []                    # clean accounting
        pool._free.append(b)        # corrupt: block free AND referenced
        mon.tick(now=2.0)
        firing = mon.firing()
        assert [a.name for a in firing] == ["kv_conservation"]
        assert firing[0].severity == "critical"
        assert firing[0].detail["free_with_refs"] == 1

    def test_kv_backpressure(self):
        wd = [w for w in health.default_watchdogs()
              if w.name == "kv_backpressure"][0]
        mon = self._mon_with(wd)
        mon.tick(now=0.0)
        mon.tick(now=1.0)
        assert mon.firing() == []
        counters.inc("serving.kv.pool_exhausted")
        mon.tick(now=2.0)
        assert [a.name for a in mon.firing()] == ["kv_backpressure"]

    def test_goodput_accounted(self):
        wd = [w for w in health.default_watchdogs()
              if w.name == "goodput_accounted"][0]
        mon = self._mon_with(wd)
        counters.set_gauge("goodput.wall_ns", 0)     # no ledger report yet
        counters.set_gauge("goodput.accounted", 0.5)
        mon.tick(now=0.0)
        mon.tick(now=1.0)
        assert mon.firing() == []                    # abstain: no wall
        counters.set_gauge("goodput.wall_ns", 1e9)
        counters.set_gauge("goodput.accounted", 0.999)
        mon.tick(now=2.0)
        assert mon.firing() == []                    # healthy ledger
        counters.set_gauge("goodput.accounted", 0.5)
        mon.tick(now=3.0)
        assert [a.name for a in mon.firing()] == ["goodput_accounted"]
        counters.set_gauge("goodput.wall_ns", 0)

    def test_spec_acceptance_collapse(self):
        wd = [w for w in health.default_watchdogs()
              if w.name == "spec_acceptance"][0]
        mon = self._mon_with(wd)
        counters.set_gauge("serving.spec.acceptance", 0.01)
        mon.tick(now=0.0)
        mon.tick(now=1.0)
        assert mon.firing() == []          # collapse but no draft volume
        counters.inc("serving.spec.drafted", 32)
        mon.tick(now=2.0)
        assert [a.name for a in mon.firing()] == ["spec_acceptance"]
        counters.set_gauge("serving.spec.acceptance", 0.8)
        counters.inc("serving.spec.drafted", 32)
        mon.tick(now=3.0)
        assert mon.firing() == []          # healthy draft: resolves

    def test_prefetch_stall(self):
        wd = [w for w in health.default_watchdogs()
              if w.name == "prefetch_stall"][0]
        mon = self._mon_with(wd)
        mon.tick(now=0.0)
        counters.inc("io.prefetch_stall_ns", 1e9)
        mon.tick(now=10.0)                 # 10% of the window: fine
        assert mon.firing() == []
        counters.inc("io.prefetch_stall_ns", 13e9)
        mon.tick(now=20.0)                 # 70% of the 20s window
        assert [a.name for a in mon.firing()] == ["prefetch_stall"]

    def test_broken_rule_never_kills_the_tick(self):
        def boom(w, m):
            raise RuntimeError("rule bug")
        mon = self._mon_with(Watchdog("broken_rule", boom))
        mon.tick(now=0.0)
        mon.tick(now=1.0)                  # must not raise
        assert mon.firing() == []
        assert mon.ticks == 2


# -- alert lifecycle ---------------------------------------------------------
class TestAlertLifecycle:
    def test_dedupe_single_fire_single_dump(self):
        state = [True]
        mon = HealthMonitor(rules=[
            Watchdog("dedupe_rule", lambda w, m: (state[0], {}))])
        before = counters.snapshot()
        mon.tick(now=0.0)
        for t in range(1, 5):
            mon.tick(now=float(t))         # keeps firing every tick
        d = counters.delta(before)
        assert d.get("health.alerts.fired.dedupe_rule") == 1
        assert d.get("flight.dumps.health_dedupe_rule") == 1
        alert = mon.firing()[0]
        assert alert.fired_count == 1
        assert alert.last > alert.since    # refreshed while deduped

    def test_refire_after_resolve_counts_and_dumps_again(self):
        state = [True]
        mon = HealthMonitor(rules=[
            Watchdog("flappy_rule", lambda w, m: (state[0], {}))])
        before = counters.snapshot()
        mon.tick(now=0.0)
        mon.tick(now=1.0)                  # fire #1
        state[0] = False
        mon.tick(now=2.0)                  # resolve
        assert mon.firing() == []
        state[0] = True
        mon.tick(now=3.0)                  # fire #2
        d = counters.delta(before)
        assert d.get("health.alerts.fired.flappy_rule") == 2
        assert d.get("health.alerts.resolved.flappy_rule") == 1
        assert d.get("flight.dumps.health_flappy_rule") == 2
        assert mon.firing()[0].fired_count == 2

    def test_admission_level_follows_severity(self):
        deg, crit = [False], [False]
        mon = HealthMonitor(rules=[
            Watchdog("soft_rule", lambda w, m: (deg[0], {})),
            Watchdog("hard_rule", lambda w, m: (crit[0], {}),
                     severity="critical")])
        mon.tick(now=0.0)
        mon.tick(now=1.0)
        assert mon.admission_level() == "ok"
        deg[0] = True
        mon.tick(now=2.0)
        assert mon.admission_level() == "degraded"
        assert counters.get("health.admission_level") == 1
        crit[0] = True
        mon.tick(now=3.0)
        assert mon.admission_level() == "critical"
        assert counters.get("health.admission_level") == 2
        deg[0] = crit[0] = False
        mon.tick(now=4.0)
        assert mon.admission_level() == "ok"
        assert counters.get("health.admission_level") == 0

    def test_dump_bundle_names_rule_and_window(self, tmp_path):
        mon = HealthMonitor(rules=[
            Watchdog("bundle_rule", lambda w, m: (True, {"x": 1}))])
        mon.tick(now=0.0)
        counters.inc("test.health.moved")
        mon.tick(now=1.0)
        path = flight.last_dump_path()
        assert path is not None
        b = flight.load(path)
        assert b["reason"] == "health_bundle_rule"
        assert b["context"]["rule"] == "bundle_rule"
        assert b["context"]["detail"] == {"x": 1}
        win = b["context"]["window"]
        assert win["seconds"] == pytest.approx(1.0)
        assert win["delta"].get("test.health.moved") == 1
        # the bundle also embeds the live alert set via the provider hook
        assert b["health"]["admission_level"] == "degraded"
        assert b["health"]["alerts"][0]["name"] == "bundle_rule"


# -- zero-overhead off -------------------------------------------------------
class TestOffMode:
    def test_off_ticks_move_nothing(self):
        core_flags.set_flags({"FLAGS_health": False})
        mon = HealthMonitor()
        before = counters.snapshot()
        for _ in range(10):
            assert mon.maybe_tick() is None
        assert counters.delta(before) == {}
        assert mon.ticks == 0
        assert len(mon._ring) == 0
        assert mon.summary() == {"enabled": False,
                                 "admission_level": "ok",
                                 "alerts": [], "ticks": 0}
        core_flags.set_flags({"FLAGS_health": True})

    def test_interval_gates_tick_cadence(self):
        mon = HealthMonitor(rules=[], interval_s=10.0)
        assert mon.maybe_tick(now=0.0) is not None
        assert mon.maybe_tick(now=5.0) is None       # too soon
        assert mon.maybe_tick(now=10.0) is not None


# -- chaos-driven firing on real serving stacks ------------------------------
class TestChaos:
    def test_slow_decode_fires_exactly_itl_burn(self, tmp_path,
                                                monkeypatch):
        """Sized so that the machine's load decides neither leg: under
        six test workers the clean leg's ITL p95 read 24 ms against the
        default 15 ms target (with a 20 ms stall) and fired on its own.
        Here the target is 100 ms, four times that, and the injected
        stall 250 ms, over twice the target."""
        from paddle_tpu.serving import fleet as fleet_mod
        monkeypatch.setattr(fleet_mod, "SLOW_DECODE_STALL_S", 0.25)
        rules = [SLO("itl_burn", ("hist_p95", "serving.itl_ns"), 100e6)
                 if r.name == "itl_burn" else r
                 for r in health.default_rules()]
        fl = fleet_mod.ServingFleet(
            _model(), replicas=2, threaded=False, max_slots=2,
            max_seq_len=32, min_bucket=4, queue_size=16,
            heartbeat_timeout_s=30.0, warm_buckets=(3, 4),
            health_kw={"rules": rules})
        try:
            before = counters.snapshot()
            chs = [fl.submit([1, 2, 3], max_new_tokens=6)
                   for _ in range(4)]
            fl.join(chs)
            assert _fired(before) == {}              # clean leg: silence
            # another prompt than the clean leg's: a prefix hit would
            # compile the copy-on-write program inside the window, which
            # fleet warm-up does not cover (ROADMAP D6), and
            # ``retrace_storm`` would rightly fire beside ``itl_burn``
            chs = [fl.submit([4, 5, 6], max_new_tokens=8)
                   for _ in range(4)]
            with faultinject.fault_schedule(
                    f"slow_decode@{chs[0].rid}*8"):
                fl.join(chs)
            fired = _fired(before)
            assert fired == {"health.alerts.fired.itl_burn": 1}
            b = flight.load(flight.last_dump_path())
            assert b["reason"] == "health_itl_burn"
            assert b["context"]["rule"] == "itl_burn"
            assert b["context"]["window"]["seconds"] > 0
            # the recommendation reaches both stats surfaces
            assert fl.stats()["health"]["admission_level"] == "critical"
            rst = fl.router.stats()["health"]
            assert rst["admission_level"] == "critical"
            assert "itl_burn" in rst["alerts"]
        finally:
            fl.close()

    def test_kv_pool_exhausted_fires_exactly_kv_backpressure(self):
        from paddle_tpu.serving import LLMEngine
        eng = LLMEngine(_model(), max_slots=3,
                        max_seq_len=32, min_bucket=4, block_size=4,
                        prefill_chunk=8)
        mon = HealthMonitor(
            rules=[w for w in health.default_watchdogs()
                   if w.name in ("kv_backpressure", "kv_conservation")],
            interval_s=0.0).attach(eng)
        # warm first (compiles happen BEFORE the first snapshot)
        h0 = eng.add_request([1, 2, 3], max_new_tokens=3, seed=0)
        while not h0.is_finished:
            eng.step()
        mon.maybe_tick()
        before = counters.snapshot()
        h1 = eng.add_request([4, 5, 6], max_new_tokens=3, seed=1)
        with faultinject.fault_schedule(f"kv_pool_exhausted@{h1.rid}"):
            n = 0
            while not h1.is_finished:
                eng.step()
                mon.maybe_tick()
                n += 1
                assert n < 300
        fired = _fired(before)
        assert fired == {"health.alerts.fired.kv_backpressure": 1}
        b = flight.load(flight.last_dump_path())
        assert b["reason"] == "health_kv_backpressure"
        assert b["context"]["rule"] == "kv_backpressure"
        win = b["context"]["window"]
        assert win["delta"].get("serving.kv.pool_exhausted", 0) >= 1


# -- ops endpoints + stats wiring --------------------------------------------
class TestOpsEndpoints:
    def _get(self, srv, path):
        body = urllib.request.urlopen(srv.url(path), timeout=10).read()
        return json.loads(body)

    def test_alerts_slo_signals_live(self):
        h = metrics.get_histogram("test.health.ops_ns", "ns")
        mon = HealthMonitor(rules=[
            SLO("ops_burn", ("hist_p95", "test.health.ops_ns"), 10e6,
                windows=((5.0, 1.0),))])
        mon.tick(now=0.0)
        for _ in range(10):
            h.record(1e6)
        counters.inc("test.health.ops_reqs", 5)
        mon.tick(now=1.0)
        with OpsServer(monitor=mon) as srv:
            alerts = self._get(srv, "/alerts")
            assert alerts["enabled"] is True
            assert alerts["admission_level"] == "ok"
            assert alerts["firing"] == []
            slo = self._get(srv, "/slo")
            assert slo["slos"][0]["name"] == "ops_burn"
            assert slo["slos"][0]["windows"][0]["burn"] is not None
            sig = self._get(srv, "/signals")
            assert sig["rates_per_s"].get("test.health.ops_reqs") == \
                pytest.approx(5.0)
            assert "test.health.ops_ns" in sig["p95"]

    def test_healthz_degrades_while_firing(self):
        mon = HealthMonitor(rules=[
            Watchdog("ops_rule", lambda w, m: (True, {}))])
        mon.tick(now=0.0)
        mon.tick(now=1.0)
        assert mon.firing()
        with OpsServer(monitor=mon) as srv:
            hz = self._get(srv, "/healthz")
            assert hz["status"] == "degraded"
            assert hz["health"]["alerts"] == ["ops_rule"]
            alerts = self._get(srv, "/alerts")
            assert alerts["admission_level"] == "degraded"
            assert alerts["firing"] == ["ops_rule"]
            assert alerts["alerts"][0]["state"] == "firing"

    def test_endpoints_404_without_monitor(self):
        with OpsServer() as srv:
            for ep in ("/alerts", "/slo", "/signals"):
                with pytest.raises(urllib.error.HTTPError):
                    urllib.request.urlopen(srv.url(ep), timeout=10)

    def test_router_stats_without_fleet_is_disabled_stub(self):
        from paddle_tpu.serving.router import Router
        st = Router().stats()
        assert st["health"]["enabled"] is False
        assert st["health"]["admission_level"] == "ok"


class TestAttach:
    def test_attach_chains_and_dedupes(self):
        mon = HealthMonitor(rules=[])
        obj = object()
        assert mon.attach(obj) is mon
        mon.attach(obj)
        mon.attach(None)
        assert mon._pools() == [obj]
