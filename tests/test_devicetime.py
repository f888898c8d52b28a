"""Device-time & efficiency plane (``profiler.devicetime``): ledger math
(MFU / roofline joins and their edge cases), sampling economics (OFF is
free, ON pays exactly the budgeted fences, thread-safe arming), the
watchdogs, and the ``/programs`` + ``POST /profile`` ops endpoints."""

import json
import threading
import urllib.request
from urllib.error import HTTPError

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import flags as core_flags
from paddle_tpu.profiler import counters, devicetime, health, metrics
from paddle_tpu.profiler.ops import OpsServer

MiB = 1024 * 1024


@pytest.fixture(autouse=True)
def _dt_isolation():
    saved = {k: core_flags.flag(k) for k in
             ("FLAGS_device_time_sample", "FLAGS_peak_tflops",
              "FLAGS_peak_hbm_gbps", "FLAGS_device_telemetry")}
    devicetime.reset()
    yield
    core_flags.set_flags(saved)
    devicetime.reset()


def _seed_stats(name, **fields):
    """Stand in for capture_program_stats: plant AOT FLOPs/HBM bytes."""
    metrics.record_program(name, **fields)


# -- ledger math -------------------------------------------------------------
class TestLedgerMath:
    def test_mfu_and_compute_bound_roofline(self):
        core_flags.set_flags({"FLAGS_peak_tflops": 197.0,
                              "FLAGS_peak_hbm_gbps": 819.0})
        _seed_stats("dtm.matmul", flops=2e9, arg_bytes=MiB, out_bytes=MiB)
        devicetime._record_sample("dtm.matmul", 1e-3)   # 1ms sample
        row = devicetime.snapshot()["programs"][0]
        assert row["name"] == "dtm.matmul"
        assert row["tflops"] == pytest.approx(2.0, rel=1e-6)
        assert row["mfu"] == pytest.approx(2.0 / 197.0, rel=1e-6)
        assert row["hbm_gbps"] == pytest.approx(2 * MiB / 1e-3 / 1e9)
        # AI ~953 FLOP/B >> balance 197e12/819e9 ~240 FLOP/B
        assert row["ai"] == pytest.approx(2e9 / (2 * MiB))
        assert row["roofline"] == "compute-bound"
        # gauges republished per sample
        st = metrics.program_stats("dtm.matmul")
        assert st["mfu"] == pytest.approx(2.0 / 197.0, rel=1e-6)
        assert st["device_time_mean_ms"] == pytest.approx(1.0)

    def test_zero_flop_copy_is_bandwidth_bound(self):
        core_flags.set_flags({"FLAGS_peak_tflops": 197.0,
                              "FLAGS_peak_hbm_gbps": 819.0})
        _seed_stats("dtm.copy", arg_bytes=4 * MiB, out_bytes=4 * MiB)
        devicetime._record_sample("dtm.copy", 1e-3)
        row = devicetime.snapshot()["programs"][0]
        assert row["tflops"] is None and row["mfu"] is None
        assert row["hbm_gbps"] == pytest.approx(8 * MiB / 1e-3 / 1e9)
        assert row["roofline"] == "bandwidth-bound"

    def test_missing_peak_flags_degrade_to_unknown(self):
        core_flags.set_flags({"FLAGS_peak_tflops": 0.0,
                              "FLAGS_peak_hbm_gbps": 0.0})
        _seed_stats("dtm.nopeak", flops=2e9, arg_bytes=MiB, out_bytes=MiB)
        devicetime._record_sample("dtm.nopeak", 1e-3)
        row = devicetime.snapshot()["programs"][0]
        assert row["tflops"] == pytest.approx(2.0, rel=1e-6)  # flag-free
        assert row["mfu"] is None
        assert row["roofline"] == "unknown"

    def test_no_aot_stats_degrades_field_by_field(self):
        devicetime._record_sample("dtm.uncaptured", 1e-3)
        row = devicetime.snapshot()["programs"][0]
        assert row["mean_ms"] == pytest.approx(1.0)
        for k in ("tflops", "mfu", "hbm_gbps", "ai"):
            assert row[k] is None
        assert row["roofline"] == "unknown"

    def test_int8_decorated_program_name_joins(self):
        core_flags.set_flags({"FLAGS_peak_tflops": 197.0,
                              "FLAGS_peak_hbm_gbps": 819.0})
        name = "serving.decode_paged@off:int8"   # _prog_key-decorated
        _seed_stats(name, flops=1e9, arg_bytes=MiB, out_bytes=MiB)
        devicetime._record_sample(name, 1e-3)
        row = devicetime.snapshot()["programs"][0]
        assert row["name"] == name
        assert row["mfu"] is not None

    def test_share_and_est_total(self):
        devicetime._record_sample("dtm.a", 3e-3)
        devicetime._record_sample("dtm.b", 1e-3)
        snap = devicetime.snapshot()
        assert snap["est_total_s"] == pytest.approx(4e-3)
        by = {r["name"]: r for r in snap["programs"]}
        assert by["dtm.a"]["share"] == pytest.approx(0.75)
        assert snap["programs"][0]["name"] == "dtm.a"   # sorted by time

    def test_regression_ratio_trailing_vs_baseline(self):
        for _ in range(8):
            devicetime._record_sample("dtm.reg", 1e-3)
        for _ in range(8):
            devicetime._record_sample("dtm.reg", 4e-3)
        row = devicetime.snapshot()["programs"][0]
        assert row["regression"] == pytest.approx(4.0, rel=1e-6)

    def test_summary_table_renders(self):
        assert "no device-time samples" in devicetime.summary()
        _seed_stats("dtm.tab", flops=2e9, arg_bytes=MiB, out_bytes=MiB)
        devicetime._record_sample("dtm.tab", 1e-3)
        txt = devicetime.summary()
        assert "dtm.tab" in txt and "MFU" in txt and "Bound" in txt

    def test_bench_block_shape(self):
        devicetime._record_sample("dtm.blk", 2e-3)
        blk = devicetime.bench_block()
        assert blk["programs"]["dtm.blk"]["share"] == pytest.approx(1.0)
        assert blk["programs"]["dtm.blk"]["mean_ms"] == pytest.approx(2.0)


# -- sampling economics ------------------------------------------------------
class TestSampling:
    def test_off_is_zero_movement(self):
        before = counters.snapshot()
        for _ in range(16):
            assert devicetime.note("dts.off") is None
        d = counters.delta(before)
        assert not [k for k in d if k.startswith(("jit.devicetime.",
                                                  "program."))]
        assert devicetime.snapshot()["programs"] == []
        assert not devicetime.enabled()

    def test_every_nth_exact_budget(self):
        core_flags.set_flags({"FLAGS_device_time_sample": 4})
        devicetime.reset()
        before = counters.snapshot()
        tokens = [devicetime.note("dts.n4") for _ in range(8)]
        armed = [t for t in tokens if t is not None]
        assert len(armed) == 2                 # seq 0 and 4
        for t in armed:
            assert devicetime.observe(t) is not None
        d = counters.delta(before)
        assert d["jit.devicetime.dispatches"] == 8
        assert d["jit.devicetime.sampled_syncs"] == 2
        row = devicetime.snapshot()["programs"][0]
        assert (row["dispatches"], row["sampled"]) == (8, 2)

    def test_observe_none_token_is_noop(self):
        assert devicetime.observe(None) is None

    def test_thread_safe_exact_ceil(self):
        core_flags.set_flags({"FLAGS_device_time_sample": 2})
        devicetime.reset()
        before = counters.snapshot()

        def pump(i):
            for _ in range(25):
                devicetime.observe(devicetime.note(f"dts.t{i}"))

        threads = [threading.Thread(target=pump, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        d = counters.delta(before)
        assert d["jit.devicetime.dispatches"] == 100
        assert d["jit.devicetime.sampled_syncs"] == 50   # ceil(100/2)

    def test_flag_off_keeps_ledger_until_reset(self):
        core_flags.set_flags({"FLAGS_device_time_sample": 1})
        devicetime.observe(devicetime.note("dts.keep"))
        core_flags.set_flags({"FLAGS_device_time_sample": 0})
        assert devicetime.snapshot()["programs"]   # observer never resets
        devicetime.reset()
        assert devicetime.snapshot()["programs"] == []


# -- real engine: identity + budget under sampling ---------------------------
class TestEngineSampling:
    def test_paged_engine_identity_and_budget(self):
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        from paddle_tpu.serving import LLMEngine
        paddle.seed(31)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=64, hidden_size=32, num_layers=1, num_heads=4,
            max_seq_len=32, use_flash_attention=False))
        model.eval()
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, 64, size=n).tolist() for n in (5, 9)]

        def engine():
            return LLMEngine(model, max_slots=2, max_seq_len=32,
                             min_bucket=4,
                             block_size=4, prefill_chunk=8)

        def run(eng):
            hs = [eng.add_request(p, max_new_tokens=3) for p in prompts]
            while not all(h.is_finished for h in hs):
                eng.step()
            return [list(h.tokens) for h in hs]

        base_eng = engine()
        run(base_eng)                       # warm: compiles
        base = run(base_eng)                # reference tokens

        eng = engine()
        run(eng)                            # warm (sampling still off)
        core_flags.set_flags({"FLAGS_device_time_sample": 2})
        devicetime.reset()
        before = counters.snapshot()
        on = run(eng)
        d = counters.delta(before)
        core_flags.set_flags({"FLAGS_device_time_sample": 0})
        assert on == base                   # token identity under fences
        disp = d.get("jit.devicetime.dispatches", 0)
        assert disp > 0
        assert d.get("jit.devicetime.sampled_syncs", 0) == -(-disp // 2)
        assert not d.get("serving.retraces", 0)
        names = {r["name"] for r in devicetime.snapshot()["programs"]}
        assert "serving.decode_paged" in names


# -- watchdogs ---------------------------------------------------------------
class TestWatchdogs:
    def _mon(self, name):
        wd = [w for w in health.default_watchdogs() if w.name == name][0]
        return health.HealthMonitor(rules=[wd])

    def test_mfu_collapse_fires_then_resolves(self):
        core_flags.set_flags({"FLAGS_peak_tflops": 197.0,
                              "FLAGS_peak_hbm_gbps": 819.0})
        mon = self._mon("mfu_collapse")
        mon.tick(now=0.0)
        mon.tick(now=1.0)
        assert mon.firing() == []           # no sampling activity: gated
        # dominant program at ~1% MFU with enough samples
        _seed_stats("dtw.slow", flops=2e9, arg_bytes=MiB, out_bytes=MiB)
        for _ in range(4):
            devicetime._record_sample("dtw.slow", 1e-3)   # 2 TFLOP/s
        mon.tick(now=2.0)
        firing = mon.firing()
        assert [a.name for a in firing] == ["mfu_collapse"]
        assert firing[0].detail["program"] == "dtw.slow"
        # once the sampled window ages past the 15s watchdog span the
        # sampling-activity gate closes and the alert resolves
        mon.tick(now=18.0)
        assert mon.firing() == []

    def test_device_time_regression_fires(self):
        mon = self._mon("device_time_regression")
        mon.tick(now=0.0)
        for _ in range(8):
            devicetime._record_sample("dtw.reg", 1e-3)
        for _ in range(8):
            devicetime._record_sample("dtw.reg", 3e-3)   # 3x baseline
        mon.tick(now=1.0)
        firing = mon.firing()
        assert [a.name for a in firing] == ["device_time_regression"]
        assert firing[0].detail["regression"] == pytest.approx(3.0,
                                                               rel=1e-6)


# -- ops endpoints -----------------------------------------------------------
class TestEndpoints:
    def test_programs_endpoint(self):
        core_flags.set_flags({"FLAGS_peak_tflops": 197.0,
                              "FLAGS_peak_hbm_gbps": 819.0})
        _seed_stats("dte.prog", flops=2e9, arg_bytes=MiB, out_bytes=MiB)
        devicetime._record_sample("dte.prog", 1e-3)
        with OpsServer() as srv:
            with urllib.request.urlopen(srv.url("/programs"),
                                        timeout=10) as r:
                obj = json.loads(r.read())
        names = [p["name"] for p in obj["programs"]]
        assert "dte.prog" in names
        row = obj["programs"][names.index("dte.prog")]
        assert row["mfu"] is not None and row["roofline"] == "compute-bound"
        assert obj["program_stats"]["dte.prog"]["flops"] == 2e9

    def test_profile_endpoint_capture_and_single_flight(self, monkeypatch):
        calls = []
        started = threading.Event()
        release = threading.Event()

        def fake_start(path):
            calls.append(("start", path))
            started.set()

        def fake_stop():
            calls.append(("stop",))

        import time as _time
        import types
        monkeypatch.setattr(devicetime, "_start_trace", fake_start)
        monkeypatch.setattr(devicetime, "_stop_trace", fake_stop)
        # swap the module's time handle so only capture_profile's sleep
        # blocks on our event (the global time module stays untouched)
        monkeypatch.setattr(devicetime, "time", types.SimpleNamespace(
            sleep=lambda s: release.wait(timeout=5.0),
            perf_counter=_time.perf_counter))
        with OpsServer() as srv:
            # a long capture in flight ...
            def long_capture():
                devicetime.capture_profile(400)

            t = threading.Thread(target=long_capture)
            t.start()
            assert started.wait(timeout=5.0)
            # ... makes a concurrent POST bounce with 409
            with pytest.raises(HTTPError) as ei:
                urllib.request.urlopen(srv.url("/profile?ms=5"), data=b"",
                                       timeout=10)
            assert ei.value.code == 409
            release.set()
            t.join(timeout=5.0)
            # and once free, the POST succeeds and returns the dump path
            with urllib.request.urlopen(srv.url("/profile?ms=5"),
                                        data=b"", timeout=10) as r:
                obj = json.loads(r.read())
        assert obj["ms"] == 5 and "ptpu-profile-" in obj["path"]
        assert calls[0][0] == "start" and ("stop",) in calls

    def test_profile_bad_ms_is_400(self):
        with OpsServer() as srv:
            for q in ("ms=abc", "ms=0", "ms=-3"):
                with pytest.raises(HTTPError) as ei:
                    urllib.request.urlopen(srv.url(f"/profile?{q}"),
                                           data=b"", timeout=10)
                assert ei.value.code == 400

    def test_capture_profile_clamps_to_max(self, monkeypatch):
        monkeypatch.setattr(devicetime, "_start_trace", lambda p: None)
        monkeypatch.setattr(devicetime, "_stop_trace", lambda: None)
        out = devicetime.capture_profile(10_000_000, max_ms=50)
        assert out["ms"] == 50

    def test_capture_profile_busy_raises(self, monkeypatch):
        monkeypatch.setattr(devicetime, "_start_trace", lambda p: None)
        monkeypatch.setattr(devicetime, "_stop_trace", lambda: None)
        assert devicetime._PROFILE_LOCK.acquire(blocking=False)
        try:
            with pytest.raises(devicetime.ProfileBusy):
                devicetime.capture_profile(5)
        finally:
            devicetime._PROFILE_LOCK.release()
