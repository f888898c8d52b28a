"""Request-level distributed tracing (profiler.trace), the goodput
ledger (profiler.goodput), and the live ops endpoint (profiler.ops).

The load-bearing contracts:

* OFF is free: ``FLAGS_request_trace_sample=0`` mints no contexts and
  moves no ``trace.*`` counters (every record site gates on the context
  being None) — the machine-checked version lives in
  scripts/check_counters.py's trace phase.
* ON tells the truth: a served request's span tree names every hop
  (queue → prefill → decode.iter* → evict), the stage sums account the
  measured wall time, and ONE trace_id survives replica churn.
* Tail sampling keeps what matters: deadline-breached / errored /
  retried requests are retained even at a vanishing head sample rate.
* The goodput ledger accounts >=99% of trainer wall time into named
  buckets, clean or faulted.
* The ops endpoint serves all of it over stdlib HTTP.
"""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import flags
from paddle_tpu.profiler import counters
from paddle_tpu.profiler import trace as rtrace


@pytest.fixture(autouse=True)
def _trace_reset():
    """Every test finds and leaves tracing OFF and the kept-ring empty
    (another file's test in the same worker may have kept traces)."""
    flags.set_flags({"FLAGS_request_trace_sample": 0.0})
    rtrace.clear()
    yield
    flags.set_flags({"FLAGS_request_trace_sample": 0.0})
    rtrace.clear()


def _on(rate=1.0):
    flags.set_flags({"FLAGS_request_trace_sample": float(rate)})


@pytest.fixture(scope="module")
def model():
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=32,
                    use_flash_attention=False)
    paddle.seed(31)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _engine(m, **kw):
    from paddle_tpu.serving import LLMEngine
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("min_bucket", 4)
    return LLMEngine(m, **kw)


def _serve(eng, hs):
    while not all(h.is_finished for h in hs):
        eng.step()
    return hs


def _names(ctx):
    return [s[2] for s in ctx.spans]


class TestSampling:
    def test_off_by_default_mints_nothing(self, model):
        assert not rtrace.enabled()
        assert rtrace.new_trace(7) is None
        before = counters.snapshot()
        eng = _engine(model)
        h = eng.add_request([1, 2, 3], max_new_tokens=3)
        _serve(eng, [h])
        d = counters.delta(before)
        assert h.trace is None
        assert not any(k.startswith("trace.") and v for k, v in d.items())
        assert rtrace.kept_ids() == []

    def test_tail_keeps_deadline_breach_at_tiny_sample(self, model):
        """head_sampled is (effectively) never true at 1e-9, but a
        deadline-breached request is retained anyway — the tail is
        exactly the traffic worth debugging."""
        _on(1e-9)
        eng = _engine(model)
        h = eng.add_request([1, 2, 3, 4], max_new_tokens=16,
                            deadline_s=0.0)
        _serve(eng, [h])
        assert h.finish_reason == "deadline"
        assert h.trace is not None
        assert h.trace.head_sampled is False
        assert h.trace.keep_reason == "tail:deadline"
        assert h.trace.trace_id in rtrace.kept_ids()

    def test_finish_is_idempotent_and_blocks_late_spans(self):
        _on(1.0)
        ctx = rtrace.new_trace(5)
        ctx.add_span("queue", 0, 10)
        assert rtrace.finish(ctx, "length") is True
        n = len(ctx.spans)
        assert ctx.add_span("late", 0, 1) is None   # finished: dropped
        assert rtrace.finish(ctx, "length") is False  # second call: no-op
        assert len(ctx.spans) == n


class TestSpanTrees:
    def test_engine_span_tree(self, model):
        _on(1.0)
        eng = _engine(model)
        h = _serve(eng, [eng.add_request([1, 2, 3, 4, 5],
                                         max_new_tokens=3)])[0]
        ctx = h.trace
        assert ctx is not None and ctx.finished
        names = _names(ctx)
        assert "queue" in names
        assert "kv.reserve" in names
        assert names.count("prefill.chunk") == 1    # 5 tokens, chunk 32
        # prefill emits token 1; decode iterations emit the rest
        assert names.count("decode.iter") == 2
        assert "evict" in names                     # terminal marker
        d = ctx.to_dict()
        assert d["status"] == "length"
        assert d["tree"]["name"] == f"request[rid={h.rid}]"
        assert len(d["tree"]["children"]) == len(ctx.spans)
        assert all(d["stage_ns"][s] > 0
                   for s in ("queue", "prefill", "decode"))

    def test_small_chunks_record_one_span_each(self, model):
        _on(1.0)
        eng = _engine(model, block_size=4,
                      prefill_chunk=8)
        h = _serve(eng, [eng.add_request(list(range(1, 13)),
                                         max_new_tokens=3)])[0]
        names = _names(h.trace)
        assert "kv.reserve" in names
        assert names.count("prefill.chunk") == 2   # 12 tokens / chunk 8
        assert names.count("decode.iter") == 2

    def test_stage_sums_account_measured_wall(self, model):
        """queue + prefill + decode span time ~= arrival -> last emit."""
        _on(1.0)
        eng = _engine(model)
        h = _serve(eng, [eng.add_request([1, 2, 3, 4, 5, 6],
                                         max_new_tokens=4)])[0]
        measured = h.last_emit_ns - h.arrival_ns
        ratio = sum(h.trace.stage_ns().values()) / max(1, measured)
        assert 0.2 <= ratio <= 1.3, ratio

    def test_concurrent_add_span_is_safe(self):
        _on(1.0)
        ctx = rtrace.new_trace(9)
        n_threads, per = 8, 200

        def work(i):
            for j in range(per):
                ctx.add_span(f"w{i}", j, j + 1, k=j)

        ts = [threading.Thread(target=work, args=(i,))
              for i in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert len(ctx.spans) == n_threads * per
        sids = [s[0] for s in ctx.spans]
        assert len(set(sids)) == len(sids)          # unique span ids
        rtrace.finish(ctx, "length")
        assert len(ctx.to_dict()["spans"]) == n_threads * per


@pytest.mark.slow
class TestFleetTracing:
    def test_trace_id_survives_replica_respawn(self, model):
        """The respawned re-prefill lands in the SAME trace: one story
        per request, with redispatch + replica_died markers."""
        from paddle_tpu.resilience import faultinject
        from paddle_tpu.serving import ServingFleet
        _on(1.0)
        fleet = ServingFleet(model, replicas=2, max_slots=2,
                             max_seq_len=32, min_bucket=4, threaded=False,
                             warm_buckets=(4,))
        h = fleet.submit([1, 2, 3], max_new_tokens=4)
        tid = h.trace.trace_id
        with faultinject.fault_schedule(f"replica_crash@{h.rid}"):
            fleet.join([h])
        fleet.drain()
        assert h.finish_reason == "length"
        assert h.retries == 1
        ctx = h.trace
        assert ctx.trace_id == tid
        names = _names(ctx)
        assert "replica_died" in names
        assert "redispatch" in names
        assert names.count("prefill.chunk") == 2    # original + replay
        assert ctx.keep_reason == "tail:retried"
        assert rtrace.get_trace(tid)["rid"] == h.rid

    def test_slow_decode_stalls_are_spanned_and_counted(self, model):
        from paddle_tpu.resilience import faultinject
        from paddle_tpu.serving import ServingFleet
        _on(1.0)
        fleet = ServingFleet(model, replicas=1, max_slots=2,
                             max_seq_len=32, min_bucket=4, threaded=False,
                             warm_buckets=(4,))
        before = counters.snapshot()
        h = fleet.submit([1, 2, 3], max_new_tokens=6)
        with faultinject.fault_schedule(f"slow_decode@{h.rid}*3"):
            fleet.join([h])
        fleet.drain()
        assert h.finish_reason == "length"          # stalled, not killed
        stalls = [s for s in h.trace.spans if s[2] == "decode.stall"]
        assert len(stalls) == 3
        assert all((s[5] or {}).get("injected") for s in stalls)
        d = counters.delta(before)
        assert d.get("serving.fleet.slow_decode_stalls", 0) == 3


class TestGoodputLedger:
    def test_exclusive_buckets_and_accounting(self):
        import time
        from paddle_tpu.profiler.goodput import GoodputLedger
        led = GoodputLedger()
        led.start()
        with led.bucket("step"):
            time.sleep(0.02)
            with led.bucket("ckpt_sync"):   # child pauses the parent
                time.sleep(0.02)
            time.sleep(0.01)
        led.stop()
        r = led.report(publish=False)
        assert r["accounted"] >= 0.99
        # exclusive time: the nested ckpt_sync is NOT double-counted
        # under step (step ~30ms of the 50ms wall, never ~50ms)
        assert r["buckets_ns"]["ckpt_sync"] >= 15e6
        assert 25e6 <= r["buckets_ns"]["step"] <= 45e6
        assert r["wall_ns"] >= r["buckets_ns"]["step"]

    def test_trainer_wall_time_accounted_under_preempt(self):
        import tempfile
        import paddle_tpu.jit as pjit
        import paddle_tpu.nn as nn
        from paddle_tpu.io import DataLoader, TensorDataset
        from paddle_tpu.resilience import (CheckpointManager,
                                           FaultTolerantTrainer,
                                           faultinject)

        paddle.seed(7)
        net = nn.Sequential(nn.Linear(6, 12), nn.GELU(), nn.Linear(12, 3))
        opt = paddle.optimizer.AdamW(5e-2, parameters=net.parameters())
        step = pjit.CompiledTrainStep(
            net, lambda m, a, b: ((m(a) - b) ** 2).mean(), opt)
        rng = np.random.RandomState(3)
        ds = TensorDataset(
            [paddle.to_tensor(rng.randn(24, 6).astype("float32")),
             paddle.to_tensor(rng.randn(24, 3).astype("float32"))])
        with tempfile.TemporaryDirectory() as d:
            trainer = FaultTolerantTrainer(
                step, lambda e: DataLoader(ds, batch_size=4,
                                           shuffle=False),
                CheckpointManager(d, keep_last=2),
                epochs=1, max_steps=6, save_every=2)
            with faultinject.fault_schedule("preempt@3"):
                losses = trainer.run()
        assert len(losses) == 6
        r = trainer.goodput.report(publish=False)
        assert r["accounted"] >= 0.99, r
        assert 0.0 < r["goodput"] <= 1.0
        assert r["buckets_ns"]["compile"] > 0
        assert r["buckets_ns"]["step"] > 0
        assert r["buckets_ns"]["recovery"] > 0          # faulted run
        assert r["buckets_ns"]["restore_replay"] > 0
        # the split is exhaustive: buckets (idle-folded) sum to wall
        assert abs(sum(r["buckets_ns"].values())
                   - r["wall_ns"]) <= 0.01 * r["wall_ns"]


class TestOpsEndpoint:
    def _get(self, url):
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.status, resp.read()

    def test_endpoints_serve_live_state(self, model):
        from paddle_tpu.profiler.goodput import GoodputLedger
        from paddle_tpu.profiler.ops import OpsServer
        _on(1.0)
        eng = _engine(model)
        h = _serve(eng, [eng.add_request([1, 2, 3], max_new_tokens=2)])[0]
        import time
        led = GoodputLedger()
        led.start()
        with led.bucket("step"):
            time.sleep(0.05)   # dwell so attribution dominates overhead
        led.stop()
        with OpsServer(engine=eng, ledger=led) as srv:
            code, body = self._get(srv.url("/healthz"))
            hz = json.loads(body)
            assert code == 200 and hz["status"] == "ok"
            assert hz["traces_kept"] >= 1

            code, body = self._get(srv.url("/metrics"))
            assert code == 200 and len(body) > 0

            code, body = self._get(srv.url("/traces"))
            tr = json.loads(body)
            assert code == 200 and h.trace.trace_id in tr["kept"]
            assert tr["breakdown"]["requests"] >= 1

            code, body = self._get(
                srv.url(f"/traces/{h.trace.trace_id}"))
            t = json.loads(body)
            assert code == 200 and t["rid"] == h.rid
            assert any(s["name"] == "prefill.chunk" for s in t["spans"])

            code, body = self._get(srv.url("/goodput"))
            g = json.loads(body)
            assert code == 200 and g["accounted"] >= 0.99

            code, body = self._get(srv.url("/flight"))
            assert code == 200 and "events" in json.loads(body)

            with pytest.raises(urllib.error.HTTPError) as ei:
                self._get(srv.url("/traces/nope"))
            assert ei.value.code == 404

    def test_goodput_404_without_ledger(self, model):
        from paddle_tpu.profiler.ops import OpsServer
        with OpsServer(engine=_engine(model)) as srv:
            with pytest.raises(urllib.error.HTTPError) as ei:
                self._get(srv.url("/goodput"))
            assert ei.value.code == 404


class TestExport:
    def test_jsonl_and_chrome_export(self, tmp_path, model):
        _on(1.0)
        eng = _engine(model)
        _serve(eng, [eng.add_request([1, 2, 3, 4], max_new_tokens=2)])
        path = tmp_path / "traces.jsonl"
        rtrace.export_jsonl(str(path))
        recs = [json.loads(line)
                for line in path.read_text().splitlines()]
        assert len(recs) >= 1
        assert any(r["status"] == "length" for r in recs)
        ev = rtrace.to_chrome_trace()["traceEvents"]
        assert any(e.get("ph") == "X" and e.get("name") == "prefill.chunk"
                   for e in ev)
