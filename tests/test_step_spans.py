"""The program names its own work in the profiler's trace (PR 26).

One span primitive (``profiler.host_tracer.span``) that is live whenever
someone is profiling — a ``host_tracer.start()`` session or any
``jax.profiler`` session — and a shared no-op otherwise; the host phases of
``engine.step()`` as ``serving.*`` children of ``serving.step``, with the
KV pool's live blocks counted on the way out for whoever is profiling, and
the request trace's own spans meaning what they meant; a ``name=`` on every
Pallas kernel.  Since PR 36 the first launch after a read-back carries the
host time since the device drained (``gap_ns`` on its dispatch span, handed
to the profiler's trace as well), in the paged and the block-decoding
engine.  CPU only; nothing here is a timing.
"""

import pathlib
import re
import time

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.core import flags as core_flags
from paddle_tpu.profiler import counters, host_tracer

ROOT = pathlib.Path(__file__).resolve().parent.parent

STEP_CHILDREN = ["serving.sweep", "serving.admit",
                 "serving.prefill.operands", "serving.prefill.dispatch",
                 "serving.prefill.wait", "serving.prefill.emit",
                 "serving.decode.operands", "serving.decode.dispatch",
                 "serving.decode.wait", "serving.decode.emit",
                 "serving.gauges"]
DISPATCHES = ("serving.prefill.dispatch", "serving.decode.dispatch")
WAITS = ("serving.prefill.wait", "serving.decode.wait")


@pytest.fixture(autouse=True)
def _tracer_off():
    core_flags.set_flags({"FLAGS_host_trace_level": 1})
    if host_tracer.is_collecting():
        host_tracer.stop()
    yield
    core_flags.set_flags({"FLAGS_host_trace_level": 1})
    if host_tracer.is_collecting():
        host_tracer.stop()


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


def _engine(**kw):
    from paddle_tpu.serving import LLMEngine
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("min_bucket", 4)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_chunk", 8)
    return LLMEngine(_model(), **kw)


def _drain(eng, handles, limit=200):
    for _ in range(limit):
        if all(h.is_finished for h in handles):
            return
        eng.step()
    raise AssertionError("engine did not converge")


def _warm(eng):
    """Compile every program the measured steps will use."""
    _drain(eng, [eng.add_request(np.arange(1, 7, dtype=np.int32),
                                 max_new_tokens=3, seed=0)])


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------
class TestPrimitive:
    def test_shared_noop_with_no_session_of_either_kind(self):
        assert not host_tracer.is_collecting()
        assert not jax.profiler.TraceAnnotation.is_enabled()
        a, b = host_tracer.span("a"), host_tracer.span("b", rows=3)
        assert a is b and not host_tracer.enabled()
        before = host_tracer.span_count()
        with a as sp:
            sp.note(rows=1)          # the no-op takes counts and drops them
        assert host_tracer.span_count() == before

    def test_nesting_and_counts(self):
        host_tracer.start()
        try:
            with host_tracer.span("outer", rows=2) as outer:
                with host_tracer.span("inner"):
                    pass
                outer.note(blocks=5)
                outer.note(rows=3)   # a later note wins
        finally:
            evts = host_tracer.stop()
        by = {e[0]: e for e in evts}
        assert set(by) == {"outer", "inner"}
        assert all(len(e) == 6 for e in evts)
        assert by["outer"][4] == 0 and by["inner"][4] == 1
        assert by["outer"][5] == {"rows": 3, "blocks": 5}
        assert by["inner"][5] is None
        assert by["outer"][2] <= by["inner"][2] <= by["inner"][3] \
            <= by["outer"][3]

    def test_events_are_on_perf_counter_ns(self):
        host_tracer.start()
        try:
            t0 = time.perf_counter_ns()
            with host_tracer.span("clocked"):
                pass
            t1 = time.perf_counter_ns()
        finally:
            (ev,) = host_tracer.stop()
        assert t0 <= ev[2] <= ev[3] <= t1

    def test_store_is_bounded(self):
        host_tracer.start()
        try:
            for i in range(host_tracer.STORE_LIMIT + 10):
                with host_tracer.span("s", i=i):
                    pass
        finally:
            evts = host_tracer.stop()
        assert len(evts) == host_tracer.STORE_LIMIT
        assert evts[0][5] == {"i": 10}          # the oldest were dropped
        assert evts[-1][5] == {"i": host_tracer.STORE_LIMIT + 9}

    def test_record_event_is_the_same_primitive(self):
        host_tracer.start()
        try:
            with profiler.RecordEvent("user_event"):
                assert host_tracer.current_stack() == ["user_event"]
        finally:
            evts = host_tracer.stop()
        assert [e[0] for e in evts] == ["user_event"] and len(evts[0]) == 6
        assert not hasattr(profiler.RecordEvent("x"), "_ann")

    def test_counts_given_at_the_open_reach_the_trace(self, monkeypatch):
        """Under a (faked) profiler session a span opened with counts hands
        them to its ``TraceAnnotation``; a later ``note()`` does not, and a
        span opened without counts hands over nothing."""
        built = []

        class Annotation:
            on = True

            def __init__(self, name, **kw):
                built.append((name, kw))

            @classmethod
            def is_enabled(cls):
                return cls.on

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(host_tracer, "TraceAnnotation", Annotation)
        with host_tracer.span("serving.decode.dispatch", gap_ns=7) as sp:
            sp.note(rows=3)
        with host_tracer.span("serving.decode.wait"):
            pass
        assert built == [("serving.decode.dispatch", {"gap_ns": 7}),
                         ("serving.decode.wait", {})]
        Annotation.on = False                   # nobody profiling
        del built[:]
        with host_tracer.span("serving.decode.dispatch", gap_ns=7):
            pass
        assert built == []

    def test_chrome_trace_carries_counts(self):
        host_tracer.start()
        try:
            with host_tracer.span("counted", blocks_live=7):
                pass
        finally:
            evts = host_tracer.stop()
        (x,) = [e for e in host_tracer.to_chrome_trace(evts)["traceEvents"]
                if e["ph"] == "X"]
        assert x["args"] == {"depth": 0, "blocks_live": 7}
        assert "counted" in host_tracer.summary(evts)


class TestLifecycle:
    """``level=0``: kept with nobody profiling, in a store of its own."""

    def test_kept_with_no_session_and_not_in_the_session_store(self):
        assert not host_tracer.enabled()
        n, m = len(host_tracer.lifecycle()), host_tracer.span_count()
        with host_tracer.span("life.a", level=0, key="k") as sp:
            sp.note(bucket=8)
        ev = host_tracer.lifecycle()[-1]
        assert len(host_tracer.lifecycle()) == n + 1
        assert ev[0] == "life.a" and ev[5] == {"key": "k", "bucket": 8}
        assert host_tracer.span_count() == m

    def test_kept_whatever_the_flag_says(self):
        core_flags.set_flags({"FLAGS_host_trace_level": 0})
        with host_tracer.span("life.flag0", level=0):
            assert host_tracer.span("step.path") is host_tracer.span("x")
        assert host_tracer.lifecycle()[-1][0] == "life.flag0"

    def test_a_session_sees_it_too_and_start_does_not_drop_it(self):
        with host_tracer.span("life.before", level=0):
            pass
        host_tracer.start()
        try:
            with host_tracer.span("life.during", level=0):
                with host_tracer.span("child"):
                    pass
        finally:
            evts = host_tracer.stop()
        assert [e[0] for e in evts] == ["child", "life.during"]
        assert evts[0][4] == 1 and evts[1][4] == 0
        names = [e[0] for e in host_tracer.lifecycle()]
        assert "life.before" in names and "life.during" in names
        assert "child" not in names

    def test_lifecycle_since_ends_now_on_the_same_clock(self):
        t0 = time.perf_counter_ns()
        host_tracer.lifecycle_since("life.since", t0, traces=2)
        name, _tid, a, b, depth, counts = host_tracer.lifecycle()[-1]
        assert (name, a, depth, counts) == ("life.since", t0, 0,
                                            {"traces": 2})
        assert t0 <= b <= time.perf_counter_ns()

    def test_the_lifecycle_store_is_bounded(self, monkeypatch):
        import collections
        # a store of its own: the process's real one keeps its spans
        monkeypatch.setattr(host_tracer, "_LIFECYCLE", collections.deque(
            maxlen=host_tracer.LIFECYCLE_LIMIT))
        for i in range(host_tracer.LIFECYCLE_LIMIT + 3):
            host_tracer.lifecycle_since("life.many", 0, i=i)
        kept = host_tracer.lifecycle()
        assert len(kept) == host_tracer.LIFECYCLE_LIMIT
        assert kept[0][5] == {"i": 3}


# ---------------------------------------------------------------------------
# a profiler session is a collection session
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """One ``jax.profiler`` trace on the CPU around a few steps of a tiny
    paged engine, with NO ``host_tracer.start()``: the events the store
    kept, the level-0 control taken inside the same session, and the text
    of every event name in the written ``.xplane.pb``."""
    from jax.profiler import ProfileData
    eng = _engine()
    _warm(eng)
    out = tmp_path_factory.mktemp("trace")
    host_tracer.start()
    host_tracer.stop()                       # empty the store
    jax.profiler.start_trace(str(out))
    try:
        assert not host_tracer.is_collecting() and host_tracer.enabled()
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            hs = [eng.add_request(np.arange(1, 12, dtype=np.int32),
                                  max_new_tokens=4, seed=0)]
            _drain(eng, hs)
        core_flags.set_flags({"FLAGS_host_trace_level": 0})
        off = host_tracer.span("level0")
        core_flags.set_flags({"FLAGS_host_trace_level": 1})
    finally:
        jax.profiler.stop_trace()
    events = host_tracer.events()
    (pb,) = out.glob("plugins/profile/*/*.xplane.pb")
    written = [ev for plane in ProfileData.from_file(str(pb)).planes
               for line in plane.lines for ev in line.events]
    gaps = sorted(dict(ev.stats)["gap_ns"] for ev in written
                  if ev.name in DISPATCHES and "gap_ns" in dict(ev.stats))
    return {"events": events, "off": off,
            "names": {ev.name for ev in written}, "gaps": gaps}


class TestFollowsTheProfiler:
    def test_collects_without_host_tracer_start(self, profiled):
        names = {e[0] for e in profiled["events"]}
        assert "serving.step" in names and "serving.decode.wait" in names

    def test_level0_stays_off_under_a_profiler(self, profiled):
        assert profiled["off"] is host_tracer.span("nobody profiling")

    @pytest.mark.parametrize("name", ["serving.step",
                                      "serving.decode.operands",
                                      "serving.prefill.wait"])
    def test_names_appear_in_the_written_trace(self, profiled, name):
        assert name in profiled["names"]

    def test_a_launch_gap_is_a_stat_of_the_written_event(self, profiled):
        kept = sorted(e[5]["gap_ns"] for e in profiled["events"]
                      if e[0] in DISPATCHES and e[5])
        assert kept and profiled["gaps"] == kept
        _check_launch_gaps(profiled["events"])

    def test_off_again_after_the_session(self, profiled):
        assert not host_tracer.enabled()
        n = host_tracer.span_count()
        with host_tracer.span("after"):
            pass
        assert host_tracer.span_count() == n


# ---------------------------------------------------------------------------
# engine.step(): the children of serving.step, and the KV counts
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def paged_steps():
    """The span events of a tiny paged engine serving two requests to the
    end, the ``stats()`` after each step, and the gauges at the end."""
    eng = _engine()
    _warm(eng)
    core_flags.set_flags({"FLAGS_host_trace_level": 1})
    host_tracer.start()
    stats = []
    try:
        hs = [eng.add_request(np.arange(1, 14, dtype=np.int32),
                              max_new_tokens=5, seed=0),
              eng.add_request(np.arange(3, 9, dtype=np.int32),
                              max_new_tokens=3, seed=0)]
        for _ in range(100):
            if all(h.is_finished for h in hs):
                break
            eng.step()
            stats.append(eng.stats())
    finally:
        events = host_tracer.stop()
    return {"events": events, "stats": stats,
            "gauges": counters.snapshot(), "handles": hs}


def _steps_with_children(events):
    steps = [e for e in events if e[0] == "serving.step"]
    return [(st, [e for e in events
                  if e[1] == st[1] and e[4] == st[4] + 1
                  and st[2] <= e[2] and e[3] <= st[3]]) for st in steps]


class TestEngineStep:
    @pytest.mark.parametrize("child", STEP_CHILDREN)
    def test_every_child_is_inside_a_step(self, paged_steps, child):
        found = [c for _, kids in _steps_with_children(paged_steps["events"])
                 for c in kids if c[0] == child]
        assert found, f"no {child} span directly under serving.step"
        assert len(found) == sum(e[0] == child
                                 for e in paged_steps["events"])

    def test_old_undivided_spans_are_gone(self, paged_steps):
        names = {e[0] for e in paged_steps["events"]}
        assert not names & {"serving.prefill", "serving.decode"}

    def test_children_cover_the_step_up_to_its_self_time(self, paged_steps):
        for st, kids in _steps_with_children(paged_steps["events"]):
            kids.sort(key=lambda e: e[2])
            for a, b in zip(kids, kids[1:]):
                assert a[3] <= b[2], "sibling spans overlap"
            covered = sum(e[3] - e[2] for e in kids)
            own = (st[3] - st[2]) - covered
            assert 0 <= own < st[3] - st[2]
        decoding = [kids for _, kids in
                    _steps_with_children(paged_steps["events"])
                    if any(k[0] == "serving.decode.wait" for k in kids)]
        assert decoding
        launch = ["serving.decode.operands", "serving.decode.dispatch"]
        read = ["serving.decode.wait",           # the tokens, alone
                "serving.decode.emit"]
        orders = [[k[0] for k in sorted(kids, key=lambda e: e[2])
                   if k[0].startswith("serving.decode")] for kids in decoding]
        # the launch before read back behind this step's launch, or before
        # it (an operand goes up, a row gets its last token), or alone
        assert all(o in (launch + read, read + launch, read) for o in orders)
        assert launch + read in orders

    def test_step_counts(self, paged_steps):
        counts = [e[5] for e in paged_steps["events"]
                  if e[0] == "serving.step"]
        # what a metric reads (kv_blocks_live_peak_share and, beside a
        # model's recurrent state, recurrent_state_share), and no more
        assert all(set(c) == {"blocks_live", "blocks_total",
                              "kv_live_bytes", "state_bytes"}
                   for c in counts)
        assert all(c["state_bytes"] == 0 for c in counts)    # a GPT has none
        assert all(0 <= c["blocks_live"] <= c["blocks_total"] for c in counts)
        assert max(c["blocks_live"] for c in counts) > 0
        assert [c["blocks_live"] for c in counts] \
            == [s["blocks_live"] for s in paged_steps["stats"]]

    def test_blocks_live_returns_to_zero_and_blocks_used_does_not(
            self, paged_steps):
        assert all(h.is_finished for h in paged_steps["handles"])
        last = paged_steps["stats"][-1]
        # the prefix tree retains the finished requests' blocks
        assert last["blocks_live"] == 0 < last["blocks_used"]
        assert all(s["blocks_live"] <= s["blocks_used"] <= s["blocks_total"]
                   for s in paged_steps["stats"])
        g = paged_steps["gauges"]
        assert g["serving.kv.blocks_used"] == last["blocks_used"]

    def test_blocks_live_counts_a_shared_block_once(self):
        eng = _engine()
        prompt = np.arange(1, 18, dtype=np.int32)        # 4 full blocks
        _drain(eng, [eng.add_request(prompt, max_new_tokens=2, seed=0)])
        a = eng.add_request(prompt, max_new_tokens=6, seed=0)
        b = eng.add_request(prompt, max_new_tokens=6, seed=0)
        eng.step()
        st = eng.stats()
        tables = [t for t in eng._slot_blocks if t]
        assert len(tables) == 2 and set(tables[0]) & set(tables[1])
        assert st["blocks_live"] == len(set(tables[0]) | set(tables[1]))
        assert st["blocks_live"] <= st["blocks_used"]
        _drain(eng, [a, b])

    def test_no_lifecycle_span_opens_on_a_later_step(self):
        eng = _engine()
        hs = [eng.add_request(np.arange(1, 14, dtype=np.int32),
                              max_new_tokens=6, seed=0)]
        eng.step()                   # the first builds and calls happen here
        eng.step()
        eng.step()
        built = [e for e in host_tracer.lifecycle()
                 if e[0] == "serving.program_build"]
        assert {e[5]["key"] for e in built} >= {"prefill_paged",
                                                "decode_paged"}
        n = len(host_tracer.lifecycle())
        while not hs[0].is_finished:
            eng.step()
        assert len(host_tracer.lifecycle()) == n

    def test_default_geometry_has_the_same_children(self):
        """One block and one chunk a prompt (block 16, chunk 32): the
        step has the same children and counts its one pool."""
        eng = _engine(block_size=16, prefill_chunk=None)
        _warm(eng)
        host_tracer.start()
        try:
            _drain(eng, [eng.add_request(np.arange(1, 9, dtype=np.int32),
                                         max_new_tokens=3, seed=0)])
        finally:
            events = host_tracer.stop()
        names = {e[0] for e in events}
        assert set(STEP_CHILDREN) <= names
        step = next(e for e in events if e[0] == "serving.step")
        assert step[5]["blocks_total"] == eng.pool.capacity == 3 * 2

    def test_one_clock_for_stamps_spans_and_histograms(self):
        eng = _engine()
        t0 = time.perf_counter_ns()
        h = eng.add_request(np.arange(1, 7, dtype=np.int32),
                            max_new_tokens=2, seed=0)
        _drain(eng, [h])
        t1 = time.perf_counter_ns()
        assert t0 <= h.arrival_ns <= h.last_emit_ns <= t1
        for f in ("engine.py", "paged.py"):
            src = (ROOT / "paddle_tpu" / "serving" / f).read_text()
            assert "monotonic_ns" not in src


# ---------------------------------------------------------------------------
# the device's wait for the engine: gap_ns on the first launch after a
# read-back
# ---------------------------------------------------------------------------
def _check_launch_gaps(events):
    """Walk each thread's step-path spans in time order, keeping the
    launches not yet read back in device order: a prefill read-back reads
    its own chunk and so everything before it, a decode read-back the
    oldest unread decode launch and everything before that.  A dispatch
    after a read-back that left nothing unread (and no launch between)
    carries ``gap_ns``, no more than the host time from that first drain's
    end to the dispatch and no less than from the span after it to the
    span before the dispatch; any other dispatch carries none.  Returns
    the number of each."""
    found = {"gap": 0, "queued": 0}
    for tid in {e[1] for e in events}:
        leaves = sorted((e for e in events if e[1] == tid
                         and e[0] != "serving.step"), key=lambda e: e[2])
        unread = []                       # dispatches, oldest first
        wait = None                       # first drain since a launch
        for i, e in enumerate(leaves):
            if e[0] in WAITS:
                if e[0] == "serving.prefill.wait":
                    unread.clear()
                elif "serving.decode.dispatch" in unread:
                    del unread[:unread.index("serving.decode.dispatch") + 1]
                if not unread and wait is None:
                    wait = e
            if e[0] not in DISPATCHES:
                continue
            unread.append(e[0])
            gap = (e[5] or {}).get("gap_ns")
            if wait is None:
                assert gap is None, e
                found["queued"] += 1
                continue
            after = next(x for x in leaves if x[2] >= wait[3])
            assert 0 <= gap <= e[2] - wait[3], (e, wait)
            assert gap >= leaves[i - 1][3] - after[2], (e, wait)
            found["gap"] += 1
            wait = None
    return found


class TestLaunchGaps:
    def test_paged_engine(self, paged_steps):
        found = _check_launch_gaps(paged_steps["events"])
        # a launch queued behind an unread one follows no drain
        assert found["gap"] >= 3 and found["queued"] >= 3

    def test_a_session_stamps_nothing_once_it_ends(self, paged_steps):
        assert not host_tracer.enabled()
        # the session's last read-back left a stamp; nothing consumed it
        eng = _engine()
        eng._drained_ns = 123
        with eng._launch_span("serving.decode.dispatch") as sp:
            assert sp is host_tracer.span("nobody profiling")
        assert eng._drained_ns == 0

    def test_nothing_without_a_session(self, monkeypatch):
        from paddle_tpu.serving import LLMEngine
        seen = []
        real = LLMEngine._drained

        def drained(self, sp, t_ns=None):
            real(self, sp, t_ns)
            seen.append(self._drained_ns)
        monkeypatch.setattr(LLMEngine, "_drained", drained)
        eng = _engine()
        _warm(eng)
        n = host_tracer.span_count()
        _drain(eng, [eng.add_request(np.arange(1, 14, dtype=np.int32),
                                     max_new_tokens=4, seed=0)])
        assert seen and set(seen) == {0}        # never stamped
        assert host_tracer.span_count() == n
        assert eng._launch_span("serving.decode.dispatch") is \
            host_tracer.span("x")


# ---------------------------------------------------------------------------
# the block-decoding engine: the same children, the same gaps
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def block_steps():
    """A tiny block-decoding engine (``tests/test_sdar.py``'s size): one
    row decoding while a prompt of three chunks arrives, under a span
    session."""
    from paddle_tpu.models import sdar
    from paddle_tpu.serving import LLMEngine
    paddle.seed(7)
    model = sdar.SdarMoeForCausalLM(sdar.SdarConfig(
        vocab_size=512, hidden_size=64, moe_intermediate_size=32,
        num_layers=2, num_heads=8, num_kv_heads=2, head_dim=16,
        num_experts=16, num_experts_per_tok=8, max_seq_len=512,
        mask_token_id=500, initializer_range=0.1))
    model.eval()
    eng = LLMEngine(model, block_size=16, max_slots=3, max_seq_len=128,
                    n_blocks=25, prefill_chunk=32, min_bucket=16)
    long_prompt = np.arange(1, 71, dtype=np.int32) % 400      # 32, 32, 4
    _drain(eng, [eng.add_request(long_prompt, max_new_tokens=4, seed=0)])
    host_tracer.start()
    try:
        a = eng.add_request(np.arange(3, 9, dtype=np.int32),
                            max_new_tokens=12, seed=0)
        eng.step()
        eng.step()
        b = eng.add_request(long_prompt + 1, max_new_tokens=4, seed=0)
        _drain(eng, [a, b])
    finally:
        events = host_tracer.stop()
    return {"events": events, "engine": eng}


class TestBlockDecodeSteps:
    def test_children_and_the_order_of_a_launch(self, block_steps):
        steps = _steps_with_children(block_steps["events"])
        names = {k[0] for _, kids in steps for k in kids}
        assert names == set(STEP_CHILDREN)
        decoding = [kids for _, kids in steps
                    if any(k[0] == "serving.decode.wait" for k in kids)]
        assert decoding
        for kids in decoding:
            order = [k[0] for k in sorted(kids, key=lambda e: e[2])
                     if k[0].startswith("serving.decode")]
            assert order == ["serving.decode.operands",
                             "serving.decode.dispatch",
                             "serving.decode.wait",
                             "serving.decode.emit"]

    def test_launch_gaps(self, block_steps):
        events = block_steps["events"]
        found = _check_launch_gaps(events)
        assert found["gap"] >= 4
        # a decode launch right behind a chunk that was not the prompt's
        # last: no read-back between them, so no gap
        leaves = sorted((e for e in events if e[0] in DISPATCHES + WAITS),
                        key=lambda e: e[2])
        behind = [b for a, b in zip(leaves, leaves[1:])
                  if a[0] == "serving.prefill.dispatch"
                  and b[0] == "serving.decode.dispatch"]
        assert behind and all(b[5] is None for b in behind)


# ---------------------------------------------------------------------------
# names on the device side
# ---------------------------------------------------------------------------
def test_every_pallas_call_has_a_name():
    sites = []
    for path in sorted((ROOT / "paddle_tpu" / "kernels").glob("*.py")):
        src = path.read_text()
        for m in re.finditer(r"pallas_call\(", src):
            depth, i = 1, m.end()
            while depth:                       # to the matching ")"
                depth += {"(": 1, ")": -1}.get(src[i], 0)
                i += 1
            # a name may hold hyphens: the held experts' grouped matmul
            # is ``ragged-dot-held``, for the readers of ``ragged-dot*``
            sites.append((path.name, re.search(r'\bname="([\w-]+)"',
                                               src[m.end():i])))
    assert len(sites) >= 5
    assert all(name for _, name in sites), sites
    assert {name.group(1) for _, name in sites} >= {
        "flash_fwd", "flash_dq", "flash_dkv", "paged_decode_attn",
        "rms_norm", "ragged-dot-held"}


# ---------------------------------------------------------------------------
# the step pays for nothing nobody reads
# ---------------------------------------------------------------------------
class TestNothingUnread:
    def test_only_a_session_span_is_live(self):
        assert host_tracer.span("off").live is False
        assert host_tracer.span("kept", level=0).live is False
        host_tracer.start()
        try:
            assert host_tracer.span("on").live is True
            assert host_tracer.span("kept", level=0).live is True
        finally:
            host_tracer.stop()

    def test_blocks_live_is_counted_only_for_someone_who_profiles(
            self, monkeypatch):
        from paddle_tpu.serving import LLMEngine
        calls = []
        real = LLMEngine._blocks_live
        monkeypatch.setattr(LLMEngine, "_blocks_live",
                            lambda self: calls.append(1) or real(self))
        eng = _engine()
        _warm(eng)
        assert calls == []                       # a dozen steps, none asked
        assert eng.stats()["blocks_live"] == 0 and len(calls) == 1
        host_tracer.start()
        try:
            eng.step()
        finally:
            host_tracer.stop()
        assert len(calls) == 2

    def test_lifecycle_since_takes_both_stamps(self):
        n = len(host_tracer.lifecycle())
        host_tracer.lifecycle_since("stamped", 100, 250, rows=1)
        (ev,) = host_tracer.lifecycle()[n:]
        assert (ev[0], ev[2], ev[3], ev[5]) == ("stamped", 100, 250,
                                                {"rows": 1})

    def test_a_steady_train_step_reads_its_clock_once_and_keeps_nothing(self):
        from paddle_tpu.jit import CompiledTrainStep
        paddle.seed(13)
        model = paddle.nn.Linear(4, 4)
        opt = paddle.optimizer.SGD(1e-3, parameters=model.parameters())
        step = CompiledTrainStep(
            model, lambda m, x, y: ((m(x) - y) ** 2).mean(), opt)
        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        step(x, x)
        step(x, x)
        n, misses = (len(host_tracer.lifecycle()),
                     counters.get("jit.cache_misses"))
        t0 = time.perf_counter_ns()
        step(x, x)
        assert t0 <= step._call_t0_ns <= time.perf_counter_ns()
        assert len(host_tracer.lifecycle()) == n
        assert counters.get("jit.cache_misses") == misses


# ---------------------------------------------------------------------------
# the request trace's spans mean what they meant
# ---------------------------------------------------------------------------
@pytest.fixture
def traced_request():
    """One request served by a warm paged engine with request tracing and a
    span session on: its trace's spans and the host spans of those steps."""
    eng = _engine()
    _warm(eng)
    core_flags.set_flags({"FLAGS_request_trace_sample": 1.0})
    host_tracer.start()
    try:
        h = eng.add_request(np.arange(1, 14, dtype=np.int32),   # two chunks
                            max_new_tokens=4, seed=0)
        _drain(eng, [h])
    finally:
        events = host_tracer.stop()
        core_flags.set_flags({"FLAGS_request_trace_sample": 0.0})
    spans = [(name, t0, t1) for _, _, name, t0, t1, _ in h.trace.spans]
    return spans, events


def _host(events, name):
    return sorted((e[2], e[3]) for e in events if e[0] == name)


class TestRequestTraceKeepsItsMeaning:
    def test_a_chunk_is_its_dispatch_and_ends_before_the_read_back(
            self, traced_request):
        spans, events = traced_request
        chunks = sorted((t0, t1) for n, t0, t1 in spans
                        if n == "prefill.chunk")
        operands = _host(events, "serving.prefill.operands")
        dispatch = _host(events, "serving.prefill.dispatch")
        (wait,) = _host(events, "serving.prefill.wait")
        assert len(chunks) == len(dispatch) == 2
        for (c0, c1), (o0, o1), (d0, d1) in zip(chunks, operands, dispatch):
            # starts after the ids and the seed's key were made (inside
            # the operands span), holds the whole dispatch, no read-back
            assert o0 < c0 < o1 <= d0 and d1 <= c1
        assert chunks[-1][1] <= wait[0]           # the last chunk as well

    def test_a_decode_iteration_ends_at_the_tokens_read_back(
            self, traced_request):
        spans, events = traced_request
        iters = sorted((t0, t1) for n, t0, t1 in spans if n == "decode.iter")
        operands = _host(events, "serving.decode.operands")
        tokens = _host(events, "serving.decode.wait")
        emits = _host(events, "serving.decode.emit")
        assert iters and len(iters) == len(tokens) == len(emits)
        before = None
        for (i0, i1), (o0, o1), (_, w1), (e0, _) in zip(
                iters, operands, tokens, emits):
            # from inside the operands span (or, for a launch queued behind
            # the one before, from that one's read-back) to the launch's one
            # read-back, the tokens; the keys stay on the device, so what
            # follows is the emit loop
            assert o0 < i0 < o1 or i0 == before
            assert w1 <= i1 <= e0
            before = i1
        assert any(i0 == j1 for (i0, _), (_, j1) in zip(iters[1:], iters))

    def test_a_second_chunk_makes_no_key_and_uploads_no_table(
            self, monkeypatch):
        """What is constant for a request is made with its first chunk:
        the ``serving.prefill.operands`` span of a later chunk holds no
        ``jax.random.key`` round trip and uploads the ids alone."""
        eng = _engine()
        _warm(eng)
        made, uploads = [], []
        real_key, real_op = jax.random.key, eng.arena.operand
        monkeypatch.setattr(jax.random, "key",
                            lambda *a, **k: made.append(a) or real_key(*a, **k))
        monkeypatch.setattr(
            eng.arena, "operand", lambda x: uploads.append(
                (time.perf_counter_ns(), np.shape(x))) or real_op(x))
        h = eng.add_request(np.arange(1, 14, dtype=np.int32),   # two chunks
                            max_new_tokens=2, seed=5)
        chunks = counters.get("serving.kv.prefill_chunks")
        eng.step()
        assert counters.get("serving.kv.prefill_chunks") == chunks + 1
        assert made == [(5,)] and len(uploads) == 2     # ids, table row
        del made[:], uploads[:]
        host_tracer.start()
        try:
            eng.step()                 # the last chunk and the first launch
        finally:
            events = host_tracer.stop()
        assert counters.get("serving.kv.prefill_chunks") == chunks + 2
        assert made == [] and h.tokens
        (o0, o1), = _host(events, "serving.prefill.operands")
        (ids,) = [shape for t, shape in uploads if o0 <= t <= o1]
        assert len(ids) == 2 and ids[0] == 1
        # every other upload of the step is the decode launch's
        (d0, d1), = _host(events, "serving.decode.operands")
        assert all(d0 <= t <= d1 for t, _ in uploads[1:])
        _drain(eng, [h])

    def test_the_read_back_happens_after_the_chunk_is_counted(
            self, monkeypatch):
        """A failing read-back fires where it did: after ``prefill_chunks``
        counted the chunk and the slot left the prefilling set."""
        eng = _engine()
        _warm(eng)
        h = eng.add_request(np.arange(1, 7, dtype=np.int32),
                            max_new_tokens=2, seed=0)
        chunks = counters.get("serving.kv.prefill_chunks")

        class Boom(RuntimeError):
            pass

        real_pchunk = eng._pchunk_for

        def poisoned(C):
            fn = real_pchunk(C)

            def call(*a):
                out = list(fn(*a))
                out[-2] = _Unreadable()
                return tuple(out)
            return call

        class _Unreadable:
            def __int__(self):
                raise Boom()

        monkeypatch.setattr(eng, "_pchunk_for", poisoned)
        eng.step()          # contained: the request finishes with the error
        assert h.is_finished and isinstance(h.error, Boom)
        assert counters.get("serving.kv.prefill_chunks") == chunks + 1
        assert not eng._prefill_state
