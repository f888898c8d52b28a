"""The readers of the engine's launch gaps (PR 36), ``serve_launch_gap_p50_ms``
and ``serve_launch_gap_share``, on synthetic span lists with known answers:
a gap that began before the traced seconds, a launch made while work was
still queued (no ``gap_ns``), a gap with no launch after it in the traced
seconds, the clip at their end, and ``None`` where no span carries a gap."""

import pytest

from benchmark.layer_metrics import (serve_launch_gap_p50_ms,
                                     serve_launch_gap_share)

T_START = 100.0           # the window's zero on perf_counter, seconds
TRACED = (45.0, 50.0)
ARGS = (None, None, None)          # cell, cfg, peak: not read by these


def span(name, start_s, dur_ms, counts=None, tid=1, depth=1):
    """A span as the program keeps it, ``start_s`` on the window's clock."""
    t0 = int(round((T_START + start_s) * 1e9))
    return (name, tid, t0, t0 + int(round(dur_ms * 1e6)), depth, counts)


def launch(name, start_s, dur_ms, gap_ms=None, tid=1):
    counts = None if gap_ms is None else {"gap_ns": int(gap_ms * 1e6)}
    return span(f"serving.{name}.dispatch", start_s, dur_ms, counts, tid)


@pytest.fixture
def store(monkeypatch):
    from paddle_tpu.profiler import host_tracer

    def put(events):
        monkeypatch.setattr(host_tracer, "events", lambda: list(events))
    return put


def obs():
    return {"t_start": T_START, "traced": TRACED, "steps": []}


EVENTS = [
    launch("decode", 44.5, 0.5, 2.0),          # before the traced seconds
    launch("decode", 45.02, 0.5, 40.0),        # its gap began before them
    span("serving.step", 45.9, 10.0, {"blocks_live": 3}, depth=0),
    launch("decode", 46.0, 0.5, 2.0),          # 2.5 ms
    launch("prefill", 46.01, 1.0),             # work still queued: no gap
    launch("decode", 46.02, 0.5),
    span("serving.decode.wait", 46.03, 5.0),
    launch("decode", 46.5, 0.0, 6.0, tid=2),   # another engine's thread
    launch("prefill", 47.0, 1.0, 3.0),         # 4 ms
    launch("decode", 47.5, 0.5, 1.0),          # 1.5 ms
    launch("decode", 49.0, 1.0, 400.0),        # the engine idle: 401 ms
    launch("decode", 50.2, 0.5, 300.0),        # its gap began at 49.9; the
]                                              # launch is after the trace


def test_median_and_share(store):
    store(EVENTS)
    # gaps of 2.5, 6, 4, 1.5 and 401 ms
    assert serve_launch_gap_p50_ms.read("m", obs(), *ARGS) == \
        pytest.approx(4.0)
    assert serve_launch_gap_share.read("m", obs(), *ARGS) == \
        pytest.approx(100.0 * 0.415 / 5.0)


def test_a_gap_ends_with_the_traced_seconds(store):
    store([launch("decode", 46.0, 0.5, 2.0),
           launch("decode", 49.9995, 2.0, 1.0)])       # 1 + 0.5 inside
    assert serve_launch_gap_p50_ms.read("m", obs(), *ARGS) == \
        pytest.approx(2.0)
    assert serve_launch_gap_share.read("m", obs(), *ARGS) == \
        pytest.approx(100.0 * 0.004 / 5.0)


@pytest.mark.parametrize("reader", [serve_launch_gap_p50_ms,
                                    serve_launch_gap_share])
def test_nothing_to_read(store, reader):
    # the parent: dispatch spans without counts
    store([launch("decode", 46.0, 0.5), launch("prefill", 46.1, 1.0),
           span("serving.decode.wait", 46.2, 5.0)])
    assert reader.read("m", obs(), *ARGS) is None
    # gaps only outside the traced seconds, or begun before them
    store([launch("decode", 44.0, 0.5, 1.0), launch("decode", 45.001, 0.5,
                                                    2.0)])
    assert reader.read("m", obs(), *ARGS) is None
    # a --trace 0 run
    store(EVENTS)
    untraced = obs()
    del untraced["traced"]
    assert reader.read("m", untraced, *ARGS) is None
    store([])
    assert reader.read("m", obs(), *ARGS) is None
