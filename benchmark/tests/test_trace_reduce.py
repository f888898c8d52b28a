"""The trace reduction on a small synthetic trace with known answers."""

from collections import namedtuple

import pytest

from benchmark import trace_reduce as tr

Event = namedtuple("Event", "name start_ns duration_ns stats")
Line = namedtuple("Line", "name events")
Plane = namedtuple("Plane", "name lines")
Profile = namedtuple("Profile", "planes")


def ev(name, start_us, dur_us, **stats):
    return Event(name, start_us * 1e3, dur_us * 1e3, list(stats.items()))


def synthetic():
    """Window 0..1000 us.  Two launches of jit_decode (100..300, 500..800)
    and one of jit_pchunk (850..950).  Inside the first launch a `while`
    (100..300) holds two ops (100..180 fusion, 200..300 a Mosaic call), so
    there is a 20 us bubble inside the launch.  One op straddles the end of
    the window (980..1100)."""
    host = Plane("/host:CPU", [Line("main", [
        ev("bench.window", 0, 1000),
        ev("bench.engine_step", 0, 320), ev("bench.idle", 320, 150),
        ev("bench.engine_step", 470, 500), ev("other", 0, 1000)])])
    mosaic = '%k.1 = bf16[8] custom-call(), custom_call_target="tpu_custom_call"'
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [ev("jit_decode(17)", 100, 200),
                             ev("jit_decode(17)", 500, 300),
                             ev("jit_pchunk(9)", 850, 100),
                             ev("jit_decode(17)", 980, 120)]),
        Line("XLA Ops", [ev("%while.1 = () while()", 100, 200),
                         ev("%fusion.2 = f32[] fusion()", 100, 80),
                         ev(mosaic, 200, 100),
                         ev("%fusion.2 = f32[] fusion()", 500, 300),
                         ev("%fusion.7 = f32[] fusion()", 850, 100),
                         ev("%fusion.2 = f32[] fusion()", 980, 120)]),
        Line("Async XLA Ops", [ev("%copy-start.1", 0, 1000)])])
    return Profile([host, dev, Plane("/host:metadata", [])])


def test_busy_union_and_window():
    red = tr.reduce(synthetic())
    assert red["window_s"] == pytest.approx(1000e-6)
    # 100..300 (the while covers its bubble), 500..800, 850..950, 980..1000
    assert red["busy_s"] == pytest.approx((200 + 300 + 100 + 20) * 1e-6)
    assert red["n_devices"] == 1


def test_per_program_launches():
    red = tr.reduce(synthetic())
    assert sorted(red["programs"]) == ["jit_decode", "jit_pchunk"]
    assert red["programs"]["jit_decode"] == pytest.approx(
        [200e-6, 300e-6, 120e-6])
    assert red["programs"]["jit_pchunk"] == pytest.approx([100e-6])


def test_per_op_self_time_and_clip():
    ops = tr.reduce(synthetic())["ops"]
    # the while's self time is its bubble; children keep their own
    assert ops[("jit_decode", "%while.1")] == pytest.approx(20e-6)
    assert ops[("jit_decode", "%k.1[mosaic]")] == pytest.approx(100e-6)
    # fusion.2: 80 + 300 + the 20 us of the straddling op inside the window
    assert ops[("jit_decode", "%fusion.2")] == pytest.approx(400e-6)
    assert ops[("jit_pchunk", "%fusion.7")] == pytest.approx(100e-6)


def test_gaps_named_by_host_span():
    gaps = dict(tr.reduce(synthetic())["gaps"])
    # 0..100 and 800..850, 950..980 under engine_step; 300..500 mostly idle
    assert gaps["bench.engine_step"] == pytest.approx((100 + 50 + 30) * 1e-6)
    assert gaps["bench.idle"] == pytest.approx(200e-6)
    assert sum(gaps.values()) == pytest.approx((1000 - 620) * 1e-6)


def test_kernel_lookup_and_breakdown():
    red = tr.reduce(synthetic())
    assert tr.op_seconds(red, "tpu_custom_call") == (
        pytest.approx(100e-6), 1)
    assert tr.op_seconds(red, "no_such_kernel") == (0, 0)
    bd = tr.breakdown(red, top=2)
    assert [n for n, _ in bd["device_ops"]] == [
        "jit_decode/%fusion.2", "jit_decode/%k.1[mosaic]"] or \
        bd["device_ops"][0][0] == "jit_decode/%fusion.2"
    assert bd["idle_gaps"][0][0] == "bench.idle"


def test_no_window_span_is_an_error():
    prof = synthetic()
    prof.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        tr.reduce(prof)
