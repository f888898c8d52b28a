"""The readers of the program's own spans, counts, kernel names and set-up
account (PR 26), each on a synthetic ``obs``, span list and compile log
with known answers, the ``None`` cases among them (no trace, a program
without spans or without the account), the filter by the traced part of
the window and the cut at the instant the window opened."""

import pytest

from benchmark.layer_metrics import (flash_bwd_ms, flash_fwd_ms,
                                     input_stall_ms_per_step,
                                     kv_blocks_live_peak_share,
                                     serve_host_self_p50_ms,
                                     serve_operand_upload_p50_ms, setup)

T_START = 100.0           # the window's zero on perf_counter, seconds
TRACED = (45.0, 50.0)


def span(name, start_s, dur_ms, counts=None, tid=1, depth=1):
    """A span as the program keeps it, ``start_s`` on the window's clock."""
    t0 = int(round((T_START + start_s) * 1e9))
    return (name, tid, t0, t0 + int(round(dur_ms * 1e6)), depth, counts)


def step(start_s, dur_ms, wait_ms, operands_ms, live, tid=1):
    """One ``serving.step`` with a decode launch inside it."""
    counts = {"blocks_live": live, "blocks_total": 1024}
    return [span("serving.step", start_s, dur_ms, counts, tid, 0),
            span("serving.decode.operands", start_s + 1e-4, operands_ms,
                 tid=tid),
            span("serving.decode.wait", start_s + 1e-4 + operands_ms / 1e3,
                 wait_ms, tid=tid)]


@pytest.fixture
def store(monkeypatch):
    """Put a span list where the readers look for it."""
    from paddle_tpu.profiler import host_tracer

    def put(events):
        monkeypatch.setattr(host_tracer, "events", lambda: list(events))
    return put


def serve_obs(**kw):
    return {"t_start": T_START, "traced": TRACED, "steps": [], **kw}


ARGS = (None, None, None)          # cell, cfg, peak: not read by these


def test_host_self_upload_and_live_blocks(store):
    store(step(40.0, 80.0, 70.0, 1.0, live=900)       # before the trace
          + step(45.5, 80.0, 72.0, 2.0, live=300)
          + step(46.0, 90.0, 84.0, 3.0, live=512)
          + step(46.5, 78.0, 73.0, 4.0, live=256)
          + step(46.6, 10.0, 9.0, 0.5, live=100, tid=2)   # another thread
          + step(50.5, 80.0, 10.0, 9.0, live=1000))   # after it
    obs = serve_obs()
    # step minus its own thread's wait: 8, 6, 5 and, on thread 2, 1
    assert serve_host_self_p50_ms.read("m", obs, *ARGS) == pytest.approx(5.5)
    assert serve_operand_upload_p50_ms.read("m", obs, *ARGS) == \
        pytest.approx(2.5)
    assert kv_blocks_live_peak_share.read("m", obs, *ARGS) == \
        pytest.approx(50.0)


def test_a_wait_outside_its_step_is_not_subtracted(store):
    store([span("serving.step", 46.0, 80.0, None, depth=0),
           span("serving.prefill.wait", 46.01, 30.0),
           span("serving.decode.wait", 46.05, 40.0),      # ends after it
           span("serving.spec.round", 46.02, 5.0)])
    assert serve_host_self_p50_ms.read("m", serve_obs(), *ARGS) == \
        pytest.approx(50.0)


@pytest.mark.parametrize("reader", [serve_host_self_p50_ms,
                                    serve_operand_upload_p50_ms,
                                    kv_blocks_live_peak_share])
def test_span_readers_find_nothing(store, reader):
    store(step(46.0, 80.0, 70.0, 1.0, live=10))
    obs = serve_obs()
    del obs["traced"]                                  # a --trace 0 run
    assert reader.read("m", obs, *ARGS) is None
    store([])                                          # a program without
    assert reader.read("m", serve_obs(), *ARGS) is None
    store([("serving.step", 1, 0, 1, 0)])              # five-field events
    assert reader.read("m", serve_obs(), *ARGS) is None


def test_live_share_needs_the_counts(store):
    store([span("serving.step", 46.0, 80.0, None, depth=0),
           span("serving.step", 46.1, 80.0, None, depth=0)])
    assert kv_blocks_live_peak_share.read("m", serve_obs(), *ARGS) is None


def red(ops, labels):
    return {"ops": ops, "labels": labels}


def test_kernel_milliseconds_per_step():
    ops = {("jit_step_fn", "%flash_fwd.13[mosaic]"): 0.060,
           ("jit_step_fn", "%flash_fwd.14[mosaic]"): 0.062,
           ("jit_step_fn", "%transpose_jvp_flash_dkv__.1[mosaic]"): 0.110,
           ("jit_step_fn", "%flash_dq.10[mosaic]"): 0.058,
           ("jit_step_fn", "%rms_norm.3[mosaic]"): 0.500,
           ("jit_step_fn", "%flash_fwd_fusion.2"): 0.700,   # not a kernel
           ("jit_step_fn", "%fusion.380"): 0.900}
    obs = {"trace": red(ops, {}), "traced_steps": 2}
    assert flash_fwd_ms.read("m", obs, *ARGS) == pytest.approx(61.0)
    assert flash_bwd_ms.read("m", obs, *ARGS) == pytest.approx(84.0)
    unnamed = {"trace": red({("jit_step_fn", "%checkpoint.18[mosaic]"): 1.0},
                            {}), "traced_steps": 2}
    assert flash_fwd_ms.read("m", unnamed, *ARGS) is None      # the parent
    assert flash_bwd_ms.read("m", {"traced_steps": 2}, *ARGS) is None
    assert flash_fwd_ms.read("m", {"trace": red(ops, {})}, *ARGS) is None


def test_input_stall_per_step():
    obs = {"steps": 100, "counters": {"io.prefetch_stall_ns": 25_000_000}}
    assert input_stall_ms_per_step.read("m", obs, *ARGS) == \
        pytest.approx(0.25)
    assert input_stall_ms_per_step.read(
        "m", {"steps": 100, "counters": {}}, *ARGS) == 0.0
    assert input_stall_ms_per_step.read("m", {"steps": 0, "counters": {}},
                                        *ARGS) is None
    assert input_stall_ms_per_step.read("m", {"steps": [1, 2]},
                                        *ARGS) is None


# ---------------------------------------------------------------------------
# the set-up account
# ---------------------------------------------------------------------------
def rec(fun_name, phase, end_s, seconds):
    """A record of the compile log; ``end_s`` on the window's clock."""
    return (fun_name, phase, int(round((T_START + end_s) * 1e9)), seconds)


LOG = [rec("matmul", "trace", -9.0, 0.5),            # inside the next one
       rec("decode", "trace", -8.5, 2.0),            # [-10.5, -8.5]
       rec("jit(decode)", "lower", -8.0, 0.5),       # [-8.5, -8.0]
       rec(None, "cache_hit", -7.9, 0.0),
       rec(None, "cache_load", -7.5, 0.4),           # inside the backend
       rec("jit(decode)", "backend", -7.0, 1.0),
       rec("pchunk", "trace", -6.0, 1.0),
       rec("jit(pchunk)", "lower", -5.5, 0.25),      # a gap before it
       rec(None, "cache_miss", -5.0, 0.0),
       rec("jit(pchunk)", "backend", -2.0, 3.0),
       rec("logits_rows", "trace", 60.0, 1.0),       # the check, after
       rec("jit(logits_rows)", "backend", 62.0, 2.0),
       rec(None, "cache_miss", 62.0, 0.0)]

SETUP = {"setup.trace_lower_s": 2.0 + 0.5 + 1.0 + 0.25,
         "setup.backend_compile_s": 4.0, "setup.programs": 2.0,
         "setup.cache_misses": 1.0}


@pytest.fixture
def account(monkeypatch):
    """Put a compile log and a lifecycle list where the reader looks."""
    from paddle_tpu.core import compile_cache
    from paddle_tpu.profiler import host_tracer

    def put(log, lifecycle=()):
        if log is None:
            monkeypatch.delattr(compile_cache, "log", raising=False)
        else:
            monkeypatch.setattr(compile_cache, "log", lambda: list(log),
                                raising=False)
        monkeypatch.setattr(host_tracer, "lifecycle",
                            lambda: list(lifecycle), raising=False)
    return put


@pytest.mark.parametrize("name", sorted(SETUP))
def test_setup_counts_what_ended_before_the_window(account, name):
    account(LOG)
    obs = {"t_start": T_START}
    assert setup.read(name, obs, *ARGS) == pytest.approx(SETUP[name])
    # a window that opened earlier sees less: only decode's records
    early = {"t_start": T_START - 6.5}
    first = {"setup.trace_lower_s": 2.5, "setup.backend_compile_s": 1.0,
             "setup.programs": 1.0, "setup.cache_misses": 0.0}
    assert setup.read(name, early, *ARGS) == pytest.approx(first[name])


def test_setup_import_span(account):
    imp = ("setup.import", 1, int((T_START - 20.0) * 1e9),
           int((T_START - 17.5) * 1e9), 0, None)
    late = ("setup.import", 1, int((T_START + 1.0) * 1e9),
            int((T_START + 2.0) * 1e9), 0, None)
    other = ("setup.model_init", 1, int((T_START - 15.0) * 1e9),
             int((T_START - 14.0) * 1e9), 0, None)
    touch = ("setup.first_device_touch", 1, int((T_START - 19.5) * 1e9),
             int((T_START - 18.0) * 1e9), 0, None)
    obs = {"t_start": T_START}
    account(LOG, [other, imp, late])       # a program that keeps no touch
    assert setup.read("setup.package_import_s", obs, *ARGS) == \
        pytest.approx(2.5)
    assert setup.read("setup.first_device_touch_s", obs, *ARGS) is None
    account(LOG, [touch, other, imp, late])
    assert setup.read("setup.first_device_touch_s", obs, *ARGS) == \
        pytest.approx(1.5)
    assert setup.read("setup.package_import_s", obs, *ARGS) == \
        pytest.approx(1.0)                 # the package without the device
    account(LOG, [other, late])
    assert setup.read("setup.package_import_s", obs, *ARGS) is None


@pytest.mark.parametrize("name", sorted(SETUP) + ["setup.package_import_s",
                                                 "setup.first_device_touch_s"])
def test_setup_finds_nothing_in_a_program_without_the_account(
        account, monkeypatch, name):
    from paddle_tpu.profiler import host_tracer
    account(None)                                      # the parent: no log()
    monkeypatch.delattr(host_tracer, "lifecycle")
    assert setup.read(name, {"t_start": T_START}, *ARGS) is None


def test_setup_says_nothing_once_the_log_has_dropped_records(account):
    from paddle_tpu.core import compile_cache
    account([rec("f", "backend", -1.0, 0.1)] * compile_cache.LOG_LIMIT)
    assert setup.read("setup.programs", {"t_start": T_START}, *ARGS) is None
    assert setup.read("setup.unknown", {"t_start": T_START}, *ARGS) is None
