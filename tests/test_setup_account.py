"""The program accounts for its own set-up (PR 26).

``core.compile_cache`` listens to ``jax.monitoring``: every trace, lowering,
backend compile and hit or miss of the persistent cache leaves a record in
``compile_cache.log()``, the one store of the account; the lifecycle spans
(``profiler.host_tracer``, ``level=0``) mark the package's import, a model's
and an engine's construction, the first build of a serving program and the
first call of a train step, with no profiler running.  CPU only; nothing
here is a timing.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import compile_cache
from paddle_tpu.profiler import counters, host_tracer

def _new_records(n_before):
    return compile_cache.log()[n_before:]


def _phases(records):
    return [r[1] for r in records]


# ---------------------------------------------------------------------------
# the listener
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def one_compile():
    """One ``jax.jit`` of a name of its own, compiled and then called
    again: the records each call left."""
    def account_probe(x):
        return jnp.tanh(x) @ x

    f = jax.jit(account_probe)
    x = np.ones((4, 4), np.float32)
    n = len(compile_cache.log())
    t0 = time.perf_counter_ns()
    f(x).block_until_ready()
    t1 = time.perf_counter_ns()
    records = _new_records(n)
    f(x).block_until_ready()                      # a steady call
    return {"records": records, "steady": _new_records(n + len(records)),
            "t": (t0, t1)}


@pytest.mark.parametrize("phase", ["trace", "lower", "backend"])
def test_a_compile_is_in_the_log_with_its_seconds(one_compile, phase):
    took = [r[3] for r in one_compile["records"] if r[1] == phase]
    assert took and all(s > 0 for s in took)


def test_the_log_is_the_one_store(one_compile):
    """No counter beside it, and the compile layer knows nothing of the
    profiler (``profiler`` imports ``core.flags``: no cycle)."""
    assert one_compile["records"]
    assert not [k for k in counters.snapshot() if k.startswith("compile.")]
    assert not [v for v in vars(compile_cache).values()
                if getattr(v, "__name__", "").startswith(
                    "paddle_tpu.profiler")]


@pytest.mark.parametrize("phase,fun_name", [("trace", "account_probe"),
                                            ("lower", "jit(account_probe)"),
                                            ("backend",
                                             "jit(account_probe)")])
def test_a_compile_leaves_a_record_with_its_fun_name(one_compile, phase,
                                                     fun_name):
    t0, t1 = one_compile["t"]
    found = [r for r in one_compile["records"]
             if r[0] == fun_name and r[1] == phase]
    assert len(found) == 1
    _, _, end_ns, seconds = found[0]
    assert t0 <= end_ns <= t1                  # on perf_counter_ns
    assert 0 < seconds <= (t1 - t0) / 1e9


def test_the_records_of_one_program_come_in_order(one_compile):
    own = [r[1] for r in one_compile["records"]
           if r[0] and "account_probe" in r[0]]
    assert own == ["trace", "lower", "backend"]


def test_no_listener_runs_on_a_steady_call(one_compile):
    assert one_compile["steady"] == []


def test_the_listeners_are_registered_once():
    from jax._src import monitoring
    import importlib
    importlib.import_module("paddle_tpu.core.compile_cache")
    assert monitoring.get_event_duration_listeners().count(
        compile_cache._on_duration) == 1
    assert monitoring.get_event_listeners().count(
        compile_cache._on_event) == 1


def test_events_of_other_layers_are_ignored():
    n = len(compile_cache.log())
    compile_cache._on_duration("/jax/checkpoint/write/durations", 1.0)
    compile_cache._on_event("/jax/compilation_cache/tasks_using_cache")
    assert not _new_records(n)


def test_a_cache_load_has_no_fun_name():
    n = len(compile_cache.log())
    compile_cache._on_duration(
        "/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
    (rec,) = _new_records(n)
    assert rec[:2] == (None, "cache_load") and rec[3] == 0.25


def test_a_hit_and_a_miss_are_records_of_zero_seconds():
    n = len(compile_cache.log())
    t0 = time.perf_counter_ns()
    compile_cache._on_event("/jax/compilation_cache/cache_hits")
    compile_cache._on_event("/jax/compilation_cache/cache_misses")
    hit, miss = _new_records(n)
    assert (hit[:2], miss[:2]) == ((None, "cache_hit"), (None, "cache_miss"))
    assert hit[3] == miss[3] == 0.0
    assert t0 <= hit[2] <= miss[2] <= time.perf_counter_ns()


# ---------------------------------------------------------------------------
# the persistent cache: a miss, then a hit
# ---------------------------------------------------------------------------
_FOUND = {}      # the cache configuration the fixture below found


@pytest.fixture
def temporary_cache(tmp_path):
    """A persistent cache in a temporary directory that keeps every
    program; the configuration found is put back, since the suite runs
    without a persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ["jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes"]
    found = {k: getattr(jax.config, k) for k in keys}
    _FOUND.update(found)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()
    try:
        yield tmp_path
    finally:
        for k, v in found.items():
            jax.config.update(k, v)
        cc.reset_cache()


def _twin():
    """Two jitted functions of one program: the second is a function JAX
    has not seen (so it traces, lowers and asks the backend again, as a
    new process would) whose module and cache key are the first's."""
    def cache_probe(x):
        return jnp.cos(x) * 3.0 + x

    def again(x):
        return jnp.cos(x) * 3.0 + x
    again.__name__ = again.__qualname__ = "cache_probe"
    return jax.jit(cache_probe), jax.jit(again)


def test_first_compile_misses_and_the_second_hits(temporary_cache):
    first, second = _twin()
    x = np.ones((8,), np.float32)
    n = len(compile_cache.log())
    first(x).block_until_ready()
    mine = [r for r in _new_records(n)
            if r[0] is None or "cache_probe" in r[0]]
    assert _phases(mine) == ["trace", "lower", "cache_miss", "backend"]
    assert any(temporary_cache.iterdir())          # the entry was written
    n = len(compile_cache.log())
    second(x).block_until_ready()
    mine = [r for r in _new_records(n)
            if r[0] is None or "cache_probe" in r[0]]
    assert _phases(mine) == ["trace", "lower", "cache_hit", "cache_load",
                             "backend"]
    assert mine[3][3] > 0                          # the load's own seconds


def test_the_cache_configuration_is_put_back():
    assert _FOUND, "runs after the test that uses the fixture"
    assert {k: getattr(jax.config, k) for k in _FOUND} == _FOUND


# ---------------------------------------------------------------------------
# the log is bounded
# ---------------------------------------------------------------------------
def test_the_log_is_bounded(monkeypatch):
    import collections
    # a log of its own: the process's real one keeps its records
    monkeypatch.setattr(compile_cache, "_LOG", collections.deque(
        maxlen=compile_cache.LOG_LIMIT))
    for _ in range(compile_cache.LOG_LIMIT + 5):
        compile_cache._on_event("/jax/compilation_cache/cache_hits")
    assert len(compile_cache.log()) == compile_cache.LOG_LIMIT


# ---------------------------------------------------------------------------
# lifecycle spans, with no profiler running
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_engine():
    """A tiny model and paged engine built, one request served, with no
    session of either kind: the lifecycle spans that left."""
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import LLMEngine
    assert not host_tracer.enabled()
    n = len(host_tracer.lifecycle())
    paddle.seed(7)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        max_seq_len=32, use_flash_attention=False))
    model.eval()
    eng = LLMEngine(model, max_slots=2, max_seq_len=32,
                    min_bucket=4, block_size=4, prefill_chunk=8)
    h = eng.add_request(np.arange(1, 7, dtype=np.int32), max_new_tokens=3,
                        seed=0)
    for _ in range(50):
        if h.is_finished:
            break
        eng.step()
    assert h.is_finished
    return host_tracer.lifecycle()[n:]


def test_the_packages_import_holds_its_first_touch_of_the_device():
    touch, whole = host_tracer.lifecycle()[:2]
    assert (touch[0], touch[4], touch[5]) \
        == ("setup.first_device_touch", 0, None)
    assert (whole[0], whole[4], whole[5]) == ("setup.import", 0, None)
    assert whole[2] < touch[2] < touch[3] < whole[3] <= time.perf_counter_ns()
    assert "setup.import" not in [e[0] for e in host_tracer.events()]


@pytest.mark.parametrize("name", ["setup.model_init", "serving.engine_init",
                                  "serving.program_build"])
def test_building_a_tiny_engine_leaves_the_span(tiny_engine, name):
    assert name in [e[0] for e in tiny_engine]


def test_lifecycle_spans_of_a_tiny_engine_in_order(tiny_engine):
    spans = [(e[0], e[5]) for e in tiny_engine]
    assert spans[:2] == [("setup.model_init", None),
                         ("serving.engine_init", None)]
    builds = [c for n, c in spans if n == "serving.program_build"]
    assert {"key": "prefill_paged", "bucket": 8} in builds
    assert {"key": "decode_paged"} in builds
    assert len(builds) == len({tuple(sorted(c.items())) for c in builds})
    # none of them went to the session store: nobody was profiling (the
    # store may still hold what an earlier session of this process left)
    began = min(e[2] for e in tiny_engine)
    assert not [e for e in host_tracer.events() if e[2] >= began]


def test_a_train_steps_first_calls_and_none_after():
    from paddle_tpu.jit import CompiledTrainStep
    paddle.seed(11)
    model = paddle.nn.Linear(4, 4)
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    step = CompiledTrainStep(
        model, lambda m, x, y: ((m(x) - y) ** 2).mean(), opt)
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    n, traces = len(host_tracer.lifecycle()), counters.get("jit.traces")
    step(x, x)
    step(x, x)       # AdamW's moments exist now: the step traces again
    first = host_tracer.lifecycle()[n:]
    assert [e[0] for e in first] == ["jit.first_call"] * len(first)
    assert sum(e[5]["traces"] for e in first) \
        == counters.get("jit.traces") - traces >= 1
    n = len(host_tracer.lifecycle())
    step(x, x)
    step(x, x)
    assert len(host_tracer.lifecycle()) == n      # steady: no lifecycle span
