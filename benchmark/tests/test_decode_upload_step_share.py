"""``decode_upload_step_share`` (PR 31): the program's two counters as a
share; 0.0 for a program that has the counter and never uploaded; ``None``
for a program without it (the parent) or one that has not decoded."""

import pytest

from benchmark.layer_metrics import decode_upload_step_share as metric
from paddle_tpu.profiler import counters


@pytest.fixture(autouse=True)
def clean():
    for name in (metric.UPLOADS, metric.STEPS):
        counters.reset(name)
    yield
    for name in (metric.UPLOADS, metric.STEPS):
        counters.reset(name)


def read():
    return metric.read("decode_upload_step_share", {}, {}, {}, {})


def test_share_of_the_launches():
    counters.inc(metric.STEPS, 40)
    counters.inc(metric.UPLOADS, 3)
    assert read() == pytest.approx(7.5)


def test_zero_when_no_launch_uploaded():
    counters.inc(metric.STEPS, 8)
    counters.inc(metric.UPLOADS, 0)      # how the engine registers it
    assert read() == 0.0


def test_nothing_without_the_counter_or_a_launch():
    assert read() is None
    counters.inc(metric.STEPS, 8)        # the parent: steps, no such counter
    assert read() is None
    counters.reset(metric.STEPS)
    counters.inc(metric.UPLOADS, 0)
    assert read() is None


def test_the_engine_keeps_the_counter():
    """The names are the program's: one request through the engine uploads
    on its first launch and on no other."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import LLMEngine
    paddle.seed(3)
    m = GPTForCausalLM(GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                                 num_heads=4, max_seq_len=32,
                                 use_flash_attention=False))
    m.eval()
    eng = LLMEngine(m, kv_layout="paged", max_slots=2, max_seq_len=32,
                    block_size=4, prefill_chunk=8, min_bucket=4)
    h = eng.add_request([1, 2, 3], max_new_tokens=6)
    while not h.is_finished:
        eng.step()
    now = counters.snapshot()
    assert now[metric.STEPS] == 5 and now[metric.UPLOADS] == 1
    assert read() == pytest.approx(20.0)
