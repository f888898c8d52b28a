"""``decode_overlap_step_share``: the program's two counters as a share;
0.0 for a program that has the counter and never overlapped; ``None`` for
a program without it or one that has not decoded."""

import pytest

from benchmark.layer_metrics import decode_overlap_step_share as metric
from paddle_tpu.profiler import counters


@pytest.fixture(autouse=True)
def clean():
    for name in (metric.OVERLAPPED, metric.STEPS):
        counters.reset(name)
    yield
    for name in (metric.OVERLAPPED, metric.STEPS):
        counters.reset(name)


def read():
    return metric.read("decode_overlap_step_share", {}, {}, {}, {})


def test_share_of_the_launches():
    counters.inc(metric.STEPS, 40)
    counters.inc(metric.OVERLAPPED, 37)
    assert read() == pytest.approx(92.5)


def test_zero_when_no_launch_overlapped():
    counters.inc(metric.STEPS, 8)
    counters.inc(metric.OVERLAPPED, 0)   # how a synchronous engine registers it
    assert read() == 0.0


def test_nothing_without_the_counter_or_a_launch():
    assert read() is None
    counters.inc(metric.STEPS, 8)        # a program without the counter
    assert read() is None
    counters.reset(metric.STEPS)
    counters.inc(metric.OVERLAPPED, 0)
    assert read() is None


def test_the_engine_keeps_the_counter():
    """The names are the program's: one request through the engine makes
    six launches; the first uploads, the five after it are each enqueued
    behind an unread launch, and the step after the last only reads it
    back (its row gets its last token there)."""
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
    h = eng.add_request([1, 2, 3], max_new_tokens=7)
    while not h.is_finished:
        eng.step()
    now = counters.snapshot()
    assert now[metric.STEPS] == 6 and now[metric.OVERLAPPED] == 5
    assert read() == pytest.approx(500 / 6)
