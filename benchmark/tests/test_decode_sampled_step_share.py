"""``decode_sampled_step_share`` (PR 29): the program's two counters as a
share; 0.0 for a program that has the counter and never sampled; ``None``
for a program without it (the parent) or one that has not decoded."""

import pytest

from benchmark.layer_metrics import decode_sampled_step_share as metric
from paddle_tpu.profiler import counters


@pytest.fixture(autouse=True)
def clean():
    for name in (metric.SAMPLED, metric.STEPS):
        counters.reset(name)
    yield
    for name in (metric.SAMPLED, metric.STEPS):
        counters.reset(name)


def read():
    return metric.read("decode_sampled_step_share", {}, {}, {}, {})


def test_share_of_the_launches():
    counters.inc(metric.STEPS, 8)
    counters.inc(metric.SAMPLED, 2)
    assert read() == pytest.approx(25.0)


def test_zero_when_every_launch_was_greedy():
    counters.inc(metric.STEPS, 8)
    counters.inc(metric.SAMPLED, 0)      # how the engines register it
    assert read() == 0.0


def test_nothing_without_the_counter_or_a_launch():
    assert read() is None
    counters.inc(metric.STEPS, 8)        # the parent: steps, no such counter
    assert read() is None
    counters.reset(metric.STEPS)
    counters.inc(metric.SAMPLED, 0)
    assert read() is None


def test_the_engine_keeps_the_counter():
    """The names are the program's: a greedy request through the paged
    engine leaves both counters, the second at 0."""
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
    h = eng.add_request([1, 2, 3], max_new_tokens=3)
    while not h.is_finished:
        eng.step()
    assert read() == 0.0
