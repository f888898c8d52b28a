"""flops.py against numbers worked by hand for both configurations."""

import json
import os

import pytest

from benchmark import flops

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cfg(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_760m_counts():
    c = cfg("gpt3-large-760m")
    # per layer 4*1536^2 + 2*1536*6144 = 9,437,184 + 18,874,368
    assert flops.matmul_params(c) == 24 * 28_311_552 + 50304 * 1536
    assert flops.matmul_params(c) == 756_744_192
    # + biases/norms 9*1536+6144 a layer, positions, final norm
    assert flops.n_params(c) == (756_744_192 + 24 * 19_968
                                 + 2048 * 1536 + 2 * 1536)
    # one step of 4 x 2048: 6 per matmul parameter and token ...
    dense = 6 * 756_744_192 * 8192
    # ... + 3 x (4 * L * d * pairs), pairs = 4 * 2048*2049/2
    attn = 3 * 4 * 24 * 1536 * 4 * 2_098_176
    assert flops.train_step_flops(c, 4, 2048) == dense + attn
    assert flops.flash_flops(c, 4, 2048) == attn
    assert attn / dense == pytest.approx(0.0998, abs=1e-3)
    # q,k,v,o forward and 8 more tensors backward, bf16
    assert flops.flash_bytes(c, 4, 2048) == 12 * 24 * 8192 * 1536 * 2


def test_1_3b_decode_step():
    c = cfg("gpt3-xl-1.3b")
    assert flops.matmul_params(c) == 24 * (4 * 2048 ** 2 + 2 * 2048 * 8192) \
        + 50304 * 2048
    live = [100, 300]
    assert flops.serve_tokens_flops(c, live) == (
        2 * flops.matmul_params(c) * 2 + 4 * 24 * 2048 * 400)
    w = flops.weight_bytes(c)
    assert w == (flops.n_params(c) - 2048 * 2048) * 2
    assert 2.6e9 < w < 2.65e9
    assert flops.decode_step_bytes(c, live) == w + 2 * 24 * 2048 * 400 * 2


def test_roofline_says_which_bound():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.roofline_seconds(197e12, 1, peak) == (1.0, "flops")
    t, bound = flops.roofline_seconds(1, 819e9, peak)
    assert (t, bound) == (1.0, "bytes")
