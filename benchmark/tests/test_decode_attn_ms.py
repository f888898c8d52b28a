"""``decode_attn_ms`` (PR 27) on synthetic reductions of a trace: the
kernel's device time over the decode program's launches, and ``None``
where there is nothing to read — no trace, no launch, or a decode program
that runs no such kernel (any commit before PR 27)."""

import pytest

from benchmark.layer_metrics import decode_attn_ms


def obs(ops, launches=(0.006, 0.006, 0.006, 0.006)):
    return {"trace": {"programs": {"jit_decode": list(launches),
                                   "jit_pchunk": [0.03]},
                      "ops": ops}}


def read(o):
    return decode_attn_ms.read("decode_attn_ms", o, {}, {}, {})


def test_kernel_time_a_launch():
    o = obs({("jit_decode", "%paged_decode_attn.3[mosaic]"): 0.0020,
             ("jit_decode", "%fusion.183"): 0.0080,
             ("jit_pchunk", "%paged_decode_attn.9[mosaic]"): 0.5,
             ("jit_decode", "%paged_decode_attn_like_fusion"): 0.5})
    assert read(o) == pytest.approx(0.5)       # 2 ms over four launches


@pytest.mark.parametrize("o", [
    {},                                                   # no trace taken
    {"trace": None},
    obs({("jit_decode", "%convert.35"): 0.9}),            # the XLA twin
    obs({("jit_decode", "%paged_decode_attn.3[mosaic]"): 0.002},
        launches=()),
])
def test_nothing_to_read(o):
    assert read(o) is None
