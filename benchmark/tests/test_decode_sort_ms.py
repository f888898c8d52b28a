"""``decode_sort_ms`` (PR 29) on synthetic reductions of a trace: the
decode program's ``sort*`` ops over its launches; 0.0 where it launched
and sorted nothing; ``None`` without a trace or a launch."""

import pytest

from benchmark.layer_metrics import decode_sort_ms


def obs(ops, launches=(0.006, 0.006, 0.006, 0.006)):
    return {"trace": {"programs": {"jit_decode": list(launches),
                                   "jit_pchunk": [0.03]},
                      "ops": ops}}


def read(o):
    return decode_sort_ms.read("decode_sort_ms", o, {}, {}, {})


def test_sort_time_a_launch():
    o = obs({("jit_decode", "%sort.6"): 0.0034,
             ("jit_decode", "%sort.9"): 0.0035,
             ("jit_decode", "%fusion.183"): 0.0080,
             ("jit_decode", "%resort_fusion.2"): 0.5,
             ("jit_pchunk", "%sort.6"): 0.5})
    assert read(o) == pytest.approx(1.725)      # 6.9 ms over four launches


def test_zero_not_nothing_when_no_sort_ran():
    o = obs({("jit_decode", "%fusion.183"): 0.0080,
             ("jit_decode", "%paged_decode_attn.3[mosaic]"): 0.002})
    assert read(o) == 0.0


@pytest.mark.parametrize("o", [
    {},                                                   # no trace taken
    {"trace": None},
    obs({("jit_decode", "%sort.6"): 0.002}, launches=()),
])
def test_nothing_to_read(o):
    assert read(o) is None
