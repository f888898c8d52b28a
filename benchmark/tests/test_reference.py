"""The plain reference against paddle_tpu at a tiny size, in float32."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import harness, weights
from benchmark.reference import gpt as ref

CFG = {"n_layers": 2, "d_model": 64, "n_heads": 4, "d_head": 16,
       "d_ff": 256, "n_ctx": 32, "vocab_size": 128,
       "layer_norm_epsilon": 1e-5, "initializer_range": 0.02,
       "compute_dtype": "float32",
       "program": {"use_flash_attention": False, "recompute": None},
       "optimizer": {"name": "AdamW", "learning_rate": 1e-3, "beta1": 0.9,
                     "beta2": 0.999, "epsilon": 1e-8, "weight_decay": 0.01}}


def test_weights_alone_equal_weights_together():
    all_ = weights.make(CFG, 2 ** 31 + 9, "float32")
    one = weights.make(CFG, 2 ** 31 + 9, "float32", ("w1",))["w1"]
    assert np.array_equal(np.asarray(all_["w1"]), np.asarray(one))
    prog = weights.make_program(CFG, 2 ** 31 + 9, "float32")
    assert np.array_equal(np.asarray(prog["qkv_w"][..., 64:128]),
                          np.asarray(all_["wk"]))
    other = weights.make(CFG, 2 ** 31 + 10, "float32", ("w1",))["w1"]
    assert not np.array_equal(np.asarray(other), np.asarray(one))


def test_forward_logits_match_the_program():
    import paddle_tpu as paddle
    model = harness.build_model(CFG, 5)
    model.eval()
    ids = np.random.default_rng(0).integers(0, 128, (1, 32)).astype(np.int32)
    got = np.asarray(model(paddle.to_tensor(ids)).numpy())[0]
    params = harness.reference_params(CFG, 5)
    want = np.asarray(ref.logits_rows(params, jnp.asarray(ids[0]),
                                      jnp.int32(0), 32, 4, 1e-5, "f32"))
    assert np.abs(got - want).max() < 2e-4
    rows = np.asarray(ref.logits_rows(params, jnp.asarray(ids[0]),
                                      jnp.int32(20), 8, 4, 1e-5, "f32"))
    assert np.abs(rows - want[20:28]).max() < 1e-5


def test_training_steps_match_the_program():
    from benchmark import compare
    from benchmark.kinds import train
    cell = {"batch": 2, "seq": 32, "check_steps": 3}
    step, feed, names = train.build(cell, CFG, 7)
    prog = train.first_steps(step, feed, names, cell, CFG, 7)
    train.dispose(step, feed)
    want = train.reference_readings(cell, CFG, 7)
    numbers, where = compare.train_numbers(prog, want)
    assert where["loss_gap_not_compared"] < 1e-5, where
    assert numbers["grad_gap"] < 1e-3, (numbers, where)
    assert numbers["change_gap"] < 1e-2, (numbers, where)
    # the key bias has no gradient under softmax: its change is not compared
    assert {f"bk[{i}]" for i in range(2)} <= compare.dead_leaves(
        want["grad_norms"])


def test_fp8_rounding_is_e4m3():
    x = jnp.asarray([[448.0, 17.0, 0.30, 1e-4]])
    got = np.asarray(ref._fp8(x, -1))[0]
    # 17 -> 16 or 18 (step 2 in [16, 32)); 0.30 -> 0.3125 (step 2**-5)
    assert got[0] == 448.0 and got[1] in (16.0, 18.0)
    assert got[2] == pytest.approx(0.3125)
    assert abs(got[3]) <= 2.0 ** -9
