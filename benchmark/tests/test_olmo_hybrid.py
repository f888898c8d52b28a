"""The ``olmo_hybrid`` family's files: counts pinned to hand counts, the
weights dealt alike to program and reference, the new readers on made-up
observations."""

import json
import os

import jax.numpy as jnp
import numpy as np

from conftest import ROOT

from benchmark import olmo_hybrid_flops as hf
from benchmark import olmo_hybrid_weights as hw
from benchmark.layer_metrics import (hybrid_decode_roofline,
                                     hybrid_serve_step_mfu,
                                     prefill_chunk_program_p50_ms,
                                     recurrent_state_share)
from benchmark.reference import olmo_hybrid as ref

CFG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "olmo-hybrid-7b.json")))
TINY = dict(CFG, **CFG["rehearse"])
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_counts_are_the_hand_counts():
    # q, k: 3840 x 2880 each; v, gate, out: 3840 x 5760 each
    assert hf.linear_mixer_params(CFG) == 2 * 11_059_200 + 3 * 22_118_400 \
        == 88_473_600
    assert hf.full_mixer_params(CFG) == 58_982_400
    assert hf.mlp_params(CFG) == 126_812_160
    # 12 linear + 4 full layers and the head; the issue's 215.6 M / 185.8 M
    assert hf.matmul_params(CFG) == (12 * 215_285_760 + 4 * 185_794_560
                                     + 385_351_680) == 3_711_959_040
    # + embedding, final gain, and per layer: 2 gains; a linear layer's
    # a/b projections, A_log, dt_bias, filters, gain; a full layer's QK gains
    assert hf.n_params(CFG) == 4_100_788_944
    assert hf.weight_bytes(CFG) == 2 * (4_100_788_944 - 385_351_680)
    assert hf.attention_flops(CFG, 10) == 4 * 4 * 3840 * 10
    assert hf.delta_rule_flops(CFG, 3) == 12 * 3 * 30 * 7 * 96 * 192
    assert hf.serve_flops(CFG, 2, 5) == (2 * 3_711_959_040 * 2
                                         + 4 * 4 * 3840 * 5
                                         + 12 * 2 * 30 * 7 * 96 * 192)
    # one row: 12 layers x (30 x 96 x 192 float32 + 3 x 11520 bf16)
    assert hf.state_bytes_per_row(CFG) == 12 * (2_211_840 + 69_120)
    assert hf.decode_step_bytes(CFG, [100, 50]) == (
        hf.weight_bytes(CFG) + 2 * 4 * 3840 * 150 * 2
        + 2 * 2 * 12 * (2_211_840 + 69_120))


def test_program_and_reference_hold_the_same_weights():
    seed = 2 ** 31 + 5
    prog = dict(hw.program(TINY, seed, "float32"))
    layers = [hw.layer(TINY, seed, l, "float32") for l in range(4)]
    lin, full = layers[:3], layers[3:]
    np.testing.assert_array_equal(prog["lin_qkv_w"][1], jnp.concatenate(
        [lin[1]["wq"], lin[1]["wk"], lin[1]["wv"]], -1))
    np.testing.assert_array_equal(prog["lin_conv_w"][2], jnp.concatenate(
        [lin[2]["conv_q"], lin[2]["conv_k"], lin[2]["conv_v"]], -1))
    np.testing.assert_array_equal(prog["lin_ab_w"][0], jnp.concatenate(
        [lin[0]["wa"], lin[0]["wb"]], -1))
    np.testing.assert_array_equal(prog["att_qkv_w"][0], jnp.concatenate(
        [full[0]["wq"], full[0]["wk"], full[0]["wv"]], -1))
    np.testing.assert_array_equal(prog["down_w"][3], layers[3]["w_down"])
    np.testing.assert_array_equal(prog["lin_A_log"][1], lin[1]["A_log"])
    top = hw.top(TINY, seed, "float32")
    np.testing.assert_array_equal(prog["lm_head"], top["head"])
    # layers differ, and the decay is neither 0 nor 1
    assert not np.array_equal(lin[0]["wq"], lin[1]["wq"])
    decay = np.exp(-np.exp(lin[0]["A_log"]) * np.log1p(np.exp(
        lin[0]["dt_bias"])))
    assert np.all((decay > 0.1) & (decay < 1.0))


def test_fp8_control_moves_the_logits():
    top = hw.top(TINY, 3, "float32")
    layer = lambda l: hw.layer(TINY, 3, l, "float32")          # noqa: E731
    ids = np.arange(40) % 512
    hi = np.asarray(ref.logits_rows(top, layer, TINY, ids, 30, 8, "f32"))
    lo = np.asarray(ref.logits_rows(top, layer, TINY, ids, 30, 8, "fp8"))
    assert hi.shape == (8, 512)
    assert 1e-3 < np.abs(hi - lo).max() < 10.0


def _obs():
    steps = [{"start": 1.0, "decode_live": [100, 200], "prefill": [512, 512 * 300]},
             {"start": 2.0, "decode_live": [], "prefill": [0, 0]},
             {"start": 9.0, "decode_live": [5], "prefill": [0, 0]}]
    trace = {"programs": {"jit_decode(1)": [0.020], "jit_pchunk(7)":
                          [0.030, 0.050], "jit_pchunk(9)": [0.040]},
             "ops": {}}
    return {"traced": (0.5, 3.0), "steps": steps, "trace": trace}


def test_new_readers_on_made_up_observations():
    obs = _obs()
    need = hf.serve_flops(CFG, 512, 512 * 300) + hf.serve_tokens_flops(
        CFG, [100, 200])
    got = hybrid_serve_step_mfu.read("x", obs, {}, CFG, PEAK)
    assert abs(got - 100.0 * need / (2.5 * 197e12)) < 1e-9
    share, note = hybrid_decode_roofline.read("x", obs, {}, CFG, PEAK)
    least = hf.decode_step_bytes(CFG, [100, 200]) / 819e9
    assert abs(share - 100.0 * least / 0.020) < 1e-9 and note == "bound: bytes"
    assert share < 100.0
    assert prefill_chunk_program_p50_ms.read("x", obs, {}, CFG, PEAK) == 40.0
    # nothing to read: nothing reported, nothing raised
    empty = {"steps": [], "requests": []}
    for reader in (hybrid_serve_step_mfu, hybrid_decode_roofline,
                   prefill_chunk_program_p50_ms, recurrent_state_share):
        assert reader.read("x", empty, {}, CFG, PEAK) is None


def test_recurrent_state_share_reads_the_fullest_step(monkeypatch):
    from benchmark.layer_metrics import step_spans
    spans = [("serving.step", 0, 0.1, 0.2, {"state_bytes": 100,
                                            "kv_live_bytes": 100}),
             ("serving.step", 0, 0.3, 0.4, {"state_bytes": 100,
                                            "kv_live_bytes": 300}),
             ("serving.decode.wait", 0, 0.3, 0.4, None)]
    monkeypatch.setattr(step_spans, "traced_spans", lambda obs: spans)
    assert recurrent_state_share.read("x", {}, {}, CFG, PEAK) == 25.0
    # a model without recurrent layers leaves state_bytes 0: not reported
    monkeypatch.setattr(step_spans, "traced_spans", lambda obs: [
        ("serving.step", 0, 0.1, 0.2, {"state_bytes": 0,
                                       "kv_live_bytes": 5})])
    assert recurrent_state_share.read("x", {}, {}, CFG, PEAK) is None
