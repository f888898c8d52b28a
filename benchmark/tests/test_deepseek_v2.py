"""The ``deepseek_v2`` family's files: counts pinned to the issue's table,
the weights dealt alike to program and reference, the configuration held to
the catalog's keys, the new readers on made-up observations."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ROOT

from benchmark import compare
from benchmark import deepseek_v2_flops as df
from benchmark import deepseek_v2_weights as dw
from benchmark import run as bench_run
from benchmark.kinds import serve_open_loop_deepseek_v2 as kind
from benchmark.layer_metrics import (mla_decode_attn_ms,
                                     mla_decode_attn_roofline,
                                     mla_prefill_attn_ms,
                                     mla_prefill_attn_roofline,
                                     moe_decode_roofline, moe_expert_ms,
                                     moe_load_max_over_mean,
                                     moe_prefill_expert_ms,
                                     moe_serve_step_mfu)

CFG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "deepseek-v2.json")))
TINY = dict(CFG, **CFG["rehearse"])
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the two readings of each limit (PERF.md section 6: my chip runs, PR 32)
PROGRAM_LARGEST = {"token_gap_mean": 0.0302, "token_gap_p99": 0.7919,
                   "route_disagreement": 0.0148,
                   "route_disagreement_decode": 0.0324}
CONTROL_SMALLEST = {"token_gap_mean": 0.3939, "token_gap_p99": 1.9239,
                    "route_disagreement": 0.0516,
                    "route_disagreement_decode": 0.2620}


def test_counts_are_the_issues_table():
    assert df.attention_params(CFG) == (
        5120 * 1536 + 1536 * 24576 + 5120 * 576 + 512 * 32768
        + 16384 * 5120) == 149_225_472
    assert df.dense_mlp_params(CFG) == 188_743_680
    assert df.shared_params(CFG) == 47_185_920
    assert df.router_params(CFG) == 819_200
    assert df.expert_params(CFG) == 23_592_960
    assert df.expert_layer_params(CFG) == 991_723_520
    assert df.n_params(CFG) == 5_163_909_120
    assert df.n_params(CFG, norms=True) == 5_163_909_120 + 66_560
    assert df.latent_bytes_per_token_layer(CFG) == 1_152
    # 10.33 GB of weights and a 262,144-token pool of 640-wide rows
    assert round(2 * df.n_params(CFG, norms=True) / 1e9, 2) == 10.33
    assert 5 * 16385 * 16 * 640 * 2 == 1_677_824_000
    # absorbed decode: 278,528 operations a cached row and layer, 242 a byte
    assert df.decode_attention_flops(CFG, 1) == 5 * 128 * (576 + 512) * 2
    ops, nbytes = df.mla_decode_attn_cost(CFG, [100, 50])
    assert ops == 5 * 278_528 * 150 and nbytes == 5 * 150 * 1_152
    assert round(ops / nbytes) == 242
    assert df.prefill_attention_flops(CFG, 7) == 2 * 5 * 128 * 320 * 7
    assert df.routed_flops(CFG, 3) == 2 * 23_592_960 * 3
    every = (5 * 149_225_472 + 188_743_680 + 4 * (47_185_920 + 819_200)
             + 5120 * 25600)
    assert df.dense_token_params(CFG) == every
    assert df.serve_flops(CFG, (2, 5), [9, 4], 1.5) == (
        2 * every * 4 + 2 * 23_592_960 * int(1.5 * 4 * 4)
        + 2 * 5 * 128 * 320 * 5 + 5 * 278_528 * 13)
    # one row decoding: 1.5 of the 40 held experts a layer in expectation
    touched = 40 * (1 - (39 / 40) ** 1.5)
    assert abs(df.weight_bytes_touched(CFG, 1, 1.5) - 2 * (
        every + 4 * touched * 23_592_960)) < 1
    assert abs(df.decode_step_bytes(CFG, [100], 1.5)
               - df.weight_bytes_touched(CFG, 1, 1.5) - 5 * 100 * 1152) < 1


def test_the_file_holds_the_catalog_keys_and_the_cut():
    rows = [json.loads(l) for l in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")] \
        if os.path.exists("/opt/skills/guides/model-configs/"
                          "architectures.jsonl") else []
    published = next((r["config"] for r in rows
                      if r["name"] == "DeepSeek-V2"), None)
    reduced = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert set(CFG["reduced"]) == reduced
    assert CFG["published"] == {"num_hidden_layers": 60,
                                "n_routed_experts": 160,
                                "vocab_size": 102400}
    assert (CFG["num_hidden_layers"], CFG["n_routed_experts"],
            CFG["vocab_size"], CFG["experts_held_first"]) == (5, 40, 25600,
                                                              0)
    assert dw.share(CFG) == (160, 0, 40)
    assert "four chips" in CFG["stands_for"] and "12" in CFG["stands_for"]
    if published is not None:
        for key, value in published.items():
            if key not in reduced:
                assert CFG[key] == value, key
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "deepseek-v2")
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"]


def test_program_and_reference_hold_the_same_weights():
    seed = 2 ** 31 + 5
    prog = dict(dw.program(TINY, seed, "float32"))
    from paddle_tpu.models import deepseek_v2 as ds
    width, first, held = dw.share(TINY)
    shapes = ds.param_shapes(ds.DeepseekV2Config.from_hf(
        TINY, experts_held=(first, held), n_routed_experts=width))
    assert {n: tuple(x.shape) for n, x in prog.items()} == {
        n: s for n, (s, _, _) in shapes.items()}
    l1, l2 = (dw.layer(TINY, seed, l, "float32") for l in (1, 2))
    np.testing.assert_array_equal(prog["q_b_w"][2], jnp.concatenate(
        [l2["w_uq"], l2["w_qr"]], -1))
    np.testing.assert_array_equal(prog["kv_a_w"][1], jnp.concatenate(
        [l1["w_dkv"], l1["w_kr"]], -1))
    np.testing.assert_array_equal(prog["kv_b_k_w"][1], l1["w_uk"])
    np.testing.assert_array_equal(prog["router_w"][1], l2["w_router"])
    assert l2["w_router"].shape == (64, 16)      # the published width
    np.testing.assert_array_equal(prog["shared_gu_w"][0], jnp.concatenate(
        [l1["sh_gate"], l1["sh_up"]], -1))
    ex = dw.expert(TINY, seed, 2, 5, "float32")
    np.testing.assert_array_equal(prog["expert_gu_w"][1, 5], jnp.concatenate(
        [ex["ex_gate"], ex["ex_up"]], -1))
    np.testing.assert_array_equal(prog["expert_down_w"][1, 5], ex["ex_down"])
    l0 = dw.layer(TINY, seed, 0, "float32")
    np.testing.assert_array_equal(prog["mlp_down_w"][0], l0["w_down"])
    # an expert's draw depends on its index over ALL the experts: a chip
    # holding 4-7 holds what the chip holding 0-7 holds there
    other = dict(TINY, n_routed_experts=4, experts_held_first=4)
    np.testing.assert_array_equal(
        dw.program_tensor(other, seed, "expert_down_w", "float32")[1, 1],
        prog["expert_down_w"][1, 5])
    assert not np.array_equal(prog["expert_down_w"][0, 5],
                              prog["expert_down_w"][1, 5])
    top = dw.top(TINY, seed, "float32")
    np.testing.assert_array_equal(prog["lm_head"], top["head"])


def test_route_disagreement_counts_what_the_other_table_lacks():
    a = np.array([[4, 0, 2], [1, 1, 0]])
    assert kind.disagreement([(a, a)]) == 0.0
    b = np.array([[3, 1, 2], [1, 1, 0]])         # one choice moved
    assert kind.disagreement([(a, b)]) == 2 / 16
    assert kind.disagreement([(a, np.zeros_like(a))]) == 1.0
    # step by step nothing cancels: two tokens that swap experts read 0
    # as one table and 1 as two
    one, other = np.array([[1, 0]]), np.array([[0, 1]])
    assert kind.disagreement([(one + other, other + one)]) == 0.0
    assert kind.disagreement([(one, other), (other, one)]) == 1.0
    assert kind.disagreement([]) is None
    numbers = kind.route_numbers([(a, b, True), (one, other, False)])
    assert numbers == {"route_disagreement": 4 / 18,
                       "route_disagreement_decode": 1.0}
    chosen = np.array([[[0, 9], [1, 2], [3, 0]]])   # one layer, 3 tokens
    table = kind._held_table({"n_routed_experts": 4}, chosen, 0, 2)
    assert table.tolist() == [[1, 1, 1, 0]]
    assert kind._held_table({"n_routed_experts": 4}, chosen, 2,
                            3).tolist() == [[1, 0, 0, 1]]
    cell = {"output": {"max": 512}, "prompt": {"max": 32768}}
    assert [kind._width(cell, n) for n in (3000, 4097, 20000, 33000)] == [
        4096, 8192, 32768, 33280]


def test_the_probe_places_each_steps_tokens():
    """Two requests of 150 and 70 prompt tokens, chunks of 64: the first
    takes three steps to its first token, the second is submitted then
    and prefills beside the first one's decode launches."""
    picked = [{"prompt": np.zeros(150)}, {"prompt": np.zeros(70)}]
    routed = [64, 64, 22, 64 + 1, 6 + 1, 2, 2]
    events = [[], [], [(0, 0)], [(0, 1)], [(0, 2), (1, 0)],
              [(0, 3), (1, 1)], [(0, 4), (1, 2)]]
    probe = {"steps": [{"routed": n, "events": ev}
                       for n, ev in zip(routed, events)]}
    spans, chunked = kind.placed(probe, picked, 64)
    assert spans[:3] == [[(0, 0, 64)], [(0, 64, 128)], [(0, 128, 150)]]
    assert spans[3] == [(0, 150, 151), (1, 0, 64)]
    assert spans[4] == [(0, 151, 152), (1, 64, 70)]
    assert spans[5] == [(0, 152, 153), (1, 70, 71)]
    assert chunked == [True] * 5 + [False] * 2
    probe["steps"][5]["routed"] = 3      # an idle row was counted
    with pytest.raises(RuntimeError):
        kind.placed(probe, picked, 64)


@pytest.mark.parametrize("number", kind.COMPARED)
def test_the_controls_readings_are_not_correct(number):
    """The cell's limits against the two readings each was set from
    (``PERF.md``): the program's largest passes, and the 8-bit control's
    smallest of any ONE number fails the run."""
    limits = bench_run._load(os.path.join(
        bench_run.HERE, "workloads", "deepseek-v2.longdoc.json"))["limits"]
    assert set(limits) == set(kind.COMPARED)
    ok, _ = compare.judge(PROGRAM_LARGEST, limits)
    assert ok
    ok, compared = compare.judge(
        dict(PROGRAM_LARGEST, **{number: CONTROL_SMALLEST[number]}), limits)
    assert not ok and compared[number]["value"] > limits[number]


def _obs():
    steps = [{"start": 1.0, "prefill": [1024, 1024 * 4000],
              "decode_live": [8000, 9000]},
             {"start": 1.5, "prefill": [0, 0], "decode_live": [100]},
             {"start": 9.0, "prefill": [0, 0], "decode_live": [5]}]
    ops = {("jit_decode", "%mla_decode_attn.1[mosaic]"): 0.004,
           ("jit_decode", "%ragged-dot-none.3[mosaic]"): 0.006,
           ("jit_decode", "%ragged-dot-none.4[mosaic]"): 0.002,
           ("jit_pchunk", "%ragged-dot-none.3[mosaic]"): 0.5,
           ("jit_pchunk", "%mla_prefill_attn.10[mosaic]"): 0.1,
           ("jit_pchunk(3)", "%mla_prefill_attn.9[mosaic]"): 0.02,
           ("jit_decode", "%fusion.1"): 0.01}
    per = np.array([[10, 30], [20, 20]])
    return {"traced": (0.0, 2.0), "steps": steps,
            "trace": {"programs": {"jit_decode(7)": [0.02, 0.03],
                                   "jit_pchunk": [0.3, 0.2, 0.1],
                                   "jit_pchunk(3)": [0.1]},
                      "ops": ops},
            "moe": {"assignments": 80, "tokens": 40, "per_expert": per}}


def test_new_readers_on_a_made_up_window():
    obs = _obs()
    held = moe_serve_step_mfu.held_per_token_layer(obs, CFG)
    assert held == 80 / (40 * 4)
    need = (df.serve_flops(CFG, [1024, 1024 * 4000], [8000, 9000], held)
            + df.serve_flops(CFG, [0, 0], [100], held))
    assert abs(moe_serve_step_mfu.read("", obs, {}, CFG, PEAK)
               - 100 * need / (2.0 * 197e12)) < 1e-9
    assert mla_decode_attn_ms.read("", obs, {}, CFG, PEAK) == 2.0
    assert abs(moe_expert_ms.read("", obs, {}, CFG, PEAK) - 4.0) < 1e-12
    assert moe_load_max_over_mean.read("", obs, {}, CFG, PEAK) == 1.5
    share, note = mla_decode_attn_roofline.read("", obs, {}, CFG, PEAK)
    ops, nbytes = df.mla_decode_attn_cost(CFG, [8000, 9000])
    ops2, nbytes2 = df.mla_decode_attn_cost(CFG, [100])
    least = max(ops / 197e12, nbytes / 819e9) + max(ops2 / 197e12,
                                                    nbytes2 / 819e9)
    assert abs(share - 100 * least / 0.004) < 1e-9
    assert note == "bound: flops"
    share, note = moe_decode_roofline.read("", obs, {}, CFG, PEAK)
    assert 0 < share and note == "bound: bytes"
    # the chunk programs: four launches in the trace
    assert abs(mla_prefill_attn_ms.read("", obs, {}, CFG, PEAK) - 30.0) < 1e-9
    assert moe_prefill_expert_ms.read("", obs, {}, CFG, PEAK) == 125.0
    share, note = mla_prefill_attn_roofline.read("", obs, {}, CFG, PEAK)
    ops, nbytes = df.mla_prefill_attn_cost(CFG, 1024, 1024 * 4000)
    assert ops == 5 * 128 * (128 + 64 + 128) * 2 * 1024 * 4000
    assert nbytes == 5 * 2 * (1024 * 128 * 320 + 4000 * 576)
    assert abs(share - 100 * (ops / 197e12) / 0.12) < 1e-9
    assert note == "bound: flops"


def test_new_readers_give_nothing_where_there_is_nothing():
    """A program without the counts or the kernels (any parent commit):
    ``None``, never 0 and never an error."""
    bare = {"traced": (0.0, 2.0), "steps": _obs()["steps"],
            "trace": {"programs": {"jit_decode(7)": [0.02]},
                      "ops": {("jit_decode", "%fusion.1"): 0.01}}}
    for reader in (moe_serve_step_mfu, moe_decode_roofline,
                   mla_decode_attn_ms, mla_decode_attn_roofline,
                   moe_expert_ms, moe_load_max_over_mean,
                   mla_prefill_attn_ms, mla_prefill_attn_roofline,
                   moe_prefill_expert_ms):
        assert reader.read("", bare, {}, CFG, PEAK) is None
        assert reader.read("", {"steps": []}, {}, CFG, PEAK) is None
        assert reader.read("", dict(bare, moe=None), {}, CFG, PEAK) is None
