"""The ``afmoe`` family's files: counts pinned to the cut's table, the
pools' bytes, the walk's and the step's operations, the configuration held
to the catalog's keys, the limits of ``correct`` against the controls'
readings, the new readers on made-up observations."""

import json
import os

import numpy as np

from conftest import ROOT

from benchmark import compare
from benchmark import run as bench_run
from benchmark import trinity_flops as tf
from benchmark import trinity_weights as tw
from benchmark.kinds import serve_open_loop_trinity as kind
from benchmark.layer_metrics import (trinity_serve_step_mfu,
                                     window_decode_attn_ms,
                                     window_decode_attn_roofline)

CFG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "trinity-large-preview.json")))
CELL = json.load(open(os.path.join(
    ROOT, "benchmark", "workloads", "trinity-large.longmix.json")))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the readings each limit was set from (PERF.md section 4: the program's
# largest over three seeds on a TPU v5e, the controls' smallest over two)
PROGRAM_LARGEST = {"token_gap_mean": 0.0069442,
                   "route_disagreement": 0.0052872}
CONTROL_SMALLEST = {"token_gap_mean": 0.0501944,
                    "route_disagreement": 0.0363953}
WINDOW_CONTROL_SMALLEST = {"token_gap_mean": 0.5668393,
                           "route_disagreement": 0.3240842}


def test_the_parameter_table_is_the_cuts():
    p = tw.parameters(CFG)
    gains = 4 * 3072 + 2 * 128
    assert p["attn"] - gains == 62_914_560
    assert p["dense"] == 113_246_208
    assert p["moe"] == 786_432 + 256 + 28_311_552
    assert p["expert"] == 28_311_552
    assert 32 * p["expert"] == 905_969_664
    assert p["top"] - 3072 == 153_747_456
    assert p["attn"] - gains + p["dense"] == 176_160_768
    assert (p["attn"] - gains + 786_432 + 28_311_552
            + 32 * p["expert"]) == 997_982_208
    assert p["total"] == 4_321_903_872          # 8.64 GB in bfloat16


def test_a_row_and_the_pools():
    assert tf.kv_bytes_per_token_layer(CFG) == 4096
    assert tf.layer_counts(CFG) == (1, 4, 4, 1)
    # a ring of ceil((4096 + 2048) / 16) + 1 entries for each of 32 slots
    assert tf.window_entries(CELL, CFG) == 385
    full, window = tf.pool_bytes(CELL, CFG)
    assert full == 16385 * 16 * 4096 == 1_073_807_360
    assert window == 4 * 12321 * 16 * 4096 == 3_229_876_224
    # weights and pools: 77 % of the chip's 16.9 GB
    assert 0.76 < (2 * 4_321_903_872 + full + window) / 16.9e9 < 0.78


def test_the_walks_operations_and_bytes():
    # rows of 100 and 10,000 keys: the full layer reads all of each, the
    # four window layers 100 and 4,096
    keys = (100 + 10_000) + 4 * (100 + 4096)
    flops, nbytes = tf.window_decode_attn_cost(CFG, [100, 10_000])
    assert keys == 26_884
    assert flops == 4 * 48 * 128 * keys
    assert nbytes == 4096 * keys


def test_the_steps_operations():
    assert tf.keys_full(0, 3) == 1 + 2 + 3
    assert tf.keys_window(0, 6, 4) == 1 + 2 + 3 + 4 + 4 + 4
    assert tf.keys_window(5, 3, 4) == 4 + 4 + 4
    assert tf.keys_window(2, 3, 4) == 3 + 4 + 4
    token = (5 * 62_914_560 + 113_246_208
             + 4 * (786_432 + 28_311_552))
    assert tf.token_params(CFG) == token
    head = 3072 * 25024
    per_key = 4 * 48 * 128
    # one 2,048-token chunk at 4,096 and one decoded row of 10,000 keys,
    # half a held expert a token and layer
    full = 2048 * 4096 + 2048 * 2049 // 2 + 10_000
    window = 2048 * 4096 + 4096
    want = (2 * token * 2049 + 2 * 28_311_552 * 0.5 * 4 * 2049
            + 2 * head * 2 + per_key * (full + 4 * window))
    assert tf.serve_flops(CFG, [(4096, 2048)], [10_000], 0.5) == want


def test_the_configuration_keeps_the_catalog():
    published = {"hidden_size": 3072, "intermediate_size": 12288,
                 "moe_intermediate_size": 3072, "num_attention_heads": 48,
                 "num_key_value_heads": 8, "head_dim": 128,
                 "sliding_window": 4096, "num_experts_per_tok": 4,
                 "num_shared_experts": 1, "route_scale": 2.448,
                 "rms_norm_eps": 1e-05, "rope_theta": 10000,
                 "global_attn_every_n_layers": 4, "score_func": "sigmoid",
                 "mup_enabled": True, "max_position_embeddings": 262144}
    assert {k: CFG[k] for k in published} == published
    assert CFG["reduced"] == ["num_hidden_layers", "layer_types",
                              "num_dense_layers", "num_experts",
                              "vocab_size"]
    assert CFG["published"]["num_experts"] == 256
    assert CFG["hidden_size"] == 3072 and CFG["moe_intermediate_size"] == 3072
    assert CFG["layer_types"].count("full_attention") == 1
    assert tw.share(CFG) == (256, 0, 32)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell, cfg = bench_run.load_cell(bench, "trinity-large.longmix", False)
    assert cfg["num_experts"] == 32 and cell["schedule_seed"] == 38


def test_the_limits_fail_both_controls():
    """The program's largest readings pass; each control's smallest
    reading of each number, put in beside the program's largest of the
    others, fails."""
    ok, _ = compare.judge(PROGRAM_LARGEST, CELL["limits"])
    assert ok
    for control in (CONTROL_SMALLEST, WINDOW_CONTROL_SMALLEST):
        for name, low in control.items():
            ok, _ = compare.judge(dict(PROGRAM_LARGEST, **{name: low}),
                                  CELL["limits"])
            assert not ok, name


def test_chunks_are_placed_on_their_steps():
    obs = {"steps": [{} for _ in range(4)],
           "requests": [{"prompt": np.zeros(5000), "first_step": 3},
                        {"prompt": np.zeros(10), "first_step": 1},
                        {"prompt": np.zeros(10), "first_step": None}]}
    kind.place_chunks(obs, 2048)
    assert [s["chunks"] for s in obs["steps"]] == [
        [], [(0, 2048), (0, 10)], [(2048, 2048)], [(4096, 904)]]


def _obs(ops=None):
    return {"traced": (1.0, 2.0), "t_start": 0.0,
            "steps": [{"start": 1.2, "decode_live": [100, 10_000],
                       "chunks": [(4096, 2048)]},
                      {"start": 1.5, "decode_live": [], "chunks": []}],
            "moe": {"assignments": 2049 * 4 * 2, "tokens": 2049,
                    "per_expert": np.ones((4, 32))},
            "trace": {"ops": ops or {}, "programs": {
                "jit_decode": [0.009, 0.010]}}}


def test_the_new_readers():
    obs = _obs(ops={("jit_decode", "%window_decode_attn.3[mosaic]"): 0.002,
                    ("jit_pchunk", "%window_decode_attn.3[mosaic]"): 5.0})
    assert window_decode_attn_ms.read("", obs, CELL, CFG, PEAK) == 1.0
    share, bound = window_decode_attn_roofline.read("", obs, CELL, CFG,
                                                    PEAK)
    assert bound == "bound: bytes"
    assert abs(share - 100 * 4096 * 26_884 / 819e9 / 0.002) < 1e-9
    mfu = trinity_serve_step_mfu.read("", obs, CELL, CFG, PEAK)
    held = 2.0          # 8 assignments a token over 4 expert layers
    want = sum(tf.serve_flops(CFG, s["chunks"], s["decode_live"], held)
               for s in obs["steps"])
    assert abs(mfu - 100 * want / 197e12) < 1e-9
    # nothing to read: nothing, never 0
    bare = _obs()
    assert window_decode_attn_ms.read("", bare, CELL, CFG, PEAK) is None
    assert window_decode_attn_roofline.read("", bare, CELL, CFG,
                                            PEAK) is None
    for s in bare["steps"]:
        del s["chunks"]
    assert trinity_serve_step_mfu.read("", bare, CELL, CFG, PEAK) is None


def test_a_check_whose_prompts_stay_inside_twice_the_window_fails():
    short = {"prompt": np.zeros(2 * CFG["sliding_window"], np.int32),
             "tokens": [1, 2], "max_new_tokens": 2}
    for picked in ([], [short]):
        numbers, where = kind.check({"picked": picked}, CELL, CFG, 1)
        assert where["longest_checked"] == (len(short["prompt"]) if picked
                                            else 0)
        correct, _ = compare.judge(numbers, CELL["limits"])
        assert correct is False
