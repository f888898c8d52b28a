"""The ``jamba`` family's files: the configuration held to the catalog's
keys, parameters and operations against a hand count, the limits of
``correct`` against the controls' readings, the new readers on made-up
observations."""

import json
import os

from conftest import ROOT

from benchmark import compare
from benchmark import jamba_flops as jf
from benchmark import jamba_weights as jw
from benchmark import run as bench_run
from benchmark.layer_metrics import (jamba_serve_step_mfu,
                                     ssm_scan_decode_ms,
                                     ssm_scan_decode_roofline,
                                     ssm_scan_prefill_ms,
                                     ssm_scan_prefill_roofline)

CFG = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                  "jamba2-3b.json")))
CELL = json.load(open(os.path.join(
    ROOT, "benchmark", "workloads", "jamba2-3b.chat-burst.json")))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the readings each limit was set from (PERF.md section 4: the program's
# largest over sixteen runs on a TPU v5e, each control's reading of one seed)
PROGRAM_LARGEST = {"token_gap_mean": 0.0043385, "token_gap_p99": 0.0934067}
CONTROL_SMALLEST = {
    "control_fp8": {"token_gap_mean": 0.5968251, "token_gap_p99": 1.9722252},
    "control_bf16_state": {"token_gap_mean": 0.3151836,
                           "token_gap_p99": 2.1309280}}

# the catalog's row AI21-Jamba2-3B, its ``config``
CATALOG = {"attn_layer_offset": 7, "attn_layer_period": 14,
           "expert_layer_offset": 1, "expert_layer_period": 2,
           "hidden_act": "silu", "hidden_size": 2560,
           "intermediate_size": 8192, "mamba_conv_bias": True,
           "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
           "mamba_expand": 2, "mamba_proj_bias": False,
           "max_position_embeddings": 262144, "model_type": "jamba",
           "num_attention_heads": 20, "num_experts": 1,
           "num_experts_per_tok": 1, "num_hidden_layers": 28,
           "num_key_value_heads": 1, "num_logits_to_keep": 1,
           "rms_norm_eps": 1e-06, "sliding_window": None,
           "tie_word_embeddings": True, "use_mamba_kernels": True,
           "vocab_size": 65536}

MAMBA_MIXER = (2560 * 10240 + 4 * 5120 + 5120 + 5120 * 192 + 160 + 2 * 16
               + 160 * 5120 + 5120 + 16 * 5120 + 5120 + 5120 * 2560)
ATTN_MIXER = 2560 * 2560 * 2 + 2560 * 128 * 2
MLP = 3 * 2560 * 8192


def test_the_configuration_is_the_catalogs_whole():
    assert {k: CFG[k] for k in CATALOG} == CATALOG
    assert CFG["reduced"] == []
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "jamba2-3b")
    assert entry["reduced"] == [] and entry["source"] == CFG["source"]
    cell, cfg = bench_run.load_cell(bench, "jamba2-3b.chat-burst", False)
    assert cell["engine"]["max_slots"] == 128 and cell["arrival_cv"] == 2.0
    assert cfg["num_hidden_layers"] == 28


def test_parameters_are_the_hand_count():
    assert MAMBA_MIXER == 41_241_792 and ATTN_MIXER == 13_762_560
    p = jw.parameters(CFG)
    assert p["mamba"] == MAMBA_MIXER and p["attention"] == ATTN_MIXER
    assert p["common"] == MLP + 2 * 2560
    total = (26 * MAMBA_MIXER + 2 * ATTN_MIXER + 28 * (MLP + 2 * 2560)
             + 65536 * 2560 + 2560)
    assert p["total"] == total
    assert abs(total - 3.029e9) < 1e6


def test_operations_and_bytes_are_the_hand_count():
    products = (26 * (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560)
                + 2 * ATTN_MIXER + 28 * MLP)
    assert jf.matmul_params(CFG) == products
    assert jf.head_params(CFG) == 65536 * 2560
    assert jf.scan_flops(CFG, 3) == 26 * 3 * 7 * 5120 * 16
    # a chunk of 3 tokens reads its last row's logits alone
    assert jf.serve_flops(CFG, 3, 10, 1) == (
        2 * products * 3 + 2 * 65536 * 2560 + 4 * 2 * 2560 * 10
        + 26 * 3 * 7 * 5120 * 16 + 26 * 3 * 2 * 4 * 5120)
    assert jf.serve_tokens_flops(CFG, [4, 6]) == (
        jf.serve_flops(CFG, 2, 10, 2))
    flops, nbytes = jf.scan_cost(CFG, 512, 1)
    assert nbytes == 26 * (512 * (3 * 5120 + 32) * 4 + 2 * 16 * 5120 * 4)
    assert flops == jf.scan_flops(CFG, 512)
    # a decode step of 128 running rows moves 2.18 GB of state (a 1.09 GB
    # array read and written), and a tenth of that in inputs and outputs
    _, step = jf.scan_cost(CFG, 128, 128)
    state = 2 * 26 * 128 * 16 * 5120 * 4
    assert state < step < 1.1 * state


def test_the_limits_fail_both_controls():
    """The program's largest readings pass; each control's smallest
    reading of each number, put in beside the program's largest of the
    others, fails on one of them at least."""
    ok, _ = compare.judge(PROGRAM_LARGEST, CELL["limits"])
    assert ok
    for name, control in CONTROL_SMALLEST.items():
        fails = [k for k, low in control.items()
                 if not compare.judge(dict(PROGRAM_LARGEST, **{k: low}),
                                      CELL["limits"])[0]]
        assert fails, name


def _obs(ops=None):
    return {"traced": (1.0, 2.0), "t_start": 0.0,
            "steps": [{"start": 1.2, "decode_live": [600, 900],
                       "prefill": [512, 512 * 513 // 2],
                       "chunks": [(0, 512)]},
                      {"start": 1.5, "decode_live": [700], "prefill": [0, 0],
                       "chunks": []}],
            "trace": {"ops": ops or {}, "programs": {
                "jit_decode": [0.012, 0.013], "jit_pchunk": [0.03]}}}


def test_the_new_readers():
    obs = _obs(ops={("jit_decode", "%selective_scan.3[mosaic]"): 0.004,
                    ("jit_pchunk", "%selective_scan.7[mosaic]"): 0.006,
                    ("jit_decode", "%window_decode_attn.1[mosaic]"): 1.0})
    assert ssm_scan_decode_ms.read("", obs, CELL, CFG, PEAK) == 2.0
    assert ssm_scan_prefill_ms.read("", obs, CELL, CFG, PEAK) == 6.0
    share, bound = ssm_scan_decode_roofline.read("", obs, CELL, CFG, PEAK)
    want = (jf.scan_cost(CFG, 2, 2)[1] + jf.scan_cost(CFG, 1, 1)[1]) / 819e9
    assert bound == "bound: bytes"
    assert abs(share - 100 * want / 0.004) < 1e-9
    share, bound = ssm_scan_prefill_roofline.read("", obs, CELL, CFG, PEAK)
    want = max(jf.scan_cost(CFG, 512, 1)[0] / 197e12,
               jf.scan_cost(CFG, 512, 1)[1] / 819e9)
    assert abs(share - 100 * want / 0.006) < 1e-9
    mfu = jamba_serve_step_mfu.read("", obs, CELL, CFG, PEAK)
    need = (jf.serve_flops(CFG, 512, 512 * 513 // 2, 1)
            + jf.serve_tokens_flops(CFG, [600, 900])
            + jf.serve_tokens_flops(CFG, [700]))
    assert abs(mfu - 100 * need / 197e12) < 1e-9
    # nothing to read: nothing, never 0
    bare = _obs()
    for reader in (ssm_scan_decode_ms, ssm_scan_prefill_ms,
                   ssm_scan_decode_roofline, ssm_scan_prefill_roofline):
        assert reader.read("", bare, CELL, CFG, PEAK) is None
    del bare["traced"]
    assert jamba_serve_step_mfu.read("", bare, CELL, CFG, PEAK) is None
