"""The readers of the banded prefill kernel, ``window_prefill_attn_ms`` and
``window_prefill_attn_roofline``, on a synthetic trace of the longmix
cell's configuration: the kernel's ops in the chunk programs are counted a
launch, ops of other programs and other kernels are not, the needed
operations and bytes are the causal band's, and each reads nothing (never
0) where the trace holds no such kernel or the steps carry no chunks."""

import json
import os

from conftest import ROOT

from benchmark import trinity_flops as tf
from benchmark.layer_metrics import (window_prefill_attn_ms,
                                     window_prefill_attn_roofline)

CFG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "trinity-large-preview.json")))
CELL = json.load(open(os.path.join(
    ROOT, "benchmark", "workloads", "trinity-large.longmix.json")))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
PER_KEY = 4 * 48 * 128          # scores and weighted sums of 48 heads


def _obs(ops=None, chunks=((4096, 2048),)):
    return {"traced": (1.0, 2.0), "t_start": 0.0,
            "steps": [{"start": 1.2, "decode_live": [100],
                       "chunks": list(chunks)},
                      {"start": 1.5, "decode_live": [], "chunks": []},
                      {"start": 2.5, "decode_live": [],      # not traced
                       "chunks": [(0, 2048)]}],
            "trace": {"ops": ops or {}, "programs": {
                "jit_pchunk": [0.05, 0.04], "jit_decode": [0.009]}}}


OPS = {("jit_pchunk", "%window_prefill_attn.3[mosaic]"): 0.010,
       ("jit_pchunk", "%window_prefill_attn.7[mosaic]"): 0.002,
       ("jit_pchunk", "%fusion.756"): 0.5,
       ("jit_decode", "%window_decode_attn.2[mosaic]"): 0.003,
       ("jit_decode", "%window_prefill_attn.1[mosaic]"): 9.0}


def test_a_chunks_operations_and_bytes():
    # 2,048 queries past the window: the full layer sees 4,096 + p + 1
    # keys, each window layer 4,096
    ops, nbytes = window_prefill_attn_roofline.window_prefill_attn_cost(
        CFG, 4096, 2048)
    full = 2048 * 4096 + 2048 * 2049 // 2
    assert ops == PER_KEY * (full + 4 * 2048 * 4096)
    # queries in bf16 and outputs in float32 in five layers; the full
    # layer's 6,144 rows and each window layer's band of 6,143
    queries = 2048 * 48 * 128 * (2 + 4) * 5
    assert nbytes == queries + 4096 * (6144 + 4 * 6143)
    # a chunk at the start of a prompt: every layer's band is the chunk
    ops, nbytes = window_prefill_attn_roofline.window_prefill_attn_cost(
        CFG, 0, 300)
    assert ops == PER_KEY * 5 * tf.keys_full(0, 300)
    assert nbytes == 300 * 48 * 128 * 6 * 5 + 4096 * 5 * 300


def test_the_kernels_time_a_launch_and_its_share():
    obs = _obs(OPS, chunks=((4096, 2048), (0, 300)))
    # 12 ms of the kernel over two chunk launches
    assert abs(window_prefill_attn_ms.read("", obs, CELL, CFG, PEAK)
               - 6.0) < 1e-9
    share, bound = window_prefill_attn_roofline.read("", obs, CELL, CFG,
                                                     PEAK)
    cost = window_prefill_attn_roofline.window_prefill_attn_cost
    big_ops, _ = cost(CFG, 4096, 2048)              # bound by operations
    _, small_bytes = cost(CFG, 0, 300)              # bound by bytes
    least = big_ops / 197e12 + small_bytes / 819e9
    assert abs(share - 100 * least / 0.012) < 1e-9
    assert bound == "bound: bytes/flops"
    assert share < 100


def test_nothing_to_read_reads_nothing():
    bare = _obs({("jit_pchunk", "%fusion.756"): 0.5})
    assert window_prefill_attn_ms.read("", bare, CELL, CFG, PEAK) is None
    assert window_prefill_attn_roofline.read("", bare, CELL, CFG,
                                             PEAK) is None
    unplaced = _obs(OPS)
    for s in unplaced["steps"]:
        del s["chunks"]
    assert window_prefill_attn_roofline.read("", unplaced, CELL, CFG,
                                             PEAK) is None
    untraced = _obs(OPS)
    del untraced["trace"]
    assert window_prefill_attn_ms.read("", untraced, CELL, CFG,
                                       PEAK) is None
    assert window_prefill_attn_roofline.read("", untraced, CELL, CFG,
                                             PEAK) is None
