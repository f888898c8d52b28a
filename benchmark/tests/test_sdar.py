"""The ``sdar_moe`` family's files: counts pinned to the issue's table, the
weights dealt alike to program and reference, the configuration held to
the catalog's keys, the placing of passes on steps, the reference's layout
of a request's passes, the new readers on made-up observations."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ROOT

from benchmark import compare
from benchmark import run as bench_run
from benchmark import sdar_flops as sf
from benchmark import sdar_weights as sw
from benchmark.kinds import serve_open_loop_sdar as kind
from benchmark.layer_metrics import (block_decode_attn_ms,
                                     block_decode_attn_roofline,
                                     block_decode_program_p50_ms,
                                     diffusion_tokens_per_pass,
                                     sdar_decode_roofline,
                                     sdar_serve_step_mfu)
from benchmark.reference import sdar as ref

CFG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "sdar-30b-a3b.json")))
TINY = dict(CFG, **CFG["rehearse"])
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the two readings of each limit (PERF.md section 4: my chip runs, PR 34)
PROGRAM_LARGEST = {"token_gap_mean": 0.0016255, "token_gap_p99": 0.0391011,
                   "reveal_gap_mean": 0.0023257,
                   "route_disagreement": 0.0087200}
CONTROL_SMALLEST = {"token_gap_mean": 0.0086999, "token_gap_p99": 0.1448767,
                    "reveal_gap_mean": 0.0089096,
                    "route_disagreement": 0.0716766}


def test_counts_are_the_issues_table():
    p = sf.layer_params(CFG)
    assert p["attention"] == (2048 * 4096 + 2 * 2048 * 512
                              + 4096 * 2048) == 18_874_368
    assert p["router"] == 262_144 and p["expert"] == 4_718_592
    assert 128 * p["expert"] == 603_979_776 and p["gains"] == 4_352
    total = sf.parameters(CFG)
    assert total == sw.parameters(CFG) == {
        "layer": 623_120_640, "top": 622_331_904, "total": 4_361_055_744}
    assert 6 * total["layer"] == 3_738_723_840
    assert round(2 * total["total"] / 1e9, 2) == 8.72
    assert sf.kv_bytes_per_token_layer(CFG) == 2_048
    # the pool of 163,840 tokens in rows of 1,024 bfloat16 values
    assert 6 * 10241 * 16 * 1024 * 2 == 163_840 * 12_288 + 6 * 16 * 2048
    assert 163_840 * 12_288 == 2_013_265_920
    assert sf.block_end(CFG, 0) == 4 == sf.block_end(CFG, 3)
    assert sf.block_end(CFG, 4) == 8
    every = 6 * (18_874_368 + 262_144 + 8 * 4_718_592)
    assert sf.token_matmul_params(CFG, False) == every
    assert sf.token_matmul_params(CFG, True) == every + 151_936 * 2048
    assert sf.serve_flops(CFG, (8, 100), [12, 16]) == (
        2 * every * 8 + 2 * (every + 151_936 * 2048) * 2
        + 4 * 6 * 32 * 128 * (100 + 28))
    # 16 running rows touch 98 % of a layer's experts, 4 rows 64 %
    even = [[8 / 128] * 128] * 6
    assert round(sf.experts_touched(even, 64) / 768, 2) == 0.98
    assert round(sf.experts_touched(even, 16) / 768, 2) == 0.64
    dense = 2 * (6 * (18_874_368 + 262_144 + 4_352) + 151_936 * 2048 + 2048)
    assert sf.dense_bytes(CFG) == dense
    assert abs(sf.decode_step_bytes(CFG, [100, 20], even)
               - dense - 2 * 4_718_592 * sf.experts_touched(even, 8)
               - 6 * 2048 * 120) < 1
    assert sf.decode_step_flops(CFG, [100]) == sf.serve_flops(
        CFG, (0, 0), [104] * 4)
    ops, nbytes = sf.block_decode_attn_cost(CFG, [100, 20])
    assert ops == 4 * 6 * 32 * 128 * 4 * (104 + 24)
    assert nbytes == 6 * (2048 * 120 + 2 * 4 * (2048 + 4096 * 6))


def test_the_file_holds_the_catalog_keys_and_the_cut():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    rows = [json.loads(l) for l in open(path)] if os.path.exists(path) else []
    published = next((r["config"] for r in rows
                      if r["name"] == "SDAR-30B-A3B-Chat"), None)
    assert CFG["reduced"] == ["num_hidden_layers"]
    assert CFG["published"] == {"num_hidden_layers": 48}
    assert CFG["num_hidden_layers"] == 6
    assert "8 pipeline stages" in CFG["stands_for"]
    for key in ("block_length", "denoising_steps", "mask_token_id",
                "remasking", "qk_norm", "rotary_layout", "weights"):
        assert key in CFG["assumed"], key
    assert (CFG["block_length"], CFG["denoising_steps"],
            CFG["mask_token_id"]) == (4, 4, 151669)
    if published is not None:
        for key, value in published.items():
            if key != "num_hidden_layers":
                assert CFG[key] == value, key
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "sdar-30b-a3b")
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"]
    cell = next(w for w in bench["workloads"]
                if w["name"] == "sdar-30b-a3b.chat")
    assert cell["traffic"] == "chat-blocks" and cell["chips"] == 1
    mine = {m["name"] for g in ("end_to_end", "per_layer") for m in bench[g]
            if "sdar-30b-a3b.chat" in m.get("workloads", [])}
    assert {"ttft_p90_ms", "serve_tokens_per_s", "sdar_serve_step_mfu",
            "diffusion_tokens_per_pass", "block_decode_program_p50_ms",
            "sdar_decode_roofline", "moe_load_max_over_mean",
            "moe_prefill_expert_ms"} <= mine
    assert "itl_p90_ms" not in mine


def test_program_and_reference_hold_the_same_weights():
    seed = 2 ** 31 + 5
    prog = dict(sw.program(TINY, seed, "float32"))
    from paddle_tpu.models import sdar
    shapes = sdar.param_shapes(sdar.SdarConfig.from_hf(
        TINY, mask_token_id=TINY["mask_token_id"]))
    assert {n: tuple(x.shape) for n, x in prog.items()} == {
        n: s for n, (s, _, _) in shapes.items()}
    l0, l1 = (sw.layer(TINY, seed, l, "float32") for l in (0, 1))
    np.testing.assert_array_equal(prog["kv_w"][1], jnp.concatenate(
        [l1["w_k"], l1["w_v"]], -1))
    np.testing.assert_array_equal(prog["q_w"][0], l0["w_q"])
    np.testing.assert_array_equal(prog["router_w"][1], l1["w_router"])
    ex = sw.expert(TINY, seed, 1, 5, "float32")
    np.testing.assert_array_equal(prog["expert_gu_w"][1, 5], jnp.concatenate(
        [ex["ex_gate"], ex["ex_up"]], -1))
    np.testing.assert_array_equal(prog["expert_down_w"][1, 5], ex["ex_down"])
    assert not np.array_equal(prog["expert_down_w"][0, 5],
                              prog["expert_down_w"][1, 5])
    np.testing.assert_array_equal(prog["lm_head"],
                                  sw.top(TINY, seed, "float32")["head"])


def _blocks():
    """A prompt of 10 tokens (two whole blocks and a remainder of 2), 5 new
    tokens: a first block of 2 masked positions, then a whole one of which
    ``max_new_tokens`` cuts the last position off."""
    return [{"step": 4, "start": 8, "passes": 3, "tokens": [8, 9, 50, 51],
             "reveal_steps": [-1, -1, 1, 0]},
            {"step": 9, "start": 12, "passes": 5, "tokens": [52, 53, 54, 55],
             "reveal_steps": [2, 0, 3, 1]}]


def test_passes_and_chunks_are_placed_on_the_steps_that_ran_them():
    reqs = [{"prompt": np.arange(10), "blocks": _blocks()}]
    steps = [{"prefill": [0, 0]} for _ in range(11)]
    kind.place(reqs, steps, 4, 4)
    assert [s["passes"] for s in steps] == (
        [[]] * 2 + [[8]] * 3 + [[12]] * 5 + [[]])
    # chunks of 4: the second on the step of the first pass, the first a
    # step before; a token sees up to its block's end
    assert [s["prefill"] for s in steps[:3]] == [[0, 0], [4, 16], [4, 32]]
    assert kind.chunk_spans(10, 4, 4) == [(0, 4), (4, 8)]
    assert kind.chunk_spans(3, 4, 4) == []
    assert kind.chunk_spans(21, 8, 4) == [(0, 8), (8, 16), (16, 20)]


def test_the_references_layout_of_a_requests_passes():
    ids, pos, mask, state_row = kind.layout(np.arange(10), _blocks(), 4, 99)
    M = 16
    assert len(ids) == kind._MIN_WIDTH == len(pos) == len(mask)
    assert ids[:M].tolist() == list(range(8)) + [8, 9, 50, 51, 52, 53, 54, 55]
    assert state_row == {(0, 0): 16, (0, 1): 20, (1, 0): 24, (1, 1): 28,
                         (1, 2): 32, (1, 3): 36}
    # a pass's state: what was revealed before it, masks elsewhere
    assert ids[16:24].tolist() == [8, 9, 99, 99, 8, 9, 99, 51]
    assert ids[24:40].tolist() == [99] * 4 + [99, 53, 99, 99] + [
        99, 53, 99, 55] + [52, 53, 99, 55]
    assert pos[16:24].tolist() == [8, 9, 10, 11] * 2
    assert pos[36:40].tolist() == [12, 13, 14, 15]
    assert np.array_equal(mask[:M, :M], ref.block_causal(M, 4))
    # state rows see the earlier blocks' final tokens and themselves
    assert mask[20, :8].all() and not mask[20, 8:20].any()
    assert mask[20, 20:24].all() and not mask[20, 24:].any()
    assert mask[36, :12].all() and not mask[36, 12:36].any()
    assert mask[100, 100] and mask[100].sum() == 1      # padding


def test_gaps_are_read_at_the_pass_that_revealed():
    blocks = _blocks()
    r = {"prompt": np.arange(10), "max_new_tokens": 5}
    _, _, _, state_row = kind.layout(r["prompt"], blocks, 4, 99)
    # every row: best logit 5, log-sum 6, the judged token's logit 5 - row/100
    stats = {row + p: np.array([5.0, 6.0 + 0.1 * p, 5.0 - (row + p) / 100])
             for row in state_row.values() for p in range(4)}
    token, reveal = kind.gaps_of(r, blocks, state_row, stats, 4)
    # 2 + 4 positions revealed; the last lies past max_new_tokens
    assert len(reveal) == 6 and len(token) == 5
    # block 0 pass 0 revealed position 3 where position 2 was more
    # confident (its log-sum is smaller): a gap of 0.1
    assert abs(reveal[0] - 0.1) < 1e-12 and reveal[1] == 0
    assert abs(token[0] - (16 + 3) / 100) < 1e-12


def test_the_probe_places_each_steps_rows():
    picked = [{"prompt": np.arange(10)}]
    probe = {"blocks": [_blocks()],
             "steps": [{"routed": n} for n in
                       [0, 4, 8, 4, 4, 4, 4, 4, 4, 4]]}
    spans, chunked = kind.placed(probe, picked, 4, 4)
    assert spans[1] == [(0, "main", 0, 4)] and chunked[:3] == [
        False, True, True]
    assert spans[2] == [(0, "state", 0, 0), (0, "main", 4, 8)]
    assert spans[4] == [(0, "main", 8, 12)]
    assert spans[5] == [(0, "state", 1, 0)] and spans[9] == [
        (0, "main", 12, 16)]
    probe["steps"][3]["routed"] = 8
    with pytest.raises(RuntimeError):
        kind.placed(probe, picked, 4, 4)


@pytest.mark.parametrize("number", kind.COMPARED)
def test_the_controls_readings_are_not_correct(number):
    """The cell's limits against the two readings each was set from
    (``PERF.md``): the program's largest passes, and the 8-bit control's
    smallest of any ONE number fails the run."""
    limits = bench_run._load(os.path.join(
        bench_run.HERE, "workloads", "sdar-30b-a3b.chat.json"))["limits"]
    assert set(limits) == set(kind.COMPARED)
    ok, _ = compare.judge(PROGRAM_LARGEST, limits)
    assert ok
    ok, compared = compare.judge(
        dict(PROGRAM_LARGEST, **{number: CONTROL_SMALLEST[number]}), limits)
    assert not ok and compared[number]["value"] > limits[number]


def _obs():
    steps = [{"start": 1.0, "prefill": [512, 512 * 600],
              "decode_live": [516] * 4, "passes": [512, 100, 2000]},
             {"start": 1.5, "prefill": [0, 0], "decode_live": [],
              "passes": [512, 100]},
             {"start": 9.0, "prefill": [0, 0], "decode_live": [8],
              "passes": [4]}]
    ops = {("jit_decode", "%block_decode_attn.1[mosaic]"): 0.004,
           ("jit_decode", "%ragged-dot-none.3[mosaic]"): 0.006,
           ("jit_pchunk", "%ragged-dot-none.3[mosaic]"): 0.5,
           ("jit_decode", "%fusion.1"): 0.01}
    per = np.full((6, 128), 10)
    per[0, 0] = 30
    return {"traced": (0.0, 2.0), "steps": steps,
            "trace": {"programs": {"jit_decode(7)": [0.02, 0.03],
                                   "jit_pchunk": [0.3]}, "ops": ops},
            "moe": {"assignments": int(per.sum()), "tokens": 160,
                    "per_expert": per},
            "counters": {"serving.diffusion.row_passes": 50,
                         "serving.decode_tokens": 36}}


def test_new_readers_on_a_made_up_window():
    obs = _obs()
    need = (sf.serve_flops(CFG, [512, 512 * 600], [516] * 4)
            + sf.serve_flops(CFG, [0, 0], []))
    assert abs(sdar_serve_step_mfu.read("", obs, {}, CFG, PEAK)
               - 100 * need / (2.0 * 197e12)) < 1e-9
    assert diffusion_tokens_per_pass.read("", obs, {}, CFG, PEAK) == 0.72
    assert block_decode_program_p50_ms.read("", obs, {}, CFG, PEAK) == 25.0
    assert block_decode_attn_ms.read("", obs, {}, CFG, PEAK) == 2.0
    load = sdar_decode_roofline.load(obs)
    assert load[0][0] == 30 / 160 and load[1][1] == 10 / 160
    share, note = sdar_decode_roofline.read("", obs, {}, CFG, PEAK)
    least = sum(max(sf.decode_step_flops(CFG, s) / 197e12,
                    sf.decode_step_bytes(CFG, s, load) / 819e9)
                for s in ([512, 100, 2000], [512, 100]))
    assert abs(share - 100 * least / 0.05) < 1e-9 and note == "bound: bytes"
    share, note = block_decode_attn_roofline.read("", obs, {}, CFG, PEAK)
    least = sum(max(o / 197e12, b / 819e9) for o, b in (
        sf.block_decode_attn_cost(CFG, s)
        for s in ([512, 100, 2000], [512, 100])))
    assert abs(share - 100 * least / 0.004) < 1e-9 and note == "bound: bytes"


def test_new_readers_give_nothing_where_there_is_nothing():
    """A program without the counts or the kernel (any parent commit, any
    other family): ``None``, never 0 and never an error."""
    bare = {"traced": (0.0, 2.0),
            "steps": [{"start": 1.0, "prefill": [8, 36],
                       "decode_live": [9]}],
            "trace": {"programs": {"jit_decode(7)": [0.02]},
                      "ops": {("jit_decode", "%fusion.1"): 0.01}}}
    for reader in (diffusion_tokens_per_pass, block_decode_program_p50_ms,
                   sdar_decode_roofline, block_decode_attn_ms,
                   block_decode_attn_roofline):
        assert reader.read("", bare, {}, CFG, PEAK) is None, reader
        assert reader.read("", {"steps": []}, {}, CFG, PEAK) is None
        assert reader.read("", dict(bare, moe=None, counters={}), {}, CFG,
                           PEAK) is None
    assert sdar_serve_step_mfu.read("", {"steps": []}, {}, CFG,
                                    PEAK) is None
