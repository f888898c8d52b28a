"""`correct` has to come out false when the timed path is broken, and when
the reference is computed in the precision below the configuration's.

Each test skips the harness's look for a chip (``--rehearse``: the tiny
sizes on the CPU) and drives the rest of a run through ``run.main`` with
one fault planted underneath the timed path."""

import json

import pytest

from benchmark import compare, run as bench_run


def _run(capsys, workload, seed=3, seconds=1.0):
    rc = bench_run.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", "0",
                         "--rehearse"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


PRETRAIN, CHAT = "gpt3-760m.pretrain", "gpt3-1.3b.chat"


def test_sound_runs_are_correct(capsys):
    assert _run(capsys, PRETRAIN)["correct"] is True
    line = _run(capsys, CHAT, seconds=4.0)
    assert line["correct"] is True and line["failed"] == 0


def test_step_that_returns_its_state_unchanged(capsys, monkeypatch):
    from paddle_tpu.jit import CompiledTrainStep
    real = CompiledTrainStep._dispatch_single

    def frozen(self, args_data, lr_val):
        state = self._state
        keep = __import__("jax").tree_util.tree_map(
            lambda x: x.copy() if hasattr(x, "copy") else x, state)
        loss = real(self, args_data, lr_val)
        if self.optimizer._step_count > 1:    # after the state exists
            self._state = keep
        return loss
    monkeypatch.setattr(CompiledTrainStep, "_dispatch_single", frozen)
    line = _run(capsys, PRETRAIN)
    assert line["correct"] is False
    assert line["compared"]["change_gap"]["value"] > \
        line["compared"]["change_gap"]["limit"]


def test_half_of_the_batch_left_out(capsys, monkeypatch):
    from benchmark.kinds import train
    real_build = train.build

    def build(cell, cfg, seed):
        step, feed, names = real_build(cell, cfg, seed)
        call = type(step).__call__

        class Half(type(step)):
            def __call__(self, x, y):
                n = x.shape[0] // 2
                return call(self, x[:n], y[:n])
        step.__class__ = Half
        return step, feed, names
    monkeypatch.setattr(train, "build", build)
    line = _run(capsys, PRETRAIN)
    assert line["correct"] is False


def test_token_altered_where_it_is_produced(capsys, monkeypatch):
    from paddle_tpu.serving.engine import LLMEngine
    real = LLMEngine._emit

    def emit(self, req, tok, events):
        if len(req.tokens) == 2:
            tok = (int(tok) + 1) % self.config.vocab_size
        return real(self, req, tok, events)
    monkeypatch.setattr(LLMEngine, "_emit", emit)
    line = _run(capsys, CHAT, seconds=4.0)
    assert line["correct"] is False
    assert line["compared"]["token_gap"]["value"] > \
        line["compared"]["token_gap"]["limit"]


def test_control_in_lower_precision_is_not_correct():
    """The reference in 8-bit floats, put in the program's place, has to
    fail at least one of the cell's numbers at its limits."""
    from benchmark.kinds import train
    bench = bench_run._load(bench_run.os.path.join(bench_run.ROOT,
                                                   "BENCHMARK.json"))
    cell, cfg = bench_run.load_cell(bench, PRETRAIN, rehearse=True)
    ref = train.reference_readings(cell, cfg, 3)
    low = train.reference_readings(cell, cfg, 3, prec="fp8")
    numbers, _ = compare.train_numbers(low, ref)
    correct, compared = compare.judge(numbers, cell["limits"])
    assert correct is False, compared
    same, _ = compare.judge(compare.train_numbers(ref, ref)[0],
                            cell["limits"])
    assert same is True


def test_serving_control_in_lower_precision_is_not_correct():
    """At each position of the same prompts and tokens, the token that the
    8-bit-float reference puts first lies further below the reference's
    best than the limit allows (at a vocabulary where logits crowd: the
    rehearsal's own 512 tokens have too few near ties)."""
    import numpy as np
    from benchmark.kinds import serve_open_loop as sol
    bench = bench_run._load(bench_run.os.path.join(bench_run.ROOT,
                                                   "BENCHMARK.json"))
    cell, cfg = bench_run.load_cell(bench, CHAT, rehearse=True)
    # 0.08 * sqrt(128) gives the logits the spread of the real model's
    cfg.update(vocab_size=8192, d_model=128, n_heads=4, d_head=32,
               d_ff=512, n_layers=4, initializer_range=0.08)
    rng = np.random.default_rng(0)

    picked = [{"prompt": rng.integers(0, cfg["vocab_size"], 48,
                                      dtype=np.int32),
               "tokens": rng.integers(0, cfg["vocab_size"], 16).tolist(),
               "max_new_tokens": 16} for _ in range(6)]
    _, low = sol.reference_gaps(picked, cell, cfg, 3, control=True)
    assert low.max() > cell["limits"]["token_gap"]
