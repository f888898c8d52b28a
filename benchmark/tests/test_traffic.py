"""The traffic generator, and latency counted from the due time."""

import json
import os
import time

import numpy as np

from benchmark import traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def chat(rate=2.5):
    with open(os.path.join(HERE, "workloads", "gpt3-1.3b.chat.json")) as f:
        cell = json.load(f)
    cell.pop("rehearse")
    cell["rate_per_s"] = rate
    return cell


def test_same_seed_same_requests_and_fixed_count():
    a = traffic.serve_requests(chat(), 2 ** 31 + 5, 40, 50304)
    b = traffic.serve_requests(chat(), 2 ** 31 + 5, 40, 50304)
    c = traffic.serve_requests(chat(), 7, 40, 50304)
    assert len(a) == len(b) == len(c) == 100
    for x, y in zip(a, b):
        assert x["due_s"] == y["due_s"]
        assert x["max_new_tokens"] == y["max_new_tokens"]
        assert np.array_equal(x["prompt"], y["prompt"])
    # another seed: the cell's own schedule, other tokens
    assert [r["due_s"] for r in a] == [r["due_s"] for r in c]
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in c]
    assert not np.array_equal(a[0]["prompt"], c[0]["prompt"])
    # without a schedule of its own, a cell's seed deals the same work
    free = dict(chat(), schedule_seed=None)
    free.pop("schedule_seed")
    d = traffic.serve_requests(free, 7, 40, 50304)
    e = traffic.serve_requests(free, 8, 40, 50304)
    key = lambda rs: sorted(len(r["prompt"]) for r in rs)
    assert key(d) == key(e) == key(a)
    assert [r["due_s"] for r in d] != [r["due_s"] for r in e]
    assert abs(d[-1]["due_s"] - e[-1]["due_s"]) < 1e-9


def test_lengths_inside_their_clips_and_due_inside_window():
    reqs = traffic.serve_requests(chat(), 3, 40, 50304)
    assert all(32 <= len(r["prompt"]) <= 1024 for r in reqs)
    assert all(8 <= r["max_new_tokens"] <= 256 for r in reqs)
    assert min(len(r["prompt"]) for r in reqs) <= 40
    assert max(len(r["prompt"]) for r in reqs) == 1024
    med = np.median([len(r["prompt"]) for r in reqs])
    assert 230 <= med <= 280
    due = [r["due_s"] for r in reqs]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 40
    assert all(r["prompt"].dtype == np.int32 and r["prompt"].max() < 50304
               for r in reqs)


def test_train_batches_differ_and_repeat():
    a = traffic.train_batch(2 ** 31 + 1, 0, 4, 64, 512)
    b = traffic.train_batch(2 ** 31 + 1, 0, 4, 64, 512)
    c = traffic.train_batch(2 ** 31 + 1, 1, 4, 64, 512)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    assert np.array_equal(a[0][:, 1:], a[1][:, :-1])
    assert len({row.tobytes() for row in a[0]}) == 4


class _Handle:
    def __init__(self, n):
        self.tokens, self.n = [], n
        self.is_finished, self.finish_reason = False, None


class _StallingEngine:
    """One token per live request and step; step 3 stalls for 0.3 s."""
    prefill_chunk = 128

    def __init__(self):
        self.live, self.calls = [], 0

    def add_request(self, prompt, max_new_tokens, **kw):
        h = _Handle(max_new_tokens)
        self.live.append(h)
        self.fresh = getattr(self, "fresh", []) + [h]
        return h

    def has_work(self):
        return bool(self.live)

    def step(self):
        self.calls += 1
        if self.calls == 3:
            time.sleep(0.3)
        events = [{"type": "admitted", "request": h} for h in self.fresh]
        self.fresh = []
        for h in list(self.live):
            h.tokens.append(1)
            events.append({"type": "token", "request": h, "token": 1,
                           "index": len(h.tokens) - 1})
            if len(h.tokens) == h.n:
                h.is_finished, h.finish_reason = True, "length"
                self.live.remove(h)
        time.sleep(0.01)
        return events


def test_latency_counts_from_due_time_when_a_step_stalls():
    from benchmark.kinds import serve_open_loop as sol
    prompt = np.ones(8, np.int32)
    reqs = [{"due_s": 0.0, "prompt": prompt, "max_new_tokens": 60},
            {"due_s": 0.1, "prompt": prompt, "max_new_tokens": 2}]
    loop = sol._Loop(_StallingEngine(), reqs, with_stats=False)
    loop.run(2.0, drain=True)
    loop.close()
    assert reqs[0]["finished"] and reqs[0]["tokens"] == [1] * 60
    late = reqs[1]["submit_s"] - reqs[1]["due_s"]
    ttft = reqs[1]["token_s"][0] - reqs[1]["due_s"]
    # due during the stall: submitted late, and the wait is in its TTFT
    assert late > 0.15
    assert ttft >= late and ttft > 0.15
    assert reqs[1]["admitted_s"] >= reqs[1]["submit_s"]
    assert len(reqs[0]["token_s"]) == 60
