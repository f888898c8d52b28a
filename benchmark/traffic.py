"""The one traffic generator: a cell's file of parameters -> its inputs.

Everything a run feeds the program comes from here and from ``--seed``.
A later cell is a new file of parameters, not new code.

Every seed offers the SAME work: lengths and the gaps between arrivals are
the quantiles of their distributions (so the sum of tokens and the span of
arrivals never vary).  They are dealt out by the cell's ``schedule_seed``
where its file gives one, so that the schedule is part of the cell and the
run's ``--seed`` draws the token ids (and the weights) alone; without it
the run's seed deals them too.  PR 24 measured why: at 0.8 of the knee two
orders of the same requests read a p90 time to first token 25 % apart,
which no bound could hold.  A run-to-run difference is then the system's,
not the sample's; another schedule is another cell, as a data file.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _rng(seed, stream):
    return np.random.default_rng([int(seed), int(stream)])


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------
def train_batch(seed, step, batch, seq, vocab):
    """Step ``step``'s ``(ids, labels)``, ``int32 [batch, seq]``: uniform
    token ids, the labels the same rows shifted by one.  Every row of every
    step differs."""
    rows = _rng(seed, step).integers(0, vocab, (batch, seq + 1),
                                     dtype=np.int32)
    return rows[:, :-1], rows[:, 1:]


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def _lognormal_quantiles(n, median, sigma, lo, hi):
    """``n`` whole numbers at the mid-quantiles of a log-normal, clipped."""
    nd = NormalDist()
    q = [(i + 0.5) / n for i in range(n)]
    x = [median * math.exp(sigma * nd.inv_cdf(p)) for p in q]
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def lengths(spec, n):
    """``n`` lengths for a ``{"dist": ..}`` entry of a cell's file."""
    if spec["dist"] == "lognormal":
        return _lognormal_quantiles(n, spec["median"], spec["sigma"],
                                    spec["min"], spec["max"])
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def arrival_gaps(n, seconds, cv=1.0):
    """``n`` gaps between arrivals whose sum is ``seconds * n / (n + 1)``:
    the mid-quantiles of a gamma distribution's gaps with coefficient of
    variation ``cv`` (1.0: exponential gaps, a Poisson process conditioned
    on its count), found by sorting a fixed large sample."""
    if cv <= 0:
        gaps = np.ones(n)
    else:
        shape = 1.0 / (cv * cv)
        sample = np.sort(np.random.default_rng(0).gamma(shape, 1.0, 64 * n))
        gaps = sample[32::64][:n]
    return gaps * (seconds * n / (n + 1)) / gaps.sum()


def serve_requests(cell, seed, seconds, vocab):
    """The requests of one run, sorted by the second they are due:
    ``[{"due_s", "prompt" (int32 array), "max_new_tokens"}]``.

    ``cell["rate_per_s"]`` fixes their number, ``round(rate * seconds)``.
    ``cell["shared_prefix_tokens"]`` (0 if absent) leading tokens are the
    same in every prompt."""
    n = max(1, round(cell["rate_per_s"] * seconds))
    deal = _rng(cell.get("schedule_seed", seed), 0)
    prompts = deal.permutation(lengths(cell["prompt"], n))
    outputs = deal.permutation(lengths(cell["output"], n))
    gaps = deal.permutation(arrival_gaps(
        n, seconds, cell.get("arrival_cv", 1.0)))
    due = np.cumsum(gaps)
    rng = _rng(seed, 3)
    shared = rng.integers(0, vocab, int(cell.get("shared_prefix_tokens", 0)),
                          dtype=np.int32)
    reqs = []
    for i in range(n):
        own = rng.integers(0, vocab, max(int(prompts[i]) - len(shared), 1),
                           dtype=np.int32)
        reqs.append({"due_s": float(due[i]),
                     "prompt": np.concatenate([shared, own]),
                     "max_new_tokens": int(outputs[i])})
    return reqs


def warmup_prompts(cell, seed, vocab, buckets):
    """One prompt per prefill bucket, of other tokens than any request's
    (so the prefix cache learns nothing the window could hit)."""
    rng = _rng(seed, 1)
    return [rng.integers(0, vocab, int(b), dtype=np.int32) for b in buckets]
