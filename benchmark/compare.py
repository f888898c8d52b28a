"""The comparisons that decide ``correct``, and how a run prints them.

Each number compared has a limit of its own, kept in the cell's file under
``limits`` with the readings it was set from in ``PERF.md``.  A run is
correct when every number is at or under its limit (and is a number at
all).
"""

from __future__ import annotations

import json
import math
import sys

import numpy as np


def percentile(values, q):
    """Nearest-rank percentile (``q`` in 0..100) of a non-empty list."""
    xs = sorted(values)
    return xs[min(len(xs) - 1, max(0, math.ceil(q / 100.0 * len(xs)) - 1))]


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------
def worst_leaf_gap(prog, ref, skip=()):
    """The widest gap between the program's and the reference's norm of one
    leaf, against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero).  ``prog`` and
    ``ref`` are ``{leaf: norm or per-layer vector of norms}``; leaves are
    named ``wq[3]``.  Returns ``(gap, leaf)``."""
    flat_p, flat_r = flatten(prog), flatten(ref)
    if set(flat_p) != set(flat_r):
        raise ValueError("the two sides do not have the same leaves: "
                         f"{sorted(set(flat_p) ^ set(flat_r))[:6]}")
    median = float(np.median(list(flat_r.values())))
    worst, at = 0.0, None
    for leaf, r in flat_r.items():
        if leaf in skip:
            continue
        gap = abs(flat_p[leaf] - r) / max(r, median, 1e-30)
        if not gap <= worst:        # also catches nan
            worst, at = gap, leaf
    return float(worst), at


def flatten(norms):
    out = {}
    for name, x in norms.items():
        x = np.asarray(x, np.float64)
        if x.ndim == 0:
            out[name] = float(x)
        else:
            out.update({f"{name}[{i}]": float(v) for i, v in enumerate(x)})
    return out


def dead_leaves(ref_grad_norms):
    """Leaves whose gradient is nought to rounding in the reference (a
    key's bias under softmax): under a thousandth of the median leaf's.
    Adam moves them by round-off alone, so their change is not compared."""
    flat = flatten(ref_grad_norms)
    median = float(np.median(list(flat.values())))
    return {leaf for leaf, v in flat.items() if v < 1e-3 * median}


def train_numbers(prog, ref):
    """The numbers of a training cell, ``(compared, beside)``.  Each side
    is a dictionary with ``losses`` (one per step), ``grad_norms`` (step
    1's, per leaf) and ``change_norms`` (per leaf, after the steps).

    The widest loss gap is worked out and shown ``beside`` but NOT
    compared: PR 24 found no upper reading for it (the program reads
    0.2e-4 to 2.0e-4 over 15 seeds, the fp8 control the same 0.2e-4 to
    0.9e-4, and the half-batch fault 5e-4, under ten times the lower
    reading), so a limit on it could only fail sound runs."""
    n = min(len(prog["losses"]), len(ref["losses"]))
    loss_gap = max(abs(p - r) / abs(r) for p, r in
                   zip(prog["losses"][:n], ref["losses"][:n]))
    grad_gap, grad_at = worst_leaf_gap(prog["grad_norms"],
                                       ref["grad_norms"])
    change_gap, change_at = worst_leaf_gap(
        prog["change_norms"], ref["change_norms"],
        skip=dead_leaves(ref["grad_norms"]))
    return ({"grad_gap": grad_gap, "change_gap": change_gap},
            {"grad_gap_at": grad_at, "change_gap_at": change_at,
             "loss_gap_not_compared": float(loss_gap)})


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def token_gaps(ref_logits, tokens):
    """For each served token, how far its reference logit lies below the
    reference's best at that position (0 where the served token is the
    reference's own choice).  ``ref_logits [n, V]``, ``tokens [n]``."""
    lg = np.asarray(ref_logits, np.float64)
    tok = np.asarray(tokens, np.int64)
    if np.any(tok < 0) or np.any(tok >= lg.shape[1]):
        return np.full(len(tok), np.inf)
    return lg.max(-1) - lg[np.arange(len(tok)), tok]


# ---------------------------------------------------------------------------
def judge(numbers, limits):
    """``(correct, compared)``: ``compared`` is ``{name: {"value",
    "limit"}}`` for the result line.  A number with no limit in the cell's
    file is an error: a limit is set from readings, never left open."""
    compared, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        if limit is None:
            raise ValueError(f"the cell's file sets no limit for {name!r}")
        good = isinstance(value, (int, float)) and math.isfinite(
            value) and value <= limit
        ok = ok and good
        compared[name] = {"value": value if math.isfinite(value) else
                          repr(value), "limit": limit}
    return ok, compared


def print_compared(compared, extra=None):
    """Each number beside its limit, as the last lines on standard error."""
    for name, c in compared.items():
        print(f"compared {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    if extra:
        print("compared at " + json.dumps(extra), file=sys.stderr)
    sys.stderr.flush()
