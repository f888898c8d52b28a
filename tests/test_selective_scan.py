"""The selective scan (``kernels/selective_scan.py``): the Pallas kernel in
interpret mode and the XLA twin against a sequential float64 loop, on the
serving engine's stacked state array.

Tolerance: both forms compute in float32 in the loop's order, so they read
1e-6 against float64 on outputs of magnitude 1; ``TOL = 1e-5`` leaves ten
times of room.  Rows that do not run, other rows and other layers are
compared bit for bit."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.kernels import selective_scan as ss
from paddle_tpu.profiler import counters

TOL = 1e-5
N = 16


@pytest.fixture(params=["kernel", "twin"])
def form(request):
    """The form under test: the kernel through the interpret hook, or the
    twin."""
    pa._INTERPRET[0] = request.param == "kernel"
    yield request.param
    pa._INTERPRET[0] = False


def _inputs(R, T, E, seed=0):
    r = np.random.default_rng(seed)
    x = r.normal(size=(R, T, E)).astype(np.float32)
    dt = np.exp(r.uniform(np.log(1e-3), np.log(0.1), (R, T, E)))
    B = r.normal(size=(R, T, N)).astype(np.float32)
    C = r.normal(size=(R, T, N)).astype(np.float32)
    A = -np.broadcast_to(np.arange(1, N + 1, dtype=np.float32)[:, None],
                         (N, E)) * r.uniform(0.5, 1.5, (N, E))
    D = r.uniform(0.5, 1.5, E)
    return [np.asarray(a, np.float32) for a in (x, dt, B, C, A, D)]


def _loop(x, dt, B, C, A, D, h):
    """The recurrence position by position, in float64."""
    x, dt, B, C, A, D, h = (np.asarray(a, np.float64)
                            for a in (x, dt, B, C, A, D, h))
    y = np.zeros(x.shape)
    for t in range(x.shape[1]):
        h = (np.exp(dt[:, t, None, :] * A) * h
             + B[:, t, :, None] * (dt[:, t] * x[:, t])[:, None, :])
        y[:, t] = (C[:, t, :, None] * h).sum(1) + D * x[:, t]
    return y, h


def _scan(args, state, layer, rows, reset, run, conv=None, tail=None):
    """The form under test over ``args``; the convolution's rows are zeros
    where the test does not look at them."""
    L, S, _, E = state.shape
    R = len(rows)
    conv = np.zeros((L, S, 3, E), np.float32) if conv is None else conv
    tail = np.zeros((R, 3, E), np.float32) if tail is None else tail
    y, st, cv = ss.scan(*map(jnp.asarray, args), jnp.asarray(state),
                        jnp.asarray(conv), jnp.asarray(tail), layer,
                        jnp.asarray(rows), jnp.asarray(reset),
                        jnp.asarray(run))
    return np.asarray(y), np.asarray(st), np.asarray(cv)


@pytest.mark.parametrize("T,E", [(1, 200), (3, 1024), (17, 1024)])
def test_the_scan_is_the_loop_and_leaves_the_rest_bit_for_bit(form, T, E):
    """Three rows of a 5-slot, 2-layer state, in slots 4, 1, 2: the first
    does not run, the second starts from zero, the third from its state;
    the rows that run take their new convolution tails, the others keep
    theirs.  ``E`` 200 is not whole lanes (one block of all of it)."""
    args = _inputs(3, T, E)
    state = np.random.default_rng(1).normal(size=(2, 5, N, E)).astype(
        np.float32)
    rows = np.array([4, 1, 2])
    reset = np.array([False, True, False])
    run = np.array([False, True, True])
    conv = np.random.default_rng(2).normal(size=(2, 5, 3, E)).astype(
        np.float32)
    tail = np.random.default_rng(3).normal(size=(3, 3, E)).astype(np.float32)
    before = counters.snapshot()
    y, st, cv = _scan(args, state, 1, rows, reset, run, conv, tail)
    took = counters.delta(before)
    assert took.get(f"kernels.selective_scan."
                    f"{'pallas' if form == 'kernel' else 'xla'}") == 1
    h0 = np.where(reset[:, None, None], 0.0, state[1, rows])
    want_y, want_h = _loop(*args, h0)
    np.testing.assert_allclose(y[1:], want_y[1:], atol=TOL, rtol=TOL)
    np.testing.assert_allclose(st[1, rows[1:]], want_h[1:], atol=TOL,
                               rtol=TOL)
    assert np.array_equal(y[0], np.zeros_like(y[0]))
    assert np.array_equal(st[1, 4], state[1, 4])
    assert np.array_equal(st[0], state[0])
    assert np.array_equal(st[1, [0, 3]], state[1, [0, 3]])
    assert np.array_equal(cv[1, rows[1:]], tail[1:])
    assert np.array_equal(cv[1, 4], conv[1, 4])
    assert np.array_equal(cv[0], conv[0])
    assert np.array_equal(cv[1, [0, 3]], conv[1, [0, 3]])


def test_a_state_carried_between_two_calls_is_one_call(form):
    """17 positions as 9 then 8, the second call starting from the state the
    first left, read what one call of 17 reads, and so does the 8 padded
    to 11 with ``dt = 0``; positions of ``dt = 0`` leave the state bit for
    bit."""
    E = 1024
    args = _inputs(1, 17, E, seed=2)
    zero = np.zeros((1, 2, N, E), np.float32)
    rows, yes, no = np.array([1]), np.array([True]), np.array([False])
    y, whole, _ = _scan(args, zero, 0, rows, yes, yes)
    y1, st, _ = _scan([a[:, :9] if a.ndim == 3 else a for a in args], zero,
                      0, rows, yes, yes)
    tail = [a[:, 9:] if a.ndim == 3 else a for a in args]
    padded = [np.pad(a, ((0, 0), (0, 3), (0, 0))) if a.ndim == 3 else a
              for a in tail]
    y2, st2, _ = _scan(padded, st, 0, rows, no, yes)
    np.testing.assert_allclose(np.concatenate([y1, y2[:, :8]], 1), y,
                               atol=TOL, rtol=TOL)
    np.testing.assert_allclose(st2, whole, atol=TOL, rtol=TOL)
    _, st3, _ = _scan(tail, st, 0, rows, no, yes)
    np.testing.assert_allclose(st3, st2, atol=TOL, rtol=TOL)
    still = [a if i != 1 else np.zeros_like(a) for i, a in enumerate(padded)]
    _, st4, _ = _scan(still, st2, 0, rows, no, yes)
    assert np.array_equal(st4, st2)


def test_the_kernel_is_chosen_by_what_the_code_can_observe(monkeypatch):
    assert ss.kernel_mode(5120) == "off"          # the CPU
    monkeypatch.setattr(ss, "on_tpu", lambda: True)
    assert ss.kernel_mode(5120) == "pallas"
    assert ss.kernel_mode(200) == "off"           # not whole lanes
    monkeypatch.setattr(pa, "_INTERPRET", [True])
    monkeypatch.setattr(ss, "_INTERPRET", [True])
    assert ss.kernel_mode(200) == "pallas"
