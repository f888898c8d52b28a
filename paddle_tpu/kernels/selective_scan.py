"""The selective scan of a Mamba-1 layer (Gu and Dao, arXiv:2312.00752) as
the serving programs need it: a prefill chunk of one row, and a decode
step of every slot row, by one kernel.

Per channel ``e`` of ``E`` and state index ``n`` of ``N``, with the
convolved input ``x_t[e]``, an input-dependent step ``dt_t[e] > 0``, the
input and output projections ``B_t[n]``, ``C_t[n]``, a fixed ``A[n, e] <
0`` and a skip ``D[e]``, the layer keeps a diagonal state ``h [N, E]`` per
request:

    h_t[n, e] = exp(dt_t[e] A[n, e]) h_{t-1}[n, e] + dt_t[e] x_t[e] B_t[n]
    y_t[e]    = sum_n C_t[n] h_t[n, e] + D[e] x_t[e]

Its decay differs for every channel and state index and follows the
input, so no chunked form through matrix products exists: the scan walks
time.  A position with ``dt = 0`` leaves the state bit for bit (``h * 1 +
0``), which is how the caller marks the padded tail of a bucket.

The state lives in the serving engine's per-slot array ``state [L, S, N,
E]`` float32 (channels on lanes: ``[E, N]`` would pad ``N = 16`` to 128
lanes, eight times the bytes); both forms read and write rows ``rows`` of
layer ``layer`` of it and hand the array back.  Beside it they write, for
the rows that run, the last inputs of the layer's convolution (``conv
[L, S, a, b]``, the tail the caller computed, kept per slot in the shape
:func:`tail_shape` gives): the kernel in place, so that no program
updates either array but the scan itself (a per-layer update of the
whole stacked tail by XLA read and wrote all of it, 0.45 ms a layer of a
decode step on a TPU v5e).

* :func:`selective_scan` — the Pallas kernel (``name="selective_scan"``):
  grid ``(row, block of channels)``; the layer, the rows and two flags a
  row (start from zero; advance at all, else keep the row bit for bit) as
  scalar prefetch; the row's state block, and its convolution tail, read
  in and written back in place through ``input_output_aliases``; inside
  a block, ``_SUB`` channels at a time hold their ``[N, _SUB]`` state in
  registers while the kernel walks time, eight positions a load.  ``exp(dt
  A)`` and ``dt x B`` are formed a step at a time and never reach HBM.
* :func:`selective_scan_xla` — the twin (the CPU path and the tests'
  reference): a ``lax.scan`` over time of the same arithmetic.
* :func:`scan` — the one entry point of the models: the kernel where
  :func:`kernel_mode` says it can run, else the twin, counted where the
  programs are traced (``kernels.selective_scan.pallas`` / ``.xla``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..device import on_tpu
from ..profiler import counters
from ._shapes import LANE, check_equal
from .paged_attention import _INTERPRET

#: channels whose state a step of the time loop holds in registers
#: (``[16, 512]`` float32: 8 vregs)
_SUB = 512

#: positions loaded at once (one sublane tile of float32)
_GROUP = 8

#: VMEM a grid step's double-buffered blocks may take
_VMEM_BUDGET = 8 * 2 ** 20


def kernel_mode(channels):
    """``"pallas"`` where the kernel can run (the tests' interpret hook, or
    a TPU with channels of whole 128-lane tiles), else ``"off"``, the XLA
    twin."""
    if _INTERPRET[0] or (on_tpu() and channels % LANE == 0):
        return "pallas"
    return "off"


def tail_shape(taps, channels):
    """The per-slot shape the last ``taps`` inputs of ``channels`` channels
    are kept in: eight rows where they divide, so that a slot's block is
    whole sublanes and the kernel writes it in place (a block of 3 rows
    makes the compiler re-lay out the whole stacked array around the
    call); else ``(taps, channels)``."""
    n = taps * channels
    return (8, n // 8) if n % 8 == 0 else (taps, channels)


def scan(x, dt, B, C, A, D, state, conv, tail, layer, rows, reset, run):
    """The form :func:`kernel_mode` chooses; arguments and result as
    :func:`selective_scan_xla`."""
    if kernel_mode(x.shape[-1]) == "pallas":
        counters.inc("kernels.selective_scan.pallas")
        return selective_scan(x, dt, B, C, A, D, state, conv, tail, layer,
                              rows, reset, run)
    counters.inc("kernels.selective_scan.xla")
    return selective_scan_xla(x, dt, B, C, A, D, state, conv, tail, layer,
                              rows, reset, run)


def selective_scan_xla(x, dt, B, C, A, D, state, conv, tail, layer, rows,
                       reset, run):
    """The twin.  ``x, dt [R, T, E]`` and ``B, C [R, T, N]`` for ``R`` rows
    of ``T`` positions, ``A [N, E]``, ``D [E]`` (float32 arithmetic
    whatever their types), ``state [L, S, N, E]`` float32, ``conv [L, S,
    a, b]`` and each row's new tail ``tail [R, a, b]``, ``layer``
    an index, ``rows [R]`` each row's slot, ``reset [R]`` bool (start from
    a zero state), ``run [R]`` bool (advance and take the new tail; a row
    that does not run keeps its state and tail bit for bit and reads ``y
    = 0``).  Returns ``(y [R, T, E] float32, state, conv)``."""
    f32 = jnp.float32
    old = state[layer, rows]                                  # [R, N, E]
    h0 = jnp.where(reset[:, None, None], 0.0, old)
    A, D = A.astype(f32), D.astype(f32)

    def step(h, xs):
        x_t, dt_t, b_t, c_t = xs
        h = (jnp.exp(dt_t[:, None, :] * A) * h
             + b_t[:, :, None] * (dt_t * x_t)[:, None, :])
        return h, jnp.sum(c_t[:, :, None] * h, axis=1) + D * x_t

    time = lambda a: jnp.swapaxes(a.astype(f32), 0, 1)      # noqa: E731
    h, y = jax.lax.scan(step, h0, (time(x), time(dt), time(B), time(C)))
    h = jnp.where(run[:, None, None], h, old)
    y = jnp.where(run[None, :, None], y, 0.0)
    tail = jnp.where(run[:, None, None], tail.astype(conv.dtype),
                     conv[layer, rows])
    return (jnp.swapaxes(y, 0, 1), state.at[layer, rows].set(h),
            conv.at[layer, rows].set(tail))


def _kernel(layer_ref, rows_ref, reset_ref, run_ref, x_ref, dt_ref, bc_ref,
            a_ref, d_ref, tail_ref, h_ref, conv_ref, y_ref, h_out, conv_out,
            *, N, g, n_groups, sub):
    """One grid step = one row's block of channels.  ``bc_ref [1, G, 2N,
    g]`` holds ``B`` then ``C`` of each group of ``g`` positions with the
    positions on lanes, so that a position's column is a static lane
    slice."""
    from jax.experimental import pallas as pl

    r = pl.program_id(0)
    first = pl.program_id(1) == 0        # the row's tail, once
    Eb = x_ref.shape[2]
    fresh = reset_ref[r] != 0

    def tile(j, _):
        e0 = pl.multiple_of(j * sub, sub)
        lanes = pl.ds(e0, sub)
        a = a_ref[:, lanes]                                   # [N, sub]
        dv = d_ref[:, lanes]                                  # [1, sub]
        h = jnp.where(fresh, 0.0, h_ref[0, 0, :, lanes])
        row = jax.lax.broadcasted_iota(jnp.int32, (g, sub), 0)

        def group(k, h):
            t0 = 0 if n_groups == 1 else pl.multiple_of(k * g, g)
            xs = x_ref[0, pl.ds(t0, g), lanes].astype(jnp.float32)
            ds = dt_ref[0, pl.ds(t0, g), lanes].astype(jnp.float32)
            bc = bc_ref[0, k].astype(jnp.float32)             # [2N, g]
            ys = jnp.zeros((g, sub), jnp.float32)
            for i in range(g):
                x_t, dt_t = xs[i:i + 1], ds[i:i + 1]          # [1, sub]
                b_t, c_t = bc[:N, i:i + 1], bc[N:, i:i + 1]   # [N, 1]
                h = jnp.exp(dt_t * a) * h + b_t * (dt_t * x_t)
                y_t = jnp.sum(c_t * h, axis=0, keepdims=True) + dv * x_t
                ys = jnp.where(row == i, y_t, ys)
            y_ref[0, pl.ds(t0, g), lanes] = ys
            return h

        h = (group(0, h) if n_groups == 1
             else jax.lax.fori_loop(0, n_groups, group, h))
        h_out[0, 0, :, lanes] = h
        return 0

    run = run_ref[r] != 0

    @pl.when(run)
    def _():
        jax.lax.fori_loop(0, Eb // sub, tile, 0)

    @pl.when(jnp.logical_not(run))
    def _():
        h_out[...] = h_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(first)
    def _():
        conv_out[0, 0] = jnp.where(run, tail_ref[0], conv_ref[0, 0])


def _block(T, E, N):
    """Channels a grid step takes: the widest divisor of ``E`` in whole
    ``_SUB`` tiles whose double-buffered blocks fit ``_VMEM_BUDGET``
    (all of ``E`` where it is not whole tiles, or a decode step's one
    position leaves room)."""
    if E % _SUB:
        return E, E
    per_channel = 2 * (3 * T * 4 + 2 * N * 4)       # x, dt, y; state in/out
    fits = [b for b in range(_SUB, E + 1, _SUB)
            if E % b == 0 and b * per_channel <= _VMEM_BUDGET]
    return (max(fits) if fits else _SUB), _SUB


def selective_scan(x, dt, B, C, A, D, state, conv, tail, layer, rows, reset,
                   run):
    """The kernel; arguments and result as :func:`selective_scan_xla`.  A
    chunk of more than ``_GROUP`` positions is padded to whole groups with
    ``dt = 0``, which the state passes through unchanged."""
    R, T, E = x.shape
    N = B.shape[-1]
    check_equal("selective_scan", dt_shape=(dt.size, x.size),
                B_rows=(B.shape[0] * B.shape[1], R * T),
                C_shape=(C.size, B.size), A_shape=(A.size, N * E),
                state_state=(state.shape[2], N),
                state_channels=(state.shape[3], E), rows=(rows.shape[0], R),
                conv_slots=(conv.shape[1], state.shape[1]),
                tail_rows=(tail.shape[0], R),
                tail_shape=(tail.shape[1] * tail.shape[2],
                            conv.shape[2] * conv.shape[3]))
    g = T if T <= _GROUP else _GROUP
    n_groups = -(-T // g)
    Tp = n_groups * g
    f32 = jnp.float32
    pad = lambda a: jnp.pad(a.astype(f32),                  # noqa: E731
                            ((0, 0), (0, Tp - T), (0, 0)))
    # [R, groups, 2N, g]: a group's B then C with its positions on lanes
    bc = jnp.swapaxes(jnp.concatenate([pad(B), pad(C)], -1).reshape(
        R, n_groups, g, 2 * N), 2, 3)
    Eb, sub = _block(Tp, E, N)
    return _call(pad(x), pad(dt), bc, A.astype(f32).reshape(N, E),
                 D.astype(f32).reshape(1, E), tail.astype(conv.dtype), state,
                 conv, jnp.reshape(layer, (1,)).astype(jnp.int32),
                 rows.astype(jnp.int32), reset.astype(jnp.int32),
                 run.astype(jnp.int32), Eb=Eb, sub=sub, g=g, T=T,
                 interpret=_INTERPRET[0])


@functools.partial(jax.jit, static_argnames=("Eb", "sub", "g", "T",
                                             "interpret"))
def _call(x, dt, bc, A, D, tail, state, conv, layer, rows, reset, run, *, Eb,
          sub, g, T, interpret):
    # jitted so that the calls of one program at the same shapes (a
    # period's layers) trace and lower the kernel once
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, Tp, E = x.shape
    N = A.shape[0]
    a, b = conv.shape[2:]
    n_groups = Tp // g
    seq = lambda r, e, *_: (r, 0, e)                       # noqa: E731
    held = lambda r, e, lyr, rws, *_: (lyr[0], rws[r], 0, e)  # noqa: E731
    whole = lambda r, e, lyr, rws, *_: (lyr[0], rws[r], 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(R, E // Eb),
        in_specs=[pl.BlockSpec((1, Tp, Eb), seq),
                  pl.BlockSpec((1, Tp, Eb), seq),
                  pl.BlockSpec((1, n_groups, 2 * N, g),
                               lambda r, e, *_: (r, 0, 0, 0)),
                  pl.BlockSpec((N, Eb), lambda r, e, *_: (0, e)),
                  pl.BlockSpec((1, Eb), lambda r, e, *_: (0, e)),
                  pl.BlockSpec((1, a, b), lambda r, e, *_: (r, 0, 0)),
                  pl.BlockSpec((1, 1, N, Eb), held),
                  pl.BlockSpec((1, 1, a, b), whole)],
        out_specs=[pl.BlockSpec((1, Tp, Eb), seq),
                   pl.BlockSpec((1, 1, N, Eb), held),
                   pl.BlockSpec((1, 1, a, b), whole)])
    # the blocks of x, dt and y, B and C (lanes padded), A, the state in
    # and out, the tail in and the convolution's rows in and out (sublanes
    # padded), each double-buffered
    need = 2 * 4 * (3 * Tp * Eb + n_groups * 2 * N * LANE
                    + N * Eb + 8 * Eb + 2 * N * Eb + 3 * 16 * b)
    y, state, conv = pl.pallas_call(
        functools.partial(_kernel, N=N, g=g, n_groups=n_groups, sub=sub),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, Tp, E), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(conv.shape, conv.dtype)],
        input_output_aliases={10: 1, 11: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=need + 8 * 2 ** 20),
        interpret=interpret,
        name="selective_scan",
    )(layer, rows, reset, run, x, dt, bc, A, D, tail, state, conv)
    return y[:, :T], state, conv
