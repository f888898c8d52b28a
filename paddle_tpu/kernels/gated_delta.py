"""The gated delta rule (Gated DeltaNet: Yang, Kautz, Hatamizadeh,
arXiv:2412.06464) as the serving programs need it.

Per head, with keys ``k_t`` (L2-normalised), values ``v_t``, a decay
``alpha_t = exp(g_t)`` in (0, 1) and a write strength ``beta_t``, the layer
keeps a matrix ``S`` of ``[d_k, d_v]`` per request instead of a growing
K/V:

    S'_t = alpha_t S_{t-1}
    u_t  = beta_t (v_t - S'_t^T k_t)
    S_t  = S'_t + k_t u_t^T          o_t = S_t^T q_t

* :func:`gdn_step` — the one-token update of a decode launch, over every
  slot row at once.
* :func:`gdn_chunk` — the chunked form of a prefill chunk.  Within a chunk
  of ``CHUNK`` positions the ``u_t`` solve a unit lower-triangular system
  ``(I + A) U = beta (V - Gamma K S_0)`` with ``A[t, s] = beta_t
  (gamma_t / gamma_s) (k_t . k_s)`` for ``s < t`` and ``gamma`` the running
  product of the decays; the state is then carried from chunk to chunk:
  initial state in, final state out.  A position with ``g = 0`` and
  ``beta = 0`` (how the caller marks the padded tail of a bucket) leaves the
  state untouched.
* :func:`causal_conv` — the depthwise causal convolution in front of the
  rule (and of a Mamba layer's scan, ``models/jamba.py``, with a bias),
  over a chunk and the tail of inputs the previous chunk left.

Everything here is float32 at ``HIGHEST``: the triangular solve amplifies
rounding (``beta`` reaches 2, so ``I + A`` is not diagonally dominant) and
the state is summed over thousands of positions.

The solve (:func:`_solve_unit_lower`) is exact and blocked: the diagonal
blocks of ``BLOCK`` rows are inverted by forward substitution, every block
of every chunk and head one row at a time, and the blocks are then solved
across by batched matrix products, ``c / BLOCK`` dependent steps.  A
library ``triangular_solve`` walks the 64 rows of a chunk one after the
other and took 1.18 of a layer's 1.59 ms on a TPU v5e at the docqa cell's
shape for under 0.6 GFLOP; this form takes 0.1 of 0.50 ms (PERF.md,
section 5).  The doubling product ``(I - A)(I + A^2)(I + A^4)...`` would be
fewer products still and is not an option: once keys correlate the powers
of ``A`` grow by orders of magnitude before they cancel, and float32 reads
errors of 1e+3 and more where substitution reads 1e-6.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: positions solved together by the chunked form
CHUNK = 64
#: rows of a diagonal block of the chunk's system, inverted by substitution
BLOCK = 16

_HI = jax.lax.Precision.HIGHEST


def gdn_step(q, k, v, g, beta, state):
    """One token for every row: ``q, k [B, H, dk]``, ``v [B, H, dv]``,
    ``g, beta [B, H]``, ``state [B, H, dk, dv]`` (all float32).  Returns
    ``(o [B, H, dv], new state)``."""
    s = state * jnp.exp(g)[..., None, None]
    u = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", s, k,
                                          precision=_HI))
    s = s + k[..., :, None] * u[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", s, q, precision=_HI), s


def _solve_unit_lower(A, rhs):
    """``X`` of ``(I + A) X = rhs``: ``A [..., c, c]`` strictly lower
    triangular, ``rhs [..., c, R]`` (float32).  Exact, by blocks of
    ``b = min(BLOCK, c)`` rows: each diagonal block ``I + A_ii`` is inverted
    by forward substitution, row ``r`` of the inverse being ``e_r -
    A_ii[r, :r] @ inv[:r]`` (every block at once, ``b - 1`` small steps),
    then ``X_i = inv_i (rhs_i - sum_{j<i} A_ij X_j)`` by batched products."""
    c = A.shape[-1]
    b = min(BLOCK, c)
    m = -(-c // b)
    if m * b != c:      # whole blocks: the rows added solve x = 0
        lead = ((0, 0),) * (A.ndim - 2)
        A = jnp.pad(A, lead + ((0, m * b - c),) * 2)
        rhs = jnp.pad(rhs, lead + ((0, m * b - c), (0, 0)))
    blocks = [slice(i * b, (i + 1) * b) for i in range(m)]
    D = jnp.stack([A[..., s, s] for s in blocks], -3)       # [..., m, b, b]
    eye = jnp.eye(b, dtype=A.dtype)
    rows = jnp.arange(b)[:, None]

    def substitute(r, inv):
        # rows r.. of inv are still the identity's and D[r] is zero from
        # its diagonal on, so the whole product is the sum over s < r
        d = jax.lax.dynamic_slice_in_dim(D, r, 1, axis=-2)
        return jnp.where(rows == r,
                         eye - jnp.matmul(d, inv, precision=_HI), inv)

    inv = jax.lax.fori_loop(1, b, substitute,
                            jnp.broadcast_to(eye, D.shape))
    X = []
    for i, s in enumerate(blocks):
        r = rhs[..., s, :]
        if i:
            r = r - jnp.matmul(A[..., s, :i * b], jnp.concatenate(X, -2),
                               precision=_HI)
        X.append(jnp.matmul(inv[..., i, :, :], r, precision=_HI))
    return jnp.concatenate(X, -2)[..., :c, :]


def gdn_chunk(q, k, v, g, beta, state):
    """``T`` positions of ``B`` sequences: ``q, k [B, T, H, dk]``,
    ``v [B, T, H, dv]``, ``g, beta [B, T, H]``, ``state [B, H, dk, dv]``
    (all float32; ``T`` a multiple of ``min(T, CHUNK)``).  Returns
    ``(o [B, T, H, dv], final state)``."""
    B, T, H, K = q.shape
    V = v.shape[-1]
    c = min(T, CHUNK)
    n = T // c
    if n * c != T:
        raise ValueError(f"gdn_chunk: {T} positions are not whole chunks "
                         f"of {c}")

    def chunks(x):      # [B, T, H, ...] -> [n, B, H, c, ...]
        x = x.reshape(B, n, c, H, *x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-1)                          # log gamma_t
    t = jnp.arange(c)
    below, upto = t[:, None] > t[None, :], t[:, None] >= t[None, :]
    # gamma_t / gamma_s on and under the diagonal; above it the quotient
    # would overflow and is never used
    decay = jnp.exp(jnp.where(upto, G[..., :, None] - G[..., None, :],
                              -jnp.inf))
    kk = jnp.einsum("...tk,...sk->...ts", k, k, precision=_HI)
    A = jnp.where(below, beta[..., None] * decay * kk, 0.0)
    rhs = jnp.concatenate(
        [beta[..., None] * v, (beta * jnp.exp(G))[..., None] * k], -1)
    sol = _solve_unit_lower(A, rhs)
    u0, w = sol[..., :V], sol[..., V:]       # U = u0 - w S_0
    qk = jnp.where(upto, decay * jnp.einsum(
        "...tk,...sk->...ts", q, k, precision=_HI), 0.0)
    q_in = q * jnp.exp(G)[..., None]                    # reads S_0
    k_out = k * jnp.exp(G[..., -1:] - G)[..., None]     # decays to the end
    g_all = jnp.exp(G[..., -1])

    def carry(s, xs):
        u0_i, w_i, qk_i, q_i, k_i, g_i = xs
        u = u0_i - jnp.matmul(w_i, s, precision=_HI)
        o = jnp.matmul(q_i, s, precision=_HI) + jnp.matmul(
            qk_i, u, precision=_HI)
        s = g_i[..., None, None] * s + jnp.einsum(
            "...tk,...tv->...kv", k_i, u, precision=_HI)
        return s, o

    state, o = jax.lax.scan(carry, state, (u0, w, qk, q_in, k_out, g_all))
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1)       # [B, n, c, H, V]
    return o.reshape(B, T, H, V), state


def causal_conv(x, w, tail, length=None, bias=None):
    """Depthwise causal convolution of ``x [B, T, C]`` with the filter
    ``w [W, C]`` (``y_t = sum_i w_i x_{t-W+1+i}``, plus ``bias [C]`` where
    given), the ``W - 1`` inputs before ``x`` given by ``tail [B, W - 1,
    C]``.  Returns ``(y, new tail)``: the last ``W - 1`` inputs up to
    ``length`` (default ``T``), so the padded end of a bucket never reaches
    the next chunk."""
    W = w.shape[0]
    T = x.shape[1]
    xin = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    y = sum(w[i] * xin[:, i:i + T] for i in range(W))
    if bias is not None:
        y = y + bias
    if length is None:
        return y, xin[:, T:]
    return y, jax.lax.dynamic_slice_in_dim(xin, length, W - 1, axis=1)
