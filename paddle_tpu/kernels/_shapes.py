"""Shared Pallas preflight checks + the mask-fill constant.

Two things every kernel in this package needs and each used to hand-roll:

* **Block-shape preflight** — Mosaic reports an illegal BlockSpec as an
  opaque lowering error deep inside XLA (the round-1 bench died on one).  The
  validators here run *before* ``pallas_call`` and raise a ``ValueError``
  that names the offending dimension, the kernel, and the constraint, so
  a bad configuration fails at the call site in plain English.
* **``NEG_INF``** — the additive mask fill.  A hard-coded ``-1e30``
  is representable in every float dtype we use, but it is NOT the most
  negative finite value, and mask arithmetic that mixes fills from
  different sites can drift.  ``neg_inf(dtype)`` returns
  ``finfo(dtype).min`` — the most negative *finite* value, so
  ``exp(fill - m)`` underflows to exactly 0 and bf16 mask fills can
  never round to ``-inf`` (whose ``inf - inf`` arithmetic NaNs).
"""

from __future__ import annotations

import jax.numpy as jnp

#: TPU vector lane width: the last dim of every VMEM tile.
LANE = 128

#: itemsize -> minimum second-to-last (sublane) tile dim.
_MIN_SUBLANE = {4: 8, 2: 16, 1: 32}


def min_sublane(dtype) -> int:
    """Minimum sublane tile extent for ``dtype`` (fp32 8, bf16 16,
    int8/fp8 32)."""
    return _MIN_SUBLANE.get(jnp.dtype(dtype).itemsize, 8)


def neg_inf(dtype=jnp.float32) -> float:
    """Most negative finite value of ``dtype`` — the dtype-aware mask
    fill (``jnp.finfo(dtype).min``)."""
    return float(jnp.finfo(jnp.dtype(dtype)).min)


#: fp32 mask fill shared by the kernels and the jnp reference twins
#: (``gpt.decode_paged``/``_cached_layers`` mask their fp32 logits with
#: this).  Use ``neg_inf(dtype)`` when filling a non-fp32 array.
NEG_INF = neg_inf(jnp.float32)


def check_divides(kernel: str, **dims):
    """Each kwarg is ``name=(size, block)``: ``block`` must be a positive
    divisor of ``size``.  Raises ``ValueError`` naming the offending dim."""
    for name, (size, block) in dims.items():
        size, block = int(size), int(block)
        if block < 1:
            raise ValueError(
                f"{kernel}: block for dim '{name}' must be >= 1, got "
                f"{block}")
        if size % block:
            raise ValueError(
                f"{kernel}: dim '{name}'={size} is not divisible by its "
                f"block shape {block} — Pallas would silently skip the "
                f"ragged tail; pick a block that divides {size}")


def check_equal(kernel: str, **dims):
    """Each kwarg is ``name=(got, want)``: operand-consistency preflight.
    Raises ``ValueError`` naming the offending dim."""
    for name, (got, want) in dims.items():
        if int(got) != int(want):
            raise ValueError(
                f"{kernel}: dim '{name}'={got} does not match the "
                f"required {want} (operand shapes disagree)")


def check_min_tile(kernel: str, dtype, *, sublane=None, lane=None,
                   sublane_name="sublane", lane_name="lane"):
    """TPU tiling minimums: the last dim must be a multiple of the
    128-wide lane, the second-to-last a multiple of the dtype's minimum
    sublane extent.  Pass only the dims the kernel actually tiles."""
    if lane is not None and int(lane) % LANE:
        raise ValueError(
            f"{kernel}: dim '{lane_name}'={lane} must be a multiple of "
            f"the {LANE}-wide TPU lane")
    ms = min_sublane(dtype)
    if sublane is not None and int(sublane) % ms:
        raise ValueError(
            f"{kernel}: dim '{sublane_name}'={sublane} must be a "
            f"multiple of the minimum sublane tile {ms} for "
            f"{jnp.dtype(dtype).name}")
