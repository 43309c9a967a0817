"""Bitonic sorting network over (magnitude, index) keys + carried payloads.

The top-k selection kernels need a *partial sort*: the ``per_block`` largest
|x| entries of each lane block, ties broken toward the lower index (so the
result is element-wise identical to the argmax→mask loop, which picks the
first maximum).  A bitonic network gives that in ``O(log² L)``
compare-exchange stages of full-width vector ops — independent of k — where
the argmax loop pays k sequential reductions.

Layout: a block of ``L`` elements (a power of two, at least one (8, 128)
tile) is held as ``(..., L // 128, 128)``; flat position ``i = row·128 +
lane``.  Stage structure::

    for k in 2, 4, ..., L:          # bitonic run length being merged
        for j in k/2, k/4, ..., 1:  # compare-exchange distance
            element i meets its partner i ^ j

The partner is found by rotating the block by ``j`` both ways and picking
with the iota mask ``i & j`` — lane rotations for ``j < 128``, sublane
rotations (by ``j / 128`` rows) above — so no stage reshapes a vector, which
Mosaic cannot lower.  The same code runs inside a compiled Pallas kernel and
in interpret mode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

LANES = 128
MIN_ROWS = 8    # one full (8, 128) f32 tile: sublane rotations stay in-vreg


def block_rows(length: int) -> int:
    """Rows of the ``(rows, 128)`` tile that holds a block of ``length``
    elements: the next power of two ``>= length``, at least one (8, 128)
    tile."""
    padded = 1 << max(0, int(length) - 1).bit_length()
    return max(MIN_ROWS, padded // LANES)


def flat_position(shape) -> jax.Array:
    """``row·128 + lane`` over the last two axes, broadcast over the rest."""
    row = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 2)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return row * LANES + lane


def _partner(t, j: int, lower):
    """``t`` at flat position ``i ^ j`` for every ``i`` (``lower`` ⇔ ``i & j == 0``)."""
    if j < LANES:
        axis, size, shift = t.ndim - 1, LANES, j
    else:
        axis, size, shift = t.ndim - 2, t.shape[-2], j // LANES
    ahead = pltpu.roll(t, size - shift, axis)     # ahead[i] = t[i + j]
    behind = pltpu.roll(t, shift, axis)           # behind[i] = t[i - j]
    return jnp.where(lower, ahead, behind)


def bitonic_sort_desc(mag, idx, *payloads):
    """Sort ``(..., rows, 128)`` blocks, in flat order, by (mag descending,
    idx ascending).

    ``idx`` must be unique within a block (positions), making the order a
    strict total order, so the network's output is deterministic and matches
    first-occurrence argmax selection on magnitude ties.  Extra ``payloads``
    (same shape) are carried through the permutation.  ``rows · 128`` must be
    a power of two (see :func:`block_rows`).  Returns ``(mag, idx,
    *payloads)`` sorted.
    """
    length = mag.shape[-2] * mag.shape[-1]
    if mag.shape[-1] != LANES or length & (length - 1):
        raise ValueError(f"want (..., 2^m, 128) blocks, got {mag.shape}")
    pos = flat_position(mag.shape)
    k = 2
    while k <= length:
        desc = (pos & k) == 0       # run direction: descending where bit k clear
        j = k // 2
        while j >= 1:
            lower = (pos & j) == 0
            p_mag = _partner(mag, j, lower)
            p_idx = _partner(idx, j, lower)
            # "this element ranks below its partner" in (mag desc, idx asc)
            below = (mag < p_mag) | ((mag == p_mag) & (idx > p_idx))
            # the lower slot of a descending pair keeps the higher rank, the
            # upper slot the lower rank; ascending runs flip both
            take = below == (lower == desc)
            mag = jnp.where(take, p_mag, mag)
            idx = jnp.where(take, p_idx, idx)
            payloads = tuple(jnp.where(take, _partner(p, j, lower), p)
                             for p in payloads)
            j //= 2
        k *= 2
    return (mag, idx, *payloads)


__all__ = ["LANES", "block_rows", "bitonic_sort_desc", "flat_position"]
