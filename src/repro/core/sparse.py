"""Sparse accumulate wire format (§5.2): blocked top-k (index, value) pairs.

The paper represents a sparse vector as (index, non-zero element) pairs and
transfers those when they are cheaper than the dense vector.  On TPU we keep
the same decision rule but produce the pairs with a *blocked* top-k so shapes
stay static under jit: ``k`` is the per-contribution budget, spread over
128-lane-friendly blocks (``per_block = ceil(k / nblocks)`` entries selected
per block, no global sort).  When every block's nnz fits its per-block quota
the representation is lossless — exactly the condition under which the auto
mode may select it.

This module is the *dispatching layer* shared by both backends:

* :func:`blocked_topk_sparsify` routes to the Pallas
  :mod:`repro.kernels.topk_compress` kernel by default (interpret-mode
  fallback off-TPU) and keeps the jnp formulation as a tested reference
  (``impl="jnp"``).  Both produce the same :class:`SparsePairs` format.
* :func:`blocked_topk_accumulate` is the host ``DAddAccumulator``'s pairs
  reduce: the same selection fused with the scatter-add, one
  :mod:`repro.kernels.accumulate.fused_scatter` launch per round.
* :class:`SparsePairs` is the pair container of the SPMD collective
  (:func:`blocked_topk_sparsify`, then :func:`densify`) — its static length
  (:func:`pair_capacity`) is what both backends' wire-traffic accounting is
  derived from.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

DEFAULT_BLOCK = 1024


# ---------------------------------------------------------------------------
# Selection layout: one formula, used by the sparsifier, the benefit rule and
# the traffic accounting on BOTH backends — keep them from drifting apart.
# ---------------------------------------------------------------------------


def block_layout(n: int, k: int, block: int = DEFAULT_BLOCK) -> tuple[int, int, int]:
    """``(nblocks, block_eff, per_block)`` of the blocked top-k selection.

    A length-``n`` vector is split into ``nblocks`` blocks of ``block_eff``
    elements; each block contributes its ``per_block`` largest-|x| entries.
    """
    n, k, block = int(n), int(k), int(block)
    if n <= 0:
        raise ValueError(f"vector length must be positive, got {n}")
    if k <= 0:
        raise ValueError(f"top-k budget must be positive, got {k}")
    block_eff = max(1, min(block, n))
    nblocks = -(-n // block_eff)
    per_block = min(block_eff, max(1, -(-k // nblocks)))
    return nblocks, block_eff, per_block


def pair_capacity(n: int, k: int, block: int = DEFAULT_BLOCK) -> int:
    """Static number of (index, value) pairs a budget-``k`` compression of a
    length-``n`` vector puts on the wire (``nblocks * per_block`` ≈ k).

    This is the figure wire-traffic accounting uses on both backends: under
    jit the pair arrays have exactly this length regardless of the data.
    """
    nblocks, _, per_block = block_layout(n, k, block)
    return nblocks * per_block


def default_auto_k(n: int) -> int:
    """Default budget for ``AccumMode.AUTO`` when none was given: ~V/4, so the
    pairs representation (2·capacity elements) stays under half the dense
    vector whenever it is selected.  Auto is lossless by construction, so a
    defaulted budget never changes results — only which wire format wins."""
    return max(1, int(n) // 4)


# ---------------------------------------------------------------------------
# The shared pair format
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclass
class SparsePairs:
    """Blocked top-k compression of one length-``n`` contribution.

    ``idx``/``vals`` have static length :func:`pair_capacity`; positions
    beyond a block's nnz carry ``(0, 0.0)`` and scatter-add as no-ops.
    Iterable as ``(idx, vals)`` for tuple-style call sites.
    """

    idx: jax.Array
    vals: jax.Array
    n: int  # dense vector length

    def tree_flatten(self):
        return (self.idx, self.vals), self.n

    @classmethod
    def tree_unflatten(cls, n, children):
        return cls(children[0], children[1], n)

    def __iter__(self):
        yield self.idx
        yield self.vals

    @property
    def num_pairs(self) -> int:
        """Pairs on the wire — the static capacity, not the data's nnz."""
        return int(self.idx.shape[-1])

    @property
    def wire_elements(self) -> int:
        """Wire cost in vector elements: one index + one value per pair."""
        return 2 * self.num_pairs

    def densify(self) -> jax.Array:
        """Scatter-add the pairs back into a dense length-``n`` vector."""
        return densify(self.idx, self.vals, self.n)


# ---------------------------------------------------------------------------
# Sparsifiers
# ---------------------------------------------------------------------------


def topk_sparsify(x: jax.Array, k: int):
    """(indices, values) of the k largest-magnitude entries of a 1-D x —
    the unblocked (global sort) form, kept for small vectors and tests."""
    _, idx = jax.lax.top_k(jnp.abs(x), k)
    return idx, x[idx]


def _blocked_topk_jnp(x: jax.Array, nblocks: int, block_eff: int, per_block: int):
    """jnp reference path: same selection schedule as the Pallas kernel."""
    n = x.shape[0]
    pad = nblocks * block_eff - n
    xp = jnp.pad(x, (0, pad)).reshape(nblocks, block_eff)
    valid = jnp.arange(nblocks * block_eff).reshape(nblocks, block_eff) < n
    mag = jnp.where(valid, jnp.abs(xp), -1.0)
    _, idx = jax.lax.top_k(mag, per_block)                   # (nblocks, per_block)
    base = (jnp.arange(nblocks) * block_eff)[:, None]
    flat_idx = (idx + base).reshape(-1)
    vals = jnp.take_along_axis(xp, idx, axis=1).reshape(-1)
    ok = jnp.take_along_axis(mag, idx, axis=1).reshape(-1) >= 0
    return flat_idx, jnp.where(ok, vals, jnp.zeros((), x.dtype))


def blocked_topk_sparsify(x: jax.Array, k: int, block: int = DEFAULT_BLOCK, *,
                          impl: str = "pallas") -> SparsePairs:
    """Compress a 1-D ``x`` to :class:`SparsePairs` under budget ``k``.

    ``impl="pallas"`` (default) dispatches to the
    :mod:`repro.kernels.topk_compress` kernel — compiled on TPU, interpret
    mode elsewhere; ``impl="jnp"`` is the pure-jnp reference with the same
    selection schedule.  Lossless iff every block's nnz fits its per-block
    quota (in particular whenever ``nnz(x) <= per_block`` for every block).
    """
    n = x.shape[0]
    nblocks, block_eff, per_block = block_layout(n, k, block)
    if impl == "pallas":
        from repro.kernels.topk_compress.ops import topk_compress
        idx, vals = topk_compress(x, k_per_block=per_block, block_v=block_eff)
    elif impl == "jnp":
        idx, vals = _blocked_topk_jnp(x, nblocks, block_eff, per_block)
    else:
        raise ValueError(f"impl must be pallas|jnp, got {impl!r}")
    # normalise the padded tail: index 0 / value 0 is a harmless scatter-add
    in_range = idx < n
    return SparsePairs(jnp.where(in_range, idx, 0).astype(jnp.int32),
                       jnp.where(in_range, vals, jnp.zeros((), vals.dtype)), n)


def densify(idx: jax.Array, vals: jax.Array, n: int) -> jax.Array:
    """Scatter-add (index, value) pairs into a dense length-n vector."""
    return jnp.zeros((n,), vals.dtype).at[idx.reshape(-1)].add(vals.reshape(-1))


@partial(jax.jit, static_argnums=(1, 2, 3))
def _fused_accumulate_jnp(mat: jax.Array, nblocks: int, block_eff: int,
                          per_block: int) -> jax.Array:
    """jnp reference for the fused kernel: same selection + fold schedule."""
    n, v = mat.shape
    pad = nblocks * block_eff - v
    xp = jnp.pad(mat, ((0, 0), (0, pad))).astype(jnp.float32)
    xp = xp.reshape(n, nblocks, block_eff)
    idx = jnp.broadcast_to(jnp.arange(block_eff), xp.shape)
    valid = (jnp.arange(nblocks * block_eff) < v).reshape(1, nblocks, block_eff)
    mag = jnp.where(valid, jnp.abs(xp), -1.0)
    if per_block < block_eff:
        thr_mag, thr_pos = jax.lax.top_k(mag, per_block)     # ties → lowest idx
        thr_mag = thr_mag[..., -1:]
        thr_idx = thr_pos[..., -1:]
        sel = (mag > thr_mag) | ((mag == thr_mag) & (idx <= thr_idx))
    else:
        sel = jnp.broadcast_to(valid, xp.shape)
    contrib = jnp.where(sel & valid, xp, 0.0)
    acc = contrib[0]
    for t in range(1, n):                     # left-fold: same order as kernel
        acc = acc + contrib[t]
    return acc.reshape(-1)[:v].astype(mat.dtype)


def blocked_topk_accumulate(mat: jax.Array, k: int, block: int = DEFAULT_BLOCK,
                            *, impl: str = "pallas") -> jax.Array:
    """Sum of the budget-``k`` blocked top-k compressions of each row of a
    stacked (N, V) round — the host accumulator's SPARSE/AUTO reduce.

    Selection and application are merged: ``impl="pallas"`` (default) is one
    :mod:`repro.kernels.accumulate.fused_scatter` launch, with no pair arrays
    or dense per-thread intermediates; ``impl="jnp"`` is the pure-jnp
    reference with the same selection + left-fold schedule.  Both routes
    equal, bit for bit, a :func:`blocked_topk_sparsify` per row followed by
    one :func:`densify` of the concatenated pairs: selection is block-local
    with ties broken toward the lower index, and the left-fold matches the
    scatter-add's per-index association order.
    """
    v = mat.shape[1]
    nblocks, block_eff, per_block = block_layout(v, k, block)
    if impl == "pallas":
        from repro.kernels.accumulate.fused_scatter import fused_topk_scatter
        return fused_topk_scatter(mat, per_block=per_block, block_eff=block_eff)
    elif impl == "jnp":
        return _fused_accumulate_jnp(mat, nblocks, block_eff, per_block)
    raise ValueError(f"impl must be pallas|jnp, got {impl!r}")


def nnz(x: jax.Array) -> jax.Array:
    return jnp.sum((x != 0).astype(jnp.int32))


def sparse_beneficial(x: jax.Array, k: int, block: int = DEFAULT_BLOCK) -> jax.Array:
    """Paper's auto rule, blocked-selection aware: pairs win when the blocked
    top-k is lossless (every block's nnz fits its per-block quota) and the
    pairs are smaller than the dense vector (2·capacity < V)."""
    n = x.shape[0]
    nblocks, block_eff, per_block = block_layout(n, k, block)
    pad = nblocks * block_eff - n
    xp = jnp.pad(x, (0, pad)).reshape(nblocks, block_eff)
    per_block_nnz = jnp.sum((xp != 0).astype(jnp.int32), axis=1)
    cheaper = 2 * pair_capacity(n, k, block) < n
    return jnp.logical_and(jnp.all(per_block_nnz <= per_block), cheaper)


@partial(jax.jit, static_argnums=(1, 2))
def _all_beneficial(mat: jax.Array, k: int, block: int) -> jax.Array:
    return jnp.all(jax.vmap(lambda f: sparse_beneficial(f, k, block))(mat))


def sparse_beneficial_batch(vectors, k: int, block: int = DEFAULT_BLOCK) -> jax.Array:
    """The auto rule for a whole accumulator round in ONE jitted call: True
    iff *every* contribution is losslessly compressible AND cheaper.

    The host accumulator's round closes on the driver; evaluating the rule
    per contribution costs O(N) small device syncs per round.  Stacking the
    (same-shape, by the ragged-round contract) contributions and deciding
    under one jit collapses that to a single scalar sync."""
    mat = jnp.stack([jnp.asarray(v).reshape(-1) for v in vectors])
    return _all_beneficial(mat, int(k), int(block))
