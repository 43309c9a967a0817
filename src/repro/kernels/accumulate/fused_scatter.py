"""Fused sparsify→scatter-add — the accumulator's SPARSE reduce in one launch.

The unfused host path materialises, per round, N pair arrays (one
``topk_compress`` launch per thread) plus a dense scatter-add over their
concatenation.  But the blocked top-k selection is *block-local*: whether an
entry of block ``j`` survives depends only on block ``j``'s magnitudes.  So
selection and application fuse — grid over V-blocks, and for each block:

1. per-row (mag desc, idx asc) bitonic partial sort → the ``per_block``-th
   entry is each row's selection threshold,
2. mask each row to its selected entries (ties broken toward the lower
   index, matching ``topk_compress``'s pair stream exactly),
3. left-fold the N masked rows in fp32 — the same association order as
   scatter-adding the threads' pairs in thread order, so the fused result is
   bit-exact with the compress→densify→add path.

No (index, value) pairs or dense per-thread intermediates ever hit HBM; the
wire-accounting figures are unchanged because the *logical* pair count of a
budget-k compression is static (:func:`repro.core.sparse.pair_capacity`)
whether or not the pairs are materialised.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitonic import LANES, bitonic_sort_desc, block_rows, flat_position


def _fused_scatter_kernel(x_ref, o_ref, *, per_block: int, block_eff: int,
                          total: int):
    x = x_ref[...].astype(jnp.float32)                       # (N, rows, 128)
    col = flat_position(x.shape)
    base = pl.program_id(0) * block_eff
    valid = (col < block_eff) & (base + col < total)
    mag = jnp.where(valid, jnp.abs(x), -1.0)
    if per_block < block_eff:
        sorted_mag, sorted_col = bitonic_sort_desc(mag, col)
        # each row's per_block-th entry in (mag desc, idx asc) order is its
        # selection threshold; pick it out with a one-hot max per row
        at = col == per_block - 1

        def threshold(t, fill):
            t = jnp.where(at, t, fill)
            return jnp.max(jnp.max(t, axis=2, keepdims=True), axis=1,
                           keepdims=True)                    # (N, 1, 1)

        thr_mag = threshold(sorted_mag, -jnp.inf)
        thr_col = threshold(sorted_col, -1)
        # Selected ⇔ ranks at or above the threshold entry in (mag desc,
        # idx asc) order — exactly per_block entries per row.
        sel = (mag > thr_mag) | ((mag == thr_mag) & (col <= thr_col))
    else:
        sel = valid                                          # quota ≥ block: all
    contrib = jnp.where(sel & valid, x, 0.0)
    # Left-fold, not jnp.sum: matches the scatter-add's per-index association
    # order (thread 0 first) for bit-exact parity with the unfused path.
    acc = contrib[0]
    for t in range(1, contrib.shape[0]):
        acc = acc + contrib[t]
    o_ref[...] = acc.astype(o_ref.dtype)


def fused_topk_scatter_blocked(x, *, per_block: int, block_eff: int,
                               interpret: bool = False):
    """x (N, V) → (V,): sum of each row's blocked top-``per_block`` entries."""
    n, v = x.shape
    block_eff = min(block_eff, v)
    nblocks = pl.cdiv(v, block_eff)
    rows = block_rows(block_eff)
    xb = jnp.pad(x, ((0, 0), (0, nblocks * block_eff - v)))
    xb = jnp.pad(xb.reshape(n, nblocks, block_eff),
                 ((0, 0), (0, 0), (0, rows * LANES - block_eff)))
    kernel = functools.partial(_fused_scatter_kernel, per_block=per_block,
                               block_eff=block_eff, total=v)
    out = pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec((n, None, rows, LANES), lambda j: (0, j, 0, 0))],
        out_specs=pl.BlockSpec((None, rows, LANES), lambda j: (j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nblocks, rows, LANES), x.dtype),
        interpret=interpret,
    )(xb.reshape(n, nblocks, rows, LANES))
    return out.reshape(nblocks, rows * LANES)[:, :block_eff].reshape(-1)[:v]


@functools.partial(jax.jit, static_argnames=("per_block", "block_eff", "interpret"))
def fused_topk_scatter(x, *, per_block: int, block_eff: int, interpret=None):
    """Jit'd entry point: compiled Pallas on TPU, interpret mode elsewhere."""
    if x.ndim != 2:
        raise ValueError(f"fused_topk_scatter wants (N, V), got shape {x.shape}")
    if per_block < 1:
        raise ValueError(f"per_block must be >= 1, got {per_block}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return fused_topk_scatter_blocked(x, per_block=per_block,
                                      block_eff=block_eff, interpret=interpret)
