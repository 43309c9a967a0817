"""Per-block top-k magnitude compression — the accumulator's sparse mode.

STEP §5.2 ships sparse vectors as (index, value) pairs.  For gradients the
production form is blocked top-k: each lane block contributes its
``k_per_block`` largest-|x| entries, so selection is lane-parallel with no
global sort (the same schedule :func:`repro.core.sparse.blocked_topk_sparsify`
implements in jnp — that is the oracle).

Layout: the wrapper pads each ``block_v`` block into one ``(rows, 128)`` tile
(:func:`repro.kernels.bitonic.block_rows`); grid = (V / block_v,), one tile
per step.  Each step writes its pairs as a ``(ceil(k/128), 128)`` tile and
the wrapper keeps the first ``k`` slots.  Two selection methods, identical
outputs:

* ``method="argmax"`` — k iterations of (max → first index at the max →
  record → mask), all whole-tile reductions.  k sequential reductions; fine
  for small budgets (k ≤ 64 per block).
* ``method="bitonic"`` — one :mod:`repro.kernels.bitonic` sort per block,
  O(log² block_v) vector stages *independent of k*, so large budgets stop
  scaling linearly.

Both stay in VMEM; ties break toward the lower index in both (the argmax loop
takes the least index at the maximum, the bitonic comparator orders (mag
desc, idx asc)), so the pair streams are element-wise identical.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitonic import LANES, bitonic_sort_desc, block_rows, flat_position


def _reduce_tile(fn, t):
    """Reduce a 2-D tile to (1, 1), one axis at a time."""
    return fn(fn(t, axis=1, keepdims=True), axis=0, keepdims=True)


def _block_magnitudes(x_ref, *, block_v: int, total: int):
    """(x, in-block position, |x| with -1 on padding) for this step's tile."""
    x = x_ref[...].astype(jnp.float32)                       # (rows, 128)
    col = flat_position(x.shape)
    base = pl.program_id(0) * block_v
    valid = (col < block_v) & (base + col < total)
    return x, col, base, jnp.where(valid, jnp.abs(x), -1.0)


def _topk_kernel(x_ref, idx_ref, val_ref, *, k: int, block_v: int, total: int):
    x, col, base, mag = _block_magnitudes(x_ref, block_v=block_v, total=total)
    slot = flat_position(idx_ref.shape)
    big = jnp.int32(x.size)

    def body(i, carry):
        mag_c, out_idx, out_val = carry
        top = _reduce_tile(jnp.max, mag_c)                   # (1, 1)
        first = _reduce_tile(jnp.min, jnp.where(mag_c == top, col, big))
        hit = col == first
        # exactly one hit; -0.0 is the additive identity even for x = -0.0
        val = _reduce_tile(jnp.sum, jnp.where(hit, x, -0.0))
        ok = top >= 0                       # padded/exhausted → (0, 0) pair
        here = slot == i
        out_idx = jnp.where(here, jnp.where(ok, base + first, 0), out_idx)
        out_val = jnp.where(here, jnp.where(ok, val, 0.0), out_val)
        return jnp.where(hit, -2.0, mag_c), out_idx, out_val

    init = (mag, jnp.zeros(idx_ref.shape, jnp.int32),
            jnp.zeros(val_ref.shape, jnp.float32))
    _, out_idx, out_val = jax.lax.fori_loop(0, k, body, init)
    idx_ref[...] = out_idx
    val_ref[...] = out_val.astype(val_ref.dtype)


def _topk_bitonic_kernel(x_ref, idx_ref, val_ref, *, k: int, block_v: int,
                         total: int):
    x, col, base, mag = _block_magnitudes(x_ref, block_v=block_v, total=total)
    top_mag, top_col, top_val = bitonic_sort_desc(mag, col, x)
    out_rows = idx_ref.shape[0]
    top_mag, top_col, top_val = (t[:out_rows] for t in (top_mag, top_col, top_val))
    ok = top_mag >= 0                            # padded/exhausted → (0, 0) pair
    idx_ref[...] = jnp.where(ok, base + top_col, 0)
    val_ref[...] = jnp.where(ok, top_val, 0.0).astype(val_ref.dtype)


_KERNELS = {"argmax": _topk_kernel, "bitonic": _topk_bitonic_kernel}

# The argmax loop pays k sequential reductions, the bitonic network a fixed
# log²-stage cost — the crossover sits around one VMEM block's worth of k.
BITONIC_MIN_K = 65


def topk_compress_blocked(x, *, k_per_block: int, block_v: int = 1024,
                          interpret: bool = False, method: str | None = None):
    """x (V,) → (idx (nblocks*k,), vals (nblocks*k,)) — blocked top-k pairs."""
    v = x.shape[0]
    block_v = min(block_v, v)
    nblocks = pl.cdiv(v, block_v)
    if method is None:
        method = "bitonic" if k_per_block >= BITONIC_MIN_K else "argmax"
    if method not in _KERNELS:
        raise ValueError(f"method must be argmax|bitonic, got {method!r}")
    rows = block_rows(block_v)
    out_rows = pl.cdiv(k_per_block, LANES)
    xb = jnp.pad(x, (0, nblocks * block_v - v)).reshape(nblocks, block_v)
    xb = jnp.pad(xb, ((0, 0), (0, rows * LANES - block_v)))
    kernel = functools.partial(_KERNELS[method], k=k_per_block, block_v=block_v,
                               total=v)
    out_spec = pl.BlockSpec((None, out_rows, LANES), lambda j: (j, 0, 0))
    idx, vals = pl.pallas_call(
        kernel,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec((None, rows, LANES), lambda j: (j, 0, 0))],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((nblocks, out_rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((nblocks, out_rows, LANES), x.dtype),
        ],
        interpret=interpret,
    )(xb.reshape(nblocks, rows, LANES))

    def first_k(t):
        return t.reshape(nblocks, out_rows * LANES)[:, :k_per_block].reshape(-1)

    return first_k(idx), first_k(vals)
