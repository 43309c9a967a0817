"""K-means assignment kernel: nearest-center via MXU distance GEMM.

The paper's K-means hot loop (§6.5) is distance computation + argmin per
point.  ‖p − c‖² = ‖p‖² − 2·p·c + ‖c‖², so the TPU schedule is one
(block_n, D) × (D, K) GEMM per point tile (centers stay VMEM-resident) plus a
lane reduction — exactly how the MXU wants it.  Outputs the assignment and
the distance (needed for the inertia metric).  Grid = (N / block_n,).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _assign_kernel(pts_ref, ctr_ref, assign_ref, dist_ref):
    pts = pts_ref[...].astype(jnp.float32)                    # (bn, D)
    ctr = ctr_ref[...].astype(jnp.float32)                    # (K, D)
    p2 = jnp.sum(pts * pts, axis=1, keepdims=True)            # (bn, 1)
    c2 = jnp.sum(ctr * ctr, axis=1)[None, :]                  # (1, K)
    dots = jax.lax.dot_general(pts, ctr, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    d2 = p2 - 2.0 * dots + c2                                  # (bn, K)
    assign_ref[...] = jnp.argmin(d2, axis=1).astype(jnp.int32)
    dist_ref[...] = jnp.min(d2, axis=1)


def kmeans_assign_blocked(points, centers, *, block_n: int = 1024,
                          interpret: bool = False):
    """points (N, D), centers (K, D) → (assign (N,) int32, dist² (N,) f32)."""
    n, d = points.shape
    k = centers.shape[0]
    block_n = min(block_n, n)
    grid = (pl.cdiv(n, block_n),)
    return pl.pallas_call(
        _assign_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda j: (j, 0)),
            pl.BlockSpec((k, d), lambda j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n,), lambda j: (j,)),
            pl.BlockSpec((block_n,), lambda j: (j,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
        ],
        interpret=interpret,
    )(points, centers)
