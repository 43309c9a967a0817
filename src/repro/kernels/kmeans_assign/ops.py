"""Jit'd wrapper for the k-means assignment kernel."""

from functools import partial

import jax

from repro.kernels.kmeans_assign.kernel import kmeans_assign_blocked


@partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_assign(points, centers, *, block_n: int = 1024, interpret=None):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return kmeans_assign_blocked(points, centers, block_n=block_n, interpret=interpret)
