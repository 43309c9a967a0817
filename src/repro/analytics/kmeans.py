"""K-means (paper §6.5) on the Session facade: Lloyd iterations, shared centers.

Per iteration, each thread assigns its points to the nearest center (the
``kmeans_assign`` Pallas kernel is the TPU hot loop), builds per-cluster
partial sums + counts, and ships them through the accumulator — the shared
centers in DSM are then ``sum / count``.  One ``thread_proc`` serves both the
host backend (DThreadPool + DAddAccumulator, the paper's programming model)
and the SPMD backend (shard_map over a mesh, the production path).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import AccumMode, Session
from repro.core.session import SpmdBackend, deprecated_entry


# f32 matmuls at full precision: a TPU's default rounds their inputs to bf16,
# which moves points across cluster boundaries and the centres with them
HIGHEST = jax.lax.Precision.HIGHEST


@jax.jit
def _assign(points, centers):
    d2 = (jnp.sum(points**2, axis=1, keepdims=True)
          - 2.0 * jnp.matmul(points, centers.T, precision=HIGHEST)
          + jnp.sum(centers**2, axis=1)[None])
    return jnp.argmin(d2, axis=1), jnp.min(d2, axis=1)


def _partials(points, assign, k):
    onehot = jax.nn.one_hot(assign, k, dtype=jnp.float32)      # (n, k)
    sums = jnp.matmul(onehot.T, points, precision=HIGHEST)      # (k, d)
    counts = jnp.sum(onehot, axis=0)                            # (k,)
    return sums, counts


def inertia(points, centers) -> float:
    _, d = _assign(jnp.asarray(points), jnp.asarray(centers))
    return float(jnp.sum(d))


def fit_reference(x, k: int, iters: int = 10, seed: int = 0):
    rng = np.random.default_rng(seed)
    centers = jnp.asarray(x[rng.choice(x.shape[0], k, replace=False)])
    xj = jnp.asarray(x)
    for _ in range(iters):
        a, _ = _assign(xj, centers)
        sums, counts = _partials(xj, a, k)
        centers = sums / jnp.maximum(counts[:, None], 1.0)
    return np.asarray(centers)


def fit(x, k: int, *, iters: int = 10, seed: int = 0,
        mode: Optional[AccumMode | str] = None, use_kernel: bool = False,
        session: Optional[Session] = None, backend: str = "host",
        n_nodes: int = 2, threads_per_node: int = 2, mesh=None):
    """Lloyd iterations through the Table-1 facade; backend-agnostic.

    Returns ``(centers, session)``.
    """
    sess = session or Session(backend=backend, n_nodes=n_nodes,
                              threads_per_node=threads_per_node, mesh=mesh)
    rng = np.random.default_rng(seed)
    d = x.shape[1]
    centers = sess.def_global(
        "centers", jnp.asarray(x[rng.choice(x.shape[0], k, replace=False)]))
    partials = sess.new_array("partials", (k * (d + 1),))

    if use_kernel:
        from repro.kernels.kmeans_assign.ops import kmeans_assign as assign_fn
    else:
        assign_fn = _assign

    def thread_proc(ctx, pts):
        def step(_):                       # the shared centers carry the state
            with ctx.span("kmeans.round"):
                a, _dist = assign_fn(pts, centers.get())
                sums, counts = _partials(pts, a, k)
                flat = partials.accumulate(
                    jnp.concatenate([sums.reshape(-1), counts]), mode=mode)
                sums_g = flat[: k * d].reshape(k, d)
                counts_g = flat[k * d:]
                # §4.5 pattern: every thread re-derives the identical center update
                centers.set(sums_g / jnp.maximum(counts_g[:, None], 1.0))
            return _
        ctx.iterate(step, None, iters)
        return None

    sess.run(thread_proc, data=(jnp.asarray(x),))
    return np.asarray(centers.get()), sess


# ---------------------------------------------------------------------------
# Deprecated pre-Session entry points
# ---------------------------------------------------------------------------


def fit_threads(x, k: int, *, n_nodes: int = 2, threads_per_node: int = 2,
                iters: int = 10, seed: int = 0,
                mode: AccumMode | str = AccumMode.REDUCE_SCATTER,
                use_kernel: bool = False):
    """Deprecated shim: ``fit(backend="host")`` with the old return tuple."""
    deprecated_entry("kmeans.fit_threads", 'kmeans.fit(backend="host")')
    sess = Session(backend="host", n_nodes=n_nodes,
                   threads_per_node=threads_per_node, accum_mode=mode)
    centers, sess = fit(x, k, iters=iters, seed=seed, mode=mode,
                        use_kernel=use_kernel, session=sess)
    return centers, sess.store, sess.accumulator("partials")


def fit_spmd(x, k: int, mesh, *, iters: int = 10, seed: int = 0,
             mode: AccumMode | str = AccumMode.REDUCE_SCATTER):
    """Deprecated shim: ``fit(backend="spmd")``."""
    deprecated_entry("kmeans.fit_spmd", 'kmeans.fit(backend="spmd")')
    sess = Session(backend=SpmdBackend(mesh=mesh))
    centers, _ = fit(x, k, iters=iters, seed=seed, mode=mode, session=sess)
    return centers
