"""PageRank (paper §6.7) on the Session facade: edge-partitioned credits.

Each thread owns a slice of the edge list; per iteration it computes the
credit vector its sources send along their out-edges and accumulates it
(the paper: "communication cost is proportional to the number of vertices",
because the accumulator ships V-length vectors, not per-edge messages as
Husky does).  The accumulator's ``sparse``/``auto`` modes engage when the
per-thread credit vector is sparse — graphs with concentrated out-degrees.
One ``thread_proc`` serves both the host and SPMD backends; the out-degree
vector rides along replicated (``broadcast=``).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import AccumMode, Session
from repro.core.session import SpmdBackend, deprecated_entry

DAMPING = 0.85


def _credits(src, dst, ranks, out_deg, n_vertices):
    """Credit vector contributed by this thread's edges: the gather of each
    edge's share of its source's rank, then the scatter-add of the shares
    into their destinations.  Each half is a ``jax.named_scope``, which
    names its ops in a device profile.

    The share is divided per vertex, then gathered once per edge:
    ``(ranks / out_deg)[src]`` is ``ranks[src] / out_deg[src]`` bit for bit
    (the same operands through the same f32 divide) at V divides and one
    E-length gather."""
    with jax.named_scope("pagerank.gather"):
        w = (ranks / out_deg)[src]
    with jax.named_scope("pagerank.scatter"):
        return jnp.zeros((n_vertices,), jnp.float32).at[dst].add(w)


@partial(jax.jit, static_argnums=1)
def _out_degree(edges, n_vertices: int):
    """Every vertex's out-degree, at least 1, in f32: ``fit``'s prologue."""
    with jax.named_scope("pagerank.out_degree"):
        return jnp.maximum(jnp.zeros(n_vertices).at[edges[:, 0]].add(1.0), 1.0)


def fit_reference(edges, n_vertices: int, iters: int = 10):
    src, dst = jnp.asarray(edges[:, 0]), jnp.asarray(edges[:, 1])
    out_deg = jnp.maximum(jnp.zeros(n_vertices).at[src].add(1.0), 1.0)
    ranks = jnp.full((n_vertices,), 1.0 / n_vertices)
    for _ in range(iters):
        credits = _credits(src, dst, ranks, out_deg, n_vertices)
        ranks = (1 - DAMPING) / n_vertices + DAMPING * credits
    return np.asarray(ranks)


def fit(edges, n_vertices: int, *, iters: int = 10,
        mode: Optional[AccumMode | str] = AccumMode.AUTO, k: Optional[int] = None,
        session: Optional[Session] = None, backend: str = "host",
        n_nodes: int = 2, threads_per_node: int = 2, mesh=None):
    """Credit accumulation through the Table-1 facade; backend-agnostic.

    ``mode="auto"`` ships (index, value) pairs only on rounds where every
    thread's credit vector compresses losslessly under the budget ``k``
    (default ~V/4) — identical results either way, cheaper wire format when
    out-degrees concentrate.  ``k`` becomes the credits ref's declared budget.
    Returns ``(ranks, session)``.
    """
    sess = session or Session(backend=backend, n_nodes=n_nodes,
                              threads_per_node=threads_per_node, mesh=mesh)
    out_deg = _out_degree(jnp.asarray(edges), n_vertices)
    ranks = sess.def_global("ranks", jnp.full((n_vertices,), 1.0 / n_vertices))
    credits = sess.new_array("credits", (n_vertices,), sparse_k=k)

    def thread_proc(ctx, edges_loc, deg):
        src, dst = edges_loc[:, 0], edges_loc[:, 1]

        def step(_):                       # the shared ranks carry the state
            with ctx.span("pagerank.round"):
                total = credits.accumulate(
                    _credits(src, dst, ranks.get(), deg, n_vertices), mode=mode)
                ranks.set((1 - DAMPING) / n_vertices + DAMPING * total)
            return _
        ctx.iterate(step, None, iters)
        return None

    sess.run(thread_proc, data=(jnp.asarray(edges),), broadcast=(out_deg,))
    return np.asarray(ranks.get()), sess


# ---------------------------------------------------------------------------
# Deprecated pre-Session entry points
# ---------------------------------------------------------------------------


def fit_threads(edges, n_vertices: int, *, n_nodes: int = 2,
                threads_per_node: int = 2, iters: int = 10,
                mode: AccumMode | str = AccumMode.AUTO):
    """Deprecated shim: ``fit(backend="host")`` with the old return tuple."""
    deprecated_entry("pagerank.fit_threads", 'pagerank.fit(backend="host")')
    sess = Session(backend="host", n_nodes=n_nodes,
                   threads_per_node=threads_per_node, accum_mode=mode)
    ranks, sess = fit(edges, n_vertices, iters=iters, mode=mode, session=sess)
    return ranks, sess.store, sess.accumulator("credits")


def fit_spmd(edges, n_vertices: int, mesh, *, iters: int = 10,
             mode: AccumMode | str = AccumMode.REDUCE_SCATTER, k: int = 0):
    """Deprecated shim: ``fit(backend="spmd")``."""
    deprecated_entry("pagerank.fit_spmd", 'pagerank.fit(backend="spmd")')
    sess = Session(backend=SpmdBackend(mesh=mesh))
    ranks, _ = fit(edges, n_vertices, iters=iters, mode=mode, k=k or None,
                   session=sess)
    return ranks
