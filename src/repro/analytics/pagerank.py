"""PageRank (paper §6.7) on the Session facade: edge-partitioned credits.

Each thread owns a slice of the edge list; per iteration it computes the
credit vector its sources send along their out-edges and accumulates it
(the paper: "communication cost is proportional to the number of vertices",
because the accumulator ships V-length vectors, not per-edge messages as
Husky does).  The accumulator's ``sparse``/``auto`` modes engage when the
per-thread credit vector is sparse — graphs with concentrated out-degrees.
One ``thread_proc`` serves both the host and SPMD backends; the out-degree
vector rides along replicated (``broadcast=``).  Each thread sorts its edges
by destination once per job (``_by_dst``); a round then gathers each edge's
share and sums every vertex's contiguous run of in-edges (``_credits``),
with no sort and no scatter.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import AccumMode, Session
from repro.core.session import SpmdBackend, deprecated_entry

DAMPING = 0.85
LANES = 128   # width of a row of the segmented sum: a TPU vector register's lanes


class RunEnds(NamedTuple):
    """Where each vertex's run of in-edges ends in a thread's destination
    order, cut into rows of :data:`LANES`."""
    row: jax.Array        # [V] int32: the row of the vertex's last in-edge
    col: jax.Array        # [V] int32: its column
    has_in: jax.Array     # [V] bool: the vertex has in-edges


def _shift(x, by: int, axis: int):
    """``x`` moved ``by`` places up ``axis`` (down if negative), zeros shifted in."""
    pads = [(0, 0, 0)] * x.ndim
    pads[axis] = (by, -by, 0)
    return jax.lax.pad(x, jnp.zeros((), x.dtype), pads)


def _since(starts, axis: int):
    """How many places back along ``axis`` the last True of ``starts`` at or
    before each element lies (the element's own index where there is none)."""
    idx = jax.lax.broadcasted_iota(jnp.int32, starts.shape, axis)
    return idx - jax.lax.cummax(jnp.where(starts, idx, 0), axis=axis)


def _pack_left(marks):
    """For each row of 0/1 ``marks``, the column of its k-th marker, at
    column k (-1 past its last).  Each marker moves left by the number of
    unmarked places before it, in log-step shifts by the bits of that
    distance, low bit first: the markers keep their order and never land on
    one another."""
    col = jax.lax.broadcasted_iota(jnp.int32, marks.shape, 1)
    dist = jnp.where(marks == 1, col + 1 - jnp.cumsum(marks, axis=1), 0)
    at = jnp.where(marks == 1, col, -1)
    step = 1
    while step < marks.shape[1]:
        go = (dist & step) != 0
        come = _shift(go, -step, 1)
        at = jnp.where(come, _shift(at, -step, 1), jnp.where(go, -1, at))
        dist = jnp.where(come, _shift(dist, -step, 1), jnp.where(go, 0, dist))
        step *= 2
    return at


def _run_sums(x, reach, axis: int):
    """Inclusive sums within runs along ``axis``: ``x[i]`` becomes the sum of
    ``x`` over ``[i - reach[i], i]`` (clipped at 0), by log-step shifted adds
    (Hillis and Steele), each step one elementwise pass.  A run of ``n``
    values is summed as a tree of depth ``ceil(log2 n)``: no value outside
    the run is added."""
    step = 1
    while step < x.shape[axis]:
        x = jnp.where(reach >= step, x + _shift(x, step, axis), x)
        step *= 2
    return x


@partial(jax.jit, static_argnums=2)
def _by_dst(src, dst, n_vertices: int):
    """The once-per-job destination order of one thread's edges: the sources
    in destination order, ``[rows, LANES]`` int32 (0 past the last edge);
    each edge's reach, ``[rows, LANES]`` uint8: how many places back in its
    row its run of equal destinations starts, or its column + 1 where the
    run starts in an earlier row; and the :class:`RunEnds`.

    One ``lax.sort`` of two rows: the edges by destination, with their
    sources, and the destinations merged with one marker per vertex, each
    after its vertex's in-edges.  The n-th marker sits at ``n`` plus the
    number of edges into vertices up to ``n``: the end of vertex n's run.
    The markers are found row by row: a count per row, a scatter of one
    value per row (which row holds the n-th marker), and each row's markers
    packed to its front (:func:`_pack_left`), so no step scatters or
    searches per edge or per vertex."""
    if n_vertices >= 1 << 30:
        raise ValueError(f"{n_vertices} vertices: keys 2*v+1 must fit int32")
    with jax.named_scope("pagerank.by_dst"):
        e, v = src.shape[0], n_vertices
        ids = jnp.arange(v, dtype=jnp.int32)
        keys = jnp.stack([jnp.concatenate([2 * dst, 2 * ids + 1]),       # merged
                          jnp.concatenate([2 * dst, jnp.full((v,), 2 * v)])])  # edges
        vals = jnp.broadcast_to(jnp.concatenate([src, jnp.zeros((v,), jnp.int32)]),
                                keys.shape)     # read in the edges' row only
        keys, vals = jax.lax.sort((keys, vals), dimension=1, num_keys=1,
                                  is_stable=False)

        # edges into vertices 0..n, for each n: where the markers sit
        n = keys.shape[1]
        rows0 = -(-n // LANES)
        marks = jnp.pad(keys[0] & 1, (0, rows0 * LANES - n)).reshape(rows0, LANES)
        seen = jnp.cumsum(marks, axis=1)          # markers up to each column
        through = jnp.cumsum(seen[:, -1])         # markers up to each row's end
        row_of = jnp.cumsum(jnp.zeros((v + 1,), jnp.int32).at[through].add(
            1, indices_are_sorted=True))[:v]
        kth = ids - (through - seen[:, -1])[row_of]
        upto = row_of * LANES + _pack_left(marks)[row_of, kth] - ids

        # the edges alone, in rows; the padding is a run of its own (vertex v)
        rows = max(1, -(-e // LANES))
        pad = rows * LANES - e
        key = jnp.pad(keys[1, :e] >> 1, (0, pad), constant_values=v).reshape(rows, LANES)
        lane = jax.lax.broadcasted_iota(jnp.int32, key.shape, 1)
        reach = _since((key != _shift(key, 1, 1)) | (lane == 0), 1)
        before = jnp.concatenate([jnp.full((1,), -1), key[:-1, -1]]) == key[:, 0]
        reach = jnp.where((reach == lane) & before[:, None], lane + 1, reach)
        end = jnp.maximum(upto - 1, 0)
        row, col = end // LANES, end % LANES
        has_in = upto > jnp.concatenate([jnp.zeros((1,), jnp.int32), upto[:-1]])
        return (jnp.pad(vals[1, :e], (0, pad)).reshape(rows, LANES),
                reach.astype(jnp.uint8), RunEnds(row, col, has_in))


def _shares(src, ranks, out_deg):
    """Each edge's share of its source's rank.  Divided per vertex, then
    gathered once per edge: ``(ranks / out_deg)[src]`` is
    ``ranks[src] / out_deg[src]`` bit for bit (the same operands through the
    same f32 divide) at V divides and one E-length gather."""
    return (ranks / out_deg)[src]


@jax.jit
def _credits(src, reach, ranks, out_deg, ends: RunEnds):
    """Credit vector contributed by this thread's edges, over the order
    :func:`_by_dst` built: the gather of each edge's share of its source's
    rank, then each vertex's sum over its own run of in-edges.  Each half is
    a ``jax.named_scope``, which names its ops in a device profile.

    The sum needs no sort and no scatter: a segmented sum within each row
    (:func:`_run_sums`), the same over the rows' last runs, added to the
    runs that continue from an earlier row, then one read per vertex at its
    run's end.  Each credit is an f32 sum of its own in-edges only; a vertex
    with none gets 0."""
    with jax.named_scope("pagerank.gather"):
        w = _shares(src, ranks, out_deg)
    with jax.named_scope("pagerank.scatter"):
        w = _run_sums(w, reach, 1)
        across = _run_sums(w[:, -1], _since(reach[:, -1] < LANES, 0), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
        w = jnp.where(reach > lane, w + _shift(across, 1, 0)[:, None], w)
        return jnp.where(ends.has_in, w[ends.row, ends.col], 0.0)


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
        credits = jnp.zeros((n_vertices,), jnp.float32).at[dst].add((ranks / out_deg)[src])
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
        src, reach, ends = _by_dst(edges_loc[:, 0], edges_loc[:, 1], n_vertices)

        def step(_):                       # the shared ranks carry the state
            with ctx.span("pagerank.round"):
                total = credits.accumulate(
                    _credits(src, reach, ranks.get(), deg, ends), mode=mode)
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
