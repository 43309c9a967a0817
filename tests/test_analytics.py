"""The paper's four applications: threads == reference, traffic accounting."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analytics import kmeans, logreg, nmf, pagerank
from repro.core import AccumMode, Session
from repro.data import (kmeans_dataset, logreg_dataset, nmf_dataset, powerlaw_graph,
                        rmat_graph)
from repro.utils.hlo import op_scopes


def test_logreg_threads_match_reference():
    x, y, _ = logreg_dataset(400, 24, seed=0)
    ref = logreg.fit_reference(x, y, iters=10, lr=1e-3)
    th, store, accu = logreg.fit_threads(x, y, n_nodes=2, threads_per_node=2,
                                         iters=10, lr=1e-3)
    np.testing.assert_allclose(th, ref, rtol=1e-4, atol=1e-5)
    assert accu.bytes_transferred == (4 + 1) * 24 * 10   # (N+1)·V per round
    assert logreg.loss(th, x, y) < logreg.loss(np.zeros(24, np.float32), x, y)


def test_logreg_gather_all_traffic_is_higher():
    x, y, _ = logreg_dataset(200, 16, seed=1)
    _, _, naive = logreg.fit_threads(x, y, n_nodes=2, threads_per_node=2,
                                     iters=5, mode=AccumMode.GATHER_ALL)
    _, _, rs = logreg.fit_threads(x, y, n_nodes=2, threads_per_node=2,
                                  iters=5, mode=AccumMode.REDUCE_SCATTER)
    assert naive.bytes_transferred == (2 * 4 + 1) * 16 * 5
    assert rs.bytes_transferred == (4 + 1) * 16 * 5


def test_kmeans_threads_match_reference():
    x, _, _ = kmeans_dataset(600, 8, 5, seed=1)
    cr = kmeans.fit_reference(x, 5, iters=8, seed=1)
    ct, _, _ = kmeans.fit_threads(x, 5, n_nodes=2, threads_per_node=2, iters=8, seed=1)
    np.testing.assert_allclose(np.sort(ct, axis=0), np.sort(cr, axis=0),
                               rtol=1e-3, atol=1e-3)


def test_kmeans_kernel_path():
    x, _, _ = kmeans_dataset(300, 8, 4, seed=2)
    cr = kmeans.fit_reference(x, 4, iters=5, seed=2)
    ck, _, _ = kmeans.fit_threads(x, 4, n_nodes=1, threads_per_node=2, iters=5,
                                  seed=2, use_kernel=True)
    np.testing.assert_allclose(np.sort(ck, axis=0), np.sort(cr, axis=0),
                               rtol=1e-3, atol=1e-3)


def test_nmf_threads_match_reference():
    r, _, _ = nmf_dataset(120, 32, 4, seed=2)
    pr, qr = nmf.fit_reference(r, 4, iters=10, seed=2)
    pt, qt, _, _ = nmf.fit_threads(r, 4, n_nodes=2, threads_per_node=2,
                                   iters=10, seed=2)
    np.testing.assert_allclose(nmf.frob_loss(r, pt, qt), nmf.frob_loss(r, pr, qr),
                               rtol=1e-2)


def test_pagerank_threads_match_reference():
    edges = powerlaw_graph(300, 5, seed=3)
    rr = pagerank.fit_reference(edges, 300, iters=10)
    rt, _, accu = pagerank.fit_threads(edges, 300, n_nodes=2, threads_per_node=2,
                                       iters=10, mode=AccumMode.AUTO)
    np.testing.assert_allclose(rt, rr, rtol=1e-4, atol=1e-6)
    assert abs(float(np.sum(rr)) - 1.0) < 0.05  # ranks ≈ distribution


def _repeats_and_self_loops(n_vertices):
    """Every vertex loops to itself twice and sends one edge three times."""
    v = np.arange(n_vertices, dtype=np.int32)
    loops = np.stack([v, v], axis=1)
    out = np.stack([v, (v * 7 + 3) % n_vertices], axis=1)
    return np.concatenate([loops, out, loops, out, out])


def _sinks(n_vertices):
    """Only the even vertices send edges: the odd half has no out-edges."""
    rng = np.random.default_rng(11)
    src = 2 * rng.integers(0, n_vertices // 2, size=6 * n_vertices)
    dst = rng.integers(0, n_vertices, size=src.size)
    return np.stack([src, dst], axis=1).astype(np.int32)


GRAPHS = {
    "powerlaw": lambda n: powerlaw_graph(n, 8, seed=5),
    "rmat": lambda n: rmat_graph(n, 16 * n, seed=6),
    "self_loops_and_repeats": _repeats_and_self_loops,
    "sinks": _sinks,
}


def _few_targets(n_vertices):
    """Every edge goes into one of eight vertices: most have no in-edges."""
    rng = np.random.default_rng(12)
    src = rng.integers(0, n_vertices, size=3 * n_vertices)
    dst = rng.choice(n_vertices, size=8, replace=False)[rng.integers(0, 8, size=src.size)]
    return np.stack([src, dst], axis=1).astype(np.int32)


def _one_target(n_vertices):
    """Every edge goes into one vertex: its run crosses every row."""
    src = np.random.default_rng(13).integers(0, n_vertices, size=5 * n_vertices)
    return np.stack([src, np.full_like(src, 7)], axis=1).astype(np.int32)


def _random_edges(n_edges, seed):
    def make(n_vertices):
        rng = np.random.default_rng(seed)
        return rng.integers(0, n_vertices, size=(n_edges, 2)).astype(np.int32)
    return make


def _first_and_last(n_vertices):
    """Edges only into vertex 0 and vertex V-1, from vertex 0 and V-1."""
    ends = np.array([0, n_vertices - 1], np.int32)
    return np.stack(np.meshgrid(ends, ends), axis=-1).reshape(-1, 2).repeat(3, axis=0)


# credit sums at the edges of the destination order's rows and runs
EDGE_CASES = {
    "few_targets": _few_targets,
    "one_target": _one_target,
    "ragged_last_row": _random_edges(3 * pagerank.LANES + 5, 14),
    "less_than_a_row": _random_edges(37, 15),
    "first_and_last_vertex": _first_and_last,
    "single_edge": lambda n: np.array([[3, n - 2]], np.int32),
}


def _credit_inputs(graph, n_vertices=512):
    edges = {**GRAPHS, **EDGE_CASES}[graph](n_vertices)
    src, dst = jnp.asarray(edges[:, 0]), jnp.asarray(edges[:, 1])
    out_deg = jnp.maximum(jnp.zeros(n_vertices).at[src].add(1.0), 1.0)
    ranks = jnp.asarray(np.random.default_rng(7).random(n_vertices), jnp.float32)
    return src, dst, ranks / ranks.sum(), out_deg, n_vertices


@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_credits_equal_the_per_edge_division_bit_for_bit(graph):
    """The gather half of the credits: dividing per vertex and gathering once
    gives each edge, in destination order, the same f32 quotient as gathering
    both operands and dividing per edge."""
    src, dst, ranks, out_deg, n = _credit_inputs(graph)
    if graph == "sinks":
        assert int(jnp.sum(jnp.zeros(n).at[src].add(1.0) == 0)) >= n // 2
    by_src, _, _ = pagerank._by_dst(src, dst, n)
    two_gathers = jax.jit(lambda s, r, o: r[s] / o[s])(by_src, ranks, out_deg)
    jitted = jax.jit(pagerank._shares)(by_src, ranks, out_deg)
    eager = pagerank._shares(by_src, ranks, out_deg)     # the host backend's form
    assert jitted.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(jitted), np.asarray(two_gathers))
    np.testing.assert_array_equal(np.asarray(eager), np.asarray(two_gathers))


@pytest.mark.parametrize("graph", sorted(GRAPHS) + sorted(EDGE_CASES))
def test_credits_sum_each_vertex_in_edges(graph):
    """Each credit is the sum of its own in-edges' shares, and exactly 0 with
    none.  The sum is a tree: at most log2(LANES) levels within a row,
    ceil(log2(rows)) across rows and one to join them; a tree of depth d
    rounds to within d·eps/2 of the exact sum of its non-negative terms
    (at most 7 ulps of the largest credit at these sizes)."""
    src, dst, ranks, out_deg, n = _credit_inputs(graph)
    by_src, reach, ends = pagerank._by_dst(src, dst, n)
    got = np.asarray(pagerank._credits(by_src, reach, ranks, out_deg, ends))
    shares = np.asarray(ranks / out_deg, np.float64)[np.asarray(src)]
    want = np.bincount(np.asarray(dst), weights=shares, minlength=n)
    rows = reach.shape[0]
    depth = np.log2(pagerank.LANES) + np.ceil(np.log2(rows)) + 1
    eps = np.finfo(np.float32).eps
    assert got.dtype == np.float32 and got.shape == (n,)
    np.testing.assert_array_equal(got[want == 0], 0.0)
    assert np.max(np.abs(got - want)) <= depth * eps / 2 * np.max(want)


def test_credits_hold_one_gather():
    """The compiled round reads each edge once (one gather with an output of
    one value per edge, padded to whole rows), reads each vertex's credit at
    its run's end (gathers of one value per vertex), and sorts and scatters
    nothing."""
    src, dst, ranks, out_deg, n = _credit_inputs("powerlaw")
    by_src, reach, ends = pagerank._by_dst(src, dst, n)
    text = pagerank._credits.lower(by_src, reach, ranks, out_deg, ends).compile().as_text()
    sizes = [int(np.prod([int(d) for d in m.group(1).split(",") if d]))
             for m in re.finditer(r"= \w+\[([0-9,]*)\]\S* gather\(", text)]
    assert sorted(sizes)[-1] == by_src.size >= len(src)
    assert sorted(sizes)[:-1] and set(sorted(sizes)[:-1]) == {n}, sizes
    assert not re.search(r"\b(sort|scatter)\(", text)


@pytest.mark.parametrize("graph", sorted(GRAPHS))
@pytest.mark.parametrize("backend", ["spmd", "host"])
def test_pagerank_fit_matches_reference(backend, graph):
    """``fit`` on the SPMD backend and on the host backend's 2x2 threads
    agrees with ``fit_reference``, which scatter-adds edge by edge."""
    n = 512
    edges = GRAPHS[graph](n)
    ref = pagerank.fit_reference(edges, n, iters=6)
    ranks, _ = pagerank.fit(edges, n, iters=6, backend=backend, n_nodes=2,
                            threads_per_node=2)
    np.testing.assert_allclose(ranks, ref, rtol=1e-5, atol=1e-7)


def _spmd_program(n_vertices, edges, iters=3):
    """The compiled SPMD program ``fit`` runs, lowered through ``Session.lower``."""
    sess = Session(backend="spmd")
    programs = []

    def lower_instead(thread_proc, *, data=(), broadcast=(), timeout=None):
        lowered = sess.lower(thread_proc, data=data, broadcast=broadcast)
        programs.append(lowered.compile().as_text())
        return []
    sess.run = lower_instead
    pagerank.fit(edges, n_vertices, iters=iters, session=sess)
    (text,) = programs
    return text


def test_spmd_pagerank_program_gathers_once_per_edge():
    """The SPMD program ``fit`` runs: one gather under ``pagerank.gather``,
    and one device op there with an f32 output of one value per edge (the
    edges padded to whole rows of the destination order)."""
    n_vertices, edges = 256, powerlaw_graph(256, 16, seed=2)
    text = _spmd_program(n_vertices, edges)
    gathers = [line for line in text.splitlines()
               if re.search(r"\bgather\(", line) and "pagerank.gather" in line]
    assert len(gathers) == 1, gathers
    outputs = {m.group(1): (m.group(2), m.group(3)) for m in
               re.finditer(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([0-9,]*)\]", text, re.M)}
    per_edge = -(-len(edges) // pagerank.LANES) * pagerank.LANES
    per_edge = [op for op, scope in op_scopes(text).items()
                if "pagerank.gather" in scope.split("/") and op in outputs
                and outputs[op][0] == "f32"
                and np.prod([int(d) for d in outputs[op][1].split(",")]) == per_edge]
    assert len(per_edge) == 1, per_edge


def test_spmd_pagerank_sorts_once_per_job_and_never_in_a_round():
    """In the SPMD program ``fit`` runs, the one sort is the destination
    order's, outside the rounds' loop, and PageRank's own part of a round
    holds no sort and no scatter (the accumulator's sparse branch scatters
    into the pairs it receives, and is not PageRank's)."""
    text = _spmd_program(256, powerlaw_graph(256, 16, seed=2))
    scope = {}
    for line in text.splitlines():
        if re.search(r"\b(sort|scatter)\(", line):
            scope[line] = re.search(r'op_name="([^"]*)"', line).group(1).split("/")
    sorts = [s for line, s in scope.items() if re.search(r"\bsort\(", line)]
    assert len(sorts) == 1 and "pagerank.by_dst" in sorts[0], sorts
    assert "while" not in sorts[0] and "pagerank.round" not in sorts[0]
    in_round = [s for s in scope.values() if "pagerank.round" in s]
    assert all(any(p.startswith("accumulate.") for p in s) for s in in_round), in_round
    assert not [s for s in scope.values()
                if {"pagerank.gather", "pagerank.scatter"} & set(s)]


def test_deprecated_shims_warn_and_stay_correct():
    """fit_threads / fit_spmd are shims: they must warn DeprecationWarning AND
    still return the same results as the fit() they forward to."""
    from repro.core.compat import make_mesh
    mesh1 = make_mesh((1,), ("data",))

    x, y, _ = logreg_dataset(200, 16, seed=5)
    ref_lr = logreg.fit_reference(x, y, iters=6, lr=1e-3)
    with pytest.warns(DeprecationWarning, match="logreg.fit_threads"):
        th, store, accu = logreg.fit_threads(x, y, n_nodes=2, threads_per_node=2,
                                             iters=6, lr=1e-3)
    np.testing.assert_allclose(th, ref_lr, rtol=1e-4, atol=1e-5)
    assert accu.rounds == 6
    with pytest.warns(DeprecationWarning, match="logreg.fit_spmd"):
        th_s = logreg.fit_spmd(x, y, mesh1, iters=6, lr=1e-3)
    np.testing.assert_allclose(th_s, ref_lr, rtol=1e-4, atol=1e-5)

    xk, _, _ = kmeans_dataset(300, 8, 4, seed=6)
    ref_km = kmeans.fit_reference(xk, 4, iters=5, seed=6)
    with pytest.warns(DeprecationWarning, match="kmeans.fit_threads"):
        ck, _, _ = kmeans.fit_threads(xk, 4, n_nodes=2, threads_per_node=2,
                                      iters=5, seed=6)
    np.testing.assert_allclose(np.sort(ck, axis=0), np.sort(ref_km, axis=0),
                               rtol=1e-3, atol=1e-3)
    with pytest.warns(DeprecationWarning, match="kmeans.fit_spmd"):
        cs = kmeans.fit_spmd(xk, 4, mesh1, iters=5, seed=6)
    np.testing.assert_allclose(np.sort(cs, axis=0), np.sort(ref_km, axis=0),
                               rtol=1e-3, atol=1e-3)


def test_logreg_ssp_async_converges():
    """Bounded-staleness async training reaches the same loss ballpark as sync."""
    x, y, _ = logreg_dataset(400, 16, seed=4)
    ref = logreg.fit_reference(x, y, iters=12, lr=1e-3)
    ssp, clock = logreg.fit_ssp(x, y, n_workers=4, staleness=1, iters=12, lr=1e-3)
    l_ref, l_ssp = logreg.loss(ref, x, y), logreg.loss(ssp, x, y)
    assert l_ssp < l_ref * 1.5 + 0.05  # async: same ballpark, not bitwise
    # staleness=0 degenerates to sync (every worker waits each tick)
    sync0, clock0 = logreg.fit_ssp(x, y, n_workers=2, staleness=0, iters=5, lr=1e-3)
    assert np.all(np.isfinite(sync0))
