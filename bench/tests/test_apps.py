"""The app adapters at CPU sizes: data generators, compulsory work, and each
plain reference against the program's ``fit`` and against its control."""

import jax
import numpy as np
import pytest

from bench import registry
from bench.run import make_session
from bench.tests.conftest import TINY

def readings(root, name, seed):
    from bench.control import readings as read
    return read(registry.resolve(name, root), seed, jax.devices()[:1], program=True)


@pytest.mark.parametrize("cell", ["pagerank-g500-s22.spmd", "pagerank-g500-s22.host2x2",
                                  "kmeans-covtype.spmd"])
def test_program_agrees_and_control_fails(tiny_root, cell):
    """At a size a test run holds, ``fit`` through Session meets the limit and
    the reference computed one precision step lower does not."""
    r = readings(tiny_root, cell, seed=7)
    for name, limit in r["limits"].items():
        assert r["program"][name] <= limit, r
        assert r["control"][name] > limit, r


def test_reference_imports_nothing_of_the_program():
    """Only ``run_job``, the timed entry, imports the program."""
    import ast
    from bench.tests.conftest import ROOT

    def imports(tree):
        return [n for n in ast.walk(tree) if isinstance(n, (ast.Import, ast.ImportFrom))
                and any(m.startswith("repro") for m in
                        [getattr(n, "module", None) or ""] + [a.name for a in n.names])]

    for path in (ROOT / "bench" / "apps").glob("*.py"):
        tree = ast.parse(path.read_text())
        allowed = {id(n) for f in ast.walk(tree)
                   if isinstance(f, ast.FunctionDef) and f.name == "run_job"
                   for n in imports(f)}
        assert allowed, path
        assert all(id(n) in allowed for n in imports(tree)), path


def test_rmat_quadrants_and_skew_match_the_host_generator():
    from repro.data import rmat_graph
    app = registry.resolve("pagerank-g500-s22.spmd").app
    abc = (0.57, 0.19, 0.19)
    n_vertices, n_edges = 1 << 12, 400_000
    src, dst = app.rmat_bits(jax.random.key(3), scale=12, n_edges=n_edges, abc=abc)
    top_src, top_dst = np.asarray(src) >> 11, np.asarray(dst) >> 11
    quadrants = [np.mean((top_src == i) & (top_dst == j)) for i in (0, 1) for j in (0, 1)]
    np.testing.assert_allclose(quadrants, [0.57, 0.19, 0.19, 0.05], atol=0.005)

    ours = np.asarray(app.rmat_edges(app.seed_key(3), scale=12, n_edges=n_edges, abc=abc))
    theirs = rmat_graph(n_vertices, n_edges, seed=3)
    assert ours.shape == theirs.shape and ours.dtype == theirs.dtype
    assert ours.min() >= 0 and ours.max() < n_vertices

    def busiest(col):
        return np.bincount(col, minlength=n_vertices).max() / n_edges
    for c in (0, 1):
        assert busiest(ours[:, c]) == pytest.approx(busiest(theirs[:, c]), rel=0.35)


def test_data_is_the_seeds():
    app = registry.resolve("pagerank-g500-s22.spmd").app
    big = 2**31 + 12345
    a, b, c = (np.asarray(app.rmat_edges(app.seed_key(s), scale=9, n_edges=1000,
                                         abc=(0.57, 0.19, 0.19))) for s in (big, big, big + 1))
    assert np.array_equal(a, b) and not np.array_equal(a, c)


def test_compulsory_work_on_known_shapes(tiny_root):
    pr = registry.resolve("pagerank-g500-s22.spmd")
    assert pr.app.round_work({"scale": 3, "edgefactor": 16}) == {
        "bytes": 8 * 128 + 12 * 8, "flops": 256}
    w = pr.app.round_work(pr.config)
    assert w["bytes"] == 8 * 67_108_864 + 12 * 4_194_304          # ~0.59 GB
    km = registry.resolve("kmeans-covtype.spmd", tiny_root)
    cfg = {"class_sizes": [400, 600], "n_features": 54, "k": 7}
    assert km.app.kernel_work(cfg, "kmeans_assign") == {
        "bytes": 4 * (1000 * 54 + 7 * 54 + 2000), "flops": 2 * 1000 * 54 * 7}
    assert km.app.round_work(cfg) == {
        "bytes": 4 * (1000 * 54 + 7 * 54 + 2000) + 4 * (1000 * 54 + 1000),
        "flops": 2 * 1000 * 54 * 7 + 1000 * 54}


def test_tiny_sizes_cut_scale_only():
    for name, change in TINY.items():
        for key in change:
            assert not key.endswith(("_dim", "_rank", "features")), key
