"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: the installed TPU compiler compiles each kernel for a chip that
is described, not attached, and the program must hold the Mosaic kernel
(``tpu_custom_call``).  Widths are those of the chip smoke test: PageRank's
credit vector at SNAP soc-LiveJournal1's vertex count, a host-backend round
of 4 threads, and k-means at UCI Covertype's shape; and a host-backend
round of sparse logistic regression over 10**6 hashed features.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V = 4_847_571                       # soc-LiveJournal1 vertices
COVTYPE = (581_012, 54)             # UCI Covertype rows x features
K = 7


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A described chip, with the persistent compile cache off: an entry
    compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _topk(method, k_per_block):
    from repro.kernels.topk_compress.ops import topk_compress

    def run(x):
        return topk_compress(x, k_per_block=k_per_block, interpret=False,
                             method=method)
    return run, [((V,), jnp.float32)]


def _fused(v=V):
    from repro.kernels.accumulate.fused_scatter import fused_topk_scatter

    def run(x):
        return fused_topk_scatter(x, per_block=256, block_eff=1024,
                                  interpret=False)
    return run, [((4, v), jnp.float32)]


def _kmeans():
    from repro.kernels.kmeans_assign.ops import kmeans_assign

    def run(points, centers):
        return kmeans_assign(points, centers, interpret=False)
    return run, [(COVTYPE, jnp.float32), ((K, COVTYPE[1]), jnp.float32)]


CASES = {
    "topk_argmax_k16": lambda: _topk("argmax", 16),
    "topk_bitonic_k256": lambda: _topk("bitonic", 256),
    "fused_topk_scatter_4xV": _fused,
    # a sparse logistic regression round at 10**6 hashed features: 977
    # blocks, 576 valid lanes in the last
    "fused_topk_scatter_4x1M": lambda: _fused(1_000_000),
    "kmeans_assign_covtype": _kmeans,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def pagerank_round(one_chip):
    """``_credits`` compiled for a described v5e on a Graph 500 SCALE 20 graph
    (edgefactor 16), over the destination order ``_by_dst`` builds: the
    compiled text, V and the padded edge count."""
    from repro.analytics import pagerank

    n_vertices, n_edges = 1 << 20, 1 << 24
    edge = jax.ShapeDtypeStruct((n_edges,), jnp.int32)
    order = jax.eval_shape(lambda s, d: pagerank._by_dst(s, d, n_vertices), edge, edge)
    src, reach, ends = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), order)
    vec = jax.ShapeDtypeStruct((n_vertices,), jnp.float32, sharding=one_chip)
    text = pagerank._credits.lower(src, reach, vec, vec, ends).compile().as_text()
    return text, n_vertices, src.size


def test_pagerank_credits_divide_per_vertex_on_v5e(pagerank_round):
    """The round's gather half for a described v5e at SCALE 20 (SCALE 22's
    destination sort takes minutes to compile here): the divide is a
    V-length op of its own, and the one E-length gather reads its result and
    divides nothing per edge."""
    from repro.utils.hlo import op_scopes

    text, n_vertices, n_edges = pagerank_round
    ops = {m.group(1): (m.group(2), m.group(3)) for m in re.finditer(
        r"^\s*(?:ROOT )?%(\S+) = (f32\[\d+\])\S* (\w+)\(", text, re.M)}
    scoped = {op: ops[op] for op, scope in op_scopes(text).items()
              if op in ops and "pagerank.gather" in scope.split("/")}
    assert sorted(scoped.values()) == [(f"f32[{n_vertices}]", "divide"),
                                       (f"f32[{n_edges}]", "fusion")], scoped
    (gather,) = [op for op, (shape, _) in scoped.items() if shape == f"f32[{n_edges}]"]
    body = re.search(rf"%{re.escape(gather)} = .*?calls=%([\w.\-]+)", text).group(1)
    start = text.index(f"%{body} ")
    fused = text[start:text.index("\n}", start)]
    assert " gather(" in fused and " divide(" not in fused


def test_pagerank_round_sorts_and_scatters_nothing_on_v5e(pagerank_round):
    """The round compiled for a described v5e at SCALE 20 sums each vertex's
    run of in-edges without a sort or a scatter: the destination order is
    built once per job, outside the round."""
    text, _, _ = pagerank_round
    assert " gather(" in text
    assert not re.search(r"\b(sort|scatter)\(", text)


def test_pagerank_destination_order_sorts_once_on_v5e(one_chip):
    """``_by_dst`` for a described v5e on the Graph 500 SCALE 22 graph of the
    benchmark: one sort, the destination order's own.  The per-row scatter
    that finds the markers' rows is told its indices are sorted; untold, XLA
    sorts its 557,056 updates first at this size (not at SCALE 20)."""
    from repro.analytics import pagerank

    n_vertices, n_edges = 1 << 22, 1 << 26
    edge = jax.ShapeDtypeStruct((n_edges,), jnp.int32, sharding=one_chip)
    text = pagerank._by_dst.lower(edge, edge, n_vertices).compile().as_text()
    sorts = [line for line in text.splitlines() if re.search(r"\bsort\(", line)]
    assert len(sorts) == 1 and "pagerank.by_dst" in sorts[0], sorts
