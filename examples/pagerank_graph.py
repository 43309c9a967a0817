"""PageRank over a power-law graph (paper §6.7) with the auto accumulator.

Shows the paper's sparse/auto accumulator decision in action: threads owning
edges with concentrated destinations produce sparse credit vectors, and the
``auto`` mode ships (index, value) pairs only when cheaper.  Everything runs
through the Session facade with the iteration written via ``ctx.iterate`` —
swap ``backend="spmd"`` to put the same workload on a device mesh, where the
loop lowers to one ``lax.scan`` instead of unrolling.

    PYTHONPATH=src python examples/pagerank_graph.py
"""

import numpy as np

from repro.analytics import pagerank
from repro.core import AccumMode
from repro.data import powerlaw_graph
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    n_vertices = 2000
    edges = powerlaw_graph(n_vertices, avg_degree=8, seed=0)
    print(f"graph: {n_vertices} vertices, {edges.shape[0]} edges")

    ref = pagerank.fit_reference(edges, n_vertices, iters=15)
    for mode in (AccumMode.GATHER_ALL, AccumMode.REDUCE_SCATTER, AccumMode.AUTO):
        ranks, sess = pagerank.fit(edges, n_vertices, backend="host", n_nodes=2,
                                   threads_per_node=2, iters=15, mode=mode)
        drift = float(np.max(np.abs(ranks - ref)))
        print(f"[{mode.value:>14s}] top vertex {int(np.argmax(ranks))} "
              f"drift {drift:.2e} wire {sess.wire_traffic():>9d} elems")
    print("top-5 ranked vertices:", np.argsort(-ref)[:5].tolist())


if __name__ == "__main__":
    main()
