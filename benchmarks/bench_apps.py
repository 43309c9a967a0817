"""Paper Figs. 4–10: the four applications vs iterations and thread counts.

GENE/LRS/KMS/FOREST/NMFS/LJ-scale datasets are shrunk to CPU-bench size but
keep the papers' sweep structure: running time vs #iterations and vs
#threads, per application.  All workloads iterate via ``ctx.iterate`` through
the `step.Session` facade — host backend for the paper sweeps, plus an SPMD
sweep where the loop lowers to one ``lax.scan`` (so rising iters should cost
runtime, not compile time).  The derived column records the sweep point + the
quality metric so regressions in either speed or convergence are visible.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from benchmarks.common import emit, timeit
from repro.analytics import kmeans, logreg, nmf, pagerank
from repro.data import kmeans_dataset, logreg_dataset, nmf_dataset, powerlaw_graph
from repro.utils.compile_cache import enable_compile_cache


def bench_logreg():
    x, y, _ = logreg_dataset(2000, 128, seed=0)   # GENE-shaped (n >> d)
    for iters in (6, 10, 14):
        us = timeit(lambda: logreg.fit(x, y, n_nodes=2, threads_per_node=2,
                                       iters=iters, lr=1e-3), iters=2)
        theta, _ = logreg.fit(x, y, n_nodes=2, threads_per_node=2,
                              iters=iters, lr=1e-3)
        emit(f"logreg_iters{iters}", us, f"loss={logreg.loss(theta, x, y):.4f}")
    for threads in (1, 2, 4):
        us = timeit(lambda: logreg.fit(x, y, n_nodes=1, threads_per_node=threads,
                                       iters=10, lr=1e-3), iters=2)
        emit(f"logreg_threads{threads}", us, "iters=10")


def bench_kmeans():
    x, _, _ = kmeans_dataset(20000, 32, 16, seed=0)   # KMS-shaped
    for k in (8, 16, 32):
        us = timeit(lambda: kmeans.fit(x, k, n_nodes=2, threads_per_node=2,
                                       iters=10, seed=0), iters=2)
        c, _ = kmeans.fit(x, k, n_nodes=2, threads_per_node=2, iters=10, seed=0)
        emit(f"kmeans_k{k}", us, f"inertia={kmeans.inertia(x, c):.0f}")
    for iters in (6, 10, 14):
        us = timeit(lambda: kmeans.fit(x, 16, n_nodes=2, threads_per_node=2,
                                       iters=iters, seed=0), iters=2)
        emit(f"kmeans_iters{iters}", us, "k=16")


def bench_nmf():
    r, _, _ = nmf_dataset(2000, 256, 16, seed=0)   # NMFS-shaped
    for rank in (8, 16, 32):
        us = timeit(lambda: nmf.fit(r, rank, n_nodes=2, threads_per_node=2,
                                    iters=10, seed=0), iters=2)
        p, q, _ = nmf.fit(r, rank, n_nodes=2, threads_per_node=2, iters=10, seed=0)
        emit(f"nmf_rank{rank}", us, f"frob={nmf.frob_loss(r, p, q):.4f}")
    for iters in (6, 10, 14):
        us = timeit(lambda: nmf.fit(r, 16, n_nodes=2, threads_per_node=2,
                                    iters=iters, seed=0), iters=2)
        emit(f"nmf_iters{iters}", us, "rank=16")


def bench_pagerank():
    n_v = 20000
    edges = powerlaw_graph(n_v, 8, seed=0)   # LJ-shaped
    for iters in (6, 10, 14):
        us = timeit(lambda: pagerank.fit(edges, n_v, n_nodes=2,
                                         threads_per_node=2, iters=iters), iters=2)
        emit(f"pagerank_iters{iters}", us, f"edges={edges.shape[0]}")
    for threads in (1, 2, 4):
        us = timeit(lambda: pagerank.fit(edges, n_v, n_nodes=1,
                                         threads_per_node=threads, iters=10), iters=2)
        emit(f"pagerank_threads{threads}", us, "iters=10")


def bench_spmd_scan():
    """The scan path end-to-end: wall time vs iters on the SPMD backend."""
    x, y, _ = logreg_dataset(2000, 128, seed=0)
    for iters in (8, 64):
        us = timeit(lambda: logreg.fit(x, y, backend="spmd", iters=iters,
                                       lr=1e-3), iters=2)
        theta, _ = logreg.fit(x, y, backend="spmd", iters=iters, lr=1e-3)
        emit(f"logreg_spmd_scan_iters{iters}", us,
             f"loss={logreg.loss(theta, x, y):.4f}")


def main():
    enable_compile_cache()
    bench_logreg()
    bench_kmeans()
    bench_nmf()
    bench_pagerank()
    bench_spmd_scan()


if __name__ == "__main__":
    main()
