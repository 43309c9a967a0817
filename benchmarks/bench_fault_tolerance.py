"""Paper Fig. 11: recovery time, single-node vs multi-node recovery.

K-means over a 16-thread pool; node killed at iteration 6; recovery reloads
the dead node's partitions and redoes the iteration on 1 survivor (single) vs
all survivors (multi).  Reports per-phase times like the paper (data loading
vs recomputation).
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from benchmarks.common import emit
from repro.analytics import kmeans
from repro.data import kmeans_dataset, partition_rows
from repro.ft import plan_recovery
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    x, _, _ = kmeans_dataset(40000, 32, 16, seed=0)
    n_nodes, tpn = 4, 4
    n_threads = n_nodes * tpn

    # normal per-iteration time
    t0 = time.perf_counter()
    centers, _, _ = kmeans.fit_threads(x, 16, n_nodes=n_nodes, threads_per_node=tpn,
                                       iters=5, seed=0)
    per_iter_us = (time.perf_counter() - t0) / 5 * 1e6
    emit("ft_normal_iter", per_iter_us, "iters=5")

    tids_by_node = {n: [n * tpn + i for i in range(tpn)] for n in range(n_nodes)}
    failed = [1]

    for mode in ("single", "multi"):
        plan = plan_recovery(failed, list(range(n_nodes)), tids_by_node, mode=mode)
        # data loading: survivors re-read the dead node's partitions
        t0 = time.perf_counter()
        lost = [t for t in range(n_threads) if t in plan.reassignment]
        _reloaded = [x[slice(*partition_rows(x.shape[0], t, n_threads))].copy()
                     for t in lost]
        if mode == "single":
            pass  # one node does all the copies serially (already serial here)
        t_load = (time.perf_counter() - t0) * 1e6
        # recomputation: redo iteration 6 on the surviving pool
        t0 = time.perf_counter()
        kmeans.fit_threads(x, 16, n_nodes=len(plan.new_world),
                           threads_per_node=tpn if mode == "multi" else tpn * 2,
                           iters=1, seed=0)
        t_recompute = (time.perf_counter() - t0) * 1e6
        emit(f"ft_{mode}_recovery", t_load + t_recompute,
             f"load_us={t_load:.0f};recompute_us={t_recompute:.0f};"
             f"survivors={len(plan.new_world)}")


if __name__ == "__main__":
    main()
