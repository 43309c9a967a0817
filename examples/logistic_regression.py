"""Paper §4.5 end-to-end: logistic regression three ways, one workload.

1. fit_reference        — single-thread oracle
2. fit(backend="host")  — the paper's DThread + DSM + accumulator program
3. fit(backend="spmd")  — the same thread_proc as shard_map over a mesh

All three produce identical parameters (the accumulator is exact), which is
the point: the STEP programming model is a *semantics-preserving* distribution
of the sequential program, and the Session facade makes the substrate a
constructor argument instead of a rewrite.  The workload's loop is written
once with ``ctx.iterate``; on the SPMD backend it lowers to one ``lax.scan``,
so the printed iteration count is free at compile time (O(1) program size).

    PYTHONPATH=src python examples/logistic_regression.py
"""

import numpy as np

from repro.analytics import logreg
from repro.core import AccumMode
from repro.data import logreg_dataset
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    x, y, theta_true = logreg_dataset(n_rows=2000, n_features=64, seed=0)

    ref = logreg.fit_reference(x, y, iters=20, lr=1e-3)
    print(f"reference loss: {logreg.loss(ref, x, y):.4f}")

    for mode in (AccumMode.GATHER_ALL, AccumMode.REDUCE_SCATTER, AccumMode.AUTO):
        theta, sess = logreg.fit(x, y, backend="host", n_nodes=2,
                                 threads_per_node=2, iters=20, lr=1e-3, mode=mode)
        drift = float(np.max(np.abs(theta - ref)))
        print(f"host[{mode.value:>14s}] loss {logreg.loss(theta, x, y):.4f} "
              f"drift {drift:.2e} wire {sess.wire_traffic():>8d} elems")

    spmd, sess = logreg.fit(x, y, backend="spmd", iters=20, lr=1e-3)
    print(f"spmd[{sess.backend.n_threads} threads] loss: "
          f"{logreg.loss(spmd, x, y):.4f} "
          f"drift {float(np.max(np.abs(spmd - ref))):.2e} "
          f"wire {sess.wire_traffic():>8d} elems")


if __name__ == "__main__":
    main()
