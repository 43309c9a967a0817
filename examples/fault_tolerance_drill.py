"""Fault-tolerance drill (paper §5.4 + Fig. 11), on the `step.Session` facade.

Runs distributed K-means sessions, kills a node via the heartbeat monitor,
and recovers twice — single-node vs multi-node recovery — through
``ft.session_recovery``, which replans thread placement over the survivors
and rolls a fresh Session onto the surviving DSM.  With a sharded store
(``shards=n_nodes``), recovery also removes the dead node's shard from the
consistent-hash ring: only its ~1/S of keys migrate to survivors, epochs
intact.  Then demonstrates checkpoint/rollback exactness for the shared
state.

    PYTHONPATH=src python examples/fault_tolerance_drill.py
"""

import tempfile
import time

import numpy as np

from repro.analytics import kmeans
from repro.core import Session
from repro.data import kmeans_dataset
from repro.ft import HeartbeatMonitor, save_checkpoint, restore_checkpoint, session_recovery
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    x, _, _ = kmeans_dataset(4000, 16, 8, seed=0)
    n_nodes, tpn = 4, 2

    # -- failure detection ---------------------------------------------------
    failures = []
    mon = HeartbeatMonitor(list(range(n_nodes)), timeout=0.2,
                           on_failure=lambda dead: failures.append(dead))
    mon.start()
    for node in range(n_nodes):
        mon.beat(node)
    mon.declare_dead(2)   # drill: node 2 dies
    time.sleep(0.1)
    mon.stop()
    print(f"heartbeat detected failures: {failures}")

    # -- recovery planning: single vs multi (Fig. 11) --------------------------
    for mode in ("single", "multi"):
        failed_session = Session(backend="host", n_nodes=n_nodes,
                                 threads_per_node=tpn, shards=n_nodes)
        kmeans.fit(x, 8, iters=1, seed=0, session=failed_session)
        plan, recovered = session_recovery(
            failed_session, failures[0] if failures else [2], mode=mode,
            threads_per_node=tpn if mode == "multi" else tpn * 2)
        t0 = time.time()
        # recovery = reload the dead node's partitions + recompute one iteration
        centers, _ = kmeans.fit(x, 8, iters=1, seed=0, session=recovered)
        dt = (time.time() - t0) * 1e3
        mig = plan.migration
        moved = (f"ring: moved {len(mig.moved)}/{mig.total_names} keys off "
                 f"shard {mig.removed}" if mig else "ring: unchanged")
        print(f"{mode:>6s}-node recovery: reassign {plan.reassignment} "
              f"redo-iteration {dt:.0f}ms  {moved}")

    # -- checkpoint/rollback exactness ------------------------------------------
    with tempfile.TemporaryDirectory() as d:
        centers1, _ = kmeans.fit(x, 8, n_nodes=2, threads_per_node=2,
                                 iters=6, seed=0)
        save_checkpoint(d, 6, {"centers": centers1})
        restored, _, step = restore_checkpoint(d, {"centers": centers1})
        assert np.allclose(restored["centers"], centers1)
        print(f"checkpoint at iter {step} restores bit-exact: True")


if __name__ == "__main__":
    main()
