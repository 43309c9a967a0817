"""Quickstart: the STEP-JAX stack in ~40 lines, through the `step.Session` facade.

One `Session` object is the whole Table-1 API: shared state is declared with
``def_global``/``new_array`` and handled via typed `SharedRef` handles
(``.get()/.set()/.inc()/.accumulate()``), threads are spawned with
``session.run``, and the *same* workload code executes on the host backend
(paper-faithful DThreads + blocking accumulator) or the SPMD backend
(shard_map over a device mesh) — pick one at ``Session(backend=...)``.
Per-thread loops are written with ``ctx.iterate(step, carry, iters)``: a
guarded Python loop on the host backend, a single ``lax.scan`` under SPMD
(compile time O(1) in ``iters``).  The script declares shared state, runs a
tiny ``ctx.iterate`` program and the paper's worked example (distributed
multi-threaded logistic regression) on both backends, then trains a tiny LM
end-to-end through the production step builder.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax.numpy as jnp
import numpy as np

from repro.analytics import logreg
from repro.core import AccumMode, Session
from repro.data import logreg_dataset
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    # 1. the Table-1 facade: DSM declaration through handles (paper §4.1)
    sess = Session(backend="host", n_nodes=2, threads_per_node=2)
    step_size = sess.def_global("step_size", 1e-3)
    grad = sess.new_array("grad", (32,))
    print(f"DSM declared: {sess.names()}, grad addr=0x{grad.address:x}, "
          f"step_size={float(step_size.get()):g}")

    # 1b. the iteration engine: one logical loop, two lowerings — a guarded
    # Python loop here on the host backend, one lax.scan under SPMD.
    total = sess.new_array("total", ())

    def count_rounds(ctx):
        return ctx.iterate(lambda c: c + total.accumulate(jnp.float32(1.0)),
                           jnp.float32(0.0), 5)

    per_thread = sess.run(count_rounds)
    print(f"ctx.iterate: 5 rounds x {sess.backend.n_threads} threads -> "
          f"carry {float(per_thread[0]):g} per thread")

    # 2. the paper's §4.5 example on BOTH backends — same thread_proc
    x, y, _ = logreg_dataset(n_rows=800, n_features=32, seed=0)
    theta, hsess = logreg.fit(x, y, backend="host", n_nodes=2, threads_per_node=2,
                              iters=15, lr=1e-3, mode=AccumMode.REDUCE_SCATTER)
    print(f"logreg[host] loss: {logreg.loss(theta, x, y):.4f} "
          f"(accumulator wire traffic: {hsess.wire_traffic()} elements, "
          f"(N+1)·V·iters = {(4 + 1) * 32 * 15})")
    theta_s, ssess = logreg.fit(x, y, backend="spmd", iters=15, lr=1e-3)
    print(f"logreg[spmd] loss: {logreg.loss(theta_s, x, y):.4f} "
          f"drift vs host {float(np.max(np.abs(theta_s - theta))):.2e}")

    # 3. a tiny LM through the production trainer
    from repro.launch.train import train
    losses = train("qwen3-1.7b", smoke=True, steps=10, batch=4, seq=64)
    print(f"LM train: loss {losses[0]:.3f} → {losses[-1]:.3f}")


if __name__ == "__main__":
    main()
