"""Compile-cost of the SPMD `ctx.iterate` scan path vs trip count.

The point of lowering `ctx.iterate` to one ``lax.scan`` is that the traced
program — and therefore trace+lower and XLA compile wall-time — is O(1) in
``iters`` instead of O(iters) unrolled HLO.  This benchmark measures the
paper's §4.5 logreg step at iters ∈ {2, 32, 256}: per point it reports
trace+lower time, compile time and the lowered line count (which must be
constant), and writes the whole table to ``benchmarks/BENCH_compile.json``
so the perf trajectory has data across PRs.

    PYTHONPATH=src python -m benchmarks.bench_compile
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax.numpy as jnp

from benchmarks.common import emit, write_bench
from repro.core import Session
from repro.core.compat import cost_analysis
from repro.utils.compile_cache import enable_compile_cache

ITERS_SWEEP = (2, 32, 256)
N_ROWS, N_FEATURES = 256, 64


def _program(sess, grad, iters: int):
    """The §4.5 logreg round as a `ctx.iterate` step function."""

    def thread_proc(ctx, xs, ys):
        def step(theta):
            total = grad.accumulate((ys - 1.0 / (1.0 + jnp.exp(-(xs @ theta)))) @ xs)
            return theta + 1e-3 * total

        return ctx.iterate(step, jnp.zeros((N_FEATURES,), jnp.float32), iters)

    return thread_proc


def main():
    enable_compile_cache()
    xs = jnp.ones((N_ROWS, N_FEATURES), jnp.float32)
    ys = jnp.ones((N_ROWS,), jnp.float32)
    rows = {}
    for iters in ITERS_SWEEP:
        sess = Session(backend="spmd")
        grad = sess.new_array("grad", (N_FEATURES,))
        proc = _program(sess, grad, iters)
        t0 = time.perf_counter()
        lowered = sess.lower(proc, data=(xs, ys))
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        rows[str(iters)] = {
            "trace_lower_s": t1 - t0,
            "compile_s": t2 - t1,
            "lowered_lines": len(lowered.as_text().splitlines()),
            "flops": cost_analysis(compiled).get("flops"),
        }
        emit(f"compile_iters{iters}", (t2 - t0) * 1e6,
             f"lines={rows[str(iters)]['lowered_lines']}")

    lines = {r["lowered_lines"] for r in rows.values()}
    rows["constant_program_size"] = len(lines) == 1
    out = write_bench("BENCH_compile.json", rows)
    print(f"# wrote {out} (constant_program_size={rows['constant_program_size']})",
          flush=True)


if __name__ == "__main__":
    main()
