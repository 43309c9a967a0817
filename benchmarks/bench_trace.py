"""step.trace overhead: the ≤5%-when-disabled acceptance measurement.

Two workloads, three tracer states each:

1. the S=8 sharded concurrent cached read/write mix from the shard sweep
   (the DSM hot path the tracer instruments most densely), and
2. a 2-thread host logreg fit (store + cache + accumulator + barrier paths
   together);

each timed under ``noop`` (no tracer attached anywhere — the pre-step.trace
baseline), ``disabled`` (tracers attached but off, the shipping default:
must cost ≤5% on the rw mix), and ``enabled`` (full recording, reported for
scale, not gated).  Results land in ``benchmarks/BENCH_trace.json``.
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks.bench_dsm_modes import _mixed_workload
from benchmarks.common import emit, write_bench
from repro.core import DSMCache, GlobalStore, Session, telemetry
from repro.core.telemetry import NULL_TRACER, Tracer
from repro.utils.compile_cache import enable_compile_cache


def _rw_mix_once(state: str, n_threads=8, n_names=64, ops_per_thread=120,
                 write_every=2):
    store = GlobalStore(shards=8)
    cache = DSMCache(store, n_nodes=n_threads, capacity=n_names)
    tracer = None
    if state == "disabled":
        tracer = Tracer(enabled=False)
    elif state == "enabled":
        tracer = Tracer(enabled=True)
    if tracer is not None:
        store.tracer = tracer
        cache.tracer = tracer
    names = [f"v{i}" for i in range(n_names)]
    for n in names:
        store.new_array(n, (262144,))
    _mixed_workload(store, cache, names, n_threads, 20, write_every)  # warmup
    dt = _mixed_workload(store, cache, names, n_threads, ops_per_thread,
                         write_every)
    events = 0
    if tracer is not None:
        events = tracer.snapshot()["events"]
        tracer.disable()
    return dt, n_threads * ops_per_thread, events


def _rw_mix_all(states, repeats=7, **kw):
    """Interleave states round-robin and keep each state's best run: the mix
    is dominated by 1 MiB payload writes and thread scheduling, so
    back-to-back blocks would mostly measure machine drift, not the tracer."""
    best = {}
    for _ in range(repeats):
        for state in states:
            dt, ops, events = _rw_mix_once(state, **kw)
            if state not in best or dt < best[state][0]:
                best[state] = (dt, ops, events)
    return best


def _logreg_fit(state: str, repeats=5):
    from repro.analytics import logreg

    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 64)).astype(np.float32)
    y = (rng.random(256) > 0.5).astype(np.float32)
    import time

    # absorb jit compilation before any state is timed
    logreg.fit(x, y, iters=2, n_nodes=2, threads_per_node=1)
    best = None
    for _ in range(repeats):
        sess = Session(backend="host", n_nodes=2, threads_per_node=1,
                       trace=(state == "enabled"))
        if state == "noop":
            # strip even the disabled per-object tracers: the pre-step.trace
            # baseline had no tracer attribute lookups beyond the flag check
            sess.tracer = NULL_TRACER
        t0 = time.perf_counter()
        theta, _ = logreg.fit(x, y, iters=20, session=sess)
        dt = time.perf_counter() - t0
        events = sess.tracer.snapshot()["events"] if state == "enabled" else 0
        sess.tracer.disable()
        if best is None or dt < best[0]:
            best = (dt, events)
    return best


def main():
    enable_compile_cache()
    assert telemetry.armed_count() == 0
    results = {"workload_rw": {"threads": 8, "shards": 8, "names": 64,
                               "ops_per_thread": 120, "vector_len": 262144},
               "workload_logreg": {"n": 256, "d": 64, "iters": 20,
                                   "threads": 2}}

    rw = _rw_mix_all(("noop", "disabled", "enabled"))
    for state, (dt, ops, events) in rw.items():
        results[f"rw_{state}"] = {"seconds": dt, "ops_per_sec": ops / dt,
                                  "events": events}
        emit(f"trace_rw_mix_{state}", dt / ops * 1e6,
             f"ops_per_sec={ops / dt:.0f};events={events}")

    for state in ("noop", "disabled", "enabled"):
        dt, events = _logreg_fit(state)
        results[f"logreg_{state}"] = {"seconds": dt, "events": events}
        emit(f"trace_logreg_{state}", dt * 1e6, f"events={events}")

    rw_overhead = (results["rw_disabled"]["seconds"]
                   / results["rw_noop"]["seconds"] - 1.0) * 100
    en_overhead = (results["rw_enabled"]["seconds"]
                   / results["rw_noop"]["seconds"] - 1.0) * 100
    lr_overhead = (results["logreg_disabled"]["seconds"]
                   / results["logreg_noop"]["seconds"] - 1.0) * 100
    results["disabled_overhead_pct_rw"] = rw_overhead
    results["enabled_overhead_pct_rw"] = en_overhead
    results["disabled_overhead_pct_logreg"] = lr_overhead
    results["acceptance_limit_pct"] = 5.0
    results["disabled_within_limit"] = rw_overhead <= 5.0
    emit("trace_disabled_overhead_rw", 0.0,
         f"pct={rw_overhead:.2f};limit=5;ok={rw_overhead <= 5.0}")
    emit("trace_enabled_overhead_rw", 0.0, f"pct={en_overhead:.2f}")

    write_bench("BENCH_trace.json", results)
    assert telemetry.armed_count() == 0, "benchmark leaked an enabled tracer"


if __name__ == "__main__":
    main()
