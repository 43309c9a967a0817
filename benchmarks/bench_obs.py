"""step.obs overhead: the ≤5%-when-armed acceptance measurement.

Same protocol as ``bench_trace``/``bench_check``: the S=8 sharded concurrent
cached read/write mix plus a 2-thread host logreg fit, each timed under

* ``noop``     — no tracer attached anywhere (pre-step.trace baseline),
* ``disabled`` — tracer attached but off (the shipping default), and
* ``armed``    — a :class:`FlightRecorder` armed on that tracer, i.e. the
  tracer running in **record-only** mode: hists/counters accumulate and
  slow/always-record events land in the bounded ring, but no unbounded span
  list grows and fast spans early-return without taking the tracer lock.

The gate is ``armed``: the flight recorder exists to be left on in
production, so its rw-mix overhead must stay ≤5% over ``noop`` (full tracing
costs ~29% on the same mix — see BENCH_trace.json — which is exactly why
record-only mode exists).  Results land in ``benchmarks/BENCH_obs.json``.
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks.bench_dsm_modes import _mixed_workload
from benchmarks.common import emit, write_bench
from repro.core import DSMCache, GlobalStore, Session, telemetry
from repro.core.telemetry import NULL_TRACER, Tracer
from repro.obs import FlightRecorder
from repro.utils.compile_cache import enable_compile_cache

STATES = ("noop", "disabled", "armed")


def _rw_mix_once(state: str, n_threads=8, n_names=64, ops_per_thread=120,
                 write_every=2):
    store = GlobalStore(shards=8)
    cache = DSMCache(store, n_nodes=n_threads, capacity=n_names)
    tracer = None
    recorder = None
    if state in ("disabled", "armed"):
        tracer = Tracer(enabled=False)
        store.tracer = tracer
        cache.tracer = tracer
    if state == "armed":
        recorder = FlightRecorder()
        recorder.attach(tracer)
    names = [f"v{i}" for i in range(n_names)]
    for n in names:
        store.new_array(n, (262144,))
    _mixed_workload(store, cache, names, n_threads, 20, write_every)  # warmup
    dt = _mixed_workload(store, cache, names, n_threads, ops_per_thread,
                         write_every)
    ring_held = 0
    if recorder is not None:
        ring_held = len(recorder.events())
        recorder.close()
    return dt, n_threads * ops_per_thread, ring_held


def _rw_mix_all(states, repeats=7, **kw):
    """Interleave states round-robin and keep each state's best run (the mix
    is dominated by payload writes and scheduling drift — see bench_trace)."""
    best = {}
    for _ in range(repeats):
        for state in states:
            dt, ops, ring = _rw_mix_once(state, **kw)
            if state not in best or dt < best[state][0]:
                best[state] = (dt, ops, ring)
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
                       record=(state == "armed"))
        if state == "noop":
            sess.tracer = NULL_TRACER
        t0 = time.perf_counter()
        logreg.fit(x, y, iters=20, session=sess)
        dt = time.perf_counter() - t0
        ring = len(sess.recorder.events()) if state == "armed" else 0
        sess.recorder.close()
        sess.tracer.disable()
        if best is None or dt < best[0]:
            best = (dt, ring)
    return best


def main():
    enable_compile_cache()
    assert telemetry.armed_count() == 0
    results = {"workload_rw": {"threads": 8, "shards": 8, "names": 64,
                               "ops_per_thread": 120, "vector_len": 262144},
               "workload_logreg": {"n": 256, "d": 64, "iters": 20,
                                   "threads": 2}}

    rw = _rw_mix_all(STATES)
    for state, (dt, ops, ring) in rw.items():
        results[f"rw_{state}"] = {"seconds": dt, "ops_per_sec": ops / dt,
                                  "ring_events": ring}
        emit(f"obs_rw_mix_{state}", dt / ops * 1e6,
             f"ops_per_sec={ops / dt:.0f};ring={ring}")

    for state in STATES:
        dt, ring = _logreg_fit(state)
        results[f"logreg_{state}"] = {"seconds": dt, "ring_events": ring}
        emit(f"obs_logreg_{state}", dt * 1e6, f"ring={ring}")

    rw_armed = (results["rw_armed"]["seconds"]
                / results["rw_noop"]["seconds"] - 1.0) * 100
    rw_disabled = (results["rw_disabled"]["seconds"]
                   / results["rw_noop"]["seconds"] - 1.0) * 100
    lr_armed = (results["logreg_armed"]["seconds"]
                / results["logreg_noop"]["seconds"] - 1.0) * 100
    results["armed_overhead_pct_rw"] = rw_armed
    results["disabled_overhead_pct_rw"] = rw_disabled
    results["armed_overhead_pct_logreg"] = lr_armed
    results["acceptance_limit_pct"] = 5.0
    results["armed_within_limit"] = rw_armed <= 5.0
    emit("obs_armed_overhead_rw", 0.0,
         f"pct={rw_armed:.2f};limit=5;ok={rw_armed <= 5.0}")
    emit("obs_armed_overhead_logreg", 0.0, f"pct={lr_armed:.2f}")

    write_bench("BENCH_obs.json", results)
    assert telemetry.armed_count() == 0, "benchmark leaked an armed recorder"


if __name__ == "__main__":
    main()
