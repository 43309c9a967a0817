"""step.tiers acceptance benchmark: live incremental rebalancing vs
stop-the-world, plus the no-cold-tier default-path overhead gate.

Three measurements on the S=8 concurrent read/write mix (the
``bench_dsm_modes`` shard-sweep workload):

1. **default-path overhead gate** — the exact PR 8 ``s8`` cell re-measured
   on the refactored (two-tier-capable) store with ``cold_tier=None``.
   Compared against the committed ``BENCH_shards.json`` baseline; the gate
   passes when current throughput is >= 95% of baseline.
2. **rebalance under load, incremental** — an ``add_shard`` lands mid-run
   with readers/writers flowing: max single reader/writer pause and the
   throughput dip while the migration window is open.
3. **rebalance under load, stop-the-world** — the same join via the legacy
   ``incremental=False`` path (every involved shard lock held for the whole
   move) for the pause/dip comparison.

Results go to ``benchmarks/BENCH_rebalance.json``.
"""

import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks.common import emit, write_bench
from repro.core import DSMCache, GlobalStore
from repro.utils.compile_cache import enable_compile_cache

HERE = os.path.dirname(os.path.abspath(__file__))


# -- 1. default-path overhead gate -------------------------------------------


def _mixed_workload(store, cache, names, n_threads, ops_per_thread, write_every):
    """The bench_dsm_modes memoized S=8 mix, byte for byte: pre-resolved
    owner handles, 1 MiB payloads, every ``write_every``-th op a write."""
    payload = [np.full((262144,), float(t), np.float32) for t in range(n_threads)]
    handles = {name: store.owner_handle(name) for name in names}
    errs = []

    def worker(node):
        try:
            for i in range(ops_per_thread):
                name = names[(node * 31 + i) % len(names)]
                owner = handles[name]
                if i % write_every == node % write_every:
                    cache.write(node, name, payload[node], owner=owner)
                else:
                    cache.read(node, name, owner=owner)
        except Exception as e:  # pragma: no cover - surfaced below
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(n_threads)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errs:
        raise errs[0]
    return time.perf_counter() - t0


def _gate_sample(n_threads, n_names, ops_per_thread, write_every):
    store = GlobalStore(shards=8)                    # cold_tier=None default
    cache = DSMCache(store, n_nodes=n_threads, capacity=n_names)
    names = [f"v{i}" for i in range(n_names)]
    for n in names:
        store.new_array(n, (262144,))
    _mixed_workload(store, cache, names, n_threads, 20, write_every)  # warmup
    dt = sorted(_mixed_workload(store, cache, names, n_threads,
                                ops_per_thread, write_every)
                for _ in range(5))[2]
    return n_threads * ops_per_thread / dt


def overhead_gate(n_threads=8, n_names=64, ops_per_thread=240, write_every=2):
    """The committed baseline comes from ``BENCH_shards.json`` — regenerated
    by ``bench_dsm_modes`` earlier in the same ``benchmarks.run`` session, so
    both sides are measured minutes apart on the same machine.  Two samples
    (fresh store each) with the max taken guard against one-sided load
    drift between the two module runs."""
    samples = [_gate_sample(n_threads, n_names, ops_per_thread, write_every)
               for _ in range(2)]
    current = max(samples)
    baseline = None
    baseline_path = os.path.join(HERE, "BENCH_shards.json")
    try:
        with open(baseline_path) as f:
            baseline = json.load(f)["s8"]["memoized_ops_per_sec"]
    except (OSError, KeyError, ValueError):
        pass
    row = {"current_ops_per_sec": current, "samples_ops_per_sec": samples,
           "baseline_ops_per_sec": baseline, "threshold": 0.95}
    if baseline:
        row["ratio"] = current / baseline
        row["pass"] = row["ratio"] >= row["threshold"]
        emit("rebalance_default_path_gate", 1e6 / current,
             f"ratio={row['ratio']:.3f};pass={row['pass']}")
    else:
        emit("rebalance_default_path_gate", 1e6 / current,
             "baseline=missing")
    return row


# -- 2/3. rebalance under live load -------------------------------------------


def rebalance_under_load(incremental, n_threads=4, n_names=2048,
                         steady_s=0.4, join_id=17):
    """S=8 rw mix with an ``add_shard`` landing mid-run.  Every op records
    (start, duration); the window timestamps split steady-state ops from the
    ops that overlapped the migration.  Many small entries keep single ops
    fast (~tens of µs) while giving the join a real arc to move — the
    regime where stop-the-world visibly freezes every worker and the
    incremental window should not."""
    store = GlobalStore(shards=8)
    cache = DSMCache(store, n_nodes=n_threads, capacity=n_names)
    names = [f"r{i}" for i in range(n_names)]
    for n in names:
        store.new_array(n, (256,))
    handles = {n: store.owner_handle(n) for n in names}
    payload = [np.full((256,), float(t), np.float32)
               for t in range(n_threads)]
    stop = threading.Event()
    ops = [[] for _ in range(n_threads)]             # (t_start, dt) per thread
    errs = []

    def worker(node):
        lat = ops[node]
        i = 0
        try:
            while not stop.is_set():
                name = names[(node * 31 + i) % len(names)]
                t0 = time.perf_counter()
                if i % 2 == node % 2:
                    cache.write(node, name, payload[node], owner=handles[name])
                else:
                    cache.read(node, name, owner=handles[name])
                lat.append((t0, time.perf_counter() - t0))
                i += 1
        except Exception as e:  # pragma: no cover - surfaced below
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(n_threads)]
    for t in threads:
        t.start()
    time.sleep(steady_s)
    t_mig0 = time.perf_counter()
    mig = store.add_shard(join_id, incremental=incremental)  # drains inline
    t_mig1 = time.perf_counter()
    time.sleep(steady_s)
    stop.set()
    for t in threads:
        t.join()
    if errs:
        raise errs[0]
    flat = [x for lane in ops for x in lane]
    steady = sorted(dt for t0, dt in flat if t0 + dt < t_mig0 or t0 > t_mig1)
    during = sorted(dt for t0, dt in flat
                    if t0 <= t_mig1 and t0 + dt >= t_mig0)
    steady_span = 2 * steady_s
    mig_span = max(t_mig1 - t_mig0, 1e-9)
    steady_rate = len(steady) / steady_span
    during_rate = len(during) / mig_span

    def pct(lat, q):
        return lat[min(int(q * len(lat)), len(lat) - 1)] if lat else 0.0

    return {"mode": "incremental" if incremental else "stop_the_world",
            "entries_moved": len(mig.moved),
            "bytes_moved": mig.bytes_moved,
            "window_s": mig.window_s,
            "reader_pulls": mig.pulled,
            "max_op_pause_s": max(during, default=0.0),
            "p99_op_pause_s": pct(during, 0.99),
            "p50_op_pause_s": pct(during, 0.50),
            "steady_max_op_s": max(steady, default=0.0),
            "steady_p99_op_s": pct(steady, 0.99),
            "steady_ops_per_sec": steady_rate,
            "during_ops_per_sec": during_rate,
            "throughput_dip": 1.0 - min(during_rate / max(steady_rate, 1e-9),
                                        1.0)}


def main():
    enable_compile_cache()
    # a 0.5ms GIL quantum keeps scheduler starvation out of the pause
    # measurement — what remains is actual lock blocking
    sys.setswitchinterval(0.0005)
    results = {"workload": {"gate_threads": 8, "gate_names": 64,
                            "rebalance_threads": 4, "rebalance_names": 2048,
                            "write_every": 2,
                            "gil_switch_interval_s": 0.0005}}
    results["overhead_gate"] = overhead_gate()
    inc = rebalance_under_load(True)
    stw = rebalance_under_load(False)
    results["incremental"] = inc
    results["stop_the_world"] = stw
    results["pause_ratio_stw_over_incremental"] = (
        stw["max_op_pause_s"] / max(inc["max_op_pause_s"], 1e-9))
    for row in (inc, stw):
        emit(f"rebalance_{row['mode']}", row["window_s"] * 1e6,
             f"moved={row['entries_moved']};"
             f"max_pause_ms={row['max_op_pause_s'] * 1e3:.2f};"
             f"dip={row['throughput_dip']:.2f}")
    emit("rebalance_pause_ratio", 0.0,
         f"stw_over_incremental={results['pause_ratio_stw_over_incremental']:.2f}x")
    write_bench("BENCH_rebalance.json", results)


if __name__ == "__main__":
    main()
