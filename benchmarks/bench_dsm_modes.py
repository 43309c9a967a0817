"""Paper Fig. 3: fine- vs coarse-grained DSM, plus the shard-count sweep.

Three structural measurements on real machinery:
1. transfer counts through the GlobalStore under each granularity (the paper's
   request-count argument: coarse-grained = 1 bulk transfer per object, fine =
   1 per 32-bit word), plus wall time of get/set round trips;
2. the TPU realisation — a 200-leaf parameter pytree moved leaf-by-leaf
   ("fine") vs packed into one 128-aligned buffer ("coarse", pack_tree) —
   which is the latency-vs-bandwidth trade the paper measures on memcached;
3. the ``step.shards`` sweep — S=1 vs S=8 consistent-hash shards under a
   concurrent multi-thread cached read/write mix (the workload the seed's
   single cache lock serialised), written to ``benchmarks/BENCH_shards.json``.
"""

import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks.common import emit, timeit, write_bench
from repro.core import DSMCache, GlobalStore, pack_spec, pack_tree, unpack_tree
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    n_leaves, leaf = 200, 512
    tree = {f"w{i}": jnp.full((leaf,), float(i)) for i in range(n_leaves)}

    for gran in ("fine", "coarse"):
        store = GlobalStore(granularity=gran)
        for k, v in tree.items():
            store.new_array(k, (leaf,))

        def roundtrip():
            for k, v in tree.items():
                store.set(k, v, bump_epoch=False)
            for k in tree:
                store.get(k)

        us = timeit(roundtrip, warmup=1, iters=3)
        emit(f"dsm_{gran}_roundtrip", us, f"transfers={store.stats['transfers']}")

    # packed vs per-leaf device transfer
    spec = pack_spec(tree)

    def fine_put():
        out = [jax.device_put(v) for v in tree.values()]
        jax.block_until_ready(out)

    def coarse_put():
        buf = jax.device_put(pack_tree(tree, spec))
        jax.block_until_ready(buf)

    us_fine = timeit(fine_put, warmup=1, iters=5)
    us_coarse = timeit(coarse_put, warmup=1, iters=5)
    emit("dsm_fine_device_put", us_fine, f"n_transfers={n_leaves}")
    emit("dsm_coarse_device_put", us_coarse,
         f"n_transfers=1;speedup={us_fine / max(us_coarse, 1e-9):.2f}x;pad_waste={spec.padding_waste}")

    # roundtrip correctness of the coarse path
    buf = pack_tree(tree, spec)
    back = unpack_tree(buf, spec)
    ok = all(np.allclose(tree[k], back[k]) for k in tree)
    emit("dsm_coarse_roundtrip_exact", 0.0, f"ok={ok}")

    shard_sweep()


def _mixed_workload(store, cache, names, n_threads, ops_per_thread, write_every,
                    memoize_owners=False):
    """Concurrent cached read/write mix: each worker node loops over its
    name stream, writing a fresh host buffer every `write_every`-th op (the
    numpy→jax conversion happens under the owning shard's lock — exactly the
    hold the seed's single lock serialised across all names).

    With ``memoize_owners=True`` each op carries its pre-resolved
    :class:`OwnerHandle`, so the hot loop never re-hashes the ring — the
    memoization the ``SharedRef`` path uses."""
    payload = [np.full((262144,), float(t), np.float32) for t in range(n_threads)]
    handles = ({name: store.owner_handle(name) for name in names}
               if memoize_owners else {})
    errs = []

    def worker(node):
        try:
            for i in range(ops_per_thread):
                name = names[(node * 31 + i) % len(names)]
                owner = handles.get(name)
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


def shard_sweep(n_threads: int = 8, n_names: int = 64,
                ops_per_thread: int = 240, write_every: int = 2):
    """S=1 vs S=8 × hashed vs memoized owners: the same mixed read/write
    workload over the same namespace.  Per-shard locks let ops on different
    shards overlap; pre-resolved :class:`OwnerHandle`\\ s additionally take
    the per-op ring hash out of the locked hot path (median of 5 runs)."""
    results = {"workload": {"threads": n_threads, "names": n_names,
                            "ops_per_thread": ops_per_thread,
                            "write_every": write_every, "vector_len": 262144}}
    total_ops = n_threads * ops_per_thread
    for shards in (1, 8):
        row = {}
        for label, memo in (("hashed", False), ("memoized", True)):
            # fresh store + cache per cell: identical cold-cache start, so the
            # hashed/memoized comparison is owner resolution and nothing else
            store = GlobalStore(shards=shards)
            cache = DSMCache(store, n_nodes=n_threads, capacity=n_names)
            names = [f"v{i}" for i in range(n_names)]
            for n in names:
                store.new_array(n, (262144,))
            _mixed_workload(store, cache, names, n_threads, 20, write_every,
                            memoize_owners=memo)  # warmup
            dt = sorted(_mixed_workload(store, cache, names, n_threads,
                                        ops_per_thread, write_every,
                                        memoize_owners=memo)
                        for _ in range(5))[2]
            row[f"{label}_seconds"] = dt
            row[f"{label}_ops_per_sec"] = total_ops / dt
            emit(f"dsm_sharded_rw_mix_s{shards}_{label}", dt / total_ops * 1e6,
                 f"ops_per_sec={total_ops / dt:.0f}")
        # headline ops_per_sec is the memoized path — what SharedRef users get
        row["seconds"] = row["memoized_seconds"]
        row["ops_per_sec"] = row["memoized_ops_per_sec"]
        row["owner_memo_speedup"] = (row["memoized_ops_per_sec"]
                                     / row["hashed_ops_per_sec"])
        row["cache_hit_rate"] = cache.stats.hit_rate
        row["shards_busy"] = sum(1 for r in store.shard_stats().values()
                                 if r["get"] + r["set"] > 0)
        results[f"s{shards}"] = row
    # the per-shard-locking story is measured on the hashed path (the PR 5
    # workload, where per-op resolution + lock hold is what sharding relieves)
    results["speedup_s8_over_s1"] = (results["s8"]["hashed_ops_per_sec"]
                                     / results["s1"]["hashed_ops_per_sec"])
    emit("dsm_sharded_speedup", 0.0,
         f"s8_over_s1={results['speedup_s8_over_s1']:.2f}x;"
         f"memo_s8={results['s8']['owner_memo_speedup']:.2f}x")
    write_bench("BENCH_shards.json", results)


if __name__ == "__main__":
    main()
