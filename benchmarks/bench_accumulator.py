"""Paper §5.2 / Table: accumulator traffic — (2N+1)·V vs (N+1)·V, plus the
sparse wire format.

Validates the paper's claim three ways:
1. host accumulator: exact wire-traffic accounting per mode (sparse figures
   derived from the actual pair-array lengths);
2. SPMD lowering on a mesh over every device (8 virtual ones on a CPU
   host, the chips on a TPU host): per-device collective bytes parsed from
   the compiled HLO — gather_all ≈ N·V vs reduce_scatter ≈ 2·V per device —
   plus wall time per accumulate call;
3. dense-vs-sparse-vs-auto sweep over nnz density: which wire format the auto
   rule picks, what it costs, and Pallas-vs-jnp sparsifier wall time.

The whole table is written to ``benchmarks/BENCH_accumulator.json`` so the
perf trajectory has data across PRs (``python -m benchmarks.run --only
accumulator``).
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from benchmarks.common import emit, timeit, write_bench
from repro.core import AccumMode, DAddAccumulator, GlobalStore, accumulate, shard_map
from repro.core.sparse import blocked_topk_sparsify, pair_capacity
from repro.launch.mesh import make_host_mesh
from repro.utils.hlo import collective_bytes_from_hlo
from repro.utils.compile_cache import enable_compile_cache

RESULTS = {}


def host_layer():
    V, N, iters, k = 4096, 8, 5, 256
    P_cap = pair_capacity(V, k)
    for mode in (AccumMode.GATHER_ALL, AccumMode.REDUCE_SCATTER,
                 AccumMode.SPARSE, AccumMode.AUTO):
        store = GlobalStore()
        store.new_array("out", (V,))
        acc = DAddAccumulator(store, "out", N, 4, mode, k=k)
        vec = jnp.ones((V,))

        def worker():
            for _ in range(iters):
                acc.accumulate(vec)

        ts = [threading.Thread(target=worker) for _ in range(N)]
        t0 = time.perf_counter()
        [t.start() for t in ts]
        [t.join() for t in ts]
        us = (time.perf_counter() - t0) * 1e6 / iters
        model = {"gather_all": (2 * N + 1) * V,
                 "reduce_scatter": (N + 1) * V,
                 "sparse": N * 2 * P_cap + V,   # pairs actually shipped (lossy here)
                 "auto": (N + 1) * V}[mode.value]  # dense input → dense branch
        assert acc.bytes_transferred == model * iters, (
            mode, acc.bytes_transferred, model * iters)
        emit(f"accum_host_{mode.value}", us,
             f"wire_elems={acc.bytes_transferred};model_per_round={model}")
        RESULTS[f"host_{mode.value}"] = {
            "us_per_round": us, "wire_elems": acc.bytes_transferred,
            "model_per_round": model}


def spmd_layer():
    n = len(jax.devices())
    mesh = make_host_mesh(data=n)
    V = 1 << 16
    x = jnp.arange(n * V, dtype=jnp.float32).reshape(n, V)
    # sparse input (each shard has <= k nonzeros) for the sparse/auto rows
    xs = np.zeros((n, V), np.float32)
    for i in range(n):
        xs[i, (np.arange(5) * 1024 + i * 7) % V] = float(i + 1)  # ≤1 nnz per block
    xs = jnp.asarray(xs)

    for mode in ("gather_all", "reduce_scatter", "hierarchical", "sparse", "auto"):
        k = 256 if mode in ("sparse", "auto") else None
        inp = xs if mode == "sparse" else x
        expect = np.asarray(jnp.sum(inp, axis=0))
        f = jax.jit(shard_map(
            lambda v: accumulate(v[0], "data", mode, inner_axis="data", k=k)[None],
            mesh=mesh, in_specs=P("data", None), out_specs=P("data", None),
            check_vma=False))
        lowered = f.lower(inp)
        compiled = lowered.compile()
        coll = collective_bytes_from_hlo(compiled.as_text())
        out = np.asarray(f(inp))[0]
        exact = bool(np.allclose(out, expect))
        us = timeit(lambda: jax.block_until_ready(f(inp)), warmup=1, iters=5)
        emit(f"accum_spmd_{mode}", us,
             f"coll_bytes_per_dev={coll.total_bytes:.0f};"
             f"wire_bytes_per_dev={coll.total_wire_bytes:.0f};"
             f"ops={coll.total_count};exact={exact}")
        RESULTS[f"spmd_{mode}"] = {
            "us_per_call": us, "coll_bytes_per_dev": coll.total_bytes,
            "wire_bytes_per_dev": coll.total_wire_bytes, "exact": exact}


def sparsity_sweep():
    """Dense vs sparse vs auto over nnz density: wire cost + branch taken,
    and Pallas-vs-jnp sparsifier wall time at each density."""
    V, N, k = 1 << 14, 4, 512
    P_cap = pair_capacity(V, k)
    rng = np.random.default_rng(0)
    sweep = {}
    for density in (0.001, 0.01, 0.03, 0.25, 1.0):
        vecs = []
        for _ in range(N):
            v = np.zeros(V, np.float32)
            nnz = max(1, int(V * density))
            pos = rng.choice(V, size=nnz, replace=False)
            v[pos] = rng.normal(size=nnz)
            vecs.append(jnp.asarray(v))

        row = {"nnz": int(np.sum(np.asarray(vecs[0]) != 0)),
               "pair_capacity": P_cap}
        for mode in (AccumMode.REDUCE_SCATTER, AccumMode.SPARSE, AccumMode.AUTO):
            store = GlobalStore()
            store.new_array("out", (V,))
            acc = DAddAccumulator(store, "out", N, 4, mode, k=k)
            ts = [threading.Thread(target=acc.accumulate, args=(v,)) for v in vecs]
            t0 = time.perf_counter()
            [t.start() for t in ts]
            [t.join() for t in ts]
            us = (time.perf_counter() - t0) * 1e6
            row[mode.value] = {"us": us, "wire_elems": acc.bytes_transferred,
                               "branch": acc.last_mode.value}
            emit(f"accum_density{density}_{mode.value}", us,
                 f"wire_elems={acc.bytes_transferred};branch={acc.last_mode.value}")

        x = vecs[0]
        us_pl = timeit(lambda: jax.block_until_ready(
            tuple(blocked_topk_sparsify(x, k))), warmup=1, iters=5)
        us_jnp = timeit(lambda: jax.block_until_ready(
            tuple(blocked_topk_sparsify(x, k, impl="jnp"))), warmup=1, iters=5)
        row["sparsify_pallas_us"] = us_pl
        row["sparsify_jnp_us"] = us_jnp
        emit(f"sparsify_density{density}", us_pl, f"jnp_us={us_jnp:.1f}")
        sweep[str(density)] = row
    RESULTS["density_sweep"] = sweep


def main():
    enable_compile_cache()
    # the SPMD layer spans every device: 8 virtual ones on a CPU host (the
    # option only shapes the CPU backend), the chips themselves on a TPU host
    jax.config.update("jax_num_cpu_devices", 8)
    host_layer()
    spmd_layer()
    sparsity_sweep()
    out = write_bench("BENCH_accumulator.json", RESULTS)
    print(f"# wrote {out}", flush=True)


if __name__ == "__main__":
    main()
