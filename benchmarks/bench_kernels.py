"""Pallas kernel micro-benchmarks (interpret mode on CPU) vs jnp oracles.

On CPU, interpret-mode kernels are expected to be SLOWER than the fused jnp
oracle — the numbers here are correctness/overhead tracking, not TPU perf;
the TPU target engages via Mosaic on real hardware.  Derived column carries
the oracle time for the ratio.

Three hot-path sweeps additionally land in ``benchmarks/BENCH_kernels.json``:

* ``fused_density_sweep`` — the accumulator round at the bench shape
  (N=4, V=16384, k=512): one fused sparsify→scatter-add launch vs the
  historical compress→densify→add chain vs the jnp reference, across the
  same nnz densities as BENCH_accumulator.json;
* ``topk_methods`` — bitonic partial sort vs the k×(argmax→mask) loop in
  ``topk_compress`` over k_per_block ∈ {16, 64, 256};
* ``owner_memo`` — ``store.get`` with a pre-resolved :class:`OwnerHandle`
  vs re-hashing the ring on every call (S=8).
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit, write_bench
from repro.utils.compile_cache import enable_compile_cache

RESULTS = {}


def fused_density_sweep():
    """Fused one-launch accumulate vs compress→densify→add, per density."""
    from repro.core.sparse import DEFAULT_BLOCK, blocked_topk_accumulate
    N, V, k = 4, 1 << 14, 512
    rng = np.random.default_rng(0)
    sweep = {"shape": {"n": N, "v": V, "k": k, "block": DEFAULT_BLOCK}}
    for density in (0.001, 0.01, 0.03, 0.25, 1.0):
        mat = rng.normal(size=(N, V)).astype(np.float32)
        mat[rng.random((N, V)) >= density] = 0.0
        mat = jnp.asarray(mat)
        us_fused = timeit(lambda: jax.block_until_ready(
            blocked_topk_accumulate(mat, k, fused=True, impl="pallas")),
            warmup=2, iters=5)
        us_unfused = timeit(lambda: jax.block_until_ready(
            blocked_topk_accumulate(mat, k, fused=False)),
            warmup=2, iters=5)
        us_jnp = timeit(lambda: jax.block_until_ready(
            blocked_topk_accumulate(mat, k, fused=True, impl="jnp")),
            warmup=2, iters=5)
        speedup = us_unfused / max(us_fused, 1e-9)
        sweep[str(density)] = {"fused_us": us_fused, "unfused_us": us_unfused,
                               "jnp_us": us_jnp,
                               "speedup_fused_over_unfused": speedup}
        emit(f"fused_accum_density{density}", us_fused,
             f"unfused_us={us_unfused:.0f};jnp_us={us_jnp:.0f};"
             f"speedup={speedup:.2f}x")
    speeds = [row["speedup_fused_over_unfused"]
              for key, row in sweep.items() if key != "shape"]
    sweep["min_speedup"] = min(speeds)
    emit("fused_accum_min_speedup", 0.0, f"{sweep['min_speedup']:.2f}x")
    RESULTS["fused_density_sweep"] = sweep


def topk_methods_sweep():
    """Bitonic partial sort vs the argmax loop, k_per_block ∈ {16, 64, 256}."""
    from repro.kernels.topk_compress.ops import topk_compress
    V, block_v = 1 << 14, 1024
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(V,)), jnp.float32)
    sweep = {"shape": {"v": V, "block_v": block_v}}
    for k in (16, 64, 256):
        row = {}
        for method in ("argmax", "bitonic"):
            us = timeit(lambda: jax.block_until_ready(tuple(
                topk_compress(x, k_per_block=k, block_v=block_v,
                              method=method))), warmup=2, iters=5)
            row[f"{method}_us"] = us
        row["speedup_bitonic_over_argmax"] = (row["argmax_us"]
                                              / max(row["bitonic_us"], 1e-9))
        sweep[f"k{k}"] = row
        emit(f"topk_k{k}_bitonic", row["bitonic_us"],
             f"argmax_us={row['argmax_us']:.0f};"
             f"speedup={row['speedup_bitonic_over_argmax']:.2f}x")
    RESULTS["topk_methods"] = sweep


def owner_memo_bench():
    """store.get with a pre-resolved OwnerHandle vs re-hashing every call."""
    from repro.core import GlobalStore
    n_names, iters = 64, 50
    store = GlobalStore(shards=8)
    names = [f"v{i}" for i in range(n_names)]
    for n in names:
        store.def_global(n, float(len(n)))
    handles = {n: store.owner_handle(n) for n in names}

    def hashed():
        for n in names:
            store.get(n)

    def memoized():
        for n in names:
            store.get(n, owner=handles[n])

    us_hash = timeit(hashed, warmup=2, iters=iters)
    us_memo = timeit(memoized, warmup=2, iters=iters)
    speedup = us_hash / max(us_memo, 1e-9)
    RESULTS["owner_memo"] = {"shards": 8, "names": n_names,
                             "hashed_us": us_hash, "memoized_us": us_memo,
                             "speedup_memo_over_hash": speedup}
    emit("owner_memo_get", us_memo,
         f"hashed_us={us_hash:.1f};speedup={speedup:.2f}x")


def main():
    enable_compile_cache()
    rng = np.random.default_rng(0)

    from repro.kernels.flash_attention.ops import flash_attention as fa
    from repro.models.attention import naive_attention
    q = jnp.asarray(rng.normal(size=(1, 256, 2, 2, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), jnp.float32)
    us_k = timeit(lambda: jax.block_until_ready(fa(q, k, v, causal=True)), iters=3)
    ref = jax.jit(lambda a, b, c: naive_attention(a, b, c, causal=True))
    us_r = timeit(lambda: jax.block_until_ready(ref(q, k, v)), iters=3)
    emit("kernel_flash_attention", us_k, f"oracle_us={us_r:.0f}")

    from repro.kernels.accumulate.ops import accumulate as acc
    from repro.kernels.accumulate.ref import accumulate_ref
    x = jnp.asarray(rng.normal(size=(16, 65536)), jnp.float32)
    us_k = timeit(lambda: jax.block_until_ready(acc(x)), iters=3)
    refj = jax.jit(accumulate_ref)
    us_r = timeit(lambda: jax.block_until_ready(refj(x)), iters=3)
    emit("kernel_accumulate", us_k, f"oracle_us={us_r:.0f}")

    from repro.kernels.topk_compress.ops import topk_compress
    v1 = jnp.asarray(rng.normal(size=(65536,)), jnp.float32)
    us_k = timeit(lambda: jax.block_until_ready(topk_compress(v1, k_per_block=16)), iters=3)
    emit("kernel_topk_compress", us_k, "k_per_block=16")

    from repro.kernels.sparse_update.ops import scatter_add
    idx = jnp.asarray(rng.integers(0, 65536, size=(1024,)), jnp.int32)
    vals = jnp.asarray(rng.normal(size=(1024,)), jnp.float32)
    us_k = timeit(lambda: jax.block_until_ready(scatter_add(idx, vals, out_len=65536)), iters=3)
    emit("kernel_sparse_update", us_k, "M=1024,V=65536")

    from repro.kernels.kmeans_assign.ops import kmeans_assign
    pts = jnp.asarray(rng.normal(size=(8192, 64)), jnp.float32)
    ctr = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)
    us_k = timeit(lambda: jax.block_until_ready(kmeans_assign(pts, ctr)), iters=3)
    emit("kernel_kmeans_assign", us_k, "N=8192,K=32,D=64")

    from repro.kernels.ssd_scan.ops import ssd
    from repro.models.mamba import ssd_chunked
    b, T, H, P, G, N = 1, 512, 4, 32, 1, 32
    xs = jnp.asarray(rng.normal(size=(b, T, H, P)), jnp.float32) * 0.3
    dt = jnp.asarray(np.abs(rng.normal(size=(b, T, H))) * 0.3 + 0.1, jnp.float32)
    A_log = jnp.asarray(np.log(np.linspace(1.0, 4.0, H)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(b, T, G, N)), jnp.float32) * 0.3
    C = jnp.asarray(rng.normal(size=(b, T, G, N)), jnp.float32) * 0.3
    us_k = timeit(lambda: jax.block_until_ready(ssd(xs, dt, A_log, B, C, chunk=64)[0]), iters=3)
    refj = jax.jit(lambda *a: ssd_chunked(*a, chunk=64)[0])
    us_r = timeit(lambda: jax.block_until_ready(refj(xs, dt, A_log, B, C)), iters=3)
    emit("kernel_ssd_scan", us_k, f"oracle_us={us_r:.0f}")

    fused_density_sweep()
    topk_methods_sweep()
    owner_memo_bench()
    out = write_bench("BENCH_kernels.json", RESULTS)
    print(f"# wrote {out}", flush=True)


if __name__ == "__main__":
    main()
