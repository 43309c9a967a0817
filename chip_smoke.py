"""Smoke test of STEP on a TPU: the Session backends driving the paper's apps
at public-dataset scale, each checked against the app's ``fit_reference``.

    python chip_smoke.py             # phases (a)-(e) on one chip
    python chip_smoke.py --chips 4   # phases (a) and (d) over four chips

Phases, all data generated from ``--seed`` by ``repro.data``:

(a) PageRank, SPMD backend, ``mode="auto"``, 10 iterations, on a Graph500
    R-MAT graph of SNAP soc-LiveJournal1's size (4,847,571 vertices,
    68,993,773 edges);
(b) the same graph on the host backend (2 nodes x 2 threads), ``mode="auto"``;
(c) k-means, SPMD backend, Pallas assignment kernel, at the shape of UCI
    Covertype (581,012 x 54, k = 7);
(d) logistic regression, SPMD backend, reduce-scatter accumulator, on a dense
    2,097,152 x 128 f32 matrix (1 GiB);
(e) the sparse accumulator's kernels at LiveJournal's width: the fused
    sparsify->scatter-add of a (4, V) round and the blocked top-k pairs (argmax
    and bitonic selection), each bit-exact with its jnp reference.  The auto
    rule sends this graph's dense credit vectors down the dense path, so this
    is where the pairs kernels run.

Every phase prints one JSON line: its error against the reference, its
checks, and wall and compile seconds (information, not measurements of
record).  For (a) and (c) the compiled SPMD program must hold a Pallas kernel
(``tpu_custom_call``); over several chips, the SPMD input must be split
across them.  The script exits non-zero, without a result line, when no TPU
is attached, when a phase raises, or when a check fails.  The last line of a
passing run is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# SNAP soc-LiveJournal1 and UCI Covertype sizes
LJ_VERTICES, LJ_EDGES = 4_847_571, 68_993_773
COVTYPE_ROWS, COVTYPE_FEATURES, COVTYPE_CLASSES = 581_012, 54, 7
LOGREG_ROWS, LOGREG_FEATURES = 2_097_152, 128        # 1 GiB of f32
ITERS = 10
LOGREG_LR = 1e-6      # keeps sigma(x . theta) off saturation at this row count
KERNEL_THREADS, KERNEL_DENSITY = 4, 0.3   # phase (e): one host-backend round

# Largest |out - ref| / max|ref| each app may show.  PageRank has no matmul:
# only the order of f32 additions differs.  k-means runs its matmuls at full
# f32 precision, in the Pallas kernel and in the reference alike, but the two
# round differently in the last bits, so a point within that rounding of a
# tie between two centres can land elsewhere and move both.  Logistic
# regression's matmuls run at the TPU's default f32 precision (one bf16
# pass), so a last-bit difference in theta can round differently to bf16
# between the partitioned program and the one-device reference.
TOL = {"pagerank": 1e-4, "kmeans": 1e-3, "logreg": 1e-4}


def _session_class():
    from repro.core import Session

    class InspectedSession(Session):
        """A Session that compiles each SPMD program before running it and
        keeps the executable, to show what the program holds."""

        compiled = None
        compile_s = 0.0

        def spawn(self, thread_proc, *, data=(), broadcast=()):
            t0 = time.perf_counter()
            self.compiled = self.lower(thread_proc, data=data,
                                       broadcast=broadcast).compile()
            self.compile_s = time.perf_counter() - t0
            super().spawn(thread_proc, data=data, broadcast=broadcast)

    return InspectedSession


def _errors(out, ref) -> dict:
    """Max abs error and the same over max |ref|; inf where ``out`` is not
    finite."""
    import numpy as np
    out, ref = np.asarray(out, np.float64), np.asarray(ref, np.float64)
    if out.shape != ref.shape or not np.all(np.isfinite(out)):
        return {"max_abs_err": float("inf"), "rel_err": float("inf")}
    err = float(np.max(np.abs(out - ref)))
    return {"max_abs_err": err, "rel_err": err / float(np.max(np.abs(ref)))}


def _spmd_facts(sess, rows: int, n_chips: int) -> tuple[dict, dict]:
    """Facts and checks of the compiled SPMD program: whether it holds a
    Pallas kernel, and whether the first data argument is split by rows over
    every chip of the mesh."""
    compiled = sess.compiled
    sharding = compiled.input_shardings[0][0]
    per_device = int(sharding.shard_shape((rows, 1))[0])
    facts = {"compile_s": sess.compile_s,
             "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
             "devices": len(sharding.device_set), "rows_per_device": per_device}
    split = facts["devices"] == n_chips and per_device * n_chips == rows
    return facts, {"input_split": split}


def _sparse_rounds(sess, n_vertices: int) -> int:
    """Rounds of the credits accumulate that shipped pairs, read off the
    wire count: a pairs round costs 2·capacity per thread plus the V-element
    republish, a dense round (N+1)·V."""
    from repro.core.sparse import default_auto_k, pair_capacity
    n = sess.backend.n_threads
    sparse = 2 * n * pair_capacity(n_vertices, default_auto_k(n_vertices)) \
        + n_vertices
    dense = (n + 1) * n_vertices
    return (ITERS * dense - sess.wire_traffic()) // (dense - sparse)


def phase_pagerank_spmd(edges, n_vertices: int, mesh, n_chips: int) -> dict:
    from repro.analytics import pagerank
    from repro.core import SpmdBackend
    sess = _session_class()(backend=SpmdBackend(mesh=mesh))
    t0 = time.perf_counter()
    ranks, sess = pagerank.fit(edges, n_vertices, iters=ITERS, mode="auto",
                               session=sess)
    wall = time.perf_counter() - t0
    ref = pagerank.fit_reference(edges, n_vertices, iters=ITERS)
    err = _errors(ranks, ref)
    facts, checks = _spmd_facts(sess, edges.shape[0], n_chips)
    return {"phase": "a", "app": "pagerank", "backend": "spmd",
            **err, "tol": TOL["pagerank"], "wall_s": wall,
            "sparse_rounds": _sparse_rounds(sess, n_vertices), **facts,
            "checks": {"rel_err": err["rel_err"] <= TOL["pagerank"],
                       "pallas_kernel": facts["tpu_custom_call"], **checks}}


def phase_pagerank_host(edges, n_vertices: int) -> dict:
    from repro.analytics import pagerank
    from repro.core import Session
    sess = Session(backend="host", n_nodes=2, threads_per_node=2)
    t0 = time.perf_counter()
    ranks, sess = pagerank.fit(edges, n_vertices, iters=ITERS, mode="auto",
                               session=sess)
    wall = time.perf_counter() - t0
    ref = pagerank.fit_reference(edges, n_vertices, iters=ITERS)
    err = _errors(ranks, ref)
    return {"phase": "b", "app": "pagerank", "backend": "host",
            **err, "tol": TOL["pagerank"], "wall_s": wall,
            "sparse_rounds": _sparse_rounds(sess, n_vertices),
            "checks": {"rel_err": err["rel_err"] <= TOL["pagerank"]}}


def phase_kmeans_spmd(x, k: int, mesh, n_chips: int, seed: int) -> dict:
    from repro.analytics import kmeans
    from repro.core import SpmdBackend
    sess = _session_class()(backend=SpmdBackend(mesh=mesh))
    t0 = time.perf_counter()
    centers, sess = kmeans.fit(x, k, iters=ITERS, seed=seed, use_kernel=True,
                               session=sess)
    wall = time.perf_counter() - t0
    ref = kmeans.fit_reference(x, k, iters=ITERS, seed=seed)
    err = _errors(centers, ref)
    facts, checks = _spmd_facts(sess, x.shape[0], n_chips)
    return {"phase": "c", "app": "kmeans", "backend": "spmd",
            **err, "tol": TOL["kmeans"], "wall_s": wall, **facts,
            "checks": {"rel_err": err["rel_err"] <= TOL["kmeans"],
                       "pallas_kernel": facts["tpu_custom_call"], **checks}}


def phase_logreg_spmd(x, y, mesh, n_chips: int) -> dict:
    from repro.analytics import logreg
    from repro.core import SpmdBackend
    sess = _session_class()(backend=SpmdBackend(mesh=mesh))
    t0 = time.perf_counter()
    theta, sess = logreg.fit(x, y, iters=ITERS, lr=LOGREG_LR,
                             mode="reduce_scatter", session=sess)
    wall = time.perf_counter() - t0
    ref = logreg.fit_reference(x, y, iters=ITERS, lr=LOGREG_LR)
    err = _errors(theta, ref)
    facts, checks = _spmd_facts(sess, x.shape[0], n_chips)
    return {"phase": "d", "app": "logreg", "backend": "spmd",
            **err, "tol": TOL["logreg"], "wall_s": wall,
            "bytes": int(x.nbytes), **facts,
            "checks": {"rel_err": err["rel_err"] <= TOL["logreg"], **checks}}


def phase_sparse_kernels(n: int, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.sparse import (blocked_topk_accumulate,
                                   blocked_topk_sparsify, default_auto_k,
                                   densify)
    kv, kx = jax.random.split(jax.random.key(seed))
    shape = (KERNEL_THREADS, n)
    mat = jnp.where(jax.random.uniform(kx, shape) < KERNEL_DENSITY,
                    jax.random.normal(kv, shape), 0.0)
    k = default_auto_k(n)          # per-block quota 256: bitonic selection
    t0 = time.perf_counter()
    fused = blocked_topk_accumulate(mat, k)
    jax.block_until_ready(fused)
    wall = time.perf_counter() - t0
    checks = {"fused_bitexact": bool(jnp.array_equal(
        fused, blocked_topk_accumulate(mat, k, impl="jnp")))}
    # a budget of 16 per block takes the argmax loop
    for name, budget in (("argmax", 16 * -(-n // 1024)), ("bitonic", k)):
        pairs = blocked_topk_sparsify(mat[0], budget)
        ref = blocked_topk_sparsify(mat[0], budget, impl="jnp")
        checks[f"topk_{name}_bitexact"] = bool(np.array_equal(
            np.asarray(pairs.densify()), np.asarray(densify(ref.idx, ref.vals, n))))
    return {"phase": "e", "app": "sparse-accumulate kernels", "shape": shape,
            "density": KERNEL_DENSITY, "wall_s": wall, "checks": checks}


def run_phases(n_chips: int, seed: int, devices) -> bool:
    from repro.core import make_mesh
    from repro.data import kmeans_dataset, logreg_dataset, rmat_graph

    mesh = make_mesh((n_chips,), ("data",), devices=devices[:n_chips])

    def report(result: dict) -> bool:
        print(json.dumps(result), flush=True)
        return all(result["checks"].values())

    ok = True
    t0 = time.perf_counter()
    # shard_map splits rows evenly: keep a multiple of the chip count
    edges = rmat_graph(LJ_VERTICES, LJ_EDGES - LJ_EDGES % n_chips, seed=seed)
    print(f"# graph: {LJ_VERTICES} vertices, {edges.shape[0]} edges, "
          f"generated in {time.perf_counter() - t0:.1f} s", flush=True)
    ok &= report(phase_pagerank_spmd(edges, LJ_VERTICES, mesh, n_chips))
    if n_chips == 1:
        ok &= report(phase_pagerank_host(edges, LJ_VERTICES))
    del edges
    if n_chips == 1:
        x, _, _ = kmeans_dataset(COVTYPE_ROWS, COVTYPE_FEATURES,
                                 COVTYPE_CLASSES, seed=seed)
        ok &= report(phase_kmeans_spmd(x, COVTYPE_CLASSES, mesh, n_chips, seed))
        del x
    x, y, _ = logreg_dataset(LOGREG_ROWS, LOGREG_FEATURES, seed=seed)
    ok &= report(phase_logreg_spmd(x, y, mesh, n_chips))
    del x, y
    if n_chips == 1:
        ok &= report(phase_sparse_kernels(LJ_VERTICES, seed))
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases (a)-(e); 4: phases (a) and (d) over 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU attached (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} attached",
              file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro.utils.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"# jax {jax.__version__} on {device}, compile cache {cache}",
          flush=True)
    t0 = time.perf_counter()
    ok = run_phases(args.chips, args.seed, devices)
    print(f"# all phases in {time.perf_counter() - t0:.1f} s", flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
