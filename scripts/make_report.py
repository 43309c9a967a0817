"""Generate the EXPERIMENTS.md §Dry-run and §Roofline tables from the JSONs.

    PYTHONPATH=src python scripts/make_report.py [--out experiments/dryrun]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

ARCH_ORDER = ["deepseek-v3-671b", "moonshot-v1-16b-a3b", "starcoder2-3b",
              "qwen3-4b", "qwen2-72b", "qwen3-1.7b", "llama-3.2-vision-90b",
              "zamba2-2.7b", "hubert-xlarge", "mamba2-2.7b"]
SHAPE_ORDER = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def load(out_dir):
    from repro.configs import SHAPES, get_arch
    from repro.launch.roofline import model_flops

    recs, skips = {}, {}
    for fn in os.listdir(out_dir):
        if not fn.endswith(".json"):
            continue
        r = json.load(open(os.path.join(out_dir, fn)))
        key = (r["arch"], r["shape"], r["mesh"], r.get("variant", "baseline"))
        if r.get("skipped"):
            skips[key] = r
        else:
            # recompute MODEL_FLOPS/useful with the *current* formula so all
            # rows are mutually consistent regardless of when they were run
            mf = model_flops(get_arch(r["arch"]), SHAPES[r["shape"]])
            r["model_flops_total"] = mf
            if r["hlo_flops"]:
                r["useful_ratio"] = (mf / r["n_devices"]) / r["hlo_flops"]
            recs[key] = r
    return recs, skips


def fmt_bytes(b):
    return f"{b/2**30:.2f}"


def improvement_note(r):
    """One sentence on what moves the dominant term down."""
    b = r["bottleneck"]
    shape = r["shape"]
    if b == "memory":
        if "decode" in shape or "long" in shape:
            return "decode is cache-read bound: shrink per-token cache reads (MLA/SSM already minimal; quantize KV to int8)"
        return "cut HBM traffic: fuse attention internals into the Pallas flash kernel (keeps scores in VMEM) + bf16 intermediates"
    if b == "collective":
        return "cut TP collectives: bf16 all-reduce, sequence-sharded activations (AG/RS decomposition), hierarchical cross-pod reduce"
    return "compute-bound: raise MXU utilisation (bigger per-device tiles, skip causal-masked blocks, fewer remat recomputes)"


def export_check_report(path):
    """Run the four analytics apps under an armed checker plus the seeded
    race from examples/race_demo.py, and export one findings JSON — the
    artifact showing zero findings on real apps and a caught seeded race."""
    import numpy as np

    from repro.analytics import kmeans, logreg, nmf, pagerank
    from repro.check import Checker
    from repro.core import Session

    rng = np.random.default_rng(0)
    x = rng.normal(size=(128, 16)).astype(np.float32)
    y = (rng.random(128) > 0.5).astype(np.float32)
    pts = rng.normal(size=(96, 4)).astype(np.float32)
    r = np.abs(rng.normal(size=(32, 16))).astype(np.float32)
    edges = np.stack([rng.integers(0, 24, 80), rng.integers(0, 24, 80)],
                     axis=1).astype(np.int32)

    report = {"apps": {}, "seeded_race": None}
    for name, call in (
            ("logreg", lambda s: logreg.fit(x, y, iters=3, session=s)),
            ("kmeans", lambda s: kmeans.fit(pts, 3, iters=3, session=s)),
            ("nmf", lambda s: nmf.fit(r, 4, iters=3, session=s)),
            ("pagerank", lambda s: pagerank.fit(edges, 24, iters=3, session=s))):
        sess = Session(backend="host", n_nodes=2, threads_per_node=2,
                       shards=8, check=True)
        try:
            call(sess)
            report["apps"][name] = sess.checker.report()
        finally:
            sess.checker.disable()

    ck = Checker(enabled=True)
    try:
        sess = Session(backend="host", n_nodes=1, threads_per_node=2,
                       check=ck)
        import jax.numpy as jnp
        counter = sess.def_global("counter", jnp.float32(0))

        def proc(ctx):
            for _ in range(4):
                v = counter.get()
                counter.set(v + jnp.float32(ctx.tid + 1))
            return None

        sess.run(proc)
        report["seeded_race"] = ck.report()
    finally:
        ck.disable()

    with open(path, "w") as f:
        json.dump(report, f, indent=2)
    clean = all(rep["count"] == 0 for rep in report["apps"].values())
    caught = report["seeded_race"]["count"] > 0
    print(f"wrote {path}: apps clean={clean}, "
          f"seeded race caught={caught} "
          f"({report['seeded_race']['count']} finding(s))")


def export_sample_trace(path):
    """Run a small 2-thread logreg fit with tracing armed and export the
    Chrome-trace JSON — the artifact to drag into https://ui.perfetto.dev."""
    import numpy as np

    from repro.analytics import logreg
    from repro.core import Session

    rng = np.random.default_rng(0)
    x = rng.normal(size=(128, 32)).astype(np.float32)
    y = (rng.random(128) > 0.5).astype(np.float32)
    sess = Session(backend="host", n_nodes=2, threads_per_node=1, trace=True)
    try:
        logreg.fit(x, y, iters=5, session=sess)
        sess.tracer.export(path)
        snap = sess.tracer.snapshot()
        print(f"wrote {path}: {snap['events']} events, "
              f"categories {sorted(snap['spans_by_category'])}")
    finally:
        sess.tracer.disable()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--export-trace", default=None, metavar="PATH",
                    help="run a traced 2-thread logreg fit and write the "
                         "Perfetto-loadable trace JSON to PATH, then exit")
    ap.add_argument("--export-check", default=None, metavar="PATH",
                    help="run the four analytics apps and a seeded race "
                         "under an armed checker and write the findings "
                         "JSON to PATH, then exit")
    args = ap.parse_args()
    if args.export_trace:
        export_sample_trace(args.export_trace)
        return
    if args.export_check:
        export_check_report(args.export_check)
        return
    if not os.path.isdir(args.out):
        print(f"# no dry-run records at {args.out}; skipping dryrun/roofline")
        return
    recs, skips = load(args.out)

    print("### Dry-run matrix (lower+compile status, bytes/device)\n")
    print("| arch | shape | single-pod (256) | multi-pod (512) |")
    print("|---|---|---|---|")
    for a in ARCH_ORDER:
        for s in SHAPE_ORDER:
            cells = []
            for mesh in ("single", "multi"):
                k = (a, s, mesh, args.variant)
                if k in recs:
                    r = recs[k]
                    cells.append(f"OK — peak {fmt_bytes(r['peak_bytes'])} GiB, "
                                 f"{r['collective_by_op'] and '+'.join(sorted(r['collective_by_op'])) or 'no-coll'}")
                elif k in skips:
                    cells.append(f"SKIP ({skips[k]['reason'].split(':')[0]})")
                else:
                    cells.append("—")
            print(f"| {a} | {s} | {cells[0]} | {cells[1]} |")

    print("\n### Roofline (single-pod, per device, baseline)\n")
    print("| arch | shape | compute (ms) | memory (ms) | collective (ms) | bottleneck | MODEL_FLOPS | useful | peak GiB | note |")
    print("|---|---|---|---|---|---|---|---|---|---|")
    for a in ARCH_ORDER:
        for s in SHAPE_ORDER:
            k = (a, s, "single", args.variant)
            if k in recs:
                r = recs[k]
                print(f"| {a} | {s} | {r['compute_s']*1e3:.2f} | {r['memory_s']*1e3:.2f} | "
                      f"{r['collective_s']*1e3:.2f} | **{r['bottleneck']}** | "
                      f"{r['model_flops_total']:.2e} | {r['useful_ratio']:.3f} | "
                      f"{fmt_bytes(r['peak_bytes'])} | {improvement_note(r)} |")
            elif k in skips:
                print(f"| {a} | {s} | — | — | — | skipped | — | — | — | {skips[k]['reason']} |")


if __name__ == "__main__":
    main()
