"""The readers of the program's own spans and scopes: by hand on synthetic
runs, None on a program without them, listed in BENCHMARK.json for the
cells where they find something to read, and on a tiny traced job."""

import jax
import pytest

from bench import program, registry, run
from bench.trace import Summary
from bench.tests.conftest import ROOT

NEW = {"spmd_prepare_ms": ["pagerank-g500-s22.spmd", "kmeans-covtype.spmd"],
       "credits_gather_ms": ["pagerank-g500-s22.spmd"],
       "credits_scatter_ms": ["pagerank-g500-s22.spmd"],
       "accum_sync_ms": ["pagerank-g500-s22.host2x2"]}


def reader(name):
    return registry.load_module(ROOT / "bench" / "metrics" / f"{name}.py")


def span(name, dur_us, **args):
    s = {"name": name, "cat": "x", "ph": "X", "ts": 0.0, "dur": dur_us, "pid": 0, "tid": 0}
    if args:
        s["args"] = args
    return s


def make_run(jobs_spans, op_s=None, traced_jobs=None, rounds=10):
    jobs = [run.Job(rounds=rounds, wire=0, spans=spans) for spans in jobs_spans]
    summary = None if op_s is None else Summary(window_s=1.0, busy_s=0.5, n_devices=1,
                                                op_s=op_s)
    return run.Run(cell=None, peaks={}, setup_s=0.0, window_s=1.0, jobs=jobs, trace=summary,
                   traced_jobs=len(jobs) if traced_jobs is None else traced_jobs)


@pytest.mark.parametrize("name", sorted(NEW))
def test_listed_for_the_cells_that_have_it(name):
    entry = {m["name"]: m for m in registry.spec(ROOT)["per_layer"]}[name]
    assert entry["workloads"] == NEW[name] and entry["moves"] == "round_ms"
    for cell in (w["name"] for w in registry.spec(ROOT)["workloads"]):
        assert (name in registry.resolve(cell).readers) == (cell in NEW[name]), cell


def test_spmd_prepare_ms_by_hand():
    stages = [span("spmd.trace", 3000), span("spmd.lower", 2000), span("spmd.compile", 1000),
              span("spmd.run", 50000), span("spmd.writeback", 500), span("session.run", 60000)]
    r = make_run([stages, stages[:3] + stages[:3]])
    # (6 + 12) ms over two jobs
    assert reader("spmd_prepare_ms").read(r) == pytest.approx(9.0)
    # the unstaged join's spans read nothing
    old = [span("spmd.trace", 3000), span("spmd.execute", 50000)]
    assert reader("spmd_prepare_ms").read(make_run([old])) is None
    assert reader("spmd_prepare_ms").read(make_run([[]])) is None


def test_accum_sync_ms_by_hand():
    spans = [span("accumulate.round", 9000), span("accumulate.sync", 6000),
             span("accumulate.round", 7000), span("accumulate.sync", 2000)]
    r = make_run([spans, [span("accumulate.sync", 1000)]])
    assert reader("accum_sync_ms").read(r) == pytest.approx(3.0)
    assert reader("accum_reduce_ms").read(r) == pytest.approx(8.0)
    assert reader("accum_sync_ms").read(make_run([[span("accumulate.round", 9000)]])) is None


SCOPES = {"fusion.1": "jit(body)/while/body/pagerank.round/pagerank.gather/div",
          "fusion.2": "jit(body)/while/body/pagerank.round/pagerank.scatter/scatter-add",
          "sort.3": "jit(body)/while/body/pagerank.round/pagerank.scatter/scatter-add",
          "fusion.4": "jit(body)/while/body/pagerank.round/accumulate.auto/add"}


def test_credits_by_hand():
    compile_span = span("spmd.compile", 1000, hlo_scopes=SCOPES)
    op_s = {"%fusion.1 = f32[64]{0:T(1024)} fusion(f32[8]{0:T(1024)} %p.1)": 0.020,
            "fusion.2": 0.015, "sort.3": 0.005, "fusion.4": 0.001,
            "%while.1 = (s32[], f32[8]{0}) while(%tuple)": 0.045,   # holds the body ops
            "copy.7": 0.002}
    r = make_run([[compile_span], [compile_span]], op_s=op_s, traced_jobs=1)
    # one traced job of 10 rounds
    assert reader("credits_gather_ms").read(r) == pytest.approx(2.0)
    assert reader("credits_scatter_ms").read(r) == pytest.approx(2.0)
    for name in ("credits_gather_ms", "credits_scatter_ms"):
        # no scope map (a program without it), or no trace: nothing to read
        assert reader(name).read(make_run([[span("spmd.compile", 1)]], op_s=op_s)) is None
        assert reader(name).read(make_run([[compile_span]])) is None
    assert program.scope_ms_per_round(r, "kmeans.assign") is None


@pytest.mark.parametrize("cell", ["pagerank-g500-s22.spmd", "pagerank-g500-s22.host2x2"])
def test_readers_on_a_tiny_traced_job(tiny_root, cell):
    """A tiny armed job through the harness's own run_job: its spans give
    the program-span readings, and its scope map names its ops."""
    from repro.core import telemetry
    c = registry.resolve(cell, tiny_root)
    monitor = run.Monitor()
    try:
        data = c.app.make_data(c.config, 3)
        job, _ = run.run_job(c, data, 3, jax.devices()[:1], True, monitor)
    finally:
        monitor.close()
        telemetry.reset()               # the harness leaves its sessions armed
    r = run.Run(cell=c, peaks={}, setup_s=0.0, window_s=1.0, jobs=[job], traced_jobs=1)
    readings = {name: rd.read(r) for name, rd in c.readers.items() if name in NEW}
    if cell.endswith(".spmd"):
        assert readings["spmd_prepare_ms"] > 0
        scopes = program.op_scopes(r).values()
        for scope in ("pagerank.gather", "pagerank.scatter", "accumulate.auto_decide"):
            assert any(scope in s.split("/") for s in scopes), scope
        # every op its own second: each half reads its ops' share of the round
        r.trace = Summary(window_s=1.0, busy_s=1.0, n_devices=1,
                          op_s={op: 1.0 for op in program.op_scopes(r)})
        gather = reader("credits_gather_ms").read(r)
        scatter = reader("credits_scatter_ms").read(r)
        assert gather > 0 and scatter > 0
        assert gather + scatter <= 1e3 * len(r.trace.op_s) / r.traced_rounds
    else:
        assert 0 < readings["accum_sync_ms"] <= reader("accum_reduce_ms").read(r)
        assert readings == {"accum_sync_ms": readings["accum_sync_ms"]}
