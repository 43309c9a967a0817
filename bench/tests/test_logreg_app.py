"""The sparse logistic regression cell at CPU sizes: its rows, its job
against the plain reference and the bfloat16 control, its compulsory work,
and the readers of the fused kernel and of the ``accumulate.fused`` span."""

import ast
import json

import jax
import numpy as np
import pytest

from bench import registry, run
from bench.tests.conftest import ROOT, TINY, copy_bench
from bench.trace import Summary

CELL = "logreg-criteo.host2x2"
# 4 threads of 20 rounds of 64 rows, over 2**15 hashed features: the rounds
# take the pairs path as the full size's do
SMALL = {"rows": 4 * 20 * 64 + 3, "n_features": 1 << 15, "batch": 64, "iters": 20,
         "lr": 0.05 / 256}


@pytest.fixture
def small_root(tmp_path):
    return copy_bench(tmp_path, {**TINY, "logreg-criteo": SMALL})


def reader(name):
    return registry.load_module(ROOT / "bench" / "metrics" / f"{name}.py")


def test_program_agrees_and_control_fails(small_root):
    """At a size a test run holds, ``fit`` through Session meets the limit
    and the reference with bfloat16 margins and gradients does not."""
    from bench.control import readings
    r = readings(registry.resolve(CELL, small_root), 7, jax.devices()[:1], program=True)
    for name, limit in r["limits"].items():
        assert r["program"][name] <= limit, r
        assert r["control"][name] > limit, r


def test_rounds_take_the_pairs_path(small_root):
    cell = registry.resolve(CELL, small_root)
    from repro.core import telemetry
    monitor = run.Monitor()
    try:
        data = cell.app.make_data(cell.config, 2**33 + 1)
        job, _ = run.run_job(cell, data, 1, jax.devices()[:1], True, monitor)
    finally:
        monitor.close()
        telemetry.reset()               # the harness leaves its sessions armed
    v = SMALL["n_features"]
    assert job.wire == SMALL["iters"] * (2 * 4 * (v // 4) + v)
    assert sum(s["name"] == "accumulate.fused" for s in job.spans) == SMALL["iters"]


def test_reference_imports_nothing_of_the_program():
    tree = ast.parse((ROOT / "bench" / "apps" / "logreg.py").read_text())
    run_job = next(f for f in tree.body if isinstance(f, ast.FunctionDef) and f.name == "run_job")
    inside = {id(n) for n in ast.walk(run_job)}
    for node in ast.walk(tree):
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            names = [getattr(node, "module", None) or ""] + [a.name for a in node.names]
            if any(m.startswith("repro") for m in names):
                assert id(node) in inside, ast.dump(node)


def test_rows_have_the_source_shape_and_are_the_seeds():
    cell = registry.resolve(CELL)
    cfg = {**cell.config, "rows": 50_000}
    big = 2**31 + 12345
    a, b, c = (cell.app.make_data(cfg, s) for s in (big, big, big + 1))
    assert a["idx"].shape == a["val"].shape == (50_000, 39)
    assert np.array_equal(a["idx"], b["idx"]) and not np.array_equal(a["idx"], c["idx"])
    idx = np.asarray(a["idx"])
    assert idx.min() >= 0 and idx.max() < 1_000_000
    assert np.all(np.asarray(a["val"]) == 1.0)
    assert float(np.mean(a["y"])) == pytest.approx(0.256, abs=0.01)
    # log-uniform values: a 3-value field's first value takes log(2)/log(3)
    # of its rows; a 10**7-value field's most common value few of them
    field9, field3 = idx[:, 13 + 8], idx[:, 13 + 2]
    assert np.mean(field9 == np.bincount(field9).argmax()) == pytest.approx(0.631, abs=0.01)
    assert np.mean(field3 == np.bincount(field3).argmax()) < 0.06


def test_partition_matches_the_host_backend():
    from repro.data.pipeline import partition_rows
    app = registry.resolve(CELL).app
    for n_rows in (11_460_154, 17, 4 * 9):
        assert app.partition_starts(n_rows, 4) == [partition_rows(n_rows, t, 4)[0]
                                                    for t in range(4)]


def test_compulsory_work_on_known_shapes():
    cell = registry.resolve(CELL)
    assert cell.app.round_work(cell.config) == {"bytes": 16_384 * (39 * 8 + 4),
                                                "flops": 4 * 16_384 * 39}
    assert cell.app.round_work(cell.config) == {"bytes": 5_177_344, "flops": 2_555_904}
    work = cell.app.kernel_work(cell.config, "fused_topk_scatter")
    assert work == {"bytes": 20_000_000, "flops": 3_000_000}
    assert work["bytes"] / 819e9 == pytest.approx(24.4e-6, rel=1e-3)
    with pytest.raises(KeyError):
        cell.app.kernel_work(cell.config, "kmeans_assign")


def test_config_states_the_deployment():
    cfg = registry.resolve(CELL).config
    assert cfg["reduced"] == ["rows"]
    assert cfg["rows"] * cfg["nodes"] <= 45_840_617 < (cfg["rows"] + 1) * cfg["nodes"]
    assert cfg["integer_fields"] + len(cfg["categorical_vocab"]) == cfg["fields"] == 39
    per_thread = cfg["rows"] // cfg["threads"]
    assert cfg["iters"] == per_thread // cfg["batch"] == 699
    assert cfg["lr"] == 0.05 / (cfg["threads"] * cfg["batch"])


def make_run(cell, jobs_spans, op_s=None, traced_jobs=None, rounds=10):
    jobs = [run.Job(rounds=rounds, wire=0, spans=spans) for spans in jobs_spans]
    summary = None if op_s is None else Summary(window_s=1.0, busy_s=0.5, n_devices=1,
                                                op_s=op_s)
    return run.Run(cell=cell, peaks=json.loads((ROOT / "bench" / "peaks.json").read_text())
                   ["TPU v5 lite"], setup_s=0.0, window_s=1.0, jobs=jobs, trace=summary,
                   traced_jobs=len(jobs) if traced_jobs is None else traced_jobs)


def span(name, dur_us):
    return {"name": name, "cat": "accumulate-round", "ph": "X", "ts": 0.0, "dur": dur_us,
            "pid": 0, "tid": 0}


def test_new_readers_read_none_without_their_sources():
    cell = registry.resolve(CELL)
    for name in ("fused_scatter_roofline", "accum_fused_ms"):
        assert reader(name).read(make_run(cell, [[span("accumulate.round", 900)]])) is None
        assert reader(name).read(make_run(cell, [[span("accumulate.round", 900)]],
                                          op_s={"fusion.1": 0.1})) is None
    # spans but no kernel in the trace, or no trace at all
    fused = [span("accumulate.fused", 500)]
    assert reader("fused_scatter_roofline").read(make_run(cell, [fused])) is None
    assert reader("fused_scatter_roofline").read(
        make_run(cell, [fused], op_s={"fusion.1": 0.1})) is None
    # a cell whose adapter names no such kernel
    other = registry.resolve("kmeans-covtype.spmd")
    assert reader("fused_scatter_roofline").read(
        make_run(other, [fused], op_s={"fused_topk_scatter.1": 0.1})) is None


def test_new_readers_by_hand():
    cell = registry.resolve(CELL)
    job = [span("accumulate.round", 3000), span("accumulate.fused", 1000)] * 2
    op_s = {"%fused_topk_scatter.1 = f32[977,8,128]{2,1,0:T(8,128)} custom-call(%copy)": 0.004,
            # a consumer names the kernel as an operand: not the kernel
            "%slice.2 = f32[1000000]{0} slice(%fused_topk_scatter.1)": 0.5,
            "fusion.3": 1.0}
    r = make_run(cell, [job, job, job], op_s=op_s, traced_jobs=2)
    # four calls in the two traced jobs took 4 ms: 1 ms a call against 24.42 µs
    assert reader("fused_scatter_roofline").read(r) == pytest.approx(
        100 * 20_000_000 / 819e9 / 1e-3)
    assert reader("accum_fused_ms").read(r) == pytest.approx(1.0)


def test_listed_for_the_new_cell_alone():
    spec = registry.spec(ROOT)
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    for name in ("fused_scatter_roofline", "accum_fused_ms"):
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "round_ms"
    cell = registry.resolve(CELL)
    assert {m["name"] for m in cell.metrics["per_layer"]} == {
        "round_device_ms", "round_roofline", "job_prep_ms", "cache_misses_per_job",
        "wire_elems_per_round", "device_idle", "fused_scatter_roofline", "accum_fused_ms"}
    assert (cell.chips, cell.traffic_name) == (1, "host2x2")
