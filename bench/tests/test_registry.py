"""BENCHMARK.json: every entry resolves to its files, every name and unit is
well formed, and a new cell, configuration or metric needs only new files
and entries."""

import json
import re

import pytest

from bench import registry
from bench.tests.conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = registry.spec(ROOT)


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["command"][1].startswith(SPEC["paths"][0] + "/")
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_resolves(cell):
    c = registry.resolve(cell)
    assert c.app.rounds(c.config) > 0
    assert c.config["limits"] and all(v >= 0 for v in c.config["limits"].values())
    assert {m["name"] for m in c.metrics["end_to_end"]} >= {"setup_s", "round_ms"}
    assert c.metrics["per_layer"], "every cell reports a per-layer metric"
    for name, reader in c.readers.items():
        assert callable(reader.read), name


def test_names_units_and_sources():
    names = [e["name"] for kind in ("configs", "workloads", "end_to_end", "per_layer")
             for e in SPEC[kind]]
    assert len(names) == len(set(names))
    for name in names:
        assert NAME.match(name), name
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and m["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/configs/")
        assert json.loads((ROOT / c["file"]).read_text())["reduced"] == c["reduced"]


def test_peaks_table_has_a_source():
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    for kind, row in peaks.items():
        assert row["source"] and row["flops_per_s"] > 0 and row["hbm_bytes_per_s"] > 0


def test_new_parts_are_found_by_name(tiny_root):
    """A configuration, a traffic mix, a cell and a metric added as files and
    entries, with no file of the harness edited."""
    bench = tiny_root / "bench"
    cfg = json.loads((bench / "configs" / "pagerank-g500-s22.json").read_text())
    cfg["scale"] = 11
    (bench / "configs" / "pagerank-small.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "host1x3.json").write_text(json.dumps(
        {"backend": "host", "n_nodes": 1, "threads_per_node": 3}))
    (bench / "metrics" / "jobs_in_window.py").write_text(
        "def read(run):\n    return len(run.jobs)\n")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "pagerank-small", "source": "test",
                            "file": "bench/configs/pagerank-small.json",
                            "reduced": ["scale"], "why": "test"})
    spec["workloads"].append({"name": "pagerank-small.host1x3", "config": "pagerank-small",
                              "traffic": "host1x3", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "jobs_in_window", "unit": "count", "better": "higher",
                              "source": "host_clock", "layer": "app", "moves": "round_ms",
                              "workloads": ["pagerank-small.host1x3"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = registry.resolve("pagerank-small.host1x3", tiny_root)
    assert cell.config["scale"] == 11
    assert cell.traffic["threads_per_node"] == 3
    assert cell.readers["jobs_in_window"].read(type("R", (), {"jobs": [1, 2]})) == 2
    assert "jobs_in_window" not in registry.resolve("pagerank-g500-s22.spmd", tiny_root).readers
