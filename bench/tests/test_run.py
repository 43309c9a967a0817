"""bench/run.py: no result without a TPU, and ``correct`` false when the
timed path is broken underneath a run.  The fault tests skip only the look
for a chip and drive the rest of a run at a size a CPU holds."""

import json
import os
import shutil
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from bench import registry, run
from bench.tests.conftest import ROOT

ARGS = ["--workload", "pagerank-g500-s22.spmd", "--seed", "1", "--seconds", "1", "--trace", "0"]


def _run_script(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_exits_without_a_tpu():
    p = _run_script(ROOT)
    assert p.returncode == 2, p.stderr
    assert p.stdout == ""
    assert "needs 1 TPU" in p.stderr


def test_exits_with_only_the_benchmark(tmp_path):
    """A checkout that holds only BENCHMARK.json and bench/ has no system to run."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_script(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""


def _one_run(root, cell):
    out = run.run_cell(registry.resolve(cell, root), seed=5, seconds=0.01, trace=False,
                       devices=jax.devices()[:1], t_start=time.perf_counter())
    json.dumps(out)
    return out


@pytest.mark.parametrize("cell", ["pagerank-g500-s22.spmd", "pagerank-g500-s22.host2x2",
                                  "kmeans-covtype.spmd"])
def test_sound_run_is_correct(tiny_root, cell):
    out = _one_run(tiny_root, cell)
    assert out["correct"] and out["attempted"] >= 1 and out["failed"] == 0, out
    assert set(out["metrics"]) == {"round_ms", "setup_s"}
    assert list(out)[-1] == "checks"


def _state_unchanged(monkeypatch, app):
    from repro.core.session import SharedRef
    monkeypatch.setattr(SharedRef, "set", lambda self, value: None)


def _half_batch(monkeypatch, app):
    if app == "pagerank":
        from repro.analytics import pagerank
        credits = pagerank._credits

        def half(src, dst, ranks, out_deg, n):
            h = src.shape[0] // 2
            return credits(src[:h], dst[:h], ranks, out_deg, n)
        monkeypatch.setattr(pagerank, "_credits", half)
    else:
        from repro.analytics import kmeans
        partials = kmeans._partials

        def half(points, assign, k):
            h = points.shape[0] // 2
            return partials(points[:h], assign[:h], k)
        monkeypatch.setattr(kmeans, "_partials", half)


def _no_exchange(monkeypatch, app):
    from repro.core.session import HostWorkerCtx
    monkeypatch.setattr(HostWorkerCtx, "accumulate",
                        lambda self, name, local, mode, k: local)


def _answer_altered(monkeypatch, app):
    import importlib
    module = importlib.import_module(f"repro.analytics.{app}")
    fit = module.fit

    def altered(*args, **kwargs):
        result, sess = fit(*args, **kwargs)
        result = np.array(result)
        flat = result.reshape(result.shape[0], -1)
        flat[:, 0] += 1e-3 * np.abs(result).max()   # one value in each row
        return result, sess
    monkeypatch.setattr(module, "fit", altered)


FAULTS = [("pagerank-g500-s22.spmd", _state_unchanged), ("pagerank-g500-s22.spmd", _half_batch),
          ("pagerank-g500-s22.spmd", _answer_altered),
          ("pagerank-g500-s22.host2x2", _state_unchanged), ("pagerank-g500-s22.host2x2", _half_batch),
          ("pagerank-g500-s22.host2x2", _no_exchange), ("pagerank-g500-s22.host2x2", _answer_altered),
          ("kmeans-covtype.spmd", _state_unchanged), ("kmeans-covtype.spmd", _half_batch),
          ("kmeans-covtype.spmd", _answer_altered)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__.strip('_')}" for c, f in FAULTS])
def test_fault_is_not_correct(tiny_root, monkeypatch, cell, fault):
    app = registry.resolve(cell, tiny_root).config["app"]
    fault(monkeypatch, app)
    out = _one_run(tiny_root, cell)
    assert out["correct"] is False and out["failed"] >= 1, out
