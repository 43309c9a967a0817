"""A copy of the benchmark in a temporary checkout, with its configurations
cut to sizes a CPU test run holds."""

import json
import shutil
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

# tiny sizes: every key here is a scale, none a width
TINY = {"pagerank-g500-s22": {"scale": 12},
        "kmeans-covtype": {"class_sizes": [23840, 31301, 3754, 747, 1493, 2367, 2534]}}


def copy_bench(dest: Path, sizes=TINY) -> Path:
    shutil.copy(ROOT / "BENCHMARK.json", dest)
    shutil.copytree(ROOT / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name, change in sizes.items():
        path = dest / "bench" / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg.update(change)
        path.write_text(json.dumps(cfg))
    return dest


@pytest.fixture
def tiny_root(tmp_path):
    return copy_bench(tmp_path)
