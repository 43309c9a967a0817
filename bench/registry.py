"""Find a cell's parts by name.  Nothing here names a cell, a configuration or
a metric: ``BENCHMARK.json`` does, and each part is a file of its own.

* configuration ``<c>``: ``BENCHMARK.json`` ``configs`` entry -> its ``file``;
  the file's ``app`` names the adapter ``bench/apps/<app>.py``;
* traffic ``<t>``: ``bench/traffic/<t>.json``;
* metric ``<m>``: a reader ``bench/metrics/<m>.py`` with ``read(run)``.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Dict, List

ROOT = Path(__file__).resolve().parents[1]      # the checkout


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    app: ModuleType
    metrics: Dict[str, List[dict]]              # "end_to_end" / "per_layer" -> entries
    readers: Dict[str, ModuleType]              # metric name -> reader


def load_module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(f"bench_part_{path.parent.name}_{path.stem}",
                                                  path)
    if spec is None or spec.loader is None:
        raise ImportError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, root: Path = ROOT) -> Cell:
    bench = spec(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / entry["file"]).read_text())
    parts = root / "bench"
    traffic = json.loads((parts / "traffic" / f"{cell['traffic']}.json").read_text())
    metrics = {kind: [m for m in bench[kind] if applies(m, name)]
               for kind in ("end_to_end", "per_layer")}
    readers = {m["name"]: load_module(parts / "metrics" / f"{m['name']}.py")
               for kind in metrics.values() for m in kind}
    return Cell(name=name, chips=cell["chips"], config_name=cell["config"], config=config,
                traffic_name=cell["traffic"], traffic=traffic,
                app=load_module(parts / "apps" / f"{config['app']}.py"),
                metrics=metrics, readers=readers)
