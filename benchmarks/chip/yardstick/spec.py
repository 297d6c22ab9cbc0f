"""Resolve a cell of ``BENCHMARK.json`` to its files, by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own:

* ``configs/<config>.json`` -- the configuration as it is run (sizes,
  guarantees, the deployment it stands for);
* ``configs/<config>.py`` -- its plain reference and its ``run(ctx)``;
* ``traffic/<traffic>.json`` -- the traffic mix's parameters;
* ``metrics/<metric>.py`` -- the reader of one per-layer metric, a
  ``read(ctx) -> float | None``.

A later change adds a cell, a configuration or a metric by adding files and
``BENCHMARK.json`` entries; no existing file has to change.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from typing import List, Optional

#: benchmarks/chip
HERE = Path(__file__).resolve().parents[1]
#: the checkout's root, where BENCHMARK.json lives
ROOT = HERE.parents[1]


def load_module(path: Path, name: Optional[str] = None):
    """Import a file whose name need not be a Python identifier."""
    spec = importlib.util.spec_from_file_location(
        name or "chipbench_" + path.stem.replace("-", "_").replace(".", "_"),
        path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    entry: dict               # the workloads entry
    config: dict              # configs/<config>.json
    traffic: dict             # traffic/<traffic>.json
    end_to_end: List[dict]    # metrics this cell reports with --trace 0
    per_layer: List[dict]     # metrics this cell reports with --trace 1

    def driver(self):
        """The configuration's module (``configs/<config>.py``)."""
        return load_module(HERE / "configs" / f"{self.entry['config']}.py")


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def resolve(cell_name: str, bench: Optional[dict] = None,
            root: Path = ROOT) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"unknown workload {cell_name!r}; BENCHMARK.json "
                       f"has {sorted(cells)}")
    entry = cells[cell_name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[entry["config"]]
    with open(root / cfg_entry["file"]) as f:
        config = json.load(f)
    with open(HERE / "traffic" / f"{entry['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _reports(m, cell_name)]
    layer = [m for m in bench["per_layer"] if cell_name in m["workloads"]]
    return Cell(cell_name, int(entry["chips"]), entry, config, traffic, e2e,
                layer)


def metric_reader(name: str):
    """``metrics/<name>.py``'s ``read``."""
    return load_module(HERE / "metrics" / f"{name}.py").read
