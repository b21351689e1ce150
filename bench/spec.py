"""Find a cell's parts by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix and the
metrics it reports.  Every part is a file of its own, found by its name:

  configuration   the ``file`` its ``configs`` entry names (JSON)
  traffic mix     ``bench/traffic/<traffic>.json``
  reference       ``bench/references/<reference>.py``, named in the
                  configuration file
  per-layer       ``bench/metrics/<name>.py``, else ``bench/metrics/<base>.py``
  metric          where ``<base>`` is the name up to its first ``.``
                  (``mfu.online`` and ``mfu.offline`` share ``mfu.py``)

So a later change adds a configuration, a mix, a cell or a metric with new
files and new entries, and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
import zlib
from pathlib import Path
from types import ModuleType
from typing import List

ROOT = Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict            # the configuration file's contents
    traffic_name: str
    traffic: dict           # the traffic file's contents
    end_to_end: List[dict]  # BENCHMARK.json metric entries this cell reports
    per_layer: List[dict]
    root: Path

    @property
    def bench_dir(self) -> Path:
        return self.root / "bench"


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration and traffic loaded.
    Raises ``KeyError`` for a name that ``BENCHMARK.json`` lacks."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[w["config"]]
    with open(root / entry["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)],
                root=root)


def _load_module(path: Path) -> ModuleType:
    """Import a part by its path, once per process (its jitted functions
    then compile once per process too)."""
    name = f"bench_part_{zlib.crc32(str(path.resolve()).encode()):08x}"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[name] = mod
    return sys.modules[name]


def metric_reader(cell: Cell, name: str):
    """The ``read(obs)`` function of per-layer metric ``name``."""
    mdir = cell.bench_dir / "metrics"
    for stem in (name, name.split(".")[0]):
        path = mdir / f"{stem}.py"
        if path.exists():
            return _load_module(path).read
    raise FileNotFoundError(f"no reader for metric {name!r} under {mdir}")


def reference(cell: Cell) -> ModuleType:
    """The configuration's plain reference module."""
    ref = cell.config["bench"]["reference"]
    return _load_module(cell.bench_dir / "references" / f"{ref}.py")
