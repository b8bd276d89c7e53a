"""``BENCHMARK.json`` and the files it names, resolved by name.

A cell is one entry of ``workloads``.  Everything that belongs to it is a
file of its own, found from the names in that entry:

- the configuration: the ``file`` of the ``configs`` entry it names;
- the configuration's float32 reference: ``bench/references/<config>.py``
  where that file exists, a module with a ``Reference`` class and
  ``init_params``; else ``bench/reference.py`` (the dense Qwen decoder);
- the traffic: ``bench/traffic/<traffic>.json``;
- its correctness limits: ``bench/workloads/<cell>.json``;
- each per-layer metric: a reader ``bench/metrics/<metric>.py``.

So a later cell, configuration, reference for another architecture or
metric is added as files and entries, without editing a file that is
there.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import re
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List

ROOT = Path(__file__).resolve().parents[1]
DEFAULT_REFERENCE = "bench.reference"
NAME_RE = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT_RE = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


def _json(path: Path):
    with open(path) as f:
        return json.load(f)


_MODULES: Dict[Path, ModuleType] = {}


def _module(path: Path, name: str) -> ModuleType:
    """The module of the file ``path``, executed once per process."""
    path = path.resolve()
    if path not in _MODULES:
        spec = importlib.util.spec_from_file_location(
            name + re.sub(r"\W", "_", path.stem), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


@dataclass
class Cell:
    name: str
    entry: dict                 # the ``workloads`` entry
    config: dict                # the configuration file's contents
    traffic: dict               # bench/traffic/<traffic>.json
    limits: Dict[str, float]    # bench/workloads/<cell>.json "limits"
    end_to_end: List[dict] = field(default_factory=list)
    per_layer: List[dict] = field(default_factory=list)
    root: Path = ROOT

    @property
    def model(self) -> dict:
        return self.config["model"]

    def reader(self, metric: str) -> Callable:
        """``read`` of ``bench/metrics/<metric>.py``."""
        return _module(self.root / "bench" / "metrics" / f"{metric}.py",
                       "bench_metric_").read

    def reference(self) -> ModuleType:
        """The configuration's reference module:
        ``bench/references/<config>.py`` where it exists, else
        ``bench.reference``."""
        path = (self.root / "bench" / "references"
                / f"{self.entry.get('config')}.py")
        if path.is_file():
            return _module(path, "bench_reference_")
        return importlib.import_module(DEFAULT_REFERENCE)


def load(root: Path = ROOT) -> dict:
    return _json(Path(root) / "BENCHMARK.json")


def applies(metric: dict, cell: str, bench: dict) -> bool:
    """Whether ``metric`` is reported in ``cell``: the cells its
    ``workloads`` list, or else every cell that reports the end-to-end
    metric it moves (a per-layer metric) or every cell (end-to-end)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    if moves is None:
        return True
    target = next(m for m in bench["end_to_end"] if m["name"] == moves)
    return applies(target, cell, bench)


def resolve(name: str, root: Path = ROOT) -> Cell:
    root = Path(root)
    bench = load(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(by_name)}")
    entry = by_name[name]
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    return Cell(
        name=name, entry=entry,
        config=_json(root / cfg_entry["file"]),
        traffic=_json(root / "bench" / "traffic" / f"{entry['traffic']}.json"),
        limits=_json(root / "bench" / "workloads" / f"{name}.json")["limits"],
        end_to_end=[m for m in bench["end_to_end"]
                    if applies(m, name, bench)],
        per_layer=[m for m in bench["per_layer"]
                   if applies(m, name, bench)],
        root=root)
