"""Find a cell and everything it names, by name, under ``bench/``.

``BENCHMARK.json`` lists the cells; a cell names a configuration file
(``configs/<config>.json``) and a traffic file (``traffic/<traffic>.json``).
Each metric of ``end_to_end`` and ``per_layer`` is a module of its own
(``end_to_end/<name>.py``, ``metrics/<name>.py``) with one function
``read(run) -> float | None``. Adding a cell, a mix or a metric adds files
and entries; no existing file changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(RuntimeError):
    pass


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    kind: str                       # "end_to_end" | "per_layer"
    entry: dict
    read: Callable

    def applies_to(self, cell: str) -> bool:
        cells = self.entry.get("workloads")
        return cells is None or cell in cells


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict                    # configs/<config>.json
    traffic: dict                   # traffic/<traffic>.json
    end_to_end: list
    per_layer: list


METRIC_DIRS = {"end_to_end": "end_to_end", "per_layer": "metrics"}


def metric_module(kind: str, name: str, root: str = ROOT):
    """The reader module ``bench/<end_to_end|metrics>/<name>.py``."""
    path = os.path.join(root, "bench", METRIC_DIRS[kind], name + ".py")
    if not os.path.exists(path):
        raise SpecError(f"no reader for {kind} metric {name!r} ({path})")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{METRIC_DIRS[kind]}_{name}", path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its
    configuration, traffic and metric readers loaded."""
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(root, "bench", "traffic",
                                      w["traffic"] + ".json"))
    metrics = {}
    for kind in ("end_to_end", "per_layer"):
        metrics[kind] = [
            Metric(m["name"], m["unit"], kind, m,
                   metric_module(kind, m["name"], root).read)
            for m in spec[kind]
            if m.get("workloads") is None or name in m["workloads"]]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=metrics["end_to_end"],
                per_layer=metrics["per_layer"])
