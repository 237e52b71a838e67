"""Resolve a cell of ``BENCHMARK.json`` into its parts, by name.

Every part lives in a file of its own under ``<root>/benchmark``, so a cell
that a later PR adds is found without an edit here.  Plug-in modules
(queries, loops, metrics) are loaded by path from ``root``: the test suite
points ``root`` at a scratch copy that holds only new files.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
from types import ModuleType
from typing import Any, Dict, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass(frozen=True)
class Metric:
    spec: Dict[str, Any]  # the BENCHMARK.json entry
    reader: ModuleType  # benchmark/metrics/<name>.py: read(ctx) -> float|None

    @property
    def name(self) -> str:
        return self.spec["name"]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    query: ModuleType  # benchmark/queries/<query>.py
    loop: ModuleType  # benchmark/loops/<loop>.py
    end_to_end: Tuple[Metric, ...]
    per_layer: Tuple[Metric, ...]


def module_file(name: str) -> str:
    """File name of the plug-in for ``name`` (``a.b-c`` -> ``a_b_c.py``)."""
    return re.sub(r"[^A-Za-z0-9_]", "_", name) + ".py"


def _read_json(root: str, rel: str) -> Dict[str, Any]:
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def _plugin(root: str, kind: str, name: str) -> ModuleType:
    path = os.path.join(root, "benchmark", kind, module_file(name))
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} module for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{module_file(name)[:-3]}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its parts."""
    bench = _read_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(cells: {', '.join(sorted(cells))})")
    w = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    traffic = _read_json(root, f"benchmark/traffic/{w['traffic']}.json")

    def metrics(kind):
        return tuple(
            Metric(m, _plugin(root, "metrics", m["name"]))
            for m in bench[kind]
            if workload in m.get("workloads", (workload,)))

    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=_read_json(root, entry["file"]),
        traffic=traffic,
        query=_plugin(root, "queries", traffic["query"]),
        loop=_plugin(root, "loops", traffic["loop"]),
        end_to_end=metrics("end_to_end"),
        per_layer=metrics("per_layer"),
    )


def peaks_for(device_kind: str, root: str = ROOT) -> Dict[str, Any]:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    table = _read_json(root, "benchmark/peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in benchmark/peaks.json (known: {sorted(table)})")
    return table[device_kind]
