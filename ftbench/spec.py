"""What ``BENCHMARK.json`` names, resolved to files under its ``paths``.

A cell names a configuration and a traffic mix; a per-layer metric names a
reader.  All three are found by name, so a later PR adds one as a file of
its own plus an entry in ``BENCHMARK.json`` and edits nothing that is here.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench_dir: str  # the directory that holds traffic/ and layer_metrics/


def _read(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _in_cell(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = _read(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        names = [w["name"] for w in bench["workloads"]]
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; it has {names}")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    bench_dir = os.path.join(root, bench["paths"][0])
    return Cell(
        name=workload,
        chips=int(entry["chips"]),
        config=_read(os.path.join(root, cfg_entry["file"])),
        traffic=_read(os.path.join(bench_dir, "traffic", entry["traffic"] + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _in_cell(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _in_cell(m, workload)],
        bench_dir=bench_dir,
    )


def load_metric(name: str, bench_dir: str) -> Optional[Any]:
    """The module ``layer_metrics/<name>.py`` (its ``META`` and
    ``read(sources)``), or None where there is no such file.  By file, not
    by import name: a metric's name may hold a dot."""
    path = os.path.join(bench_dir, "layer_metrics", name + ".py")
    if not os.path.exists(path):
        return None
    module_spec = importlib.util.spec_from_file_location(
        "ftbench_layer_metric_" + name.replace(".", "_").replace("-", "_"), path
    )
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module
