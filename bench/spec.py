"""The benchmark's data, found by name: ``BENCHMARK.json`` at the root of
the checkout names each cell's configuration and traffic mix; the files
are ``bench/configs/<config>.json`` (as ``BENCHMARK.json`` gives it),
``bench/traffic/<traffic>.json`` and ``bench/metrics/<metric>.py``.  A
new cell, configuration, mix or per-layer metric is new files plus an
entry: nothing here names one.  No JAX here.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    root: str                 # checkout the files were read from
    chips: int
    config: dict
    traffic: dict
    traffic_path: str
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str, reported: Optional[set] = None
             ) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric["moves"] in reported


def load_cell(name: str, spec_path: Optional[str] = None) -> Cell:
    """The cell ``name`` with everything it needs, read from files."""
    spec_path = os.path.abspath(spec_path or os.path.join(ROOT,
                                                          "BENCHMARK.json"))
    root = os.path.dirname(spec_path)
    with open(spec_path, encoding="utf-8") as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {spec_path}; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    cfgs = {c["name"]: c for c in spec["configs"]}
    with open(os.path.join(root, cfgs[w["config"]]["file"]),
              encoding="utf-8") as f:
        config = json.load(f)
    tpath = os.path.join(root, "bench", "traffic", w["traffic"] + ".json")
    with open(tpath, encoding="utf-8") as f:
        traffic = json.load(f)
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"] if _applies(m, name, reported)]
    return Cell(name, root, int(w["chips"]), config, traffic, tpath, e2e,
                layer)


def reader(metric: str, root: str = ROOT) -> Callable:
    """``read(ctx)`` of ``<root>/bench/metrics/<metric>.py``."""
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def read_metrics(metrics: List[dict], ctx) -> Dict[str, dict]:
    """Each metric its reader finds something for; a reader that finds
    nothing returns None, and the metric is left out."""
    out = {}
    for m in metrics:
        v = reader(m["name"], ctx.cell.root)(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out
