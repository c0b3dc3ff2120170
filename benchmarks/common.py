"""Shared benchmark scaffolding.

This container is a single CPU core, so absolute wall-clock is meaningless
vs. the paper's 6-24 node cluster; what reproduces are the paper's
*relative* claims (batch-size effects, framework overhead decomposition,
UDF complexity ordering).  Where the paper scales nodes, we measure the
per-invocation overhead + per-record compute directly and report the
derived scaling model alongside the measured single-core wall time —
labeled as such.  Every figure emits CSV rows: name,value,unit,notes.
"""

from __future__ import annotations

import contextlib
import csv
import json
import time
from typing import Dict, List

from repro import compile_cache
from repro.core import (FeedConfig, FeedManager, RefStore,
                        SyntheticAdapter, pipeline)
from repro.core.enrich import queries as Q
from repro.kernels import DISPATCH_MODES, set_dispatch_mode

ROWS: List[Dict] = []

# paper batch sizes
BATCH_1X, BATCH_4X, BATCH_16X = 420, 1680, 6720


def emit(fig: str, name: str, value, unit: str, notes: str = "") -> None:
    row = {"fig": fig, "name": name, "value": round(value, 6)
           if isinstance(value, float) else value, "unit": unit,
           "notes": notes}
    ROWS.append(row)
    print(f"{fig},{name},{row['value']},{unit},{notes}", flush=True)


def write_json(fig: str, path: str) -> None:
    """Machine-readable counterpart of the CSV stream: one document per
    figure, metrics keyed by name — the input format of
    benchmarks/regression_gate.py (the CI threshold gate)."""
    metrics = {r["name"]: {"value": r["value"], "unit": r["unit"],
                           "notes": r["notes"]}
               for r in ROWS if r["fig"] == fig}
    with open(path, "w") as f:
        json.dump({"fig": fig, "metrics": metrics}, f, indent=2,
                  sort_keys=True)
        f.write("\n")


def write_csv(path: str) -> None:
    with open(path, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=["fig", "name", "value", "unit",
                                          "notes"])
        w.writeheader()
        w.writerows(ROWS)


def make_manager(scale: float = 0.02, overrides=None,
                 persistent_cache: bool = True) -> FeedManager:
    """Reference tables + manager; every driver calls this before its
    first compile, so it also places JAX's persistent compile cache —
    or, with ``persistent_cache=False``, keeps the process off it."""
    if persistent_cache:
        compile_cache.enable()
    else:
        compile_cache.disable()
    store = RefStore()
    Q.make_reference_tables(store, scale=scale, seed=7,
                            scale_overrides=overrides)
    return FeedManager(store)


def add_dispatch_arg(parser) -> None:
    """The --dispatch axis shared by the enrichment benchmarks: route
    operators through the Pallas kernels or the jnp reference paths (see
    core/enrich/dispatch.py).  Off-TPU the pallas path runs in interpret
    mode — an emulator, so absolute numbers are meaningless there; on TPU
    it is the production path."""
    parser.add_argument("--dispatch", choices=DISPATCH_MODES,
                        default="auto",
                        help="kernel dispatch mode (default: auto)")


def set_dispatch(mode: str) -> None:
    set_dispatch_mode(mode)


def run_feed(mgr: FeedManager, name: str, total: int, batch: int,
             udf=None, framework: str = "new", partitions: int = 2,
             model: str = "per_batch", refresh: str = "always",
             coalesce_rows=None):
    """coalesce_rows=None is the production default (auto: on for the
    decoupled framework); pass 0 for exact-invocation comparisons.
    framework="new" builds a plan (the shim lowering is gone); the
    coupled/insert baselines keep their cfg-driven measurement rigs."""
    adapter = SyntheticAdapter(total=total, frame_size=batch, seed=11)
    if framework == "new":
        p = (pipeline(adapter, name)
             .parse(batch_size=batch, model=model, refresh=refresh)
             .options(num_partitions=partitions,
                      coalesce_rows=coalesce_rows))
        if udf is not None:
            p.enrich(udf)
        h = mgr.submit(p.store())
    else:
        cfg = FeedConfig(name=name, udf=udf, batch_size=batch,
                         num_partitions=partitions, framework=framework,
                         model=model, refresh=refresh,
                         coalesce_rows=coalesce_rows)
        h = mgr.start(cfg, adapter)
    stats = h.join(timeout=1200)
    assert stats.stored == total, (name, stats.stored, total)
    return stats


@contextlib.contextmanager
def timed():
    t = {}
    t0 = time.perf_counter()
    yield t
    t["s"] = time.perf_counter() - t0
