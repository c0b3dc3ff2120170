"""Tiny versions of the benchmark's cells for CPU rehearsals: the same
files, with the reference tables, the batch and the rates cut down so a
run takes seconds on a CPU."""

import dataclasses
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def shrink(cell, tmp_path, batch=256, table_div=500, rate=1500,
           head=50000):
    """The cell at rehearsal size; its traffic file is rewritten under
    ``tmp_path``."""
    cfg = dict(cell.config, batch_size=batch)
    cfg["tables"] = {k: max(8, v // table_div)
                     for k, v in cfg["tables"].items()}
    tr = dict(cell.traffic, head_records_per_s=head)
    if tr["kind"] == "poisson":
        tr["rate"] = rate
    path = tmp_path / f"{cell.name}_traffic.json"
    path.write_text(json.dumps(tr))
    return dataclasses.replace(cell, config=cfg, traffic=tr,
                               traffic_path=str(path))


@pytest.fixture
def tiny(tmp_path):
    from bench import spec

    def make(name, **kw):
        return shrink(spec.load_cell(name), tmp_path, **kw)
    return make
