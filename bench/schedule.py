"""What a traffic mix offers in one run: how many tweets, and when each is
due, from the mix's data file and the run's seed.

Two kinds of mix:

* ``backlog``: every tweet of the window is due when the window opens,
  and the generator keeps the socket full until the window closes (the
  paper's throughput set-up, Fig 25).  It makes ``head_records_per_s``
  times the window's length ahead, more than the feed can take in.
* ``poisson``: an open loop of independent users at a fixed rate.  The
  inter-arrival gaps are one fixed set drawn from the mix's own seed;
  the run's seed only orders them, so every seed offers the same load.
  The schedule runs one frame past the window, so that the frame holding
  the window's last tweets fills as it would in a stream that goes on.

Both start with ``warmup_batches`` frames, sent and stored before the
window opens.  The generator process imports this module: no JAX here.
"""

from __future__ import annotations

import dataclasses
import json
import math
from typing import Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Schedule:
    kind: str                 # backlog | poisson
    warm: int                 # warm-up tweets, ids [0, warm)
    planned: int              # window tweets prepared, ids from ``warm``
    offsets: Optional[np.ndarray]   # poisson: due time after window open
    head: int                 # tweets prepared before the run may start

    def due_in_window(self, seconds: float) -> int:
        """Window tweets due before the window closes (poisson)."""
        return int(np.searchsorted(self.offsets, seconds, side="left"))


def load(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def make(traffic: dict, seed: int, seconds: float, batch: int,
         rate: Optional[float] = None) -> Schedule:
    """The schedule of one run; ``rate`` overrides a poisson mix's rate
    (for a sweep that looks for the highest sustained rate)."""
    warm = int(traffic["warmup_batches"]) * batch
    kind = traffic["kind"]
    if kind == "backlog":
        head = int(math.ceil(traffic["head_records_per_s"] * seconds))
        return Schedule(kind, warm, 0, None, head)
    if kind == "poisson":
        lam = float(rate if rate is not None else traffic["rate"])
        frames = int(math.ceil(lam * seconds / batch)) + 1
        n = frames * batch
        gaps = np.random.default_rng(int(traffic["gap_seed"])
                                     ).exponential(1.0 / lam, n)
        order = np.random.default_rng([int(seed), 3]).permutation(n)
        offsets = np.cumsum(gaps[order])
        sched = Schedule(kind, warm, n, offsets, n)
        last = sched.due_in_window(seconds)
        if (last // batch + 1) * batch > n:
            raise ValueError("poisson schedule ends inside the frame of "
                             "the window's last tweet")
        return sched
    raise ValueError(f"unknown traffic kind {kind!r}")
