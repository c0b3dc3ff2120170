"""Find the highest rate a poisson cell's feed sustains, once, on the chip.

    python3 bench/sweep.py --backlog <cell> --workload <poisson cell> \\
        --seed <n> --seconds <s> [--fractions 0.8,0.9,1.0]

Runs the backlog cell once to read its ``ingest_records_per_s`` (the
capacity), then the poisson cell at each fraction of it, one process at a
time.  A rate is sustained when its run is correct and latency does not
grow across the window: the median visible latency of the window's last
fifth is within ``GROWTH`` of the first fifth's.  A queue that grows by
even a few tweets a batch fails that over a long window.  The highest
fraction that holds, with every lower one holding too, is the sustained
rate; a cell offers ``LOAD`` of it.  Prints one JSON object.  The
benchmark's own runs never sweep: the rate is fixed in the traffic file.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys

RUN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "run.py")
GROWTH = 1.10        # last fifth's median latency over the first fifth's
LOAD = 0.8           # share of the sustained rate a steady cell offers


def _run(args):
    p = subprocess.run([sys.executable, RUN] + args, capture_output=True,
                       text=True)
    lines = p.stdout.strip().splitlines()
    res = json.loads(lines[-1]) if p.returncode == 0 and lines else None
    m = re.search(r"visible p50 by fifth of the window: \[(.*)\]", p.stderr)
    fifths = [float(x) for x in m.group(1).split(",")] if m else None
    return res, fifths


def sustained(res, fifths) -> bool:
    return bool(res and res["correct"] and fifths
                and fifths[-1] <= GROWTH * fifths[0])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--backlog", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--fractions", default="0.8,0.9,1.0")
    a = ap.parse_args()
    common = ["--seconds", str(a.seconds), "--trace", "0"]
    res, _ = _run(["--workload", a.backlog, "--seed", str(a.seed)] + common)
    if res is None:
        print(json.dumps({"error": "backlog run gave no result"}))
        return 1
    backlog = {k: v["value"] for k, v in res["metrics"].items()}
    cap = backlog["ingest_records_per_s"]
    rows, best = [], None
    for i, f in enumerate(float(x) for x in a.fractions.split(",")):
        rate = round(cap * f, -2)
        res, fifths = _run(["--workload", a.workload,
                            "--seed", str(a.seed + 1 + i),
                            "--rate", str(rate)] + common)
        ok = sustained(res, fifths)
        rows.append({"fraction": f, "rate": rate, "fifths_p50_s": fifths,
                     "sustained": ok,
                     "metrics": res and {k: v["value"] for k, v
                                         in res["metrics"].items()}})
        if not ok:
            break
        best = rate
    print(json.dumps({"capacity": cap, "backlog": backlog, "runs": rows,
                      "sustained": best,
                      "cell_rate": best and round(LOAD * best, -2)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
