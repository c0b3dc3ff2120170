"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

A cell is an entry of ``BENCHMARK.json``: a configuration
(``bench/configs/``) under a traffic mix (``bench/traffic/``).  The run:

1. starts the traffic generator (``bench/gen.py``), a process without JAX
   that makes the window's tweets while this one sets up;
2. makes the reference tables from the seed, submits the configuration's
   plan, ``SocketAdapter -> parse -> enrich -> store``, to a
   ``FeedManager``, and warms it up with the mix's warm-up frames, sent
   through the socket and stored like any other;
3. opens the window: the generator sends on its schedule for
   ``--seconds``; every store write is stamped as it returns;
4. closes the window, drains what was in flight, reads the chip's peak
   memory, and compares every stored tweet with what was sent and with
   the plain reference (``bench/check.py``).

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from the program's counters over the window and
from a profiler trace of part of it.  The last line of stdout is one
JSON object; the numbers compared for ``correct`` are the last lines of
stderr and the last key of that object.  Off a TPU, or with fewer chips
than the cell asks for, or with a compile inside the window, the run
exits non-zero.  ``--control 1`` puts the cell's control in the
program's place for the comparison; it has to come out not correct.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import check, data, schedule, spec, stats  # noqa: E402

# the compile cache lives in the checkout at a fixed path, so that only a
# cell's first run in a checkout compiles
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
TRACE_SECONDS = 3.0      # length of the profiled part of a traced window
TRACE_AT = 0.4           # where it starts, as a share of the window
KERNELS = {              # kernel -> its instruction in the device's HLO
    "hash_probe": r"^%sorted_probe_pallas(\.\d+)? = ",
    "segment_sum": r"^%segment_sum_pallas(\.\d+)? = ",
}


class RunError(RuntimeError):
    """The run cannot give a result."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# generator process
# ---------------------------------------------------------------------------

class GenProc:
    def __init__(self, cell: spec.Cell, seed: int, seconds: float,
                 rate: Optional[float]):
        cmd = [sys.executable, os.path.join(ROOT, "bench", "gen.py"),
               "--traffic", cell.traffic_path, "--seed", str(seed),
               "--seconds", str(seconds),
               "--batch", str(cell.config["batch_size"])]
        if rate is not None:
            cmd += ["--rate", str(rate)]
        self.p = subprocess.Popen(cmd, stdin=subprocess.PIPE,
                                  stdout=subprocess.PIPE, text=True)

    def expect(self, word: str, timeout: float = 600.0) -> str:
        box: List[str] = []
        t = threading.Thread(target=lambda: box.append(
            self.p.stdout.readline()), daemon=True)
        t.start()
        t.join(timeout)
        line = box[0].strip() if box else ""
        if not line.startswith(word):
            raise RunError(f"generator said {line!r}, expected {word!r}")
        return line[len(word):].strip()

    def send(self, line: str) -> None:
        self.p.stdin.write(line + "\n")
        self.p.stdin.flush()

    def close(self) -> None:
        if self.p.poll() is None:
            try:
                self.p.stdin.close()
            except OSError:
                pass
            try:
                self.p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.p.kill()
                self.p.wait()


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Ctx:
    """What the per-layer metric readers see (``bench/metrics/``)."""
    cell: spec.Cell
    device_kind: str
    window: Dict[str, float]          # program counters over the window
    batches: int                      # computing invocations in the window
    queue_wait_p95_s: Optional[float]
    trace: Optional[dict]             # bench.trace.reduce(...)


# the counters the first readers were written against; each of them
# keeps its name and value among all that ``_counters`` finds
FIXED = ("parse_s", "upload_s", "convert_s", "state_s", "apply_s",
         "invocations")


def _counters(h) -> Dict[str, float]:
    """Every numeric counter the program keeps, as it stands; a metric
    reads its rise over the window by name (``Ctx.window``).  Each
    runner's ``ComputingStats`` field under its own name, summed over the
    runners; each stage's ``StageStats`` field as
    ``stage.<stage>.<field>``; the intake's as ``intake.<field>``; the
    store's writes as ``store_write_s`` and ``store_batches``; and every
    numeric counter and gauge of the feed's registry (``h.metrics()``)
    under its registry name, where no name above has it."""
    out: Dict[str, float] = {k: 0 for k in FIXED}
    for r in h.runners:
        for f in dataclasses.fields(r.stats):
            v = getattr(r.stats, f.name)
            if isinstance(v, (int, float)):
                out[f.name] = out.get(f.name, 0) + v
        for stage, ss in r.stats.per_stage.items():
            for f in dataclasses.fields(ss):
                key = f"stage.{stage}.{f.name}"
                out[key] = out.get(key, 0) + getattr(ss, f.name)
    intake = getattr(h, "intake", None)
    for f in ("draw_s", "fill_s", "wait_output_s", "frames_in"):
        if intake is not None and hasattr(intake, f):
            out[f"intake.{f}"] = getattr(intake, f)
    out["store_write_s"] = h.storage.write_s
    out["store_batches"] = h.storage.batches
    for k, v in h.metrics().items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out.setdefault(k, v)
    return out


class _Compiles:
    """Compiles anywhere in the process, through JAX's monitoring
    events; counting only while ``on``."""

    def __init__(self):
        import jax
        self.on = False
        self.n = 0
        self.names: List[str] = []

        def listen(event, *a, **kw):
            if self.on and ("compile" in event or "cache" in event):
                self.n += 1
                self.names.append(event)

        jax.monitoring.register_event_duration_secs_listener(listen)
        jax.monitoring.register_event_listener(listen)


def build_feed(cell: spec.Cell, seed: int, trace: bool):
    """Reference tables, the plan and its feed, from the configuration."""
    from repro.core import FeedManager, RefStore, SocketAdapter, pipeline
    from repro.core.enrich import queries as Q
    cfg = cell.config
    tables = data.reference_tables(cfg["tables"], seed)
    store = RefStore()
    for name, t in tables.items():
        rt = store.create(name, t["key"].shape[0] + cfg["table_headroom"],
                          data.SCHEMAS[name])
        rt.upsert(t["key"], **{c: v for c, v in t.items() if c != "key"})
    udfs = [Q.get_udf(u) for u in cfg["plan"]["udfs"]]
    udf = udfs[0] if len(udfs) == 1 else Q.chain(cfg["name"], *udfs)
    adapter = SocketAdapter("127.0.0.1", 0, frame_size=cfg["batch_size"])
    opts = {"coalesce_rows": cfg["coalesce_rows"],
            "num_partitions": cfg["partitions"]}
    if trace:
        opts["trace"] = True
    plan = (pipeline(adapter, cell.name)
            .parse(batch_size=cfg["batch_size"], model=cfg["model"],
                   refresh=cfg["refresh"])
            .options(**opts).enrich(udf).store())
    mgr = FeedManager(store)
    return tables, store, mgr, udf, adapter, plan


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             control: Optional[str] = None, rate: Optional[float] = None,
             dump: Optional[str] = None) -> dict:
    import jax
    cfg = cell.config
    gen = GenProc(cell, seed, seconds, rate)
    try:
        return _run(cell, cfg, gen, seed, seconds, trace, control, rate,
                    dump, jax)
    finally:
        gen.close()


def _run(cell, cfg, gen, seed, seconds, trace, control, rate, dump, jax):
    sched = schedule.make(cell.traffic, seed, seconds, cfg["batch_size"],
                          rate)
    compiles = _Compiles()
    tables, store, mgr, udf, adapter, plan = build_feed(cell, seed, trace)
    h = mgr.submit(plan)
    stamps: List = []
    write = h.storage.write

    def stamped_write(batch, *a, **kw):
        n = write(batch, *a, **kw)
        stamps.append((time.monotonic(), batch["id"][batch["valid"]]))
        return n

    h.storage.write = stamped_write
    gen.expect("ready")
    gen.send(f"connect {adapter.address[1]}")
    warm = int(gen.expect("warm"))
    deadline = time.monotonic() + 900
    while len(stamps) < warm // cfg["batch_size"]:
        if time.monotonic() > deadline or h.intake.error is not None:
            raise RunError("warm-up frames were not stored")
        time.sleep(0.01)
    pre0 = mgr.predeploy.compiles
    c0 = _counters(h)
    compiles.on = True
    t0 = time.monotonic() + 0.005
    gen.send(f"go {t0!r}")
    setup_s = t0 - T_PROCESS
    tdir = spans_mono = None
    if trace:
        tdir, spans_mono = _traced_part(jax, h, t0, seconds)
    time.sleep(max(0.0, t0 + seconds - time.monotonic()))
    c1 = _counters(h)
    pre1 = mgr.predeploy.compiles
    compiles.on = False
    qwait = h.metrics()["holder_backlog_age_s"]
    gen.send("stop")
    report = json.loads(gen.expect("report", timeout=300))
    h.join(timeout=900)
    if pre1 != pre0 or compiles.n:
        raise RunError(f"compiles inside the window: predeploy "
                       f"{pre1 - pre0}, events {compiles.names[:8]}")
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev),
              "memory_peak_bytes": max(
                  (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in dev)}
    stored = _stored_rows(h)
    del h, mgr, store, plan
    offered = report["warm"] + report["sent"]
    log(f"generator: {json.dumps(report)}")
    if report.get("dry_events"):
        raise RunError(f"the generator ran dry {report['dry_events']} "
                       f"times in the window (first at "
                       f"{report['first_dry_s']} s)")

    vis = _visible(stamps, offered)
    t_check = time.monotonic()
    checks, per_col = check.compare(stored, offered, seed, cfg, tables,
                                    control)
    log(f"compared per enriched column (mismatches): {json.dumps(per_col)} "
        f"in {time.monotonic() - t_check:.1f} s")
    end_to_end = _end_to_end(cell, sched, vis, t0, seconds, setup_s)
    d_win = {k: c1.get(k, 0) - c0.get(k, 0) for k in {**c0, **c1}}
    result = {
        "correct": check.passed(checks),
        "attempted": offered,
        "failed": sum(int(c["value"]) for c in checks.values()),
    }
    if trace:
        from bench import trace as trace_mod
        norm = trace_mod.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        result["metrics"], result["breakdown"], tr = _per_layer(
            cell, device, d_win, qwait, norm, spans_mono, dump)
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
    else:
        result["metrics"] = end_to_end
    result["device"] = device
    log(f"end to end: {json.dumps(end_to_end)}")
    log(f"window counters: {json.dumps(d_win)}")
    for line in check.lines(checks):
        log(line)
    result["checks"] = checks
    return result


def _traced_part(jax, h, t0: float, seconds: float):
    """Profile ``TRACE_SECONDS`` of the window; the program's own spans
    are drained over the same stretch."""
    start = t0 + TRACE_AT * seconds
    time.sleep(max(0.0, start - time.monotonic()))
    tdir = tempfile.mkdtemp(prefix="bench-trace-")
    h.drain_trace()
    # no Python tracer: it slows every host thread several times over,
    # and the host sets this system's pace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        marker_mono = time.monotonic()
        time.sleep(min(TRACE_SECONDS, 0.5 * seconds))
    jax.profiler.stop_trace()
    return tdir, (h.drain_trace(), marker_mono)


def _stored_rows(h) -> Dict:
    chunks = [c for p in h.storage.partitions for c in p.scan()]
    cols = {k: [] for k in chunks[0]} if chunks else {"id": []}
    for c in chunks:
        m = c["valid"]
        for k in cols:
            cols[k].append(c[k][m])
    import numpy as np
    return {k: np.concatenate(v) for k, v in cols.items()}


def _visible(stamps, offered: int):
    """Monotonic time at which the store write holding each tweet
    returned (the first, where one was written twice); nan if never."""
    import numpy as np
    vis = np.full(offered, np.nan)
    for t, ids in stamps:
        ids = ids[(ids >= 0) & (ids < offered)]
        new = ids[np.isnan(vis[ids])]
        vis[new] = t
    return vis


def _end_to_end(cell, sched, vis, t0, seconds, setup_s) -> Dict[str, dict]:
    import numpy as np
    out = {}
    names = {m["name"]: m for m in cell.end_to_end}
    w = vis[sched.warm:]
    if "ingest_records_per_s" in names:
        out["ingest_records_per_s"] = stats.rate(w[~np.isnan(w)], t0,
                                                 seconds)
    if sched.kind == "poisson":
        n_in = sched.due_in_window(seconds)
        lat = w[:n_in] - (t0 + sched.offsets[:n_in])
        lat = lat[~np.isnan(lat)]
        # a rate the feed cannot sustain shows as latency that grows
        # from the first fifth of the window to the last
        fifths = [stats.percentile(p, 0.5) for p in np.array_split(lat, 5)
                  if p.size]
        log(f"visible p50 by fifth of the window: {fifths}")
        if lat.size:
            out["visible_p50_s"] = stats.percentile(lat, 0.5)
            out["visible_p95_s"] = stats.percentile(lat, 0.95)
            log(f"visible p95: {out['visible_p95_s']}")
    out["setup_s"] = setup_s
    return {k: {"value": v, "unit": names[k]["unit"]}
            for k, v in out.items() if k in names}


def _per_layer(cell, device, d_win, qwait, norm, spans_mono, dump):
    from bench import trace as tr
    spans, marker_mono = spans_mono
    lo, _ = tr.window(norm)
    prog = tr.to_trace_clock(spans, marker_mono, lo)
    red = tr.reduce(norm, KERNELS, prog)
    if dump:
        tr.save(dict(norm, program_spans=list(spans),
                     marker_mono=marker_mono), dump)
    q95 = qwait.percentile(0.95) if qwait.count else None
    ctx = Ctx(cell, device["kind"], d_win, int(d_win["invocations"]),
              q95 if q95 == q95 else None, red)
    metrics = spec.read_metrics(cell.per_layer, ctx)
    breakdown = {"device_ops": red["device_ops"],
                 "idle_gaps": red["idle_gaps"]}
    return metrics, breakdown, red


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="compare the cell's control in the program's "
                         "place")
    ap.add_argument("--rate", type=float, default=None,
                    help="override a poisson mix's rate (rate sweeps)")
    ap.add_argument("--spec", default=None,
                    help="a benchmark file other than BENCHMARK.json")
    ap.add_argument("--dump", default=None,
                    help="write the traced part, reduced to plain JSON, "
                         "to this .json.gz")
    a = ap.parse_args(argv)
    cell = spec.load_cell(a.workload, a.spec)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from repro import compile_cache
    devs = jax.devices()
    if jax.default_backend() != "tpu" or len(devs) < cell.chips:
        log(f"JAX found {len(devs)} {jax.default_backend()} device(s); "
            f"cell {cell.name} needs {cell.chips} TPU chip(s)")
        return 3
    compile_cache.enable()
    control = cell.config["control"] if a.control else None
    try:
        result = run_cell(cell, a.seed, a.seconds, bool(a.trace), control,
                          a.rate, a.dump)
    except RunError as e:
        log(f"run failed: {e}")
        return 4
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
