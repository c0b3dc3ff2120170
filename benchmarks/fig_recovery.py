"""fig_recovery — fault-injection harness for durable feeds
(core/durability.py + core/recovery.py), the recovery axis the paper's
experiments assume but never measure: SIGKILL a process mid-ingest (with
rolling reference updates in flight), restart, and demand exactly-once.

Sections:

  kill-restart  ``--kills`` rounds: a child process runs a durable Q1
                feed (WAL + coordinated checkpoints) against a throttled
                synthetic stream while a rolling updater upserts
                safety_levels keys; the parent SIGKILLs it at a random
                point of the ingest window, then a second child recovers
                the feed (``FeedManager.resume``) from the surviving
                durable directory.  Hard asserts per round: rows lost
                = 0 and rows duplicated = 0 over the full stream.
                Emits the replay backlog and the recovery time (resume
                call until the replayed backlog is re-stored) per round,
                plus max/mean aggregates.

  throughput    durable (default interval fsync) vs non-durable ingest
                of the same stream, both spilling to disk, interleaved
                warm/steady rounds.  Emits the ratio; acceptance: the
                WAL costs <= 10% steady-state throughput at paper-scale
                runs (smoke-scale floor is looser — see
                benchmarks/regression_gate.py).

The child re-enters this module with ``--child``; the crash is a real
SIGKILL of a separate interpreter, so no Python-level cleanup (atexit,
finally, flush-on-close) can soften it.  The resume-and-verify step runs
in its own child too (``--resume``): the parent never starts JAX's
backend while a child lives, because an accelerator belongs to one
process at a time.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time
from collections import Counter

import numpy as np

from benchmarks.common import BATCH_1X, emit, make_manager, write_json
from benchmarks.fig_repair import RollingUpdater
from repro.core import (CompactionSpec, DurableSpec, RepairSpec,
                        SyntheticAdapter, pipeline)
from repro.core.enrich import queries as Q

FIG = "fig_recovery"


def durable_plan(durable_dir: str, total: int, batch: int, seed: int,
                 rate, name: str, refresh=None):
    """The plan both sides build: the child runs it, the parent resumes
    it — recovery requires the identical deterministic plan (same seed,
    same frame size), modulo the rate limit (replay is unthrottled)."""
    return (pipeline(SyntheticAdapter(total=total, frame_size=batch,
                                      seed=seed, rate=rate), name)
            .parse(batch_size=batch)
            .options(num_partitions=2, holder_capacity=16)
            .enrich(Q.Q1)
            # small flush segments + an aggressive leveled-merge policy:
            # segment merges rewrite the store WHILE the kill window is
            # open, so every crash image also stresses the merge path's
            # manifest-before-GC ordering (exactly-once must still hold)
            .store(segment_rows=500, sort_key="country",
                   compact=CompactionSpec(budget_rows_s=1e6,
                                          interval_s=0.05,
                                          yield_backlog_batches=1e9,
                                          merge_fanin=4,
                                          level_target_rows=100_000),
                   durable=DurableSpec(dir=durable_dir,
                                       fsync="interval",
                                       fsync_interval_s=0.02,
                                       checkpoint_interval_s=0.3),
                   refresh=refresh))


def stored_id_counts(storage) -> Counter:
    """LIVE occurrence count per primary key across all partitions.
    Physical dead rows (repair re-appends; compaction reclaims) are not
    duplicates — but the same pk live in two partitions, or twice in
    one, is exactly the row-double-delivery a replay bug would produce."""
    counts: Counter = Counter()
    for part in storage.partitions:
        snap = part.snapshot_view()
        try:
            for u in snap.units:
                ids = np.asarray(u.read(("id",))["id"])
                for i in ids[snap.live_mask(ids, u.base)]:
                    counts[int(i)] += 1
        finally:
            snap.release()
    return counts


# ---------------------------------------------------------------------------
# child: the process that gets killed
# ---------------------------------------------------------------------------

def child_main(args) -> None:
    mgr = make_manager(scale=0.02)
    nbase = len(mgr.refstore["safety_levels"])
    upd = RollingUpdater(mgr.refstore["safety_levels"], nbase,
                         args.update_every,
                         min(args.update_keys, nbase))
    h = mgr.submit(durable_plan(
        args.durable_dir, args.total, args.batch, args.seed, args.rate,
        args.name, refresh=RepairSpec(budget_rows_s=20_000)))
    upd.start()
    print("READY", flush=True)
    h.join(timeout=1200)
    upd.stop()
    print("DONE", flush=True)


# ---------------------------------------------------------------------------
# parent: kill, restart, verify exactly-once
# ---------------------------------------------------------------------------

def resume_main(args) -> None:
    """Second child: resume the killed feed, verify, report one line."""
    r = resume_and_verify(args.durable_dir, args.total, args.batch,
                          args.seed, args.name, args.kill_after)
    print("RESULT " + json.dumps(r), flush=True)


def resume_and_verify(dur_dir: str, total: int, batch: int, seed: int,
                      name: str, delay: float) -> dict:
    # restart: fresh manager, fresh (pristine) ref tables, same plan
    mgr = make_manager(scale=0.02)
    plan = durable_plan(dur_dir, total, batch, seed, None, name)
    t0 = time.perf_counter()
    h = mgr.resume(plan)
    rt = h.durability
    backlog = rt.replayed_records
    # recovery time = resume() until the replayed backlog is re-stored
    # (the checkpoint watermark reaches the pre-crash WAL tail)
    while rt.ledger.watermark() < rt.replay_target_seq:
        time.sleep(0.005)
    recovery_s = time.perf_counter() - t0
    h.join(timeout=600)           # raises if any job errored

    counts = stored_id_counts(h.storage)
    lost = total - len(counts)
    dups = sum(c - 1 for c in counts.values())
    assert lost == 0, (f"{name}: {lost} rows lost after kill at "
                       f"+{delay:.2f}s (backlog={backlog})")
    assert dups == 0, (f"{name}: {dups} duplicate rows after kill "
                       f"at +{delay:.2f}s (backlog={backlog})")
    return {"kill_after_s": delay, "backlog": backlog,
            "recovery_s": recovery_s, "lost": lost, "dups": dups}


def run_round(rnd: int, dur_dir: str, total: int, batch: int, rate: float,
              update_every: float, update_keys: int, rng) -> dict:
    name = f"rec{rnd}"
    seed = 100 + rnd
    common = ["--durable-dir", dur_dir, "--total", str(total),
              "--batch", str(batch), "--seed", str(seed), "--name", name,
              "--json-out", ""]
    cmd = [sys.executable, "-m", "benchmarks.fig_recovery", "--child",
           *common, "--rate", str(rate),
           "--update-every", str(update_every),
           "--update-keys", str(update_keys)]
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True)
    try:
        for line in proc.stdout:
            if line.startswith("READY"):
                break
        else:
            raise RuntimeError(f"round {rnd}: child died before READY "
                               f"(rc={proc.wait()})")
        # kill at a random point of the nominal ingest window
        window = total / rate
        delay = rng.uniform(0.1 * window, 0.8 * window)
        time.sleep(delay)
    finally:
        proc.kill()
        proc.wait()
        proc.stdout.close()

    # the killed child has exited, so the resuming child is the only
    # process that holds JAX's backend
    done = subprocess.run(
        [sys.executable, "-m", "benchmarks.fig_recovery", "--resume",
         *common, "--kill-after", repr(delay)],
        stdout=subprocess.PIPE, text=True)
    result = [ln for ln in done.stdout.splitlines()
              if ln.startswith("RESULT ")]
    if done.returncode != 0 or not result:
        raise RuntimeError(f"round {rnd}: resume/verify failed "
                           f"(rc={done.returncode}); see its stderr")
    return json.loads(result[-1][len("RESULT "):])


def bench_kill_restart(base_dir: str, kills: int, total: int, batch: int,
                       rate: float, update_every: float,
                       update_keys: int) -> None:
    rng = np.random.default_rng(29)
    rounds = []
    for rnd in range(kills):
        dur_dir = os.path.join(base_dir, f"round{rnd}")
        try:
            r = run_round(rnd, dur_dir, total, batch, rate,
                          update_every, update_keys, rng)
        finally:
            shutil.rmtree(dur_dir, ignore_errors=True)
        rounds.append(r)
        emit(FIG, f"recovery_round{rnd}_s", r["recovery_s"], "s",
             f"kill at +{r['kill_after_s']:.2f}s, replay backlog "
             f"{r['backlog']} records, lost={r['lost']} dups={r['dups']}")
    emit(FIG, "kills", len(rounds), "count",
         f"SIGKILL rounds over a {total}-row stream @{rate:.0f} rec/s "
         f"with rolling ref updates every {update_every}s")
    emit(FIG, "rows_lost_total", sum(r["lost"] for r in rounds), "rows",
         "exactly-once: must be 0")
    emit(FIG, "rows_duplicated_total", sum(r["dups"] for r in rounds),
         "rows", "exactly-once: must be 0")
    emit(FIG, "backlog_max_records",
         max(r["backlog"] for r in rounds), "records",
         "largest WAL tail replayed on restart")
    rec = [r["recovery_s"] for r in rounds]
    emit(FIG, "recovery_max_s", max(rec), "s",
         "resume() -> backlog re-stored, worst round; every round resumes "
         "in a fresh process (its compiles are in it)")
    emit(FIG, "recovery_mean_s", sum(rec) / len(rec), "s", "")


# ---------------------------------------------------------------------------
# throughput: the price of the WAL at default fsync
# ---------------------------------------------------------------------------

def bench_throughput(base_dir: str, total: int, batch: int) -> None:
    mgr = make_manager(scale=0.02)
    samples = {"plain": [], "durable": []}
    # rounds interleave plain/durable so slow system drift (page cache,
    # XLA autotuning, thermal) hits both sides equally; the emitted
    # number is the per-side MEDIAN of the steady rounds
    for rnd in ("warm", "steady1", "steady2", "steady3"):
        for label in ("plain", "durable"):
            name = f"tp-{label}-{rnd}"
            adapter = SyntheticAdapter(total=total, frame_size=batch,
                                       seed=23)
            spill = os.path.join(base_dir, name)
            if label == "durable":
                p = (pipeline(adapter, name)
                     .parse(batch_size=batch)
                     .options(num_partitions=2, holder_capacity=16)
                     .enrich(Q.Q1)
                     .store(durable=DurableSpec(dir=spill)))
            else:
                p = (pipeline(adapter, name)
                     .parse(batch_size=batch)
                     .options(num_partitions=2, holder_capacity=16)
                     .enrich(Q.Q1)
                     .store(spill_dir=spill))
            h = mgr.submit(p)
            s = h.join(timeout=1200)
            assert s.stored == total, (name, s.stored, total)
            shutil.rmtree(spill, ignore_errors=True)
            if rnd != "warm":
                # steady-state ingest rate: the final coordinated
                # checkpoint (flush + snapshot at join) is shutdown
                # drain, excluded like fig_repair's repair_drain_s
                ingest_s = s.wall_s - s.durable_finish_s
                samples[label].append(s.records_in / ingest_s
                                      if ingest_s else 0.0)
    res = {}
    for label, xs in samples.items():
        res[label] = sorted(xs)[len(xs) // 2]
        emit(FIG, f"throughput_{label}", res[label], "rec/s",
             f"unthrottled x{total} rows, both spilling to disk, "
             f"median of {len(xs)} interleaved steady rounds, "
             "final-checkpoint drain excluded")
    emit(FIG, "durable_throughput_ratio", res["durable"] / res["plain"],
         "ratio", "acceptance (full profile): >= 0.9 at default "
         "interval fsync")


def main(base_dir: str, kills: int, total: int, batch: int, rate: float,
         update_every: float, update_keys: int) -> None:
    if kills > 0:               # --kills 0: throughput-only run
        bench_kill_restart(base_dir, kills, total, batch, rate,
                           update_every, update_keys)
    bench_throughput(base_dir, total, batch)


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--resume", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--kill-after", type=float, default=0.0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--durable-dir", default="",
                    help=argparse.SUPPRESS)
    ap.add_argument("--seed", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--name", default="rec", help=argparse.SUPPRESS)
    ap.add_argument("--kills", type=int, default=5,
                    help="SIGKILL/restart rounds")
    ap.add_argument("--total", type=int, default=40_000)
    ap.add_argument("--batch", type=int, default=BATCH_1X)
    ap.add_argument("--rate", type=float, default=6000.0,
                    help="child ingest throttle (rec/s) — sets the kill "
                         "window; replay on resume is unthrottled")
    ap.add_argument("--update-every", type=float, default=0.1,
                    help="seconds between rolling ref upserts (child)")
    ap.add_argument("--update-keys", type=int, default=25,
                    help="keys upserted per rolling update")
    ap.add_argument("--work-dir", default="",
                    help="durable-dir root (default: a temp dir)")
    ap.add_argument("--json-out", default="BENCH_fig_recovery.json",
                    help="machine-readable metrics file "
                         "(empty string disables)")
    args = ap.parse_args()
    if args.child:
        child_main(args)
    elif args.resume:
        resume_main(args)
    else:
        import tempfile
        base = args.work_dir or tempfile.mkdtemp(prefix="fig_recovery_")
        try:
            main(base, args.kills, args.total, args.batch, args.rate,
                 args.update_every, args.update_keys)
        finally:
            if not args.work_dir:
                shutil.rmtree(base, ignore_errors=True)
        if args.json_out:
            write_json(FIG, args.json_out)
