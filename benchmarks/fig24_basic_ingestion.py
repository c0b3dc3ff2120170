"""Fig 24 — basic ingestion (no UDF): 'current feeds' (coupled, single
parsing node) vs 'balanced current feeds' (parsing spread) vs the new
framework at 1X/4X/16X batch sizes, plus the Approach-1 INSERT baseline
(per-statement recompilation).

Paper claims reproduced: (1) larger batches -> fewer computing-job
invocations -> higher throughput; (2) decoupling parse from storage beats
the coupled single-intake pipeline; (3) repeated INSERT pays compilation
per statement and is far slower."""

from __future__ import annotations

from benchmarks.common import (BATCH_1X, BATCH_4X, BATCH_16X, emit,
                               make_manager, run_feed)
from repro.core import FeedConfig, SyntheticAdapter

FIG = "fig24"


def main(total: int = 20_000) -> None:
    # the INSERT baseline's subject is the per-statement compile: keep
    # this process off the persistent cache, which would serve it from disk
    mgr = make_manager(persistent_cache=False)

    for label, batch in (("new_1X", BATCH_1X), ("new_4X", BATCH_4X),
                         ("new_16X", BATCH_16X)):
        s = run_feed(mgr, f"f24-{label}", total, batch, udf=None,
                     framework="new", partitions=2)
        emit(FIG, f"{label}_records_per_s", s.records_per_s, "rec/s",
             f"invocations={s.computing.invocations}")
        emit(FIG, f"{label}_parse_s", s.computing.parse_s, "s")

    s = run_feed(mgr, "f24-current", total, BATCH_1X, udf=None,
                 framework="current", partitions=1)
    emit(FIG, "current_records_per_s", s.records_per_s, "rec/s",
         "single intake node parses everything")

    s = run_feed(mgr, "f24-balanced", total, BATCH_1X, udf=None,
                 framework="balanced", partitions=2)
    emit(FIG, "balanced_records_per_s", s.records_per_s, "rec/s",
         "parsing spread over partitions")

    # Approach-1 INSERT vs predeployed: visible only with a UDF attached
    # (the compiled artifact is the enrichment plan).  Same workload both
    # ways, small slice (the INSERT path recompiles every statement).
    from repro.core.enrich import queries as Q
    ins_total = max(BATCH_1X * 4, total // 10)
    h = mgr.start(FeedConfig(name="f24-insert-q1", udf=Q.Q1,
                             batch_size=BATCH_1X, framework="insert"),
                  SyntheticAdapter(total=ins_total, frame_size=BATCH_1X,
                                   seed=11))
    s = h.join(timeout=1200)
    assert s.stored == ins_total, (s.stored, ins_total)
    per = h.statement_compile_s
    emit(FIG, "insert_q1_records_per_s", s.records_per_s, "rec/s",
         f"{ins_total} records, {len(per)} statements each compiled anew "
         f"(mean compile {sum(per) / len(per):.3f}s)")
    s = run_feed(mgr, "f24-new-q1", ins_total, BATCH_1X, udf=Q.Q1,
                 framework="new", partitions=1)
    emit(FIG, "new_q1_records_per_s", s.records_per_s, "rec/s",
         f"predeployed: compiles={s.predeploy['compiles']}, "
         f"invocations={s.computing.invocations}")


if __name__ == "__main__":
    main()
