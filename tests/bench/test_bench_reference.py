"""The benchmark's numpy reference of UDF2 and Q1-Q7 against the
program's own enrichment on the CPU, column by column, at a small size;
and the bfloat16 control one step below it."""

import numpy as np

from bench import data, reference

UDFS = ["udf2", "q1", "q2", "q3", "q4", "q5", "q6", "q7"]
SEED = 2**32 + 99


def _tables(div=200):
    from repro.core.enrich import queries as Q
    cards = {k: max(8, v // div) for k, v in Q.PAPER_CARDINALITIES.items()}
    return data.reference_tables(cards, SEED)


def _program(tables, batch):
    from repro.core import RefStore
    from repro.core.computing import ComputingRunner, ComputingSpec
    from repro.core.enrich import queries as Q
    store = RefStore()
    for name, t in tables.items():
        rt = store.create(name, t["key"].shape[0] + 64, data.SCHEMAS[name])
        rt.upsert(t["key"], **{c: v for c, v in t.items() if c != "key"})
    udf = Q.chain("all", *[Q.get_udf(u) for u in UDFS])
    runner = ComputingRunner(ComputingSpec(udf, batch["id"].shape[0]),
                             store)
    return runner.run(dict(batch, valid=np.ones(batch["id"].shape[0],
                                                bool)))


def test_reference_matches_the_program_on_every_enriched_column():
    tables = _tables()
    t = data.parsed_columns(data.tweets(SEED, 0, 512))
    got = _program(tables, t)
    want = reference.enrich(UDFS, t, tables)
    assert set(want) <= set(got)
    for c, v in want.items():
        np.testing.assert_array_equal(np.asarray(got[c]), v, err_msg=c)
    # the sample exercises the joins: not every answer is a fill value
    assert (want["nearby_monument_count"] > 0).any()
    assert (want["district"] >= 0).any()
    assert (want["suspect_threat_level"] > 0).any()


def test_bfloat16_control_moves_the_spatial_columns():
    tables = _tables(div=10)
    t = data.parsed_columns(data.tweets(SEED, 0, 2048))
    f32 = reference.enrich(["q4", "q6"], t, tables)
    bf16 = reference.enrich(["q4", "q6"], t, tables, "bfloat16")
    moved = [c for c in f32 if not np.array_equal(f32[c], bf16[c])]
    assert "nearby_monuments" in moved and "district" in moved
