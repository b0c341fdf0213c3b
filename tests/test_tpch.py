"""TPC-H end-to-end: all 22 queries, parsed from SQL, executed on both
engines, results checked against the sqlite3 external oracle.

This is the parity harness SURVEY §4 calls for (reference model:
TPCHQuerySuite.scala:26 + golden files). Scale sf=0.02 keeps the suite
fast while producing non-empty results for every query.
"""

import pytest

from spark_tpu.tpch.gen import generate_tables, register_views
from spark_tpu.tpch.oracle import assert_rows_match, load_sqlite, run_oracle
from spark_tpu.tpch.queries import QUERIES

SF = 0.02


@pytest.fixture(scope="module")
def tpch(spark):
    # seed chosen so every query returns rows at this tiny SF (q18's
    # HAVING sum(l_quantity) > 300 is the tightest: 2 qualifying orders)
    tables = generate_tables(SF, seed=99)
    register_views(spark, tables)
    conn = load_sqlite(tables)
    return spark, tables, conn


def _rows(df):
    return [tuple(r.values()) for r in
            (row.asDict() if hasattr(row, "asDict") else row
             for row in df.collect())]


ALL_QUERIES = sorted(QUERIES)

# Default (fast) selections keep the suite under ~5 minutes while still
# covering every operator class: grouped agg (1), joins+limit (3, 5),
# multi-join+expr (9), outer-join agg subquery (13), anti/semi patterns
# (16, 21, 22), quantity having (18). The FULL 22-query x both-engine
# sweep runs with --runslow (VERDICT r3 weak #4: a suite nobody can
# wait for stops being run).
FAST_SINGLE = {1, 3, 5, 13, 16, 18, 22}
FAST_MESH = {1, 5}


def _mark_slow(qnums, fast):
    return [q if q in fast
            else pytest.param(q, marks=pytest.mark.slow)
            for q in qnums]


@pytest.mark.parametrize("qnum", _mark_slow(ALL_QUERIES, FAST_SINGLE))
def test_query_parity_single_device(tpch, qnum):
    spark, _, conn = tpch
    df = spark.sql(QUERIES[qnum])
    got = [tuple(r.values()) for r in (r.asDict() for r in df.collect())]
    want = run_oracle(conn, QUERIES[qnum])
    assert want, f"q{qnum}: oracle returned no rows — bad generator seed?"
    assert_rows_match(got, want, label=f"q{qnum}")


@pytest.mark.parametrize("qnum", _mark_slow(ALL_QUERIES, FAST_MESH))
def test_query_parity_mesh(tpch, qnum):
    """Distributed runs of ALL 22 queries vs the same oracle."""
    from spark_tpu.parallel.executor import MeshExecutor
    from spark_tpu.parallel.mesh import make_mesh
    from spark_tpu.sql.parser import parse_sql

    spark, _, conn = tpch
    plan = parse_sql(QUERIES[qnum], spark.catalog)
    ex = MeshExecutor(make_mesh(8))
    batch = ex.execute_logical(plan)
    got = [tuple(d.values()) for d in batch.to_pylist()]
    want = run_oracle(conn, QUERIES[qnum])
    assert_rows_match(got, want, label=f"q{qnum}[mesh]")


def test_all_queries_parse(tpch):
    """Every query text must at least tokenize+parse (plan shape only;
    execution parity above). Uses the module fixture's views — a
    private re-registration here would CLOBBER the shared catalog and
    silently poison every later test in the module (found the hard way:
    re-execution parity compared sf0.001 results to the sf0.02
    oracle)."""
    from spark_tpu.sql.parser import parse_sql

    spark, _, _ = tpch
    for qnum, text in QUERIES.items():
        plan = parse_sql(text, spark.catalog)
        assert plan.schema.names, f"q{qnum} produced no schema"


@pytest.mark.parametrize("qnum", _mark_slow([3, 5, 7, 10, 18],
                                             {3, 5, 18}))
def test_query_parity_reexecution(tpch, qnum):
    """Second executions replay through the adaptive TRACED join paths
    (sized expansion / swapped / unique-build gather chosen by output
    capacity) — assert they produce the same oracle-checked rows as the
    first, blocking, run."""
    spark, _, conn = tpch
    df = spark.sql(QUERIES[qnum])
    first = _rows(df)
    second = _rows(df)
    want = run_oracle(conn, QUERIES[qnum])
    assert_rows_match(first, want, label=f"q{qnum}[run1]")
    assert_rows_match(second, want, label=f"q{qnum}[run2]")


@pytest.mark.parametrize("qnum", _mark_slow([1, 6, 14, 19], {6}))
def test_query_parity_parquet_scan(tpch, tmp_path, qnum):
    """Parquet-backed runs: decimal columns + predicate pushdown through
    the datasource (the in-memory fixture path skips translate_filters
    entirely, so q6-style decimal-vs-float pushed literals only get
    exercised here)."""
    from spark_tpu.tpch.gen import write_parquet

    spark, tables, conn = tpch
    path = str(tmp_path / "tpch_pq")
    write_parquet(tables, path)
    try:
        register_views(spark, path=path)
        df = spark.sql(QUERIES[qnum])
        got = _rows(df)
        want = run_oracle(conn, QUERIES[qnum])
        assert_rows_match(got, want, label=f"q{qnum}[parquet]")
    finally:
        register_views(spark, tables)  # restore in-memory views


@pytest.fixture(scope="module")
def smoke_reference(tmp_path_factory):
    """(sqlite connection, parquet directory) of one SF0.01 dataset."""
    from spark_tpu.tpch.gen import write_parquet

    tables = generate_tables(0.01, seed=7)
    path = str(tmp_path_factory.mktemp("smoke_ref") / "tpch")
    write_parquet(tables, path)
    return load_sqlite(tables), path


@pytest.mark.parametrize("qnum", [1, 3, 5, 6, "15_revenue", "15_revenue0"])
def test_chip_smoke_reference_matches_oracle(smoke_reference, qnum):
    """chip_smoke.py checks the chip's SF1 answers against a pandas
    recompute (the sqlite oracle cannot load SF1 inside a smoke run's
    time); here that recompute is itself held to the oracle, at SF0.01
    over the same parquet layout. chip_smoke is a plain module until
    its main() runs: importing it touches neither jax nor the device."""
    import chip_smoke

    conn, path = smoke_reference
    got = chip_smoke.REFERENCE[qnum](path)
    want = run_oracle(conn, chip_smoke.query_text(qnum))
    assert want, f"q{qnum}: oracle returned no rows"
    assert_rows_match(got, want, label=f"q{qnum}[pandas reference]")
