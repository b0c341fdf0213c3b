"""TPC-H Q14 through the single-device engine as the benchmark's cell
``tpch_sf1_q14`` runs it, at SF0.01: parquet from benchmark/tpch_gen.py, the
SQL text and the expected row from benchmark/queries/q14.sql / q14.py (the
files the chip uses, loaded by path). The first execution is the blocking
run (host syncs size the join, ``_JOIN_STATS`` / ``_JOIN_INDEX`` are
recorded); the second traces the replay through the cached index; from the
third on nothing is built. A second seed moves values and no shape."""

import importlib.util
import os
import sys

import pytest

from spark_tpu import metrics

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
SF, STRUCTURE = 0.01, 20260729
SEEDS = (11, 2**31 + 12345)       # the driver's seeds pass 32 signed bits
EXECUTIONS = 5

#: seed -> the (site, rows, dtype) of its sort events, in order
_SORTS = {}


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def bench():
    """benchmark/tpch_gen.py and queries/q14.py, by path; the query's
    file imports its sibling ``reference`` by name."""
    sys.path.insert(0, BENCH)
    try:
        gen = _load("bench_tpch_gen", os.path.join(BENCH, "tpch_gen.py"))
        q14 = _load("bench_q14", os.path.join(BENCH, "queries", "q14.py"))
        import reference

        with open(os.path.join(BENCH, "queries", "q14.sql")) as f:
            yield gen, q14, reference, f.read()
    finally:
        sys.path.remove(BENCH)


def _new_events(seen):
    events = [e for e in metrics.recent(4096) if e["n"] > seen[0]]
    if events:
        seen[0] = events[-1]["n"]
    return events


def _lookups():
    c = metrics.compile_cache_stats()
    return c["hits"] + c["misses"]


@pytest.mark.parametrize("seed", SEEDS)
def test_q14_blocks_once_then_replays_the_join_through_its_index(
        spark, bench, tmp_path_factory, seed):
    gen, q14, reference, text = bench
    path = gen.ensure_dataset(str(tmp_path_factory.mktemp("q14")), SF, seed,
                              STRUCTURE)
    gen.register_views(spark, path)
    want = q14.reference(path)
    last = metrics.recent(1)
    seen = [last[-1]["n"] if last else -1]
    runs = []
    for _ in range(EXECUTIONS):
        before = _lookups()
        rows = [tuple(r.asDict().values())
                for r in spark.sql(text).collect()]
        # every execution equals the plain reference (rel 1e-6 on the
        # one float), the blocking run and the traced replay alike
        assert reference.rows_differ(rows, want) is None
        runs.append((_new_events(seen), _lookups() - before))

    def kinds(i, kind):
        return [e for e in runs[i][0] if e["kind"] == kind]

    # the first execution is the blocking run
    assert any(e.get("op") == "blocking" for e in kinds(0, "stage"))
    assert not kinds(0, "join")
    # the traced program's join was built from the cached index; a seed
    # whose shapes the process has seen finds the stage in the stage
    # cache and traces nothing at all
    joins = [e for i in range(EXECUTIONS) for e in kinds(i, "join")]
    assert joins or not kinds(1, "stage_compile")
    assert all(e["rung"] in ("index", "table") and e["how"] == "inner"
               and e["build_rows"] >= 2000 and e["probe_cap"] > 0
               for e in joins)
    # sorts are built in the first two executions only, and from the
    # third on nothing is compiled or looked up
    assert kinds(0, "sort")
    for i in range(2, EXECUTIONS):
        events, lookups = runs[i]
        assert lookups == 0, i
        assert not [e for e in events if e["kind"] in (
            "stage_compile", "sort", "join", "seg_sum")], i
        assert any(e.get("op") == "fused" for e in kinds(i, "stage"))
    _SORTS[seed] = [(e["site"], e["rows"], e["dtype"])
                    for i in range(EXECUTIONS) for e in kinds(i, "sort")]
    # a second seed moves values, never a shape or a sort site
    if len(_SORTS) == len(SEEDS):
        assert _SORTS[SEEDS[0]] == _SORTS[SEEDS[1]]
        assert {site for site, _r, _d in _SORTS[seed]} <= {
            "join_index", "searchsorted"}
