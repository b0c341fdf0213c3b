"""The sort-based aggregate (``HashAggregateExec._trace_sorted``: a GROUP BY
on a key column, K > 64) as the benchmark's cell ``tpch_sf10_q15_revenue``
runs it, at SF0.03: parquet from benchmark/tpch_gen.py, the SQL text and the
expected row from benchmark/queries/q15_revenue.sql / .py (the files the
chip uses, loaded by path). The first execution counts the groups in a stage
of its own (a host sync sizes the output, ``_AGG_STATS`` records the count)
and runs the aggregate sized by it as another; the second traces the whole
query's stage with that count as a static capacity; from the third on
nothing is built. A second seed moves values and no shape, so it builds no
program. The two halves run under scopes of their own inside the
operator's, and only there: Q1's direct path keeps
``spark.HashAggregateExec`` innermost (``agg_roofline_pct`` reads it)."""

import decimal
import os
import re
import sys

import jax
import pytest

from spark_tpu import metrics, trace
from test_operator_scopes import _captured_stages
from test_q14_join_replay import _load, _lookups, _new_events

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
SF, STRUCTURE = 0.03, 20260729    # an SF no other test file uses
SEEDS = (35, 2**31 + 3535)        # the driver's seeds pass 32 signed bits
EXECUTIONS = 4
COLUMNS = ["l_suppkey", "l_extendedprice", "l_discount", "l_shipdate"]


def _rows(df):
    return [tuple(r.asDict().values()) for r in df.collect()]


@pytest.fixture(scope="module")
def runs(spark, tmp_path_factory):
    """Everything the module looks at, run once, seed after seed in one
    session: per seed the dataset's path, the rows, build events and
    compile-cache lookups of each execution of the cell's text, the
    stages it captured and the stage cache's size after the seed; for the
    first seed also the inner view's rows and Q1's captured stages."""
    import pyarrow.parquet as pq

    from spark_tpu.physical import planner as PL
    from spark_tpu.tpch import oracle
    from spark_tpu.tpch.queries import QUERIES

    sys.path.insert(0, BENCH)
    try:
        gen = _load("bench_tpch_gen", os.path.join(BENCH, "tpch_gen.py"))
        q15 = _load("bench_q15_revenue",
                    os.path.join(BENCH, "queries", "q15_revenue.py"))
    finally:
        sys.path.remove(BENCH)
    with open(os.path.join(BENCH, "queries", "q15_revenue.sql")) as f:
        text = f.read()
    view = re.search(r"from \((.*)\) revenue0", text, re.S).group(1)
    root = str(tmp_path_factory.mktemp("q15"))
    out = {"text": text}
    for seed in SEEDS:
        path = gen.ensure_dataset(root, SF, seed, STRUCTURE)
        gen.register_views(spark, path)
        last = metrics.recent(1)
        seen = [last[-1]["n"] if last else -1]
        executions = []
        with _captured_stages() as captured:
            for _ in range(EXECUTIONS):
                before = _lookups()
                rows = _rows(spark.sql(text))
                executions.append((rows, _new_events(seen),
                                   _lookups() - before))
        conn = oracle.load_sqlite({"lineitem": pq.read_table(
            os.path.join(path, "lineitem.parquet"), columns=COLUMNS)})
        run = {"executions": executions, "captured": list(captured),
               "reference": q15.reference(path),
               "oracle": oracle.run_oracle(conn, text),
               "oracle_view": oracle.run_oracle(conn, view)}
        if seed == SEEDS[0]:
            run["view"] = _rows(spark.sql(view))
            with _captured_stages() as q1_stages:
                spark.sql(QUERIES[1]).collect()
            run["q1_stages"] = list(q1_stages)
        conn.close()
        run["stage_cache"] = len(PL._STAGE_CACHE)
        out[seed] = run
    return out


def _scale4(x: float) -> decimal.Decimal:
    """sqlite sums in REAL; a sum of some two dozen products of two
    hundredths is off by 1e-9 at most, so rounded to the result's own
    scale it is the exact decimal."""
    return decimal.Decimal(repr(round(x, 4))).quantize(
        decimal.Decimal("0.0001"))


@pytest.mark.parametrize("seed", SEEDS)
def test_every_execution_equals_the_pandas_reference(runs, seed):
    want = runs[seed]["reference"]
    assert len(want) == 1 and want[0][0].as_tuple().exponent == -4
    for rows, _events, _lookups_made in runs[seed]["executions"]:
        assert rows == want       # Decimal against Decimal: exact


@pytest.mark.parametrize("seed", SEEDS)
def test_the_cell_equals_the_sqlite_oracle(runs, seed):
    ((want,),) = runs[seed]["oracle"]
    assert runs[seed]["executions"][-1][0] == [(_scale4(want),)]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_two_references_agree(runs, seed):
    ((want,),) = runs[seed]["oracle"]
    assert runs[seed]["reference"] == [(_scale4(want),)]


def test_the_inner_view_equals_the_oracle_group_by_group(runs):
    run = runs[SEEDS[0]]
    want = sorted((k, _scale4(v)) for k, v in run["oracle_view"])
    assert len(want) == int(SF * 10_000)      # every supplier has a row
    assert sorted(run["view"]) == want
    assert max(v for _k, v in want) == run["reference"][0][0]


def test_a_second_seed_gives_another_answer(runs):
    assert runs[SEEDS[0]]["reference"] != runs[SEEDS[1]]["reference"]


def _of(events, kind):
    return [e for e in events if e["kind"] == kind]


@pytest.mark.parametrize("execution", [0, 1])
def test_the_aggregate_is_built_sorted(runs, execution):
    """The first execution counts the groups in one stage and runs the
    aggregate sized by the count as another; the second traces the whole
    query's stage. Each aggregate stage builds two XLA sorts (the key's
    argsort and the live rows'), one int64 ``seg_sum`` on the cumsum rung
    (past the masked rung's 64 slots, over the sorted ids), and a
    ``group_by`` event that says so; the count sorts once more. A
    tripwire on the program's shape."""
    _rows_got, events, _n = runs[SEEDS[0]]["executions"][execution]
    stages = [e["node"].split("[")[0] for e in _of(events, "stage_compile")]
    assert stages == [["GroupCount", "HashAggregate"], ["Compact"]][execution]
    assert [(e["site"], e["dtype"]) for e in _of(events, "sort")] == [
        ("lexsort", "int64"), ("lexsort", "bool")] * len(stages)
    (summed,) = _of(events, "seg_sum")
    assert summed["k"] > 64 and summed["rung"] == "cumsum"
    assert summed["dtype"] == "int64" and "limbs" not in summed
    by_key, of_all = _of(events, "group_by")
    groups = int(SF * 10_000)
    assert by_key == {**by_key, "strategy": "sorted", "keys": ["int64"],
                      "rows": summed["rows"], "k": summed["k"],
                      "groups": groups}
    assert 0 <= by_key["k"] - groups < 256
    # the outer max: no key, one slot, the direct path
    assert of_all == {**of_all, "strategy": "direct", "keys": [], "k": 1,
                      "groups": None}


def test_the_first_execution_compiles_stages_not_operations(runs):
    """Run op by op, the blocking first execution compiled some 390
    programs at this size (550 at SF10, 2,093 s cold on the v5e: PERF.md,
    PR 35), most of them the levels of ``seg_first``'s segmented scan;
    counted and then run as stages it looks up two stages' worth."""
    _rows_got, _events, lookups = runs[SEEDS[0]]["executions"][0]
    assert lookups <= 40


@pytest.mark.parametrize("execution", [2, 3])
def test_a_steady_execution_builds_and_compiles_nothing(runs, execution):
    _rows_got, events, lookups = runs[SEEDS[0]]["executions"][execution]
    assert [e["kind"] for e in events
            if e["kind"] in trace.BUILD_EVENTS | {"stage_compile"}] == []
    assert lookups == 0


def test_a_second_seed_builds_no_new_program(runs):
    """Another seed's arrays are new, so its first execution counts
    again, through the first seed's stage, and finds the same count; the
    stages it then needs are the first seed's too."""
    first, second = runs[SEEDS[0]], runs[SEEDS[1]]
    assert len(first["captured"]) == 3 and second["captured"] == []
    assert second["stage_cache"] == first["stage_cache"]
    events = [e for _r, ev, _n in second["executions"] for e in ev]
    assert [e["kind"] for e in events
            if e["kind"] in ("stage_compile", "sort", "seg_sum")] == []
    # the outer max of the first execution runs eagerly
    assert [e["strategy"] for e in _of(events, "group_by")] == ["direct"]
    assert all(n == 0 for _r, _ev, n in second["executions"][1:])


def _op_names(trace_fn, example_args):
    text = jax.jit(trace_fn).lower(example_args).as_text(debug_info=True)
    return [m for m in re.findall(r'"([^"]*)"', text) if "spark." in m]


def _innermost(op_name):
    return re.findall(r"(?:^|/)spark\.([A-Za-z_]\w*)", op_name)[-1]


def test_the_sorted_stage_names_its_two_halves(runs):
    _plan, trace_fn, example_args = runs[SEEDS[0]]["captured"][-1]
    names = _op_names(trace_fn, example_args)
    inner = {_innermost(n) for n in names}
    assert {"GroupSort", "GroupSum", "HashAggregateExec"} <= inner
    assert trace.INNER_SCOPES == {"GroupSort", "GroupSum"}
    # each half lies inside the operator's own scope
    for half in trace.INNER_SCOPES:
        assert any(f"spark.HashAggregateExec/spark.{half}/" in n
                   for n in names)
    # the sort and the sums' cumsums are where they are said to be,
    # and nothing is scattered into the group slots
    assert any(_innermost(n) == "GroupSort" and "sort" in n for n in names)
    assert any(_innermost(n) == "GroupSum" and "cumsum" in n for n in names)
    assert not any("scatter" in n for n in names)


def test_the_direct_path_keeps_the_operators_scope_innermost(runs):
    stages = [s for s in runs[SEEDS[0]]["q1_stages"]
              if "Aggregate" in s[0].tree_string()]
    assert stages
    for _plan, trace_fn, example_args in stages:
        names = _op_names(trace_fn, example_args)
        under = [n for n in names if "spark.HashAggregateExec" in n]
        assert under
        assert {_innermost(n) for n in under} == {"HashAggregateExec"}
        assert not any("spark.Group" in n for n in names)


def test_the_scopes_and_the_event_are_registered_and_linted():
    import ast

    tools = os.path.join(os.path.dirname(BENCH), "tools")
    assert "group_by" in trace.BUILD_EVENTS
    sys.path.insert(0, tools)
    try:
        import lint_invariants
    finally:
        sys.path.remove(tools)
    out = []
    lint_invariants._check_span_names(
        ast.parse("trace.inner_scope('GroupSort')\n"
                  "_trace.inner_scope('GroupBogus')\n"
                  "trace.built('group_by', strategy='sorted')\n"
                  "trace.built('grouped_by', strategy='sorted')"),
        "x.py", out)
    assert [(f.rule, f.line) for f in out] == [("span-names", 2),
                                               ("span-names", 4)]
    assert "INNER_SCOPES" in out[0].message
    assert "BUILD_EVENTS" in out[1].message


def test_a_group_count_inside_its_bucket_finds_the_stage_it_had(spark):
    """The traced program keeps of the observed group count only its
    256-slot bucket (the live groups are counted on the device), and the
    stage cache tells two bindings apart by that: 70 and 90 groups over
    the same capacity are one stage, 300 are another."""
    import pandas as pd

    def run(groups, times):
        keys = [(i * 7919) % groups for i in range(4999)]
        df = spark.createDataFrame(pd.DataFrame({
            "k": keys, "v": [i % 13 for i in range(4999)]}))
        got = []
        for _ in range(times):
            got = sorted(_rows(df.groupBy("k").sum("v")))
        want = {}
        for k, i in zip(keys, range(4999)):
            want[k] = want.get(k, 0) + i % 13
        assert got == sorted(want.items())

    with _captured_stages() as first:
        run(70, 2)      # the count and the sized aggregate, then the query
    with _captured_stages() as second:
        run(90, 3)
    with _captured_stages() as third:
        run(300, 2)     # the count's stage is the one it had
    assert len(first) == 3 and second == [] and len(third) == 2


@pytest.mark.parametrize("case", ["dense", "holes", "dead_tail", "one_row",
                                  "nothing_masked"])
def test_seg_first_over_sorted_ids_is_the_first_masked_row(case):
    """``kernels.seg_first`` on monotone ids (a reverse running minimum
    since PR 35, a segmented scan before): every segment's first masked
    row by position, against a loop; empty segments and segments with no
    masked row are not found."""
    import numpy as np

    from spark_tpu.physical import kernels as K

    rng = np.random.default_rng(35)
    n, k = (1, 3) if case == "one_row" else (4099, 300)
    seg = np.sort(rng.integers(0, max(1, k - 40), n)).astype(np.int32)
    mask = {"dense": np.ones(n, bool),
            "holes": rng.random(n) < 0.4,
            "dead_tail": np.arange(n) < n // 3,
            "one_row": np.ones(n, bool),
            "nothing_masked": np.zeros(n, bool)}[case]
    if case == "dead_tail":
        seg[n // 3:] = seg[n // 3 - 1]    # dead rows carry the last live id
    data = rng.integers(-10**12, 10**12, n)
    got, found = K.seg_first(jax.numpy.asarray(data), jax.numpy.asarray(seg),
                             jax.numpy.asarray(mask), k, n, sorted_seg=True)
    jitted = jax.jit(lambda d, s, m: K.seg_first(d, s, m, k, n, True))(
        data, seg, mask)
    for values, flags in ((got, found), jitted):
        values, flags = np.asarray(values), np.asarray(flags)
        for g in range(k):
            rows = np.flatnonzero((seg == g) & mask)
            assert bool(flags[g]) == bool(rows.size), (case, g)
            if rows.size:
                assert values[g] == data[rows[0]], (case, g)
