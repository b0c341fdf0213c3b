"""A multi-key equi-join whose build side is unique on one of its keys,
looked up by that key alone and its other keys tested on the matched row
(``planner._lookup``), and a first execution bounded by a direct
aggregate, as TPC-H Q5 runs them: its last join is (o_custkey,
s_nationkey) = (c_custkey, c_nationkey), customer unique on c_custkey,
under GROUP BY n_name and ORDER BY without a LIMIT.

Seeded random joins against a plain pandas merge: the unique key is
spread over 1..100,000, so the two keys' packed domain (some 10^8 slots)
passes ``K.JOIN_TABLE_MAX`` at a few thousand rows, as Q5's does at SF1
and above, and the looked-up key alone takes the dense table. A build
side unique on neither key alone keeps the packed path. Then the
benchmark's Q5 text (benchmark/queries/q5_volume.sql) over its generator's
data at SF0.01, the dense tables' limit lowered below its two-key join's
domain and above every single key's, against the sqlite oracle."""

import os
import sys

import numpy as np
import pyarrow as pa
import pytest

from spark_tpu import metrics, trace
from spark_tpu.physical import kernels as K
from test_join_sizing import _scale4
from test_q14_join_replay import _load

SEEDS = (5, 2**31 + 77)
#: (build rows, probe rows) by seed: capacities of their own, so each
#: seed's join is traced and not found in the stage cache
ROWS = {SEEDS[0]: (3000, 5000), SEEDS[1]: (4500, 7000)}
KEYS = 100_000
SQL = {"inner": "JOIN", "left": "LEFT JOIN", "left_semi": "LEFT SEMI JOIN",
       "left_anti": "LEFT ANTI JOIN"}


def _nullable(rng, values, share):
    return pa.array(values, pa.int64(), mask=rng.random(len(values)) < share)


def _tables(seed: int, unique: bool):
    """(probe, build) as arrow tables: build rows keyed (bk, br), unique on
    the pair; unique on bk alone where ``unique``, else every bk twice
    with two nations. A probe row takes a build row's bk four times in
    five and its br one time in two; a twentieth of either side's keys
    are null."""
    rng = np.random.default_rng(seed)
    nb, n = ROWS[seed]
    if unique:
        bk = rng.choice(np.arange(1, KEYS + 1), nb, replace=False)
        br = rng.integers(0, 25, bk.size)
    else:
        half = rng.choice(np.arange(1, KEYS + 1), nb // 2, replace=False)
        first = rng.integers(0, 25, half.size)
        bk = np.repeat(half, 2)
        br = np.stack([first, (first + rng.integers(1, 25, half.size)) % 25],
                      axis=1).ravel()
    pick = rng.integers(0, bk.size, n)
    pk = np.where(rng.random(n) < 0.8, bk[pick], rng.integers(1, KEYS + 1, n))
    pr = np.where(rng.random(n) < 0.5, br[pick], rng.integers(0, 25, n))
    probe = pa.table({"id": pa.array(np.arange(n), pa.int64()),
                      "k": _nullable(rng, pk, 0.05),
                      "r": _nullable(rng, pr, 0.05)})
    build = pa.table({"bk": _nullable(rng, bk, 0.05),
                      "br": _nullable(rng, br, 0.05),
                      "v": pa.array(np.arange(bk.size) * 7, pa.int64())})
    return probe, build


def _expected(probe, build, how):
    """The join by a plain pandas merge: a null key equals nothing."""
    p, b = probe.to_pandas(), build.to_pandas()
    matched = p.dropna(subset=["k", "r"]).merge(
        b.dropna(subset=["bk", "br"]), left_on=["k", "r"],
        right_on=["bk", "br"])
    pairs = {(int(i), int(v)) for i, v in zip(matched.id, matched.v)}
    hit = {i for i, _ in pairs}
    if how == "inner":
        return sorted(pairs)
    if how == "left":
        return sorted(pairs | {(int(i), None) for i in p.id if i not in hit})
    if how == "left_semi":
        return sorted((i,) for i in hit)
    return sorted((int(i),) for i in p.id if i not in hit)


def _last() -> int:
    return (metrics.recent(1) or [{"n": -1}])[-1]["n"]


def _key(row):
    return tuple((v is None, v) for v in row)


def _run(spark, seed, unique, how):
    """The rows of the join's two executions and the ``join`` build
    events of the first."""
    probe, build = _tables(seed, unique)
    spark.createDataFrame(probe).createOrReplaceTempView("lookup_probe")
    spark.createDataFrame(build).createOrReplaceTempView("lookup_build")
    cols = "p.id" if how in ("left_semi", "left_anti") else "p.id, b.v"
    text = (f"SELECT {cols} FROM lookup_probe p {SQL[how]} lookup_build b "
            "ON p.k = b.bk AND p.r = b.br")
    last = _last()
    rows = [sorted((tuple(r) for r in spark.sql(text).collect()), key=_key)
            for _ in range(2)]
    events = [e for e in metrics.recent(4096)
              if e["n"] > last and e["kind"] == "join"]
    return rows, events, _expected(probe, build, how)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("how", list(SQL))
def test_a_build_side_unique_on_one_key_is_looked_up_by_it(spark, seed, how):
    rows, events, want = _run(spark, seed, True, how)
    assert rows[0] == rows[1] == sorted(want, key=_key)
    assert events, "the join was traced"
    for e in events:
        # the row and code tables set in the program: no sort, no index
        assert (e["looked_up"], e["tested"], e["rung"]) == (
            ["k=bk"], ["r=br"], "lookup")


@pytest.mark.parametrize("how", ["inner", "left"])
def test_a_build_side_unique_on_neither_key_keeps_the_packed_path(spark, how):
    rows, events, want = _run(spark, SEEDS[0], False, how)
    assert rows[0] == rows[1] == sorted(want, key=_key)
    assert events
    for e in events:
        # the two keys' domain passes the dense tables: the sorted index
        assert (e["looked_up"], e["tested"], e["rung"]) == (
            ["k=bk", "r=br"], [], "index")


# ---- TPC-H Q5: the whole query's stage compiled once ------------------------

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
SF, STRUCTURE = 0.01, 20260729
#: below Q5's two-key domain at SF0.01 (2,048 customer keys x 1,024 for the
#: nation key's 25) and above every single key's (15,360 order keys)
TABLE_MAX = 1 << 20


def _q5_runs(spark, root, seed, executions):
    """The rows and events of ``executions`` runs of the benchmark's Q5
    text over the generator's data for ``seed`` at SF0.01, the dense
    tables' limit lowered, and the sqlite oracle's rows."""
    import pyarrow.parquet as pq

    from spark_tpu.tpch import oracle

    sys.path.insert(0, BENCH)
    try:
        gen = _load("bench_tpch_gen", os.path.join(BENCH, "tpch_gen.py"))
    finally:
        sys.path.remove(BENCH)
    with open(os.path.join(BENCH, "queries", "q5_volume.sql")) as f:
        text = f.read()
    path = gen.ensure_dataset(root, SF, seed, STRUCTURE)
    gen.register_views(spark, path)
    runs = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(K, "JOIN_TABLE_MAX", TABLE_MAX)
        for _ in range(executions):
            last = _last()
            rows = [tuple(r) for r in spark.sql(text).collect()]
            runs.append((rows, [e for e in metrics.recent(4096)
                                if e["n"] > last]))
    conn = oracle.load_sqlite({t: pq.read_table(
        os.path.join(path, f"{t}.parquet")) for t in (
            "customer", "orders", "lineitem", "supplier", "nation",
            "region")})
    want = [(n, _scale4(v)) for n, v in oracle.run_oracle(conn, text)]
    conn.close()
    return runs, want


@pytest.fixture(scope="module")
def q5(spark, tmp_path_factory):
    """Three executions of Q5 on the first seed it runs on: its events."""
    return _q5_runs(spark, str(tmp_path_factory.mktemp("q5")), SEEDS[1], 3)


@pytest.mark.parametrize("seed", SEEDS)
def test_every_q5_execution_equals_the_sqlite_oracle(q5, spark,
                                                     tmp_path_factory, seed):
    executions, want = q5 if seed == SEEDS[1] else _q5_runs(
        spark, str(tmp_path_factory.mktemp("q5_seed")), seed, 2)
    assert len(want) == 5
    for rows, events in executions:
        assert rows == want         # in the ORDER BY's order, exactly
    looked_up = [e for rows, events in executions for e in events
                 if e["kind"] == "join" and e["tested"]]
    assert all(e["rung"] == "lookup" for e in looked_up)


def test_q5s_first_execution_compiles_the_whole_query_once(q5):
    """A GROUP BY of a dictionary string has its slots when it is traced,
    so the first execution runs the stage every later one runs (a
    ``Compact`` of the output to one capacity bucket) and the second
    compiles nothing: one ``first_exec`` event, ``bound`` direct."""
    (_r0, first), (_r1, second), _ = q5[0]
    stages = [e["node"].split("[")[0] for e in first
              if e["kind"] == "stage_compile"]
    assert stages.count("Compact") == 1 and stages[-1] == "Compact"
    assert "Sort" not in stages
    (fe,) = [e for e in first if e["kind"] == "first_exec"]
    assert fe["bound"] == "direct"
    assert fe["programs"] == len(stages)
    assert fe["sorts"] == len([e for e in first if e["kind"] == "sort"])
    assert [e for e in second
            if e["kind"] in trace.BUILD_EVENTS | {"stage_compile"}] == []


def test_q5s_steady_program_looks_its_two_key_join_up_and_sorts_once(q5):
    """A tripwire on the program every execution runs: the build events
    its trace recorded (those after the last sizing or index program).
    Every join is on a dense table, the two-key join looked up by
    c_custkey with c_nationkey tested (``lookup``: its tables set in the
    program, no index recorded for it), and the only sorts are the
    ORDER BY's, over the aggregate's 26 slots."""
    first = q5[0][0][1]
    compiled = [i for i, e in enumerate(first)
                if e["kind"] == "stage_compile"]
    steady = first[compiled[-2] + 1:compiled[-1]]
    joins = [e for e in steady if e["kind"] == "join"]
    assert [e["rung"] for e in joins] == ["table"] * 4 + ["lookup"]
    assert (joins[-1]["looked_up"], joins[-1]["tested"]) == (
        ["o_custkey=c_custkey"], ["s_nationkey=c_nationkey"])
    assert all(e["tested"] == [] for e in joins[:-1])
    sorts = [(e["site"], e["rows"]) for e in steady if e["kind"] == "sort"]
    assert sorts and {s for s, _ in sorts} == {"lexsort"}
    assert {rows for _, rows in sorts} == {26}



@pytest.mark.parametrize("text,bound", [
    ("SELECT n_name FROM nation JOIN region ON n_regionkey = r_regionkey "
     "LIMIT 3", "limit"),
    ("SELECT count(*) FROM nation JOIN region ON n_regionkey = r_regionkey",
     "keyless"),
    ("SELECT r_name, count(*) FROM nation JOIN region "
     "ON n_regionkey = r_regionkey GROUP BY r_name", "direct"),
    # an integer key's groups are counted on the device: no bound
    ("SELECT n_regionkey, count(*) FROM nation JOIN region "
     "ON n_regionkey = r_regionkey GROUP BY n_regionkey", "none"),
])
def test_first_exec_names_what_bounded_the_first_execution(q5, spark, text,
                                                           bound):
    """(Over the Q5 fixture's views.) One ``first_exec`` event a first
    execution; where a bound held it ran the whole query's stage and the
    second execution builds and compiles nothing, where none did the
    second compiles the query again, compacted to the live rows."""
    events = []
    for _ in range(2):
        last = _last()
        spark.sql(text).collect()
        events.append([e for e in metrics.recent(4096) if e["n"] > last])
    (fe,) = [e for e in events[0] if e["kind"] == "first_exec"]
    assert fe["bound"] == bound and fe["programs"] >= 1
    again = [e["kind"] for e in events[1]
             if e["kind"] in trace.BUILD_EVENTS | {"stage_compile"}]
    if bound == "none":
        assert "stage_compile" in again
    else:
        assert again == []
