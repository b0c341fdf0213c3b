"""Distributed (mesh) execution vs single-device oracle.

The local-mesh harness from SURVEY.md §4: 8 virtual CPU devices
(conftest sets xla_force_host_platform_device_count) stand in for a TPU
slice, the single-device engine is the correctness oracle — the same
role DAGSchedulerSuite's mock backend and local-cluster[n,c,m] play in
the reference (reference: core/.../scheduler/DAGSchedulerSuite.scala:159,
SchedulerIntegrationSuite.scala:50).
"""

import numpy as np
import pyarrow as pa
import pytest

import spark_tpu.expr.expressions as E
import spark_tpu.plan.logical as L
from spark_tpu.columnar.arrow import from_arrow
from spark_tpu.parallel.executor import MeshExecutor
from spark_tpu.parallel.mesh import make_mesh
from spark_tpu.physical.planner import execute_logical


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


@pytest.fixture(scope="module")
def ex(mesh):
    return MeshExecutor(mesh)


def _rows(batch, sort_keys=None):
    rows = [tuple(d.values()) for d in batch.to_pylist()]
    if sort_keys is not None:
        rows.sort(key=lambda r: tuple(
            (v is None, v) for v in (r[i] for i in sort_keys)))
    return rows


def check(ex, plan, ordered=False, order_cols=None):
    """Distributed result == single-device result. Unordered plans
    compare as multisets; ordered plans additionally compare the
    sort-key column sequence (ties may permute — SQL sorts are not
    stable, and neither is Spark's)."""
    got = ex.execute_logical(plan)
    want = execute_logical(plan)
    all_keys = list(range(len(want.schema.names)))
    assert _rows(got, all_keys) == _rows(want, all_keys)
    if ordered:
        names = list(want.schema.names)
        idx = [names.index(c) for c in (order_cols or names)]
        got_keys = [tuple(r[i] for i in idx) for r in _rows(got)]
        want_keys = [tuple(r[i] for i in idx) for r in _rows(want)]
        assert got_keys == want_keys


def table(rng, n=5000, with_nulls=True):
    ks = rng.integers(0, 50, n)
    vs = (rng.normal(size=n) * 10).astype(object)
    if with_nulls:
        vs[rng.random(n) < 0.1] = None
    tag = np.array(["red", "green", "blue", "gold"])[rng.integers(0, 4, n)]
    return from_arrow(pa.table({
        "k": pa.array(ks, pa.int64()),
        "v": pa.array(list(vs), pa.float64()),
        "tag": pa.array(list(tag), pa.string()),
    }))


@pytest.fixture(scope="module")
def rel(rng):
    return L.Relation(table(rng))


def test_filter_project(ex, rel):
    plan = L.Project((E.Col("k"), E.Alias(E.Col("v") * 2.0, "v2")),
                     L.Filter(E.Col("k") > 25, rel))
    check(ex, plan)


def test_range(ex):
    plan = L.Filter(E.Col("id") % 7 == 0, L.Range(0, 10000, 3))
    check(ex, plan)


def test_global_agg(ex, rel):
    plan = L.Aggregate(
        (), (E.Alias(E.Sum(E.Col("v")), "s"),
             E.Alias(E.Count(None), "n"),
             E.Alias(E.Avg(E.Col("v")), "a"),
             E.Alias(E.Min(E.Col("k")), "mn"),
             E.Alias(E.Max(E.Col("k")), "mx"),
             E.Alias(E.StddevVariance("stddev_samp", E.Col("v")), "sd")),
        rel)
    got = ex.execute_logical(plan).to_pylist()[0]
    want = execute_logical(plan).to_pylist()[0]
    for key in ("s", "a", "sd"):
        assert got[key] == pytest.approx(want[key], rel=1e-9)
    assert got["n"] == want["n"]
    assert got["mn"] == want["mn"]
    assert got["mx"] == want["mx"]


def test_direct_group_agg_psum(ex, rel):
    """String-dictionary keys -> PSumAgg path (no shuffle)."""
    plan = L.Aggregate(
        (E.Col("tag"),),
        (E.Col("tag"), E.Alias(E.Sum(E.Col("v")), "s"),
         E.Alias(E.Count(None), "n")),
        rel)
    got = {r[0]: r[1:] for r in _rows(ex.execute_logical(plan))}
    want = {r[0]: r[1:] for r in _rows(execute_logical(plan))}
    assert set(got) == set(want)
    for k in want:
        assert got[k][0] == pytest.approx(want[k][0], rel=1e-9)
        assert got[k][1] == want[k][1]


@pytest.fixture(scope="module")
def typed_rel():
    """Dictionary keys over a decimal, an int, a float and a second
    dictionary column, each with nulls; a generator of its own (the
    session's ``rng`` is a stream other tests draw from)."""
    import decimal

    r = np.random.default_rng(30)
    n = 4000

    def nulled(values, share=0.15):
        out = np.array(values, dtype=object)
        out[r.random(n) < share] = None
        return list(out)

    cents = r.integers(-50_000, 900_000, n)
    return L.Relation(from_arrow(pa.table({
        "tag": pa.array(list(np.array(["red", "green", "blue", "gold"])[
            r.integers(0, 4, n)]), pa.string()),
        "d": pa.array(nulled([decimal.Decimal(int(c)).scaleb(-2)
                              for c in cents]), pa.decimal128(12, 2)),
        "i": pa.array(nulled(r.integers(-1000, 1000, n).tolist()),
                      pa.int64()),
        "f": pa.array(nulled((r.normal(size=n) * 10).tolist()),
                      pa.float64()),
        "s": pa.array(nulled(np.array(["ash", "elm", "oak", "yew", "fir"])[
            r.integers(0, 5, n)].tolist()), pa.string()),
    })))


@pytest.mark.parametrize("agg, exact", [
    (E.Count(None), True),
    (E.Count(E.Col("f")), True),
    (E.Sum(E.Col("d")), True),
    (E.Sum(E.Col("i")), True),
    (E.Sum(E.Col("f")), False),
    (E.Avg(E.Col("d")), True),
    (E.Avg(E.Col("f")), False),
    (E.Min(E.Col("i")), True),
    (E.Max(E.Col("d")), True),
    (E.Min(E.Col("s")), True),
    (E.Max(E.Col("s")), True),
    (E.StddevVariance("stddev_samp", E.Col("f")), False),
    (E.StddevVariance("var_pop", E.Col("i")), False),
    (E.First(E.Col("i")), True),
    (E.First(E.Col("i"), ignore_nulls=True), True),
    (E.First(E.Col("s"), ignore_nulls=True), True),
    (E.Count(E.Col("i"), distinct=True), True),
], ids=lambda a: getattr(a, "name", None))
@pytest.mark.parametrize("grouped", [True, False],
                         ids=["by_tag", "global"])
def test_aggregate_evaluator_agrees_across_engines(ex, typed_rel, agg,
                                                   exact, grouped):
    """One evaluator (``P._compute_agg``) under both engines: with
    dictionary keys, or no keys, the mesh runs it through ``PSumAggExec``
    with psum / pmin / pmax as its merge (DISTINCT by keys: through the
    exchange and ``DistSortAggExec``), one chip with the identity.
    Integers, decimals and dictionary values agree exactly, floats to
    1e-9."""
    keys = (E.Col("tag"),) if grouped else ()
    plan = L.Aggregate(keys, keys + (E.Alias(agg, "x"),), typed_rel)
    got = {r[:-1]: r[-1] for r in _rows(ex.execute_logical(plan))}
    want = {r[:-1]: r[-1] for r in _rows(execute_logical(plan))}
    assert set(got) == set(want) and len(want) == (4 if grouped else 1)
    for k, w in want.items():
        assert w is not None
        if exact:
            assert got[k] == w and type(got[k]) is type(w)
        else:
            assert got[k] == pytest.approx(w, rel=1e-9)


@pytest.mark.parametrize("agg", [
    E.Percentile(E.Col("f"), 0.5),
    E.Percentile(E.Col("f"), 0.5, interpolate=True),
    E.Collect(E.Col("i")),
    E.Collect(E.Col("i"), unique=True),
], ids=lambda a: a.name)
def test_mesh_merge_refuses_what_needs_a_groups_rows_together(
        ex, typed_rel, agg):
    plan = L.Aggregate((E.Col("tag"),),
                       (E.Col("tag"), E.Alias(agg, "x")), typed_rel)
    with pytest.raises(NotImplementedError, match="distributed aggregate"):
        ex.execute_logical(plan)
    assert len(_rows(execute_logical(plan))) == 4


@pytest.mark.slow
def test_shuffle_group_agg(ex, rel):
    """int keys -> hash exchange + sort-agg path."""
    plan = L.Aggregate(
        (E.Col("k"),),
        (E.Col("k"), E.Alias(E.Sum(E.Col("v")), "s"),
         E.Alias(E.Count(E.Col("v")), "n"),
         E.Alias(E.Avg(E.Col("v")), "a")),
        rel)
    got = {r[0]: r[1:] for r in _rows(ex.execute_logical(plan))}
    want = {r[0]: r[1:] for r in _rows(execute_logical(plan))}
    assert set(got) == set(want)
    for k in want:
        assert got[k][0] == pytest.approx(want[k][0], rel=1e-9)
        assert got[k][1] == want[k][1]


def test_sort_global(ex, rel):
    plan = L.Sort((E.SortOrder(E.Col("v"), ascending=True),
                   E.SortOrder(E.Col("k"), ascending=False)), rel)
    check(ex, plan, ordered=True, order_cols=["v", "k"])


def test_sort_desc_nulls(ex, rel):
    plan = L.Sort((E.SortOrder(E.Col("v"), ascending=False),), rel)
    check(ex, plan, ordered=True, order_cols=["v"])


def test_sort_string_key(ex, rel):
    plan = L.Sort((E.SortOrder(E.Col("tag")),
                   E.SortOrder(E.Col("k"))), rel)
    check(ex, plan, ordered=True, order_cols=["tag", "k"])


def _check_limit(ex, plan, key_name):
    """limit keeps a tie-dependent subset; compare the key column
    sequence only (Spark gives the same non-guarantee on ties)."""
    got = ex.execute_logical(plan)
    want = execute_logical(plan)
    ki = list(want.schema.names).index(key_name)
    assert [r[ki] for r in _rows(got)] == [r[ki] for r in _rows(want)]


def test_limit_after_sort(ex, rel):
    plan = L.Limit(17, L.Sort((E.SortOrder(E.Col("v")),), rel))
    _check_limit(ex, plan, "v")


def test_limit_offset(ex, rel):
    plan = L.Limit(10, L.Sort((E.SortOrder(E.Col("v")),), rel), offset=5)
    _check_limit(ex, plan, "v")


def test_distinct(ex, rel):
    plan = L.Distinct(L.Project((E.Col("k"),), rel))
    check(ex, plan)


def test_union(ex, rel, rng):
    other = L.Relation(table(rng, n=1000))
    plan = L.Union(L.Filter(E.Col("k") < 10, rel),
                   L.Filter(E.Col("k") >= 40, other))
    check(ex, plan)


def test_repartition(ex, rel):
    plan = L.Repartition(8, (E.Col("k"),), rel)
    check(ex, plan)


# ---- joins ------------------------------------------------------------------


@pytest.fixture(scope="module")
def join_sides(rng):
    n = 2000
    left = from_arrow(pa.table({
        "id": pa.array(rng.integers(0, 300, n), pa.int64()),
        "x": pa.array(rng.normal(size=n), pa.float64()),
    }))
    m = 400
    right = from_arrow(pa.table({
        "id": pa.array(rng.integers(0, 300, m), pa.int64()),
        "name": pa.array(
            list(np.array(["a", "b", "c"])[rng.integers(0, 3, m)]),
            pa.string()),
    }))
    return L.Relation(left), L.Relation(right)


@pytest.mark.parametrize("how", ["inner", "left", "right", "full",
                                 "left_semi", "left_anti"])
def test_join_partitioned(ex, join_sides, how):
    l, r = join_sides
    plan = L.Join(l, r, how, (E.Col("id"),), (E.Col("id"),))
    big = MeshExecutor(ex.mesh, broadcast_threshold=1)  # force partition
    got = big.execute_logical(plan)
    want = execute_logical(plan)
    keys = list(range(len(want.schema.names)))
    assert _rows(got, keys) == _rows(want, keys)


@pytest.mark.parametrize("how", ["inner", "left", "left_semi", "left_anti"])
def test_join_broadcast(ex, join_sides, how):
    l, r = join_sides
    plan = L.Join(l, r, how, (E.Col("id"),), (E.Col("id"),))
    check(ex, plan)


def test_join_with_condition(ex, join_sides):
    l, r = join_sides
    plan = L.Join(l, r, "inner", (E.Col("id"),), (E.Col("id"),),
                  condition=E.Col("x") > 0.0)
    check(ex, plan)


def test_join_string_key(ex, rng):
    n = 1500
    left = L.Relation(from_arrow(pa.table({
        "tag": pa.array(
            list(np.array(["red", "green", "blue"])[rng.integers(0, 3, n)])),
        "v": pa.array(rng.normal(size=n))})))
    right = L.Relation(from_arrow(pa.table({
        "tag": pa.array(
            list(np.array(["green", "blue", "gold"])[rng.integers(0, 3, 100)])),
        "w": pa.array(rng.normal(size=100))})))
    plan = L.Join(left, right, "inner", (E.Col("tag"),), (E.Col("tag"),))
    big = MeshExecutor(ex.mesh, broadcast_threshold=1)
    got = big.execute_logical(plan)
    want = execute_logical(plan)
    keys = list(range(len(want.schema.names)))
    assert _rows(got, keys) == _rows(want, keys)


def test_cross_join(ex, rng):
    left = L.Relation(from_arrow(pa.table({"a": np.arange(37)})))
    right = L.Relation(from_arrow(pa.table({"b": np.arange(11)})))
    plan = L.Join(left, right, "cross", (), ())
    check(ex, plan)


# ---- regressions ------------------------------------------------------------


def test_relation_cache_no_id_aliasing(ex):
    """Fresh Batch objects reusing a dead Batch's id must not serve stale
    shards (cache keys are weak object refs, not id())."""
    for i in range(6):
        b = from_arrow(pa.table({"x": np.full(100, i, dtype=np.int64)}))
        plan = L.Distinct(L.Relation(b))
        got = {r[0] for r in _rows(ex.execute_logical(plan))}
        assert got == {i}, (i, got)


def test_join_computed_string_key(ex, rng):
    """Computed (non-Col) string join keys: union dictionaries must come
    from the evaluated keys, not static schema analysis."""
    n = 300
    left = L.Relation(from_arrow(pa.table({
        "tag": pa.array(
            list(np.array(["xred", "xgreen", "xblue"])[rng.integers(0, 3, n)])),
        "v": pa.array(rng.normal(size=n))})))
    right = L.Relation(from_arrow(pa.table({
        "t2": pa.array(
            list(np.array(["red", "green", "gold"])[rng.integers(0, 3, 80)])),
        "w": pa.array(rng.normal(size=80))})))
    key = E.Substring(E.Col("tag"), 2, 100)
    plan = L.Join(left, right, "inner", (key,), (E.Col("t2"),))
    for threshold in (1, 1 << 20):  # partitioned and broadcast paths
        mex = MeshExecutor(ex.mesh, broadcast_threshold=threshold)
        got = mex.execute_logical(plan)
        want = execute_logical(plan)
        keys = list(range(len(want.schema.names)))
        assert _rows(got, keys) == _rows(want, keys), threshold


def test_limit_unsorted_flat_order(ex):
    """Flat array order == global row order: limit over an unsorted
    relation returns the same leading rows as single-device."""
    b = from_arrow(pa.table({"x": np.arange(2000, dtype=np.int64)}))
    plan = L.Limit(7, L.Relation(b))
    got = ex.execute_logical(plan)
    want = execute_logical(plan)
    assert _rows(got) == _rows(want)


def test_sample_varies_across_devices(ex):
    plan = L.Sample(0.5, 7, L.Range(0, 4096, 1))
    got = ex.execute_logical(plan)
    kept = np.sort(np.array([r[0] for r in _rows(got)]))
    n = kept.size
    assert 1500 < n < 2600
    # a correlated per-device pattern would keep aligned runs; check the
    # kept set is not simply blocks of consecutive ids
    gaps = np.diff(kept)
    assert (gaps == 1).mean() < 0.8


# ---- end-to-end through the session API ------------------------------------


def test_session_mesh_master(rng):
    from spark_tpu.api.session import SparkSession
    import spark_tpu.api.functions as F

    SparkSession._reset()
    try:
        spark = (SparkSession.builder.master("mesh[8]")
                 .appName("dist-test").getOrCreate())
        assert spark.mesh_executor is not None
        df = spark.range(1000).withColumn(
            "g", (E.Col("id") % 10).alias("g"))
        out = (df.groupBy("g").agg(F.count("*").alias("n"),
                                   F.sum("id").alias("s"))
               .sort("g").collect())
        assert len(out) == 10
        assert all(r["n"] == 100 for r in out)
        assert sum(r["s"] for r in out) == 999 * 1000 // 2
        assert df.count() == 1000
    finally:
        SparkSession._reset()


def test_skew_join_rebalances_to_broadcast(spark):
    """90%-one-key join: the hash exchange would land ~all pairs on one
    device (and static shapes size EVERY device at that capacity); the
    skew detector re-plans as a broadcast join over the balanced
    pre-exchange distribution (reference: OptimizeSkewedJoin.scala:37 /
    DynamicJoinSelection). Asserts bounded per-device pair capacity AND
    row parity."""
    import numpy as np
    import pyarrow as pa

    from spark_tpu import metrics
    from spark_tpu.parallel.executor import MeshExecutor
    from spark_tpu.parallel.mesh import make_mesh
    from spark_tpu.sql.parser import parse_sql

    rng = np.random.default_rng(17)
    n = 40_000
    hot = rng.random(n) < 0.9
    keys = np.where(hot, 7, rng.integers(0, 1000, n))
    spark.createDataFrame(pa.table({
        "k": pa.array(keys, pa.int64()),
        "v": pa.array(np.arange(n), pa.int64()),
    })).createOrReplaceTempView("skew_probe")
    spark.createDataFrame(pa.table({
        "k": pa.array(np.arange(1000), pa.int64()),
        "w": pa.array(np.arange(1000) * 10, pa.int64()),
    })).createOrReplaceTempView("skew_build")
    # force the exchange path: drop the broadcast threshold (on the
    # EXECUTOR's conf) so the skew detector has to fire
    from spark_tpu import conf as _conf

    metrics.reset()
    sql = ("select count(*) as c, sum(w) as s from skew_probe "
           "join skew_build on skew_probe.k = skew_build.k")
    plan = parse_sql(sql, spark.catalog)
    ex = MeshExecutor(make_mesh(8))
    ex.conf.set(_conf.BROADCAST_THRESHOLD.key, 1)
    ex.conf.set(_conf.SKEW_MIN_PAIRS.key, 5000)
    from spark_tpu.parallel import operators as D

    apply_caps = []
    real_run_stage = ex._run_stage

    def spy(stage):
        if isinstance(stage, D.JoinApplyExec):
            apply_caps.append(stage.pair_capacity)
        return real_run_stage(stage)

    ex._run_stage = spy
    got = ex.execute_logical(plan).to_pylist()[0]
    evs = [e for e in metrics.recent(300)
           if e["kind"] == "skew_join_broadcast"]
    assert evs, "skew detector did not fire"
    # bounded capacity: the apply stage sizes near total/d, NOT near the
    # hot device's pre-rebalance count (~0.9 * n)
    assert apply_caps, "no JoinApplyExec observed"
    assert max(apply_caps) <= (n // 8) * 2 + 2048, apply_caps
    want = spark.sql(sql).collect()[0]
    assert got["c"] == want["c"] == n
    assert got["s"] == want["s"]


def test_multi_distinct_different_columns_global(spark):
    """Global aggregate mixing DISTINCT aggs over DIFFERENT columns
    (reference: RewriteDistinctAggregates.scala:1) — previously a
    NotImplementedError cliff."""
    from spark_tpu.expr import expressions as E
    from spark_tpu.parallel.executor import MeshExecutor
    from spark_tpu.parallel.mesh import make_mesh
    from spark_tpu.plan import logical as L

    rows = [{"a": i % 7, "b": i % 11, "v": i} for i in range(2000)]
    df = spark.createDataFrame(rows)
    plan = L.Aggregate(
        (), (E.Alias(E.Count(E.Col("a"), distinct=True), "da"),
             E.Alias(E.Count(E.Col("b"), distinct=True), "db"),
             E.Alias(E.Sum(E.Col("v")), "s"),
             E.Alias(E.Count(None), "n")),
        df._plan)
    ex = MeshExecutor(make_mesh(8))
    r = ex.execute_logical(plan).to_pylist()[0]
    assert (r["da"], r["db"], r["s"], r["n"]) == (
        7, 11, sum(x["v"] for x in rows), 2000)


def test_windows_with_different_partition_keys(spark):
    """Two window specs with DIFFERENT partition key sets in one
    SELECT chain exchanges (WindowExec ClusteredDistribution cascade)."""
    import sqlite3

    from spark_tpu.parallel.executor import MeshExecutor
    from spark_tpu.parallel.mesh import make_mesh
    from spark_tpu.plan.optimizer import optimize
    from spark_tpu.plan.subquery import rewrite_subqueries
    from spark_tpu.sql.parser import parse_sql

    rows = [{"g": i % 3, "h": i % 5, "v": i} for i in range(200)]
    spark.createDataFrame(rows).createOrReplaceTempView("mw")
    sql = ("select g, h, v, sum(v) over (partition by g) as sg, "
           "sum(v) over (partition by h) as sh, "
           "row_number() over (order by v) as rn from mw "
           "order by v")
    plan = optimize(rewrite_subqueries(
        parse_sql(sql, catalog=spark.catalog)))
    ex = MeshExecutor(make_mesh(8))
    got = [(r["g"], r["h"], r["v"], r["sg"], r["sh"], r["rn"])
           for r in ex.execute_logical(plan).to_pylist()]

    conn = sqlite3.connect(":memory:")
    conn.execute("create table mw(g int, h int, v int)")
    conn.executemany("insert into mw values (?,?,?)",
                     [(r["g"], r["h"], r["v"]) for r in rows])
    want = conn.execute(sql).fetchall()
    assert got == [tuple(w) for w in want]
