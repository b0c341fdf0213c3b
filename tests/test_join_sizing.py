"""A join's first execution sized in compiled programs, and the capacities
of what the data's values move, as the benchmark's cell ``tpch_sf10_q3``
runs TPC-H Q3: parquet from benchmark/tpch_gen.py and the SQL text from
benchmark/queries/q3_shipping.sql (the files the chip uses, loaded by
path), at SF0.02, two seeds. Every execution equals the sqlite oracle
exactly; the first sizes each of the two joins in compiled stages
(``join.size``: the keys' ranges, the matches, the build side's index)
and runs none of them operation by operation; the second binds what the
first recorded and builds nothing."""

import datetime
import decimal
import os
import re
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from spark_tpu import metrics, trace
from spark_tpu.physical import kernels as K
from spark_tpu.physical import operators as P
from test_q14_join_replay import _load, _lookups, _new_events

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
SF, STRUCTURE = 0.02, 20260729    # an SF no other test file uses
SEEDS = (40, 2**31 + 4040)        # a run's seed may pass 32 signed bits
EXECUTIONS = 3
Q3_COUNTS_SF10 = {"customers": (299_205, 301_042),    # ten seeds' extremes
                  "orders": (1_548_290, 1_565_034),
                  "joined": (316_944, 321_037)}


def _scale4(x: float) -> decimal.Decimal:
    """sqlite sums in REAL; an order's revenue, a sum of at most seven
    products of two hundredths, is off by 1e-9 at most, so rounded to the
    result's own scale it is the exact decimal."""
    return decimal.Decimal(repr(round(x, 4))).quantize(
        decimal.Decimal("0.0001"))


def _rows(df):
    return [tuple(r.asDict().values()) for r in df.collect()]


@pytest.fixture(scope="module")
def runs(spark, tmp_path_factory):
    """Per seed: the rows, events and compile-cache lookups of each
    execution of the cell's text and of its groups (the text without
    ORDER BY / LIMIT), and the sqlite oracle's rows of both."""
    import pyarrow.parquet as pq

    from spark_tpu.tpch import oracle

    sys.path.insert(0, BENCH)
    try:
        gen = _load("bench_tpch_gen", os.path.join(BENCH, "tpch_gen.py"))
    finally:
        sys.path.remove(BENCH)
    with open(os.path.join(BENCH, "queries", "q3_shipping.sql")) as f:
        text = f.read()
    groups = re.sub(r"\border by.*", "", text, flags=re.S)
    root = str(tmp_path_factory.mktemp("q3"))
    out = {}
    for seed in SEEDS:
        path = gen.ensure_dataset(root, SF, seed, STRUCTURE)
        gen.register_views(spark, path)
        last = metrics.recent(1)
        seen = [last[-1]["n"] if last else -1]
        executions = []
        for _ in range(EXECUTIONS):
            before = _lookups()
            rows = _rows(spark.sql(text))
            executions.append((rows, _new_events(seen), _lookups() - before))
        conn = oracle.load_sqlite({t: pq.read_table(
            os.path.join(path, f"{t}.parquet"))
            for t in ("customer", "orders", "lineitem")})
        out[seed] = {"executions": executions,
                     "oracle": oracle.run_oracle(conn, text),
                     "groups": sorted(_rows(spark.sql(groups))),
                     "oracle_groups": sorted(oracle.run_oracle(conn, groups))}
        conn.close()
    return out


def _exact(rows):
    """The oracle's rows as the engine's types: sqlite keeps a date as
    its ISO text."""
    return [(k, _scale4(v), datetime.date.fromisoformat(d), p)
            for k, v, d, p in rows]


@pytest.mark.parametrize("seed", SEEDS)
def test_every_execution_equals_the_sqlite_oracle(runs, seed):
    want = _exact(runs[seed]["oracle"])
    assert len(want) == 10
    for rows, _events, _n in runs[seed]["executions"]:
        assert [r[1].as_tuple().exponent for r in rows] == [-4] * 10
        assert rows == want       # Decimal against Decimal: exact


@pytest.mark.parametrize("seed", SEEDS)
def test_every_group_equals_the_sqlite_oracle(runs, seed):
    """The cell's ``correct`` compares the top ten; here every order's
    revenue is held to the oracle, as chip_smoke.py's q3_groups does on
    the chip."""
    want = _exact(runs[seed]["oracle_groups"])
    assert len(want) > 100
    assert runs[seed]["groups"] == want


def _of(events, kind, name=None):
    return [e for e in events if e["kind"] == kind
            and (name is None or e.get("name") == name)]


def test_the_first_execution_sizes_the_joins_in_compiled_stages(runs):
    """No join runs operation by operation (some eighty programs each on
    a cold chip, the XLA sorts among them tens of seconds apiece): each
    of the two is sized in a ``join.size`` span by three compiled stages
    (its keys' ranges, its matches, the build side's index), then run as
    a stage of its own where the next operator is sized from its output,
    and the whole query's stage runs last, bound to all of it."""
    _rows_got, events, lookups = runs[SEEDS[0]]["executions"][0]
    assert not [e for e in _of(events, "stage") if e["op"] == "blocking"]
    sized = _of(events, "span", "join.size")
    assert [e["how"] for e in sized] == ["inner", "inner"]
    for e in sized:
        assert 0 < e["rows"] <= e["capacity"]
    stages = [e["node"].split("[")[0] for e in _of(events, "stage_compile")]
    assert stages == ["JoinSize", "JoinSize", "JoinIndex", "Join",
                      "JoinSize", "JoinSize", "JoinIndex", "Join",
                      "GroupCount", "Compact"]
    assert lookups <= 2 * len(stages)
    # the joins of the whole query's stage, traced from the recorded
    # index and stats: the capacities the spans chose
    joins = _of(events, "join")
    assert {(e["rung"], e["sized"]) for e in joins} == {("table", True)}
    assert [e["capacity"] for e in joins[-2:]] == [
        e["capacity"] for e in sized]


@pytest.mark.parametrize("execution", [1, 2])
def test_a_later_execution_binds_the_stats_and_builds_nothing(
        runs, execution):
    _rows_got, events, lookups = runs[SEEDS[0]]["executions"][execution]
    assert lookups == 0
    assert [e["kind"] for e in events
            if e["kind"] in trace.BUILD_EVENTS | {"stage_compile"}] == []
    assert not _of(events, "span", "join.size")
    assert [e["op"] for e in _of(events, "stage")] == ["fused"]


def test_a_second_seed_sizes_again_through_the_same_programs(runs):
    """Another seed's arrays are new, so its joins are sized again; at
    this scale its counts fall in the first seed's buckets, so every
    sizing program and stage is found in the stage cache."""
    _rows_got, events, _n = runs[SEEDS[1]]["executions"][0]
    assert len(_of(events, "span", "join.size")) == 2
    assert _of(events, "stage_compile") == []


# ---- the capacities a seed moves --------------------------------------------


def test_relative_bucket_is_monotone_covers_and_steps_an_eighth():
    rng = np.random.default_rng(40)
    ns = np.unique(np.concatenate([
        np.arange(0, 70_000), rng.integers(1, 1 << 40, 20_000),
        [(1 << k) + d for k in range(10, 40) for d in (-1, 0, 1)]]))
    caps = np.array([K.relative_bucket(int(n)) for n in ns])
    assert (np.diff(caps) >= 0).all()                 # monotone
    assert (caps >= np.maximum(ns, 1)).all()          # never below
    small = ns < 16_384                               # bucket's below 2^14
    assert all(c == K.bucket(int(n)) for n, c in zip(ns[small], caps[small]))
    step = np.maximum(1024, 2.0 ** (np.floor(np.log2(np.maximum(ns, 1)))
                                    - K.RELATIVE_BUCKET_BITS))
    assert ((caps - np.maximum(ns, 1)) < step).all()  # padding under a step
    assert ((caps - ns)[ns >= 16_384] <= ns[ns >= 16_384] / 8).all()
    # TPC-H Q3 at SF10: ten seeds' orders of BUILDING customers and their
    # lines after the cut, each in one bucket whatever the seed
    for lo, hi in Q3_COUNTS_SF10.values():
        assert K.relative_bucket(lo) == K.relative_bucket(hi)
    assert K.relative_bucket(1_555_000) == 1_572_864
    assert K.relative_bucket(319_000) == 327_680


@pytest.mark.parametrize("keeps,relative", [
    (("==", "A"), True), (("in", ("A", "B")), True), (("<", 55_000), False)])
def test_a_scan_that_keeps_string_values_takes_the_relative_grid(
        tmp_path, keeps, relative):
    """Two loads of a table whose string column a seed draws: the rows
    a pushed ``seg = 'A'`` (or ``IN``) keeps differ by some hundreds and
    share ``relative_bucket``'s capacity, so the programs that read the
    scan are the same; a range on an integer keeps ``bucket``'s (the
    date-filtered scans of the accepted cells: Q15's 2,421,760 rows)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_tpu.expr import expressions as E
    from spark_tpu.io.datasource import FileSource

    op, value = keeps
    keep = (E.In(E.Col("seg"), value) if op == "in"
            else E.Cmp(op, E.Col("seg" if op == "==" else "v"),
                       E.Literal(value)))
    caps = []
    for n in (54_500, 55_500):
        path = str(tmp_path / f"t{n}.parquet")
        pq.write_table(pa.table({
            "v": np.arange(110_000),
            "seg": np.where(np.arange(110_000) < n, "A", "C")}), path)
        batch = FileSource("parquet", [path]).read(("v",), (keep,))
        assert int(np.asarray(batch.data.row_mask).sum()) == (
            n if op != "<" else 55_000)
        caps.append(batch.capacity)
    if relative:
        assert caps == [K.relative_bucket(55_000)] * 2 == [57_344] * 2
    else:
        assert caps == [K.bucket(55_000)] * 2 == [55_296] * 2
    # TPC-H Q3 at SF10: ten seeds' BUILDING customers in one capacity
    lo, hi = Q3_COUNTS_SF10["customers"]
    assert K.relative_bucket(lo) == K.relative_bucket(hi) == 327_680


def test_key_packing_absorbs_a_bound_the_data_moves():
    """Whether customer 1,500,000 (who has no orders) is in the segment
    moves the observed greatest key: the packing, which is in the
    program, does not move."""
    assert K.key_packing(1, 1_500_000) == K.key_packing(1, 1_499_999) == (
        0, 1_500_160)
    for lo, hi in [(-5, 7), (3000, 3001), (10, 2**40), (0, 0)]:
        mn, rg = K.key_packing(lo, hi)
        assert mn <= lo and hi < mn + rg and mn % 1024 == rg % 1024 == 0
    assert K.key_packing(5, 4) == (0, 1024)           # no live key


@pytest.mark.parametrize("domain", [4096, None])
def test_join_counts_agree_with_a_loop(domain):
    """K.join_counts over a dense domain (two scatter-adds) and past it
    (two sorted builds): the matches, the most matches of a probe row
    and of a build row, dead and masked rows excluded."""
    rng = np.random.default_rng(41)
    build = rng.integers(0, 3000, 5000)
    probe = rng.integers(0, 3000, 2000)
    bok, pok = rng.random(5000) < 0.8, rng.random(2000) < 0.9
    got = [int(v) for v in K.join_counts(
        jnp.asarray(build), jnp.asarray(bok), jnp.asarray(probe),
        jnp.asarray(pok), domain)]
    bcount = np.bincount(build[bok], minlength=3000)
    pcount = np.bincount(probe[pok], minlength=3000)
    per_probe = np.where(pok, bcount[probe], 0)
    assert got == [per_probe.sum(), per_probe.max(),
                   np.where(bok, pcount[build], 0).max()]


def test_dense_tables_are_the_sorted_keys_runs():
    rng = np.random.default_rng(42)
    key = jnp.asarray(rng.integers(0, 500, 3000))
    ok = jnp.asarray(rng.random(3000) < 0.7)
    _perm, skey, lo, cnt = K.make_join_index(key, ok, 512)
    vals = jnp.arange(512)
    want_lo = jnp.searchsorted(skey, vals, side="left")
    want_hi = jnp.searchsorted(skey, vals, side="right")
    assert (np.asarray(lo) == np.asarray(want_lo)).all()
    assert (np.asarray(cnt) == np.asarray(want_hi - want_lo)).all()
    assert lo.dtype == cnt.dtype == jnp.int32


def test_q15s_slots_stay_where_they_are():
    """A sort-based aggregate bound to 100,000 groups keeps 100,096 slots
    (at most 101,096 whatever the sizing rule): rounded up to a power of
    two they cost tpch_sf10_q15_revenue 6.1 % of ``query_ms`` in its
    slot-sized bound searches (ledger; PERF.md section 6)."""
    slots = P.HashAggregateExec((), (), None, 100_000).sorted_slots
    assert slots <= 101_096 and slots == 100_096


@pytest.mark.parametrize("groups,capacity,slots", [
    (100_000, 2_421_760, 100_096),    # tpch_sf10_q15_revenue: the count's
    (120_000, 327_680, 327_680),      # tpch_sf10_q3: past a quarter, rows'
    (384, 2048, 512), (600, 2048, 2048)])
def test_groups_past_a_quarter_of_the_rows_take_their_capacity(
        groups, capacity, slots):
    """What ``_trace_sorted`` sizes its output by: the group count's
    256-slot bucket, or where the groups pass a quarter of the rows the
    rows' capacity, which a seed that moves the count does not move."""
    from spark_tpu import types as T
    from spark_tpu.expr import expressions as E
    from spark_tpu.expr.compiler import TV

    agg = P.HashAggregateExec((E.Col("k"),), (E.Col("k"),),
                              P._SchemaOnly(None), groups)
    pipe = P.Pipe({"k": TV(jnp.zeros((capacity,), jnp.int64), None,
                           T.INT64)}, jnp.ones((capacity,), bool), ["k"])
    last = metrics.recent(1)[-1]["n"] if metrics.recent(1) else -1
    out = agg._trace_sorted(pipe)
    events = [e for e in metrics.recent(64)
              if e["n"] > last and e["kind"] == "group_by"]
    assert out.capacity == slots
    assert [(e["k"], e["groups"]) for e in events] == [(slots, groups)]
