"""The benchmark's copies start from the things they copy (SF0.01, CPU):
the generator has spark_tpu/tpch/gen.py's schemas, key ranges and foreign
keys; no seed moves a shape; and the pandas references agree with
spark_tpu/tpch/oracle.py's sqlite answers on the benchmark's data."""

import datetime
import os

import pyarrow.compute as pc
import pytest

import harness
import reference
import tpch_gen
from conftest import BENCH

SF = 0.01
STRUCTURE = 20260729
SEEDS = (11, 12, 2**31 + 12345)      # the driver's seeds pass 32 signed bits


@pytest.fixture(scope="module")
def datasets():
    return {s: tpch_gen.generate_tables(SF, s, STRUCTURE) for s in SEEDS}


@pytest.fixture(scope="module")
def on_disk(datasets, tmp_path_factory):
    root = str(tmp_path_factory.mktemp("copies"))
    path = tpch_gen.ensure_dataset(root, SF, SEEDS[0], STRUCTURE)
    assert os.path.exists(os.path.join(path, "_DONE"))
    return path


def test_schemas_are_the_programs(datasets):
    from spark_tpu.tpch.gen import generate_tables

    theirs = generate_tables(SF)
    mine = datasets[SEEDS[0]]
    assert set(mine) == set(theirs) == set(tpch_gen.TABLES)
    for name in theirs:
        assert mine[name].schema.equals(theirs[name].schema), name


def test_key_ranges_and_foreign_keys(datasets):
    t = datasets[SEEDS[0]]
    n = {k: v.num_rows for k, v in t.items()}
    assert (n["region"], n["nation"], n["part"], n["supplier"],
            n["partsupp"], n["customer"], n["orders"]) == (
                5, 25, 2000, 100, 8000, 1500, 15000)
    for table, key in (("part", "p_partkey"), ("supplier", "s_suppkey"),
                       ("customer", "c_custkey"), ("orders", "o_orderkey")):
        assert t[table][key].to_pylist() == list(range(1, n[table] + 1))

    def inside(child, col, parent, key):
        assert pc.all(pc.is_in(t[child][col],
                               value_set=t[parent][key].combine_chunks())
                      ).as_py(), (child, col)

    inside("lineitem", "l_orderkey", "orders", "o_orderkey")
    inside("lineitem", "l_partkey", "part", "p_partkey")
    inside("lineitem", "l_suppkey", "supplier", "s_suppkey")
    inside("orders", "o_custkey", "customer", "c_custkey")
    inside("partsupp", "ps_partkey", "part", "p_partkey")
    inside("partsupp", "ps_suppkey", "supplier", "s_suppkey")
    inside("customer", "c_nationkey", "nation", "n_nationkey")
    inside("nation", "n_regionkey", "region", "r_regionkey")
    # (l_partkey, l_suppkey) is one of the part's four partsupp pairs
    pairs = set(zip(t["partsupp"]["ps_partkey"].to_pylist(),
                    t["partsupp"]["ps_suppkey"].to_pylist()))
    li = set(zip(t["lineitem"]["l_partkey"].to_pylist(),
                 t["lineitem"]["l_suppkey"].to_pylist()))
    assert li <= pairs


def test_a_seed_sets_values_and_never_a_shape(datasets):
    a, b, c = (datasets[s] for s in SEEDS)
    for name in tpch_gen.TABLES:
        assert a[name].num_rows == b[name].num_rows == c[name].num_rows
    # what decides shapes is the same column for every seed
    for col in ("l_orderkey", "l_linenumber", "l_shipdate", "l_commitdate",
                "l_receiptdate"):
        assert a["lineitem"][col].equals(b["lineitem"][col]), col
        assert a["lineitem"][col].equals(c["lineitem"][col]), col
    assert a["orders"]["o_orderdate"].equals(c["orders"]["o_orderdate"])
    # the row count behind q14's pushed (date-only) filter
    lo, hi = datetime.date(1995, 9, 1), datetime.date(1995, 10, 1)

    def month(t):
        d = t["lineitem"]["l_shipdate"]
        return pc.sum(pc.and_(pc.greater_equal(d, lo),
                              pc.less(d, hi))).as_py()

    assert month(a) == month(b) == month(c) > 0
    # while the values differ
    for table, col in (("lineitem", "l_quantity"),
                       ("lineitem", "l_extendedprice"),
                       ("lineitem", "l_discount"), ("lineitem", "l_partkey"),
                       ("orders", "o_custkey"), ("part", "p_type")):
        assert not a[table][col].equals(b[table][col]), col
        assert not a[table][col].equals(c[table][col]), col


def test_same_seed_same_data(datasets):
    again = tpch_gen.generate_tables(SF, SEEDS[0], STRUCTURE)
    for name in tpch_gen.TABLES:
        assert again[name].equals(datasets[SEEDS[0]][name]), name


def test_the_directory_is_keyed_by_seed(on_disk, tmp_path):
    root = os.path.dirname(on_disk)
    other = tpch_gen.ensure_dataset(str(tmp_path), SF, SEEDS[1], STRUCTURE)
    assert os.path.basename(other) != os.path.basename(on_disk)
    assert tpch_gen.ensure_dataset(root, SF, SEEDS[0], STRUCTURE) == on_disk


@pytest.fixture(scope="module")
def sqlite(datasets):
    from spark_tpu.tpch.oracle import load_sqlite

    conn = load_sqlite(datasets[SEEDS[0]])
    yield conn
    conn.close()


@pytest.mark.parametrize("name", ["q1", "q3", "q5", "q6", "q14"])
def test_reference_agrees_with_the_sqlite_oracle(name, on_disk, sqlite):
    from spark_tpu.tpch.oracle import assert_rows_match, run_oracle

    q = harness.Query(BENCH, name)
    q.prepare(on_disk)
    assert_rows_match(q.want, run_oracle(sqlite, q.text), label=name)
    assert q.table_rows == sum(
        reference.table_rows(on_disk, t) for t in q.tables) > 0


def test_hbm_bytes_come_from_the_data(on_disk):
    rows = reference.table_rows(on_disk, "lineitem")
    q1 = harness.Query(BENCH, "q1").module
    q6 = harness.Query(BENCH, "q6").module
    q14 = harness.Query(BENCH, "q14").module
    assert q1.hbm_bytes(on_disk) == rows * 44
    assert 0 < q6.hbm_bytes(on_disk) < 0.05 * rows * 28
    assert q14.hbm_bytes(on_disk) > 2000 * 12


def test_the_comparison_is_exact_where_it_can_be():
    from decimal import Decimal as D

    d = datetime.date(1995, 3, 15)
    want = [("A", 1, D("10.50"), 0.5, d)]
    assert reference.rows_differ([("A", 1, D("10.5"), 0.5000001, d)],
                                 want) is None
    assert "col 2" in reference.rows_differ(
        [("A", 1, D("10.51"), 0.5, d)], want)
    assert "col 3" in reference.rows_differ(
        [("A", 1, D("10.50"), 0.51, d)], want)
    assert "col 1" in reference.rows_differ(
        [("A", 2, D("10.50"), 0.5, d)], want)
    assert "rows" in reference.rows_differ([], want)
    # a Decimal average against the reference's float: within REL
    assert reference.rows_differ([(D("25.614231"),)],
                                 [(25.6142314,)]) is None
