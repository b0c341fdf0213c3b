"""The cell ``tpch_sf10_q3`` rehearsed end to end on the CPU at SF0.01 (as
test_q15_revenue_cell.py rehearses its cell), its query's files at SF0.03
on two seeds, and its two per-layer metrics: ``join_size_s`` and
``join_roofline_pct``. Each reader returns ``None``, and never raises, on a
context without its span or its scope, which is what a program that sizes
a join operation by operation gives. The numbers are CPU numbers and are
thrown away."""

import json
import os

import pytest

import harness
import reference
import tpch_gen
from conftest import BENCH, ROOT
from test_rehearsal import build_root, check, last_line, names, rehearse
from test_sf10_and_join_cells import _ctx, _reader

CELL = "tpch_sf10_q3"
NEW = {"join_size_s": "s", "join_roofline_pct": "%"}
SEEDS = (2**31 + 303, 33)


@pytest.fixture(scope="module")
def tmp(tmp_path_factory):
    return str(tmp_path_factory.mktemp("q3_cell"))


@pytest.fixture(scope="module")
def root(tmp):
    return build_root(tmp)


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_entries_are_additions():
    spec = _spec()
    (cell,) = [w for w in spec["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tpch_sf10_q3_local", "repeat_q3_shipping", 1)
    (entry,) = [c for c in spec["configs"] if c["name"] == cell["config"]]
    assert entry["file"] == "benchmark/configs/tpch_sf10_q3_local.json"
    assert entry["reduced"] == ["scale_factor"]
    (size,) = [m for m in spec["per_layer"] if m["name"] == "join_size_s"]
    assert (size["unit"], size["source"], size["moves"], size["workloads"]) == (
        "s", "program_span", "setup_s", ["tpch_sf1_q14", CELL])
    (roof,) = [m for m in spec["per_layer"]
               if m["name"] == "join_roofline_pct"]
    assert (roof["unit"], roof["source"], roof["moves"], roof["workloads"]) == (
        "%", "device_trace", "query_ms", [CELL])
    with open(os.path.join(BENCH, "traffic",
                           "repeat_q3_shipping.json")) as f:
        assert json.load(f) == {
            "loop": "closed", "clients": 1, "queries": ["q3_shipping"],
            "order": "round_robin", "literals": "validation"}


def test_the_configuration_is_the_sf10_deployment_with_another_query():
    with open(os.path.join(BENCH, "configs", "tpch_sf10_q3_local.json")) as f:
        mine = json.load(f)
    with open(os.path.join(BENCH, "configs",
                           "tpch_sf10_q15_local.json")) as f:
        q15 = json.load(f)
    for key in ("scale_factor", "structure_seed", "master", "chips",
                "session_conf", "guarantees", "reduced"):
        assert mine[key] == q15[key], key
    assert len(mine["source"]) <= 200
    for said in ("2.4.3", "BUILDING", "1995-03-15", "4.1.3"):
        assert said in mine["source"], said
    assert set(mine["assumed"]) >= {
        "literals", "query_streams", "row_counts", "seed_moved_counts",
        "decimal_scale", "decimal_headroom"}
    (entry,) = [c for c in _spec()["configs"] if c["name"] == mine["name"]]
    assert entry["source"] == mine["source"]
    with open(os.path.join(BENCH, "queries", "q3_shipping.sql")) as f, \
            open(os.path.join(BENCH, "queries", "q3.sql")) as g:
        assert f.read() == g.read()        # the spec's text, as q3.sql


@pytest.mark.parametrize("trace", [0, 1])
def test_new_cell(root, tmp, trace):
    result = last_line(rehearse(root, tmp, CELL, trace))
    check(root, result, CELL, trace)
    assert "query_p95_ms" not in result["metrics"]
    if trace:
        assert NEW.items() <= names(root, CELL, "per_layer").items()
        for name in NEW:
            assert result["metrics"][name]["value"] > 0, name
        for name in ("join_roofline_pct", "hbm_roofline_pct"):
            assert result["metrics"][name]["value"] < 100, name
    else:
        assert {"query_ms", "rows_per_s", "setup_s"} == set(result["metrics"])


def test_q14_reports_its_join_sizing(root, tmp):
    result = last_line(rehearse(root, tmp, "tpch_sf1_q14", 1))
    check(root, result, "tpch_sf1_q14", 1)
    assert result["metrics"]["join_size_s"]["value"] > 0


@pytest.fixture(scope="module")
def spark():
    from spark_tpu.api.session import SparkSession

    return SparkSession.builder.appName("q3-cell").getOrCreate()


@pytest.fixture(scope="module")
def paths(tmp):
    return [tpch_gen.ensure_dataset(os.path.join(tmp, "data03"), 0.03, seed,
                                    20260729) for seed in SEEDS]


def test_the_engine_equals_the_reference_on_two_seeds(spark, paths):
    q = harness.Query(BENCH, "q3_shipping")
    assert q.tables == ("customer", "orders", "lineitem") and q.ordered
    answers = []
    for path in paths:
        tpch_gen.register_views(spark, path)
        q.prepare(path)
        assert len(q.want) == 10
        assert all(r[1].as_tuple().exponent == -4 for r in q.want)
        # no two of the ten tie on the ORDER BY keys: the order is the
        # query's, not the sort's, and the rows are compared in it
        keys = [(-r[1], r[2]) for r in q.want]
        assert keys == sorted(set(keys))
        for _ in range(2):      # the first execution and the steady one
            assert q.differ(spark.sql(q.text).collect()) is None
        answers.append(q.want)
    assert answers[0] != answers[1]       # the seed moves the values


def test_the_byte_counts_are_the_querys_work(paths):
    module = harness.Query(BENCH, "q3_shipping").module
    for path in paths:
        tables = sum(reference.table_rows(path, t) * 8 * 4
                     for t in ("customer", "orders", "lineitem"))
        for count in (module.hbm_bytes, module.join_bytes):
            assert 0 < count(path) < tables
        cust, orders, li = module._sides(path)
        assert module.hbm_bytes(path) == (
            len(cust) * 4 + len(orders) * 16 + len(li) * 12)
        assert module.join_bytes(path) > module.hbm_bytes(path)


# ---- the readers on contexts without their events ---------------------------


class _Query:
    name = "q3_shipping"
    module = harness.Query(BENCH, "q3_shipping").module


class _Execution:
    error = None
    query = _Query()


@pytest.mark.parametrize("name", list(NEW))
def test_a_reader_with_nothing_to_read_returns_none(name):
    read = _reader(name)
    mine = dict(executions=[_Execution()])
    assert read(_ctx(**mine)) is None
    # a program that sizes its joins operation by operation: sort and
    # stage events, no join.size span, no trace file
    old = [{"kind": "sort", "site": "join_index", "rows": 1024, "n": 1},
           {"kind": "span", "name": "stage.run", "ms": 3.0, "n": 2}]
    assert read(_ctx(setup_events=old, **mine)) is None
    assert read(_ctx(setup_events=old, trace=None, **mine)) is None
    # a query without a join byte count (every other cell's)
    assert read(_ctx(setup_events=old)) is None


def test_join_size_s_sums_the_spans_of_set_up():
    events = [{"kind": "span", "name": "join.size", "ms": 250.0, "n": 1},
              {"kind": "span", "name": "join.size", "ms": 750.0, "n": 2},
              {"kind": "span", "name": "stage.run", "ms": 9.0, "n": 3}]
    assert _reader("join_size_s")(_ctx(setup_events=events)) == 1.0


def test_a_program_without_the_compiled_join_sizing_is_refused_at_once(
        monkeypatch):
    """A program that sizes a join's first execution operation by
    operation would not end inside a run at SF10: the query's file
    refuses it before anything is generated or compiled, with a non-zero
    exit code and nothing on stdout. It knows it by the ``join.size``
    span, which the cell's ``join_size_s`` reads; no class or function
    of the program is named."""
    from spark_tpu import trace

    path = os.path.join(BENCH, "queries", "q3_shipping.py")
    harness._load_module(path, "q3_shipping")     # this program: loads
    with open(path) as f:
        assert "physical" not in f.read()
    monkeypatch.setattr(trace, "SPAN_NAMES", trace.SPAN_NAMES - {"join.size"})
    with pytest.raises(SystemExit) as refused:
        harness._load_module(path, "q3_shipping")
    assert "join.size" in str(refused.value)
    assert refused.value.code not in (0, None)
