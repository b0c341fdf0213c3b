"""The cell ``tpch_sf10_q5`` rehearsed end to end on the CPU at SF0.01 (as
test_q3_cell.py rehearses its cell), its query's files at SF0.03 on two
seeds, and its per-layer metric ``first_exec_programs``, which returns
``None``, and never raises, on a context without the ``first_exec`` event,
which is what a program that records none gives. The numbers are CPU
numbers and are thrown away."""

import json
import os

import pytest

import harness
import reference
import tpch_gen
from conftest import BENCH, ROOT
from test_rehearsal import build_root, check, last_line, names, rehearse
from test_sf10_and_join_cells import _ctx, _reader

CELL = "tpch_sf10_q5"
SEEDS = (2**31 + 505, 55)
TABLES = ("customer", "orders", "lineitem", "supplier", "nation", "region")


@pytest.fixture(scope="module")
def tmp(tmp_path_factory):
    return str(tmp_path_factory.mktemp("q5_cell"))


@pytest.fixture(scope="module")
def root(tmp):
    return build_root(tmp)


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_entries_are_additions():
    spec = _spec()
    assert spec["workloads"][-1]["name"] == CELL
    assert spec["configs"][-1]["name"] == "tpch_sf10_q5_local"
    assert spec["per_layer"][-1]["name"] == "first_exec_programs"
    (cell,) = [w for w in spec["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tpch_sf10_q5_local", "repeat_q5_volume", 1)
    (entry,) = [c for c in spec["configs"] if c["name"] == cell["config"]]
    assert entry["file"] == "benchmark/configs/tpch_sf10_q5_local.json"
    assert entry["reduced"] == ["scale_factor"]
    (metric,) = [m for m in spec["per_layer"]
                 if m["name"] == "first_exec_programs"]
    assert metric == {"name": "first_exec_programs", "unit": "count",
                      "better": "lower", "source": "program_counter",
                      "layer": "planner, blocking run", "moves": "setup_s",
                      "workloads": [CELL]}
    with open(os.path.join(BENCH, "traffic", "repeat_q5_volume.json")) as f:
        assert json.load(f) == {
            "loop": "closed", "clients": 1, "queries": ["q5_volume"],
            "order": "round_robin", "literals": "validation"}


def test_the_configuration_is_the_sf10_deployment_with_another_query():
    with open(os.path.join(BENCH, "configs", "tpch_sf10_q5_local.json")) as f:
        mine = json.load(f)
    with open(os.path.join(BENCH, "configs", "tpch_sf10_q3_local.json")) as f:
        q3 = json.load(f)
    for key in ("scale_factor", "structure_seed", "master", "chips",
                "session_conf", "guarantees", "reduced"):
        assert mine[key] == q3[key], key
    assert len(mine["source"]) <= 200
    for said in ("2.4.5", "ASIA", "1994-01-01", "4.1.3"):
        assert said in mine["source"], said
    assert set(mine["assumed"]) >= {
        "literals", "query_streams", "row_counts", "structure_fixed_counts",
        "seed_moved_counts", "decimal_scale", "decimal_headroom",
        "resident_bytes"}
    (entry,) = [c for c in _spec()["configs"] if c["name"] == mine["name"]]
    assert entry["source"] == mine["source"]
    with open(os.path.join(BENCH, "queries", "q5_volume.sql")) as f, \
            open(os.path.join(BENCH, "queries", "q5.sql")) as g:
        assert f.read() == g.read()        # the spec's text, as q5.sql


@pytest.mark.parametrize("trace", [0, 1])
def test_new_cell(root, tmp, trace):
    result = last_line(rehearse(root, tmp, CELL, trace))
    check(root, result, CELL, trace)
    assert "query_p95_ms" not in result["metrics"]
    if trace:
        assert names(root, CELL, "per_layer")["first_exec_programs"] == "count"
        # the sizing and index programs of five joins, four join stages
        # the next join is sized over, the whole query's stage
        assert result["metrics"]["first_exec_programs"]["value"] >= 10
        assert result["metrics"]["hbm_roofline_pct"]["value"] < 100
    else:
        assert {"query_ms", "rows_per_s", "setup_s"} == set(result["metrics"])


@pytest.fixture(scope="module")
def spark():
    from spark_tpu.api.session import SparkSession

    return SparkSession.builder.appName("q5-cell").getOrCreate()


@pytest.fixture(scope="module")
def paths(tmp):
    return [tpch_gen.ensure_dataset(os.path.join(tmp, "data03"), 0.03, seed,
                                    20260729) for seed in SEEDS]


def test_the_engine_equals_the_reference_on_two_seeds(spark, paths):
    q = harness.Query(BENCH, "q5_volume")
    assert q.tables == TABLES and q.ordered
    answers = []
    for path in paths:
        tpch_gen.register_views(spark, path)
        q.prepare(path)
        assert len(q.want) == 5       # ASIA's five nations
        assert all(r[1].as_tuple().exponent == -4 for r in q.want)
        revenue = [r[1] for r in q.want]
        assert revenue == sorted(set(revenue), reverse=True)  # no tie
        for _ in range(2):      # the first execution and the steady one
            assert q.differ(spark.sql(q.text).collect()) is None
        answers.append(q.want)
    assert answers[0] != answers[1]       # the seed moves the values


def test_the_byte_counts_are_the_querys_work(paths):
    module = harness.Query(BENCH, "q5_volume").module
    for path in paths:
        tables = sum(reference.table_rows(path, t) * 8 * 4 for t in TABLES)
        for count in (module.hbm_bytes, module.join_bytes):
            assert 0 < count(path) < tables
        region, nation, supp, li, orders, cust = module._sides(path)
        assert len(region) == 1 and len(nation) == 25
        assert module.hbm_bytes(path) == 4 * (
            1 + 75 + 2 * len(supp) + 4 * len(li) + 3 * len(orders)
            + 2 * len(cust))
        assert module.join_bytes(path) > module.hbm_bytes(path)
        _sides, joins = module._joined(path)
        # ASIA's 5 nations, their suppliers, those suppliers' lines, the
        # lines of 1994, the lines whose customer shares their nation
        assert len(joins[0]) == 5 and 0 < len(joins[1]) < len(supp)
        assert len(li) > len(joins[2]) > len(joins[3]) > len(joins[4]) > 0


# ---- the reader on contexts without its event -------------------------------


def test_first_exec_programs_reads_none_without_the_event():
    read = _reader("first_exec_programs")
    assert read(_ctx()) is None
    # a program older than the event: stage compiles and sorts, no
    # first_exec
    old = [{"kind": "stage_compile", "node": "Sort[revenue DESC]", "n": 1},
           {"kind": "sort", "site": "join_index", "rows": 1024, "n": 2}]
    assert read(_ctx(setup_events=old)) is None


def test_first_exec_programs_reads_the_first_executions_programs():
    events = [{"kind": "stage_compile", "node": "JoinSize[...]", "n": 1},
              {"kind": "first_exec", "programs": 20, "sorts": 8,
               "bound": "direct", "n": 2}]
    assert _reader("first_exec_programs")(_ctx(setup_events=events)) == 20
