"""The cell of PR 35, ``tpch_sf10_q15_revenue``, rehearsed end to end on the
CPU at SF0.01 (as test_sf10_and_join_cells.py rehearses PR 28's), and its
three per-layer metrics: ``group_sort_ms``, ``group_sum_ms`` and
``group_agg_roofline_pct``. Each reader returns ``None``, and never raises,
on a context without its scopes or its ``group_by`` event, which is what a
program older than PR 35 gives. The numbers are CPU numbers and are thrown
away."""

import json
import os

import pytest

import harness
import reference
import tpch_gen
from conftest import BENCH, ROOT
from test_rehearsal import build_root, check, last_line, names, rehearse
from test_sf10_and_join_cells import _ctx, _reader

CELL = "tpch_sf10_q15_revenue"
NEW = {"group_sort_ms": "ms", "group_sum_ms": "ms",
       "group_agg_roofline_pct": "%"}


@pytest.fixture(scope="module")
def tmp(tmp_path_factory):
    return str(tmp_path_factory.mktemp("q15_cell"))


@pytest.fixture(scope="module")
def root(tmp):
    return build_root(tmp)


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_the_entries_are_additions():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    (cell,) = [w for w in spec["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tpch_sf10_q15_local", "repeat_q15_revenue", 1)
    (entry,) = [c for c in spec["configs"] if c["name"] == cell["config"]]
    assert entry["file"] == "benchmark/configs/tpch_sf10_q15_local.json"
    assert entry["reduced"] == ["scale_factor"]
    for name, unit in NEW.items():
        (m,) = [m for m in spec["per_layer"] if m["name"] == name]
        assert (m["unit"], m["layer"], m["source"], m["moves"],
                m["workloads"]) == (unit, "kernels", "device_trace",
                                    "query_ms", [CELL])
    with open(os.path.join(BENCH, "traffic",
                           "repeat_q15_revenue.json")) as f:
        assert json.load(f) == {
            "loop": "closed", "clients": 1, "queries": ["q15_revenue"],
            "order": "round_robin", "literals": "validation"}


def test_the_configuration_is_the_sf10_deployment_with_another_query():
    mine, sf10 = _config("tpch_sf10_q15_local"), _config("tpch_sf10_local")
    for key in ("scale_factor", "structure_seed", "master", "chips",
                "session_conf", "guarantees", "reduced"):
        assert mine[key] == sf10[key], key
    assert mine["source"] != sf10["source"] and len(mine["source"]) <= 200
    for said in ("2.4.15", "1996-01-01", "max(total_revenue)", "4.1.3"):
        assert said in mine["source"], said
    assert set(mine["assumed"]) >= {
        "view_inlined", "join_and_outer_select_left_out", "query_streams",
        "literals", "row_counts", "decimal_headroom"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [c for c in json.load(f)["configs"]
                    if c["name"] == mine["name"]]
    assert entry["source"] == mine["source"]


@pytest.mark.parametrize("trace", [0, 1])
def test_new_cell(root, tmp, trace):
    result = last_line(rehearse(root, tmp, CELL, trace))
    check(root, result, CELL, trace)
    assert "query_p95_ms" not in result["metrics"]
    if trace:
        assert NEW.items() <= names(root, CELL, "per_layer").items()
        for name in NEW:
            assert result["metrics"][name]["value"] > 0, name
        assert result["metrics"]["group_agg_roofline_pct"]["value"] < 100
        assert result["metrics"]["hbm_roofline_pct"]["value"] > 0
    else:
        assert {"query_ms", "rows_per_s", "setup_s"} == set(result["metrics"])


def test_the_reference_is_one_exact_row_and_moves_with_the_seed(tmp):
    q = harness.Query(BENCH, "q15_revenue")
    assert q.tables == ("lineitem",) and not q.ordered
    rows, moved = [], []
    for seed in (2**31 + 7, 12):
        path = tpch_gen.ensure_dataset(os.path.join(tmp, "data"), 0.01, seed,
                                       20260729)
        q.prepare(path)
        ((value,),) = q.want
        assert value.as_tuple().exponent == -4     # decimal(.., 4), exact
        rows.append(value)
        li = reference.frame(path, "lineitem", ["l_shipdate", "l_suppkey"])
        quarter = li[(li.l_shipdate >= reference.days(1996, 1, 1))
                     & (li.l_shipdate < reference.days(1996, 4, 1))]
        # what the query must move: three int32 a row in, a sum a group out
        assert q.module.hbm_bytes(path) == (
            len(quarter) * 12 + quarter.l_suppkey.nunique() * 8)
        moved.append((len(quarter), quarter.l_suppkey.nunique()))
    assert rows[0] != rows[1]
    assert moved[0] == moved[1]       # a seed sets values and never a shape


# ---- the readers on contexts without their events ---------------------------


class _Query:
    name = "q15_revenue"


class _Execution:
    error = None
    query = _Query()


@pytest.mark.parametrize("name", list(NEW))
def test_a_reader_with_nothing_to_read_returns_none(name):
    read = _reader(name)
    mine = dict(executions=[_Execution()])
    assert read(_ctx(**mine)) is None
    # the parent of PR 35: seg_sum and sort events, no group_by event, no
    # scope of its own under the aggregate's, no trace file
    old = [{"kind": "seg_sum", "rows": 1024, "k": 512, "n": 1},
           {"kind": "sort", "site": "lexsort", "rows": 1024, "n": 2}]
    assert read(_ctx(setup_events=old, **mine)) is None
    assert read(_ctx(setup_events=old, trace=None, **mine)) is None
    # the event without a trace to read the scopes from
    new = old + [{"kind": "group_by", "strategy": "sorted", "rows": 1024,
                  "k": 512, "groups": 500, "keys": ["int64"], "n": 3}]
    assert read(_ctx(setup_events=new, **mine)) is None
    # a query the reader has no byte count for (the direct path's cells)
    assert read(_ctx(setup_events=new)) is None


def test_agg_bytes_count_the_querys_work():
    module = harness._load_module(os.path.join(
        BENCH, "layer_metrics", "group_agg_roofline_pct.py"),
        "group_agg_roofline_pct")
    assert module.agg_bytes("q15_revenue", 2_380_800, 100_000) == (
        2_380_800 * 13 + 100_000 * 12)


def test_a_program_that_cannot_finish_the_cell_is_refused_at_once(
        monkeypatch):
    """The parent of PR 35 ran the first execution for 2,093 s (my chip
    run, PR 35), past a run's 1,200 s: the query's file refuses such a
    program before anything is generated or compiled, with a non-zero
    exit code and nothing on stdout. It knows it by the ``group_by``
    build event, which the cell's metrics read and which came with the
    compiled count; no class or function of the program is named."""
    from spark_tpu import trace

    path = os.path.join(BENCH, "queries", "q15_revenue.py")
    harness._load_module(path, "q15_revenue")     # this program: loads
    with open(path) as f:
        assert "physical" not in f.read()
    monkeypatch.setattr(trace, "BUILD_EVENTS",
                        trace.BUILD_EVENTS - {"group_by"})
    with pytest.raises(SystemExit) as refused:
        harness._load_module(path, "q15_revenue")
    assert "group_by" in str(refused.value)
    assert refused.value.code not in (0, None)
