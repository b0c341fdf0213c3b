"""The cells of PR 28, ``tpch_sf10_q1`` and ``tpch_sf1_q14``, rehearsed end
to end on the CPU at SF0.01 (as test_rehearsal.py rehearses the others), and
their three per-layer metrics: ``sort_programs``, ``join_device_ms`` and
``agg_roofline_pct``. Each reader returns ``None``, and never raises, on a
context without its events or scopes, which is what a program older than
PR 28 gives. The numbers are CPU numbers and are thrown away."""

import json
import os

import pytest

import harness
import op_scopes
import reduce_trace
from conftest import BENCH, ROOT
from test_rehearsal import build_root, check, last_line, names, rehearse

NEW = {"tpch_sf10_q1": {"agg_roofline_pct": "%"},
       "tpch_sf1_q14": {"sort_programs": "count", "join_device_ms": "ms"}}


@pytest.fixture(scope="module")
def tmp(tmp_path_factory):
    return str(tmp_path_factory.mktemp("new_cells"))


@pytest.fixture(scope="module")
def root(tmp):
    return build_root(tmp)


def test_the_entries_are_additions():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    assert set(NEW) <= set(cells)
    assert cells["tpch_sf10_q1"]["config"] == "tpch_sf10_local"
    assert cells["tpch_sf1_q14"]["config"] == "tpch_sf1_local"
    assert all(cells[c]["chips"] == 1 for c in NEW)
    with open(os.path.join(BENCH, "configs", "tpch_sf10_local.json")) as f:
        sf10 = json.load(f)
    with open(os.path.join(BENCH, "configs", "tpch_sf1_local.json")) as f:
        sf1 = json.load(f)
    assert sf10["scale_factor"] == 10.0 and sf10["session_conf"] == {}
    for key in ("structure_seed", "master", "chips", "guarantees", "reduced"):
        assert sf10[key] == sf1[key], key
    assert set(sf10["assumed"]) >= {"two_streams", "query_streams",
                                    "literals", "row_counts",
                                    "decimal_headroom"}
    for cell, metrics in NEW.items():
        for name, unit in metrics.items():
            (m,) = [m for m in spec["per_layer"] if m["name"] == name]
            assert m["unit"] == unit and m["workloads"] == [cell]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", list(NEW))
def test_new_cell(root, tmp, cell, trace):
    result = last_line(rehearse(root, tmp, cell, trace))
    check(root, result, cell, trace)
    assert "query_p95_ms" not in result["metrics"]
    if trace:
        assert NEW[cell].items() <= names(root, cell, "per_layer").items()
        for name in NEW[cell]:
            assert result["metrics"][name]["value"] > 0, name
        assert result["metrics"]["hbm_roofline_pct"]["value"] > 0
    else:
        assert {"query_ms", "rows_per_s", "setup_s"} == set(result["metrics"])


# ---- the readers on contexts without their events ---------------------------


class _Cell:
    bench_dir = "/nonexistent"
    entry = {"name": "nothing"}


class _Query:
    name = "q1"


class _Execution:
    error = None
    query = _Query()


def _reader(name):
    return harness._load_module(
        os.path.join(BENCH, "layer_metrics", name + ".py"), name).read


def _ctx(**over):
    ctx = {"cell": _Cell(), "chips": 1, "executions": [_Execution()],
           "peaks": {"hbm_bytes_per_s": 819e9}, "setup_events": [],
           "slice_events": [], "trace": {"executions": 2, "busy_s": 0.1}}
    ctx.update(over)
    return ctx


@pytest.mark.parametrize("name", ["sort_programs", "join_device_ms",
                                  "agg_roofline_pct"])
def test_a_reader_with_nothing_to_read_returns_none(name):
    read = _reader(name)
    assert read(_ctx()) is None
    # the parent of PR 28: seg_sum events, no sort event, no trace file
    old = [{"kind": "seg_sum", "rows": 1024, "n": 1},
           {"kind": "stage_compile", "ms": 1.0, "n": 2}]
    assert read(_ctx(setup_events=old)) is None
    assert read(_ctx(setup_events=old, trace=None)) is None


def test_sort_programs_counts_the_sort_events_of_set_up():
    events = [{"kind": "sort", "site": "join_index", "rows": 9, "n": i}
              for i in range(3)] + [{"kind": "seg_sum", "rows": 9, "n": 4}]
    assert _reader("sort_programs")(_ctx(setup_events=events)) == 3


def test_agg_bytes_are_capacity_times_the_row():
    module = harness._load_module(os.path.join(
        BENCH, "layer_metrics", "agg_roofline_pct.py"), "agg_roofline_pct")
    assert module.agg_bytes("q1", 59_990_016) == 59_990_016 * 41


# ---- the wire reader against ProfileData ------------------------------------

XSPACE = """
planes {
  name: "/device:TPU:0"
  lines {
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 2000000
             stats { metadata_id: 2 uint64_value: 7 } }
    events { metadata_id: 3 offset_ps: 8000000 duration_ps: 1000000
             stats { metadata_id: 2 uint64_value: 7 } }
    events { metadata_id: 3 offset_ps: 90000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" stats {
    metadata_id: 1 str_value: "jit(stage_fn)/spark.JoinExec/gather" } } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } }
  event_metadata { key: 3 value { id: 3 name: "copy.3" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "program_id" } }
}
planes {
  name: "/host:CPU"
  lines {
    name: "main"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 9000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 9000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.slice" } }
  event_metadata { key: 2 value { id: 2 name: "bench.collect" } }
}
planes {
  name: "/host:metadata"
  event_metadata { key: 7 value { id: 7 name: "jit_stage_fn(7)" stats {
    metadata_id: 1 bytes_value: "HLO" } } }
  stat_metadata { key: 1 value { id: 1 name: "hlo_proto" } }
}
"""


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _field(number, payload):
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _hlo_proto(instructions):
    """A serialized HloProto of one computation: (name, op_name) pairs."""
    body = b"".join(
        _field(2, _field(1, name.encode())
               + _field(7, _field(2, op_name.encode())))
        for name, op_name in instructions)
    return _field(1, _field(3, _field(1, b"main") + body))


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    from jax.profiler import ProfileData

    proto = _hlo_proto([
        ("fusion.2", "jit(stage_fn)/spark.HashAggregateExec/reduce_sum"),
        ("copy.3", "jit(stage_fn)/transpose")])
    escaped = "".join("\\%03o" % b for b in proto)
    path = str(tmp_path_factory.mktemp("xplane") / "t.xplane.pb")
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(
            XSPACE.replace('"HLO"', '"' + escaped + '"')))
    return path


def test_the_wire_reader_sees_what_profile_data_sees(xplane):
    mine = [(p.name, [(name, [(p.event_meta[m][0], t0 + o / 1e3, d / 1e3)
                              for m, o, d, _s in events])
                      for name, t0, events in p.lines])
            for p in op_scopes.read_planes(xplane)]
    assert mine == reduce_trace.load(xplane)


def test_scopes_come_from_the_stat_or_from_the_hlo_module(xplane):
    planes = op_scopes.read_planes(xplane)
    assert op_scopes.slice_bounds(planes) == (1000.0, 21000.0)
    ((plane, ops),) = op_scopes.scoped_ops(planes)
    assert plane == "/device:TPU:0"
    assert [(name, scope) for name, _s, _d, scope in ops] == [
        ("fusion.1", "JoinExec"),            # its own metadata's stat
        ("fusion.2", "HashAggregateExec"),   # the HLO module, program 7
        ("copy.3", None),                    # an op_name with no scope
        ("copy.3", None)]                    # no program_id at all
    seconds = op_scopes.scope_seconds(xplane)
    # the last copy lies outside the slice
    assert seconds == {"JoinExec": 4e-6, "HashAggregateExec": 2e-6,
                       None: 1e-6}


def test_the_innermost_scope_names_the_operation():
    assert op_scopes.scope_of(
        "jit(f)/spark.HashAggregateExec/spark.FilterExec/and") == "FilterExec"
    assert op_scopes.scope_of("jit(f)/jit(main)/mul") is None
    assert op_scopes.scope_of("/root/repo/spark_tpu/x.py") is None
    assert op_scopes.scope_of(None) is None
