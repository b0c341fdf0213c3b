"""The three readers of PR 37 that split ``glue_ms``: ``tier_decide_ms``
(span ``tier.decide``), ``admission_note_ms`` (the two ``admission.note``)
and ``host_unnamed_ms`` (``glue`` less those), on a hand-written list of
span events whose every number was worked out by hand, on a program that
lacks the spans (the parent of PR 37), and in the CPU rehearsal of a one-chip
and of the mesh cell. The rehearsal's numbers are CPU numbers and are thrown
away: only that they are there and add up is checked."""

import json
import os

import pytest

import span_times
from conftest import ROOT
from test_rehearsal import build_root, check, last_line, rehearse
from test_sf10_and_join_cells import _ctx, _reader
from test_span_times import EVENTS, span

NEW = ("tier_decide_ms", "admission_note_ms", "host_unnamed_ms")


def execution(tid, decide, notes, pin_own, root_own=0.5, stage=2.0):
    """One execution's span events: ``storage.pin`` holds a probe of 0.1,
    a ``tier.decide``, a stage and the ``admission.note`` spans, and has
    ``pin_own`` of its own; the root has ``root_own`` and a fetch of 1.0."""
    pin = 0.1 + decide + stage + sum(notes) + pin_own
    out = [span(tid, tid + "p", tid + "s", "mview.probe", 0.1),
           span(tid, tid + "d", tid + "s", "tier.decide", decide),
           span(tid, tid + "r", tid + "s", "stage.run", stage)]
    out += [span(tid, f"{tid}n{i}", tid + "s", "admission.note", ms)
            for i, ms in enumerate(notes)]
    out += [span(tid, tid + "s", tid + "e", "storage.pin", pin),
            span(tid, tid + "f", tid + "e", "query.fetch", 1.0),
            span(tid, tid + "e", None, "query.execute",
                 pin + 1.0 + root_own)]
    return out


# three executions: glue = decide + notes + pin_own + 0.1 + root_own
SPLIT = (execution("A", 0.2, (0.25, 0.05), 0.3)           # glue 1.4
         + [span("p", "p", None, "query.parse", 0.4)]
         + execution("B", 0.6, (0.3, 0.2), 0.1)           # glue 1.8
         + execution("C", 0.4, (0.35,), 0.9, stage=5.0))  # glue 2.25


def test_the_entries_are_additions_with_no_workloads_key():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    assert [m["name"] for m in spec["per_layer"][-3:]] == list(NEW)
    for m in spec["per_layer"][-3:]:
        assert m == {"name": m["name"], "unit": "ms", "better": "lower",
                     "source": "program_span", "layer": "entry / SQL",
                     "moves": "query_ms"}


@pytest.mark.parametrize("name,want", [
    ("tier_decide_ms", 0.4),                 # 0.2, 0.6, 0.4
    ("admission_note_ms", 0.35),             # 0.30, 0.50, 0.35
    ("host_unnamed_ms", 0.9),                # 0.9, 0.7, 1.5
    ("glue_ms", 1.8)])                       # 1.4, 1.8, 2.25
def test_the_split_of_glue(name, want):
    assert _reader(name)(_ctx(slice_events=SPLIT)) == pytest.approx(want)


def test_the_parts_are_the_whole_execution_by_execution():
    for d in span_times.per_execution(SPLIT):
        if "glue" in d:
            own = d["storage.pin"] + d["mview.probe"] + d["query.execute"]
            assert d["glue"] == pytest.approx(
                d["tier.decide"] + d["admission.note"] + own)


def test_unnamed_is_never_negative():
    # children that ran side by side can take more than their parent has:
    # storage.pin stops at 0 and glue is short of the two spans' sum
    events = [span("A", "d", "s", "tier.decide", 2.0),
              span("A", "n", "s", "admission.note", 2.0),
              span("A", "s", "e", "storage.pin", 3.0),
              span("A", "e", None, "query.execute", 3.0)]
    assert _reader("host_unnamed_ms")(_ctx(slice_events=events)) == 0.0


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_without_a_query(name):
    read = _reader(name)
    assert read(_ctx()) is None
    only_parse = [e for e in EVENTS if e.get("name") == "query.parse"]
    assert read(_ctx(slice_events=only_parse)) is None
    flat = [e for e in EVENTS if e["kind"] != "span"]
    assert read(_ctx(slice_events=flat)) is None


def test_a_program_without_the_spans():
    """The parent of PR 37: the two spans' readers find nothing and return
    None; nothing is named, so the unnamed part is ``glue_ms``."""
    ctx = _ctx(slice_events=EVENTS)
    assert _reader("tier_decide_ms")(ctx) is None
    assert _reader("admission_note_ms")(ctx) is None
    assert _reader("host_unnamed_ms")(ctx) == pytest.approx(
        _reader("glue_ms")(ctx))


@pytest.mark.parametrize("workload,devices", [("tpch_sf1_q6", 1),
                                              ("tpch_sf1_mesh4_q1", 4)])
def test_the_rehearsal_prints_all_three(tmp_path, workload, devices):
    tmp = str(tmp_path)
    root = build_root(tmp)
    result = last_line(rehearse(root, tmp, workload, 1, devices=devices))
    check(root, result, workload, 1)
    got = {n: result["metrics"][n]["value"] for n in NEW + ("glue_ms",)}
    assert all(v >= 0 for v in got.values()), got
    assert got["tier_decide_ms"] > 0 and got["admission_note_ms"] > 0
    # medians of the parts against the median of the whole, over a slice
    # of a few executions on a shared CPU: near, not equal
    assert sum(got[n] for n in NEW) == pytest.approx(got["glue_ms"],
                                                     rel=0.25)
    # the mesh's wait on the device is still read, under its one name
    assert result["metrics"]["device_wait_ms"]["value"] > 0
