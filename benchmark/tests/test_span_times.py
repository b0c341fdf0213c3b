"""span_times.py: the arithmetic on a hand-written list of span events, and
on a synthetic trace whose every number was worked out by hand
(data/synthetic_spans_xspace.txt): two executions under ``bench.collect``
with the program's ``spark.*`` annotations nested in them, a second host
thread, two device planes of which only the first counts.

    window  bench.slice = [1000, 21000) ns
    dev 0   busy [3900,6900) + [7200,7400) + [12500,12600) + [14900,17900)
            = 6300 ns, idle 13700 ns in five gaps
    launch  3400 -> 3900 and 13100 -> 14900 (the op at 12500 is before the
            dispatch): median of 500 and 1800 ns
"""

import os

import pytest

import reduce_trace
import span_times
from test_rehearsal import build_root, edit, last_line, rehearse

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "synthetic_spans_xspace.txt")


def span(trace_id, span_id, parent_id, name, ms):
    return {"kind": "span", "trace_id": trace_id, "span_id": span_id,
            "parent_id": parent_id, "name": name, "ms": ms}


EVENTS = [
    span("p1", "p1", None, "query.parse", 0.3),
    # execution A: every phase, nested three deep
    span("A", "a2", "a1", "query.analysis", 0.5),
    span("A", "a4", "a3", "mview.probe", 0.2),
    span("A", "a5", "a3", "query.optimize", 1.0),
    span("A", "a6", "a3", "query.plan", 0.8),
    span("A", "a9", "a7", "compile.probe", 0.1),
    span("A", "a8", "a7", "stage.dispatch", 1.5),
    span("A", "a7", "a3", "stage.run", 2.0),
    span("A", "a3", "a1", "storage.pin", 6.0),
    {"kind": "stage", "trace_id": "A", "span_id": "a7", "ms": 2.0},
    span("A", "a11", "a10", "device.wait", 1.0),
    span("A", "a12", "a10", "fetch.copy", 0.7),
    span("A", "a10", "a1", "query.fetch", 2.5),
    span("A", "a13", "a1", "query.rows", 0.4),
    span("A", "a1", None, "query.execute", 10.0),
    span("p2", "p2", None, "query.parse", 0.5),
    # execution B: no optimize; a span whose parent is not in the list;
    # two children side by side that take more than their parent has
    span("B", "b3", "b2", "stage.dispatch", 3.0),
    span("B", "b2", "b1", "stage.run", 4.0),
    span("B", "b8", "gone", "exchange.stats", 2.5),
    span("B", "b9", "b11", "pipeline.decode", 0.8),
    span("B", "b10", "b11", "pipeline.decode", 0.8),
    span("B", "b11", "b1", "storage.pin", 1.0),
    span("B", "b5", "b4", "device.wait", 10.0),
    span("B", "b6", "b4", "fetch.copy", 1.0),
    span("B", "b4", "b1", "query.fetch", 12.0),
    span("B", "b7", "b1", "query.rows", 1.0),
    span("B", "b1", None, "query.execute", 20.0),
    {"kind": "span", "name": "query.execute", "ms": 99.0, "span_id": "x"},
]


def test_self_times_of_one_trace():
    spans = [e for e in EVENTS if e.get("trace_id") == "A"
             and e["kind"] == "span"]
    own = span_times.self_ms(spans)
    assert own == pytest.approx({
        "a1": 0.6, "a2": 0.5, "a3": 2.0, "a4": 0.2, "a5": 1.0, "a6": 0.8,
        "a7": 0.4, "a8": 1.5, "a9": 0.1, "a10": 0.8, "a11": 1.0,
        "a12": 0.7, "a13": 0.4})
    assert sum(own.values()) == pytest.approx(10.0)   # they partition the root


def test_per_execution_and_the_orphan():
    traces = span_times.per_execution(EVENTS)
    assert len(traces) == 4                  # p1, A, p2, B; the id-less is out
    a, b = traces[1], traces[3]
    assert a["root"] == 10.0 and b["root"] == 20.0
    assert a["glue"] == pytest.approx(0.6 + 2.0 + 0.2)
    # B: the root's 2.0, storage.pin stops at 0, its two children's 1.6;
    # the orphan takes from nobody and is not under the root
    assert b["storage.pin"] == 0.0
    assert b["exchange.stats"] == 2.5
    assert b["glue"] == pytest.approx(2.0 + 0.0 + 1.6)
    named = sum(v for k, v in a.items() if k in span_times.NAMED)
    assert named + a["glue"] == pytest.approx(a["root"])


@pytest.mark.parametrize("metric,want", [
    ("parse_ms", 0.4), ("optimize_ms", 1.0), ("plan_ms", 0.8),
    ("dispatch_ms", (2.0 + 4.0) / 2), ("device_wait_ms", (1.0 + 10.0) / 2),
    ("fetch_ms", (0.7 + 1.0) / 2), ("rows_ms", (1.2 + 2.0) / 2),
    ("analysis_ms", 0.5)])
def test_metric_medians(metric, want):
    assert span_times.metric({"slice_events": EVENTS}, metric) \
        == pytest.approx(want)


def test_glue_and_nothing_to_read():
    assert span_times.median_ms(EVENTS, ("glue",)) == pytest.approx(3.2)
    flat = [e for e in EVENTS if e["kind"] != "span"]
    assert span_times.median_ms(flat, ("glue",)) is None
    assert span_times.metric({"slice_events": flat}, "parse_ms") is None
    assert span_times.metric({"slice_events": []}, "dispatch_ms") is None


@pytest.fixture(scope="module")
def planes():
    from jax.profiler import ProfileData

    with open(DATA) as f:
        return reduce_trace.planes_of(ProfileData.from_text_proto(f.read()))


def test_launch_is_dispatch_to_first_device_operation(planes):
    assert span_times.launch_ms(planes) == pytest.approx(1150e-6)
    no_spans = [(p, [(ln, [e for e in ev if not e[0].startswith("spark.")])
                     for ln, ev in lines]) for p, lines in planes]
    assert span_times.launch_ms(no_spans) is None      # the parent commit
    no_device = [(p, lines) for p, lines in planes if "TPU" not in p]
    assert span_times.launch_ms(no_device) is None


def test_idle_by_innermost_annotation(planes):
    idle = span_times.idle_by_span(planes)
    assert idle["window_s"] == pytest.approx(20000e-9)
    assert idle["idle_s"] == pytest.approx(13700e-9)
    assert dict(idle["idle_by_span"]) == pytest.approx({
        "bench.slice": 2000e-9, "bench.sql": 1200e-9,
        "bench.collect": 400e-9, "spark.query.parse": 800e-9,
        "spark.query.execute": 1900e-9, "spark.query.plan": 1000e-9,
        "spark.stage.run": 400e-9, "spark.stage.dispatch": 1300e-9,
        "spark.query.fetch": 1700e-9, "spark.device.wait": 900e-9,
        "spark.fetch.copy": 1300e-9, "spark.query.rows": 800e-9})
    # the accepted reducer gives the same idle time to bench.* alone
    old = dict(reduce_trace.reduce(planes, chips=2)["idle_gaps"])
    assert old["bench.collect"] == pytest.approx(9700e-9)
    assert idle["idle_in_collect_s"] == pytest.approx(9700e-9)
    assert idle["named_share"] == pytest.approx(9300 / 9700)


def test_longest_gap_names_its_spans(planes):
    idle = span_times.idle_by_span(planes)
    assert idle["longest_gap_s"] == pytest.approx(5100e-9)   # [7400,12500)
    assert dict(idle["longest_gap_by_span"]) == pytest.approx({
        "spark.fetch.copy": 1100e-9, "spark.query.fetch": 500e-9,
        "spark.query.rows": 800e-9, "spark.query.execute": 500e-9,
        "bench.collect": 200e-9, "bench.slice": 1000e-9,
        "bench.sql": 1000e-9})
    assert idle["longest_gap_in"] == [
        "bench.collect", "spark.query.execute", "spark.query.fetch",
        "spark.fetch.copy"]


def test_annotation_self_times_and_the_report(planes):
    rows = {n: (c, ms) for n, c, ms in span_times.annotation_self_ms(planes)}
    assert rows["spark.query.execute"] == (2, pytest.approx(2000e-6))
    assert rows["spark.query.fetch"] == (2, pytest.approx(2000e-6))
    assert rows["spark.device.wait"] == (2, pytest.approx(6300e-6))
    assert rows["spark.pipeline.decode"] == (1, pytest.approx(1000e-6))
    text = span_times.report(planes)
    assert "2 x bench.collect" in text and "launch_ms: 0.0011" in text
    assert "95.88 % of it under a spark.* annotation" in text
    assert "most of it in bench.collect > spark.query.execute > " \
           "spark.query.fetch > spark.fetch.copy" in text


def test_innermost_cuts_at_every_boundary():
    notes = [("a", 0, 10), ("b", 2, 3), ("c", 3, 1), ("d", 20, 5)]
    assert span_times.innermost(notes, 1, 22) == [
        (1, 2, ("a",)), (2, 3, ("a", "b")), (3, 4, ("a", "b", "c")),
        (4, 5, ("a", "b")), (5, 10, ("a",)), (10, 20, ()), (20, 22, ("d",))]


# ---- the ring is read 256 events at a time ----------------------------------

LOST = ("def read(ctx):\n"
        "    ns = [e['n'] for e in ctx['slice_events']]\n"
        "    return (ns[-1] - ns[0] + 1) - len(ns)\n")
PER_EXECUTION = ("def read(ctx):\n"
                 "    return len(ctx['slice_events']) / "
                 "len(ctx['executions'])\n")


@pytest.mark.parametrize("workload,devices", [("tpch_sf1_q6", 1),
                                              ("tpch_sf1_q1", 1),
                                              ("tpch_sf1_mesh4_q1", 4)])
def test_an_execution_fits_the_harness_read(tmp_path, workload, devices):
    """The harness reads ``events.new(last=256)`` after every execution of
    the slice: with one span a phase an execution stays far below that, and
    no event of the slice is lost (their numbers are consecutive)."""
    tmp = str(tmp_path)
    root = build_root(tmp)
    for name, text in (("events_lost", LOST),
                       ("events_per_execution", PER_EXECUTION)):
        with open(os.path.join(root, "benchmark", "layer_metrics",
                               name + ".py"), "w") as f:
            f.write(text)

    def add(spec):
        for name in ("events_lost", "events_per_execution"):
            spec["per_layer"].append({
                "name": name, "unit": "count", "better": "lower",
                "source": "program_counter", "layer": "entry / SQL",
                "moves": "query_ms"})

    edit(os.path.join(root, "BENCHMARK.json"), add)
    result = last_line(rehearse(root, tmp, workload, 1, devices=devices))
    metrics = result["metrics"]
    assert metrics["events_lost"]["value"] == 0
    assert 10 <= metrics["events_per_execution"]["value"] <= 64
    for name in ("parse_ms", "optimize_ms", "plan_ms", "dispatch_ms",
                 "device_wait_ms", "fetch_ms", "rows_ms", "glue_ms",
                 "launch_ms", "scan_transfer_s"):
        assert metrics[name]["value"] >= 0, name
    # the spans of an execution add up to what the benchmark's clock saw
    parts = sum(metrics[n]["value"] for n in (
        "optimize_ms", "plan_ms", "dispatch_ms", "device_wait_ms",
        "fetch_ms", "rows_ms", "glue_ms", "analysis_ms"))
    assert parts > 0
