"""Tracing/profiling glue (spark_tpu/tracing.py; SURVEY §5)."""

import os

from spark_tpu import metrics, tracing


def test_query_profile_rolls_up_stage_events(spark):
    metrics.reset()
    spark.range(1000).filter("id % 3 = 0").count()
    prof = tracing.query_profile()
    assert prof, "no stage events recorded by the engine"
    assert all({"count", "total_ms", "max_ms"} <= set(v)
               for v in prof.values())
    text = tracing.format_profile(prof)
    assert "operator" in text and "total_ms" in text


def _span_names_inside(events, outer):
    """Names of the host-plane events that lie inside an ``outer`` event
    on the same line (thread)."""
    found = set()
    for o in [e for e in events if e[0] == outer]:
        found |= {n for n, s, d in events
                  if n != outer and o[1] <= s and s + d <= o[1] + o[2]}
    return found


def _host_lines(trace_dir):
    """[(name, start ns, duration ns)] per line of the xplane's host plane."""
    from jax.profiler import ProfileData

    files = []
    for root, _, names in os.walk(trace_dir):
        files += [os.path.join(root, n) for n in names
                  if n.endswith(".xplane.pb")]
    assert files, "jax profiler produced no xplane file"
    data = ProfileData.from_file(sorted(files)[-1])
    return [[(e.name, e.start_ns, e.duration_ns) for e in line.events]
            for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines]


def test_planning_phases_are_spans(spark):
    """parse / optimize / plan are phases of the one span stream (they
    replace tracing.PlanningTracker): ``spark.sql`` records a
    ``query.parse`` trace of its own, the execution holds
    ``query.optimize`` and ``query.plan`` under its root, each with a
    duration, in that order."""
    spark.range(1000).createOrReplaceTempView("phases_t")
    metrics.reset()
    df = spark.sql("SELECT id % 3 AS k, COUNT(*) AS n FROM phases_t "
                   "GROUP BY id % 3")
    parse = [e for e in metrics.recent(50) if e.get("kind") == "span"]
    assert [e["name"] for e in parse] == ["query.parse"]
    assert parse[0]["parent_id"] is None and parse[0]["ms"] > 0
    assert len(df.collect()) == 3
    spans = {e["name"]: e for e in metrics.last_query()
             if e.get("kind") == "span"}
    assert {"query.execute", "query.optimize", "query.plan"} <= set(spans)
    root = spans["query.execute"]
    assert root["trace_id"] != parse[0]["trace_id"]
    for name in ("query.optimize", "query.plan"):
        assert spans[name]["trace_id"] == root["trace_id"]
        assert 0 < spans[name]["ms"] <= root["ms"]
    assert spans["query.optimize"]["t0"] <= spans["query.plan"]["t0"]
    assert not hasattr(tracing, "PlanningTracker")


def test_spans_are_annotations_on_the_profilers_clock(tmp_path, spark):
    """Under ``tracing.trace(dir)`` every sampled span is a ``spark.<name>``
    TraceAnnotation: the xplane's host plane holds ``spark.query.execute``
    with ``spark.stage.dispatch`` nested in it, both inside the caller's
    own annotation (spans annotate themselves; tracing.annotate is gone)."""
    import jax

    spark.range(100).count()       # compile outside the trace
    d = str(tmp_path / "trace")
    with tracing.trace(d):
        with jax.profiler.TraceAnnotation("caller.q1"):
            assert spark.range(100).count() == 100
    lines = [ln for ln in _host_lines(d)
             if any(n == "caller.q1" for n, _s, _d in ln)]
    assert len(lines) == 1, "the caller's annotation is on one thread"
    inside_caller = _span_names_inside(lines[0], "caller.q1")
    assert {"spark.query.execute", "spark.stage.dispatch",
            "spark.query.fetch", "spark.device.wait"} <= inside_caller
    inside_root = _span_names_inside(lines[0], "spark.query.execute")
    assert {"spark.stage.run", "spark.stage.dispatch", "spark.query.plan",
            "spark.fetch.copy", "spark.query.rows"} <= inside_root
    assert "spark.stage.dispatch" in _span_names_inside(
        lines[0], "spark.stage.run")
    assert not hasattr(tracing, "annotate")


def test_tracing_off_opens_no_annotation(tmp_path, spark):
    spark.range(100).count()
    spark.conf.set("spark.tpu.trace.enabled", False)
    try:
        d = str(tmp_path / "trace_off")
        with tracing.trace(d):
            assert spark.range(100).count() == 100
    finally:
        spark.conf.unset("spark.tpu.trace.enabled")
    names = {n for ln in _host_lines(d) for n, _s, _d in ln}
    assert not {n for n in names if n.startswith("spark.")}


def test_pipeline_profile_rolls_up_chunk_events():
    evs = [
        {"kind": "chunked_agg", "chunks": 4, "decode_ms": 10.0,
         "transfer_ms": 5.0, "compute_ms": 8.0, "wall_ms": 20.0,
         "overlap_ms": 5.0, "pipeline_depth": 2},
        {"kind": "chunked_agg", "chunks": 2, "decode_ms": 4.0,
         "transfer_ms": 1.0, "compute_ms": 2.0, "wall_ms": 10.0,
         "overlap_ms": 1.0, "pipeline_depth": 2},
        {"kind": "stage", "op": "HashAggregate", "ms": 3.0},
    ]
    prof = tracing.pipeline_profile(evs)
    assert set(prof) == {"chunked_agg"}
    rec = prof["chunked_agg"]
    assert rec["chunks"] == 6
    assert rec["decode_ms"] == 14.0
    assert rec["overlap_ms"] == 6.0
    assert rec["overlap_ratio"] == 0.2  # 6 / 30
    text = tracing.format_pipeline_profile(prof)
    assert "chunked_agg" in text and "overlap" in text

    assert tracing.pipeline_profile([]) == {}
    assert "no out-of-HBM" in tracing.format_pipeline_profile({})
