"""End-to-end query tracing (spark_tpu/trace/): hierarchical spans,
cross-replica context propagation, Perfetto export, and the overhead
guard.

Covers the PR-11 acceptance scenarios: a q3-shaped plan produces a
well-formed span tree (single root, no orphans); one trace through a
2-replica fleet — including the 429-shed re-dispatch path — shares one
trace_id end to end and renders as valid Chrome trace-event JSON;
results are byte-identical with tracing on/off/sampled; sampling is
honored; and always-on tracing stays under the 3% overhead budget.
"""

import json
import statistics
import time

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_tpu import conf as CF
from spark_tpu import history, metrics, trace, tracing
from spark_tpu.conf import RuntimeConf
from spark_tpu.connect.server import Client, ConnectServer
from spark_tpu.scheduler import QueryScheduler
from spark_tpu.serve import FederationRouter, serve_fleet

pytestmark = [pytest.mark.trace, pytest.mark.timeout(120)]


@pytest.fixture
def trace_conf(spark):
    """Trace-conf sandbox: spark.tpu.trace.* overrides set inside the
    test are unset afterwards (tracing reverts to always-on)."""
    yield spark.conf
    for k in list(spark.conf._overrides):
        if k.startswith("spark.tpu.trace"):
            spark.conf.unset(k)


def _write_parquet(path, nrows=64, nkeys=4):
    t = pa.table({
        "k": [i % nkeys for i in range(nrows)],
        "v": [float(i) * 0.5 for i in range(nrows)]})
    pq.write_table(t, str(path))
    return str(path)


def _ipc_bytes(table: pa.Table) -> bytes:
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, table.schema) as w:
        w.write_table(table)
    return sink.getvalue().to_pybytes()


def _spans(evs):
    return [e for e in evs if e.get("kind") == "span"]


def _roots(spans):
    ids = {e.get("span_id") for e in spans}
    return [e for e in spans if e.get("parent_id") is None
            or e.get("parent_id") not in ids]


# ---- registration / satellites ---------------------------------------------


def test_trace_conf_keys_registered():
    for key in ("spark.tpu.trace.enabled",
                "spark.tpu.trace.sampleRatio"):
        assert CF.is_registered(key), key


def test_trace_marker_gets_deadlock_guard(request):
    assert request.node.get_closest_marker("timeout") is not None


def test_span_names_registry():
    assert trace.SPAN_NAMES
    for name in ("router.dispatch", "connect.request", "scheduler.run",
                 "query.execute", "stage.run", "tier.decide",
                 "pipeline.decode", "pipeline.transfer", "fault.retry",
                 "query.parse", "query.optimize", "query.plan",
                 "stage.dispatch", "query.fetch", "device.wait",
                 "fetch.copy", "query.rows", "admission.note"):
        assert name in trace.SPAN_NAMES, name
    # the host's wait on the device has ONE name
    assert "stage.device" not in trace.SPAN_NAMES


def test_span_ids_unique_and_wire_safe():
    """Span ids come from a process-wide counter behind a per-process
    prefix: distinct, and made of the characters the header allows."""
    with trace.span("query.execute") as root:
        ids = []
        for _ in range(100):
            with trace.span("stage.run") as ctx:
                ids.append(ctx.span_id)
                assert ctx.parent_id == root.span_id
                assert ctx.trace_id == root.trace_id
        got = trace.from_header(trace.header_value())
    assert len(set(ids)) == 100 and root.span_id not in ids
    assert got is not None and got.span_id == root.span_id
    assert trace.current() is None


def test_span_ids_and_ring_numbers_under_threads():
    """More threads than cores open spans at once: every span id is
    distinct, every event keeps its own parent, and the ring's numbers
    rise by one (the harness reads the ring by number)."""
    import sys
    import threading

    metrics.reset()
    threads_n, spans_n = 16, 150
    seen, errors = [], []

    def work(k):
        try:
            with trace.span("query.execute", worker=k) as root:
                for _ in range(spans_n):
                    with trace.span("stage.run") as ctx:
                        assert ctx.parent_id == root.span_id
                        seen.append(ctx.span_id)
        except Exception as e:  # surfaced below, on the test's thread
            errors.append(repr(e))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(threads_n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads)
    assert len(seen) == len(set(seen)) == threads_n * spans_n
    evs = metrics.recent(4096)
    spans = _spans(evs)
    assert len(spans) == threads_n * (spans_n + 1)
    assert len({e["span_id"] for e in spans}) == len(spans)
    roots = {e["span_id"]: e for e in spans if e["name"] == "query.execute"}
    assert len(roots) == threads_n
    for e in spans:
        if e["name"] == "stage.run":
            assert roots[e["parent_id"]]["trace_id"] == e["trace_id"]
    ns = [e["n"] for e in evs]
    assert ns == list(range(ns[0], ns[0] + len(ns)))


def test_span_records_error_and_resets_context():
    metrics.reset()
    with pytest.raises(ValueError):
        with trace.span("query.execute"):
            with trace.span("stage.run", op="x"):
                raise ValueError("boom")
    assert trace.current() is None
    spans = {e["name"]: e for e in _spans(metrics.recent(10))}
    assert "boom" in spans["stage.run"]["error"]
    assert "boom" in spans["query.execute"]["error"]
    assert spans["stage.run"]["op"] == "x"


def test_header_roundtrip_and_malformed_dropped():
    ctx = trace.SpanContext("ab12" * 4, "cd34" * 2, None, True)
    got = trace.from_header(ctx.header())
    assert got is not None
    assert got.trace_id == ctx.trace_id
    assert got.span_id == ctx.span_id
    assert got.sampled is True
    # a remote parent arrives with no local parent_id
    assert got.parent_id is None
    for bad in (None, "", "zz", "a-b", "a-b-c-d", "xyz!-12-1",
                "--1", "ab12-"):
        assert trace.from_header(bad) is None, bad


# ---- span-tree well-formedness ---------------------------------------------


def test_span_tree_well_formed_multi_stage_plan(spark, tmp_path):
    """A q3-shaped plan (join + aggregate + sort: several stages, an
    exchange) produces ONE trace whose span tree has exactly one root,
    no orphaned parent_ids, and per-stage spans."""
    _write_parquet(tmp_path / "tr_a.parquet", 96, 6)
    _write_parquet(tmp_path / "tr_b.parquet", 48, 6)
    spark.read.parquet(str(tmp_path / "tr_a.parquet")) \
        .createOrReplaceTempView("tr_a")
    spark.read.parquet(str(tmp_path / "tr_b.parquet")) \
        .createOrReplaceTempView("tr_b")
    rows = spark.sql(
        "SELECT a.k, SUM(a.v + b.v) AS s FROM tr_a a "
        "JOIN tr_b b ON a.k = b.k GROUP BY a.k ORDER BY s").collect()
    assert rows
    evs = metrics.last_query()
    spans = _spans(evs)
    assert spans, "tracing is on by default — spans must be recorded"
    tids = {e.get("trace_id") for e in spans}
    assert len(tids) == 1
    roots = _roots(spans)
    assert len(roots) == 1, [r.get("name") for r in roots]
    # no orphans: every non-root parent_id is a recorded span
    ids = {e.get("span_id") for e in spans}
    for e in spans:
        if e is not roots[0]:
            assert e.get("parent_id") in ids, e
    names = {e.get("name") for e in spans}
    assert "query.execute" in names
    assert "stage.run" in names
    # flat events (stage, exchange) are stamped with the same trace id
    stages = [e for e in evs if e.get("kind") == "stage"]
    assert stages
    assert all(e.get("trace_id") == next(iter(tids)) for e in stages)


def _own_ms(spans, root):
    """span_id -> self time: a span's ``ms`` less its children's."""
    own = {e["span_id"]: e["ms"] for e in spans}
    for e in spans:
        if e is not root:
            own[e["parent_id"]] -= e["ms"]
    return own


def _inside(child, parent, slack_ms=0.5):
    """``child``'s interval lies inside ``parent``'s (t0 is on
    time.time(), ms on perf_counter: allow the two clocks a little)."""
    c0, p0 = child["t0"] * 1e3, parent["t0"] * 1e3
    return (c0 >= p0 - slack_ms
            and c0 + child["ms"] <= p0 + parent["ms"] + slack_ms)


def test_collect_is_one_trace_with_every_phase(spark, tmp_path):
    """One ``spark.sql(...).collect()``: one trace whose root
    ``query.execute`` covers the query, fetch included, with a span for
    every phase, children inside their parents and no orphan."""
    _write_parquet(tmp_path / "tr_ph.parquet", 96, 6)
    spark.read.parquet(str(tmp_path / "tr_ph.parquet")) \
        .createOrReplaceTempView("tr_ph")
    q = "SELECT SUM(v) AS s FROM tr_ph WHERE k = 1"
    for _ in range(3):
        spark.sql(q).collect()          # reach the fused steady state
    df = spark.sql(q)
    c0 = time.perf_counter()
    rows = df.collect()
    wall_ms = (time.perf_counter() - c0) * 1e3
    assert len(rows) == 1
    spans = _spans(metrics.last_query())
    assert len({e["trace_id"] for e in spans}) == 1
    roots = _roots(spans)
    assert [r["name"] for r in roots] == ["query.execute"]
    root = roots[0]
    by_id = {e["span_id"]: e for e in spans}
    assert len(by_id) == len(spans)
    for e in spans:
        if e is not root:
            assert e["parent_id"] in by_id, e            # no orphan
            assert _inside(e, by_id[e["parent_id"]]), e

    def parent_name(name):
        found = [by_id[e["parent_id"]]["name"] for e in spans
                 if e["name"] == name]
        assert found, f"no {name} span"
        return found

    names = {e["name"] for e in spans}
    assert {"query.optimize", "query.plan", "stage.run", "stage.dispatch",
            "query.fetch", "device.wait", "fetch.copy",
            "query.rows"} <= names
    assert parent_name("stage.dispatch") == ["stage.run"]
    assert parent_name("device.wait") == ["query.fetch"]
    assert parent_name("fetch.copy") == ["query.fetch"]
    assert parent_name("query.fetch") == ["query.execute"]
    assert parent_name("query.rows") == ["query.execute"]
    # the plan is bound before the first stage runs, and the fetch
    # follows the last one
    at = {e["name"]: e["t0"] for e in spans}
    assert at["query.optimize"] <= at["query.plan"] <= at["stage.run"] \
        <= at["query.fetch"] <= at["query.rows"]
    # the root is the caller's collect() to within the call overhead
    assert root["ms"] <= wall_ms
    assert wall_ms - root["ms"] <= max(0.05 * wall_ms, 0.3), (
        wall_ms, root["ms"])
    # self times partition the root
    own = _own_ms(spans, root)
    assert sum(own.values()) == pytest.approx(root["ms"])
    assert all(v >= -0.05 for v in own.values()), own


def _steady_spans(spark, tmp_path, view, text, runs=3):
    """``text`` run ``runs`` times over a small parquet view ``view``;
    the span events of the last execution."""
    _write_parquet(tmp_path / f"{view}.parquet", 96, 6)
    spark.read.parquet(str(tmp_path / f"{view}.parquet")) \
        .createOrReplaceTempView(view)
    for _ in range(runs):
        rows = spark.sql(text).collect()
    assert rows
    return _spans(metrics.last_query())


def test_tier_decide_and_admission_note_where_glue_is_spent(spark,
                                                            tmp_path):
    """A resident one-chip query: ONE tier.decide, closed before the
    plan is bound and the stage runs, and TWO admission.note (the
    optimized plan's, then the raw one's) after the stage, all under
    storage.pin."""
    spans = _steady_spans(
        spark, tmp_path, "tr_td",
        "SELECT k, SUM(v) AS s FROM tr_td GROUP BY k ORDER BY k")
    by_id = {e["span_id"]: e for e in spans}
    decide = [e for e in spans if e["name"] == "tier.decide"]
    notes = [e for e in spans if e["name"] == "admission.note"]
    assert [e["tier"] for e in decide] == ["resident"]
    assert [e["key"] for e in notes] == ["optimized", "raw"]
    for e in decide + notes:
        assert by_id[e["parent_id"]]["name"] == "storage.pin"
    for e in notes:
        assert e["events"] > 0 and e["bytes"] >= 0
    at = {e["name"]: e for e in spans if e["name"] != "admission.note"}

    def end(e):
        return e["t0"] + e["ms"] / 1e3

    assert at["query.optimize"]["t0"] <= decide[0]["t0"]
    assert end(decide[0]) <= at["query.plan"]["t0"] + 5e-5
    assert end(at["stage.run"]) <= notes[0]["t0"] + 5e-5
    assert end(notes[1]) <= at["query.fetch"]["t0"] + 5e-5


def test_tier_decide_says_chunked_under_a_small_budget(spark, tmp_path):
    spark.conf.set("spark.tpu.maxDeviceBatchBytes", 1024)
    try:
        spans = _steady_spans(
            spark, tmp_path, "tr_tc",
            "SELECT k, COUNT(v) AS n FROM tr_tc GROUP BY k", runs=1)
    finally:
        spark.conf.unset("spark.tpu.maxDeviceBatchBytes")
    decide = [e for e in spans if e["name"] == "tier.decide"]
    assert [e["tier"] for e in decide] == ["chunked"]
    # only a resident run is noted under the optimized plan
    assert [e["key"] for e in spans
            if e["name"] == "admission.note"] == ["raw"]


def test_self_times_partition_every_execution(spark, tmp_path):
    """With the new spans in the tree the self times of all names still
    add up to ``query.execute``, execution by execution."""
    _write_parquet(tmp_path / "tr_pt.parquet", 96, 6)
    spark.read.parquet(str(tmp_path / "tr_pt.parquet")) \
        .createOrReplaceTempView("tr_pt")
    for _ in range(6):
        spark.sql("SELECT k, SUM(v) AS s FROM tr_pt GROUP BY k").collect()
        spans = _spans(metrics.last_query())
        root, = _roots(spans)
        assert root["name"] == "query.execute"
        total = sum(max(0.0, v) for v in _own_ms(spans, root).values())
        assert total == pytest.approx(root["ms"], rel=0.02)
        assert {"tier.decide", "admission.note"} <= {e["name"]
                                                     for e in spans}


def test_slo_components_read_the_wait_on_one_chip(spark, tmp_path):
    """The SLO model's device component is the host's wait on the
    device under its one name, so a one-chip ticket has one."""
    import types

    from spark_tpu.slo.controller import SloController

    _write_parquet(tmp_path / "tr_slo.parquet", 64, 4)
    df = spark.read.parquet(str(tmp_path / "tr_slo.parquet"))
    with trace.span("scheduler.run") as ctx:
        assert df.groupBy("k").sum("v").collect()
    device_ms, transfer_ms, cold = SloController._span_components(
        types.SimpleNamespace(_trace_ctx=ctx))
    waits = [e["ms"] for e in _spans(metrics.query_events(ctx.trace_id))
             if e["name"] == "device.wait"]
    assert waits and device_ms == pytest.approx(sum(waits)) and device_ms > 0
    assert transfer_ms == 0.0


@pytest.mark.parametrize("action", ["collect", "toPandas", "toArrow",
                                    "count"])
def test_every_action_fetches_inside_the_root(spark, action):
    df = spark.range(50).filter("id % 2 = 0")
    getattr(df, action)()
    spans = _spans(metrics.last_query())
    roots = _roots(spans)
    assert [r["name"] for r in roots] == ["query.execute"], action
    names = {e["name"] for e in spans}
    assert {"query.fetch", "device.wait", "fetch.copy",
            "query.rows"} <= names, action


@pytest.fixture
def mesh2():
    """A ``mesh[2]`` session in place of the suite's; put back after."""
    from spark_tpu.api.session import SparkSession

    prev = SparkSession._active
    SparkSession._reset()
    try:
        yield (SparkSession.builder.master("mesh[2]")
               .appName("trace-mesh").getOrCreate())
    finally:
        SparkSession._reset()
        SparkSession._active = prev


def test_mesh_execution_has_the_same_phases(mesh2):
    """mesh[2]: the mesh engine's own optimize / plan spans, the enqueue
    as stage.dispatch under stage.run as on one chip, no forced sync of
    its own (the wait is device.wait, in the fetch), and the gather as
    fetch.copy."""
    df = mesh2.range(4000).groupBy().sum("id")
    assert df.collect()[0][0] == sum(range(4000))
    spans = _spans(metrics.last_query())
    by_id = {e["span_id"]: e for e in spans}
    roots = _roots(spans)
    assert [r["name"] for r in roots] == ["query.execute"]
    names = {e["name"] for e in spans}
    assert {"query.optimize", "tier.decide", "query.plan", "stage.run",
            "stage.dispatch", "admission.note", "device.wait",
            "fetch.copy", "query.fetch", "query.rows"} <= names
    assert "stage.device" not in names
    for e in spans:
        if e["name"] == "stage.dispatch":
            assert by_id[e["parent_id"]]["name"] == "stage.run"
    assert any(e["name"] == "fetch.copy" and e.get("op") == "gather"
               for e in spans)
    bd = tracing.trace_breakdown(spans)
    assert bd["device_ms"] == pytest.approx(
        sum(e["ms"] for e in spans if e["name"] == "device.wait"), abs=0.01)
    assert bd["device_ms"] > 0
    assert bd["device_ms"] + bd["fetch_ms"] + bd["host_ms"] == \
        pytest.approx(bd["wall_ms"], abs=0.01)


def test_mesh_schedule_does_not_depend_on_sampling(mesh2, monkeypatch):
    """A multi-stage mesh query forces the same number of syncs, and
    returns the same rows, whether its trace samples or not."""
    import jax

    from spark_tpu.api import functions as F

    a = mesh2.range(6000).withColumnRenamed("id", "k")
    b = mesh2.range(3000).withColumnRenamed("id", "k2")
    df = a.join(b, a["k"] == b["k2"]).groupBy().agg(
        F.sum("k").alias("s"), F.count("k").alias("n"))
    df.collect()                        # build the stages once
    syncs = []
    block = jax.block_until_ready

    def counted(x):
        syncs.append(1)
        return block(x)

    monkeypatch.setattr(jax, "block_until_ready", counted)
    seen = {}
    for ratio in (1.0, 0.0):            # the session goes with the test
        mesh2.conf.set("spark.tpu.trace.sampleRatio", ratio)
        del syncs[:]
        rows = df.collect()
        evs = metrics.last_query()
        seen[ratio] = (rows, len(syncs),
                       sum(e["kind"] == "stage" for e in evs))
        assert bool(_spans(evs)) == (ratio == 1.0)
    assert seen[1.0] == seen[0.0]
    rows, n_syncs, stages = seen[1.0]
    assert (rows[0][0], rows[0][1]) == (sum(range(3000)), 3000)
    assert stages >= 2 and n_syncs == 1       # the fetch's, and no other


def test_mesh_readback_between_stages_is_a_device_wait(mesh2):
    """A stage that holds an exchange reads its output's mask back for
    the exchange's record: the host waits for the stage there, under
    the one name that wait has."""
    df = mesh2.range(4000).selectExpr("id % 4 AS k", "id").groupBy("k") \
        .sum("id").orderBy("k")
    assert [r[1] for r in df.collect()] == [
        sum(range(k, 4000, 4)) for k in range(4)]
    spans = _spans(metrics.last_query())
    by_id = {e["span_id"]: e for e in spans}
    readbacks = [e for e in spans if e["name"] == "device.wait"
                 and e.get("op") == "readback"]
    assert readbacks
    assert {by_id[e["parent_id"]]["name"] for e in readbacks} == {"stage.run"}
    assert any(e["kind"] == "exchange" and e.get("mode") == "fused"
               for e in metrics.last_query())


def test_tracing_off_same_rows_no_span_event(spark, tmp_path, trace_conf):
    _write_parquet(tmp_path / "tr_off.parquet", 64, 4)
    spark.read.parquet(str(tmp_path / "tr_off.parquet")) \
        .createOrReplaceTempView("tr_off")
    q = "SELECT k, SUM(v) AS s FROM tr_off GROUP BY k ORDER BY k"
    want = spark.sql(q).collect()
    trace_conf.set("spark.tpu.trace.enabled", False)
    before = metrics.recent(1)[-1]["n"]
    got = spark.sql(q).collect()
    assert got == want
    new = [e for e in metrics.recent(4096) if e["n"] > before]
    assert new, "flat events are still recorded"
    assert _spans(new) == []
    # ids are stamped on flat events all the same
    assert all(e.get("trace_id") for e in new if e["kind"] == "stage")


def test_breakdown_components_sum_to_wall(spark, tmp_path):
    _write_parquet(tmp_path / "tr_bd.parquet", 64, 4)
    spark.read.parquet(str(tmp_path / "tr_bd.parquet")) \
        .createOrReplaceTempView("tr_bd")
    spark.sql("SELECT k, SUM(v) FROM tr_bd GROUP BY k").collect()
    bd = tracing.trace_breakdown()
    assert bd["wall_ms"] > 0
    # on the single-device session the device's share is the wait in
    # fetch_host (device.wait), and the copy is taken out of host_ms
    assert bd["device_ms"] > 0 and bd["fetch_ms"] > 0
    total = (bd["queue_ms"] + bd["device_ms"] + bd["transfer_ms"]
             + bd["fetch_ms"] + bd["host_ms"])
    # host_ms is the remainder by construction: the split sums to wall
    # well inside the 10% acceptance bound
    assert abs(total - bd["wall_ms"]) <= max(0.1 * bd["wall_ms"], 1.0)
    assert tracing.format_trace().startswith("trace ")


def test_chrome_trace_valid_json(spark, tmp_path):
    _write_parquet(tmp_path / "tr_ct.parquet", 64, 4)
    spark.read.parquet(str(tmp_path / "tr_ct.parquet")) \
        .createOrReplaceTempView("tr_ct")
    spark.sql("SELECT k, SUM(v) FROM tr_ct GROUP BY k").collect()
    evs = metrics.last_query()
    tid = next(e["trace_id"] for e in _spans(evs))
    doc = history.chrome_trace(metrics.query_events(tid))
    blob = json.dumps(doc)  # must serialize
    assert json.loads(blob)["displayTimeUnit"] == "ms"
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert xs
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    assert {e["name"] for e in xs} >= {"query.execute", "stage.run"}


# ---- fleet propagation ------------------------------------------------------


def test_fleet_propagation_two_replicas(spark, tmp_path):
    """One trace spans client -> router -> replica -> scheduler ->
    stages, and GET /trace/<id> through the router renders it."""
    _write_parquet(tmp_path / "tr_fl.parquet", 64, 4)
    spark.read.parquet(str(tmp_path / "tr_fl.parquet")) \
        .createOrReplaceTempView("tr_fl")
    fleet = serve_fleet(spark, replicas=2)
    try:
        c = Client(fleet.url, timeout=60)
        rows = c.sql("SELECT k, SUM(v) FROM tr_fl GROUP BY k")
        assert rows.num_rows
        assert c.last_trace_id
        spans = _spans(metrics.query_events(c.last_trace_id))
        names = {e.get("name") for e in spans}
        assert names >= {"connect.client", "router.dispatch",
                         "router.forward", "connect.request",
                         "scheduler.run", "query.execute", "stage.run"}
        roots = _roots(spans)
        assert len(roots) == 1
        assert roots[0]["name"] == "connect.client"
        # the Perfetto export fetched over HTTP covers the whole path
        doc = c.trace()
        xs = {e["name"] for e in doc["traceEvents"]
              if e.get("ph") == "X"}
        assert xs >= {"router.dispatch", "connect.request",
                      "scheduler.run", "stage.run"}
    finally:
        fleet.stop()


def test_shed_redispatch_shares_one_trace(spark, tmp_path):
    """A 429-shed re-dispatch stays in ONE trace: both forward
    attempts (the saturated replica and the one that served) appear as
    router.forward spans under the same trace_id."""
    import urllib.request

    _write_parquet(tmp_path / "tr_sh.parquet", 48, 4)
    spark.read.parquet(str(tmp_path / "tr_sh.parquet")) \
        .createOrReplaceTempView("tr_sh")
    full = ConnectServer(
        spark, port=0, replica_id="full",
        scheduler=QueryScheduler(conf=RuntimeConf(
            {"spark.tpu.scheduler.queueDepth": 0}))).start()
    ok = ConnectServer(spark, port=0, replica_id="ok").start()
    router = FederationRouter([full, ok], conf=spark.conf).start()
    try:
        req = urllib.request.Request(
            router.url + "/sql",
            data=json.dumps(
                {"query": "SELECT k FROM tr_sh WHERE k > 0"}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as resp:
            assert resp.status == 200
            tid = resp.headers.get("X-SparkTpu-Trace-Id")
        assert tid
        evs = metrics.query_events(tid)
        forwards = [e for e in _spans(evs)
                    if e.get("name") == "router.forward"]
        tried = {e.get("replica") for e in forwards}
        assert "ok" in tried
        if "full" in tried:  # round-robin picked the saturated one 1st
            assert len(forwards) >= 2
            sheds = [e for e in evs if e.get("kind") == "serve"
                     and e.get("phase") == "shed"]
            assert sheds and all(e.get("trace_id") == tid
                                 for e in sheds)
    finally:
        router.stop()
        full.stop()
        ok.stop()


# ---- byte identity / sampling / overhead ------------------------------------


def test_on_off_sweep_byte_identity(spark, tmp_path, trace_conf):
    """Tracing never touches data: every cell of the on/off/sampled
    sweep serializes the identical arrow stream."""
    _write_parquet(tmp_path / "tr_bi.parquet", 96, 6)
    spark.read.parquet(str(tmp_path / "tr_bi.parquet")) \
        .createOrReplaceTempView("tr_bi")

    def run():
        return _ipc_bytes(spark.sql(
            "SELECT k, SUM(v) AS s FROM tr_bi GROUP BY k ORDER BY k"
        ).toArrow())

    ref = run()
    for enabled, ratio in ((True, 1.0), (True, 0.5), (True, 0.0),
                           (False, 1.0)):
        trace_conf.set("spark.tpu.trace.enabled", enabled)
        trace_conf.set("spark.tpu.trace.sampleRatio", ratio)
        assert run() == ref, (enabled, ratio)


def test_sampling_honored(spark, tmp_path, trace_conf):
    _write_parquet(tmp_path / "tr_sa.parquet", 64, 4)
    spark.read.parquet(str(tmp_path / "tr_sa.parquet")) \
        .createOrReplaceTempView("tr_sa")

    def run_and_spans(q):
        spark.sql(q).collect()
        return _spans(metrics.last_query())

    trace_conf.set("spark.tpu.trace.sampleRatio", 0.0)
    assert run_and_spans(
        "SELECT k, SUM(v) FROM tr_sa GROUP BY k") == []
    trace_conf.set("spark.tpu.trace.sampleRatio", 1.0)
    assert run_and_spans(
        "SELECT k, SUM(v), COUNT(*) FROM tr_sa GROUP BY k")
    trace_conf.set("spark.tpu.trace.enabled", False)
    assert run_and_spans(
        "SELECT k, MAX(v) FROM tr_sa GROUP BY k") == []


def test_overhead_under_three_percent(spark, tmp_path, trace_conf):
    """Always-on tracing costs <3% on a warm q1-shaped query
    (median-of-alternating-runs; small absolute slack absorbs timer
    noise on runs this short)."""
    _write_parquet(tmp_path / "tr_oh.parquet", 256, 8)
    spark.read.parquet(str(tmp_path / "tr_oh.parquet")) \
        .createOrReplaceTempView("tr_oh")
    q = ("SELECT k, SUM(v) AS s, AVG(v) AS a, COUNT(*) AS n "
         "FROM tr_oh WHERE v >= 0 GROUP BY k ORDER BY k")
    spark.sql(q).collect()  # warm: compile once, outside the clock
    on, off = [], []
    for _ in range(5):
        for enabled, sink in ((True, on), (False, off)):
            trace_conf.set("spark.tpu.trace.enabled", enabled)
            t0 = time.perf_counter()
            spark.sql(q).collect()
            sink.append(time.perf_counter() - t0)
    med_on = statistics.median(on)
    med_off = statistics.median(off)
    assert med_on <= med_off * 1.03 + 0.010, (med_on, med_off)
