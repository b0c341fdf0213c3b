"""Deterministic fault injection + the HBM-pressure degradation ladder
(spark_tpu/faults.py; reference chaos peers: FailureSuite.scala,
DAGSchedulerSuite's MockBackend killing executors mid-stage, and
TungstenAggregationIterator's sort-fallback under memory pressure).

The fault-matrix contract: with each injection point firing once
(``nth:1``), every golden query either returns results identical to the
no-fault run (recovered/degraded paths) or raises a typed, single-cause
error — no hangs, no silent wrong answers.
"""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_tpu import faults, metrics, recovery, tracing
from spark_tpu.conf import RuntimeConf

_TEST_CONF_KEYS = tuple(
    f"spark.tpu.faultInjection.{p}" for p in faults.POINTS) + (
    "spark.tpu.faultInjection.hangSeconds",
    "spark.tpu.maxDeviceBatchBytes",
    "spark.tpu.chunkRows",
    "spark.tpu.chunkRetryAttempts",
    "spark.tpu.oomDegrade.floorBytes",
    "spark.tpu.pipelineDepth",
    "spark.stage.maxConsecutiveAttempts",
)


@pytest.fixture()
def fconf(spark):
    """The session conf with guaranteed cleanup: every fault-injection
    arm and tier knob is unset and the arming counters dropped, so a
    failing test cannot leak faults into the rest of the suite."""
    conf = spark.conf
    faults.reset(conf)
    yield conf
    for key in _TEST_CONF_KEYS:
        try:
            conf.unset(key)
        except KeyError:
            pass
    faults.reset(conf)


@pytest.fixture(scope="module")
def fact_parquet(spark, tmp_path_factory):
    """Integer-valued fact table: SUM/COUNT are exact in every tier, so
    chunked-vs-resident results compare with == (the cross-tier oracle
    the degradation tests need)."""
    rng = np.random.default_rng(7)
    n = 200_000
    tbl = pa.table({
        "k": pa.array(rng.integers(0, 50, n), pa.int64()),
        "v": pa.array(rng.integers(0, 1000, n), pa.int64()),
    })
    path = str(tmp_path_factory.mktemp("faults") / "fact.parquet")
    pq.write_table(tbl, path, row_group_size=20_000)
    return path


_GOLDEN = "SELECT k, SUM(v) AS s, COUNT(*) AS c FROM {t} GROUP BY k ORDER BY k"


def _golden(spark, path, view="fact_tbl"):
    spark.read.parquet(path).createOrReplaceTempView(view)
    query = _GOLDEN.format(t=view)
    return lambda: [r.asDict() for r in spark.sql(query).collect()]


def _kinds(n=4096):
    return [e["kind"] for e in metrics.recent(n)]


def _set_chunked(conf):
    conf.set("spark.tpu.maxDeviceBatchBytes", 1 << 19)
    conf.set("spark.tpu.chunkRows", 50_000)
    conf.set("spark.tpu.oomDegrade.floorBytes", 1 << 16)


# ---- spec grammar / arming mechanics ----------------------------------------


def test_parse_spec_validation():
    assert faults.parse_spec("none") is None
    assert faults.parse_spec("") is None
    s = faults.parse_spec("nth:3")
    assert s.mode == "nth" and s.k == 3 and s.kind == "transient"
    s = faults.parse_spec("nth:1:oom")
    assert s.kind == "oom"
    s = faults.parse_spec("prob:0.25:99:corrupt")
    assert s.mode == "prob" and s.p == 0.25 and s.seed == 99 \
        and s.kind == "corrupt"
    for bad in ("nth", "nth:x", "nth:1:bogus", "prob:0.5", "prob:p:1",
                "wat:1", "nth:1:2:3"):
        with pytest.raises(ValueError):
            faults.parse_spec(bad)


def test_nth_fires_exactly_once():
    conf = RuntimeConf({})
    conf.set("spark.tpu.faultInjection.execute.device", "nth:2")
    faults.inject("execute.device", conf)  # arrival 1: no fire
    with pytest.raises(faults.InjectedTransientError) as ei:
        faults.inject("execute.device", conf)  # arrival 2: fires
    assert "UNAVAILABLE" in str(ei.value)
    assert ei.value.point == "execute.device"
    for _ in range(5):  # never re-fires
        faults.inject("execute.device", conf)
    assert faults.fire_count(conf, "execute.device") == 1
    # changing the spec re-arms the point
    conf.set("spark.tpu.faultInjection.execute.device", "nth:1:corrupt")
    with pytest.raises(faults.InjectedCorruptionError):
        faults.inject("execute.device", conf)


def test_prob_spec_is_deterministic():
    def fires(conf):
        out = []
        for _ in range(20):
            try:
                faults.inject("execute.device", conf)
                out.append(False)
            except faults.InjectedFault:
                out.append(True)
        return out

    a, b = RuntimeConf({}), RuntimeConf({})
    for c in (a, b):
        c.set("spark.tpu.faultInjection.execute.device", "prob:0.5:1234")
    assert fires(a) == fires(b)  # same seed, same stream
    c = RuntimeConf({})
    c.set("spark.tpu.faultInjection.execute.device", "prob:0.0:1")
    assert fires(c) == [False] * 20
    c = RuntimeConf({})
    c.set("spark.tpu.faultInjection.execute.device", "prob:1.0:1")
    assert fires(c) == [True] * 20


def test_unknown_point_rejected():
    conf = RuntimeConf({})
    with pytest.raises(ValueError, match="unknown fault-injection point"):
        faults.inject("no.such.seam", conf)


def test_disarmed_inject_is_noop(fconf):
    faults.inject("execute.device", fconf)  # default spec: none
    assert faults.fire_count(fconf, "execute.device") == 0


# ---- fault matrix: pipeline seams (chunked tier) ----------------------------


@pytest.mark.parametrize("point", ["pipeline.decode", "pipeline.transfer"])
@pytest.mark.parametrize("kind", ["transient", "hang", "oom", "corrupt"])
def test_fault_matrix_pipeline(spark, fconf, fact_parquet, point, kind):
    run = _golden(spark, fact_parquet)
    _set_chunked(fconf)
    oracle = run()  # no-fault oracle under the same chunked conf
    metrics.reset()
    fconf.set("spark.tpu.faultInjection.hangSeconds", 0.02)
    fconf.set(f"spark.tpu.faultInjection.{point}", f"nth:2:{kind}")
    faults.reset(fconf)
    if kind == "corrupt":
        # unrecoverable by design: surfaces unretried as the typed error
        with pytest.raises(faults.InjectedCorruptionError, match="DATA_LOSS"):
            run()
        return
    got = run()
    assert got == oracle
    kinds = _kinds()
    assert "fault_injected" in kinds
    if kind in ("transient", "hang"):
        # absorbed by the per-chunk retry inside the pipeline producer
        assert "chunk_retry" in kinds and "fault_recovered" in kinds
    else:  # oom: replanned through the ladder at a halved budget
        assert "degraded_to_chunked" in kinds


# ---- fault matrix: whole-batch device execution -----------------------------


@pytest.mark.parametrize("kind", ["transient", "hang", "oom", "corrupt"])
def test_fault_matrix_execute_device(spark, fconf, fact_parquet, kind):
    run = _golden(spark, fact_parquet)
    oracle = run()  # resident no-fault oracle
    metrics.reset()
    fconf.set("spark.tpu.faultInjection.hangSeconds", 0.02)
    fconf.set("spark.tpu.faultInjection.execute.device", f"nth:1:{kind}")
    fconf.set("spark.tpu.chunkRows", 50_000)  # ladder's chunk size
    faults.reset(fconf)
    if kind == "corrupt":
        with pytest.raises(faults.InjectedCorruptionError, match="DATA_LOSS"):
            run()
        return
    got = run()
    assert got == oracle
    kinds = _kinds()
    assert "fault_injected" in kinds and "fault_recovered" in kinds
    if kind in ("transient", "hang"):
        assert "stage_retry" in kinds  # blind retry is right for these
    else:
        # OOM must NOT blind-retry the identical plan — it degrades
        assert "degraded_to_chunked" in kinds
        assert "stage_retry" not in kinds


def test_oom_degradation_ladder_whole_batch_to_chunked(
        spark, fconf, fact_parquet):
    """The acceptance path spelled out: an injected whole-batch OOM
    demonstrably re-executes via the chunked tier (degraded_to_chunked
    metric at a halved budget) with oracle-identical output, and the
    session budget is untouched afterwards."""
    run = _golden(spark, fact_parquet)
    oracle = run()
    metrics.reset()
    fconf.set("spark.tpu.faultInjection.execute.device", "nth:1:oom")
    fconf.set("spark.tpu.chunkRows", 50_000)
    faults.reset(fconf)
    assert run() == oracle
    degr = [e for e in metrics.recent(4096)
            if e["kind"] == "degraded_to_chunked"]
    assert degr and "RESOURCE_EXHAUSTED" in degr[0]["error"]
    rec = [e for e in metrics.recent(4096)
           if e["kind"] == "fault_recovered"
           and e.get("how") == "degraded_to_chunked"]
    assert rec and rec[0]["budget"] == degr[-1]["budget"]
    from spark_tpu.physical.chunked import MAX_DEVICE_BATCH_BYTES

    # the halved budget lived on a shadow conf, not the session
    assert fconf.get(MAX_DEVICE_BATCH_BYTES) == MAX_DEVICE_BATCH_BYTES.default
    # next run (no fault armed beyond the spent nth:1): resident again
    assert run() == oracle


def test_oom_ladder_gives_up_at_floor(spark, fconf, fact_parquet):
    """An OOM that persists in the chunked tier at every halved budget
    surfaces as a clean RuntimeError naming the floor, with the
    ladder's last OOM chained — never an unbounded loop."""
    run = _golden(spark, fact_parquet)
    # whole-batch OOMs once, then every chunked attempt OOMs too
    fconf.set("spark.tpu.faultInjection.execute.device", "nth:1:oom")
    fconf.set("spark.tpu.faultInjection.pipeline.transfer",
              "prob:1.0:1:oom")
    fconf.set("spark.tpu.maxDeviceBatchBytes", 1 << 22)
    fconf.set("spark.tpu.chunkRows", 50_000)
    fconf.set("spark.tpu.oomDegrade.floorBytes", 1 << 20)
    faults.reset(fconf)
    with pytest.raises(RuntimeError, match="floor") as ei:
        run()
    assert recovery.is_oom(ei.value.__cause__)


def test_oom_unchunkable_plan_surfaces_original(spark, fconf):
    """A plan with no file-backed scan (in-memory relation) cannot be
    chunked at ANY budget: the ladder surfaces the original typed OOM
    instead of a misleading 'degraded to the floor' error."""
    spark.createDataFrame([{"k": i % 3, "v": i} for i in range(100)]) \
        .createOrReplaceTempView("mem_tbl")
    fconf.set("spark.tpu.faultInjection.execute.device", "nth:1:oom")
    faults.reset(fconf)
    with pytest.raises(faults.InjectedOOMError, match="RESOURCE_EXHAUSTED"):
        spark.sql("SELECT k, SUM(v) AS s FROM mem_tbl GROUP BY k").collect()


def test_oom_degrade_disabled_surfaces_oom(spark, fconf, fact_parquet):
    run = _golden(spark, fact_parquet)
    fconf.set("spark.tpu.oomDegrade.enabled", False)
    fconf.set("spark.tpu.faultInjection.execute.device", "nth:1:oom")
    faults.reset(fconf)
    try:
        with pytest.raises(faults.InjectedOOMError):
            run()
    finally:
        fconf.unset("spark.tpu.oomDegrade.enabled")


# ---- pipeline per-chunk retry across depths ---------------------------------


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_pipeline_chunk_retry_depth_sweep(spark, fconf, fact_parquet, depth):
    """A transient failure on one chunk's transfer costs one chunk
    retry — not the query — and the merged result stays byte-identical
    to the same-depth no-fault run."""
    run = _golden(spark, fact_parquet)
    _set_chunked(fconf)
    fconf.set("spark.tpu.pipelineDepth", depth)
    oracle = run()
    metrics.reset()
    fconf.set("spark.tpu.faultInjection.pipeline.transfer", "nth:2:transient")
    faults.reset(fconf)
    assert run() == oracle
    assert faults.fire_count(fconf, "pipeline.transfer") == 1
    kinds = _kinds()
    assert "chunk_retry" in kinds and "fault_recovered" in kinds
    # the whole query was NOT restarted for a one-chunk failure
    assert "stage_retry" not in kinds


def test_pipeline_retry_exhaustion_fails_cleanly(spark, fconf, fact_parquet):
    """Retries are bounded: a chunk that fails on every attempt relays
    the error instead of spinning (and the stage-retry wrapper's budget
    bounds the whole query)."""
    run = _golden(spark, fact_parquet)
    _set_chunked(fconf)
    fconf.set("spark.tpu.faultInjection.pipeline.transfer",
              "prob:1.0:7:transient")
    fconf.set("spark.tpu.chunkRetryAttempts", 2)
    fconf.set("spark.stage.maxConsecutiveAttempts", 2)
    faults.reset(fconf)
    with pytest.raises(RuntimeError, match="consecutive attempts"):
        run()


def test_chunk_pipeline_decode_failure_not_retried_mid_stream():
    """A REAL decode failure (the source iterator itself raised) is not
    retryable — a generator that raised is exhausted, and retrying
    next() would silently truncate the stream. Only injected decode
    faults (which fire before the source is touched) retry."""
    from spark_tpu.metrics import PipelineStats
    from spark_tpu.physical.pipeline import ChunkPipeline

    def source():
        yield 1
        raise ConnectionResetError("mid-stream")  # transient by type

    pipe = ChunkPipeline(source(), lambda x: x, depth=1,
                         byte_budget=1 << 20, stats=PipelineStats())
    with pytest.raises(ConnectionResetError):
        list(pipe)


def test_chunk_pipeline_prepare_retry_preserves_order():
    """Prepare-phase retries re-use the in-hand item: output order and
    content match the no-fault run exactly, at depth 0 and threaded."""
    from spark_tpu.metrics import PipelineStats
    from spark_tpu.physical.pipeline import ChunkPipeline

    conf = RuntimeConf({})
    conf.set("spark.tpu.faultInjection.pipeline.transfer", "nth:3")
    for depth in (0, 2):
        faults.reset(conf)
        conf.set("spark.tpu.faultInjection.pipeline.transfer", "nth:3")
        pipe = ChunkPipeline(iter(range(6)), lambda x: x * 10, depth=depth,
                             byte_budget=1 << 20, stats=PipelineStats(),
                             conf=conf)
        assert list(pipe) == [0, 10, 20, 30, 40, 50]
        assert faults.fire_count(conf, "pipeline.transfer") == 1


# ---- fault matrix: the all-to-all exchange ----------------------------------


def _sort_plan(colname, n=512):
    from spark_tpu.columnar.arrow import from_arrow
    from spark_tpu.expr import expressions as E
    from spark_tpu.plan import logical as L

    tbl = pa.table({colname: pa.array((np.arange(n) * 37) % 211)})
    return L.Sort((E.SortOrder(E.Col(colname), True),),
                  L.Relation(from_arrow(tbl)))


@pytest.fixture(scope="module")
def mesh_ex():
    from spark_tpu.parallel.executor import MeshExecutor
    from spark_tpu.parallel.mesh import make_mesh

    return MeshExecutor(make_mesh(8))


@pytest.mark.parametrize("kind", ["transient", "hang", "oom", "corrupt"])
def test_fault_matrix_exchange(spark, fconf, mesh_ex, kind):
    """The exchange seam fires at trace time. Each cell sorts a
    distinct column name so the mesh executor re-traces (a cached
    program never re-runs the Python-level collective builder) —
    transient kinds recover through the stage-retry wrapper (a failed
    trace is not cached), non-recoverable kinds surface typed."""
    colname = f"x_{kind}"
    fconf.set("spark.tpu.faultInjection.hangSeconds", 0.02)
    fconf.set("spark.tpu.faultInjection.exchange.all_to_all",
              f"nth:1:{kind}")
    faults.reset(fconf)
    if kind in ("transient", "hang"):
        got = recovery.run_stage_with_recovery(
            lambda: mesh_ex.execute_logical(_sort_plan(colname)),
            conf=fconf, label="exchange")
        vals = [r[colname] for r in got.to_pylist()]
        assert vals == sorted(vals) and len(vals) == 512
        assert faults.fire_count(fconf, "exchange.all_to_all") == 1
    elif kind == "oom":
        # no mesh-level ladder (the collective's capacity is the plan):
        # a clean typed error, never a silent wrong answer
        with pytest.raises(faults.InjectedOOMError):
            mesh_ex.execute_logical(_sort_plan(colname))
    else:
        with pytest.raises(faults.InjectedCorruptionError):
            mesh_ex.execute_logical(_sort_plan(colname))


# ---- fault matrix: streaming micro-batch commit -----------------------------


@pytest.mark.parametrize("kind", ["transient", "hang", "oom", "corrupt"])
def test_streaming_commit_crash_replays_from_wal(spark, fconf, tmp_path,
                                                 kind):
    """A crash at the commit seam — whatever killed it — loses nothing:
    the restarted query replays the WAL'd offsets and converges to the
    same state, and the replay is visible as a fault_recovered event."""
    from spark_tpu.api import functions as F
    from spark_tpu.streaming import MemoryStream

    expected_exc = {
        "transient": faults.InjectedTransientError,
        "hang": faults.InjectedDeadlineError,
        "oom": faults.InjectedOOMError,
        "corrupt": faults.InjectedCorruptionError,
    }[kind]
    ckpt = str(tmp_path / "fck")
    src = MemoryStream(pa.schema([("k", pa.string()), ("v", pa.int64())]))
    agg = spark.readStream.load(src).groupBy("k").agg(F.sum("v").alias("s"))
    q = agg.writeStream.outputMode("complete").queryName("fstr1") \
        .option("checkpointLocation", ckpt).start()
    src.add_data([{"k": "a", "v": 5}])
    q.process_all_available()

    fconf.set("spark.tpu.faultInjection.hangSeconds", 0.02)
    fconf.set("spark.tpu.faultInjection.streaming.commit", f"nth:1:{kind}")
    faults.reset(fconf)
    src.add_data([{"k": "a", "v": 7}, {"k": "b", "v": 1}])
    with pytest.raises(expected_exc):
        q.process_all_available()
    q.stop()
    fconf.unset("spark.tpu.faultInjection.streaming.commit")

    metrics.reset()
    q2 = agg.writeStream.outputMode("complete").queryName("fstr2") \
        .option("checkpointLocation", ckpt).start()
    q2.process_all_available()
    rows = {r.k: r.s for r in spark.sql("select * from fstr2").collect()}
    assert rows == {"a": 12, "b": 1}
    assert any(e["kind"] == "fault_recovered"
               and e.get("how") == "wal_replay" for e in metrics.recent(100))
    q2.stop()


def test_streaming_append_no_duplicate_after_commit_crash(
        spark, fconf, tmp_path):
    """Non-agg append output is only published AFTER the commit, so the
    crash + WAL replay emits the batch exactly once."""
    from spark_tpu.api import functions as F
    from spark_tpu.streaming import MemoryStream

    ckpt = str(tmp_path / "fck2")
    src = MemoryStream(pa.schema([("v", pa.int64())]))
    df = spark.readStream.load(src).select((F.col("v") * 10).alias("w"))
    q = df.writeStream.outputMode("append").queryName("fap1") \
        .option("checkpointLocation", ckpt).start()
    src.add_data([{"v": 1}])
    q.process_all_available()

    fconf.set("spark.tpu.faultInjection.streaming.commit", "nth:1:corrupt")
    faults.reset(fconf)
    src.add_data([{"v": 2}])
    with pytest.raises(faults.InjectedCorruptionError):
        q.process_all_available()
    q.stop()
    fconf.unset("spark.tpu.faultInjection.streaming.commit")

    q2 = df.writeStream.outputMode("append").queryName("fap2") \
        .option("checkpointLocation", ckpt).start()
    q2.process_all_available()
    vals = sorted(r.w for r in spark.sql("select * from fap2").collect())
    assert vals == [20]  # the replayed batch, exactly once — no [20, 20]
    q2.stop()


# ---- fault matrix: connect round-trip ---------------------------------------


@pytest.fixture()
def connect_srv(spark):
    from spark_tpu.connect.server import ConnectServer

    spark.createDataFrame([{"x": 1, "y": "a"}, {"x": 2, "y": "b"}]) \
        .createOrReplaceTempView("fconn_tv")
    srv = ConnectServer(spark).start()
    yield srv
    srv.stop()


@pytest.mark.parametrize("kind", ["transient", "oom", "corrupt"])
def test_fault_matrix_connect(spark, fconf, connect_srv, kind):
    from spark_tpu.connect.server import Client

    cli = Client(connect_srv.url, timeout=10.0)
    assert cli.sql("SELECT x FROM fconn_tv ORDER BY x") \
        .column("x").to_pylist() == [1, 2]
    fconf.set("spark.tpu.faultInjection.connect.request", f"nth:1:{kind}")
    faults.reset(fconf)
    marker = {"transient": "UNAVAILABLE", "oom": "RESOURCE_EXHAUSTED",
              "corrupt": "DATA_LOSS"}[kind]
    with pytest.raises(RuntimeError) as ei:
        cli.sql("SELECT x FROM fconn_tv")
    # typed marker AND the server-side traceback in the raised error
    assert marker in str(ei.value)
    assert "server traceback" in str(ei.value)
    fconf.unset("spark.tpu.faultInjection.connect.request")
    # the server survives: next request succeeds
    assert cli.sql("SELECT x FROM fconn_tv ORDER BY x") \
        .column("x").to_pylist() == [1, 2]


def test_connect_client_timeout_on_hung_server(spark, fconf, connect_srv):
    """An injected hang longer than the client deadline surfaces as a
    DEADLINE_EXCEEDED timeout instead of blocking forever (the
    satellite: urllib had no timeout at all)."""
    from spark_tpu.connect.server import Client

    fconf.set("spark.tpu.faultInjection.connect.request", "nth:1:hang")
    fconf.set("spark.tpu.faultInjection.hangSeconds", 3.0)
    faults.reset(fconf)
    cli = Client(connect_srv.url, timeout=0.3)
    with pytest.raises(RuntimeError, match="DEADLINE_EXCEEDED"):
        cli.sql("SELECT x FROM fconn_tv")


def test_connect_health_carries_heartbeat(spark):
    from spark_tpu.connect.server import Client, ConnectServer

    mon = recovery.HeartbeatMonitor(interval_s=30).start()
    srv = ConnectServer(spark, heartbeat=mon).start()
    try:
        h = Client(srv.url, timeout=10.0).health()
        assert h["status"] == "ok"
        assert h["heartbeat"]["last_ok"] is not None
        assert h["heartbeat"]["interval_s"] == 30
    finally:
        srv.stop()
        mon.stop()


def test_connect_health_without_heartbeat(spark, connect_srv):
    from spark_tpu.connect.server import Client

    h = Client(connect_srv.url, timeout=10.0).health()
    assert h["status"] == "ok" and h["heartbeat"] is None


# ---- observability ----------------------------------------------------------


def test_fault_profile_rollup(spark, fconf, fact_parquet):
    run = _golden(spark, fact_parquet)
    run()
    metrics.reset()
    fconf.set("spark.tpu.faultInjection.execute.device", "nth:1:transient")
    faults.reset(fconf)
    run()
    prof = tracing.fault_profile()
    assert prof["fault_injected"]["count"] == 1
    assert prof["fault_injected"]["points"] == {"execute.device": 1}
    assert prof["stage_retry"]["count"] == 1
    assert prof["fault_recovered"]["count"] == 1
    text = tracing.format_fault_profile(prof)
    assert "fault_injected: 1" in text and "execute.device=1" in text


def test_fault_events_reach_event_log(spark, fconf, fact_parquet, tmp_path):
    """Injected faults land in the JSONL event log, so post-mortem
    tooling (history) sees them without live metrics access."""
    import json
    import os

    run = _golden(spark, fact_parquet)
    log = str(tmp_path / "events")
    fconf.set("spark.eventLog.dir", log)
    try:
        fconf.set("spark.tpu.faultInjection.execute.device",
                  "nth:1:transient")
        faults.reset(fconf)
        run()
        files = [os.path.join(log, f) for f in os.listdir(log)]
        recorded = []
        for f in files:
            with open(f) as fh:
                recorded += [json.loads(line) for line in fh]
        kinds = {e.get("kind") for e in recorded}
        assert "fault_injected" in kinds and "fault_recovered" in kinds
    finally:
        fconf.unset("spark.eventLog.dir")
