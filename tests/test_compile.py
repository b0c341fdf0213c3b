"""AOT compilation service (spark_tpu/compile/): cross-session
executable store, structural-key fingerprints, background compile +
hot-swap, plan-history pre-warm, size-bound eviction, and the
compile.background fault matrix.

The fused stage path (and hence all store traffic) only engages on a
plan's SECOND execution in a session — the first run executes blocking
to record the adaptive stats that prove the plan fully traceable — so
every store-facing test collects each query twice per session.

Known XLA:CPU limit: LARGE serialized executables can fail
deserialize_and_load in a fresh process ("Symbols not found"); the
store's contract is that any such entry is a miss AND evicted, never a
crash. These tests keep programs small (verified to round-trip) and
separately pin the corrupt→evict policy.
"""

import contextlib
import glob
import os
import re
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import jax

from spark_tpu import conf as CF
from spark_tpu import faults, metrics
from spark_tpu.compile import store as store_mod
from spark_tpu.compile.service import PlanHistory, _replayable_sql
from spark_tpu.compile.store import (ExecutableStore, clear_process_cache,
                                     stable_plan_fingerprint)

pytestmark = pytest.mark.compile

GOLDEN = "SELECT k, SUM(v) AS s, COUNT(*) AS c FROM {t} GROUP BY k ORDER BY k"


@pytest.fixture(scope="module")
def fact_parquet(tmp_path_factory):
    """Small integer fact table: SUM/COUNT are exact in every tier, so
    chunked-vs-fused results compare with == (byte identity), and the
    fused stage program stays small enough to AOT-round-trip on
    XLA:CPU."""
    rng = np.random.default_rng(7)
    n = 5000
    tbl = pa.table({
        "k": pa.array(rng.integers(0, 8, n), pa.int64()),
        "v": pa.array(rng.integers(0, 1000, n), pa.int64()),
    })
    path = str(tmp_path_factory.mktemp("compile") / "fact.parquet")
    pq.write_table(tbl, path, row_group_size=1000)
    return path


@contextlib.contextmanager
def _session(master=None, **conf):
    """A private session with the given compile conf, restoring
    whatever session was active before (compile tests must not leak
    stores/background flags into the shared suite session)."""
    from spark_tpu.api.session import SparkSession

    prev = SparkSession._active
    SparkSession._reset()
    b = SparkSession.builder.appName("compile-test")
    if master:
        b = b.master(master)
    for key, value in conf.items():
        b = b.config(key, value)
    s = b.getOrCreate()
    try:
        yield s
    finally:
        svc = s.__dict__.get("_compile_service")
        if svc is not None:
            svc.wait_background(timeout=60)
        SparkSession._reset()
        SparkSession._active = prev


def _forget_process_state():
    """Simulate a fresh process: drop both jit stage caches and the
    store's in-process loaded-executable registry, so the next
    execution must go back to disk."""
    from spark_tpu.parallel import executor as EX
    from spark_tpu.physical import planner as PL

    PL._STAGE_CACHE.clear()
    EX._DIST_STAGE_CACHE.clear()
    clear_process_cache()


def _rows(spark, query):
    return [r.asDict() for r in spark.sql(query).collect()]


def _run_twice(spark, path, view="compile_fact"):
    """First run records adaptive stats (blocking), second engages the
    fused stage path and hence the executable store."""
    spark.read.parquet(path).createOrReplaceTempView(view)
    q = GOLDEN.format(t=view)
    out = _rows(spark, q)
    assert _rows(spark, q) == out
    return out


# ---- cross-session executable cache ----------------------------------------


@pytest.mark.timeout(300)
def test_cross_session_cache_hit(fact_parquet, tmp_path):
    """A second session pointed at the same store dir serves its fused
    stage from disk — no trace, no compile — with byte-identical
    results."""
    store_dir = str(tmp_path / "store")
    _forget_process_state()
    metrics.reset_exec_store()
    with _session(**{"spark.tpu.compile.store.dir": store_dir}) as s1:
        rows1 = _run_twice(s1, fact_parquet)
        st1 = metrics.exec_store_stats()
        assert st1["misses"] >= 1 and st1["puts"] >= 1
        assert s1.compile_service.store.stats()["entries"] >= 1

    # fresh session, fresh "process": the only warm state is the disk
    _forget_process_state()
    metrics.reset_exec_store()
    with _session(**{"spark.tpu.compile.store.dir": store_dir}) as s2:
        rows2 = _run_twice(s2, fact_parquet)
        st2 = metrics.exec_store_stats()
        assert st2["hits"] >= 1, f"no store hit in fresh session: {st2}"
        assert st2["corrupt"] == 0
    assert rows2 == rows1


@pytest.mark.timeout(300)
def test_cross_session_cache_hit_mesh8(fact_parquet, tmp_path):
    """The same over mesh[8]: a stored distributed stage is loaded onto
    the mesh's eight devices (the executor hands them to the store)."""
    store_dir = str(tmp_path / "store")
    conf = {"spark.tpu.compile.store.dir": store_dir}
    _forget_process_state()
    metrics.reset_exec_store()
    with _session("mesh[8]", **conf) as s1:
        rows1 = _run_twice(s1, fact_parquet)
        assert metrics.exec_store_stats()["puts"] >= 1

    _forget_process_state()
    metrics.reset_exec_store()
    with _session("mesh[8]", **conf) as s2:
        rows2 = _run_twice(s2, fact_parquet)
        st2 = metrics.exec_store_stats()
        assert st2["hits"] >= 1, f"no store hit over mesh[8]: {st2}"
        assert st2["corrupt"] == 0
    assert rows2 == rows1


@pytest.mark.timeout(120)
@pytest.mark.parametrize("n_devices", [1, 8])
def test_load_runs_on_the_devices_it_is_given(tmp_path, n_devices):
    """jax's deserialize_and_load defaults to EVERY device of the
    backend, which turns a one-device program into an 8-shard one as
    soon as 8 devices are visible. The store loads a single-device
    executable onto the default device and a mesh stage onto the
    devices its caller names."""
    from jax.sharding import NamedSharding, PartitionSpec

    from spark_tpu.parallel.mesh import DATA_AXIS, make_mesh

    assert len(jax.devices()) == 8
    store = ExecutableStore(str(tmp_path / "store"), max_bytes=1 << 30)
    host = np.arange(64, dtype=np.int64)
    if n_devices == 1:
        fn, devices = (lambda a: a[0] * 2), None
        args = (jax.device_put(host, jax.devices()[0]),)
    else:
        mesh = make_mesh(n_devices)
        spec = PartitionSpec(DATA_AXIS)
        fn = jax.shard_map(
            lambda a: a[0] * 2 + jax.lax.axis_index(DATA_AXIS),
            mesh=mesh, in_specs=((spec,),), out_specs=spec,
            check_vma=False)
        args = (jax.device_put(host, NamedSharding(mesh, spec)),)
        devices = tuple(mesh.devices.flat)
    jitted = jax.jit(fn)
    digest = f"{n_devices:032d}"
    assert store.put(digest, jitted.lower(args).compile(), None, args)

    clear_process_cache()  # force the disk deserialize path
    entry = store.load(digest, args, devices)
    assert entry is not None, "stored executable did not load"
    out = entry["compiled"](args)
    assert len(out.sharding.device_set) == n_devices
    np.testing.assert_array_equal(np.asarray(out), np.asarray(jitted(args)))


def _fresh_session_cache_dir(monkeypatch, **env):
    """jax's cache-dir config after a new session, started from a
    config that names no directory, under the given environment."""
    from spark_tpu.api import session as S

    for key in ("JAX_COMPILATION_CACHE_DIR", "SPARK_TPU_JAX_CACHE"):
        monkeypatch.delenv(key, raising=False)
    for key, value in env.items():
        monkeypatch.setenv(key, value)
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    # what jax itself does with the variable at import
    jax.config.update("jax_compilation_cache_dir",
                      env.get("JAX_COMPILATION_CACHE_DIR"))
    try:
        S._enable_compilation_cache()
        return jax.config.jax_compilation_cache_dir
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


def test_compile_cache_dir_rule(monkeypatch, tmp_path):
    """One function places the compile cache: JAX_COMPILATION_CACHE_DIR
    wins and nothing else is set; unset, a fixed directory inside the
    checkout; SPARK_TPU_JAX_CACHE=0 leaves jax untouched."""
    from spark_tpu.api.session import DEFAULT_JAX_CACHE_DIR

    placed = str(tmp_path / "placed")
    assert _fresh_session_cache_dir(
        monkeypatch, JAX_COMPILATION_CACHE_DIR=placed) == placed
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert DEFAULT_JAX_CACHE_DIR == os.path.join(repo, ".jax_cache")
    assert _fresh_session_cache_dir(monkeypatch) == DEFAULT_JAX_CACHE_DIR
    assert _fresh_session_cache_dir(
        monkeypatch, SPARK_TPU_JAX_CACHE="0") is None


@pytest.mark.timeout(120)
def test_store_disabled_is_legacy(fact_parquet):
    """No compile conf at all → no service, no store traffic, plain
    jit path (zero behavior change)."""
    metrics.reset_exec_store()
    with _session() as s:
        assert s.compile_service is None
        _run_twice(s, fact_parquet)
    st = metrics.exec_store_stats()
    assert st["hits"] == st["misses"] == st["puts"] == 0


# ---- structural-key fingerprint sensitivity --------------------------------


def test_fingerprint_sensitivity():
    """The fingerprint must be stable across calls for identical
    inputs, and MISS on any capacity (arg shape), mesh, platform,
    tier, or adaptive-snapshot change."""
    with _session() as s:
        plan = s.createDataFrame(
            [{"k": i % 3, "v": i} for i in range(10)])._plan
        args = (np.arange(16, dtype=np.int64),)
        base = stable_plan_fingerprint("fused", plan, args)
        assert base == stable_plan_fingerprint("fused", plan, args)

        grown = (np.arange(32, dtype=np.int64),)  # capacity change
        assert stable_plan_fingerprint("fused", plan, grown) != base
        assert stable_plan_fingerprint(
            "fused", plan, args, mesh_size=8) != base
        assert stable_plan_fingerprint(
            "fused", plan, args, platform="tpu") != base
        assert stable_plan_fingerprint("dist", plan, args) != base
        assert stable_plan_fingerprint(
            "fused", plan, args, extra={"stats": 1}) != base


def test_fingerprint_survives_hash_salting(fact_parquet, tmp_path):
    """The digest must not depend on PYTHONHASHSEED (dict/str hash()
    is process-salted): two structurally identical plans built from
    scratch fingerprint identically."""
    with _session() as s:
        s.read.parquet(fact_parquet).createOrReplaceTempView("fp_a")
        s.read.parquet(fact_parquet).createOrReplaceTempView("fp_b")
        q = GOLDEN.format(t="fp_a")
        p1 = s.sql(q)._plan
        p2 = s.sql(q)._plan
        args = (np.arange(8, dtype=np.int64),)
        assert stable_plan_fingerprint("fused", p1, args) == \
            stable_plan_fingerprint("fused", p2, args)


# ---- background compile + hot-swap byte identity ---------------------------


@pytest.mark.timeout(480)
@pytest.mark.parametrize("master", [None, "mesh[2]", "mesh[8]"],
                         ids=["dev1", "dev2", "dev8"])
def test_hot_swap_byte_identity(fact_parquet, master):
    """The three-way invariant on every device count: fused-only,
    chunked-while-compiling, and post-swap executions of one query all
    return byte-identical rows; the first request is chunk-served and
    the swap happens exactly once."""
    view = "swap_fact"
    q = GOLDEN.format(t=view)
    with _session(master=master) as plain:
        plain.read.parquet(fact_parquet).createOrReplaceTempView(view)
        fused = _rows(plain, q)
        assert _rows(plain, q) == fused  # fused re-run, same bytes

    metrics.reset_exec_store()
    with _session(master=master, **{
            "spark.tpu.compile.background": True,
            "spark.tpu.compile.chunkFirst.budgetBytes": 16384}) as s:
        svc = s.compile_service
        s.read.parquet(fact_parquet).createOrReplaceTempView(view)
        first = _rows(s, q)  # served chunked, compile in background
        assert svc.wait_background(timeout=120)
        after = _rows(s, q)  # swapped to the fused executable
        st = metrics.exec_store_stats()
        assert st["background"] >= 1, "first request was not chunk-served"
        assert st["swaps"] == 1
        assert st["fallbacks"] == 0
        assert svc.status()["background"]["by_status"] == {"ready": 1}
    assert first == fused
    assert after == fused


@pytest.mark.timeout(120)
def test_background_unchunkable_runs_foreground():
    """A plan with no chunkable shape (in-memory relation) has nothing
    to hide the compile behind: it runs foreground, is marked ready,
    and never crashes or double-probes."""
    with _session(**{"spark.tpu.compile.background": True}) as s:
        df = s.createDataFrame([{"k": i % 3, "v": i} for i in range(100)])
        df.createOrReplaceTempView("mem_t")
        q = "SELECT k, SUM(v) AS s FROM mem_t GROUP BY k ORDER BY k"
        rows = _rows(s, q)
        assert _rows(s, q) == rows
        assert s.compile_service.status()["background"]["by_status"] \
            == {"ready": 1}


# ---- fault matrix: compile.background --------------------------------------


@pytest.mark.timeout(300)
@pytest.mark.parametrize("kind", list(faults.KINDS))
def test_background_failure_pins_chunked(fact_parquet, kind):
    """Every failure kind injected into the background compile job
    leaves the plan pinned to the chunked tier: no swap, no crash,
    byte-identical answers on every subsequent request."""
    view = "fault_fact"
    q = GOLDEN.format(t=view)
    with _session() as plain:
        plain.read.parquet(fact_parquet).createOrReplaceTempView(view)
        oracle = _rows(plain, q)

    metrics.reset_exec_store()
    with _session(**{
            "spark.tpu.compile.background": True,
            "spark.tpu.compile.chunkFirst.budgetBytes": 16384,
            "spark.tpu.faultInjection.compile.background":
                f"nth:1:{kind}"}) as s:
        svc = s.compile_service
        faults.reset(s.conf)
        try:
            s.read.parquet(fact_parquet).createOrReplaceTempView(view)
            first = _rows(s, q)
            assert svc.wait_background(timeout=120)
            again = _rows(s, q)  # still chunked: the compile failed
            st = metrics.exec_store_stats()
            assert st["fallbacks"] == 1
            assert st["swaps"] == 0
            assert st["background"] == 2, "both requests chunk-served"
            assert svc.status()["background"]["by_status"] \
                == {"failed": 1}
        finally:
            faults.reset(s.conf)
    assert first == oracle
    assert again == oracle


@pytest.mark.timeout(300)
def test_symbols_not_found_reload_recompiles_silently(
        fact_parquet, tmp_path, monkeypatch):
    """Regression for the XLA:CPU large-program limit (ROADMAP item 1):
    a stored executable whose re-load dies with "Symbols not found"
    must behave exactly like a corrupt entry — evicted from disk and
    recompiled fresh — with the query never seeing the error, and the
    recompiled entry must round-trip once re-loads work again."""
    store_dir = str(tmp_path / "store")
    _forget_process_state()
    metrics.reset_exec_store()
    with _session(**{"spark.tpu.compile.store.dir": store_dir}) as s1:
        rows1 = _run_twice(s1, fact_parquet)
        assert s1.compile_service.store.stats()["entries"] >= 1

    # fresh "process" whose XLA refuses to re-load the serialization
    _forget_process_state()
    metrics.reset_exec_store()
    from jax.experimental import serialize_executable as _se

    def boom(*a, **k):
        raise RuntimeError(
            "Symbols not found: [__xla_cpu_runtime_AllReduce]")

    monkeypatch.setattr(_se, "deserialize_and_load", boom)
    with _session(**{"spark.tpu.compile.store.dir": store_dir}) as s2:
        rows2 = _run_twice(s2, fact_parquet)  # must not raise
        st = metrics.exec_store_stats()
        assert st["corrupt"] >= 1, "failed re-load must read as corrupt"
        assert st["hits"] == 0
        assert st["puts"] >= 1, "recompile must re-populate the store"
    assert rows2 == rows1

    # with real deserialization back, the re-populated entries serve
    monkeypatch.undo()
    _forget_process_state()
    metrics.reset_exec_store()
    with _session(**{"spark.tpu.compile.store.dir": store_dir}) as s3:
        rows3 = _run_twice(s3, fact_parquet)
        st = metrics.exec_store_stats()
        assert st["hits"] >= 1 and st["corrupt"] == 0
    assert rows3 == rows1


@pytest.mark.timeout(120)
def test_corrupt_entry_is_miss_and_evicted(tmp_path):
    """A poisoned serialized executable must read as a miss AND be
    evicted from disk, never wedge a session."""
    store = ExecutableStore(str(tmp_path / "store"), max_bytes=1 << 30)
    args = (np.arange(16, dtype=np.int64),)
    compiled = jax.jit(lambda a: a[0] + 1).lower(args).compile()
    assert store.put("d" * 32, compiled, None, args)

    clear_process_cache()  # force the disk deserialize path
    path = store._entry_path("d" * 32)
    with open(path, "wb") as f:
        f.write(b"not a pickle")
    before = metrics.exec_store_stats()["corrupt"]
    assert store.load("d" * 32, args) is None
    assert metrics.exec_store_stats()["corrupt"] == before + 1
    assert not os.path.exists(path), "corrupt entry must be evicted"
    # subsequent loads are plain misses, not repeated corruption events
    assert store.load("d" * 32, args) is None
    assert metrics.exec_store_stats()["corrupt"] == before + 1


# ---- size bound / LRU eviction ---------------------------------------------


@pytest.mark.timeout(120)
def test_eviction_at_size_bound(tmp_path):
    """When the store exceeds maxBytes the least-recently-used entry
    goes first; a load of the survivor still round-trips."""
    store = ExecutableStore(str(tmp_path / "store"), max_bytes=1 << 30)
    args = (np.arange(16, dtype=np.int64),)

    def put(digest, c):
        compiled = jax.jit(lambda a: a[0] + c).lower(args).compile()
        assert store.put(digest, compiled, None, args)

    put("a" * 32, 1)
    one_entry = store.total_bytes()
    assert one_entry > 0
    time.sleep(0.05)  # separate mtimes for LRU ordering
    store.max_bytes = int(one_entry * 1.5)
    before = metrics.exec_store_stats()["evictions"]
    put("b" * 32, 2)  # put runs enforce_budget: 2 entries > 1.5x one
    assert metrics.exec_store_stats()["evictions"] >= before + 1
    assert not os.path.exists(store._entry_path("a" * 32))
    assert os.path.exists(store._entry_path("b" * 32))
    assert store.stats()["entries"] == 1
    assert store.total_bytes() <= store.max_bytes

    clear_process_cache()
    entry = store.load("b" * 32, args)
    assert entry is not None
    out = entry["compiled"](args)
    np.testing.assert_array_equal(np.asarray(out), np.arange(16) + 2)


# ---- plan history + pre-warm -----------------------------------------------


def test_plan_history_journal_and_compaction(tmp_path):
    path = str(tmp_path / "hist.jsonl")
    h = PlanHistory(path, max_entries=4)
    for i in range(10):
        h.note(f"fp{i % 5}", sql=f"SELECT {i % 5}")
    # reloaded history aggregates counts and keeps most-frequent-first
    h2 = PlanHistory(path, max_entries=4)
    top = h2.top(3)
    assert len(top) == 3
    counts = [n for _fp, _sql, n in top]
    assert counts == sorted(counts, reverse=True)
    # compaction bounds the on-disk journal near maxEntries lines
    with open(path) as f:
        assert len(f.readlines()) <= 2 * 4 + 1

    assert _replayable_sql("SELECT 1") == "SELECT 1"
    assert _replayable_sql("  with t as (select 1) select * from t")
    assert _replayable_sql("CREATE VIEW v AS SELECT 1") is None
    assert _replayable_sql(None) is None


@pytest.mark.timeout(300)
def test_prewarm_from_history(fact_parquet, tmp_path):
    """Queries served in one session are replayed most-frequent-first
    by prewarm() in the next: the stage caches, executable store, and
    admission's measured-bytes table are hot before the first client
    query."""
    store_dir = str(tmp_path / "store")
    view = "warm_fact"
    hot = GOLDEN.format(t=view)
    cold = f"SELECT COUNT(*) AS c FROM {view}"
    _forget_process_state()
    with _session(**{"spark.tpu.compile.store.dir": store_dir}) as s1:
        s1.read.parquet(fact_parquet).createOrReplaceTempView(view)
        _rows(s1, hot)
        _rows(s1, hot)
        _rows(s1, cold)
        svc1 = s1.compile_service
        assert svc1.history is not None and svc1.history.size() >= 2
    assert os.path.exists(os.path.join(store_dir, "plan_history.jsonl"))

    _forget_process_state()
    metrics.reset_exec_store()
    with _session(**{"spark.tpu.compile.store.dir": store_dir}) as s2:
        s2.read.parquet(fact_parquet).createOrReplaceTempView(view)
        report = s2.compile_service.prewarm(
            block=True, budget_s=120.0, max_queries=8)
        assert report is not None and not report["errors"]
        replayed = report["replayed"]
        assert len(replayed) == 2
        # most-frequent-first: the twice-served query replays first
        assert replayed[0]["count"] >= replayed[1]["count"]
        assert metrics.exec_store_stats()["prewarmed"] == 2
        status = s2.compile_service.status()
        assert status["prewarm"] is report
        assert status["history"]["entries"] >= 2


@pytest.mark.timeout(120)
def test_prewarm_time_budget_skips(fact_parquet, tmp_path):
    """A zero time budget replays nothing and records why — the
    skipped marks name the budget."""
    store_dir = str(tmp_path / "store")
    with _session(**{"spark.tpu.compile.store.dir": store_dir}) as s:
        s.read.parquet(fact_parquet).createOrReplaceTempView("budget_t")
        _rows(s, "SELECT COUNT(*) AS c FROM budget_t")
        report = s.compile_service.prewarm(block=True, budget_s=0.0,
                                           max_queries=8)
        assert report["replayed"] == []
        assert any(e["reason"] == "time budget"
                   for e in report["skipped"])


# ---- conf hygiene -----------------------------------------------------------


def test_all_compile_conf_keys_declared():
    """Every spark.tpu.compile.* key referenced anywhere in the source
    is registered in conf.py with a default and a docstring."""
    root = os.path.join(os.path.dirname(__file__), "..", "spark_tpu")
    used = set()
    for path in glob.glob(os.path.join(root, "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            used.update(re.findall(r"spark\.tpu\.compile(?:\.\w+)+",
                                   f.read()))
    assert used, "no spark.tpu.compile.* keys found in source"
    for key in used:
        assert key in CF._REGISTRY, f"{key} not registered in conf.py"
        entry = CF._REGISTRY[key]
        assert entry.doc and len(entry.doc) > 20, f"{key} lacks a doc"
        assert entry.default is not None, f"{key} lacks a default"
    assert "spark.tpu.faultInjection.compile.background" in CF._REGISTRY
