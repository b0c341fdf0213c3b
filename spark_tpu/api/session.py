"""SparkSession: the user entry point (reference:
sql/core/src/main/scala/org/apache/spark/sql/SparkSession.scala and
SparkContext.scala:85 — collapsed: there is no driver/executor split to
bootstrap, the 'cluster' is the jax device mesh).
"""

from __future__ import annotations

import datetime
import os
import threading
from typing import Any, Dict, Iterable, List, Optional, Sequence, Union

import numpy as np

import jax

from spark_tpu import locks
from spark_tpu import trace
from spark_tpu import types as T
from spark_tpu.api.dataframe import DataFrame
from spark_tpu.conf import RuntimeConf
from spark_tpu.plan import logical as L
from spark_tpu.types import Field, Schema


#: the compile cache's home when JAX_COMPILATION_CACHE_DIR is unset: a
#: fixed path inside the checkout (the path is part of what jax keys
#: entries on, so a directory that moves never hits)
DEFAULT_JAX_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def _enable_compilation_cache() -> None:
    """Persistent XLA compilation cache: the one place that decides
    where it lives. ``JAX_COMPILATION_CACHE_DIR`` set -> jax has read it
    already and no directory is set here; unset -> ``<repo>/.jax_cache``.
    ``SPARK_TPU_JAX_CACHE=0`` turns the cache off (the test suite: XLA:CPU
    executable (de)serialization has crashed long multi-hundred-compile
    processes). The disk cache turns warm-process startup into loads
    (the analogue of the reference reusing Janino-compiled classes
    across queries, CodeGenerator.scala:1442 'cache')."""
    if os.environ.get("SPARK_TPU_JAX_CACHE", "").lower() in ("0", "off"):
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_JAX_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _harden_cache_writes()


def _harden_cache_writes() -> None:
    """Make persistent-cache entry writes atomic. jax's LRUCache.put
    writes entries with a bare ``Path.write_bytes`` (lru_cache.py:152) —
    a process killed mid-write leaves a TRUNCATED serialized executable,
    and every later process SIGSEGVs inside
    ``backend.deserialize_executable`` when it reads the entry (observed:
    full-suite segfaults after a timeout-killed run poisoned the cache).
    Wrap put() so entry files land via write-temp + os.replace."""
    try:
        from jax._src import lru_cache as _lru
    except Exception:
        return
    if getattr(_lru.LRUCache.put, "_spark_tpu_atomic", False):
        return
    orig = _lru.LRUCache.put
    suffix = getattr(_lru, "_CACHE_SUFFIX", None)
    if suffix is None:
        return  # unknown layout: leave jax untouched

    def put(self, key, val, _orig=orig, _suffix=suffix):
        # Pre-create the entry file ATOMICALLY (temp + rename), then let
        # the original put run: it sees the entry exists and returns,
        # still doing its own locking/eviction bookkeeping. No global
        # state is patched, so concurrent writers are unaffected.
        import os
        import threading

        try:
            if key:
                cache_path = self.path / f"{key}{_suffix}"
                tmp = cache_path.with_name(
                    f"{cache_path.name}.tmp{os.getpid()}-"
                    f"{threading.get_ident()}")
                tmp.write_bytes(val)
                os.replace(tmp, cache_path)
        except OSError:
            pass  # fall through: original non-atomic path still works
        return _orig(self, key, val)

    put._spark_tpu_atomic = True
    _lru.LRUCache.put = put


def _instrument_compile_cache() -> None:
    """Count persistent compilation-cache hits/misses. jax's lookup
    funnel is ``compilation_cache.get_executable_and_time`` — returns a
    deserialized executable on a disk hit, None on a miss (followed by
    a fresh XLA compile, which jax then writes back to the cache dir).
    Wrapping it feeds metrics.note_compile_cache so warmup time is
    attributable."""
    try:
        from jax._src import compilation_cache as _cc
    except Exception:
        return
    fn = getattr(_cc, "get_executable_and_time", None)
    if fn is None or getattr(fn, "_spark_tpu_counted", False):
        return

    from spark_tpu import metrics as _metrics

    def get_executable_and_time(*a, _orig=fn, **kw):
        out = _orig(*a, **kw)
        executable = out[0] if isinstance(out, tuple) else out
        _metrics.note_compile_cache(executable is not None)
        return out

    get_executable_and_time._spark_tpu_counted = True
    _cc.get_executable_and_time = get_executable_and_time


class CacheManager:
    """Lazy in-memory plan cache (reference: CacheManager.scala +
    InMemoryRelation): cache() registers the logical plan; the first
    execution materializes it to a device Batch held in the
    HBM-resident MemoryStore (storage/store.py), and every later query
    whose tree contains a cached subplan scans the stored batch instead
    of recomputing. Identity is structural_key() — injective plan
    structure plus leaf batch/source identity.

    Because the batches live in the byte-accounted store, cached plans
    are EVICTABLE: execution admission or storage pressure may drop an
    unpinned entry LRU-first, and the next query that needs it simply
    re-materializes (the plan registration survives eviction — only
    the bytes are reclaimed). uncache()/clear() remove the store entry
    too, releasing its bytes immediately.

    Thread-safe: the registry mutates under a lock, and each entry
    materializes under its own per-entry lock (single-flight — two
    concurrent queries hitting the same cold cached plan must not
    both materialize it; the registry lock is NOT held during the
    materializing run, so unrelated queries proceed)."""

    def __init__(self, store=None):
        if store is None:
            # standalone manager (tests / sessions built without a
            # store): private unified budget, same code path
            from spark_tpu.storage import MemoryStore, UnifiedMemoryManager

            store = MemoryStore(UnifiedMemoryManager())
        self._store = store
        # entry = [plan, entry lock]
        self._entries: Dict[str, list] = {}
        self._lock = locks.named_lock("session.cache.registry")
        # set by SparkSession: the materialized-view manager; when a
        # cached key is a registered view, materialization delegates
        # to its freshness-checking refresh path (spark_tpu/mview/)
        self._mview = None

    @staticmethod
    def _key(plan: L.LogicalPlan):
        # injective structural identity incl. leaf batch/source identity
        return plan.structural_key()

    @staticmethod
    def _skey(key):
        # namespace cache entries apart from auto-cached scans, which
        # share the store
        return ("cache", key)

    def add(self, plan: L.LogicalPlan) -> None:
        with self._lock:
            self._entries.setdefault(
                self._key(plan), [plan, locks.named_lock("session.cache.entry")])
        if self._mview is not None:
            self._mview.maybe_register(plan)

    def drop(self, plan: L.LogicalPlan) -> bool:
        key = self._key(plan)
        with self._lock:
            entry = self._entries.pop(key, None)
        if entry is None:
            return False
        if self._mview is not None:
            self._mview.unregister(key)
        self._store.remove(self._skey(key))  # releases the bytes
        return True

    def clear(self) -> None:
        with self._lock:
            keys = list(self._entries)
            self._entries.clear()
        if self._mview is not None:
            self._mview.clear_file_views()
        for key in keys:
            self._store.remove(self._skey(key))

    def apply(self, plan: L.LogicalPlan, run) -> L.LogicalPlan:
        """Substitute cached subtrees, LARGEST first (top-down — the
        reference CacheManager matches outermost plans first so a cached
        derived plan hits even when its own subtree is also cached)."""
        with self._lock:
            if not self._entries:
                return plan

        def go(node: L.LogicalPlan) -> L.LogicalPlan:
            with self._lock:
                entry = self._entries.get(self._key(node))
            if entry is not None:
                return L.Relation(self._materialize(node, entry, run))
            children = tuple(go(c) for c in node.children())
            return node.with_children(children) if children else node

        return go(plan)

    def _materialize(self, node: L.LogicalPlan, entry: list, run):
        """Store-hit or single-flight recompute; pin=True holds the
        batch for the duration of the enclosing query's pin_scope."""
        key = self._key(node)
        skey = self._skey(key)
        if self._mview is not None:
            view = self._mview.view_for(key)
            if view is not None:
                # registered materialized view: the manager checks the
                # source fingerprint and refreshes in place before
                # serving (a plain store hit would serve stale bytes)
                return self._mview.materialize(
                    view, entry[1], run, self._store, skey)
        batch = self._store.get(skey, pin=True)
        if batch is not None:
            return batch
        with entry[1]:  # single-flight materialization
            batch = self._store.get(skey, pin=True)
            if batch is not None:
                return batch
            batch = run(entry[0])
            # a rejected put (cannot fit under the unified budget even
            # after evicting the store's LRU tail) still serves THIS
            # query its batch; the entry stays recomputable
            self._store.put(skey, batch, pin=True)
            return batch


class Catalog:
    """Temp-view + table registry (reference:
    sql/catalyst/.../catalog/SessionCatalog.scala:61, pared to the
    in-memory session catalog; file-backed tables register here too)."""

    def __init__(self, session: "SparkSession"):
        self._session = session
        self._views: Dict[str, L.LogicalPlan] = {}

    def _register_view(self, name: str, plan: L.LogicalPlan) -> None:
        self._views[name.lower()] = plan

    def lookup(self, name: str) -> L.LogicalPlan:
        key = name.lower()
        if key not in self._views:
            plan = self._load_persistent(key)
            if plan is None:
                raise KeyError(f"table or view not found: {name}")
            return plan
        return self._views[key]

    def _warehouse(self) -> str:
        from spark_tpu import conf as CF

        return self._session.conf.get(CF.WAREHOUSE_DIR)

    def _load_persistent(self, key: str):
        """Persistent (saveAsTable) tier: tables live as
        <warehouse>/<name>/{_table.json,data/} and survive sessions
        (reference: SessionCatalog external-catalog lookup)."""
        import json
        import os

        meta_path = os.path.join(self._warehouse(), key, "_table.json")
        if not os.path.exists(meta_path):
            return None
        with open(meta_path) as f:
            meta = json.load(f)
        from spark_tpu.io.datasource import FileSource

        options = dict(meta.get("options") or {})
        if meta.get("partition_by"):
            # partition columns live in hive directory names
            options["partitioning"] = "hive"
        src = FileSource(meta.get("format", "parquet"),
                         [os.path.join(self._warehouse(), key, "data")],
                         options=options)
        plan = L.UnresolvedScan(src)
        self._views[key] = plan  # memoize for the session
        return plan

    def refresh_persistent(self, key: str) -> None:
        """Drop any memoized plan so the next lookup re-reads the
        (re)written table."""
        self._views.pop(key, None)

    def listTables(self) -> List[str]:
        import os

        names = set(self._views)
        wh = self._warehouse()
        if os.path.isdir(wh):
            for d in os.listdir(wh):
                if os.path.exists(os.path.join(wh, d, "_table.json")):
                    names.add(d)
        return sorted(names)

    def dropTempView(self, name: str) -> bool:
        return self._views.pop(name.lower(), None) is not None

    def tableExists(self, name: str) -> bool:
        return name.lower() in self._views


class SparkSessionBuilder:
    def __init__(self):
        self._conf: Dict[str, Any] = {}
        self._app_name = "spark-tpu"
        self._ext_fns: list = []

    def appName(self, name: str) -> "SparkSessionBuilder":
        self._app_name = name
        return self

    def withExtensions(self, fn) -> "SparkSessionBuilder":
        """fn(extensions) registers injection points at session build
        (reference: SparkSession.Builder.withExtensions)."""
        self._ext_fns.append(fn)
        return self

    def master(self, url: str) -> "SparkSessionBuilder":
        """master URL analogue (reference: SparkContext master parsing):
        ``local`` / ``local[*]`` = single-device; ``mesh[N]`` = SPMD
        execution over an N-device jax mesh (the cluster IS the mesh)."""
        if url.startswith("mesh"):
            n = None
            if "[" in url:
                inner = url[url.index("[") + 1:url.index("]")]
                n = None if inner == "*" else int(inner)
            from spark_tpu import conf as CF

            self._conf[CF.MESH_DEVICES.key] = n if n is not None else -1
        return self

    def config(self, key: str, value: Any) -> "SparkSessionBuilder":
        self._conf[key] = value
        return self

    def getOrCreate(self) -> "SparkSession":
        if SparkSession._active is None:
            SparkSession._active = SparkSession(self._app_name, self._conf)
        else:
            for k, v in self._conf.items():
                SparkSession._active.conf.set(k, v)
        for fn in self._ext_fns:
            fn(SparkSession._active.extensions)
        self._ext_fns = []
        return SparkSession._active


class SparkSession:
    _active: Optional["SparkSession"] = None

    builder = SparkSessionBuilder()

    def __init__(self, app_name: str = "spark-tpu",
                 conf: Optional[Dict[str, Any]] = None):
        # SQL engines need 64-bit ints/floats; flip jax's default.
        jax.config.update("jax_enable_x64", True)
        _enable_compilation_cache()
        _instrument_compile_cache()
        self.app_name = app_name
        self.conf = RuntimeConf(conf)
        # runtime lock-order validation (spark.tpu.debug.lockOrder):
        # flip the global flag before any service builds its locks
        locks.configure(self.conf)
        self.catalog = Catalog(self)
        # unified storage/execution HBM accounting: the MemoryStore
        # (cached/auto-cached batches) and the scheduler's admission
        # controller share one budget (spark.tpu.scheduler.hbmBudgetBytes)
        from spark_tpu.storage import MemoryStore, UnifiedMemoryManager

        self.memory_manager = UnifiedMemoryManager(conf=self.conf)
        self.memory_store = MemoryStore(self.memory_manager)
        self.cache_manager = CacheManager(store=self.memory_store)
        # materialized views ride on the plan cache: cache() promotes
        # qualifying aggregates to views; the cache's materialize path
        # delegates to the view manager for freshness (spark_tpu/mview/)
        from spark_tpu.mview import ViewManager

        self.mview_manager = ViewManager(self)
        self.cache_manager._mview = self.mview_manager
        self._stopped = False
        from spark_tpu.extensions import Extensions

        self.extensions = Extensions()
        self._read = None
        self._mesh = None
        self._mesh_executor = None
        from spark_tpu import conf as CF

        n = self.conf.get(CF.MESH_DEVICES)
        if n is not None:
            from spark_tpu.parallel.mesh import make_mesh

            self._mesh = make_mesh(None if n == -1 else int(n))
        # live status UI/REST server (reference: SparkUI.scala:40),
        # gated on spark.ui.enabled
        from spark_tpu import ui as _ui

        self._ui = _ui.maybe_start(self)
        # last: plugins may exercise any session API from init(session)
        self.extensions.load_plugins(self)

    @property
    def ui_web_url(self) -> Optional[str]:
        """URL of the live status UI when enabled (reference:
        SparkContext.uiWebUrl)."""
        return self._ui.url if self._ui is not None else None

    @property
    def mesh_executor(self):
        """Distributed executor when running under a mesh master URL."""
        if self._mesh is None:
            return None
        if self._mesh_executor is None:
            from spark_tpu.parallel.executor import MeshExecutor

            self._mesh_executor = MeshExecutor(self._mesh, conf=self.conf)
        return self._mesh_executor

    @property
    def compile_service(self):
        """AOT compilation service (spark_tpu/compile/) when any
        spark.tpu.compile.* feature is enabled; None otherwise —
        callers treat None as 'legacy behavior'. Re-resolved per
        access so conf changes (store dir, background flag) take
        effect immediately."""
        from spark_tpu.compile import maybe_service

        return maybe_service(self)

    # -- builder is reset-safe for tests
    @classmethod
    def _reset(cls):
        cls._active = None
        cls.builder = SparkSessionBuilder()

    @classmethod
    def getActiveSession(cls) -> Optional["SparkSession"]:
        """Reference: SparkSession.getActiveSession."""
        return cls._active

    @classmethod
    def setActiveSession(cls, session: "SparkSession") -> None:
        cls._active = session

    def _ensure_active(self) -> None:
        """Make this session the process-current one if none is (global
        lookups — injected functions/rules, conf-driven optimizer flags —
        resolve against the active session; a session that is executing
        a query is by definition current). A stop()ed session never
        resurrects itself: getOrCreate() must build a fresh one."""
        if SparkSession._active is None and not self._stopped:
            SparkSession._active = self

    @property
    def sparkContext(self):
        """RDD-tier entry point (reference: SparkContext.scala:85)."""
        if getattr(self, "_sc", None) is None:
            from spark_tpu.rdd import SparkContext

            self._sc = SparkContext(self)
        return self._sc

    @property
    def read(self):
        from spark_tpu.io.readwriter import DataFrameReader

        return DataFrameReader(self)

    @property
    def readStream(self):
        from spark_tpu.streaming.readwriter import DataStreamReader

        return DataStreamReader(self)

    def range(self, start: int, end: Optional[int] = None,
              step: int = 1, numSlices: Optional[int] = None) -> DataFrame:
        if end is None:
            start, end = 0, start
        return DataFrame(self, L.Range(int(start), int(end), int(step)))

    def table(self, name: str) -> DataFrame:
        return DataFrame(self, self.catalog.lookup(name))

    def sql(self, query: str) -> DataFrame:
        from spark_tpu.sql.parser import parse_sql

        self._ensure_active()
        # injected parser hooks first (injectParser:318 analogue)
        with trace.span("query.parse"):
            plan = self.extensions.parse(query, self.catalog, parse_sql)
        df = DataFrame(self, plan)
        # carried for the compile service's served-plan history: SQL
        # text is the cross-process-replayable identity of this plan
        df._sql_text = query
        return df

    def createDataFrame(
        self,
        data: Union["pa.Table", "pd.DataFrame", Iterable],
        schema: Optional[Union[Schema, Sequence[str]]] = None,
    ) -> DataFrame:
        import pandas as pd
        import pyarrow as pa

        from spark_tpu.columnar.arrow import from_arrow

        if isinstance(data, pa.Table):
            table = data
        elif isinstance(data, pd.DataFrame):
            table = pa.Table.from_pandas(data, preserve_index=False)
        else:
            rows = list(data)
            if not rows:
                raise ValueError("cannot create DataFrame from empty data "
                                 "without an explicit arrow/pandas input")
            if isinstance(rows[0], dict):
                names = list(rows[0].keys())
                cols = {n: [r[n] for r in rows] for n in names}
            else:
                if schema is None:
                    raise ValueError("tuple rows require column names")
                names = (list(schema.names) if isinstance(schema, Schema)
                         else list(schema))
                cols = {n: [r[i] for r in rows] for i, n in enumerate(names)}
            table = pa.table(cols)
        df = DataFrame(self, L.Relation(from_arrow(table)))
        if isinstance(schema, Sequence) and not isinstance(schema, Schema) \
                and schema is not None and not isinstance(schema, str):
            old = df.columns
            if list(schema) != old and len(schema) == len(old):
                for o, n in zip(old, schema):
                    df = df.withColumnRenamed(o, n)
        return df

    def _stop_services(self) -> None:
        """Stop and join every background service/thread the session
        owns (compile workers, scheduler worker pool, heartbeat
        monitor, status UI). Split from ``stop()`` so tests can
        quiesce the threads without tearing down the singleton."""
        svc = self.__dict__.pop("_compile_service", None)
        if svc is not None:
            svc.stop()
        sched = getattr(self, "query_scheduler", None)
        if sched is not None:
            sched.stop()
            self.query_scheduler = None
        hb = getattr(self, "heartbeat_monitor", None)
        if hb is not None:
            hb.stop()
            self.heartbeat_monitor = None
        if self._ui is not None:
            self._ui.stop()
            self._ui = None

    def stop(self) -> None:
        self._stopped = True
        self._stop_services()
        self.extensions.shutdown_plugins()
        SparkSession._reset()

    @property
    def version(self) -> str:
        from spark_tpu import __version__

        return __version__

    def __repr__(self):
        return f"<SparkSession app={self.app_name} devices={jax.device_count()}>"
