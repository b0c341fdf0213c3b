"""Session-facing compilation service.

Three policies live here, all keeping XLA off the query critical path:

* **Stage-cache integration** (``build_stage_callable``): when the
  fused/distributed stage caches take a fresh entry, the callable they
  store consults the cross-session executable store first — a hit
  skips trace AND compile; a miss AOT-compiles on first call and
  persists the executable for the next session.
* **Background compile + hot-swap** (``CompileService.execute_plan``):
  with spark.tpu.compile.background on, a plan whose executables are
  not yet ready is served through the chunked tier (small per-chunk
  programs, sub-second compiles) while the fused executable compiles
  on a daemon thread; once ready the next execution atomically swaps
  to the fused path — byte-identical either way. A background failure
  pins the plan to the chunked tier permanently (no swap, no crash).
* **Plan-history pre-warm** (``CompileService.prewarm``): served SQL
  is journaled (plan_history.jsonl); at server start the history is
  replayed most-frequent-first on a bounded worker pool so the plan
  space is traced + compiled before the first client query arrives.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
import weakref
from typing import (Any, Callable, Dict, List, Optional, Sequence,
                    Tuple)

import jax

from spark_tpu import locks
from spark_tpu import conf as CF
from spark_tpu import faults, metrics
from spark_tpu.compile.store import (ExecutableStore,
                                     compiled_call_signature,
                                     stable_plan_fingerprint)


def active_service() -> Optional["CompileService"]:
    """The active session's compile service, or None when disabled —
    callers (planner/executor stage caches) treat None as 'behave
    exactly as before'."""
    try:
        from spark_tpu.api.session import SparkSession

        sess = SparkSession._active
        if sess is None:
            return None
        return sess.compile_service
    except Exception:
        return None


def maybe_service(session) -> Optional["CompileService"]:
    """Build (or reuse) the session's CompileService when any
    ``spark.tpu.compile.*`` feature is enabled; None otherwise. Reused
    across calls unless the store dir changed (tests point one session
    at several tmpdirs)."""
    conf = session.conf
    try:
        root = str(conf.get(CF.COMPILE_STORE_DIR) or "")
        background = bool(conf.get(CF.COMPILE_BACKGROUND))
        hist = str(conf.get(CF.COMPILE_HISTORY_PATH) or "")
    except Exception:
        return None
    if not root and not background and not hist:
        session.__dict__.pop("_compile_service", None)
        return None
    cur = session.__dict__.get("_compile_service")
    if cur is not None and cur.root == root \
            and cur._history_path_cfg == hist:
        return cur
    svc = CompileService(session)
    session.__dict__["_compile_service"] = svc
    return svc


def build_stage_callable(tier: str, plan, trace_fn: Callable, example_args,
                         schema_box: dict, *, mesh_size: int = 1,
                         platform: Optional[str] = None,
                         extra: Any = None,
                         devices: Optional[Sequence] = None) -> Callable:
    """The callable a stage cache stores for a fresh entry.

    Without an active service (or with the store disabled) this is
    exactly the legacy ``jax.jit(trace_fn)`` — zero behavior change.
    With a store it becomes a hybrid: serve a persisted AOT executable
    when one matches, else AOT-compile on first call and persist."""
    jitted = jax.jit(trace_fn)
    svc = active_service()
    if svc is None or svc.store is None:
        return jitted
    try:
        from spark_tpu import trace

        with trace.span("compile.probe", tier=tier):
            return svc.stage_callable(tier, plan, jitted, example_args,
                                      schema_box, mesh_size=mesh_size,
                                      platform=platform, extra=extra,
                                      devices=devices)
    except Exception as e:
        metrics.record("compile", phase="stage_callable_error",
                       error=repr(e))
        return jitted


class PlanHistory:
    """Append-only JSONL journal of served plans (fingerprint + SQL when
    the plan came from SQL text), aggregated in memory for
    most-frequent-first replay. Compacted once the file grows past
    ~2x maxEntries lines."""

    def __init__(self, path: str, max_entries: int = 512):
        self.path = path
        self.max_entries = max(1, int(max_entries))
        self._lock = locks.named_lock("compile.history")
        #: fp -> [count, sql-or-None]
        self._counts: Dict[str, List] = {}
        self._lines = 0
        self._load()

    def _load(self) -> None:
        # read outside the lock, apply under it: the counters are
        # lock-guarded state everywhere else, and holding the lock
        # across file IO is exactly what the concurrency linter bans
        try:
            with open(self.path) as f:
                raw = f.readlines()
        except OSError:
            return
        with self._lock:
            for line in raw:
                self._lines += 1
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                fp = rec.get("fp")
                if not fp:
                    continue
                ent = self._counts.setdefault(fp, [0, None])
                ent[0] += int(rec.get("n", 1))
                if rec.get("sql"):
                    ent[1] = rec["sql"]

    def note(self, fp: str, sql: Optional[str] = None) -> None:
        with self._lock:
            ent = self._counts.setdefault(fp, [0, None])
            ent[0] += 1
            if sql:
                ent[1] = sql
            rec = {"fp": fp, "ts": round(time.time(), 2)}
            if sql:
                rec["sql"] = sql
            try:
                os.makedirs(os.path.dirname(self.path) or ".",
                            exist_ok=True)
                with open(self.path, "a") as f:
                    f.write(json.dumps(rec) + "\n")
                self._lines += 1
            except OSError:
                return
            if self._lines > 2 * self.max_entries:
                self._compact_locked()

    def _compact_locked(self) -> None:
        top = sorted(self._counts.items(), key=lambda kv: -kv[1][0])
        top = top[:self.max_entries]
        tmp = f"{self.path}.tmp{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                for fp, (n, sql) in top:
                    rec = {"fp": fp, "n": n}
                    if sql:
                        rec["sql"] = sql
                    f.write(json.dumps(rec) + "\n")
            os.replace(tmp, self.path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return
        self._counts = {fp: [n, sql] for fp, (n, sql) in top}
        self._lines = len(top)

    def top(self, limit: int) -> List[Tuple[str, Optional[str], int]]:
        """[(fp, sql-or-None, count)] most-frequent-first."""
        with self._lock:
            items = sorted(self._counts.items(), key=lambda kv: -kv[1][0])
        return [(fp, sql, n) for fp, (n, sql) in items[:max(0, limit)]]

    def size(self) -> int:
        with self._lock:
            return len(self._counts)


def _replayable_sql(sql: Optional[str]) -> Optional[str]:
    """Only SELECT-shaped statements are safe to replay at pre-warm
    (CREATE/DROP VIEW would mutate the catalog; INSERT-style side
    effects don't exist here but the allowlist is the right shape)."""
    if not sql:
        return None
    head = sql.lstrip().upper()
    if head.startswith("SELECT") or head.startswith("WITH"):
        return sql
    return None


class CompileService:
    """Per-session compilation policy: executable store, background
    compile + hot-swap routing, served-plan history, pre-warm."""

    def __init__(self, session):
        self._session_ref = weakref.ref(session)
        conf = session.conf
        self.root = str(conf.get(CF.COMPILE_STORE_DIR) or "")
        self._history_path_cfg = str(
            conf.get(CF.COMPILE_HISTORY_PATH) or "")
        self.store: Optional[ExecutableStore] = None
        if self.root:
            self.store = ExecutableStore(
                self.root, int(conf.get(CF.COMPILE_STORE_MAX_BYTES)))
        hist_path = self._history_path_cfg or (
            os.path.join(self.root, "plan_history.jsonl")
            if self.root else "")
        self.history: Optional[PlanHistory] = None
        if hist_path:
            self.history = PlanHistory(
                hist_path, int(conf.get(CF.COMPILE_HISTORY_MAX_ENTRIES)))
        #: routing-key -> {"status": new|compiling|ready|failed,
        #:                 "chunk_serves": int, "swapped": bool, ...}
        self._plans: Dict[Any, dict] = {}
        self._plans_lock = locks.named_lock("compile.plans")
        self._jobs: List[threading.Thread] = []
        self._jobs_lock = locks.named_lock("compile.jobs")
        self._prewarm_report: Optional[dict] = None
        self._stopped = False

    # -- conf plumbing

    def _conf(self):
        sess = self._session_ref()
        return sess.conf if sess is not None else CF.RuntimeConf()

    # -- stage-cache integration ---------------------------------------------

    def stage_callable(self, tier: str, plan, jitted, example_args,
                       schema_box: dict, *, mesh_size: int = 1,
                       platform: Optional[str] = None,
                       extra: Any = None,
                       devices: Optional[Sequence] = None) -> Callable:
        store = self.store
        digest = stable_plan_fingerprint(
            tier, plan, example_args, mesh_size=mesh_size,
            platform=platform, extra=extra)
        entry = store.load(digest, example_args, devices)
        if entry is not None:
            metrics.note_exec_store("hits")
            metrics.record("compile", phase="store_hit", tier=tier,
                           digest=digest)
            schema_box["schema"] = entry["schema"]
            compiled, sig = entry["compiled"], entry["sig"]

            def hit_call(args):
                if compiled_call_signature(args) == sig:
                    return compiled(args)
                return jitted(args)  # shape drift: fall back to jit

            return hit_call

        metrics.note_exec_store("misses")
        state: dict = {}
        state_lock = locks.named_lock("compile.stage")
        serialize = bool(self._conf().get(CF.COMPILE_STORE_SERIALIZE))

        def miss_call(args):
            with state_lock:
                compiled = state.get("compiled")
                failed = state.get("failed")
            if compiled is not None:
                if compiled_call_signature(args) == state["sig"]:
                    return compiled(args)
                return jitted(args)
            if failed:
                return jitted(args)
            with state_lock:
                # re-check under the lock; first thread in compiles
                compiled = state.get("compiled")
                if compiled is None and not state.get("failed"):
                    t0 = time.perf_counter()
                    try:
                        # explicit AOT lower+compile (vs calling the
                        # jit) so the Compiled object is ours to
                        # serialize; tracing fills schema_box
                        compiled = jitted.lower(args).compile()
                        state["sig"] = compiled_call_signature(args)
                        state["compiled"] = compiled
                    except Exception as e:
                        state["failed"] = True
                        metrics.record("compile", phase="aot_failed",
                                       tier=tier, digest=digest,
                                       error=repr(e))
                        return jitted(args)
                    metrics.record(
                        "compile", phase="aot_compile", tier=tier,
                        digest=digest,
                        ms=round((time.perf_counter() - t0) * 1e3, 2))
                    if serialize:
                        store.put(digest, compiled,
                                  schema_box.get("schema"), args)
            if compiled_call_signature(args) == state.get("sig"):
                return state["compiled"](args)
            return jitted(args)

        return miss_call

    # -- background compile + hot-swap ---------------------------------------

    def _routing_key(self, lp) -> Any:
        try:
            return lp.structural_key()
        except Exception:
            return id(lp)

    def execute_plan(self, lp, conf, run_fn):
        """DataFrame._execute's entry point: route one plan execution
        through the background-compile state machine (or straight down
        the recovery ladder when backgrounding is off)."""
        from spark_tpu import recovery

        if not bool(conf.get(CF.COMPILE_BACKGROUND)):
            return recovery.run_plan_with_oom_degradation(lp, conf, run_fn)

        key = self._routing_key(lp)
        with self._plans_lock:
            info = self._plans.setdefault(
                key, {"status": "new", "chunk_serves": 0,
                      "swapped": False, "error": None})
            status = info["status"]

        if status == "ready":
            swap = False
            with self._plans_lock:
                if info["chunk_serves"] and not info["swapped"]:
                    info["swapped"] = True
                    swap = True
            if swap:
                metrics.note_exec_store("swaps")
                metrics.record("compile", phase="swap",
                               chunk_serves=info["chunk_serves"])
            return recovery.run_plan_with_oom_degradation(lp, conf, run_fn)

        # compiling / failed / new: serve through the chunked tier so
        # this request never blocks on the fused XLA compile
        found, shadow = recovery.plan_chunk_first(
            lp, conf, int(conf.get(CF.COMPILE_CHUNK_FIRST_BUDGET)))
        if found is None:
            # plan has no chunkable shape (e.g. in-memory relation):
            # nothing to hide the compile behind — run in the
            # foreground and mark ready so we don't re-probe
            out = recovery.run_plan_with_oom_degradation(lp, conf, run_fn)
            with self._plans_lock:
                if info["status"] not in ("failed",):
                    info["status"] = "ready"
            metrics.record("compile", phase="unchunkable_foreground")
            return out

        spawn = False
        with self._plans_lock:
            if info["status"] == "new":
                info["status"] = "compiling"
                spawn = True
        if spawn:
            # start the fused compile BEFORE serving, so it overlaps
            # the chunked execution below
            self._spawn_background(key, lp, conf, run_fn)
        with self._plans_lock:
            info["chunk_serves"] += 1
            serves = info["chunk_serves"]
        metrics.note_exec_store("background")
        metrics.record("compile", phase="chunk_first_serve",
                       status=info["status"], serve=serves)
        from spark_tpu.physical.chunked import execute_chunked

        try:
            return execute_chunked(found, shadow, run_fn)
        except Exception:
            # the chunked serve itself failed (not a compile problem):
            # fall through to the full recovery ladder
            return recovery.run_plan_with_oom_degradation(lp, conf, run_fn)

    def _spawn_background(self, key, lp, conf, run_fn) -> None:
        def job():
            t0 = time.perf_counter()
            metrics.record("compile", phase="background_start")
            try:
                from spark_tpu import recovery

                faults.inject("compile.background", conf)
                # executing the plan once through the normal path is
                # the compile: it populates the stage caches AND the
                # executable store for this and future sessions
                recovery.run_plan_with_oom_degradation(lp, conf, run_fn)
            except Exception as e:
                with self._plans_lock:
                    self._plans[key]["status"] = "failed"
                    self._plans[key]["error"] = repr(e)
                metrics.note_exec_store("fallbacks")
                metrics.record("compile", phase="background_failed",
                               error=repr(e))
                return
            with self._plans_lock:
                self._plans[key]["status"] = "ready"
            metrics.record(
                "compile", phase="background_done",
                ms=round((time.perf_counter() - t0) * 1e3, 2))

        t = threading.Thread(target=job, name="spark-tpu-bg-compile",
                             daemon=True)
        with self._jobs_lock:
            self._jobs = [j for j in self._jobs if j.is_alive()]
            self._jobs.append(t)
        t.start()

    def wait_background(self, timeout: float = 30.0) -> bool:
        """Join live background-compile jobs (tests + graceful stop);
        True when none remain alive."""
        deadline = time.monotonic() + timeout
        with self._jobs_lock:
            jobs = list(self._jobs)
        for t in jobs:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
        return not any(t.is_alive() for t in jobs)

    # -- served-plan history + pre-warm --------------------------------------

    def note_served(self, plan, sql: Optional[str] = None) -> None:
        """Journal one served plan (DataFrame._execute calls this for
        every execution; the scheduler passes SQL text through)."""
        if self.history is None:
            return
        sql = _replayable_sql(sql)
        if sql is not None:
            fp = "sql:" + hashlib.sha1(
                " ".join(sql.split()).encode()).hexdigest()[:24]
        else:
            try:
                fp = "plan:" + hashlib.sha1(
                    repr(type(plan).__name__).encode()).hexdigest()[:24]
            except Exception:
                return
        try:
            self.history.note(fp, sql)
        except Exception:
            pass

    def prewarm(self, session=None, block: bool = True,
                budget_s: Optional[float] = None,
                max_queries: Optional[int] = None) -> Optional[dict]:
        """Replay the served-plan history most-frequent-first, bounded
        by time/count budgets, populating the stage caches and the
        executable store. ``block=False`` runs on a daemon thread
        (connect-server start) and returns immediately."""
        session = session or self._session_ref()
        if session is None or self.history is None:
            return None
        if not block:
            t = threading.Thread(
                target=lambda: self.prewarm(session, block=True,
                                            budget_s=budget_s,
                                            max_queries=max_queries),
                name="spark-tpu-prewarm", daemon=True)
            with self._jobs_lock:
                self._jobs.append(t)
            t.start()
            return None
        if metrics.brownout_level() > 0:
            # fleet brownout: pre-warm is exactly the analysis-heavy
            # optional work the fleet sheds FIRST under pressure —
            # skipping it costs warmth, never correctness
            metrics.record("compile", phase="prewarm_brownout_skip",
                           level=metrics.brownout_level())
            return {"replayed": [], "skipped": [], "errors": [],
                    "brownout": True}
        conf = session.conf
        if budget_s is None:
            budget_s = float(conf.get(CF.COMPILE_PREWARM_BUDGET_S))
        if max_queries is None:
            max_queries = int(conf.get(CF.COMPILE_PREWARM_MAX_QUERIES))
        workers = max(1, int(conf.get(CF.COMPILE_PREWARM_WORKERS)))
        entries = self.history.top(max_queries)
        t0 = time.monotonic()
        report: dict = {"replayed": [], "skipped": [], "errors": [],
                        "budget_s": budget_s}
        report_lock = locks.named_lock("compile.prewarm")
        metrics.record("compile", phase="prewarm_start",
                       candidates=len(entries), workers=workers)

        def replay_one(fp: str, sql: str, count: int) -> None:
            q0 = time.perf_counter()
            try:
                session.sql(sql).collect()
            except Exception as e:
                with report_lock:
                    report["errors"].append(
                        {"fp": fp, "sql": sql[:120], "error": repr(e)})
                return
            metrics.note_exec_store("prewarmed")
            with report_lock:
                report["replayed"].append(
                    {"fp": fp, "sql": sql[:120], "count": count,
                     "ms": round((time.perf_counter() - q0) * 1e3, 1)})

        pending = []
        for fp, sql, count in entries:
            sql = _replayable_sql(sql)
            if sql is None:
                report["skipped"].append({"fp": fp, "reason": "no sql"})
                continue
            pending.append((fp, sql, count))

        if workers == 1:
            for fp, sql, count in pending:
                if time.monotonic() - t0 > budget_s:
                    report["skipped"].append(
                        {"fp": fp, "reason": "time budget"})
                    continue
                replay_one(fp, sql, count)
        else:
            idx = [0]
            idx_lock = locks.named_lock("compile.prewarm")

            def worker():
                while True:
                    with idx_lock:
                        if idx[0] >= len(pending):
                            return
                        i = idx[0]
                        idx[0] += 1
                    fp, sql, count = pending[i]
                    if time.monotonic() - t0 > budget_s:
                        with report_lock:
                            report["skipped"].append(
                                {"fp": fp, "reason": "time budget"})
                        continue
                    replay_one(fp, sql, count)

            threads = [threading.Thread(target=worker, daemon=True,
                                        name=f"spark-tpu-prewarm-{i}")
                       for i in range(min(workers, max(1, len(pending))))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        report["wall_s"] = round(time.monotonic() - t0, 2)
        metrics.record("compile", phase="prewarm_done",
                       replayed=len(report["replayed"]),
                       errors=len(report["errors"]),
                       skipped=len(report["skipped"]),
                       wall_s=report["wall_s"])
        self._prewarm_report = report
        return report

    # -- introspection --------------------------------------------------------

    def status(self) -> dict:
        with self._plans_lock:
            by_status: Dict[str, int] = {}
            for info in self._plans.values():
                by_status[info["status"]] = \
                    by_status.get(info["status"], 0) + 1
            plans = len(self._plans)
        with self._jobs_lock:
            alive = sum(1 for t in self._jobs if t.is_alive())
        try:
            from spark_tpu.scheduler import admission

            measured = admission.measured_snapshot()
        except Exception:
            measured = None
        return {
            "admission_measured": measured,
            "store": self.store.stats() if self.store else None,
            "exec_store": metrics.exec_store_stats(),
            "background": {"plans": plans, "by_status": by_status,
                           "jobs_alive": alive},
            "history": {"path": self.history.path,
                        "entries": self.history.size()}
            if self.history else None,
            "prewarm": self._prewarm_report,
        }

    def stop(self, timeout: float = 5.0) -> None:
        self._stopped = True
        self.wait_background(timeout=timeout)
