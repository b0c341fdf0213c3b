"""The materialized-view refresh/merge engine.

``ViewManager`` owns every registered view and sits behind the plan
cache: ``CacheManager._materialize`` delegates here for keys that are
registered views, so a view read is exactly a cache read PLUS a
freshness check against the shared scan fingerprint
(io/fingerprint.py). A stale file view refreshes in place — the
``MemoryStore.update`` path keeps the entry's key/LRU identity and
re-accounts only the byte delta — and when the delta is pure appends
and the aggregate is exactly re-mergeable
(analysis/legality.remerge_verdict), the refresh executes the
aggregate over the APPENDED FILES ONLY and re-merges the partials
into the cached batch. Everything else pays a transparent full
recompute; both paths produce byte-identical results (the dictionary
normalization in columnar/arrow.from_arrow makes the aggregate output
a pure function of the input row multiset).

Stream views subscribe to micro-batch delta events published by
streaming/execution.py BEFORE the WAL commit, deduplicated here by
batch id: a crash between merge and commit replays the same batch id,
which the ``batch_id <= last_batch_id`` watermark drops — replay
never double-merges.

Incremental refreshes pass through the ``mview.refresh`` fault point
with bounded transient retries (spark.tpu.mview.refreshRetries); on
exhaustion a file view falls back to a full recompute (files can be
re-scanned) while a stream view re-raises so the WAL redelivers the
delta (streams cannot).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Dict, List, Optional

import pyarrow as pa

from spark_tpu import locks
from spark_tpu import conf as CF
from spark_tpu import faults, metrics, recovery
from spark_tpu.io.fingerprint import classify_delta, source_fingerprint
from spark_tpu.mview.view import MaterializedView, inspect_plan
from spark_tpu.plan import logical as L


def _stream_key(name: str):
    return ("mview-stream", name)


class ViewManager:
    """Registry + refresh engine for one session's materialized views.

    Thread-safe: the registry mutates under ``_lock``; each view
    refreshes under its own ``view.lock`` (file views additionally
    single-flight under the CacheManager's per-entry lock, which the
    delegate call passes in)."""

    def __init__(self, session):
        self._session = session
        self._views: Dict[Any, MaterializedView] = {}
        self._by_stream: Dict[str, List[MaterializedView]] = {}
        self._lock = locks.named_lock("mview.manager")

    # -- conf ---------------------------------------------------------------

    @property
    def _conf(self):
        return self._session.conf

    def enabled(self) -> bool:
        try:
            return bool(self._conf.get(CF.MVIEW_ENABLED))
        except Exception:
            return False

    def _incremental_on(self) -> bool:
        try:
            return bool(self._conf.get(CF.MVIEW_INCREMENTAL))
        except Exception:
            return True

    # -- registration -------------------------------------------------------

    def maybe_register(self, plan: L.LogicalPlan
                       ) -> Optional[MaterializedView]:
        """Promote a ``df.cache()`` registration to a file view when
        the subsystem is enabled and the plan qualifies (root Aggregate
        over one fingerprinted file scan). Never raises — a plan that
        cannot be a view simply stays a plain cache entry."""
        if not self.enabled():
            return None
        try:
            insp = inspect_plan(plan)
        except Exception as exc:  # defensive: cache() must never break
            metrics.record("mview", phase="inspect_error",
                           error=type(exc).__name__)
            return None
        if not insp.registrable or insp.kind != "file":
            return None
        key = plan.structural_key()
        with self._lock:
            view = self._views.get(key)
            if view is None:
                view = MaterializedView(key=key, plan=plan,
                                        inspection=insp)
                self._views[key] = view
                metrics.note_mview("registrations")
                metrics.record("mview", phase="register",
                               view_kind="file",
                               incremental=insp.incremental)
                metrics.set_gauge("mview.views", len(self._views))
        return view

    def register_stream_view(self, name: str, plan: L.LogicalPlan,
                             stream: str) -> MaterializedView:
        """Register an explicitly named view over a streaming
        aggregate: ``plan`` must be a root Aggregate over exactly the
        one StreamingSource of the query named ``stream``, and must be
        incrementally maintainable — streams cannot be re-scanned, so
        there is no full-recompute fallback to fall back TO."""
        insp = inspect_plan(plan)
        if not insp.registrable or insp.kind != "stream":
            why = "; ".join(m for _, m, _ in insp.diagnostics) \
                or "plan is not a stream-view candidate"
            raise ValueError(
                f"cannot register stream view {name!r}: {why}")
        key = _stream_key(name)
        view = MaterializedView(key=key, plan=plan, inspection=insp,
                                name=name, stream=stream)
        with self._lock:
            if key in self._views:
                raise ValueError(
                    f"stream view {name!r} is already registered")
            self._views[key] = view
            self._by_stream.setdefault(stream, []).append(view)
            metrics.note_mview("registrations")
            metrics.record("mview", phase="register",
                           view_kind="stream", view=name,
                           stream=stream)
            metrics.set_gauge("mview.views", len(self._views))
        return view

    def unregister(self, key) -> None:
        with self._lock:
            view = self._views.pop(key, None)
            if view is not None and view.stream:
                subs = self._by_stream.get(view.stream, [])
                if view in subs:
                    subs.remove(view)
            metrics.set_gauge("mview.views", len(self._views))

    def drop_stream_view(self, name: str) -> None:
        self.unregister(_stream_key(name))

    def clear_file_views(self) -> None:
        """Drop every file view (CacheManager.clear delegate); stream
        views were registered explicitly and survive a cache clear."""
        with self._lock:
            for key in [k for k, v in self._views.items()
                        if v.kind == "file"]:
                del self._views[key]
            metrics.set_gauge("mview.views", len(self._views))

    def view_for(self, key) -> Optional[MaterializedView]:
        with self._lock:
            return self._views.get(key)

    def stream_view(self, name: str) -> Optional[MaterializedView]:
        return self.view_for(_stream_key(name))

    def views(self) -> List[dict]:
        with self._lock:
            return [v.to_dict() for v in self._views.values()]

    # -- file-view refresh (CacheManager._materialize delegate) --------------

    def materialize(self, view: MaterializedView, entry_lock, run,
                    store, skey):
        """Serve the view's batch, refreshing first when the source
        fingerprint moved. Same contract as the plain cache path:
        pin=True holds the served batch for the enclosing query's
        pin_scope; a store rejection still serves THIS query its
        batch."""
        with entry_lock:  # single-flight, same lock the plain path uses
            batch = store.get(skey, pin=True)
            fp = source_fingerprint(view.source())
            if batch is not None and fp is not None \
                    and fp == view.fingerprint:
                metrics.note_mview("hits")
                return batch
            if batch is None or view.fingerprint is None:
                # cold or evicted-then-missed: plain materialization
                batch = run(view.plan)
                store.put(skey, batch, pin=True)
                with view.lock:
                    view.fingerprint = fp
                metrics.record("mview", phase="materialize",
                               files=len(fp or ()))
                return batch
            kind, added = ("changed", ()) if fp is None \
                else classify_delta(view.fingerprint, fp)
            if kind == "unchanged":
                return batch  # tuple-vs-map equality raced; still fresh
            fresh = self._refresh(view, batch, kind, added, run)
            store.update(skey, fresh, pin=True)
            with view.lock:
                view.fingerprint = fp
                view.refreshes += 1
            self._repopulate_serve(view, fresh)
            self._notify_invalidation(view)
            return fresh

    def _refresh(self, view: MaterializedView, cached_batch, kind: str,
                 added, run):
        """One stale-view refresh: incremental merge when legal and the
        delta is pure appends, else full recompute. The incremental
        path passes the ``mview.refresh`` fault point; transient
        faults retry, exhaustion falls back to the recompute."""
        incremental = (kind == "appended" and bool(added)
                       and view.inspection.incremental
                       and self._incremental_on())
        if not incremental:
            view.full_recomputes += 1
            metrics.note_mview("full_recomputes")
            metrics.record("mview", phase="refresh", how="full",
                           reason=kind)
            return run(view.plan)

        def merge():
            faults.inject("mview.refresh", self._conf)
            return self._merge_file_delta(view, cached_batch, added,
                                          run)

        batch, merged = self._with_retries(
            merge, fallback=lambda: run(view.plan))
        if merged:
            view.incremental_merges += 1
            metrics.note_mview("incremental_merges")
            metrics.record("mview", phase="refresh", how="incremental",
                           files=len(added))
        else:
            view.full_recomputes += 1
            metrics.note_mview("full_recomputes")
            metrics.record("mview", phase="refresh", how="fallback")
        return batch

    def _merge_file_delta(self, view: MaterializedView, cached_batch,
                          added, run):
        """Aggregate the appended files only, then re-merge the delta
        partials with the view's own cached output through the
        MergeSpec aggregate. Byte-identical to a full recompute:
        from_arrow re-sorts/dedups dictionaries, so the merged output
        is the same pure function of the total row multiset."""
        from spark_tpu.columnar.arrow import from_arrow, to_arrow

        delta_batch = run(self._delta_plan(view, added))
        old_tbl = to_arrow(cached_batch)
        delta_tbl = to_arrow(delta_batch)
        if delta_tbl.num_rows == 0:
            return cached_batch  # appended files held no rows
        union = pa.concat_tables(
            [old_tbl, delta_tbl.select(old_tbl.column_names)])
        merge_plan = view.inspection.merge_spec.merge_plan(
            L.Relation(from_arrow(union)))
        return run(merge_plan)

    def _delta_plan(self, view: MaterializedView, added
                    ) -> L.LogicalPlan:
        """The view's plan with its scan retargeted at the appended
        files only — a fresh FileSource so none of the original
        source's caches alias the delta."""
        from spark_tpu.io.datasource import FileSource

        scan = view.inspection.scan
        src = scan.source
        delta_src = FileSource(src.fmt, list(added),
                               schema=src._schema,
                               options=dict(src.options))
        new_scan = dataclasses.replace(scan, source=delta_src)

        def fn(node):
            return new_scan if node is scan else node

        return view.plan.transform_up(fn)

    # -- stream-view maintenance ---------------------------------------------

    def on_micro_batch(self, stream: str, batch_id: int,
                       delta_tbl: pa.Table) -> None:
        """Delta event from streaming/execution.py, published BEFORE
        the WAL commit: merge the micro-batch's rows into every view
        subscribed to ``stream``. Idempotent per batch id — WAL replay
        after a commit crash redelivers the same id and is dropped."""
        with self._lock:
            views = list(self._by_stream.get(stream, ()))
        for view in views:
            self._merge_stream_delta(view, batch_id, delta_tbl)

    def _merge_stream_delta(self, view: MaterializedView,
                            batch_id: int, delta_tbl: pa.Table) -> None:
        from spark_tpu.columnar.arrow import from_arrow, to_arrow
        from spark_tpu.streaming.execution import _splice

        with view.lock:
            if batch_id <= view.last_batch_id:
                metrics.note_mview("stream_dedups")
                metrics.record("mview", phase="dedup", view=view.name,
                               batch=batch_id)
                return

            def merge():
                faults.inject("mview.refresh", self._conf)
                delta_plan = _splice(
                    view.plan, L.Relation(from_arrow(delta_tbl)))
                delta_batch = self._run(delta_plan)
                if view.state is None:
                    return delta_batch
                d_tbl = to_arrow(delta_batch)
                if d_tbl.num_rows == 0:
                    return view.state
                old_tbl = to_arrow(view.state)
                union = pa.concat_tables(
                    [old_tbl, d_tbl.select(old_tbl.column_names)])
                return self._run(
                    view.inspection.merge_spec.merge_plan(
                        L.Relation(from_arrow(union))))

            # fallback=None: exhaustion re-raises, failing the batch
            # BEFORE its WAL commit — replay redelivers the delta and
            # the untouched last_batch_id accepts it
            batch, _ = self._with_retries(merge, fallback=None)
            view.state = batch
            view.last_batch_id = batch_id
            view.refreshes += 1
            view.incremental_merges += 1
            store = getattr(self._session, "memory_store", None)
            if store is not None:
                # mirror into the store for unified byte accounting;
                # the view keeps its own reference, so an eviction
                # costs bytes-visibility, never state
                store.update(("mview", view.key), batch)
            metrics.note_mview("stream_merges")
            metrics.record("mview", phase="stream_merge",
                           view=view.name, batch=batch_id,
                           rows=delta_tbl.num_rows)
            self._repopulate_serve(view, batch)
            self._notify_invalidation(view)

    def read(self, name: str):
        """The current state of stream view ``name`` as a DataFrame
        (point-in-time snapshot: a Relation over the state batch)."""
        view = self.stream_view(name)
        if view is None:
            raise KeyError(f"no stream view named {name!r}")
        with view.lock:
            state = view.state
        if state is None:
            raise ValueError(
                f"stream view {name!r} has no state yet (no "
                "micro-batch has committed)")
        from spark_tpu.api.dataframe import DataFrame

        return DataFrame(self._session, L.Relation(state))

    # -- shared plumbing ------------------------------------------------------

    def _with_retries(self, fn, fallback):
        """Run ``fn`` with bounded transient retries
        (spark.tpu.mview.refreshRetries); returns (result, True) from
        ``fn`` or (fallback(), False) after exhaustion/non-transient
        failure. ``fallback=None`` re-raises instead."""
        try:
            retries = max(0, int(self._conf.get(CF.MVIEW_REFRESH_RETRIES)))
        except Exception:
            retries = 2
        from spark_tpu import deadline

        last: Optional[BaseException] = None
        for attempt in range(retries + 1):
            try:
                return fn(), True
            except Exception as exc:
                last = exc
                if (recovery.is_transient(exc) and attempt < retries
                        and not deadline.expired()
                        and recovery.retry_allowed("mview.refresh")):
                    metrics.note_mview("refresh_retries")
                    metrics.record("mview", phase="retry",
                                   error=type(exc).__name__,
                                   attempt=attempt + 1)
                    continue
                break
        if fallback is None:
            raise last
        metrics.note_mview("refresh_fallbacks")
        metrics.record("mview", phase="fallback",
                       error=type(last).__name__)
        metrics.record("fault_recovered", point="mview.refresh",
                       how="full_recompute")
        return fallback(), False

    def _run(self, plan: L.LogicalPlan):
        """Engine for stream-view delta/merge plans — same dispatch the
        streaming runtime uses (mesh when the session has one)."""
        from spark_tpu.physical.planner import execute_logical_on

        return execute_logical_on(self._session, plan)

    def _repopulate_serve(self, view: MaterializedView, batch) -> None:
        """Push the refreshed result into the serve-tier ResultCache
        under the NEW fingerprint key, so the first post-refresh
        request hits instead of cold-missing. The bytes are exactly
        what the connect server would serialize (table_to_ipc of the
        same Arrow table), so hits stay byte-identical."""
        cache = getattr(self._session, "serve_result_cache", None)
        if cache is None or not cache.enabled():
            return
        try:
            if not bool(self._conf.get(CF.MVIEW_SERVE_REPOPULATE)):
                return
        except Exception:
            return
        try:
            from spark_tpu.columnar.arrow import to_arrow
            from spark_tpu.serve import result_cache as rc

            key = rc.plan_result_key(view.plan)
            cache.put(key, rc.table_to_ipc(to_arrow(batch)))
            metrics.note_mview("serve_repopulations")
            metrics.record("mview", phase="serve_repopulate",
                           key=rc.key_digest(key))
        except Exception as exc:  # serve repopulation is best-effort
            metrics.record("mview", phase="serve_repopulate_error",
                           error=type(exc).__name__)

    def _notify_invalidation(self, view: MaterializedView) -> None:
        """Append a versioned ``mview_refresh`` record to the session's
        fleet invalidation log the moment a refresh COMMITS: every
        subscribed replica ResultCache drops entries touching the
        view's source paths, closing the stale-serve window a TTL'd
        fingerprint probe would otherwise leave open. Only fires when
        a log already exists (fleet mode attached one) — single-replica
        serving keeps its zero-overhead path."""
        log = getattr(self._session, "serve_invalidation_log", None)
        if log is None:
            return
        try:
            scan = view.inspection.scan
            paths = getattr(getattr(scan, "source", None), "paths",
                            None) if scan is not None else None
            if paths:
                log.append("mview_refresh", paths)
        except Exception as exc:  # coherence push is best-effort;
            # the per-request fingerprint TTL still bounds staleness
            metrics.record("mview", phase="invalidate_error",
                           error=type(exc).__name__)

    def stats(self) -> dict:
        with self._lock:
            views = [v.to_dict() for v in self._views.values()]
        return {
            "views": len(views),
            "file_views": sum(1 for v in views if v["kind"] == "file"),
            "stream_views": sum(
                1 for v in views if v["kind"] == "stream"),
            "refreshes": sum(v["refreshes"] for v in views),
            "incremental_merges": sum(
                v["incremental_merges"] for v in views),
            "full_recomputes": sum(
                v["full_recomputes"] for v in views),
        }
