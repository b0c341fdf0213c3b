"""Micro-batch incremental execution (reference:
sql/core/.../execution/streaming/MicroBatchExecution.scala:41
runActivatedStream:234 constructNextBatch:475 runBatch:579, plus
IncrementalExecution.scala:43 and WatermarkTracker.scala).

Each trigger: log new source offsets to the WAL, splice the new rows
into the logical plan, run ORDINARY batch executions to (a) compute the
new rows' partial aggregates and (b) merge them with the previous state
version over a union — both of which run on whatever engine the session
uses, including the TPU mesh — then commit state + offsets. Aggregates
are incrementalized by accumulator decomposition (sum/count/min/max are
mergeable; avg = sum+count), the same partial/final split the batch
planner uses for distributed aggregation."""

from __future__ import annotations

import dataclasses
import itertools
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import pyarrow as pa

from spark_tpu import faults, metrics
from spark_tpu.expr import expressions as E
from spark_tpu.plan import logical as L
from spark_tpu.plan.incremental import AggSpec
from spark_tpu.streaming.state import OffsetLog, StateStore

_qids = itertools.count()


@dataclass(eq=False, frozen=True)
class StreamingSource(L.LogicalPlan):
    """Leaf marker for a streaming source; replaced per micro-batch by a
    Relation over the new rows (reference: StreamingExecutionRelation)."""

    source: object  # MemoryStream / RateStreamSource
    watermark_col: Optional[str] = None
    watermark_delay: int = 0  # same units as the event-time column

    @property
    def schema(self):
        return self.source.schema

    def node_string(self):
        return f"StreamingSource[{getattr(self.source, 'name', '?')}]"


def _find_source(plan: L.LogicalPlan) -> StreamingSource:
    found = L.collect_nodes(plan, StreamingSource)
    if len(found) != 1:
        raise NotImplementedError(
            f"exactly one streaming source supported, got {len(found)}")
    return found[0]


def _splice(plan: L.LogicalPlan, replacement: L.LogicalPlan):
    def fn(p):
        if isinstance(p, StreamingSource):
            return replacement
        return p

    return plan.transform_up(fn)


class StreamingQuery:
    """One running (manually or loop-triggered) streaming query
    (reference: StreamExecution + StreamingQuery)."""

    def __init__(self, session, plan: L.LogicalPlan, sink_name: str,
                 output_mode: str = "complete",
                 checkpoint_dir: Optional[str] = None):
        self._session = session
        self._plan = plan
        self.name = sink_name or f"stream{next(_qids)}"
        self.output_mode = output_mode
        self._src_node = _find_source(plan)
        self._source = self._src_node.source
        self._log = OffsetLog(checkpoint_dir)
        self._store = StateStore(checkpoint_dir)
        self._batch_id = self._log.last_committed
        self._appended: List[pa.Table] = []
        #: restored from the commit log so the watermark survives restart
        self._max_event_time: Optional[int] = self._log.last_watermark()
        self._agg, self._above, self._below = self._split_plan()
        if self._agg is not None and output_mode == "update":
            raise NotImplementedError(
                "outputMode('update') with aggregation: use 'complete' "
                "or 'append' (with a watermark)")
        if self._agg is not None and output_mode == "append":
            wm_col = self._src_node.watermark_col
            has_time_key = wm_col is not None and any(
                wm_col in g.references() for g in self._agg.groupings)
            if not has_time_key:
                raise NotImplementedError(
                    "append-mode streaming aggregation requires a "
                    "watermark and an event-time grouping key "
                    "(reference: UnsupportedOperationChecker)")
        self._register_sink()
        self.is_active = True

    # -- plan surgery ---------------------------------------------------------

    def _split_plan(self):
        """Locate the (single) streaming Aggregate: returns
        (spec_or_None, nodes-above builder, child-subtree-below)."""
        aggs = L.collect_nodes(self._plan, L.Aggregate)
        if not aggs:
            return None, None, None
        if len(aggs) > 1:
            raise NotImplementedError(
                "multiple aggregations in one streaming query")
        agg = aggs[0]
        if agg is not self._plan:
            # operators above the aggregate (filter/select/sort on the
            # result) are not incrementalized yet; refusing beats
            # silently dropping them
            raise NotImplementedError(
                "operators above a streaming aggregation are not "
                "supported; aggregate must be the query root")
        return AggSpec(agg.groupings, agg.aggregates), agg, agg.child

    # -- execution ------------------------------------------------------------

    def _run(self, plan: L.LogicalPlan):
        from spark_tpu.physical.planner import execute_logical_on

        return execute_logical_on(self._session, plan)

    def _to_arrow(self, plan: L.LogicalPlan) -> pa.Table:
        from spark_tpu.columnar.arrow import to_arrow

        return to_arrow(self._run(plan))

    def process_all_available(self) -> None:
        """Drain the source (Trigger.AvailableNow analogue)."""
        while True:
            latest = self._source.latest_offset()
            batch_id = self._batch_id + 1
            logged = self._log.offsets_for(batch_id)
            if logged is not None:
                # offsets were WAL'd but the batch never committed — a
                # crash between log_offsets and commit: replay the exact
                # same range (exactly-once restart)
                start, end = logged["start"], logged["end"]
                metrics.record("fault_recovered", point="streaming.commit",
                               how="wal_replay", batch=batch_id)
            else:
                prev = self._log.offsets_for(self._batch_id)
                start = prev["end"] if prev else 0
                end = latest
                if end <= start:
                    return
                self._log.log_offsets(batch_id, {"start": start,
                                                 "end": end})
            self._run_batch(batch_id, start, end)

    processAllAvailable = process_all_available

    def _publish_delta(self, batch_id: int, new_rows) -> None:
        """Hand the micro-batch's (late-filtered) rows to the
        materialized-view manager BEFORE the WAL commit: a crash
        between publish and commit replays the same batch id, which
        the manager's batch-id watermark drops — subscribed views
        never double-merge and never miss a committed batch. A view
        merge that fails past its retries propagates from here, so
        the batch stays uncommitted and replay redelivers it."""
        mgr = getattr(self._session, "mview_manager", None)
        if mgr is not None:
            mgr.on_micro_batch(self.name, batch_id, new_rows)

    def _run_batch(self, batch_id: int, start: int, end: int) -> None:
        from spark_tpu.columnar.arrow import from_arrow

        new_rows = self._source.get_batch(start, end)
        wm_col = self._src_node.watermark_col
        wm_before = self._watermark()
        if wm_col is not None and wm_before is not None \
                and new_rows.num_rows > 0 \
                and wm_col in new_rows.column_names:
            # rows older than the watermark are LATE and dropped before
            # any state update (reference: EventTimeWatermark filter) —
            # otherwise an already-emitted window could re-open
            import pyarrow.compute as pc

            new_rows = new_rows.filter(
                pc.greater_equal(new_rows.column(wm_col),
                                 pa.scalar(wm_before)))
        rel = L.Relation(from_arrow(new_rows))

        if self._agg is None:
            out = self._to_arrow(_splice(self._plan, rel))
            self._publish_delta(batch_id, new_rows)
            faults.inject("streaming.commit", self._session.conf)
            self._store.commit(batch_id, pa.table({}))
            self._log.commit(batch_id)
            # output is appended only AFTER the commit so a commit
            # crash + WAL replay cannot duplicate sink rows
            self._appended.append(out)
            self._batch_id = batch_id
            self._register_sink()
            return

        spec = self._agg
        batch_child = _splice(self._below, rel)
        key_aliases = tuple(E.Alias(g, n) for g, n
                            in zip(spec.groupings_exec, spec.key_names))
        partial_outs = key_aliases + tuple(spec.partials)
        if spec.session_idx is not None:
            # provisional session end = max(event) + gap per provisional
            # session key (which IS the event time, so end = key + gap)
            ev = spec.groupings[spec.session_idx].child
            partial_outs = partial_outs + (E.Alias(
                E.Max(E.Arith("+", ev, E.Literal(spec.session_gap))),
                "__send"),)
        partial = L.Aggregate(
            tuple(spec.groupings_exec), partial_outs, batch_child)
        partial_tbl = self._to_arrow(partial)

        prev = self._store.get(self._batch_id)
        if prev is not None and prev.num_rows > 0:
            merged_in = pa.concat_tables(
                [prev, partial_tbl.select(prev.column_names)])
        else:
            merged_in = partial_tbl
        mrel = L.Relation(from_arrow(merged_in))
        keys = tuple(E.Col(n) for n in spec.key_names)
        merge_outs = tuple(E.Alias(E.Col(n), n)
                           for n in spec.key_names) + tuple(spec.merges)
        if spec.session_idx is not None:
            merge_outs = merge_outs + (E.Alias(
                E.Max(E.Col("__send")), "__send"),)
        merged = L.Aggregate(keys, merge_outs, mrel)
        state_tbl = self._to_arrow(merged)
        if spec.session_idx is not None and state_tbl.num_rows > 0:
            state_tbl = self._merge_sessions(state_tbl)

        # watermark: track max event time from the new rows
        emitted: Optional[pa.Table] = None
        if wm_col is not None and new_rows.num_rows > 0 \
                and wm_col in new_rows.column_names:
            mx = pa.compute.max(new_rows.column(wm_col)).as_py()
            mx = int(mx) if mx is not None else None
            if mx is not None:
                if self._max_event_time is None \
                        or mx > self._max_event_time:
                    self._max_event_time = mx
        if self.output_mode == "append":
            state_tbl, emitted = self._evict_closed(state_tbl)

        self._publish_delta(batch_id, new_rows)
        faults.inject("streaming.commit", self._session.conf)
        self._store.commit(batch_id, state_tbl)
        self._log.commit(batch_id, watermark=self._max_event_time)
        self._batch_id = batch_id
        if emitted is not None and emitted.num_rows > 0:
            self._appended.append(self._finalize(emitted))
        self._register_sink()

    def _merge_sessions(self, state_tbl: pa.Table) -> pa.Table:
        """Merge overlapping/adjacent provisional sessions per key
        (reference: MergingSessionsExec): sort by (keys, start), a
        session chains onto the previous while start <= running max end,
        then the chained groups re-aggregate through the SAME merge
        accumulators with start=min(start), end=max(end)."""
        from spark_tpu.columnar.arrow import from_arrow

        spec = self._agg
        skey = spec.key_names[spec.session_idx]
        other = [n for i, n in enumerate(spec.key_names)
                 if i != spec.session_idx]
        df = state_tbl.to_pandas()
        df = df.sort_values(other + [skey], kind="mergesort",
                            na_position="first").reset_index(drop=True)
        if other:
            grp = df.groupby(other, dropna=False, sort=False)
            prev_end = grp["__send"].cummax().shift(1)
            new_key = grp.cumcount() == 0
        else:
            prev_end = df["__send"].cummax().shift(1)
            new_key = df.index == 0
        head = new_key | (df[skey] > prev_end)
        df["__sid"] = head.cumsum()
        rel = L.Relation(from_arrow(pa.Table.from_pandas(
            df, preserve_index=False)))
        keys2 = tuple(E.Col(n) for n in other) + (E.Col("__sid"),)
        outs = (tuple(E.Alias(E.Col(n), n) for n in other)
                + (E.Alias(E.Min(E.Col(skey)), skey),)
                + tuple(spec.merges)
                + (E.Alias(E.Max(E.Col("__send")), "__send"),))
        merged = L.Aggregate(keys2, outs, rel)
        out = self._to_arrow(merged)
        # restore the state column order (concat in the next batch
        # selects by prev.column_names)
        return out.select(state_tbl.column_names)

    def _watermark(self) -> Optional[int]:
        if self._max_event_time is None:
            return None
        return self._max_event_time - self._src_node.watermark_delay

    def _evict_closed(self, state: pa.Table):
        """Append mode: groups whose event-time key is entirely below the
        watermark can never change — emit and drop them (reference:
        statefulOperators.scala StateStoreSaveExec append mode)."""
        wm = self._watermark()
        if wm is None or state.num_rows == 0:
            return state, None
        spec = self._agg
        # the event-time grouping is the key referencing the wm column
        idx = None
        for i, g in enumerate(spec.groupings):
            if self._src_node.watermark_col in g.references():
                idx = i
                break
        if idx is None:
            return state, None
        import pyarrow.compute as pc

        if spec.session_idx is not None:
            # a session closes when the watermark passes its END
            closed = pc.less_equal(state.column("__send"),
                                   pa.scalar(wm))
            return state.filter(pc.invert(closed)), state.filter(closed)
        key = state.column(spec.key_names[idx])
        width = spec.window_widths[idx]
        if width is not None:
            # a window [start, start+width) closes when the watermark
            # passes its END
            closed = pc.less_equal(pc.add(key, pa.scalar(width)),
                                   pa.scalar(wm))
        else:
            closed = pc.less(key, pa.scalar(wm))
        return state.filter(pc.invert(closed)), state.filter(closed)

    def _finalize(self, state_tbl: pa.Table) -> pa.Table:
        from spark_tpu.columnar.arrow import from_arrow

        spec = self._agg
        out = L.Project(tuple(spec.outputs), L.Relation(
            from_arrow(state_tbl)))
        return self._to_arrow(out)

    # -- sink -----------------------------------------------------------------

    def _current_result(self) -> pa.Table:
        if self._agg is None or self.output_mode == "append":
            if self._appended:
                return pa.concat_tables(self._appended)
            # empty table with the right schema
            state = self._store.get(self._batch_id)
            if self._agg is not None and state is not None:
                return self._finalize(state.slice(0, 0))
            return pa.table({})
        state = self._store.get(self._batch_id)
        if state is None or state.num_rows == 0:
            return pa.table({})
        return self._finalize(state)

    def _register_sink(self) -> None:
        """Memory sink: results queryable as a temp view (reference:
        memory.scala MemorySink + CreateViewCommand)."""
        from spark_tpu.columnar.arrow import from_arrow

        tbl = self._current_result()
        if tbl.num_columns == 0:
            return
        self._session.catalog._register_view(
            self.name, L.Relation(from_arrow(tbl)))

    def stop(self) -> None:
        self.is_active = False
