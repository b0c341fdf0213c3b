"""Stream-stream joins (reference:
sql/core/.../streaming/StreamingSymmetricHashJoinExec.scala — symmetric
hash join with per-side watermark-bounded state;
UnsupportedOperationChecker for the mode/type matrix).

Micro-batch formulation over the batch engine: keep every row seen so
far per side (watermark-trimmed), and per trigger emit

    new_left  JOIN (right_state UNION new_right)
    UNION  left_state JOIN new_right

which covers old x new, new x old and new x new exactly once. The joins
themselves are ordinary batch L.Join executions, so they run fused on
whatever engine the session uses (single chip or mesh). State is one
arrow table per side per committed version, snapshotted like streaming
aggregation state (state.py); the global watermark is the MIN of the
per-side watermarks (matching the reference's WatermarkTracker policy
for multi-source queries), and rows below it leave the state — bounding
memory exactly as the reference's state eviction does.

Supported: INNER, LEFT OUTER, RIGHT OUTER and FULL OUTER equi-joins in
append mode, with an optional extra condition (preserved sides track
matched bits and emit null-padded rows when their state evicts past the
watermark — tests/test_stream_join.py; full outer tracks BOTH sides
symmetrically and requires watermarks on both)."""

from __future__ import annotations

import itertools
from typing import List, Optional, Tuple

import pyarrow as pa

from spark_tpu.plan import logical as L
from spark_tpu.streaming.execution import StreamingSource, _splice
from spark_tpu.streaming.state import OffsetLog, StateStore

_qids = itertools.count()


def find_streaming_join(plan: L.LogicalPlan) -> Optional[L.Join]:
    """The Join of a two-source streaming query, or None. Stateless
    operators (Project/Filter/alias — e.g. the column-ordering Project
    the USING-join API inserts) may sit above the join; they re-run per
    emitted micro-batch."""
    sources = L.collect_nodes(plan, StreamingSource)
    if len(sources) != 2:
        return None
    node = plan
    while isinstance(node, (L.Project, L.Filter, L.SubqueryAlias)):
        node = node.children()[0]
    if not isinstance(node, L.Join):
        raise NotImplementedError(
            "stream-stream join supports only stateless operators "
            "(project/filter) above the join")
    left_srcs = L.collect_nodes(node.left, StreamingSource)
    right_srcs = L.collect_nodes(node.right, StreamingSource)
    if len(left_srcs) != 1 or len(right_srcs) != 1:
        raise NotImplementedError(
            "each join side must read exactly one streaming source")
    return node


class StreamStreamJoinQuery:
    """Runner for a two-source streaming join (API-compatible subset of
    StreamingQuery: processAllAvailable / stop / is_active / name)."""

    def __init__(self, session, root: L.LogicalPlan, plan: L.Join,
                 sink_name: Optional[str],
                 output_mode: str = "append",
                 checkpoint_dir: Optional[str] = None):
        self._root = root
        if plan.how == "right":
            # right outer = sides swapped left outer (the operators
            # above — always a Project for USING joins — are reapplied
            # per batch and restore column order/selection)
            from spark_tpu.expr import expressions as E

            lnames = set(plan.left.schema.names)
            rnames = set(plan.right.schema.names)
            if lnames & rnames and (root is plan
                                    or plan.condition is not None):
                raise NotImplementedError(
                    "right outer stream join with colliding column "
                    "names and no projection above: '#2' dedup names "
                    "shift under the side swap")
            orig = plan
            orig_names = plan.schema.names
            plan = L.Join(plan.right, plan.left, "left",
                          plan.right_keys, plan.left_keys,
                          plan.condition)
            if root is orig:
                # bare-root: restore the right-join column order
                self._root = L.Project(
                    tuple(E.Col(n) for n in orig_names), plan)
        if plan.how not in ("inner", "left", "full"):
            raise NotImplementedError(
                f"stream-stream {plan.how} join: inner, left, right and "
                "full outer are supported")
        if plan.how in ("left", "full"):
            left_src = L.collect_nodes(plan.left, StreamingSource)[0]
            if left_src.watermark_col is None:
                raise NotImplementedError(
                    "outer stream-stream joins require a watermark "
                    "on the preserved side: null-padded results emit "
                    "when the watermark proves no match can arrive "
                    "(reference: StreamingSymmetricHashJoinExec "
                    "outer-join condition)")
        if plan.how == "full":
            right_src = L.collect_nodes(plan.right, StreamingSource)[0]
            if right_src.watermark_col is None:
                raise NotImplementedError(
                    "full outer stream-stream join requires watermarks "
                    "on BOTH sides (symmetric matched-bit eviction)")
        if output_mode not in ("append", "update"):
            raise NotImplementedError(
                "stream-stream joins support append mode only "
                "(reference: UnsupportedOperationChecker)")
        if not plan.left_keys:
            raise NotImplementedError(
                "stream-stream join requires equi-join keys (unbounded "
                "cross state otherwise)")
        self._session = session
        self._join = plan
        self.name = sink_name or f"stream{next(_qids)}"
        self._sides = (L.collect_nodes(plan.left, StreamingSource)[0],
                       L.collect_nodes(plan.right, StreamingSource)[0])
        self._subtrees = (plan.left, plan.right)
        preserved = {0: plan.how in ("left", "full"),
                     1: plan.how == "full"}
        for i in (0, 1):
            wc = self._sides[i].watermark_col
            if preserved[i] and wc is not None \
                    and wc not in self._subtrees[i].schema.names:
                raise NotImplementedError(
                    "outer stream-stream join: the preserved side's "
                    f"watermark column {wc!r} must survive to the join "
                    "(state eviction reads it — drop it above the join "
                    "instead)")
        self._log = OffsetLog(checkpoint_dir)
        self._store = StateStore(checkpoint_dir)
        self._batch_id = self._log.last_committed
        self._appended: List[pa.Table] = []
        wm = self._log.last_watermark()
        # per-side max event time persisted as a pair in the commit log
        self._max_event: List[Optional[int]] = list(wm) if \
            isinstance(wm, (list, tuple)) else [None, None]
        self.is_active = True
        self._register_sink()

    # -- engine plumbing ------------------------------------------------------

    def _to_arrow(self, plan: L.LogicalPlan) -> pa.Table:
        from spark_tpu.columnar.arrow import to_arrow
        from spark_tpu.physical.planner import execute_logical_on

        return to_arrow(execute_logical_on(self._session, plan))

    def _side_rows(self, side: int, start: int, end: int) -> pa.Table:
        """New source rows pushed through the side's subtree
        (projections/filters between source and join). Event-time maxima
        are tracked on the RAW rows — a projection may drop the
        watermark column before the join, but the watermark still
        advances (reference: EventTimeWatermarkExec sits at the
        source, not at the join)."""
        from spark_tpu.columnar.arrow import from_arrow

        src = self._sides[side]
        raw = src.source.get_batch(start, end)
        wm_col = src.watermark_col
        if wm_col and raw.num_rows > 0 and wm_col in raw.column_names:
            import pyarrow.compute as pc

            mx = pc.max(raw.column(wm_col)).as_py()
            if mx is not None:
                mx = int(mx)
                if self._max_event[side] is None \
                        or mx > self._max_event[side]:
                    self._max_event[side] = mx
        subtree = self._subtrees[side]
        if isinstance(subtree, StreamingSource):
            return raw
        return self._to_arrow(_splice(subtree, L.Relation(from_arrow(raw))))

    # -- trigger loop ---------------------------------------------------------

    def process_all_available(self) -> None:
        while True:
            batch_id = self._batch_id + 1
            logged = self._log.offsets_for(batch_id)
            if logged is not None:
                starts, ends = logged["start"], logged["end"]
            else:
                prev = self._log.offsets_for(self._batch_id)
                starts = prev["end"] if prev else [0, 0]
                ends = [self._sides[0].source.latest_offset(),
                        self._sides[1].source.latest_offset()]
                if ends[0] <= starts[0] and ends[1] <= starts[1]:
                    return
                self._log.log_offsets(batch_id,
                                      {"start": starts, "end": ends})
            self._run_batch(batch_id, starts, ends)

    processAllAvailable = process_all_available

    def _run_batch(self, batch_id: int, starts, ends) -> None:
        import pyarrow.compute as pc

        new = [self._side_rows(i, starts[i], ends[i]) for i in (0, 1)]
        state = self._load_state(self._batch_id)
        # which sides are PRESERVED (emit null-padded when unmatched):
        # left outer tracks side 0; full outer tracks both (reference:
        # SymmetricHashJoinStateManager KeyWithIndexToValue bookkeeping)
        track = {0: self._join.how in ("left", "full"),
                 1: self._join.how == "full"}
        tag = {0: "__lid", 1: "__rid"}
        flag = {0: "__matched", 1: "__matched_r"}
        for i in (0, 1):
            if track[i]:
                n = new[i].num_rows
                tagged = new[i].append_column(tag[i], pa.array(
                    [(batch_id << 32) + j for j in range(n)], pa.int64()))
                new[i] = tagged.append_column(
                    flag[i], pa.array([False] * n, pa.bool_()))

        out_parts = []
        matched: dict = {0: set(), 1: set()}
        right_all = pa.concat_tables([state[1], new[1]]) \
            if state[1].num_rows else new[1]
        joinables = []
        if new[0].num_rows and right_all.num_rows:
            joinables.append((new[0], right_all))
        if state[0].num_rows and new[1].num_rows:
            joinables.append((state[0], new[1]))
        for lt, rt in joinables:
            if track[0]:
                lt = lt.drop_columns([flag[0]])
            if track[1]:
                rt = rt.drop_columns([flag[1]])
            joined = self._join_tables(lt, rt)
            for i in (0, 1):
                if track[i]:
                    matched[i] |= set(
                        joined.column(tag[i]).to_pylist())
                    joined = joined.drop_columns([tag[i]])
            out_parts.append(joined)
        out_parts = [self._apply_above(t) for t in out_parts]

        # grow state; flip matched bits
        new_state = [
            pa.concat_tables([state[i], new[i]])
            if state[i].num_rows else new[i]
            for i in (0, 1)
        ]
        for i in (0, 1):
            if track[i] and matched[i] and new_state[i].num_rows:
                ids = new_state[i].column(tag[i]).to_pylist()
                flags = new_state[i].column(flag[i]).to_pylist()
                flags = [f or (x in matched[i])
                         for f, x in zip(flags, ids)]
                idx = new_state[i].schema.get_field_index(flag[i])
                new_state[i] = new_state[i].set_column(
                    idx, flag[i], pa.array(flags, pa.bool_()))

        # watermark-trim state; evicted unmatched preserved-side rows
        # emit null-padded (this is WHEN outer results appear — the
        # watermark proves no future row can match them)
        wm = self._watermark()
        if wm is not None:
            for i in (0, 1):
                wm_col = self._sides[i].watermark_col
                if wm_col and new_state[i].num_rows > 0 \
                        and wm_col in new_state[i].column_names:
                    keep = pc.greater_equal(
                        new_state[i].column(wm_col), pa.scalar(wm))
                    if track[i]:
                        evicted = new_state[i].filter(pc.invert(keep))
                        unmatched = evicted.filter(
                            pc.invert(evicted.column(flag[i])))
                        if unmatched.num_rows:
                            out_parts.append(self._apply_above(
                                self._null_padded(unmatched, side=i)))
                    new_state[i] = new_state[i].filter(keep)

        self._commit_state(batch_id, new_state)
        self._log.commit(batch_id, watermark=self._max_event)
        self._batch_id = batch_id
        for t in out_parts:
            if t.num_rows:
                self._appended.append(t)
        self._register_sink()

    def _null_padded(self, rows: pa.Table, side: int = 0) -> pa.Table:
        """Unmatched preserved-side rows shaped like the join output:
        that side's columns + all-null columns for the other side."""
        from spark_tpu.io.datasource import _pa_schema_from_schema

        clean = rows.drop_columns(
            [c for c in ("__lid", "__matched", "__rid", "__matched_r")
             if c in rows.column_names])
        n = clean.num_rows
        out_schema = _pa_schema_from_schema(self._join.schema)
        # join output = left fields then right fields (dedup-renamed);
        # map this side's columns positionally into its region
        ln = len(self._subtrees[0].schema.names)
        arrays = []
        for pos, f in enumerate(out_schema):
            src = None
            if side == 0 and pos < ln:
                src = self._subtrees[0].schema.names[pos]
            elif side == 1 and pos >= ln:
                src = self._subtrees[1].schema.names[pos - ln]
            if src is not None and src in clean.column_names:
                arrays.append(clean.column(src).cast(f.type))
            else:
                arrays.append(pa.nulls(n, f.type))
        return pa.Table.from_arrays(arrays, schema=out_schema)

    def _watermark(self) -> Optional[int]:
        """MIN of per-side watermarks (a row may still find matches from
        the slower side, so the faster side cannot evict past it)."""
        wms = []
        for i in (0, 1):
            if self._sides[i].watermark_col is not None:
                if self._max_event[i] is None:
                    return None
                wms.append(self._max_event[i]
                           - self._sides[i].watermark_delay)
        return min(wms) if wms else None

    def _join_tables(self, left: pa.Table, right: pa.Table) -> pa.Table:
        from spark_tpu.columnar.arrow import from_arrow

        j = L.Join(L.Relation(from_arrow(left)),
                   L.Relation(from_arrow(right)),
                   "inner", self._join.left_keys, self._join.right_keys,
                   self._join.condition)
        return self._to_arrow(j)

    def _apply_above(self, joined: pa.Table) -> pa.Table:
        """Re-run the stateless operators above the join (the USING
        Project, post-join filters) on one emitted batch."""
        if self._root is self._join:
            return joined
        from spark_tpu.columnar.arrow import from_arrow

        rel = L.Relation(from_arrow(joined))

        # transform_up rebuilds ancestors, so identity match fails; the
        # tree contains exactly ONE Join (find_streaming_join contract)
        def fn(p):
            return rel if isinstance(p, L.Join) else p

        return self._to_arrow(self._root.transform_up(fn))

    # -- state layout: one table per side, tagged columns -----------------------

    def _load_state(self, version: int) -> Tuple[pa.Table, pa.Table]:
        empty = (self._empty_side(0), self._empty_side(1))
        tbl = self._store.get(version)
        if tbl is None or tbl.num_rows == 0 or "__side" not in \
                tbl.column_names:
            return empty
        import pyarrow.compute as pc

        out = []
        for i in (0, 1):
            part = tbl.filter(pc.equal(tbl.column("__side"), i))
            names = [n for n in part.column_names
                     if n.startswith(f"s{i}_")]
            side = pa.table({n[3:]: part.column(n) for n in names})
            out.append(side)
        return tuple(out)  # type: ignore[return-value]

    def _empty_side(self, i: int) -> pa.Table:
        from spark_tpu.io.datasource import _pa_schema_from_schema

        schema = _pa_schema_from_schema(self._subtrees[i].schema)
        return pa.Table.from_arrays(
            [pa.array([], f.type) for f in schema], schema=schema)

    def _commit_state(self, version: int,
                      sides: List[pa.Table]) -> None:
        """Pack both sides into one table (prefixed columns + __side
        tag) so the existing versioned snapshot machinery applies."""
        parts = []
        for i, side in enumerate(sides):
            n = side.num_rows
            cols = {"__side": pa.array([i] * n, pa.int8())}
            for j, name in enumerate(side.column_names):
                cols[f"s{i}_{name}"] = side.column(name)
            parts.append(cols)
        # union of columns with nulls on the other side
        all_names: List[str] = ["__side"]
        for i, side in enumerate(sides):
            all_names += [f"s{i}_{n}" for n in side.column_names]
        arrays = {}
        for name in all_names:
            chunks = []
            for i, cols in enumerate(parts):
                n = sides[i].num_rows
                if name in cols:
                    chunks.append(cols[name])
                else:
                    typ = None
                    for c2 in parts:
                        if name in c2:
                            a = c2[name]
                            typ = a.type if isinstance(a, pa.Array) \
                                else a.chunk(0).type if a.num_chunks \
                                else pa.null()
                            break
                    chunks.append(pa.nulls(n, typ or pa.null()))
            arrays[name] = pa.concat_arrays(
                [c.combine_chunks() if isinstance(c, pa.ChunkedArray)
                 else c for c in chunks])
        self._store.commit(version, pa.table(arrays))

    # -- sink -----------------------------------------------------------------

    def _current_result(self) -> pa.Table:
        if self._appended:
            return pa.concat_tables(self._appended)
        return pa.Table.from_arrays(
            [pa.array([], f.type) for f in self._result_schema()],
            schema=self._result_schema())

    def _result_schema(self) -> pa.Schema:
        from spark_tpu.io.datasource import _pa_schema_from_schema

        return _pa_schema_from_schema(self._root.schema)

    def _register_sink(self) -> None:
        from spark_tpu.columnar.arrow import from_arrow

        tbl = self._current_result()
        if tbl.num_columns == 0:
            return
        self._session.catalog._register_view(
            self.name, L.Relation(from_arrow(tbl)))

    def stop(self) -> None:
        self.is_active = False
