"""Out-of-HBM execution: chunked scans through aggregation, joins, and
top-k, plus grace-hash partitioned joins when BOTH sides exceed HBM.

A v5e chip holds ~16 GB of HBM; TPC-H SF100 lineitem alone is ~80 GB.
When a plan's scan would exceed the device budget
(spark.tpu.maxDeviceBatchBytes), the plan is NOT materialized. Three
tiers, all built on the same merge-state decomposition streaming uses
(plan/incremental.AggSpec):

1. **Streamed aggregation** (`_ChunkedAgg`, sidecars=[]): the parquet
   dataset streams through host RAM in bounded chunks; each chunk's
   PARTIAL aggregates run on device; partials merge device-side.

2. **Streamed join tree** (`_ChunkedAgg` with sidecars): one big scan
   joined against sub-budget subplans. The small join inputs
   ("sidecars") pre-materialize ONCE to device-resident Relations; big
   chunks then flow through the ORIGINAL join tree per chunk. Sound
   because each big-side row contributes to the join output
   independently when the big side is on a preserved streamed side
   (inner/cross either side; left/semi/anti left; right right) — the
   union of per-chunk join outputs IS the join output. Join-key
   membership filters from the sidecars are applied host-side to each
   chunk before it is shipped (exact semi filter below
   spark.tpu.semiFilterExactMax keys, Bloom above it — the runtime-
   filter/Bloom pushdown of InjectRuntimeFilter.scala:36, done where it
   actually pays: the host->device transfer), and the key's min/max range
   is pushed into the parquet scan for row-group pruning.

3. **Hybrid hash join** (`_HybridHashJoinAgg`, default;
   `spark.tpu.join.hybrid.*`): both sides over budget. A planned
   single pass at ANY memory level — build staging requests a grant
   from the unified memory manager, partitions spill to host files
   beyond the granted bytes (growing from the free span first),
   overflowing buckets recursively repartition, and the final result
   is byte-identical to the static tier below. The static
   **grace-hash join** (`_GraceHashAgg`) survives as the
   hybrid-disabled path and the fallback rung when a spill seam fails
   unrecoverably: both scans hash-partition by join key into P
   host-RAM bucket sets (one streaming pass each); each bucket pair
   then joins on device as an ordinary sub-budget plan. Every key
   lands in exactly one bucket, so inner/outer/semi semantics all
   hold bucket-locally.

plus **streamed top-k** (`_ChunkedTopK`): Limit(Sort(big scan)) keeps a
running device top-(n+offset) merged per chunk.

Reference analogue: ExternalSorter.scala:93 spill-merge,
SortMergeJoinExec.scala:39 + ShuffledHashJoinExec (grace hash is the
spill-tier shape of its build), TungstenAggregationIterator.scala:82
sort-merge fallback — except the reference spills mid-operator, while
here the operator is re-planned as a merge over chunk partials (the
map-side-combine shape of AggUtils).

All three tiers stream through the asynchronous chunk pipeline
(physical/pipeline.py, ``spark.tpu.pipelineDepth``): a background
producer decodes, host-filters, and ships the next chunks while the
device merges the previous partials — chunks are always consumed in
source order, so results are byte-identical at every depth.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from spark_tpu import conf as CF
from spark_tpu.expr import expressions as E
from spark_tpu.plan import logical as L
from spark_tpu.plan.incremental import AggSpec

MAX_DEVICE_BATCH_BYTES = CF.register(
    "spark.tpu.maxDeviceBatchBytes", 5 << 30,
    "Scans whose materialized size would exceed this execute in bounded "
    "host-RAM chunks with device-side partial aggregation (out-of-HBM "
    "execution). Default assumes a 16 GB-HBM chip and ~3x working-set "
    "multiplier for sort/gather intermediates over the scan itself; "
    "chunking a resident-sized scan costs ~100x (measured SF10 q1: "
    "133 s chunked vs 0.16 s resident), so do not set this timidly.",
    int)

CHUNK_ROWS = CF.register(
    "spark.tpu.chunkRows", 1 << 21,
    "Rows per device chunk for out-of-HBM execution.", int)

SEMI_FILTER_EXACT_MAX = CF.register(
    "spark.tpu.semiFilterExactMax", 64 << 20,
    "Chunked joins filter big-side chunks host-side by join-key "
    "membership in the materialized small side. Up to this many distinct "
    "keys the filter is EXACT (sorted array + searchsorted); above it a "
    "Bloom bitset is used instead (false positives only cost transfer). "
    "0 disables the host-side filter.", int)

GRACE_PARTITIONS_MAX = CF.register(
    "spark.tpu.gracePartitionsMax", 256,
    "Upper bound on grace-hash join partition count.", int)

JOIN_HYBRID_ENABLED = CF.register(
    "spark.tpu.join.hybrid.enabled", True,
    "Route both-sides-over-budget joins through the grant-driven "
    "dynamic hybrid hash join (_HybridHashJoinAgg): build staging is "
    "sized to bytes actually GRANTED by the unified memory manager, "
    "overflow partitions spill to host files as a planned single pass, "
    "and overflowing buckets recursively repartition instead of "
    "relying on the OOM degradation ladder. Off = the static grace-"
    "hash join (which also remains the fallback rung when a hybrid "
    "spill seam fails unrecoverably).", bool)

JOIN_HYBRID_PARTITIONS_MAX = CF.register(
    "spark.tpu.join.hybrid.partitionsMax", 256,
    "Upper bound on the hybrid hash join's top-level partition count. "
    "Buckets that still exceed the device budget (skew, or a cap this "
    "low) recursively repartition with a per-level hash salt.", int)

JOIN_HYBRID_SPILL_RETRIES = CF.register(
    "spark.tpu.join.hybrid.spillRetryAttempts", 2,
    "Bounded retries for one hybrid-join spill operation (spill-file "
    "write, spill-file read-back, recursive repartition) on a "
    "transient/deadline failure before the join falls back one rung "
    "to the static grace-hash join recomputed from source.", int)

JOIN_HYBRID_GROW_WHEN_IDLE = CF.register(
    "spark.tpu.join.hybrid.growWhenIdle", True,
    "Let the hybrid hash join grow its resident set mid-pass from the "
    "unified memory manager's FREE span (never by evicting storage) "
    "before demoting a partition to a host spill file.", bool)

# join types through which a big LEFT / RIGHT child may stream
_STREAM_LEFT = ("inner", "cross", "left", "left_semi", "left_anti")
_STREAM_RIGHT = ("inner", "cross", "right")
# join types where non-matching streamed rows can be DROPPED host-side
_FILTER_LEFT = ("inner", "left_semi")
_FILTER_RIGHT = ("inner",)


def _schema_width(schema) -> int:
    """Bytes per row of the scan's (column-pruned) schema."""
    from spark_tpu.expr.compiler import _jnp_dtype

    width = 0
    for f in schema.fields:
        try:
            width += np.dtype(_jnp_dtype(f.dtype)).itemsize
        except Exception:
            width += 8
        if f.nullable:
            width += 1
    return width


def _est_scan(scan: L.UnresolvedScan) -> Optional[int]:
    try:
        rows = scan.source.count_rows(scan.filters)
    except Exception:
        return None
    return rows * _schema_width(scan.schema)


def _contains(plan: L.LogicalPlan, target: L.LogicalPlan) -> bool:
    if plan is target:
        return True
    return any(_contains(c, target) for c in plan.children())


def _peel_above(plan: L.LogicalPlan):
    above: List[L.LogicalPlan] = []
    node = plan
    while isinstance(node, (L.Project, L.Sort, L.Limit, L.Filter)) \
            and not isinstance(node, L.Aggregate):
        above.append(node)
        node = node.children()[0]
    return above, node


@dataclasses.dataclass
class _PathJoin:
    join: L.Join
    big_on_left: bool

    @property
    def sidecar(self) -> L.LogicalPlan:
        return self.join.right if self.big_on_left else self.join.left

    @property
    def big_keys(self) -> Tuple[E.Expression, ...]:
        return self.join.left_keys if self.big_on_left \
            else self.join.right_keys

    @property
    def sidecar_keys(self) -> Tuple[E.Expression, ...]:
        return self.join.right_keys if self.big_on_left \
            else self.join.left_keys

    @property
    def can_filter(self) -> bool:
        how = self.join.how
        return how in (_FILTER_LEFT if self.big_on_left else _FILTER_RIGHT)


def _stream_path(root: L.LogicalPlan,
                 big: L.UnresolvedScan) -> Optional[List[_PathJoin]]:
    """Validate that every node between ``root`` and the big scan is
    either per-row (Filter/Project/SubqueryAlias) or a join the big side
    may stream through; return the joins on the path (outermost first),
    or None when the shape is inadmissible."""
    out: List[_PathJoin] = []
    node = root
    while node is not big:
        if isinstance(node, (L.Filter, L.Project, L.SubqueryAlias)):
            node = node.children()[0]
            continue
        if isinstance(node, L.Join):
            in_left = _contains(node.left, big)
            in_right = _contains(node.right, big)
            if in_left == in_right:  # both (self-join) or neither
                return None
            how = node.how
            if in_left and how in _STREAM_LEFT:
                out.append(_PathJoin(node, True))
                node = node.left
                continue
            if in_right and how in _STREAM_RIGHT:
                out.append(_PathJoin(node, False))
                node = node.right
                continue
            return None
        return None
    return out


def _resolve_to_scan_col(expr: E.Expression, root: L.LogicalPlan,
                         big: L.UnresolvedScan) -> Optional[str]:
    """Trace a join-key expression from ``root``'s output schema down
    the streamed path to a direct column of the big scan (through
    Project aliases and join-output renames); None when it is computed
    or lands outside the scan."""
    expr = E.strip_alias(expr)
    node = root
    while node is not big:
        if not isinstance(expr, E.Col):
            return None
        name = expr.col_name
        if isinstance(node, (L.Filter, L.SubqueryAlias)):
            node = node.children()[0]
            continue
        if isinstance(node, L.Project):
            for e in node.exprs:
                if isinstance(e, E.Alias) and e.alias_name == name:
                    expr = E.strip_alias(e.child)
                    break
                if isinstance(e, E.Col) and e.col_name == name:
                    break
            else:
                return None
            node = node.children()[0]
            continue
        if isinstance(node, L.Join):
            big_on_left = _contains(node.left, big)
            out_names = list(node.schema.names)
            if name not in out_names:
                return None
            pos = out_names.index(name)
            ln = list(node.left.schema.names)
            if big_on_left:
                if pos >= len(ln):
                    return None
                expr = E.Col(ln[pos])
                node = node.left
            else:
                if pos < len(ln):
                    return None
                rn = list(node.right.schema.names)
                expr = E.Col(rn[pos - len(ln)])
                node = node.right
            continue
        return None
    if isinstance(expr, E.Col) and expr.col_name in big.schema.names:
        return expr.col_name
    return None


class _MergeState:
    """Running device-side merge of per-chunk partial batches: the state
    stays a DEVICE batch across chunks (an arrow round trip would
    download every chunk's partials through the host, once per
    chunk)."""

    def __init__(self, merge_plan_fn, run_fn):
        self._merge_plan_fn = merge_plan_fn  # (state_rel|None, partial_plan) -> plan
        self._run = run_fn
        self.batch = None
        self.chunks = 0

    def feed(self, partial_plan: L.LogicalPlan) -> None:
        from spark_tpu.physical.operators import stats_recording_disabled

        state_rel = None if self.batch is None else L.Relation(self.batch)
        plan = self._merge_plan_fn(state_rel, partial_plan)
        # every chunk plan is single-shot (fresh leaf arrays): recording
        # adaptive/output stats would cost one blocking sync per chunk
        # and flood the LRU caches with dead entries
        with stats_recording_disabled():
            self.batch = self._run(plan)
        self.chunks += 1


def _merge_plan_for(spec: AggSpec):
    """The device merge step shared by every chunked tier: re-aggregate
    the union of the running state and one chunk's partials."""
    keys = tuple(E.Col(n) for n in spec.key_names)
    merge_outs = tuple(E.Alias(E.Col(n), n)
                       for n in spec.key_names) + tuple(spec.merges)

    def merge_plan(state_rel, partial):
        if state_rel is None:
            return L.Aggregate(keys, merge_outs, partial)
        aligned = L.Project(
            tuple(E.Col(n) for n in state_rel.schema.names), partial)
        return L.Aggregate(keys, merge_outs,
                           L.Union(state_rel, aligned))

    return merge_plan


def _int_key_values(batch, col: str) -> Optional[np.ndarray]:
    """Join-key column of a device batch as host int64 values (valid
    rows only); None for non-integral keys."""
    from spark_tpu import types as T

    try:
        f = batch.schema.field(col)
    except Exception:
        return None
    dt = f.dtype
    if not (getattr(dt, "is_integral", False)
            or isinstance(dt, (T.DateType, T.DecimalType))):
        return None
    cd = batch.column(col)
    data = np.asarray(cd.data).astype(np.int64)
    mask = np.asarray(batch.data.row_mask)
    if cd.validity is not None:
        mask = mask & np.asarray(cd.validity)
    return data[mask]


class _HostKeyFilter:
    """Host-side membership filter over one big-side key column: exact
    sorted-array semi filter up to ``semiFilterExactMax`` distinct keys,
    Bloom bitset above (same mergeable hash family as sketch.py's device
    Bloom; false positives only cost transfer). Also exposes the key
    range for parquet row-group pruning."""

    _MIX = np.uint64(0x9E3779B97F4A7C15)

    def __init__(self, col: str, values: np.ndarray, exact_max: int):
        self.col = col
        uniq = np.unique(values)  # sorted
        self.lo = int(uniq[0]) if len(uniq) else 0
        self.hi = int(uniq[-1]) if len(uniq) else 0
        self.exact = len(uniq) <= exact_max
        if self.exact:
            self._keys = uniq
        else:
            # ~16 bits/key, two probes -> <1% false positives
            nbits = 1 << int(np.ceil(np.log2(max(len(uniq), 2) * 16)))
            self._nbits = np.uint64(nbits)
            words = np.zeros(nbits // 64, dtype=np.uint64)
            for salt in (np.uint64(1), np.uint64(2)):
                h = (uniq.astype(np.uint64) * self._MIX * salt) \
                    % self._nbits
                np.bitwise_or.at(words, (h // 64).astype(np.int64),
                                 np.uint64(1) << (h % np.uint64(64)))
            self._words = words

    def member(self, vals: np.ndarray) -> np.ndarray:
        vals = vals.astype(np.int64, copy=False)
        if self.exact:
            pos = np.searchsorted(self._keys, vals)
            pos = np.clip(pos, 0, max(len(self._keys) - 1, 0))
            return (self._keys[pos] == vals) if len(self._keys) \
                else np.zeros(len(vals), dtype=bool)
        ok = np.ones(len(vals), dtype=bool)
        for salt in (np.uint64(1), np.uint64(2)):
            h = (vals.astype(np.uint64) * self._MIX * salt) % self._nbits
            bit = (self._words[(h // 64).astype(np.int64)]
                   >> (h % np.uint64(64))) & np.uint64(1)
            ok &= bit.astype(bool)
        return ok

    def range_conjuncts(self, schema) -> List[E.Expression]:
        """min/max pushdown predicates for the parquet scan (row-group
        pruning; exact filtering there would re-hash per row in C++ —
        the membership test stays in numpy)."""
        from spark_tpu import types as T

        f = schema.field(self.col)
        lo: object = self.lo
        hi: object = self.hi
        if isinstance(f.dtype, T.DecimalType):
            return []  # literal would need descaling; range gain is nil
        if isinstance(f.dtype, T.DateType):
            lo = T.days_to_date(self.lo)
            hi = T.days_to_date(self.hi)
        return [E.Cmp(">=", E.Col(self.col), E.Literal(lo)),
                E.Cmp("<=", E.Col(self.col), E.Literal(hi))]


def _chunk_capacity(rows: int, cap_max: int) -> int:
    """Power-of-two capacity bucket in [2^16, cap_max]: at most ~12
    distinct compiled programs across a whole stream, while a heavily
    key-filtered chunk ships proportional to its SURVIVING rows (a
    fixed capacity padded every chunk to the maximum)."""
    cap = 1 << 16
    while cap < rows:
        cap <<= 1
    return min(cap, cap_max) if rows <= cap_max else cap_max


def _progress_logger(tag: str):
    """stderr progress lines when SPARK_TPU_PROGRESS is set — hour-long
    SF100 streams are otherwise a black box from outside. When the
    chunk pipeline's stats are passed, each line also reports the
    achieved decode/transfer-vs-compute overlap so the operator can see
    whether prefetch is actually hiding the transfer."""
    import os
    import sys
    import time

    if not os.environ.get("SPARK_TPU_PROGRESS"):
        return lambda *_, **__: None
    t0 = time.time()

    def log(chunks: int, rows: int, stats=None) -> None:
        elapsed = time.time() - t0
        extra = ""
        if stats is not None:
            ov_s = stats.overlap_ms() / 1e3
            pct = 100.0 * ov_s / elapsed if elapsed > 0 else 0.0
            extra = f" overlap={ov_s:.1f}s ({pct:.0f}%)"
        print(f"[{tag}] chunk={chunks} rows={rows} "
              f"t={elapsed:.0f}s{extra}", file=sys.stderr, flush=True)

    return log


def _empty_rel(scan: L.UnresolvedScan) -> L.Relation:
    from spark_tpu.columnar.arrow import from_arrow
    from spark_tpu.io.datasource import _pa_schema_from_schema

    return L.Relation(
        from_arrow(_pa_schema_from_schema(scan.schema).empty_table()))


def _splice(root: L.LogicalPlan, mapping: Dict[int, L.LogicalPlan]):
    def repl(p: L.LogicalPlan) -> L.LogicalPlan:
        return mapping.get(id(p), p)

    return root.transform_up(repl)


@dataclasses.dataclass
class _ChunkedAgg:
    """Tiers 1+2: Aggregate over per-row ops / streamable joins around
    ONE over-budget scan."""

    above: List[L.LogicalPlan]
    agg: L.Aggregate
    big: L.UnresolvedScan
    path_joins: List[_PathJoin]

    def execute(self, conf, run_fn):
        from spark_tpu import metrics
        from spark_tpu.columnar.arrow import arrow_to_numpy
        from spark_tpu.columnar.batch import from_numpy, round_capacity
        from spark_tpu.physical.pipeline import ChunkPipeline

        agg, scan = self.agg, self.big
        spec = AggSpec(agg.groupings, agg.aggregates)
        key_aliases = tuple(E.Alias(g, n) for g, n
                            in zip(spec.groupings_exec, spec.key_names))
        chunk_rows = conf.get(CHUNK_ROWS)
        # ONE static capacity for every chunk: a varying capacity means
        # a fresh XLA compile per chunk (~minutes each on TPU)
        fixed_cap = round_capacity(chunk_rows)
        exact_max = conf.get(SEMI_FILTER_EXACT_MAX)
        depth = conf.get(CF.PIPELINE_DEPTH)
        prefetch_budget = conf.get(CF.PREFETCH_BYTES_MAX)
        stats = metrics.PipelineStats()

        # plan-only pre-pass: which path joins COULD yield a host key
        # filter. When none can, the chunk producer starts BEFORE the
        # sidecars materialize (sidecars ship while the first big
        # chunks decode); when one can, the stream waits for the
        # sidecar key sets so the membership filter and min/max
        # row-group pruning stay effective.
        filter_col: Dict[int, str] = {}
        for pj in self.path_joins:
            if exact_max > 0 and pj.can_filter and len(pj.big_keys) == 1:
                col = _resolve_to_scan_col(
                    pj.big_keys[0],
                    pj.join.left if pj.big_on_left else pj.join.right,
                    scan)
                if col is not None:
                    filter_col[id(pj)] = col

        scan_cols = scan.columns
        filters: List[_HostKeyFilter] = []
        counters = {"rows_in": 0, "rows_kept": 0}

        def make_prepare(read_cols):
            drop_extra = (scan_cols is not None
                          and len(read_cols or ()) != len(scan_cols))

            def prepare(tbl):
                counters["rows_in"] += tbl.num_rows
                if filters:
                    with stats.timed("filter"):
                        keep = np.ones(tbl.num_rows, dtype=bool)
                        for kf in filters:
                            vals = _decode_key_np(tbl.column(kf.col))
                            if vals is None:
                                continue
                            keep &= kf.member(vals)
                        if not keep.all():
                            tbl = tbl.filter(keep)
                        if drop_extra:
                            tbl = tbl.select(list(scan_cols))
                if tbl.num_rows == 0:
                    return None
                counters["rows_kept"] += tbl.num_rows
                with stats.timed("decode"):
                    sch, arrs, vlds = arrow_to_numpy(tbl)
                with stats.timed("transfer"):
                    batch = from_numpy(
                        sch, arrs, vlds,
                        capacity=_chunk_capacity(tbl.num_rows, fixed_cap),
                        narrow_transfer=True).block_until_ready()
                return L.Relation(batch)

            return prepare

        def rel_nbytes(rel):
            return rel.batch.device_nbytes()

        pipe = None
        try:
            if depth >= 1 and not filter_col:
                pipe = ChunkPipeline(
                    scan.source.iter_batches(scan_cols,
                                             tuple(scan.filters),
                                             chunk_rows),
                    make_prepare(scan_cols), depth=depth,
                    byte_budget=prefetch_budget, stats=stats,
                    nbytes_of=rel_nbytes, conf=conf)

            # 1. materialize each sidecar ONCE; they stay
            # device-resident
            sidecar_rel: Dict[int, L.LogicalPlan] = {}
            side_log = _progress_logger("sidecar")
            for si, pj in enumerate(self.path_joins):
                side_log(si, 0)
                with stats.timed("sidecar"):
                    batch = run_fn(pj.sidecar)
                sidecar_rel[id(pj.sidecar)] = L.Relation(batch)
                col = filter_col.get(id(pj))
                if col is None:
                    continue
                skey = E.strip_alias(pj.sidecar_keys[0])
                try:
                    with stats.timed("sidecar"):
                        kb = run_fn(L.Project(
                            (E.Alias(skey, "__semi_k"),),
                            L.Relation(batch)))
                    vals = _int_key_values(kb, "__semi_k")
                except Exception:
                    vals = None
                if vals is not None:
                    filters.append(_HostKeyFilter(col, vals, exact_max))
            skeleton = _splice(agg.child, sidecar_rel) \
                if sidecar_rel else agg.child

            if pipe is None:
                # 2. push key ranges into the scan, then stream +
                # filter chunks
                scan_filters = tuple(scan.filters)
                for kf in filters:
                    try:
                        scan_filters = scan_filters \
                            + tuple(kf.range_conjuncts(scan.schema))
                    except Exception:
                        pass
                if filters and scan_cols is not None:
                    # membership columns must be in the streamed
                    # projection
                    need = [kf.col for kf in filters
                            if kf.col not in scan_cols]
                    read_cols = tuple(scan_cols) \
                        + tuple(dict.fromkeys(need))
                else:
                    read_cols = scan_cols
                pipe = ChunkPipeline(
                    scan.source.iter_batches(read_cols, scan_filters,
                                             chunk_rows),
                    make_prepare(read_cols), depth=depth,
                    byte_budget=prefetch_budget, stats=stats,
                    nbytes_of=rel_nbytes, conf=conf)

            state = _MergeState(_merge_plan_for(spec), run_fn)
            progress = _progress_logger("chunked_agg")
            for rel in pipe:
                with stats.timed("compute"):
                    chunk_plan = _splice(skeleton, {id(scan): rel})
                    partial = L.Aggregate(
                        tuple(spec.groupings_exec),
                        key_aliases + tuple(spec.partials), chunk_plan)
                    state.feed(partial)
                progress(state.chunks, counters["rows_in"], stats)
        finally:
            if pipe is not None:
                pipe.close()
        metrics.record(
            "chunked_agg", chunks=state.chunks,
            sidecars=len(sidecar_rel), key_filters=len(filters),
            rows_in=counters["rows_in"],
            rows_kept=counters["rows_kept"],
            groups=0 if state.batch is None
            else state.batch.num_valid_rows(),
            pipeline_depth=depth, **stats.finish())

        if state.batch is None:
            # empty stream: run the aggregate over an EMPTY spliced
            # relation — the original plan would rematerialize the scan
            final0: L.LogicalPlan = L.Aggregate(
                agg.groupings, agg.aggregates,
                _splice(skeleton, {id(scan): _empty_rel(scan)}))
            for node in reversed(self.above):
                final0 = node.with_children((final0,))
            return run_fn(final0)
        final: L.LogicalPlan = L.Project(tuple(spec.outputs),
                                         L.Relation(state.batch))
        for node in reversed(self.above):
            final = node.with_children((final,))
        return run_fn(final)


def _decode_key_np(col) -> Optional[np.ndarray]:
    """Arrow (chunked) column -> int64 numpy for membership testing;
    None when the storage isn't integral (dictionary/strings)."""
    import pyarrow as pa
    import pyarrow.compute as pc

    if isinstance(col, pa.ChunkedArray):
        col = col.combine_chunks()
    t = col.type
    if pa.types.is_dictionary(t):
        return None
    if pa.types.is_decimal(t):
        raw = np.frombuffer(col.buffers()[1], dtype=np.int64)
        lo = col.offset * 2
        return raw[lo:lo + 2 * len(col):2].copy()
    try:
        if pa.types.is_date(t) or pa.types.is_timestamp(t):
            col = col.cast(pa.int64())
        vals = pc.fill_null(col, 0).to_numpy(zero_copy_only=False)
        if not np.issubdtype(vals.dtype, np.integer):
            return None
        return vals.astype(np.int64, copy=False)
    except Exception:
        return None


@dataclasses.dataclass
class _GraceHashAgg:
    """Tier 3: Aggregate over Join(per-row(bigA), per-row(bigB)) with
    both scans over budget — grace-hash partitioning into host-RAM
    buckets, then per-bucket device joins feeding the merge state."""

    above: List[L.LogicalPlan]
    agg: L.Aggregate
    join: L.Join
    scan_a: L.UnresolvedScan  # under join.left
    scan_b: L.UnresolvedScan  # under join.right
    key_a: str  # partition column on scan_a
    key_b: str
    est_total: int

    _MIX = np.uint64(0x9E3779B97F4A7C15)

    def execute(self, conf, run_fn):
        from spark_tpu import metrics
        from spark_tpu.columnar.arrow import arrow_to_numpy
        from spark_tpu.columnar.batch import from_numpy
        from spark_tpu.physical.pipeline import ChunkPipeline

        budget = conf.get(MAX_DEVICE_BATCH_BYTES)
        chunk_rows = conf.get(CHUNK_ROWS)
        depth = conf.get(CF.PIPELINE_DEPTH)
        prefetch_budget = conf.get(CF.PREFETCH_BYTES_MAX)
        stats = metrics.PipelineStats()
        nparts = int(min(conf.get(GRACE_PARTITIONS_MAX),
                         max(2, -(-4 * self.est_total // max(budget, 1)))))

        def partition(scan, key_col):
            buckets: List[list] = [[] for _ in range(nparts)]
            for tbl in scan.source.iter_batches(
                    scan.columns, scan.filters, chunk_rows):
                vals = _decode_key_np(tbl.column(key_col))
                if vals is None:
                    raise NotImplementedError(
                        "grace-hash join needs an integral partition key")
                h = ((vals.astype(np.uint64) * self._MIX)
                     >> np.uint64(32)) % np.uint64(nparts)
                h = h.astype(np.int64)
                for p in np.unique(h):
                    buckets[p].append(tbl.filter(h == p))
            return buckets

        with stats.timed("decode"):
            if depth >= 1:
                # both sides' partition passes are pure host work
                # (parquet decode + hash into bucket lists) over
                # disjoint state — run them concurrently
                import concurrent.futures as _cf

                with _cf.ThreadPoolExecutor(
                        2, thread_name_prefix="grace-partition") as pool:
                    fa = pool.submit(partition, self.scan_a, self.key_a)
                    fb = pool.submit(partition, self.scan_b, self.key_b)
                    buckets_a, buckets_b = fa.result(), fb.result()
            else:
                buckets_a = partition(self.scan_a, self.key_a)
                buckets_b = partition(self.scan_b, self.key_b)

        spec = AggSpec(self.agg.groupings, self.agg.aggregates)
        key_aliases = tuple(E.Alias(g, n) for g, n
                            in zip(spec.groupings_exec, spec.key_names))
        state = _MergeState(_merge_plan_for(spec), run_fn)
        import pyarrow as pa

        def concat(parts, scan):
            if not parts:
                # typed empty table so the spliced Relation keeps schema
                from spark_tpu.io.datasource import _pa_schema_from_schema

                return _pa_schema_from_schema(scan.schema).empty_table()
            return pa.concat_tables(parts)

        from spark_tpu.columnar.batch import round_capacity

        # ONE static capacity per side across all buckets (varying
        # capacities would compile a fresh XLA program per bucket)
        cap_a = round_capacity(max(
            [sum(t.num_rows for t in b or ()) for b in buckets_a] or [1]))
        cap_b = round_capacity(max(
            [sum(t.num_rows for t in b or ()) for b in buckets_b] or [1]))
        outer = self.join.how in ("left", "right", "full")
        parts = []
        for p in range(nparts):
            if not buckets_a[p] and not buckets_b[p]:
                continue
            if not outer and (not buckets_a[p] or not buckets_b[p]):
                if self.join.how != "left_anti" or not buckets_a[p]:
                    continue
            parts.append(p)

        def prepare(p):
            with stats.timed("decode"):
                ta = concat(buckets_a[p], self.scan_a)
                tb = concat(buckets_b[p], self.scan_b)
                buckets_a[p] = buckets_b[p] = None  # free host RAM
                sa, aa, va = arrow_to_numpy(ta)
                sb, ab, vb = arrow_to_numpy(tb)
            with stats.timed("transfer"):
                ba = from_numpy(sa, aa, va, capacity=cap_a,
                                narrow_transfer=True).block_until_ready()
                bb = from_numpy(sb, ab, vb, capacity=cap_b,
                                narrow_transfer=True).block_until_ready()
            return {id(self.scan_a): L.Relation(ba),
                    id(self.scan_b): L.Relation(bb)}

        pipe = ChunkPipeline(
            parts, prepare, depth=depth, byte_budget=prefetch_budget,
            stats=stats,
            nbytes_of=lambda m: sum(r.batch.device_nbytes()
                                    for r in m.values()),
            conf=conf)
        progress = _progress_logger("grace_hash_agg")
        try:
            for mapping in pipe:
                with stats.timed("compute"):
                    chunk_plan = _splice(self.agg.child, mapping)
                    partial = L.Aggregate(
                        tuple(spec.groupings_exec),
                        key_aliases + tuple(spec.partials), chunk_plan)
                    state.feed(partial)
                progress(state.chunks, 0, stats)
        finally:
            pipe.close()
        metrics.record("grace_hash_agg", partitions=nparts,
                       chunks=state.chunks, pipeline_depth=depth,
                       **stats.finish())

        if state.batch is None:
            final0: L.LogicalPlan = L.Aggregate(
                self.agg.groupings, self.agg.aggregates,
                _splice(self.agg.child,
                        {id(self.scan_a): _empty_rel(self.scan_a),
                         id(self.scan_b): _empty_rel(self.scan_b)}))
            for node in reversed(self.above):
                final0 = node.with_children((final0,))
            return run_fn(final0)
        final: L.LogicalPlan = L.Project(tuple(spec.outputs),
                                         L.Relation(state.batch))
        for node in reversed(self.above):
            final = node.with_children((final,))
        return run_fn(final)


# recursive-repartition bounds: an overflowing bucket splits 4 ways per
# level under a fresh hash salt; recursion stops once a bucket fits the
# device budget, shrinks below the row floor (device can chunk it), has
# a single hot key (splitting cannot help), or hits the depth cap.
_RECURSE_FANOUT = 4
_RECURSE_MAX_DEPTH = 8
_RECURSE_MIN_ROWS = 4096

#: HLL registers for the host-side distinct sketch maintained during
#: the hybrid join's partition pass (same estimator as the adaptive
#: aggregation sketch — one shared implementation in spark_tpu/sketch.py)
_HLL_REGISTERS = 256


def _session_memory_manager():
    """The active session's UnifiedMemoryManager, or None standalone
    (e.g. a bare MeshExecutor in tests) — the hybrid join then stages
    fully resident, exactly like the static grace join."""
    try:
        from spark_tpu.api.session import SparkSession

        sess = SparkSession._active
        return getattr(sess, "memory_manager", None)
    except Exception:
        return None


class _HybridSpillAbort(Exception):
    """A ``join.spill`` seam exhausted its retries or hit corruption:
    the hybrid pass discards its partial state and falls back ONE rung
    to the static grace-hash join, recomputed from source."""

    def __init__(self, op: str, kind: str):
        super().__init__(f"hybrid hash join {op} aborted ({kind})")
        self.op = op
        self.kind = kind


def _spill_seam(conf, op: str, attempts: int, fn):
    """Run one spill-side operation behind the ``join.spill`` fault
    point. transient/hang faults retry up to ``attempts`` times;
    corruption or retry exhaustion aborts the hybrid pass (the caller
    falls back to the static grace-hash join); OOM propagates so the
    degradation ladder stays the LAST resort. The injection fires
    BEFORE ``fn`` touches any file, so a retried injected fault never
    sees partial writes; real mid-write I/O errors are not transient
    and abort to the recompute-from-source fallback."""
    from spark_tpu import deadline, faults, metrics, recovery, trace

    attempts = max(0, int(attempts))
    last: Optional[BaseException] = None
    for attempt in range(attempts + 1):
        try:
            with trace.span("join.spill", op=op, attempt=attempt):
                faults.inject("join.spill", conf)
                return fn()
        except deadline.DeadlineExceeded:
            # not a spill failure: the query's window closed, so the
            # abort-to-grace-hash fallback would just burn more time
            raise
        except Exception as e:
            if recovery.is_oom(e):
                raise
            if recovery.is_transient(e) and attempt < attempts:
                deadline.check(f"join.spill.{op}")
                if not recovery.retry_allowed("join.spill"):
                    raise recovery.RetryBudgetExhausted(
                        "join.spill", recovery.current_budget()) from e
                last = e
                metrics.note_join("spill_retries")
                metrics.record("stage_retry", label=f"join.spill.{op}",
                               attempt=attempt, error=repr(e))
                continue
            raise _HybridSpillAbort(
                op, getattr(e, "kind", type(e).__name__)) from e
    raise _HybridSpillAbort(
        op, getattr(last, "kind", "exhausted")) from last


class _HybridPart:
    """One side of one hybrid-join partition: resident arrow tables
    while it fits the grant, a write-through host spill file after
    demotion."""

    __slots__ = ("tables", "rows", "nbytes", "path", "sink", "writer",
                 "spilled")

    def __init__(self):
        self.tables: Optional[list] = []
        self.rows = 0
        self.nbytes = 0
        self.path: Optional[str] = None
        self.sink = None
        self.writer = None
        self.spilled = False


@dataclasses.dataclass
class _HybridHashJoinAgg:
    """Tier 3, dynamic: grant-driven hybrid hash join.

    Where the static ``_GraceHashAgg`` stages BOTH sides fully in host
    RAM and hopes, this tier executes the same join as a planned single
    pass at ANY memory level:

    1. **Grant.** Before touching data it requests an execution grant
       from the session's UnifiedMemoryManager, sized by the MEASURED
       build bytes of a prior run of the same plan shape
       (admission.seeded_build_bytes — the AQE feedback loop) or the
       planner estimate. The grant is what the staging pass may keep
       resident; a 0-byte grant means everything spills (the join still
       completes in one planned pass — it never blocks on storage).
    2. **Partition pass.** Both scans stream once, hash-bucketed with
       the grace hash. Partitions accumulate resident until the grant
       is exhausted; then the join first tries to GROW the grant from
       the manager's free span (growWhenIdle — never evicting storage)
       and otherwise demotes the largest resident partition to a
       write-through arrow-IPC spill file. A host-side HLL distinct
       sketch of the join keys is maintained during the pass.
    3. **Join pass.** Partitions execute in index order (resident
       directly, spilled read back), feeding the same device merge
       state as grace — results are byte-identical. A bucket pair whose
       working set would blow the device budget is recursively
       REPARTITIONED with a per-level hash salt instead of
       shipped-and-hoped, so the OOM ladder becomes the last resort
       rather than the sizing mechanism.

    Every spill-file write, read-back, and recursive repartition is a
    ``join.spill`` fault seam with bounded retries; unrecoverable seam
    failures fall back one rung to the static grace join recomputed
    from source. Observed staging bytes are fed back to admission, so
    the NEXT run's grant is measured, not estimated."""

    above: List[L.LogicalPlan]
    agg: L.Aggregate
    join: L.Join
    scan_a: L.UnresolvedScan
    scan_b: L.UnresolvedScan
    key_a: str
    key_b: str
    est_total: int

    _MIX = np.uint64(0x9E3779B97F4A7C15)

    def execute(self, conf, run_fn):
        from spark_tpu import metrics

        try:
            return self._execute_hybrid(conf, run_fn)
        except _HybridSpillAbort as e:
            metrics.note_join("fallbacks")
            metrics.record("fault_recovered", point="join.spill",
                           fault=e.kind, op=e.op,
                           action="grace_fallback")
            return _GraceHashAgg(
                self.above, self.agg, self.join, self.scan_a,
                self.scan_b, self.key_a, self.key_b,
                self.est_total).execute(conf, run_fn)

    def _execute_hybrid(self, conf, run_fn):
        import os
        import shutil
        import tempfile

        import pyarrow as pa

        from spark_tpu import metrics, trace
        from spark_tpu.columnar.arrow import arrow_to_numpy
        from spark_tpu.columnar.batch import from_numpy, round_capacity
        from spark_tpu.io.datasource import _pa_schema_from_schema
        from spark_tpu.physical.pipeline import ChunkPipeline
        from spark_tpu.sketch import HyperLogLog
        from spark_tpu.scheduler import admission

        budget = conf.get(MAX_DEVICE_BATCH_BYTES)
        chunk_rows = conf.get(CHUNK_ROWS)
        depth = conf.get(CF.PIPELINE_DEPTH)
        prefetch_budget = conf.get(CF.PREFETCH_BYTES_MAX)
        retries = int(conf.get(JOIN_HYBRID_SPILL_RETRIES))
        grow_idle = bool(conf.get(JOIN_HYBRID_GROW_WHEN_IDLE))
        stats = metrics.PipelineStats()
        nparts = int(min(conf.get(JOIN_HYBRID_PARTITIONS_MAX),
                         max(2, -(-4 * self.est_total
                                  // max(budget, 1)))))

        manager = _session_memory_manager()
        charge = 0
        resident_cap: Optional[int] = None  # None = ungoverned
        if manager is not None:
            request = admission.seeded_build_bytes(self.agg,
                                                   self.est_total)
            charge = manager.acquire_execution(request)
            resident_cap = charge
            metrics.note_join("grants")
            metrics.note_join("grant_bytes", charge)
            if charge == 0:
                metrics.note_join("zero_grants")
        granted0 = charge

        parts_a = [_HybridPart() for _ in range(nparts)]
        parts_b = [_HybridPart() for _ in range(nparts)]
        hll = HyperLogLog(_HLL_REGISTERS)
        counters = {"resident": 0, "staged": 0, "spill_bytes": 0,
                    "max_depth": 0}
        tmpdir: Optional[str] = None

        def spill_write(side, p, part, tables):
            nbytes = sum(t.nbytes for t in tables)

            def _do():
                nonlocal tmpdir
                if part.writer is None:
                    if tmpdir is None:
                        tmpdir = tempfile.mkdtemp(
                            prefix="spark-tpu-hybrid-join-")
                    part.path = os.path.join(tmpdir,
                                             f"{side}{p}.arrows")
                    part.sink = pa.OSFile(part.path, "wb")
                    part.writer = pa.ipc.new_stream(part.sink,
                                                    tables[0].schema)
                for t in tables:
                    part.writer.write_table(t)

            _spill_seam(conf, "write", retries, _do)
            metrics.note_join("spill_writes")
            metrics.note_join("spill_bytes", nbytes)
            counters["spill_bytes"] += nbytes

        def demote_one() -> int:
            """Spill the largest resident partition wholesale; returns
            the resident bytes freed (0 when nothing is demotable)."""
            best = None
            for side, plist in (("a", parts_a), ("b", parts_b)):
                for p, part in enumerate(plist):
                    if part.tables and (best is None
                                        or part.nbytes > best[2].nbytes):
                        best = (side, p, part)
            if best is None:
                return 0
            side, p, part = best
            tables, freed = part.tables, part.nbytes
            part.tables, part.nbytes = [], 0
            if not part.spilled:
                part.spilled = True
                metrics.note_join("spilled_partitions")
            spill_write(side, p, part, tables)
            return freed

        def partition_side(side, scan, key_col, plist):
            nonlocal charge, resident_cap
            for tbl in scan.source.iter_batches(
                    scan.columns, scan.filters, chunk_rows):
                vals = _decode_key_np(tbl.column(key_col))
                if vals is None:
                    raise NotImplementedError(
                        "hybrid hash join needs an integral "
                        "partition key")
                hll.update(vals)
                h = ((vals.astype(np.uint64) * self._MIX)
                     >> np.uint64(32)) % np.uint64(nparts)
                h = h.astype(np.int64)
                for p in np.unique(h):
                    part = plist[p]
                    sub = tbl.filter(h == p)
                    part.rows += sub.num_rows
                    counters["staged"] += sub.nbytes
                    if part.spilled:  # write-through: stays spilled
                        spill_write(side, p, part, [sub])
                        continue
                    part.tables.append(sub)
                    part.nbytes += sub.nbytes
                    counters["resident"] += sub.nbytes
                # planned spilling: keep staged bytes inside the grant
                # — grow from the manager's free span when allowed,
                # demote the largest partition otherwise
                while resident_cap is not None \
                        and counters["resident"] > resident_cap:
                    need = counters["resident"] - resident_cap
                    if grow_idle and manager is not None:
                        got = manager.try_grow(need)
                        if got:
                            charge += got
                            resident_cap += got
                            metrics.note_join("grows")
                            continue
                    freed = demote_one()
                    if freed == 0:
                        break  # nothing demotable: run over-grant
                    counters["resident"] -= freed

        def close_writers():
            for plist in (parts_a, parts_b):
                for part in plist:
                    if part.writer is not None:
                        part.writer.close()
                        part.sink.close()
                        part.writer = part.sink = None

        def read_back(part) -> "pa.Table":
            def _do():
                with pa.OSFile(part.path, "rb") as f:
                    return pa.ipc.open_stream(f).read_all()

            tbl = _spill_seam(conf, "read", retries, _do)
            metrics.note_join("spill_reads")
            return tbl

        def materialize(part, scan) -> "pa.Table":
            if part.spilled:
                return read_back(part)
            if not part.tables:
                return _pa_schema_from_schema(scan.schema).empty_table()
            return pa.concat_tables(part.tables)

        spec = AggSpec(self.agg.groupings, self.agg.aggregates)
        key_aliases = tuple(E.Alias(g, n) for g, n
                            in zip(spec.groupings_exec, spec.key_names))
        state = _MergeState(_merge_plan_for(spec), run_fn)
        outer = self.join.how in ("left", "right", "full")

        def keep_pair(has_a: bool, has_b: bool) -> bool:
            if not has_a and not has_b:
                return False
            if not outer and (not has_a or not has_b):
                return self.join.how == "left_anti" and has_a
            return True

        try:
            with trace.span("join.partition", partitions=nparts,
                            granted=granted0):
                # sequential sides (grace runs them concurrently):
                # spill/grow decisions against the shared grant stay
                # deterministic, so spill counts are reproducible
                partition_side("a", self.scan_a, self.key_a, parts_a)
                partition_side("b", self.scan_b, self.key_b, parts_b)
                close_writers()

            # ONE power-of-two capacity ladder per side: top-level caps
            # from the largest bucket, sub-buckets reuse the
            # _chunk_capacity buckets below it (bounded program count)
            cap_a = round_capacity(
                max([p.rows for p in parts_a] + [1]))
            cap_b = round_capacity(
                max([p.rows for p in parts_b] + [1]))
            parts = [p for p in range(nparts)
                     if keep_pair(parts_a[p].rows > 0,
                                  parts_b[p].rows > 0)]

            def to_device(ta, tb):
                with stats.timed("decode"):
                    sa, aa, va = arrow_to_numpy(ta)
                    sb, ab, vb = arrow_to_numpy(tb)
                with stats.timed("transfer"):
                    ba = from_numpy(
                        sa, aa, va,
                        capacity=_chunk_capacity(
                            max(ta.num_rows, 1), cap_a),
                        narrow_transfer=True).block_until_ready()
                    bb = from_numpy(
                        sb, ab, vb,
                        capacity=_chunk_capacity(
                            max(tb.num_rows, 1), cap_b),
                        narrow_transfer=True).block_until_ready()
                return {id(self.scan_a): L.Relation(ba),
                        id(self.scan_b): L.Relation(bb)}

            def split_bucket(ta, tb, level, out):
                pair = ta.nbytes + tb.nbytes
                if (4 * pair <= budget
                        or level >= _RECURSE_MAX_DEPTH
                        or max(ta.num_rows,
                               tb.num_rows) <= _RECURSE_MIN_ROWS):
                    out.append(to_device(ta, tb))
                    return
                ka = _decode_key_np(ta.column(self.key_a)) \
                    if ta.num_rows else None
                if ka is not None and len(np.unique(ka)) <= 1:
                    # single hot key: splitting cannot help; ship it
                    out.append(to_device(ta, tb))
                    return

                def _do():
                    salt = np.uint64(2 * level + 3)

                    def rehash(tbl, col):
                        if tbl.num_rows == 0:
                            return [tbl] * _RECURSE_FANOUT
                        vals = _decode_key_np(tbl.column(col))
                        h = ((vals.astype(np.uint64) * self._MIX
                              * salt) >> np.uint64(32)) \
                            % np.uint64(_RECURSE_FANOUT)
                        h = h.astype(np.int64)
                        return [tbl.filter(h == i)
                                for i in range(_RECURSE_FANOUT)]

                    return (rehash(ta, self.key_a),
                            rehash(tb, self.key_b))

                subs_a, subs_b = _spill_seam(conf, "repartition",
                                             retries, _do)
                metrics.note_join("recursive_repartitions")
                counters["max_depth"] = max(counters["max_depth"],
                                            level + 1)
                for i in range(_RECURSE_FANOUT):
                    if keep_pair(subs_a[i].num_rows > 0,
                                 subs_b[i].num_rows > 0):
                        split_bucket(subs_a[i], subs_b[i],
                                     level + 1, out)

            def prepare(p):
                ta = materialize(parts_a[p], self.scan_a)
                tb = materialize(parts_b[p], self.scan_b)
                parts_a[p].tables = parts_b[p].tables = None  # free RAM
                out: list = []
                split_bucket(ta, tb, 0, out)
                return out or None

            pipe = ChunkPipeline(
                parts, prepare, depth=depth,
                byte_budget=prefetch_budget, stats=stats,
                nbytes_of=lambda ms: sum(
                    r.batch.device_nbytes()
                    for m in ms for r in m.values()),
                conf=conf)
            progress = _progress_logger("hybrid_hash_agg")
            try:
                for mappings in pipe:
                    for mapping in mappings:
                        with stats.timed("compute"):
                            chunk_plan = _splice(self.agg.child,
                                                 mapping)
                            partial = L.Aggregate(
                                tuple(spec.groupings_exec),
                                key_aliases + tuple(spec.partials),
                                chunk_plan)
                            state.feed(partial)
                    progress(state.chunks, 0, stats)
            finally:
                pipe.close()
        finally:
            close_writers()
            if tmpdir is not None:
                shutil.rmtree(tmpdir, ignore_errors=True)
            if manager is not None:
                manager.release_execution(charge)

        spilled = sum(1 for plist in (parts_a, parts_b)
                      for pt in plist if pt.spilled)
        metrics.record(
            "hybrid_hash_agg", partitions=nparts,
            spilled_parts=spilled,
            resident_parts=2 * nparts - spilled,
            granted_bytes=granted0, grown_bytes=charge - granted0,
            staged_bytes=counters["staged"],
            spill_bytes=counters["spill_bytes"],
            depth=counters["max_depth"],
            ndv=int(hll.estimate()),
            chunks=state.chunks, pipeline_depth=depth,
            **stats.finish())
        # AQE feedback: the NEXT run of this plan shape requests a
        # grant sized by what staging actually took
        admission.note_measured_bytes(self.agg, counters["staged"])

        if state.batch is None:
            final0: L.LogicalPlan = L.Aggregate(
                self.agg.groupings, self.agg.aggregates,
                _splice(self.agg.child,
                        {id(self.scan_a): _empty_rel(self.scan_a),
                         id(self.scan_b): _empty_rel(self.scan_b)}))
            for node in reversed(self.above):
                final0 = node.with_children((final0,))
            return run_fn(final0)
        final: L.LogicalPlan = L.Project(tuple(spec.outputs),
                                         L.Relation(state.batch))
        for node in reversed(self.above):
            final = node.with_children((final,))
        return run_fn(final)


@dataclasses.dataclass
class _ChunkedTopK:
    """Streamed top-k: Limit(Sort(per-row(big scan))) keeps a running
    device top-(n+offset), merged per chunk (ExternalSorter's
    TakeOrderedAndProjectExec shape)."""

    above: List[L.LogicalPlan]  # Projects above the Limit
    limit: L.Limit
    sort: L.Sort
    chain_root: L.LogicalPlan  # sort.child (per-row ops over the scan)
    big: L.UnresolvedScan

    def execute(self, conf, run_fn):
        from spark_tpu import metrics
        from spark_tpu.columnar.arrow import arrow_to_numpy
        from spark_tpu.columnar.batch import from_numpy, round_capacity
        from spark_tpu.physical.pipeline import ChunkPipeline

        chunk_rows = conf.get(CHUNK_ROWS)
        depth = conf.get(CF.PIPELINE_DEPTH)
        prefetch_budget = conf.get(CF.PREFETCH_BYTES_MAX)
        stats = metrics.PipelineStats()
        k = self.limit.n + self.limit.offset

        def merge_plan(state_rel, chunk_plan):
            child = chunk_plan if state_rel is None else L.Union(
                state_rel,
                L.Project(tuple(E.Col(n)
                                for n in state_rel.schema.names),
                          chunk_plan))
            return L.Limit(k, L.Sort(self.sort.orders, child))

        fixed_cap = round_capacity(chunk_rows)
        state = _MergeState(merge_plan, run_fn)

        def prepare(tbl):
            if tbl.num_rows == 0:
                return None
            with stats.timed("decode"):
                sch, arrs, vlds = arrow_to_numpy(tbl)
            with stats.timed("transfer"):
                batch = from_numpy(
                    sch, arrs, vlds,
                    capacity=_chunk_capacity(tbl.num_rows, fixed_cap),
                    narrow_transfer=True).block_until_ready()
            return L.Relation(batch)

        pipe = ChunkPipeline(
            self.big.source.iter_batches(self.big.columns,
                                         self.big.filters, chunk_rows),
            prepare, depth=depth, byte_budget=prefetch_budget,
            stats=stats,
            nbytes_of=lambda rel: rel.batch.device_nbytes(),
            conf=conf)
        progress = _progress_logger("chunked_topk")
        try:
            for rel in pipe:
                with stats.timed("compute"):
                    chunk_plan = _splice(self.chain_root,
                                         {id(self.big): rel})
                    state.feed(chunk_plan)
                progress(state.chunks, 0, stats)
        finally:
            pipe.close()
        metrics.record("chunked_topk", chunks=state.chunks, k=k,
                       pipeline_depth=depth, **stats.finish())

        if state.batch is None:
            base: L.LogicalPlan = L.Limit(
                self.limit.n,
                L.Sort(self.sort.orders,
                       _splice(self.chain_root,
                               {id(self.big): _empty_rel(self.big)})),
                offset=self.limit.offset)
        else:
            base = L.Limit(self.limit.n,
                           L.Sort(self.sort.orders,
                                  L.Relation(state.batch)),
                           offset=self.limit.offset)
        for node in reversed(self.above):
            base = node.with_children((base,))
        return run_fn(base)


def find_chunkable(plan: L.LogicalPlan, conf):
    """Detect an out-of-HBM-executable shape around over-budget scans;
    returns an executable tier object (with .execute(conf, run_fn)) or
    None to run the plan resident."""
    budget = conf.get(MAX_DEVICE_BATCH_BYTES)
    above, node = _peel_above(plan)

    if isinstance(node, L.Aggregate):
        return _find_agg(above, node, budget, conf)

    # top-k tier: Project* (Limit (Sort (per-row (big scan))))
    above2: List[L.LogicalPlan] = []
    n2 = plan
    while isinstance(n2, L.Project):
        above2.append(n2)
        n2 = n2.children()[0]
    if not isinstance(n2, L.Limit):
        return None
    lim = n2
    if not isinstance(lim.child, L.Sort):
        return None
    sort = lim.child
    node = sort.child
    chain = node
    while isinstance(node, (L.Filter, L.Project, L.SubqueryAlias)):
        node = node.children()[0]
    if not isinstance(node, L.UnresolvedScan):
        return None
    est = _est_scan(node)
    if est is None or est <= budget:
        return None
    if lim.n + lim.offset > conf.get(CHUNK_ROWS):
        return None  # running state would itself exceed a chunk
    return _ChunkedTopK(above2, lim, sort, chain, node)


def _find_agg(above, agg: L.Aggregate, budget: int, conf=None):
    # cheap structural pre-check via the shared legality rule set
    # (analysis/legality.py) before paying for full AggSpec planning;
    # AggSpec itself enforces the same verdicts
    from spark_tpu.analysis import legality

    if not legality.accumulators_verdict(agg.aggregates):
        return None  # non-mergeable aggregate: execute directly
    try:
        AggSpec(agg.groupings, agg.aggregates)
    except NotImplementedError:
        return None
    scans = L.collect_nodes(agg.child, L.UnresolvedScan)
    ests = []
    for s in scans:
        e = _est_scan(s)
        if e is None:
            return None
        ests.append(e)
    big = [(s, e) for s, e in zip(scans, ests) if e > budget]
    if not big:
        return None

    if len(big) == 1:
        scan = big[0][0]
        path = _stream_path(agg.child, scan)
        if path is not None:
            # every sidecar must itself fit the device budget
            ok = True
            for pj in path:
                side_est = sum(
                    _est_scan(s) or (budget + 1)
                    for s in L.collect_nodes(pj.sidecar,
                                             L.UnresolvedScan))
                if side_est > budget:
                    ok = False
                    break
            if ok:
                return _ChunkedAgg(above, agg, scan, path)

    if len(big) == 2:
        gh = _find_grace(above, agg, big[0][0], big[1][0],
                         big[0][1] + big[1][1], conf)
        if gh is not None:
            return gh
    return None


def _find_grace(above, agg: L.Aggregate, sa: L.UnresolvedScan,
                sb: L.UnresolvedScan, est_total: int, conf=None):
    """Shape check for tier 3: one join under the aggregate separates
    the two big scans, with only per-row ops between."""
    # find the join whose sides split {sa, sb}
    joins = [j for j in L.collect_nodes(agg.child, L.Join)
             if _contains(j.left, sa) != _contains(j.left, sb)]
    if len(joins) != 1:
        return None
    join = joins[0]
    if _contains(join.left, sb):
        sa, sb = sb, sa
    # per-row only between agg and the join, and join and each scan
    node = agg.child
    while node is not join:
        if not isinstance(node, (L.Filter, L.Project, L.SubqueryAlias)):
            return None
        node = node.children()[0]

    def per_row_to(root, target):
        n = root
        while n is not target:
            if not isinstance(n, (L.Filter, L.Project, L.SubqueryAlias)):
                return False
            n = n.children()[0]
        return True

    if not per_row_to(join.left, sa) or not per_row_to(join.right, sb):
        return None
    if len(join.left_keys) != 1 or join.how == "cross":
        return None
    ka = _resolve_to_scan_col(join.left_keys[0], join.left, sa)
    kb = _resolve_to_scan_col(join.right_keys[0], join.right, sb)
    if ka is None or kb is None:
        return None
    from spark_tpu import types as T

    for scan, key in ((sa, ka), (sb, kb)):
        dt = scan.schema.field(key).dtype
        if not (getattr(dt, "is_integral", False)
                or isinstance(dt, (T.DateType, T.DecimalType))):
            return None
    hybrid = bool(conf.get(JOIN_HYBRID_ENABLED)) if conf is not None \
        else bool(JOIN_HYBRID_ENABLED.default)
    cls = _HybridHashJoinAgg if hybrid else _GraceHashAgg
    return cls(above, agg, join, sa, sb, ka, kb, est_total)


def execute_chunked(found, conf, run_fn):
    """Execute a chunkable plan (``found`` from find_chunkable);
    ``run_fn(logical_plan) -> Batch`` is the engine (single-device or
    mesh). Returns the final Batch."""
    return found.execute(conf, run_fn)
