"""Logical -> physical planning and the stage-fusing executor.

Planning mirrors SparkPlanner/SparkStrategies (reference:
sql/core/.../SparkPlanner.scala:28, SparkStrategies.scala Aggregation:522
JoinSelection:172 BasicOperators:750) collapsed into one pass — there is
a single physical choice per logical operator, with strategy decisions
(direct vs sort aggregation) deferred to trace-time metadata.

Execution replaces the whole SparkPlan.execute -> RDD -> DAGScheduler
machinery (reference: SparkPlan.scala:191, QueryExecution.scala:168):
maximal *traceable* subtrees are fused into one jitted XLA program (the
WholeStageCodegenExec.scala:627 analogue — CollapseCodegenStages:882
becomes "walk until a blocking operator"), blocking operators run
eagerly between stages with host syncs for output sizing (the AQE
stage-boundary analogue, reference: AdaptiveSparkPlanExec.scala:247).
"""

from __future__ import annotations

import dataclasses
import operator

import jax
import jax.numpy as jnp
import numpy as np

from spark_tpu import conf as CF
from spark_tpu import trace
from spark_tpu import types as T
from spark_tpu.columnar.batch import Batch, BatchData, ColumnData
from spark_tpu.expr import expressions as E
from spark_tpu.physical import kernels as K
from spark_tpu.physical import operators as P
from spark_tpu.physical import stage
from spark_tpu.plan import logical as L
from spark_tpu.types import Field, Schema


def plan_physical(plan: L.LogicalPlan) -> P.PhysicalPlan:
    if isinstance(plan, L.Relation):
        return P.BatchScanExec(plan.batch)
    if isinstance(plan, L.Range):
        return P.RangeExec(plan.start, plan.end, plan.step, plan.col_name)
    if isinstance(plan, L.UnresolvedScan):
        return P.BatchScanExec(plan.source.read(plan.columns, plan.filters))
    if isinstance(plan, L.Project):
        return P.ProjectExec(plan.exprs, plan_physical(plan.child))
    if isinstance(plan, L.Filter):
        return P.FilterExec(plan.condition, plan_physical(plan.child))
    if isinstance(plan, L.Aggregate):
        return P.HashAggregateExec(plan.groupings, plan.aggregates,
                                   plan_physical(plan.child))
    if isinstance(plan, L.Sort):
        return P.SortExec(plan.orders, plan_physical(plan.child))
    if isinstance(plan, L.Limit):
        return P.LimitExec(plan.n, plan_physical(plan.child), plan.offset)
    if isinstance(plan, L.Distinct):
        cols = tuple(E.Col(n) for n in plan.schema.names)
        return P.HashAggregateExec(cols, cols, plan_physical(plan.child))
    if isinstance(plan, L.SubqueryAlias):
        return plan_physical(plan.child)
    if isinstance(plan, L.Repartition):
        # single-device: a no-op; the mesh executor re-plans it as an
        # exchange (parallel/exchange.py)
        return plan_physical(plan.child)
    if isinstance(plan, L.Sample):
        return P.SampleExec(plan.fraction, plan.seed,
                            plan_physical(plan.child),)
    if isinstance(plan, L.Window):
        from spark_tpu.physical.window import WindowExec

        return WindowExec(plan.window_exprs, plan_physical(plan.child))
    if isinstance(plan, L.Generate):
        return P.GenerateExec(plan.generator, plan.out_name,
                              plan.pos_name, plan_physical(plan.child))
    if isinstance(plan, L.Expand):
        return P.ExpandExec(plan.projections, plan.names,
                            plan_physical(plan.child))
    if isinstance(plan, L.Join):
        return P.JoinExec(plan_physical(plan.left), plan_physical(plan.right),
                          plan.how, plan.left_keys, plan.right_keys,
                          plan.condition)
    if isinstance(plan, L.Union):
        return P.UnionExec(plan_physical(plan.left), plan_physical(plan.right))
    raise NotImplementedError(f"no physical plan for {type(plan).__name__}")


# ---- stage-fused execution --------------------------------------------------

from spark_tpu.storage.lru import LruDict  # noqa: E402

#: bounded: spark.tpu.jit.stageCacheEntries (LRU beyond the cap; an
#: evicted plan recompiles on next use)
_STAGE_CACHE = LruDict("fused", CF.JIT_STAGE_CACHE_ENTRIES)


def _bind_adaptive(plan: P.PhysicalPlan) -> None:
    """Attach recorded runtime stats to join nodes (the re-optimization
    step of AQE, reference: AdaptiveSparkPlanExec.getFinalPhysicalPlan:247
    — here 'between executions' instead of 'between stages'). A join
    whose previous run on these exact leaf arrays proved a unique build
    side becomes traceable and fuses."""
    for c in plan.children():
        _bind_adaptive(c)
    if isinstance(plan, P.JoinExec) and plan.how in (
            "inner", "left", "left_semi", "left_anti") and plan.left_keys:
        sk = plan.stats_key()
        plan.adaptive = P._JOIN_STATS.get(sk)
        plan.index_scan = plan.table_scan = None
        plan.index_orient = None
        if plan.adaptive is not None:
            idx = P._JOIN_INDEX.get(sk)
            if idx is not None:
                orient, ib, tb = idx
                plan.index_scan = P.BatchScanExec(ib, aux=True)
                plan.table_scan = (P.BatchScanExec(tb, aux=True)
                                   if tb is not None else None)
                plan.index_orient = orient
    elif isinstance(plan, P.HashAggregateExec) and plan.groupings \
            and not plan._static_direct_ok():
        plan.adaptive = P._AGG_STATS.get(plan.stats_key())
    elif isinstance(plan, P.GenerateExec):
        plan.adaptive = P._GEN_STATS.get(plan.stats_key())


def _adaptive_snapshot(plan: P.PhysicalPlan,
                       scan_key=operator.methodcaller("plan_key")) -> tuple:
    """Adaptive state of every join in tree order — part of the fused
    stage cache key (plan_key alone is stable across stats changes).
    ``scan_key`` names an embedded index / table scan: its
    ``plan_key()`` for this process's stage cache."""
    out = []

    def go(p: P.PhysicalPlan) -> None:
        if isinstance(p, P.JoinExec):
            # index presence/shape changes the traced program but is
            # deliberately excluded from plan_key (stats identity)
            out.append((p.adaptive, p.index_orient,
                        None if p.index_scan is None
                        else scan_key(p.index_scan),
                        None if p.table_scan is None
                        else scan_key(p.table_scan)))
        elif isinstance(p, P.HashAggregateExec):
            # the group count sizes the output in 256-slot buckets and
            # is counted on the device: a count that moves inside its
            # bucket finds the stage it had
            out.append(p.sorted_slots)
        elif isinstance(p, P.GenerateExec):
            out.append(p.adaptive)
        elif isinstance(p, P.CompactExec):
            # plan_key is transparent for stats stability; the snapshot
            # carries the compaction so stage programs don't collide
            out.append(("compact", p.cap))
        for c in p.children():
            go(c)

    go(plan)
    return tuple(out)


def _stable_adaptive_snapshot(plan: P.PhysicalPlan) -> tuple:
    """_adaptive_snapshot for the cross-session executable store: the
    embedded index/table scan identities use the store's content-digest
    keys instead of plan_key() (whose hash(dicts) component is salted
    per process). Only computed on the fresh-stage-entry path."""
    from spark_tpu.compile.store import stable_plan_key

    return _adaptive_snapshot(plan, stable_plan_key)


def _run_fused(plan: P.PhysicalPlan) -> Batch:
    """Compile a maximal traceable subtree to one XLA program and run it.
    The jit cache is keyed on plan structure + leaf shapes/dictionaries
    (analogue of CodeGenerator.compile's generated-class cache,
    reference: codegen/CodeGenerator.scala:1442). Cached closures hold a
    leaf-stripped plan skeleton — leaf batch data arrives as arguments."""
    scans = stage.collect_leaves(plan, P.BatchScanExec)
    key = (plan.plan_key(), _adaptive_snapshot(plan))
    entry = _STAGE_CACHE.get(key)
    fresh = entry is None
    if fresh:
        entry = stage.build_stage(
            "fused", plan, P.BatchScanExec,
            tuple(s.batch.data for s in scans), name="stage_fn",
            extra=_stable_adaptive_snapshot(plan))
        _STAGE_CACHE[key] = entry
    jitted, schema_box = entry
    trace.count("program")
    if fresh:
        # first call traces + XLA-compiles (or loads from the
        # persistent disk cache — metrics.compile_cache_stats says
        # which); timing it makes warmup attributable
        import time

        from spark_tpu import metrics

        t0 = time.perf_counter()
        with trace.span("stage.dispatch", fresh=True):
            data = jitted(tuple(s.batch.data for s in scans))
        metrics.record("stage_compile", node=plan.node_string(),
                       ms=round((time.perf_counter() - t0) * 1e3, 2))
    else:
        # the enqueue alone: nothing waits for the device here (the
        # wait is measured where the host blocks anyway, in fetch_host)
        with trace.span("stage.dispatch"):
            data = jitted(tuple(s.batch.data for s in scans))
    return Batch(schema_box["schema"], data)


#: Observed inter-stage compaction capacities per (plan, leaf-ids):
#: 0 = "compaction not worthwhile here". Replayed as explicit
#: CompactExec nodes (see _replay_compactions) so fully-traced
#: re-executions see EXACTLY the same arrays the blocking run fed to
#: downstream operators — required for _JOIN_INDEX position validity,
#: and it keeps the traced pipeline at the shrunken capacity (AQE
#: coalescing, reference: CoalesceShufflePartitions.scala).
_COMPACT_STATS = P._AdaptiveStatsCache()


def _capacity_bucket() -> int:
    """Compaction capacities round up to
    spark.tpu.adaptive.capacityBucket (active-session conf; registry
    default 1024 reproduces the historical hard-coded multiple) — the
    same bucket adaptive exchanges use, so single-device and
    distributed re-traces share one small set of capacities and the
    jit stage caches stay hot."""
    try:
        from spark_tpu.api.session import SparkSession

        sess = SparkSession._active
        if sess is not None:
            return max(1, int(sess.conf.get(CF.ADAPTIVE_CAPACITY_BUCKET)))
    except Exception:
        pass
    return max(1, int(CF.ADAPTIVE_CAPACITY_BUCKET.default))


def _compact_to(batch: Batch, new_cap: int) -> Batch:
    """Route through CompactExec so the blocking-run compaction and the
    traced replay are structurally the SAME code — _JOIN_INDEX position
    validity depends on them producing bit-identical layouts."""
    node = P.CompactExec(P.BatchScanExec(batch), new_cap)
    return node.execute_blocking([batch])


def _maybe_compact(batch: Batch, child: P.PhysicalPlan) -> Batch:
    """Shrink sparse batches between stages so capacities don't cascade
    (the reference's equivalent pressure valve is AQE partition
    coalescing, CoalesceShufflePartitions.scala). The decision is
    recorded per (plan, leaves) and replayed inside later traced
    executions — see _COMPACT_STATS."""
    cap = batch.capacity
    if cap <= 4096 or isinstance(child, P.BatchScanExec):
        return batch
    sk = child.stats_key()
    new_cap = _COMPACT_STATS.get(sk)
    if new_cap is None:
        if not P.stats_recording():
            return batch  # single-shot plan: skip the sizing sync
        live = int(np.asarray(batch.data.row_mask).sum())  # host sync
        new_cap = K.bucket(live, _capacity_bucket()) \
            if live * 4 <= cap else 0
        _COMPACT_STATS.put(sk, new_cap)
    if not new_cap or new_cap >= cap:
        return batch
    return _compact_to(batch, new_cap)


def _replay_compactions(plan: P.PhysicalPlan) -> P.PhysicalPlan:
    """Insert explicit CompactExec nodes where blocking runs compacted,
    so fused traces reproduce the identical intermediate arrays."""
    if isinstance(plan, P.BatchScanExec):
        return plan

    def replay(child: P.PhysicalPlan) -> P.PhysicalPlan:
        if isinstance(child, P.BatchScanExec):
            return child
        child = _replay_compactions(child)
        cap = _COMPACT_STATS.get(child.stats_key())
        return P.CompactExec(child, cap) if cap else child

    return plan.map_children(replay)


#: Observed live output rows per (plan, leaf-array-ids): re-executions
#: compact the result to bucket(live) ON DEVICE before the host fetch
#: (see P.CompactExec). Sound for the same reason join/agg stats replay
#: is: same immutable leaves + same plan => same live count.
_OUTPUT_STATS = P._AdaptiveStatsCache()


def _bind(plan: P.PhysicalPlan):
    """The last of planning: replay the recorded compactions, attach
    the recorded runtime stats, look the output capacity up. Returns
    (plan, its stats key, the recorded output capacity or None)."""
    plan = _replay_compactions(plan)
    _bind_adaptive(plan)
    sk = plan.stats_key()
    return plan, sk, _OUTPUT_STATS.get(sk)


def _run_bound(physical: P.PhysicalPlan, plan: P.PhysicalPlan, sk,
               cap) -> Batch:
    """Run a bound physical plan: fuse what we can, block where we
    must. An execution with no recorded output capacity is a first one:
    it records that capacity (``_first_execution``) and a ``first_exec``
    build event of the compiled programs it ran and the sorts it built."""
    if cap is not None:
        return _execute(P.CompactExec(plan, cap))
    if not P.stats_recording():
        return _execute(plan)
    with trace.tally() as counts:
        batch, bound = _first_execution(physical, plan, sk)
    trace.built("first_exec", programs=counts["program"],
                sorts=counts["sort"], bound=bound)
    return batch


def _first_execution(physical: P.PhysicalPlan, plan: P.PhysicalPlan, sk):
    """``physical`` is the plan before ``_bind``: a first execution
    whose blocking operators all block for want of their stats alone
    records them (``_size``), binds the plan again, and runs it as the
    one stage every later execution runs, where the plan's operators
    bound its output within one capacity bucket (``_rows_bound``).
    Returns the batch and what bounded it (``none`` where nothing did,
    and the live rows were counted for the next execution)."""
    if not stage.fully_traceable(plan, P.BatchScanExec) \
            and _becomes_traceable(plan):
        _size(plan)
        plan, sk, cap = _bind(physical)
        rows, bound = _rows_bound(plan) or (None, "none")
        if cap is None and rows is not None and rows <= _capacity_bucket():
            # what the first execution would record for any live count
            cap = _capacity_bucket()
            _OUTPUT_STATS.put(sk, cap)
        if cap is not None:
            return _execute(P.CompactExec(plan, cap)), bound
    batch = _execute(plan)
    live = int(np.asarray(batch.data.row_mask).sum())  # 1st run only
    _OUTPUT_STATS.put(sk, K.bucket(live, _capacity_bucket()))
    return batch, "none"


def _wants_stats(plan: P.PhysicalPlan) -> bool:
    """Blocks for want of runtime stats alone: traceable once sized."""
    return ((isinstance(plan, P.JoinExec) and plan.wants_join_size)
            or (isinstance(plan, P.HashAggregateExec)
                and plan.wants_group_count))


def _becomes_traceable(plan: P.PhysicalPlan) -> bool:
    """Would ``plan`` be one stage once every operator that blocks for
    want of its stats has them?"""
    if isinstance(plan, P.BatchScanExec):
        return True
    return ((plan.traceable or _wants_stats(plan))
            and not plan.has_blocking_exprs()
            and all(_becomes_traceable(c) for c in plan.children()))


def _rows_bound(plan: P.PhysicalPlan):
    """(the most live rows ``plan`` can return, which operator says so)
    where its operators alone say so: a LIMIT (``limit``), an aggregate
    without keys (``keyless``) or one whose keys' slots are known when
    it is traced (``direct``); None elsewhere."""
    if isinstance(plan, P.LimitExec):
        rows, bound = _rows_bound(plan.child) or (plan.n, "limit")
        return min(plan.n, rows), bound if rows < plan.n else "limit"
    if isinstance(plan, P.HashAggregateExec):
        if not plan.groupings:
            return 1, "keyless"
        slots = plan.static_slots()
        return None if slots is None else (slots, "direct")
    if isinstance(plan, (P.ProjectExec, P.FilterExec, P.SortExec,
                         P.CompactExec)):
        return _rows_bound(plan.child)
    return None


def _size(plan: P.PhysicalPlan) -> None:
    """Record the runtime stats of every operator under ``plan`` that
    blocks for want of them, bottom up (``_size_join``,
    ``_sized_aggregate``). The stages that compute a sized operator's
    inputs run; the sized operator itself does not: the plan it is bound
    into runs next, whole."""
    if stage.fully_traceable(plan, P.BatchScanExec):
        return
    if not _wants_stats(plan):
        for c in plan.children():
            _size(c)
        return
    _sized(plan, [_maybe_compact(_execute(c), c) for c in plan.children()])


def _sized(plan: P.PhysicalPlan, batches) -> P.PhysicalPlan:
    """``plan`` (``_wants_stats``) sized over its children's batches:
    its stats recorded, and it bound to them over scans of the batches."""
    if isinstance(plan, P.JoinExec):
        return _size_join(plan, batches)
    return _sized_aggregate(plan, batches[0])


def _host_row(plan: P.PhysicalPlan) -> dict:
    """Run a one-row stage; its columns as Python ints (one host sync)."""
    batch = _execute(plan)
    values = jax.device_get([c.data for c in batch.data.columns])
    return {f.name: int(v[0]) for f, v in zip(batch.schema.fields, values)}


def _key_packings(join: P.JoinExec, ranges: dict) -> tuple:
    """Per key, its (offset, range) from the observed ranges: a
    dictionary string key by its codes, an integer key by
    ``K.key_packing``."""
    return tuple((0, ranges[f"hi{i}"] + 1) if string
                 else K.key_packing(ranges[f"lo{i}"], ranges[f"hi{i}"])
                 for i, string in enumerate(join.key_is_string()))


def _packing(keys: tuple):
    """The keys packed together, or ``"hash"`` where the packed domain
    would pass 62 bits."""
    total = 1
    for _, rg in keys:
        total *= rg
        if total > (1 << 62):
            return "hash"
    return keys


def _lookup(sides: P.JoinExec, keys: tuple, packing):
    """A multi-key join whose packed keys take no dense table (hashed,
    or a domain past ``K.JOIN_TABLE_MAX``) and whose build side is
    unique on one key alone, whose own domain does: that key's index and
    the join's counts looked up by it, its other keys packed into an
    int32 code and tested on the row it finds (``K.lookup_join_ranges``:
    no sort, and no index to record). Keys are tried widest first, since
    a key narrower than the build side cannot be unique on it. None
    where no key alone is."""
    domain = P.packed_domain(packing)
    if len(keys) < 2 or (domain is not None and domain <= K.JOIN_TABLE_MAX):
        return None
    for i in sorted(range(len(keys)), key=lambda i: -keys[i][1]):
        rest = P.packed_domain(tuple(k for j, k in enumerate(keys) if j != i))
        if keys[i][1] > K.JOIN_TABLE_MAX or rest >= 1 << 31:
            continue
        counted = _host_row(P.JoinSizeExec(sides, keys, i))
        if counted["probe_max"] <= 1:
            return i, counted
    return None


def _aux_batch(named) -> Batch:
    fields = tuple(Field(name, T.INT32 if a.dtype == jnp.int32 else T.INT64,
                         nullable=False) for name, a in named)
    mask = jnp.ones((named[0][1].shape[0],), dtype=jnp.bool_)
    return Batch(Schema(fields), BatchData(
        tuple(ColumnData(a, None) for _, a in named), mask))


_DENSE_JOIN_TABLES = jax.jit(K.dense_join_tables, static_argnums=1)


def _size_join(plan: P.JoinExec, batches) -> P.JoinExec:
    """The first execution of an equi-join, sized in compiled programs
    where it ran operation by operation, some eighty programs each XLA
    sort among which the chip's compiler takes tens of seconds over
    (ROADMAP A2): the keys' ranges (a host sync packs them), the matches
    (the host sync that sizes the output and finds a unique side; for a
    multi-key join past the dense tables, first looked up by one key at
    a time, ``_lookup``), the build side's index. Records the stats and
    the index for re-executions of these leaves (``_bind_adaptive``) and
    hands back the join bound to them over its children's batches: a
    traceable stage."""
    sides = dataclasses.replace(plan, left=P.BatchScanExec(batches[0]),
                                right=P.BatchScanExec(batches[1]))
    sk = plan.stats_key()
    span = trace.span("join.size", how=plan.how)
    with span:
        keys = _key_packings(plan, _host_row(P.JoinSizeExec(sides)))
        packing = _packing(keys)
        lookup = _lookup(sides, keys, packing)
        if lookup is not None:
            # at most one candidate a probe row, by the key the build
            # side is unique on; the expansion is sized by the full keys
            i, counted = lookup
            packing = keys
            stats = P.JoinStats(keys, True, False,
                                K.relative_bucket(counted["matches"]), True,
                                i)
        else:
            counted = _host_row(P.JoinSizeExec(sides, packing))
            unique_build = counted["probe_max"] <= 1
            if plan.how in ("left_semi", "left_anti") \
                    and plan.condition is None and packing != "hash":
                stats = P.JoinStats(packing, unique_build, False, None, True)
            else:
                stats = P.JoinStats(packing, unique_build,
                                    counted["build_max"] <= 1,
                                    K.relative_bucket(counted["matches"]),
                                    True)
        sides.adaptive = stats
        lcap, rcap = batches[0].capacity, batches[1].capacity
        _, orient = sides._strategy(stats.unique_build, stats.unique_probe,
                                    stats.cap, lcap, rcap)
        span.attrs.update(rows=counted["matches"],
                          capacity=sides.output_capacity(lcap, rcap))
        P._JOIN_STATS.put(sk, stats)
        if lookup is None and plan.index_layout_stable(orient):
            ib = _execute(P.JoinIndexExec(sides, packing, orient=orient))
            tb = None
            domain = P.packed_domain(packing)
            if domain is not None and domain <= K.JOIN_TABLE_MAX:
                lo, cnt = _DENSE_JOIN_TABLES(ib.data.columns[1].data,
                                             domain)
                tb = _aux_batch((("lo", lo), ("cnt", cnt)))
            P._JOIN_INDEX.put(sk, (orient, ib, tb))
            sides.index_scan = P.BatchScanExec(ib, aux=True)
            sides.table_scan = (None if tb is None
                                else P.BatchScanExec(tb, aux=True))
            sides.index_orient = orient
    return sides


def _sized_aggregate(plan: P.HashAggregateExec,
                     batch: Batch) -> P.HashAggregateExec:
    """The first execution of an aggregate whose keys have no trace-time
    cardinality: count its groups in one compiled program (the host
    sync that sizes the output), record the count for re-executions of
    these leaves (``_bind_adaptive``), and hand back the aggregate bound
    to it over its child's batch — a traceable stage."""
    scan = P.BatchScanExec(batch)
    counted = _execute(P.GroupCountExec(plan.groupings, scan))
    groups = max(1, int(np.asarray(counted.data.columns[0].data)[0]))
    P._AGG_STATS.put(plan.stats_key(), groups)
    return dataclasses.replace(plan, child=scan, adaptive=groups)


def _execute(plan: P.PhysicalPlan) -> Batch:
    from spark_tpu import metrics

    if isinstance(plan, P.BatchScanExec):
        return plan.batch
    if stage.fully_traceable(plan, P.BatchScanExec):
        with trace.span("stage.run", op="fused"), \
                metrics.stage_timer("fused", node=plan.node_string()):
            return _run_fused(plan)
    child_batches = []
    for c in plan.children():
        b = _execute(c)
        child_batches.append(_maybe_compact(b, c))
    if _wants_stats(plan):
        return _execute(_sized(plan, child_batches))
    with trace.span("stage.run", op=type(plan).__name__), \
            metrics.stage_timer("blocking", node=plan.node_string(),
                                cap_in=[b.capacity
                                        for b in child_batches]):
        return plan.execute_blocking(child_batches)


def execute_logical(plan: L.LogicalPlan, optimize: bool = True) -> Batch:
    from spark_tpu.plan.optimizer import optimize as opt

    if optimize:
        with trace.span("query.optimize"):
            plan = opt(plan)
    # one span from the logical plan to the bound physical one; it
    # closes before the first stage runs
    with trace.span("query.plan"):
        physical = plan_physical(plan)
        bound = _bind(physical)
    return _run_bound(physical, *bound)


def execute_logical_on(session, plan: L.LogicalPlan,
                       optimize: bool = True) -> Batch:
    """THE place that picks the engine: the session's mesh executor
    when it runs under a mesh master, else (or with no session) the
    one-chip planner above."""
    ex = getattr(session, "mesh_executor", None)
    if ex is not None:
        return ex.execute_logical(plan, optimize)
    return execute_logical(plan, optimize)
