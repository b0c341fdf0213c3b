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

import numpy as np

from spark_tpu import conf as CF
from spark_tpu import trace
from spark_tpu.columnar.batch import Batch
from spark_tpu.expr import expressions as E
from spark_tpu.physical import kernels as K
from spark_tpu.physical import operators as P
from spark_tpu.physical import stage
from spark_tpu.plan import logical as L


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


def _run_bound(plan: P.PhysicalPlan, sk, cap) -> Batch:
    """Run a bound physical plan: fuse what we can, block where we
    must."""
    if cap is not None:
        return _execute(P.CompactExec(plan, cap))
    batch = _execute(plan)
    if P.stats_recording():
        live = int(np.asarray(batch.data.row_mask).sum())  # 1st run only
        _OUTPUT_STATS.put(sk, K.bucket(live, _capacity_bucket()))
    return batch


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
    if isinstance(plan, P.HashAggregateExec) and plan.wants_group_count:
        return _execute(_sized_aggregate(plan, child_batches[0]))
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
        bound = _bind(plan_physical(plan))
    return _run_bound(*bound)


def execute_logical_on(session, plan: L.LogicalPlan,
                       optimize: bool = True) -> Batch:
    """THE place that picks the engine: the session's mesh executor
    when it runs under a mesh master, else (or with no session) the
    one-chip planner above."""
    ex = getattr(session, "mesh_executor", None)
    if ex is not None:
        return ex.execute_logical(plan, optimize)
    return execute_logical(plan, optimize)
