"""The mesh executor: distributed planning + SPMD stage execution.

Replaces the whole reference control stack for a query — DAGScheduler
stage graph, TaskScheduler offers, executor task launch RPC, shuffle
fetch (reference: scheduler/DAGScheduler.scala:121 submitStage:1355,
TaskSchedulerImpl.scala:249, CoarseGrainedSchedulerBackend.scala:398) —
with: cut the plan at join boundaries, compile each cut to ONE
shard_map/jit SPMD program (exchanges ride inside as collectives), run
the programs in dependency order. "Task launch" is a single XLA
dispatch; there is nothing to serialize, offer, or fetch.

Join sizing follows the AQE pattern (reference:
adaptive/AdaptiveSparkPlanExec.scala:247 — materialize, look at stats,
re-plan): a stats pass gets key ranges, a count pass sizes the pair
capacity, then the join stage runs with static shapes.
"""

from __future__ import annotations

import contextvars
import dataclasses
import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from spark_tpu import conf as CF
from spark_tpu import trace as _trace
from spark_tpu import types as T
from spark_tpu.columnar.batch import Batch
from spark_tpu.expr import expressions as E
from spark_tpu.parallel import operators as D
from spark_tpu.parallel.mesh import DATA_AXIS, mesh_size
from spark_tpu.parallel.sharded import MeshResult, ShardedBatch
from spark_tpu.physical import kernels as K
from spark_tpu.physical import operators as P
from spark_tpu.physical import stage
from spark_tpu.physical.operators import Pipe
from spark_tpu.plan import logical as L
from spark_tpu.types import Schema

_SPEC = PartitionSpec(DATA_AXIS)

#: jit cache for stage programs, keyed on (plan structure, mesh shape,
#: platform) — the CodeGenerator.compile cache analogue. Bounded:
#: spark.tpu.jit.stageCacheEntries, LRU beyond the cap.
from spark_tpu.storage.lru import LruDict  # noqa: E402

_DIST_STAGE_CACHE = LruDict("dist", CF.JIT_STAGE_CACHE_ENTRIES)

#: OOM-degradation override (recovery.py): a run that OOMed with
#: adaptive execution off retries once with it forced on — measured
#: post-exchange compaction is the cheapest rung of the ladder, ahead
#: of chunked re-planning. Contextvar, not conf: the retry must not
#: leak into concurrently scheduled queries sharing the session conf.
FORCE_ADAPTIVE: contextvars.ContextVar = contextvars.ContextVar(
    "spark_tpu_force_adaptive", default=False)


#: the ONE HLL estimator (spark_tpu/sketch.py) — re-exported here so
#: existing callers (tests, physical/chunked.py historically) keep
#: resolving executor.hll_estimate
from spark_tpu.sketch import hll_estimate  # noqa: E402,F401

#: exchange kinds the AQE pass cuts into separate stages (broadcast /
#: single-partition exchanges use the all_gather data plane — there is
#: no (D, cap) routing buffer to shrink, so they stay fused)
_ADAPTIVE_EXCHANGES = (D.HashPartitionExchangeExec,
                       D.RoundRobinExchangeExec,
                       D.RangeExchangeExec)


def _exchange_op(ex: P.PhysicalPlan) -> str:
    if isinstance(ex, D.HashPartitionExchangeExec):
        return "hash"
    if isinstance(ex, D.RangeExchangeExec):
        return "range"
    if isinstance(ex, D.RoundRobinExchangeExec):
        return "roundrobin"
    return type(ex).__name__


def _count_exchange_nodes(plan: P.PhysicalPlan) -> int:
    n = int(isinstance(plan, _ADAPTIVE_EXCHANGES + (
        D.BroadcastExchangeExec, D.SinglePartitionExchangeExec)))
    return n + sum(_count_exchange_nodes(c) for c in plan.children())


def _exactly_remergeable(consumer: "D.DistSortAggExec",
                         schema: Schema) -> bool:
    """True when the consumer's aggregate list can be re-applied to its
    own output byte-identically — the precondition for the skew fan's
    pre-merge. The rule set (integer Sum associative under wraparound,
    non-float Min/Max order-free, everything else illegal) is shared
    with the static analyzer and incremental merges: see
    analysis/legality.py."""
    from spark_tpu.analysis import legality

    return bool(legality.remerge_verdict_cols(consumer.aggregates,
                                              schema))


def _project_sorted_by(sorted_by, exprs):
    """Translate a ShardedBatch ``sorted_by`` guarantee through a
    row-wise projection: every ordered column must survive (as a bare
    Col or Alias(Col)) under its projected name, else the guarantee is
    dropped — a partial translation would let a later sort elide on a
    prefix whose tie order the static plan resolves differently."""
    if not sorted_by:
        return None
    out = []
    for name, asc, nf in sorted_by:
        for e in exprs:
            c = E.strip_alias(e)
            if isinstance(c, E.Col) and c.col_name == name:
                out.append((e.name, asc, nf))
                break
        else:
            return None
    return tuple(out)


def _sorted_by_satisfies(sorted_by, orders) -> bool:
    """True when a batch's ``sorted_by`` guarantee makes a global sort
    by ``orders`` a no-op. Requires an EXACT pairwise match over the
    full tuple (bare Col orders, same ascending/nulls placement): equal
    length means the order is total over the guaranteed columns — on
    unique-key aggregate output there are no ties left for the skipped
    sort to break differently from the static plan."""
    if not sorted_by or len(orders) != len(sorted_by):
        return False
    for o, (name, asc, nf) in zip(orders, sorted_by):
        c = E.strip_alias(o.child)
        if not (isinstance(c, E.Col) and c.col_name == name):
            return False
        if bool(o.ascending) != bool(asc) \
                or bool(o.nulls_first_resolved) != bool(nf):
            return False
    return True


class _FusionOverflow(Exception):
    """A speculative fused program sliced off live rows (sentinel mask
    bit set): the load was genuinely skewed past the ladder anchor.
    The result is discarded and the staged path re-runs — byte
    identity is preserved, at double cost for the rare skewed query."""


class _FusionBailout(Exception):
    """A whole-query fusion attempt hit a decision that genuinely
    needs the host (typed ``reason`` lands in the ``fusion_bailout``
    metric event); execution degrades to the staged adaptive path."""

    def __init__(self, reason: str, detail: str = ""):
        self.reason = reason
        self.detail = detail
        super().__init__(reason)


def _collect_fused(plan: P.PhysicalPlan,
                   out: List["D.FusedSpanExec"]) -> None:
    if isinstance(plan, D.FusedSpanExec):
        out.append(plan)
        for t in plan.tail:  # merged chains nest further pairs here
            if isinstance(t, D.FusedSpanExec):
                out.append(t)
    for c in plan.children():
        _collect_fused(c, out)


def _walk_plan(plan: P.PhysicalPlan):
    yield plan
    for c in plan.children():
        yield from _walk_plan(c)


@dataclass(eq=False)
class _CompactExec(P.PhysicalPlan):
    """Shrink per-device capacity to a host-chosen static size (live rows
    compact to the front). The pressure valve between stages —
    CoalesceShufflePartitions analogue.

    ``sliced`` is the fast path for outputs whose live rows already sit
    within the first ``new_capacity`` slots on every device (exchange
    and fused-span outputs are front-compacted by construction — the
    compaction inside the exchange and the consumer both emit live rows
    first, and worst-case padding only appends dead rows). A plain
    slice then replaces the O(p log p) stable argsort over the PADDED
    capacity with an O(new_capacity) copy; live-row order is untouched,
    so the result is byte-identical. The caller proves slice-safety
    from the mask readback it already does (_maybe_compact)."""

    new_capacity: int
    child: P.PhysicalPlan
    sliced: bool = False
    traceable = True

    def children(self):
        return (self.child,)

    @property
    def schema(self) -> Schema:
        return self.child.schema

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        from spark_tpu.expr.compiler import TV

        pipe = child_pipes[0]
        if self.sliced:
            cols = {
                name: TV(tv.data[: self.new_capacity],
                         None if tv.validity is None
                         else tv.validity[: self.new_capacity],
                         tv.dtype, tv.dictionary)
                for name, tv in pipe.cols.items()
            }
            return Pipe(cols, pipe.mask[: self.new_capacity], pipe.order)
        perm = K.compaction_permutation(pipe.mask)
        idx = perm[: self.new_capacity]
        cols = {
            name: TV(tv.data[idx],
                     None if tv.validity is None else tv.validity[idx],
                     tv.dtype, tv.dictionary)
            for name, tv in pipe.cols.items()
        }
        return Pipe(cols, pipe.mask[idx], pipe.order)

    def plan_key(self):
        return ("Compact", self.new_capacity, self.sliced,
                self.child.plan_key())


def _row_width(schema: Schema) -> int:
    """Device bytes per row (data + validity) from the schema."""
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


def _estimated_bytes(sb) -> int:
    """Estimated device bytes of a join build side: total capacity x
    per-row width from the schema (the size estimate the reference takes
    from plan statistics, SizeInBytesOnlyStatsPlanVisitor)."""
    return int(sb.capacity) * _row_width(sb.schema)


def _decode_key_value(raw, field):
    """Device key value -> python literal (host side of the hot-key
    detection pass): dictionary codes decode to strings, dates/decimals
    to their python types, everything else to plain ints/floats."""
    if field.dictionary is not None:
        code = int(raw)
        return (field.dictionary[code]
                if 0 <= code < len(field.dictionary) else None)
    if isinstance(field.dtype, T.DateType):
        return T.days_to_date(int(raw))
    if isinstance(field.dtype, T.DecimalType):
        import decimal

        return decimal.Decimal(int(raw)).scaleb(-field.dtype.scale)
    if hasattr(raw, "item"):
        return raw.item()
    return raw


def _hot_key_pred(keys, hot) -> E.Expression:
    """OR over hot candidates of AND(key == literal)."""
    ors = None
    for vals in hot:
        ands = None
        for k, v in zip(keys, vals):
            c = E.Cmp("==", k, E.Literal(v))
            ands = c if ands is None else E.And(ands, c)
        ors = ands if ors is None else E.Or(ors, ands)
    return ors


def _null_any(keys) -> E.Expression:
    out = None
    for k in keys:
        c = E.IsNull(k)
        out = c if out is None else E.Or(out, c)
    return out


class MeshExecutor:
    """Plans and runs logical plans over a device mesh."""

    def __init__(self, mesh: Mesh, broadcast_threshold: Optional[int] = None,
                 conf=None):
        from spark_tpu import conf as _conf

        self.mesh = mesh
        self.d = mesh_size(mesh)
        self.conf = conf if conf is not None else _conf.RuntimeConf()
        #: bytes under which a join build side is broadcast (reference:
        #: SQLConf spark.sql.autoBroadcastJoinThreshold, in BYTES). The
        #: legacy row-count argument overrides when given (tests).
        self.broadcast_threshold = broadcast_threshold
        # weak keys: entries die with their Batch, and a live entry pins
        # its key so the mapping can never alias a recycled object
        import weakref

        self._relation_cache = weakref.WeakKeyDictionary()

    # ---- public entry points -----------------------------------------------

    def execute_logical(self, plan: L.LogicalPlan,
                        optimize: bool = True) -> Batch:
        from spark_tpu.plan.optimizer import optimize as opt

        if optimize:
            with _trace.span("query.optimize"):
                plan = opt(plan)
        with _trace.span("query.plan"):
            physical = self.plan(plan)
        # the result stays sharded: its fetch packs on the mesh and the
        # copies to the host gather the shards (span ``fetch.copy
        # op=gather``); its ``data`` gathers to the first device
        return MeshResult(self.run(physical))

    # ---- logical -> distributed physical -----------------------------------

    def plan(self, plan: L.LogicalPlan) -> P.PhysicalPlan:
        d = self.d
        if isinstance(plan, L.Relation):
            return D.ShardScanExec(self._shard_relation(plan.batch))
        if isinstance(plan, L.UnresolvedScan):
            return D.ShardScanExec(self._shard_relation(
                plan.source.read(plan.columns, plan.filters)))
        if isinstance(plan, L.Range):
            n = plan.num_rows
            p = K.bucket(math.ceil(max(1, n) / d), 128)
            return D.DistRangeExec(plan.start, plan.end, plan.step, n, p,
                                   plan.col_name)
        if isinstance(plan, L.Project):
            return P.ProjectExec(plan.exprs, self.plan(plan.child))
        if isinstance(plan, L.Filter):
            return P.FilterExec(plan.condition, self.plan(plan.child))
        if isinstance(plan, L.Sample):
            return D.DistSampleExec(plan.fraction, plan.seed,
                                    self.plan(plan.child))
        if isinstance(plan, L.Aggregate):
            return self._plan_aggregate(plan.groupings, plan.aggregates,
                                        self.plan(plan.child))
        if isinstance(plan, L.Distinct):
            cols = tuple(E.Col(n) for n in plan.schema.names)
            return self._plan_aggregate(cols, cols, self.plan(plan.child))
        if isinstance(plan, L.Sort):
            child = self.plan(plan.child)
            return P.SortExec(plan.orders,
                              D.RangeExchangeExec(plan.orders, child))
        if isinstance(plan, L.Limit):
            return D.DistLimitExec(plan.n, plan.offset, self.plan(plan.child))
        if isinstance(plan, L.SubqueryAlias):
            return self.plan(plan.child)
        if isinstance(plan, L.Repartition):
            child = self.plan(plan.child)
            if plan.keys:
                return D.HashPartitionExchangeExec(plan.keys, child)
            return D.RoundRobinExchangeExec(child)
        if isinstance(plan, L.Union):
            return P.UnionExec(self.plan(plan.left), self.plan(plan.right))
        if isinstance(plan, L.Join):
            return D.DistJoinBoundary(self.plan(plan.left),
                                      self.plan(plan.right), plan.how,
                                      plan.left_keys, plan.right_keys,
                                      plan.condition)
        if isinstance(plan, L.Window):
            # hash-exchange on the partition keys so every partition
            # lives whole on one device, then the ordinary local window
            # operator (reference: WindowExec.scala:87
            # requiredChildDistribution = ClusteredDistribution;
            # EnsureRequirements inserts the same shuffle)
            from spark_tpu.physical.window import WindowExec

            child = self.plan(plan.child)
            # exchanging on the key SET co-locates partitions for every
            # spec that uses the same keys in any order (the local
            # operator re-groups per spec anyway). DIFFERENT key sets
            # chain: one exchange + local window PER set, later stages
            # running over the previous stage's output — the same
            # cascade EnsureRequirements produces for mixed window
            # specs (WindowExec.scala:87 ClusteredDistribution)
            groups: list = []  # (frozen key set, keys, [exprs])
            for e in plan.window_exprs:
                p = E.strip_alias(e).partition_by
                fs = frozenset(E.expr_key(k) for k in p)
                for g in groups:
                    if g[0] == fs:
                        g[2].append(e)
                        break
                else:
                    groups.append((fs, p, [e]))
            cur = child
            for _, keys, exprs in groups:
                ex = (D.HashPartitionExchangeExec(tuple(keys), cur)
                      if keys else D.SinglePartitionExchangeExec(cur))
                cur = WindowExec(tuple(exprs), ex)
            if len(groups) > 1:
                # restore the logical output column order (window cols
                # were appended per chained stage)
                cur = P.ProjectExec(
                    tuple(E.Col(n) for n in plan.schema.names), cur)
            return cur
        raise NotImplementedError(
            f"no distributed plan for {type(plan).__name__}")

    def _plan_aggregate(self, groupings, aggregates,
                        child: P.PhysicalPlan) -> P.PhysicalPlan:
        from spark_tpu.physical.operators import rewrite_agg_outputs

        _, agg_calls = rewrite_agg_outputs(groupings, aggregates)
        distinct_aggs = [a for a in agg_calls
                         if getattr(a, "distinct", False)]
        if distinct_aggs and not groupings:
            # Global DISTINCT: exchange on the distinct child so each
            # value lives on exactly one device, then psum the deduped
            # partials (reference: RewriteDistinctAggregates.scala:1
            # plans an extra shuffle level; here it is one hash
            # exchange). All DISTINCT aggs must share one child set.
            key_sets = {tuple(E.expr_key(c) for c in a.children())
                        for a in distinct_aggs}
            if len(key_sets) > 1:
                # SPLIT per distinct child set (the reference rewrites
                # through an Expand, RewriteDistinctAggregates.scala:1;
                # here each set gets its OWN exchange+psum sub-aggregate
                # and the 1-row results cross-join back together)
                return self._plan_multi_distinct(groupings, aggregates,
                                                 agg_calls, child)
            ex = D.HashPartitionExchangeExec(
                tuple(distinct_aggs[0].children()), child)
            return D.PSumAggExec(groupings, aggregates, ex)
        probe = P.HashAggregateExec(groupings, aggregates, child)
        if not distinct_aggs and (probe._static_direct_ok() or not groupings):
            # no shuffle: local partial + psum merge
            return D.PSumAggExec(groupings, aggregates, child)
        if not distinct_aggs:
            # map-side combine (reference: AggUtils partial/final split):
            # local partial aggregation BEFORE the exchange collapses a
            # hot key to ONE row per device — a 90%-one-key distribution
            # exchanges D rows instead of the whole table (the skew
            # guard OptimizeSkewedJoin provides for joins).
            from spark_tpu.plan.incremental import AggSpec

            try:
                spec = AggSpec(tuple(groupings), tuple(aggregates))
            except NotImplementedError:
                spec = None
            if spec is not None:
                key_aliases = tuple(
                    E.Alias(g, n) for g, n
                    in zip(spec.groupings_exec, spec.key_names))
                partial = D.DistSortAggExec(
                    tuple(spec.groupings_exec),
                    key_aliases + tuple(spec.partials), child,
                    phase="partial")
                ex = D.HashPartitionExchangeExec(
                    tuple(E.Col(n) for n in spec.key_names), partial)
                key_cols = tuple(E.Col(n) for n in spec.key_names)
                final = D.DistSortAggExec(
                    key_cols,
                    tuple(E.Alias(E.Col(n), n) for n in spec.key_names)
                    + tuple(spec.merges), ex)
                return P.ProjectExec(tuple(spec.outputs), final)
        # exchange on the grouping keys -> whole groups (and for DISTINCT
        # all their values) live on one device; local sort-agg is exact.
        ex = D.HashPartitionExchangeExec(tuple(groupings), child)
        return D.DistSortAggExec(groupings, aggregates, ex)

    def _plan_multi_distinct(self, groupings, aggregates, agg_calls,
                             child: P.PhysicalPlan) -> P.PhysicalPlan:
        """Global aggregate mixing DISTINCT aggregates over DIFFERENT
        columns (and any non-distinct aggregates): one exchange+psum
        sub-aggregate per distinct child set, cross-joined 1-row
        results, final projection restoring the output expressions
        (reference: RewriteDistinctAggregates.scala:1 Expand rewrite)."""
        from spark_tpu.physical.operators import rewrite_agg_outputs

        outputs, _ = rewrite_agg_outputs(groupings, aggregates)
        buckets: dict = {}  # child-key-set (or None) -> [(idx, call)]
        for i, call in enumerate(agg_calls):
            k = (tuple(E.expr_key(c) for c in call.children())
                 if getattr(call, "distinct", False) else None)
            buckets.setdefault(k, []).append((i, call))
        sub_plans = []
        for k, items in buckets.items():
            aliases = tuple(E.Alias(call, f"__agg{i}")
                            for i, call in items)
            if k is None:
                sub_plans.append(D.PSumAggExec((), aliases, child))
            else:
                ex = D.HashPartitionExchangeExec(
                    tuple(items[0][1].children()), child)
                sub_plans.append(D.PSumAggExec((), aliases, ex))
        combined = sub_plans[0]
        for sp in sub_plans[1:]:
            combined = D.DistJoinBoundary(combined, sp, "cross",
                                          (), (), None)
        return P.ProjectExec(tuple(outputs), combined)

    def _shard_relation(self, batch) -> ShardedBatch:
        if isinstance(batch, ShardedBatch):
            # already globally placed (multi-host addressable-shard
            # feeding, multihost.sharded_batch_from_local): every
            # process contributed its OWN rows — no host gathering, no
            # single-process placement assumptions
            return batch
        sb = self._relation_cache.get(batch)
        if sb is None:
            sb = ShardedBatch.from_batch(batch, self.mesh)
            self._relation_cache[batch] = sb
        return sb

    # ---- execution ----------------------------------------------------------

    def run(self, plan: P.PhysicalPlan) -> ShardedBatch:
        plan = self._materialize_boundaries(plan)
        if self._adaptive_enabled():
            if self._fusion_enabled():
                fused = self._try_fuse(plan)
                if fused is not None:
                    sb = self._run_fused(*fused)
                    if sb is not None:
                        return sb
                    # speculative overflow: fall through to staged
            plan = self._materialize_exchanges(plan)
        if isinstance(plan, D.ShardScanExec):
            return plan.sharded
        if not stage.fully_traceable(plan, D.ShardScanExec):
            raise NotImplementedError(
                "plan contains host-only (arrow UDF) expressions, which "
                "the mesh executor cannot trace; run on the "
                "single-device engine or use a jax UDF:\n"
                + plan.tree_string())
        return self._run_stage(plan)

    def _adaptive_enabled(self) -> bool:
        if FORCE_ADAPTIVE.get():
            return True
        try:
            return bool(self.conf.get(CF.ADAPTIVE_ENABLED))
        except Exception:
            return False

    def _fusion_enabled(self) -> bool:
        try:
            return bool(self.conf.get(CF.FUSION_ENABLED))
        except Exception:
            return False

    # ---- whole-query native fusion ------------------------------------------

    def _try_fuse(self, plan: P.PhysicalPlan):
        """Tentpole of the whole-query fusion pass: when every adaptive
        exchange in ``plan`` pairs with a consumer whose ONLY host
        dependency is the capacity stats fetch, rewrite the pairs into
        FusedSpanExec nodes so the whole multi-exchange plan compiles
        and runs as ONE XLA program with zero inter-stage host sync
        (the on-device lax.switch over the capacity ladder replaces the
        staged ExchangeStatsExec round-trip). Returns (plan', n_spans)
        or None — None means take the staged path, with a typed
        ``fusion_bailout`` event whenever a decision genuinely needed
        the host."""
        from spark_tpu import faults, metrics

        if not stage.fully_traceable(plan, D.ShardScanExec):
            return None  # both paths reject it; let staged raise
        if not any(isinstance(p, _ADAPTIVE_EXCHANGES)
                   for p in _walk_plan(plan)):
            return None  # nothing to fuse, nothing to bail out of
        if FORCE_ADAPTIVE.get():
            # the OOM-degradation retry wants the staged compaction
            # rungs — measured capacities, not worst-case fused buffers
            self._fusion_bailout("oom_ladder",
                                 "FORCE_ADAPTIVE retry in flight")
            return None
        try:
            fused, n_spans = self._fuse_rewrite(plan)
        except _FusionBailout as b:
            self._fusion_bailout(b.reason, b.detail)
            return None
        if isinstance(fused, D.FusedSpanExec):
            # root span: nothing above could consume the sentinel row,
            # so the program may emit a speculative rung-sized output
            # (overflow re-runs staged — see FusedSpanExec.speculate)
            fused = dataclasses.replace(fused, speculate=True)
        try:
            # fault seam: the plan is judged fusible, the span not yet
            # built — ANY kind degrades to staged execution (the fused
            # program is pure plan rewriting; staged computes the
            # identical bytes)
            faults.inject("fusion.decide", self.conf)
        except faults.InjectedFault as e:
            metrics.note_fusion("fault_fallbacks")
            metrics.record("fault_recovered", point="fusion.decide",
                           fault=e.kind, action="staged")
            self._fusion_bailout("fault_injected", e.kind)
            return None
        return fused, n_spans

    def _fuse_rewrite(self, plan: P.PhysicalPlan):
        """Rewrite adaptive exchange + consumer pairs into fused spans;
        raises _FusionBailout on the first host-required decision. Bare
        adaptive exchanges (no whitelisted consumer) stay inline — the
        non-adaptive engine already runs them at static capacity inside
        one program, byte-identically; they just skip the staged
        compaction (``_maybe_compact`` still shrinks the final output).
        Mirrors ``_materialize_exchanges``'s pair detection exactly, so
        a plan fuses if and only if the staged path would have made
        nothing but capacity decisions for it."""
        from spark_tpu.analysis import legality

        bucket = max(1, int(self.conf.get(CF.ADAPTIVE_CAPACITY_BUCKET)))
        variants = max(1, int(self.conf.get(CF.FUSION_MAX_BUCKET_VARIANTS)))
        spans = [0]

        def pair(consumer: P.PhysicalPlan,
                 ex: P.PhysicalPlan) -> "D.FusedSpanExec":
            producer = rewrite(ex.child)
            new_ex = dataclasses.replace(ex, child=producer)
            spans[0] += 1
            span = D.FusedSpanExec(
                consumer=dataclasses.replace(consumer, child=new_ex),
                exchange=new_ex, bucket=bucket, variants=variants)
            # chain merge: when this pair's producer is another fused
            # span reached only through row-preserving interstitials,
            # nest this pair INSIDE the upstream span's branches (its
            # ``tail``) instead of consuming the upstream's worst-case-
            # padded output — every intermediate stays rung-sized and
            # the chain still compiles to ONE switch tree / program
            inters: List[P.PhysicalPlan] = []
            node = producer
            while isinstance(node, (P.ProjectExec, P.FilterExec)):
                inters.append(node)
                node = node.child
            if isinstance(node, D.FusedSpanExec):
                return dataclasses.replace(
                    node, tail=node.tail + tuple(reversed(inters))
                    + (span,))
            return span

        def rewrite(p: P.PhysicalPlan) -> P.PhysicalPlan:
            if (isinstance(p, D.DistSortAggExec)
                    and isinstance(p.child, D.HashPartitionExchangeExec)):
                ex = p.child
                if (isinstance(ex.child, D.DistSortAggExec)
                        and ex.child.phase == "partial"
                        and ex.child.groupings
                        and self._agg_adaptive_enabled()
                        and legality.strategy_verdict(
                            ex.child.aggregates,
                            ex.child.child.schema).ok):
                    # a legal strategy crossover needs the host sketch
                    # fetch; a PINNED pair (float partials) has only
                    # the capacity decision left and falls through
                    raise _FusionBailout(
                        "agg_strategy",
                        "strategy crossover needs the host sketch fetch")
                if self.d > 1 and _exactly_remergeable(p, ex.child.schema):
                    # a re-mergeable merge could skew-fan: hot
                    # destinations are elected on the host and retraced
                    # with static fan_destinations
                    raise _FusionBailout(
                        "skew_presplit",
                        "re-mergeable consumer: destination skew fan "
                        "is a host decision")
                return pair(p, ex)
            if (isinstance(p, P.SortExec)
                    and isinstance(p.child, D.RangeExchangeExec)):
                ex = p.child
                sorted_by = None
                if isinstance(ex.child, D.ShardScanExec):
                    sorted_by = ex.child.sharded.sorted_by
                elif (isinstance(ex.child, P.ProjectExec)
                        and isinstance(ex.child.child, D.ShardScanExec)):
                    sorted_by = _project_sorted_by(
                        ex.child.child.sharded.sorted_by, ex.child.exprs)
                if sorted_by and _sorted_by_satisfies(sorted_by, p.orders):
                    # the staged path skips the whole Sort stage on the
                    # producer's order guarantee — a host metadata
                    # decision the fused program cannot make
                    raise _FusionBailout(
                        "sort_elide",
                        "producer order guarantee elides the sort")
                return pair(p, ex)
            if isinstance(p, _ADAPTIVE_EXCHANGES):
                spans[0] += 1  # bare exchange, kept inline
            return p.map_children(rewrite)

        return rewrite(plan), spans[0]

    def _fusion_bailout(self, reason: str, detail: str = "") -> None:
        from spark_tpu import metrics

        metrics.note_fusion("bailouts")
        metrics.record("fusion_bailout", reason=reason, detail=detail)

    def _run_fused(self, plan: P.PhysicalPlan,
                   n_spans: int) -> Optional[ShardedBatch]:
        from spark_tpu import metrics

        try:
            with _trace.span("stage.fused", spans=n_spans,
                             devices=self.d):
                sb = self._run_stage(plan)
        except _FusionOverflow:
            # the speculative output sliced off live rows: the load is
            # genuinely skewed past the ladder anchor — discard and
            # re-run staged (byte-identical, the skew fan and measured
            # capacities belong to the host there anyway)
            self._fusion_bailout(
                "overflow", "live rows past the speculative output "
                "capacity; staged re-run")
            return None
        metrics.note_fusion("fused_programs")
        metrics.note_fusion("fused_spans", n_spans)
        metrics.record("fusion", spans=n_spans, devices=self.d,
                       capacity=sb.per_device_capacity)
        metrics.set_gauge("fusion.last_spans", n_spans)
        metrics.set_gauge("fusion.last_devices", self.d)
        return sb

    # ---- adaptive execution (AQE over the mesh) -----------------------------

    def _materialize_exchanges(self, plan: P.PhysicalPlan) -> P.PhysicalPlan:
        """The AdaptiveSparkPlanExec loop (reference:
        adaptive/AdaptiveSparkPlanExec.scala:247 createQueryStages):
        cut the fused program at hash/range/round-robin exchange
        boundaries, run each producer side as its own stage, measure it
        (ExchangeStatsExec), and splice the exchanged result back in as
        a ShardScan leaf — so every consumer re-traces against the
        measured, bucket-rounded capacity instead of the static D*cap
        worst case. A final-merge aggregate sitting directly on its
        exchange is intercepted as a pair: that is where a skewed
        destination can fan + pre-merge (see _exchange_with_stats)."""
        if (isinstance(plan, D.DistSortAggExec)
                and isinstance(plan.child, D.HashPartitionExchangeExec)):
            if (isinstance(plan.child.child, D.DistSortAggExec)
                    and plan.child.child.phase == "partial"
                    and plan.child.child.groupings
                    and self._agg_adaptive_enabled()):
                return self._adaptive_aggregate(
                    final=plan, ex=plan.child, partial=plan.child.child)
            sb = self._run_adaptive_exchange(plan.child, consumer=plan)
            return dataclasses.replace(plan, child=D.ShardScanExec(sb))
        if (isinstance(plan, P.SortExec)
                and isinstance(plan.child, D.RangeExchangeExec)):
            # global sort = local sort over a range exchange. When the
            # exchange elides (the producer already carries a TOTAL
            # key order matching these exact orders — no ties for the
            # skipped shuffle to break differently), the local sort is
            # the identity on its prefix-packed input: skip the whole
            # Sort stage, not just the exchange
            sb = self._run_adaptive_exchange(plan.child)
            if _sorted_by_satisfies(sb.sorted_by, plan.orders):
                return D.ShardScanExec(sb)
            return dataclasses.replace(plan, child=D.ShardScanExec(sb))
        if isinstance(plan, _ADAPTIVE_EXCHANGES):
            return D.ShardScanExec(self._run_adaptive_exchange(plan))
        return plan.map_children(self._materialize_exchanges)

    def _run_adaptive_exchange(self, ex: P.PhysicalPlan,
                               consumer=None) -> ShardedBatch:
        """Run the producer side of one exchange as its own stage, then
        the exchange itself under measured capacity bounds — unless the
        producer's batch already carries a ``sorted_by`` guarantee that
        satisfies a range exchange's orders (the sort-based aggregation
        rung's key-ordered output): then the whole global sort shuffle
        collapses to a no-op and the batch passes through."""
        from spark_tpu import metrics

        child = self._materialize_exchanges(ex.child)
        child_sb = self._producer_batch(child)
        if (isinstance(ex, D.RangeExchangeExec)
                and _sorted_by_satisfies(child_sb.sorted_by, ex.orders)):
            metrics.record("aqe", decision="sort_elide", op="range",
                           orders=tuple(s[0] for s in child_sb.sorted_by))
            metrics.note_agg("sort_elided")
            return child_sb
        return self._exchange_with_stats(ex, child_sb, consumer=consumer)

    def _producer_batch(self, child: P.PhysicalPlan) -> ShardedBatch:
        """Materialized producer plan -> ShardedBatch, carrying a
        ``sorted_by`` order guarantee through a row-wise projection of
        an already-ordered scan (projections are 1:1 and keep row
        order, so the guarantee survives under the projected names)."""
        if isinstance(child, D.ShardScanExec):
            return child.sharded
        sorted_by = None
        if (isinstance(child, P.ProjectExec)
                and isinstance(child.child, D.ShardScanExec)):
            sorted_by = _project_sorted_by(
                child.child.sharded.sorted_by, child.exprs)
        sb = self.run(child)
        if sorted_by:
            sb.sorted_by = sorted_by
        return sb

    def _exchange_with_stats(self, ex: P.PhysicalPlan,
                             child_sb: ShardedBatch, consumer=None,
                             allow_skew: bool = True) -> ShardedBatch:
        from spark_tpu import metrics

        d = self.d
        ex = dataclasses.replace(ex, child=D.ShardScanExec(child_sb))
        # the AQE host round-trip ROADMAP item 3 wants gone: one span
        # per stats stage + device->host fetch quantifies it per query
        with _trace.span("exchange.stats", op=_exchange_op(ex)):
            stats_sb = self._run_stage(D.ExchangeStatsExec(ex))
            # replicated psum/pmax: the flat layout puts device 0's
            # copy first; one host fetch of 2*d int64s total
            incoming = np.asarray(
                stats_sb.data.columns[0].data)[:d].astype(np.int64)
            maxslice = np.asarray(
                stats_sb.data.columns[1].data)[:d].astype(np.int64)
        bucket = max(1, int(self.conf.get(CF.ADAPTIVE_CAPACITY_BUCKET)))

        if (allow_skew and consumer is not None and d > 1
                and isinstance(ex, D.HashPartitionExchangeExec)
                and incoming.size):
            factor = int(self.conf.get(CF.ADAPTIVE_SKEW_FACTOR))
            min_rows = int(self.conf.get(CF.ADAPTIVE_SKEW_MIN_ROWS))
            med = float(np.median(incoming))
            hot = [int(j) for j in range(d)
                   if int(incoming[j]) >= min_rows
                   and float(incoming[j]) > factor * max(1.0, med)]
            if hot and _exactly_remergeable(consumer, child_sb.schema):
                metrics.record(
                    "aqe", decision="skew_split", op=_exchange_op(ex),
                    hot=tuple(hot), max_incoming=int(incoming.max()),
                    median=med, factor=factor)
                # fan: hot destinations' rows stay on their balanced
                # source devices; pre-merge collapses them to one row
                # per (device, group); only the merged groups take the
                # second (now un-skewed) exchange into the final merge
                fanned = dataclasses.replace(
                    ex, fan_destinations=tuple(hot))
                fanned_sb = self._exchange_with_stats(
                    fanned, child_sb, consumer=None, allow_skew=False)
                pre_sb = self._run_stage(dataclasses.replace(
                    consumer, child=D.ShardScanExec(fanned_sb)))
                plain = dataclasses.replace(ex, fan_destinations=None)
                return self._exchange_with_stats(
                    plain, pre_sb, consumer=None, allow_skew=False)

        max_in = int(incoming.max()) if incoming.size else 0
        max_sl = int(maxslice.max()) if maxslice.size else 0
        out_cap = K.bucket(max(1, max_in), bucket)
        slice_cap = min(child_sb.per_device_capacity,
                        K.bucket(max(1, max_sl), min(bucket, 128)))
        sb = self._run_stage(dataclasses.replace(
            ex, slice_capacity=slice_cap, out_capacity=out_cap))
        metrics.record_exchange(
            op=_exchange_op(ex), mode="adaptive", devices=d,
            rows=int(incoming.sum()),
            capacity_before=d * child_sb.per_device_capacity,
            capacity_after=sb.per_device_capacity,
            slice_capacity=slice_cap,
            buffer_bytes=d * slice_cap * _row_width(child_sb.schema))
        return sb

    # ---- runtime-adaptive aggregation ---------------------------------------

    def _agg_adaptive_enabled(self) -> bool:
        try:
            return bool(self.conf.get(CF.ADAPTIVE_AGG_ENABLED))
        except Exception:
            return True

    #: see the module-level hll_estimate — kept as a staticmethod so
    #: existing callers/tests keep working while the hybrid hash join
    #: shares the estimator without instantiating an executor
    _hll_estimate = staticmethod(hll_estimate)

    def _adaptive_aggregate(self, final: "D.DistSortAggExec",
                            ex: "D.HashPartitionExchangeExec",
                            partial: "D.DistSortAggExec") -> P.PhysicalPlan:
        """Runtime strategy switch for a partial->final aggregate pair.

        One extended stats stage over the RAW rows (the exchange the
        bypass strategy would run) measures, in a single fetch:
        routing counts (``__incoming``/``__maxslice``), an HLL distinct
        sketch over the group keys (``__ndvreg``), per-key global
        min/max/null counts (``__kmin``/``__kmax``/``__knull``), and a
        Count-Min heavy-hitter probe (``__hothash``/``__hotest``). The
        host then picks, per aggregate:

        - ``presplit`` the Count-Min probe found a KEY whose frequency
          alone overloads a device AND the crossover elected a raw-row
          exchange (bypass/sort — the strategies a hot key actually
          imbalances; partial/hash collapse it to one row per device
          first): salt the hot keys' raw rows round-robin over ALL
          devices BEFORE the exchange (salted sub-keys), partial-merge
          the salted shards, and exchange the now-balanced partials
          into the final merge — the source-side dual of the
          destination-reactive skew fan, acting before the imbalance
          instead of after it.
        - ``bypass``  estimated NDV ~ live rows, bounded key domain:
          pre-aggregation cannot shrink anything, so skip it —
          exchange raw rows by key straight to the final-equivalent
          aggregate (the partial node re-rooted on the exchanged rows;
          schemas are identical by the AggSpec alias contract).
        - ``sort``    estimated NDV ~ live rows AND the packed key
          domain is huge or unbounded (legality.strategy_crossover):
          range-partition the raw rows on the group keys and run one
          sorted segmented merge per device (DistRangeAggExec) — a
          distributed sort-aggregate whose output is key-ordered
          across the whole mesh, so a matching downstream global Sort
          elides entirely (_run_adaptive_exchange).
        - ``hash``    small measured key domain: swap the sort partial
          for DistHashPartialAggExec over measured packed codes (dense
          segment reductions through the measured selection table).
        - ``partial`` the static sort partial->final plan — always the
          fallback, and the byte-identity baseline.

        Aggregates outside legality.strategy_verdict (float Sum/Avg
        partials, float Min/Max) pin to ``partial``; every legal
        strategy is byte-identical to it (exact integer merges are
        associative+commutative, routing depends only on key values,
        and the final merge re-sorts per device — and pre-splitting in
        particular only re-partitions rows the partials are invariant
        to), pinned by the on/off x strategy sweep in
        tests/test_agg_adaptive.py.

        The sketches are advisory: ANY injected fault at
        ``agg.strategy`` (even 'corrupt' — the estimate is discarded,
        never merged into results) degrades to the static plan, and
        ``agg.presplit`` does the same for an elected pre-split, whole
        candidate list discarded."""
        from spark_tpu import faults, metrics
        from spark_tpu.analysis import legality

        d = self.d
        child = self._materialize_exchanges(partial.child)
        if isinstance(child, D.ShardScanExec):
            child_sb = child.sharded
        else:
            child_sb = self.run(child)

        # the raw-row exchange bypass would run; also the stats carrier
        raw_ex = D.HashPartitionExchangeExec(
            tuple(partial.groupings), D.ShardScanExec(child_sb))

        r = int(self.conf.get(CF.ADAPTIVE_AGG_SKETCH_REGISTERS))
        r = max(16, min(4096, r))
        if r & (r - 1):
            r = 1 << (r.bit_length() - 1)  # round down to a power of 2
        # per-key min/max only helps when every key range-compresses to
        # int64 codes exactly (ints, bools, dates, decimals, dictionary
        # strings — everything but floats)
        nk = len(partial.groupings)
        try:
            for g in partial.groupings:
                dt = legality._np_dtype(
                    E.strip_alias(g).data_type(partial.child.schema))
                if np.issubdtype(dt, np.floating):
                    nk = 0
                    break
        except Exception:
            nk = 0

        cmd = max(1, min(len(D._CM_SEEDS),
                         int(self.conf.get(CF.ADAPTIVE_AGG_CM_DEPTH))))
        cmw = max(64, min(1 << 16,
                          int(self.conf.get(CF.ADAPTIVE_AGG_CM_WIDTH))))
        if cmw & (cmw - 1):
            cmw = 1 << (cmw.bit_length() - 1)
        use_cm = d > 1  # pre-splitting needs somewhere to spread to

        with _trace.span("agg.decide", node=final.node_string()):
            stats_sb = self._run_stage(D.ExchangeStatsExec(
                raw_ex, sketch_registers=r, key_stats=nk,
                cm_depth=cmd if use_cm else 0,
                cm_width=cmw if use_cm else 0))
            cols = stats_sb.data.columns
            incoming = np.asarray(cols[0].data)[:d].astype(np.int64)
            maxslice = np.asarray(cols[1].data)[:d].astype(np.int64)
            rows = int(incoming.sum())

            verdict = legality.strategy_verdict(partial.aggregates,
                                                partial.child.schema)
            forced = str(self.conf.get(CF.ADAPTIVE_AGG_STRATEGY)).lower()

            ndv = 0
            ratio = 0.0
            mins: Tuple[int, ...] = ()
            ranges: Tuple[int, ...] = ()
            domain = 0
            hot_hashes: Tuple[int, ...] = ()
            try:
                # fault seam: everything the sketches feed the decision
                # sits inside this block, so an injected failure of ANY
                # kind degrades to the static plan, estimates discarded
                faults.inject("agg.strategy", self.conf)
                registers = np.asarray(cols[2].data)[:r].astype(np.int64)
                ndv = min(rows, int(round(self._hll_estimate(registers))))
                ratio = (ndv / rows) if rows else 0.0
                ci = 3
                if nk and rows:
                    kmin = np.asarray(cols[ci].data)[:nk].astype(np.int64)
                    kmax = np.asarray(
                        cols[ci + 1].data)[:nk].astype(np.int64)
                    if bool(np.all(kmin <= kmax)):
                        mins = tuple(int(v) for v in kmin)
                        ranges = tuple(int(mx - mn + 1)
                                       for mn, mx in zip(kmin, kmax))
                        domain = 1
                        for rg in ranges:
                            domain *= rg + 1  # + null slot per key
                            if domain > (1 << 62):
                                domain = 1 << 62
                                break
                ci += 3 if nk else 0
                if use_cm and rows:
                    hh = np.asarray(
                        cols[ci].data)[:d].astype(np.int64)
                    he = np.asarray(
                        cols[ci + 1].data)[:d].astype(np.int64)
                    # hot = one KEY alone would overload a device: its
                    # CM estimate tops the fair per-device share by the
                    # presplit factor (CM overestimates, never misses,
                    # so a collision can only salt a cold key — which
                    # the partials' partition-invariance makes free)
                    cut = max(
                        int(self.conf.get(
                            CF.ADAPTIVE_AGG_PRESPLIT_MIN_ROWS)),
                        int(self.conf.get(
                            CF.ADAPTIVE_AGG_PRESPLIT_FACTOR))
                        * max(1, rows // d))
                    hot_hashes = tuple(sorted(
                        {int(h) for h, e in zip(
                            hh.astype(np.uint64), he)
                         if int(e) >= cut}))
                sketch_ok = True
            except faults.InjectedFault as e:
                metrics.note_agg("sketch_failures")
                metrics.record("fault_recovered", point="agg.strategy",
                               fault=e.kind,
                               action="static_partial_final")
                sketch_ok = False

            hash_ok = bool(ranges) and 0 < domain <= int(
                self.conf.get(CF.ADAPTIVE_AGG_HASH_DOMAIN_LIMIT))
            presplit_ok = bool(hot_hashes) and d > 1
            if not sketch_ok:
                strategy, mode = "partial", "fallback"
            elif not verdict.ok:
                strategy, mode = "partial", "pinned"
                metrics.note_agg("pinned")
            elif forced in ("partial", "bypass", "hash", "sort",
                            "presplit"):
                # an unexecutable forced choice falls back to partial
                # (the conf doc promises forcing never breaks a query)
                strategy = forced
                if (forced == "hash" and not hash_ok) \
                        or (forced == "presplit" and not presplit_ok):
                    strategy = "partial"
                mode = "forced"
                metrics.note_agg("forced")
            elif rows:
                strategy = legality.strategy_crossover(
                    ratio, domain if ranges else -1,
                    float(self.conf.get(
                        CF.ADAPTIVE_AGG_BYPASS_NDV_RATIO)),
                    int(self.conf.get(
                        CF.ADAPTIVE_AGG_HASH_DOMAIN_LIMIT)),
                    int(self.conf.get(
                        CF.ADAPTIVE_AGG_SORT_DOMAIN_WIDTH)))
                mode = "auto"
                # pre-splitting only beats the alternatives when the
                # elected strategy exchanges RAW rows (bypass routes a
                # hot key's every row to one destination; the sort
                # rung's range partition owns it on one device). The
                # partial/hash strategies already collapse a hot key to
                # ONE row per device before their exchange — salting
                # would add a whole extra exchange for nothing.
                if strategy in ("bypass", "sort") and presplit_ok:
                    strategy = "presplit"
            else:
                strategy, mode = "partial", "auto"

            if strategy == "presplit":
                # second seam: the candidate list is pure advice — an
                # injected fault of ANY kind discards it whole and
                # degrades to the static partial->final plan
                try:
                    faults.inject("agg.presplit", self.conf)
                except faults.InjectedFault as e:
                    metrics.note_agg("presplit_failures")
                    metrics.record("fault_recovered",
                                   point="agg.presplit", fault=e.kind,
                                   action="static_partial_final")
                    strategy, mode = "partial", "presplit_fallback"

        metrics.record("agg", strategy=strategy, mode=mode, ndv=int(ndv),
                       rows=rows, ratio=round(ratio, 4),
                       domain=int(domain), devices=d,
                       hot_keys=len(hot_hashes),
                       node=final.node_string())
        metrics.note_agg(strategy)
        metrics.set_gauge("agg.last_ndv", int(ndv))
        metrics.set_gauge("agg.last_rows", rows)
        metrics.set_gauge("agg.last_strategy", strategy)

        if strategy == "bypass":
            # raw rows straight to their group's device under the
            # already-measured bounds; the partial node re-rooted on the
            # exchanged rows IS the final aggregate (AggSpec gives
            # partials and merges the same aliases and dtypes)
            bucket = max(1, int(self.conf.get(CF.ADAPTIVE_CAPACITY_BUCKET)))
            max_in = int(incoming.max()) if incoming.size else 0
            max_sl = int(maxslice.max()) if maxslice.size else 0
            out_cap = K.bucket(max(1, max_in), bucket)
            slice_cap = min(child_sb.per_device_capacity,
                            K.bucket(max(1, max_sl), min(bucket, 128)))
            sb = self._run_stage(dataclasses.replace(
                raw_ex, slice_capacity=slice_cap, out_capacity=out_cap))
            metrics.record_exchange(
                op="hash", mode="adaptive", devices=d, rows=rows,
                capacity_before=d * child_sb.per_device_capacity,
                capacity_after=sb.per_device_capacity,
                slice_capacity=slice_cap,
                buffer_bytes=d * slice_cap * _row_width(child_sb.schema))
            return dataclasses.replace(
                partial, child=D.ShardScanExec(sb), phase=None)

        if strategy == "sort":
            # the sort rung: range-partition the RAW rows on the group
            # keys (equal keys co-locate and devices own disjoint key
            # ranges), then one per-device sort-and-segment merge
            # completes a distributed sort-aggregate — output is
            # key-ordered across the mesh, marked on the batch so a
            # matching downstream global Sort elides entirely
            with _trace.span("agg.sort", rows=rows, ndv=int(ndv)):
                orders = tuple(E.SortOrder(E.strip_alias(g))
                               for g in partial.groupings)
                range_ex = D.RangeExchangeExec(
                    orders, D.ShardScanExec(child_sb))
                ex_sb = self._exchange_with_stats(range_ex, child_sb)
                out_sb = self._run_stage(D.DistRangeAggExec(
                    tuple(partial.groupings),
                    tuple(partial.aggregates),
                    D.ShardScanExec(ex_sb)))
                out_sb.sorted_by = self._agg_sorted_by(partial)
            return D.ShardScanExec(out_sb)

        if strategy == "presplit":
            # hot KEYS spread over every device BEFORE the exchange
            # (salted sub-keys), partial-merge the salted shards, then
            # the now-balanced partials take the ordinary exchange into
            # the final merge — the source-side dual of the skew fan,
            # acting on hot KEYS before the imbalance instead of hot
            # DESTINATIONS after it
            with _trace.span("agg.presplit", hot=len(hot_hashes),
                             rows=rows):
                salted = dataclasses.replace(
                    raw_ex, presplit_hashes=hot_hashes)
                salted_sb = self._exchange_with_stats(
                    salted, child_sb, consumer=None, allow_skew=False)
                pre_sb = self._run_stage(dataclasses.replace(
                    partial, child=D.ShardScanExec(salted_sb)))
                sb = self._exchange_with_stats(
                    ex, pre_sb, consumer=None, allow_skew=False)
            return dataclasses.replace(final,
                                       child=D.ShardScanExec(sb))

        if strategy == "hash":
            pre: P.PhysicalPlan = D.DistHashPartialAggExec(
                tuple(partial.groupings), tuple(partial.aggregates),
                D.ShardScanExec(child_sb), key_mins=mins,
                key_ranges=ranges)
        else:
            pre = dataclasses.replace(
                partial, child=D.ShardScanExec(child_sb))
        sb = self._run_adaptive_exchange(
            dataclasses.replace(ex, child=pre), consumer=final)
        return dataclasses.replace(final, child=D.ShardScanExec(sb))

    def _agg_sorted_by(self, partial: "D.DistSortAggExec"):
        """The ``sorted_by`` guarantee of the sort rung's output under
        the partial's ``__k{i}`` key aliases, or None when the key
        types cannot carry one: dictionary strings range-partition by
        RANK but sort locally by CODE, so the rung's output is grouped
        correctly yet not globally string-ordered; floats never reach
        here (strategy pinned) but are excluded anyway. Integer-coded
        orderable keys (ints, bools, dates, decimals) qualify — their
        code order IS their value order on both sides."""
        from spark_tpu.analysis import legality

        out = []
        for i, g in enumerate(partial.groupings):
            try:
                dt_engine = E.strip_alias(g).data_type(
                    partial.child.schema)
                dt = legality._np_dtype(dt_engine)
            except Exception:
                return None
            if isinstance(dt_engine, T.StringType) \
                    or np.issubdtype(dt, np.floating):
                return None
            alias = partial.aggregates[i]
            if not (isinstance(alias, E.Alias)
                    and E.expr_key(alias.child) == E.expr_key(
                        E.strip_alias(g))):
                return None
            out.append((alias.name, True, True))
        return tuple(out)

    def _materialize_boundaries(self, plan: P.PhysicalPlan) -> P.PhysicalPlan:
        if isinstance(plan, D.DistJoinBoundary):
            return D.ShardScanExec(self._run_join(plan))
        return plan.map_children(self._materialize_boundaries)

    def _run_stage(self, plan: P.PhysicalPlan) -> ShardedBatch:
        from spark_tpu import metrics, trace

        with trace.span("stage.run", op=type(plan).__name__), \
                metrics.stage_timer("stage", mesh=self.d,
                                    node=plan.node_string()):
            sb = self._run_stage_inner(plan)
        # measured output footprint: scheduler admission prefers these
        # over static row-count estimates once a plan has run once
        # (scheduler/admission.note_measured_bytes, fed by
        # DataFrame._execute from the query's stage_bytes events)
        metrics.record("stage_bytes",
                       bytes=int(sb.capacity) * _row_width(sb.schema))
        return sb

    def _run_stage_inner(self, plan: P.PhysicalPlan) -> ShardedBatch:
        scans = stage.collect_leaves(plan, D.ShardScanExec)
        key = (plan.plan_key(), self.d, self.mesh.devices.flat[0].platform)
        entry = _DIST_STAGE_CACHE.get(key)
        if entry is None:
            # in the executable store a plan holding fused spans keys
            # under its own tier with the bucket-ladder parameters
            # folded into the digest: the store never replays a fused
            # executable across a ladder conf change, and prewarm
            # replays fused programs as themselves
            fused_nodes: List[D.FusedSpanExec] = []
            _collect_fused(plan, fused_nodes)
            entry = stage.build_stage(
                "fused_span" if fused_nodes else "dist", plan,
                D.ShardScanExec, tuple(s.sharded.data for s in scans),
                name="local_fn",
                wrap=lambda fn: jax.shard_map(
                    fn, mesh=self.mesh, in_specs=_SPEC, out_specs=_SPEC,
                    check_vma=False),
                mesh_size=self.d, platform=key[2],
                extra=tuple(("ladder", f.bucket, f.variants)
                            for f in fused_nodes) or None,
                devices=tuple(self.mesh.devices.flat))
            _DIST_STAGE_CACHE[key] = entry
        jitted, schema_box = entry
        # the enqueue alone, sampled or not: nothing waits for the
        # device here (the wait is measured where the host blocks
        # anyway: in fetch_host, and in the read-backs below)
        with _trace.span("stage.dispatch"):
            data = jitted(tuple(s.sharded.data for s in scans))
        sb = ShardedBatch(schema_box["schema"], data, self.mesh)
        if isinstance(plan, D.FusedSpanExec) and plan.speculate:
            # the last slot of every shard is the overflow sentinel —
            # check it BEFORE any compaction could move or drop it
            if bool(self._read_mask(sb)[:, -1].any()):
                raise _FusionOverflow()
        n_ex = _count_exchange_nodes(plan)
        if n_ex and not self._adaptive_enabled():
            # fused-mode observability: exchanges ran inside this stage
            # at the static worst-case capacity; report the stage output
            # as the post-exchange shape so padding ratios compare
            # against adaptive mode. One mask readback per
            # exchange-bearing stage.
            from spark_tpu import metrics

            p = sb.per_device_capacity
            metrics.record_exchange(
                op="fused", mode="fused", devices=self.d,
                exchanges=n_ex, rows=int(self._read_mask(sb).sum()),
                capacity_before=p, capacity_after=p,
                buffer_bytes=self.d * p * _row_width(sb.schema))
        return self._maybe_compact(sb)

    def _read_mask(self, sb: ShardedBatch) -> np.ndarray:
        """``sb``'s row mask on the host, a row a device: a read-back
        between stages, so the host waits here for the stage that made
        it (``device.wait`` with ``op="readback"``)."""
        with _trace.span("device.wait", op="readback"):
            return np.asarray(sb.data.row_mask).reshape(
                self.d, sb.per_device_capacity)

    def _maybe_compact(self, sb: ShardedBatch) -> ShardedBatch:
        p = sb.per_device_capacity
        if p <= 4096:
            return sb
        m = self._read_mask(sb)
        max_live = int(m.sum(axis=1).max())
        if max_live * 4 > p:
            return sb
        new_p = K.bucket(max_live, 128)
        # slice-safe when no live row sits past new_p on any device —
        # true for front-compacted outputs (exchanges, fused spans),
        # where the stable-argsort gather would be an identity move
        sliced = not bool(m[:, new_p:].any())
        return self._run_stage(_CompactExec(new_p, D.ShardScanExec(sb),
                                            sliced))

    # ---- join lowering ------------------------------------------------------

    def _run_join(self, jb: D.DistJoinBoundary) -> ShardedBatch:
        left_sb = self.run(jb.left)
        right_sb = self.run(jb.right)
        how = jb.how

        if how == "cross":
            return self._run_cross(jb, left_sb, right_sb)

        if self.broadcast_threshold is not None:  # legacy row threshold
            small_build = right_sb.capacity <= self.broadcast_threshold
        elif self._adaptive_enabled():
            # runtime broadcast switching (reference:
            # DynamicJoinSelection.scala:40 over MapOutputStatistics):
            # measure the build side — live rows x row width, one mask
            # readback — instead of trusting the static capacity
            # estimate, which a filtered build side inflates by orders
            # of magnitude
            from spark_tpu import metrics as _metrics

            measured = (right_sb.num_valid_rows()
                        * _row_width(right_sb.schema))
            threshold = int(self.conf.get(
                CF.ADAPTIVE_BROADCAST_THRESHOLD))
            small_build = measured <= threshold
            _metrics.record(
                "aqe",
                decision=("broadcast_join" if small_build
                          else "exchange_join"),
                measured_bytes=int(measured), threshold=threshold,
                static_bytes=_estimated_bytes(right_sb))
            if self._fusion_enabled():
                # the broadcast switch is a measured-bytes host
                # decision by construction — joins always execute at
                # the staged boundary, never inside a fused span
                self._fusion_bailout(
                    "broadcast_switch",
                    "join build side measured on host")
        else:
            from spark_tpu import conf as _conf

            # read per-join so spark.conf.set takes effect immediately
            small_build = (_estimated_bytes(right_sb)
                           <= self.conf.get(_conf.BROADCAST_THRESHOLD))
        broadcast = (how in ("inner", "left", "left_semi", "left_anti")
                     and small_build)

        # Evaluate the key expressions once (a tiny projection stage) —
        # the EXECUTED schema carries the true dictionaries of computed
        # string keys (e.g. substr(col)), which static analysis of the
        # input schema cannot know. Min/max stats don't change under the
        # exchange, so pre-exchange stats are globally valid.
        lproj = self._run_stage(P.ProjectExec(
            tuple(E.Alias(k, f"__k{i}") for i, k in enumerate(jb.left_keys)),
            D.ShardScanExec(left_sb)))
        rproj = self._run_stage(P.ProjectExec(
            tuple(E.Alias(k, f"__k{i}") for i, k in enumerate(jb.right_keys)),
            D.ShardScanExec(right_sb)))
        union_dicts = self._union_dicts(lproj.schema, rproj.schema)
        mins, ranges = self._key_stats(lproj, rproj, union_dicts)

        left0, right0 = left_sb, right_sb  # pre-exchange (balanced rows)
        if not broadcast:
            left_sb = self.run(D.HashPartitionExchangeExec(
                jb.left_keys, D.ShardScanExec(left_sb),
                key_union_dicts=union_dicts))
            right_sb = self.run(D.HashPartitionExchangeExec(
                jb.right_keys, D.ShardScanExec(right_sb),
                key_union_dicts=union_dicts))

        def count_pairs(ls, rs, bcast):
            cnt_plan = D.JoinCountExec(
                D.ShardScanExec(ls), D.ShardScanExec(rs),
                jb.left_keys, jb.right_keys, mins, ranges, bcast)
            cnt_sb = self._run_stage(cnt_plan)
            return np.asarray(cnt_sb.data.columns[0].data)

        need_count = not (how in ("left_semi", "left_anti")
                          and jb.condition is None and mins is not None)
        pair_cap = 0
        if need_count:
            counts = count_pairs(left_sb, right_sb, broadcast)
            # AQE skew handling (reference: OptimizeSkewedJoin.scala:37
            # splits oversized partitions; DynamicJoinSelection demotes
            # to broadcast). Hash exchange sends every row of one hot
            # key to ONE device, so its pair count — and, under SPMD
            # static shapes, EVERY device's capacity — blows up. The
            # pre-exchange distribution is row-sliced and balanced, so
            # re-running as a broadcast join bounds per-device pairs at
            # ~total/d: pairs ride with the evenly-spread probe rows.
            from spark_tpu import conf as _conf

            factor = self.conf.get(_conf.SKEW_FACTOR)
            min_pairs = self.conf.get(_conf.SKEW_MIN_PAIRS)
            med = float(np.median(counts)) if counts.size else 0.0
            skewed = (not broadcast and counts.size
                      and int(counts.max()) >= min_pairs
                      and float(counts.max()) > factor * max(1.0, med))
            if skewed and how in ("inner", "left", "left_semi",
                                  "left_anti"):
                from spark_tpu import metrics

                if _estimated_bytes(right0) <= self.conf.get(
                        _conf.SKEW_MAX_BROADCAST_BYTES):
                    metrics.record(
                        "skew_join_broadcast", max=int(counts.max()),
                        median=med, factor=factor)
                    broadcast = True
                    left_sb, right_sb = left0, right0
                    counts = count_pairs(left_sb, right_sb, True)
                else:
                    # build too big to broadcast whole: SPLIT around the
                    # hot keys (reference: OptimizeSkewedJoin.scala:37
                    # splits oversized partitions; here the hot keys'
                    # probe rows stay row-sliced/balanced and only the
                    # hot keys' FEW build rows replicate)
                    hot = self._detect_hot_keys(jb.left_keys, left0)
                    if hot:
                        metrics.record(
                            "skew_join_split", max=int(counts.max()),
                            median=med, hot_keys=len(hot))
                        return self._run_skew_split(
                            jb, how, left0, right0, hot, union_dicts,
                            mins, ranges, count_pairs)
            pair_cap = K.bucket(int(counts.max()) if counts.size else 0)

        left0 = right0 = None  # release pre-exchange device buffers
        apply_plan = D.JoinApplyExec(
            D.ShardScanExec(left_sb), D.ShardScanExec(right_sb), how,
            jb.left_keys, jb.right_keys, jb.condition, mins, ranges,
            pair_cap, broadcast)
        return self._run_stage(apply_plan)

    def _detect_hot_keys(self, keys, sb: ShardedBatch):
        """Host-side hot-key candidates: each device reports its local
        mode (TopKeyExec); a candidate is hot when its (lower-bound)
        global count exceeds one balanced device share — the row volume
        that would pile onto a single device under a hash exchange."""
        cand = self._run_stage(D.TopKeyExec(tuple(keys),
                                            D.ShardScanExec(sb)))
        nkeys = len(keys)
        fields = cand.schema.fields
        cols = []
        for i in range(nkeys + 1):
            cd = cand.data.columns[i]
            cols.append((np.asarray(cd.data).ravel(),
                         None if cd.validity is None
                         else np.asarray(cd.validity).ravel(),
                         fields[i]))
        counts: dict = {}
        d = len(cols[0][0])
        for j in range(d):
            vals = []
            ok = True
            for i in range(nkeys):
                data, validity, f = cols[i]
                if validity is not None and not bool(validity[j]):
                    ok = False  # null hot key: nulls never join
                    break
                vals.append(_decode_key_value(data[j], f))
            if not ok:
                continue
            cnt = int(cols[nkeys][0][j])
            key = tuple(vals)
            counts[key] = counts.get(key, 0) + cnt
        total = sb.num_valid_rows()
        share = max(1, total // max(1, self.d))
        hot = [k for k, c in sorted(counts.items(),
                                    key=lambda kv: -kv[1]) if c > share]
        return hot[:4]

    def _run_skew_split(self, jb: D.DistJoinBoundary, how: str,
                        left0: ShardedBatch, right0: ShardedBatch,
                        hot, union_dicts, mins, ranges,
                        count_pairs) -> ShardedBatch:
        """AQE skew SPLIT: hot-key probe rows keep their balanced
        row-sliced placement and join against a broadcast of (only) the
        hot keys' build rows; everything else takes the normal hash
        exchange. Union of the two joins is exact for left-preserved
        join types — every probe row lands in exactly one branch and
        sees ALL build rows with its key (the all_to_all analogue of
        OptimizeSkewedJoin.scala:37 partition splitting)."""
        lpred = _hot_key_pred(jb.left_keys, hot)
        rpred = _hot_key_pred(jb.right_keys, hot)
        # null probe keys must survive into the REST branch (preserved
        # rows under outer/anti); NOT(pred) alone is NULL for them
        lkeep_rest = E.Or(E.Not(lpred), _null_any(jb.left_keys))
        rkeep_rest = E.Or(E.Not(rpred), _null_any(jb.right_keys))
        lhot = self._run_stage(P.FilterExec(lpred, D.ShardScanExec(left0)))
        lrest = self._run_stage(P.FilterExec(lkeep_rest,
                                             D.ShardScanExec(left0)))
        rhot = self._run_stage(P.FilterExec(rpred, D.ShardScanExec(right0)))
        rrest = self._run_stage(P.FilterExec(rkeep_rest,
                                             D.ShardScanExec(right0)))
        lrest_ex = self.run(D.HashPartitionExchangeExec(
            jb.left_keys, D.ShardScanExec(lrest),
            key_union_dicts=union_dicts))
        rrest_ex = self.run(D.HashPartitionExchangeExec(
            jb.right_keys, D.ShardScanExec(rrest),
            key_union_dicts=union_dicts))
        c1 = count_pairs(lrest_ex, rrest_ex, False)
        c2 = count_pairs(lhot, rhot, True)
        cap1 = K.bucket(int(c1.max()) if c1.size else 0)
        cap2 = K.bucket(int(c2.max()) if c2.size else 0)
        j1 = self._run_stage(D.JoinApplyExec(
            D.ShardScanExec(lrest_ex), D.ShardScanExec(rrest_ex), how,
            jb.left_keys, jb.right_keys, jb.condition, mins, ranges,
            cap1, broadcast=False))
        j2 = self._run_stage(D.JoinApplyExec(
            D.ShardScanExec(lhot), D.ShardScanExec(rhot), how,
            jb.left_keys, jb.right_keys, jb.condition, mins, ranges,
            cap2, broadcast=True))
        return self._run_stage(P.UnionExec(D.ShardScanExec(j1),
                                           D.ShardScanExec(j2)))

    def _run_cross(self, jb: D.DistJoinBoundary, left_sb: ShardedBatch,
                   right_sb: ShardedBatch) -> ShardedBatch:
        rn = right_sb.num_valid_rows()
        pair_cap = left_sb.per_device_capacity * max(1, rn)
        apply_plan = D.JoinApplyExec(
            D.ShardScanExec(left_sb), D.ShardScanExec(right_sb), "cross",
            (), (), jb.condition, (), (), pair_cap, broadcast=True)
        return self._run_stage(apply_plan)

    @staticmethod
    def _union_dicts(lschema: Schema, rschema: Schema):
        """Per-key unified dictionaries (trace-time constants) so string
        codes hash/pack identically on both sides. Schemas come from the
        EXECUTED key projection, so computed-key dictionaries are exact."""
        from spark_tpu.expr import compiler as C

        out = []
        for lf, rf in zip(lschema.fields, rschema.fields):
            if lf.dictionary is None and rf.dictionary is None:
                out.append(None)
            else:
                union, _ = C.unify_dictionaries(
                    (lf.dictionary or (), rf.dictionary or ()))
                out.append(union)
        return tuple(out)

    def _key_stats(self, lproj: ShardedBatch, rproj: ShardedBatch,
                   union_dicts) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
        """Host-side min/range per join key (the lightweight stats job;
        reference analogue: runtime statistics consumed by AQE)."""
        mins: List[int] = []
        ranges: List[int] = []
        total = 1
        for i, ud in enumerate(union_dicts):
            lf = lproj.schema.fields[i]
            if ud is not None or isinstance(lf.dtype, T.StringType):
                mins.append(0)
                ranges.append(max(1, len(ud or ())))
            else:
                vals = []
                for sb in (lproj, rproj):
                    cd = sb.data.columns[i]
                    m = np.asarray(sb.data.row_mask)
                    if cd.validity is not None:
                        m = m & np.asarray(cd.validity)
                    v = np.asarray(cd.data)[m]
                    if v.size:
                        vals.append((int(v.min()), int(v.max())))
                if not vals:
                    mins.append(0)
                    ranges.append(1)
                else:
                    mn = min(v[0] for v in vals)
                    mx = max(v[1] for v in vals)
                    mins.append(mn)
                    ranges.append(mx - mn + 1)
            total *= ranges[-1]
            if total > (1 << 62):
                # exact packing impossible: switch the whole join to the
                # hash-with-verify fallback (reference:
                # HashedRelation.scala:208 probe-then-confirm)
                return None, None
        return tuple(mins), tuple(ranges)
