"""Distributed physical operators (traced inside shard_map).

These compose with the single-device operators (physical/operators.py) in
ONE fused SPMD program per stage: local pipeline work is the same trace
code, and cross-device redistribution appears as exchange collectives at
exactly the points where the reference plants ShuffleExchangeExec /
BroadcastExchangeExec nodes (reference: exchange/EnsureRequirements.scala:49,
ShuffleExchangeExec.scala:120, BroadcastExchangeExec.scala:78). A whole
distributed stage — scan, filter, partial agg, psum merge, final agg —
compiles to a single XLA executable with collectives scheduled on ICI.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_tpu import types as T
from spark_tpu.expr import compiler as C
from spark_tpu.expr import expressions as E
from spark_tpu.expr.compiler import Env, TV
from spark_tpu.parallel import exchange as X
from spark_tpu.parallel.sharded import ShardedBatch
from spark_tpu.physical import kernels as K
from spark_tpu.physical import operators as P
from spark_tpu.physical.operators import Pipe, rewrite_agg_outputs
from spark_tpu.types import Field, Schema


@dataclass(eq=False)
class ShardScanExec(P.PhysicalPlan):
    """Leaf: a materialized ShardedBatch; the stage runner feeds each
    device its local slice."""

    sharded: ShardedBatch
    traceable = True

    @property
    def schema(self) -> Schema:
        return self.sharded.schema

    def node_string(self):
        return f"ShardScan{list(self.schema.names)}"

    def plan_key(self):
        return P.scan_plan_key("ShardScan", self.sharded.per_device_capacity,
                               self.sharded.schema, self.sharded.data)


@dataclass(eq=False)
class DistRangeExec(P.PhysicalPlan):
    """range() generated directly sharded: device d materializes global
    positions [d*p, (d+1)*p) — nothing is ever resident on one device
    (reference RangeExec:412 splits by numSlices; here the mesh is the
    slicing)."""

    start: int
    end: int
    step: int
    num_rows: int
    per_device: int
    col_name: str = "id"
    traceable = True

    @property
    def schema(self) -> Schema:
        return Schema((Field(self.col_name, T.INT64, nullable=False),))

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        p = self.per_device
        gpos = X.axis_index().astype(jnp.int64) * p + jnp.arange(
            p, dtype=jnp.int64)
        ids = self.start + gpos * self.step
        mask = gpos < self.num_rows
        return Pipe({self.col_name: TV(ids, None, T.INT64, None)}, mask,
                    [self.col_name])

    def plan_key(self):
        return ("DistRange", self.start, self.end, self.step, self.num_rows,
                self.per_device, self.col_name)


# ---- exchanges --------------------------------------------------------------

#: fixed odd 64-bit seeds for the Count-Min hash rows (pairwise-
#: independent enough through the avalanche rehash; depth <= 8). Fixed
#: so the probe participates in the jit plan cache like every other
#: trace constant.
_CM_SEEDS = (0x9E3779B97F4A7C15, 0xC2B2AE3D27D4EB4F,
             0x165667B19E3779F9, 0x27D4EB2F165667C5,
             0x85EBCA77C2B2AE63, 0x2545F4914F6CDD1D,
             0xD6E8FEB86659FD93, 0xA24BAED4963EE407)


@dataclass(eq=False)
class HashPartitionExchangeExec(P.PhysicalPlan):
    """``key_union_dicts`` (optional, per key): a unified string
    dictionary; codes translate through it before hashing so that two
    relations with different dictionaries route equal strings to the
    same device.

    Adaptive fields (set by executor._run_adaptive_exchange from
    measured stats; all participate in plan_key so re-traces at the same
    bucket-rounded bounds hit the jit stage cache):
    ``slice_capacity``/``out_capacity`` bound the send slice and the
    received capacity (see exchange.exchange); ``fan_destinations``
    reroutes rows bound for skewed destinations back to their source
    device (exchange.fan_local) ahead of a partial-aggregate pre-merge;
    ``presplit_hashes`` (Count-Min heavy-hitter row hashes) salts the
    rows of hot KEYS round-robin over all devices BEFORE the exchange —
    legal only on a raw-row exchange ahead of a partial->final pair
    whose accumulators are partition-invariant (legality.
    strategy_verdict), where spreading one key over many partials is
    re-merged exactly by the final; a 64-bit hash collision merely
    salts one cold key too, which the same invariance makes harmless.
    """

    keys: Tuple[E.Expression, ...]
    child: P.PhysicalPlan
    key_union_dicts: Optional[Tuple[Optional[Tuple[str, ...]], ...]] = None
    slice_capacity: Optional[int] = None
    out_capacity: Optional[int] = None
    fan_destinations: Optional[Tuple[int, ...]] = None
    presplit_hashes: Optional[Tuple[int, ...]] = None
    traceable = True

    def children(self):
        return (self.child,)

    @property
    def schema(self) -> Schema:
        return self.child.schema

    def _key_tvs(self, pipe: Pipe) -> List[TV]:
        """Key columns after union-dictionary translation — the exact
        values routing hashes over (also what the stats stage sketches
        and measures, so decisions see what the exchange will see)."""
        env = pipe.env()
        tvs = [C.evaluate(k, env) for k in self.keys]
        if self.key_union_dicts is not None:
            translated = []
            for tv, union in zip(tvs, self.key_union_dicts):
                if union is not None and tv.dictionary is not None:
                    pos = {s: i for i, s in enumerate(union)}
                    table = np.array([pos[s] for s in tv.dictionary],
                                     dtype=np.int64)
                    tv = TV(jnp.asarray(table)[tv.data], tv.validity,
                            tv.dtype, union)
                translated.append(tv)
            tvs = translated
        return tvs

    def _target(self, pipe: Pipe, d: int) -> jnp.ndarray:
        key_tvs = self._key_tvs(pipe)
        target = X.hash_target(key_tvs, pipe.mask, d)
        if self.presplit_hashes:
            h = X.hash_rows(key_tvs)
            hot = jnp.zeros(h.shape, dtype=jnp.bool_)
            for ph in self.presplit_hashes:
                hot = hot | (h == jnp.uint64(np.uint64(ph)))
            hot = hot & pipe.mask
            # hot rows round-robin over ALL devices, offset by the
            # source device so the d salted streams interleave instead
            # of marching in lockstep onto the same destinations
            rank = jnp.cumsum(hot.astype(jnp.int32)) - 1
            salted = ((rank + X.axis_index()) % d).astype(jnp.int32)
            target = jnp.where(hot, salted, target)
        if self.fan_destinations:
            target = X.fan_local(target, self.fan_destinations)
        return target

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        pipe = child_pipes[0]
        return X.exchange(pipe, self._target(pipe, X.axis_size()),
                          self.slice_capacity, self.out_capacity)

    def node_string(self):
        return f"Exchange[hash({', '.join(map(str, self.keys))})]"

    def plan_key(self):
        return ("HashExchange", tuple(E.expr_key(k) for k in self.keys),
                self.key_union_dicts, self.slice_capacity,
                self.out_capacity, self.fan_destinations,
                self.presplit_hashes, self.child.plan_key())


@dataclass(eq=False)
class RoundRobinExchangeExec(P.PhysicalPlan):
    """Balanced redistribution (RoundRobinPartitioning analogue)."""

    child: P.PhysicalPlan
    slice_capacity: Optional[int] = None
    out_capacity: Optional[int] = None
    traceable = True

    def children(self):
        return (self.child,)

    @property
    def schema(self) -> Schema:
        return self.child.schema

    def _target(self, pipe: Pipe, d: int) -> jnp.ndarray:
        rank = jnp.cumsum(pipe.mask.astype(jnp.int32)) - 1
        return ((rank + X.axis_index()) % d).astype(jnp.int32)

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        pipe = child_pipes[0]
        return X.exchange(pipe, self._target(pipe, X.axis_size()),
                          self.slice_capacity, self.out_capacity)

    def plan_key(self):
        return ("RoundRobinExchange", self.slice_capacity,
                self.out_capacity, self.child.plan_key())


@dataclass(eq=False)
class RangeExchangeExec(P.PhysicalPlan):
    """Range-partition rows by the leading sort key so device order ==
    global sort order; a local sort downstream completes a distributed
    global sort (reference: ShuffleExchangeExec.scala:280 + SortExec)."""

    orders: Tuple[E.SortOrder, ...]
    child: P.PhysicalPlan
    slice_capacity: Optional[int] = None
    out_capacity: Optional[int] = None
    traceable = True

    def children(self):
        return (self.child,)

    @property
    def schema(self) -> Schema:
        return self.child.schema

    def _target(self, pipe: Pipe, d: int) -> jnp.ndarray:
        o = self.orders[0]
        key = C.evaluate(o.child, pipe.env())
        return X.range_target(key, o.ascending, o.nulls_first_resolved, d,
                              pipe.mask)

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        pipe = child_pipes[0]
        return X.exchange(pipe, self._target(pipe, X.axis_size()),
                          self.slice_capacity, self.out_capacity)

    def node_string(self):
        return f"Exchange[range({', '.join(map(str, self.orders))})]"

    def plan_key(self):
        return ("RangeExchange",
                tuple((E.expr_key(o.child), o.ascending,
                       o.nulls_first_resolved) for o in self.orders),
                self.slice_capacity, self.out_capacity,
                self.child.plan_key())


@dataclass(eq=False)
class ExchangeStatsExec(P.PhysicalPlan):
    """Measure an exchange WITHOUT running it: re-derive the routing
    targets (the same ``_target`` computation the exchange itself will
    trace, so the counts are exact, not estimates) and reduce them to
    two d-length vectors with on-device collectives — ``__incoming``
    (psum of per-destination live counts: rows each device will
    receive) and ``__maxslice`` (pmax: the largest single (src, dest)
    send cell). One tiny SPMD stage, one host fetch of 2*d int64s —
    the MapOutputStatistics of this engine (reference:
    MapOutputTrackerMaster.getStatistics, consumed by
    AdaptiveSparkPlanExec between stages).

    Optional extensions riding the same stage + fetch (hash exchanges
    only; both default off so existing uses measure exactly as before):

    - ``sketch_registers`` > 0 adds ``__ndvreg``: HyperLogLog-style
      register maxima over the exchange keys. Register index and rank
      come from the SAME full-width hash chain routing uses (minus the
      mod-D), ranks seg-max locally (through the measured selection
      table — 64 < R <= 1024 rides the Pallas one-pass kernel on TPU)
      and pmax across the mesh; the host turns register maxima into a
      distinct-key estimate. One extra O(registers) int vector.
    - ``key_stats`` > 0 adds ``__kmin``/``__kmax``/``__knull``: global
      per-key value min/max (pmin/pmax) and a nulls-present flag over
      the translated key columns — the measured packed-code domain for
      the hash-partial aggregation strategy.
    - ``cm_depth``/``cm_width`` > 0 add ``__hothash``/``__hotest``: a
      Count-Min heavy-hitter probe over the SAME row hashes routing
      uses. Each of ``cm_depth`` rows rehashes with a fixed odd seed
      into a ``cm_width``-wide count table (seg_count local, psum
      global), the per-row estimate is the min over depths, and each
      device publishes its local argmax candidate (full 64-bit key
      hash + global CM estimate) at position ``axis_index`` of the two
      d-length vectors. The host dedups candidates by hash and elects
      hot KEYS for pre-splitting (see ``presplit_hashes`` above) —
      per-key frequency the HLL sketch cannot see, at the cost of
      2*depth collectives of width ``cm_width``.
    """

    exchange: P.PhysicalPlan  # Hash/RoundRobin/Range exchange exec
    sketch_registers: int = 0    # power of two; 0 = no distinct sketch
    key_stats: int = 0           # number of keys to min/max; 0 = none
    cm_depth: int = 0            # Count-Min hash rows; 0 = no CM probe
    cm_width: int = 0            # power of two; 0 = no CM probe
    traceable = True

    def children(self):
        return self.exchange.children()

    @property
    def schema(self) -> Schema:
        fields = [Field("__incoming", T.INT64, nullable=False),
                  Field("__maxslice", T.INT64, nullable=False)]
        if self.sketch_registers:
            fields.append(Field("__ndvreg", T.INT64, nullable=False))
        if self.key_stats:
            fields.append(Field("__kmin", T.INT64, nullable=False))
            fields.append(Field("__kmax", T.INT64, nullable=False))
            fields.append(Field("__knull", T.INT64, nullable=False))
        if self.cm_depth and self.cm_width:
            fields.append(Field("__hothash", T.INT64, nullable=False))
            fields.append(Field("__hotest", T.INT64, nullable=False))
        return Schema(tuple(fields))

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        pipe = child_pipes[0]
        d = X.axis_size()
        target = self.exchange._target(pipe, d)
        local = K.seg_count(jnp.clip(target, 0, d - 1).astype(jnp.int32),
                            pipe.mask, d)
        incoming = X.psum(local).astype(jnp.int64)
        maxslice = X.pmax(local).astype(jnp.int64)

        cap = max(d, self.sketch_registers or 0, self.key_stats or 0)

        def padded(v):
            return jnp.pad(v.astype(jnp.int64), (0, cap - v.shape[0]))

        cols = {"__incoming": TV(padded(incoming), None, T.INT64, None),
                "__maxslice": TV(padded(maxslice), None, T.INT64, None)}
        order = ["__incoming", "__maxslice"]

        if self.sketch_registers or self.key_stats or \
                (self.cm_depth and self.cm_width):
            key_tvs = self.exchange._key_tvs(pipe)

        if self.sketch_registers:
            r = int(self.sketch_registers)
            p = r.bit_length() - 1          # r = 2**p (validated by caller)
            h = X.hash_rows(key_tvs)
            idx = (h & jnp.uint64(r - 1)).astype(jnp.int32)
            w = h >> jnp.uint64(p)
            # rank = leading zeros of the (64-p)-bit suffix + 1, via the
            # float64 highest-set-bit trick (floor(log2)). f64 holds 53
            # mantissa bits < the 64-p suffix width, so a value within
            # half-ulp of a power of two can mis-rank by one register —
            # an error far inside the sketch's own ~1/sqrt(r) noise.
            wf = w.astype(jnp.float64)
            hb = jnp.floor(jnp.log2(jnp.maximum(wf, 1.0)))
            rho = jnp.where(w == jnp.uint64(0),
                            jnp.float64(64 - p + 1),
                            jnp.float64(64 - p) - hb)
            # f32 ranks (<= 56: exact) route the register max through
            # the measured selection table — Pallas one-pass on TPU
            reg = K.seg_max(rho.astype(jnp.float32), idx, pipe.mask, r)
            reg = jnp.maximum(X.pmax(reg), 0.0).astype(jnp.int64)
            cols["__ndvreg"] = TV(padded(reg), None, T.INT64, None)
            order.append("__ndvreg")

        if self.key_stats:
            mins, maxs, nulls = [], [], []
            for tv in key_tvs[:self.key_stats]:
                data = tv.data.astype(jnp.int64)
                valid = pipe.mask if tv.validity is None \
                    else pipe.mask & tv.validity
                big = jnp.iinfo(jnp.int64).max
                small = jnp.iinfo(jnp.int64).min
                mins.append(X.pmin(jnp.min(
                    jnp.where(valid, data, big))[None])[0])
                maxs.append(X.pmax(jnp.max(
                    jnp.where(valid, data, small))[None])[0])
                nnull = jnp.zeros((), jnp.int64) if tv.validity is None \
                    else (pipe.mask & ~tv.validity).sum(dtype=jnp.int64)
                nulls.append(X.psum(nnull[None])[0])
            cols["__kmin"] = TV(padded(jnp.stack(mins)), None, T.INT64,
                                None)
            cols["__kmax"] = TV(padded(jnp.stack(maxs)), None, T.INT64,
                                None)
            cols["__knull"] = TV(padded(jnp.stack(nulls)), None,
                                 T.INT64, None)
            order += ["__kmin", "__kmax", "__knull"]

        if self.cm_depth and self.cm_width:
            w = int(self.cm_width)               # power of two (caller)
            h = X.hash_rows(key_tvs)
            est = None
            for seed in _CM_SEEDS[:int(self.cm_depth)]:
                hj = K.hash64(h ^ jnp.uint64(seed))
                idx = (hj & jnp.uint64(w - 1)).astype(jnp.int32)
                table = X.psum(K.seg_count(idx, pipe.mask, w))
                e = table[idx]
                est = e if est is None else jnp.minimum(est, e)
            # dead rows estimate -1 so the argmax candidate is a live
            # row whenever one exists; the host drops est <= 0 anyway
            est = jnp.where(pipe.mask, est, jnp.int64(-1))
            cand = jnp.argmax(est)
            # each device publishes (key hash, CM estimate) of its own
            # candidate at position axis_index via a one-hot psum — the
            # whole mesh's candidate list in one d-length pair
            slot = jnp.arange(cap) == X.axis_index()
            zero = jnp.int64(0)
            cols["__hothash"] = TV(
                X.psum(jnp.where(slot, h[cand].astype(jnp.int64), zero)),
                None, T.INT64, None)
            cols["__hotest"] = TV(
                X.psum(jnp.where(slot, est[cand], zero)),
                None, T.INT64, None)
            order += ["__hothash", "__hotest"]

        # replicated reductions: keep device 0's copy live, like
        # PSumAggExec, so the result reads back once
        keep = X.axis_index() == 0
        mask = jnp.broadcast_to(keep, (cap,))
        return Pipe(cols, mask, order)

    def node_string(self):
        return f"ExchangeStats[{self.exchange.node_string()}]"

    def plan_key(self):
        return ("ExchangeStats", self.sketch_registers, self.key_stats,
                self.cm_depth, self.cm_width, self.exchange.plan_key())


@dataclass(eq=False)
class BroadcastExchangeExec(P.PhysicalPlan):
    child: P.PhysicalPlan
    traceable = True

    def children(self):
        return (self.child,)

    @property
    def schema(self) -> Schema:
        return self.child.schema

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        return X.broadcast_gather(child_pipes[0])

    def plan_key(self):
        return ("BroadcastExchange", self.child.plan_key())


@dataclass(eq=False)
class SinglePartitionExchangeExec(P.PhysicalPlan):
    child: P.PhysicalPlan
    traceable = True

    def children(self):
        return (self.child,)

    @property
    def schema(self) -> Schema:
        return self.child.schema

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        return X.to_single_partition(child_pipes[0])

    def plan_key(self):
        return ("SingleExchange", self.child.plan_key())


@dataclass(eq=False)
class DistSampleExec(P.PhysicalPlan):
    """Bernoulli sample with the device index folded into the PRNG key —
    each shard draws independently (Spark seeds per partition the same
    way: RDD.sample's per-split XORShift seed)."""

    fraction: float
    seed: int
    child: P.PhysicalPlan
    traceable = True

    def children(self):
        return (self.child,)

    @property
    def schema(self) -> Schema:
        return self.child.schema

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        pipe = child_pipes[0]
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed),
                                 X.axis_index())
        u = jax.random.uniform(key, (pipe.capacity,))
        return Pipe(pipe.cols, pipe.mask & (u < self.fraction), pipe.order)

    def plan_key(self):
        return ("DistSample", self.fraction, self.seed,
                self.child.plan_key())


@dataclass(eq=False)
class DistLimitExec(P.PhysicalPlan):
    """Global limit without gathering: each device computes its rows'
    GLOBAL live-rank as local-rank + exclusive prefix of earlier devices'
    live counts (one tiny all_gather of scalars), then masks. The
    reference runs limit as a separate single-partition stage
    (limit.scala GlobalLimitExec after a shuffle); here it is one
    collective of D int64s."""

    n: int
    offset: int
    child: P.PhysicalPlan
    traceable = True

    def children(self):
        return (self.child,)

    @property
    def schema(self) -> Schema:
        return self.child.schema

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        pipe = child_pipes[0]
        d = X.axis_size()
        me = X.axis_index()
        local = pipe.mask.astype(jnp.int64)
        count = local.sum()[None]
        all_counts = jax.lax.all_gather(count, X.DATA_AXIS, tiled=True)
        prefix = jnp.where(jnp.arange(d) < me, all_counts, 0).sum()
        rank = jnp.cumsum(local) - 1 + prefix
        keep = pipe.mask & (rank >= self.offset) & (
            rank < self.offset + self.n)
        return Pipe(pipe.cols, keep, pipe.order)

    def node_string(self):
        return f"DistLimit[{self.n}]"

    def plan_key(self):
        return ("DistLimit", self.n, self.offset, self.child.plan_key())


# ---- distributed aggregation ------------------------------------------------


class _MeshMerge:
    """``P._compute_agg``'s merge on the mesh: each device's per-segment
    partial becomes the global value through one ICI collective."""

    sum = staticmethod(X.psum)
    min = staticmethod(X.pmin)
    max = staticmethod(X.pmax)

    @staticmethod
    def one_copy(mask):
        """The merged result is replicated; keep device 0's."""
        return jnp.where(X.axis_index() == 0, mask, jnp.zeros_like(mask))

    @staticmethod
    def first(data, found, vfirst):
        """The lowest device index that found a first row wins."""
        if vfirst is None:
            vfirst = jnp.ones(found.shape, jnp.bool_)
        d = X.axis_size()
        me = X.axis_index()
        winner = X.pmin(jnp.where(found, me, d))
        mine = found & (me == winner)
        zero = jnp.zeros((), dtype=data.dtype)
        data = X.psum(jnp.where(mine, data, zero))
        valid = X.psum(jnp.where(mine, vfirst, False).astype(jnp.int32)) > 0
        return data, (winner < d) & valid


@dataclass(eq=False)
class PSumAggExec(P.PhysicalPlan):
    """Direct-path aggregation over the mesh: dense group ids from
    trace-time key cardinalities, segment-reduce locally, psum-merge
    across devices — no shuffle at all. This is the north-star operator
    (SURVEY.md §2 'Partial/final aggregation'). Output lives on device 0
    (global arrays masked elsewhere)."""

    groupings: Tuple[E.Expression, ...]
    aggregates: Tuple[E.Expression, ...]
    child: P.PhysicalPlan
    traceable = True

    def children(self):
        return (self.child,)

    @property
    def schema(self) -> Schema:
        return P.HashAggregateExec(self.groupings, self.aggregates,
                                   self.child).schema

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        return P.HashAggregateExec(
            self.groupings, self.aggregates, self.child)._trace_direct(
                child_pipes[0], merge=_MeshMerge)

    def node_string(self):
        return (f"PSumAgg[keys=[{', '.join(map(str, self.groupings))}], "
                f"out=[{', '.join(str(e) for e in self.aggregates)}]]")

    def plan_key(self):
        return ("PSumAgg", tuple(E.expr_key(g) for g in self.groupings),
                tuple(E.expr_key(a) for a in self.aggregates),
                self.child.plan_key())


@dataclass(eq=False)
class DistSortAggExec(P.PhysicalPlan):
    """General group-by after a hash exchange: each device owns whole
    groups, sorts locally, assigns group ids by change-flags. Fully
    traceable — the static segment count is the row capacity (every row
    its own group, worst case), so no host sync is needed inside the
    program (contrast: single-device sort-agg host-syncs the group count;
    reference contrast: TungstenAggregationIterator.scala:82 falls back
    to sort-based with spills)."""

    groupings: Tuple[E.Expression, ...]
    aggregates: Tuple[E.Expression, ...]
    child: P.PhysicalPlan
    #: adaptive-aggregation tag: "partial" marks the pre-exchange half
    #: of a partial->final plan (the node the runtime strategy switch
    #: may bypass or swap for a hash partial); None = ordinary
    phase: Optional[str] = None
    traceable = True

    def children(self):
        return (self.child,)

    @property
    def schema(self) -> Schema:
        return P.HashAggregateExec(self.groupings, self.aggregates,
                                   self.child).schema

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        pipe = child_pipes[0]
        cap = pipe.capacity
        env = pipe.env()
        key_tvs = [C.evaluate(g, env) for g in self.groupings]

        spipe, sorted_keys, seg, ng = P.sorted_groups(pipe, key_tvs)
        env2 = spipe.env()
        _, agg_calls = rewrite_agg_outputs(self.groupings, self.aggregates)
        agg_tvs = [P._compute_agg(a, env2, seg, spipe.mask, cap, cap,
                                  sorted_seg=True)
                   for a in agg_calls]
        out_keys = P.first_group_keys(sorted_keys, seg, spipe.mask, cap, cap,
                                      sorted_seg=True)
        out_mask = jnp.arange(cap) < ng
        agg_exec = P.HashAggregateExec(self.groupings, self.aggregates,
                                       self.child)
        return agg_exec._finalize(out_keys, agg_tvs, out_mask, cap)

    def node_string(self):
        return (f"DistSortAgg[keys=[{', '.join(map(str, self.groupings))}], "
                f"out=[{', '.join(str(e) for e in self.aggregates)}]]")

    def plan_key(self):
        return ("DistSortAgg", tuple(E.expr_key(g) for g in self.groupings),
                tuple(E.expr_key(a) for a in self.aggregates),
                self.phase, self.child.plan_key())


@dataclass(eq=False)
class DistRangeAggExec(DistSortAggExec):
    """The sort-based aggregation rung's final: the identical local
    sort-and-segment merge as DistSortAggExec, but the executor plans
    it over a RANGE exchange on the group keys instead of a hash
    exchange, so device order == global key order and the per-device
    lexsort completes a distributed global sort — the aggregate's
    output is key-ordered across the whole mesh for free, and a
    matching downstream global Sort collapses to a no-op (the executor
    marks the result batch ``sorted_by``; the sort-vs-hash trade of
    'sort-based group-by produces ordered output as a byproduct'). A
    distinct node so plan/trace cache keys and EXPLAIN output
    distinguish the rung from an ordinary hash-routed DistSortAgg."""

    def node_string(self):
        return (f"DistRangeAgg[keys=[{', '.join(map(str, self.groupings))}],"
                f" out=[{', '.join(str(e) for e in self.aggregates)}]]")

    def plan_key(self):
        return ("DistRangeAgg",) + super().plan_key()[1:]


# ---- whole-query native fusion ----------------------------------------------


def capacity_ladder(bucket: int, variants: int, worst: int,
                    devices: int = 1) -> Tuple[int, ...]:
    """The precompiled capacity rungs one fused span bakes as
    ``lax.switch`` branches, anchored at the BALANCED receive load:
    a well-spread exchange over ``devices`` destinations delivers
    ~worst/devices rows to the hottest one, so the top working rung is
    ceil(worst/devices) rounded up to the adaptive capacity bucket
    plus ONE bucket of headroom (without the headroom, a load one row
    past balanced spills to the next rung — 4x the buffer for a
    rounding miss). Below the anchor the rungs refine geometrically /4
    (bucket-rounded, same headroom) for sparse loads — aggregation
    partials after local dedup carry far fewer live rows than the
    producer's static capacity. The worst case (every live row routed
    to one destination) is always the last rung, so any measured
    incoming count is covered — the fused program can never drop a
    live row the staged path would keep. The band BETWEEN anchor and
    worst gets no rungs on purpose: range exchanges are balanced by
    equi-depth sampling, and skewed hash aggregations bail out to the
    staged skew pre-split before fusion — loads up there are the rare
    case the worst rung exists for."""
    bucket = max(1, int(bucket))
    variants = max(1, int(variants))
    worst = max(1, int(worst))
    d = max(1, int(devices))
    anchor = -(-worst // d)                            # balanced load
    anchor = -(-anchor // bucket) * bucket + bucket    # round up + headroom
    rungs: List[int] = [worst]
    c = min(anchor, worst)
    while len(rungs) < variants and c < rungs[-1]:
        rungs.append(c)
        nxt = -(-c // 4)                               # ceil(c / 4)
        nxt = -(-nxt // bucket) * bucket + bucket
        if nxt >= c:
            break
        c = nxt
    return tuple(reversed(rungs))


@dataclass(eq=False)
class FusedSpanExec(P.PhysicalPlan):
    """One adaptive exchange + consumer pair compiled as a single
    on-device span — the whole-query fusion building block (the XLA-
    native Flare move, arXiv 1703.08219: compile the operator boundary
    away instead of interpreting it).

    The staged path runs FOUR dispatches with a host sync in the
    middle: producer stage, ExchangeStatsExec stage + host fetch of
    2*d int64s, the exchange re-run at the measured capacity, then the
    re-traced consumer stage. Here the SAME stats computation
    (seg_count of the routing targets, psum across the mesh) stays on
    device and a ``lax.switch`` over the capacity ladder picks the
    rung: each branch runs the collective exchange at ITS rung's
    slice/receive capacities, traces the consumer there, and pads the
    result back to the common worst-case shape. Putting the collective
    inside the branches is safe because the branch index derives from
    psum'd counts — replicated bit-identically across the mesh — so
    every device provably takes the same branch and the all_to_all
    pairs up; it is what lets the fused program ship rung-sized ICI
    buffers instead of worst-case ones, matching the staged path's
    measured compaction to within one ladder step (4x).

    Byte-identity with the staged path holds because every transform
    is order-stable: the exchange's live-row sequence is independent
    of slice/out capacity (stable argsort-by-destination + stable
    compaction), the whitelisted consumers (SortExec, DistSortAggExec)
    are capacity-preserving and capacity-independent on live rows, and
    the padding rows are masked dead — collect never sees them. The
    executor only builds this node when the pair's ONLY adaptive
    decision is capacity; anything host-bound (skew fan, agg strategy
    crossover, sort elision) bails out to staged execution first
    (executor._try_fuse)."""

    #: the consumer node, child == ``exchange`` (kept nested so schema
    #: derivation and plan keys need no placeholder surgery; trace()
    #: feeds it pipes directly and never walks the child link)
    consumer: P.PhysicalPlan
    #: the adaptive exchange (hash/range/round-robin), child == producer
    exchange: P.PhysicalPlan
    #: capacity-ladder base (spark.tpu.adaptive.capacityBucket)
    bucket: int
    #: max ladder rungs (spark.tpu.fusion.maxBucketVariants)
    variants: int
    #: downstream chain operators applied INSIDE this span's branches,
    #: in dataflow order: row-preserving interstitials (Project/Filter)
    #: and further FusedSpanExec pairs. Nesting the downstream pairs
    #: inside the upstream branches is what keeps every intermediate
    #: shape RUNG-sized: the chained span's routing (target hashing,
    #: range sampling, argsort) traces over the selected rung's
    #: capacity instead of the worst-case padding — only the single
    #: final leaf pads to the chain's common output shape. An empty
    #: tail is a plain one-pair span.
    tail: Tuple[P.PhysicalPlan, ...] = ()
    #: speculative rung-sized OUTPUT, set by the executor only when
    #: this span is the plan root (nothing above that could touch the
    #: sentinel row). Instead of padding the leaves to the worst case
    #: — which makes output materialization and collection scale with
    #: a capacity real loads never reach — the leaves emit at the
    #: ladder anchor (+12.5% sampling margin) plus ONE sentinel slot
    #: whose mask bit says "live rows were sliced off". The executor
    #: reads the sentinel from the mask it fetches anyway; when set it
    #: discards the result and re-runs the staged path (typed
    #: ``overflow`` bailout), so byte-identity is preserved without
    #: worst-case-shaped outputs.
    speculate: bool = False
    traceable = True

    def children(self):
        return self.exchange.children()

    @property
    def schema(self) -> Schema:
        return self.tail[-1].schema if self.tail else self.consumer.schema

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        pipe = child_pipes[0]
        d = X.axis_size()
        # a producer padded to ITS worst case (an unmerged upstream
        # fused span) carries a tighter total-live-rows bound than
        # d * capacity — using it keeps chained buffers at
        # O(total rows) instead of O(d^k * rows)
        worst0 = d * pipe.capacity
        if pipe.rows_bound is not None:
            worst0 = min(worst0, int(pipe.rows_bound))
        ladder0 = capacity_ladder(self.bucket, self.variants, worst0, d)
        spec = self.speculate
        if spec and len(ladder0) > 1:
            # speculative output capacity: the ladder anchor plus a
            # 12.5% sampling margin (range-exchange bounds come from
            # samples; a hot destination can land a few percent past
            # balanced without being genuinely skewed). A single-rung
            # ladder keeps the worst-case shape — the sentinel is then
            # constant-dead and the executor check is trivially false
            b = max(1, int(self.bucket))
            f_out = min(worst0,
                        -(-(ladder0[-2] * 9 // 8) // b) * b)
        else:
            f_out = worst0
        meta: dict = {}

        def leaf(out: Pipe):
            # every nested switch path returns this one common shape:
            # f_out slots plus (speculating) one sentinel slot whose
            # mask bit records that live rows were sliced off — the
            # executor turns that into a staged re-run. Host-side
            # capture at switch-build time: every leaf traces eagerly,
            # so the dtype/dictionary metadata the pytree return
            # strips is available to rebuild the Pipe
            if out.capacity > f_out:
                over = jnp.any(out.mask[f_out:])
                out = _slice_pipe(out, f_out)
            else:
                over = jnp.zeros((), dtype=jnp.bool_)
            out = _pad_pipe(out, f_out + 1 if spec else f_out)
            mask = out.mask.at[f_out].set(over) if spec else out.mask
            meta.setdefault("order", tuple(out.order))
            meta.setdefault("tv", {n: (tv.dtype, tv.dictionary)
                                   for n, tv in out.cols.items()})
            return (mask,
                    {n: (out.cols[n].data, out.cols[n].validity)
                     for n in out.order})

        def run_ops(p: Pipe, ops):
            if not ops:
                return leaf(p)
            op, rest = ops[0], ops[1:]
            if isinstance(op, FusedSpanExec):
                return pair(p, op, rest)
            return run_ops(op.trace([p]), rest)

        def pair(p: Pipe, span: "FusedSpanExec", rest):
            # the staged ExchangeStatsExec computation, kept on
            # device: per-destination live counts, psum'd — max over
            # destinations is exactly the staged path's measured
            # out-capacity input
            target = span.exchange._target(p, d)
            local = K.seg_count(
                jnp.clip(target, 0, d - 1).astype(jnp.int32), p.mask, d)
            max_in = jnp.max(X.psum(local).astype(jnp.int64))
            # total live rows through the chain never grow (the
            # whitelisted consumers are Sort/DistSortAgg, interstitials
            # Project/Filter), so worst0 bounds every downstream span
            ladder = capacity_ladder(span.bucket, span.variants,
                                     min(d * p.capacity, worst0), d)

            def rung(ocap: int):
                def branch(_):
                    # collective INSIDE the branch, at the rung's
                    # capacities: one sender's slice to a destination
                    # can never exceed that destination's total
                    # incoming rows, so min(cap, ocap) is a safe slice
                    # bound whenever the receive rung ocap covers the
                    # measured max_in — which branch selection
                    # guarantees
                    sub = X.exchange(p, target,
                                     min(p.capacity, ocap), ocap)
                    return run_ops(span.consumer.trace([sub]), rest)
                return branch

            arr = jnp.asarray(ladder, dtype=jnp.int64)
            idx = jnp.clip(jnp.sum((arr < max_in).astype(jnp.int32)),
                           0, len(ladder) - 1)
            return jax.lax.switch(idx, [rung(c) for c in ladder], 0)

        mask, flat = pair(pipe, self, tuple(self.tail))
        cols = {n: TV(flat[n][0], flat[n][1], *meta["tv"][n])
                for n in meta["order"]}
        # row counts never grow through the chain, so total live rows
        # out <= total live rows in <= worst0
        return Pipe(cols, mask, list(meta["order"]), rows_bound=worst0)

    def node_string(self):
        chain = "".join(" -> " + (t.consumer.node_string()
                                  if isinstance(t, FusedSpanExec)
                                  else t.node_string())
                        for t in self.tail)
        return (f"FusedSpan[bucket={self.bucket}, "
                f"variants={self.variants}, "
                f"consumer={self.consumer.node_string()}{chain}]")

    def plan_key(self):
        # structural fingerprint of the WHOLE fused span plus the
        # bucket-ladder parameters: the jit stage cache and the
        # compile-store digest both key on this, so a conf change to
        # the ladder recompiles instead of replaying a mismatched
        # executable
        return ("FusedSpan", self.bucket, self.variants,
                self.speculate, self.consumer.plan_key(),
                self.exchange.plan_key(),
                tuple(t.plan_key() for t in self.tail))


def _slice_pipe(pipe: Pipe, capacity: int) -> Pipe:
    """Truncate a pipe to its first ``capacity`` slots (live rows past
    the cut are LOST — callers must detect that and fall back; see
    FusedSpanExec speculative output)."""
    cols = {
        name: TV(tv.data[:capacity],
                 None if tv.validity is None else tv.validity[:capacity],
                 tv.dtype, tv.dictionary)
        for name, tv in pipe.cols.items()
    }
    return Pipe(cols, pipe.mask[:capacity], pipe.order)


def _pad_pipe(pipe: Pipe, capacity: int) -> Pipe:
    """Grow a pipe to ``capacity`` slots with dead rows (mask False, so
    collect and every mask-respecting consumer ignore them). Needed so
    all ladder branches return one common static shape."""
    cap = pipe.capacity
    if cap >= int(capacity):
        return pipe
    n = int(capacity) - cap

    def grow(a, fill):
        pad = ((0, n),) + ((0, 0),) * (a.ndim - 1)
        return jnp.pad(a, pad, constant_values=fill)

    cols = {
        name: TV(grow(tv.data, 0),
                 None if tv.validity is None else grow(tv.validity, False),
                 tv.dtype, tv.dictionary)
        for name, tv in pipe.cols.items()
    }
    return Pipe(cols, grow(pipe.mask, False), pipe.order)


@dataclass(eq=False)
class DistHashPartialAggExec(P.PhysicalPlan):
    """Hash-based partial aggregation over a RUNTIME-MEASURED key
    domain: the stats stage measured each key's global [min, max] (and
    nulls-present), so keys range-compress to collision-free packed
    codes and the partials are dense segment reductions over
    num_segments = the measured domain — no sort, no host sync, and
    the reductions route through the measured selection table
    (<= 64 XLA fused, 64 < K <= 1024 the Pallas one-pass kernel; see
    ops/pallas_agg.py). This is the runtime analogue of the static
    direct path in physical/operators.HashAggregateExec, unlocked for
    int keys whose cardinality only the data knows.

    Output schema/order contract: identical to the sort-based partial
    (key aliases + partial accumulators), so the downstream exchange
    and final merge are strategy-oblivious. Per-group values are
    byte-identical to the sort partial for strategy-legal aggregates
    (legality.strategy_verdict); only row order and capacity differ,
    and the final merge re-groups anyway."""

    groupings: Tuple[E.Expression, ...]
    aggregates: Tuple[E.Expression, ...]
    child: P.PhysicalPlan
    key_mins: Tuple[int, ...] = ()    # measured per-key global min
    key_ranges: Tuple[int, ...] = ()  # measured value range (max-min+1)
    traceable = True

    def children(self):
        return (self.child,)

    @property
    def schema(self) -> Schema:
        return P.HashAggregateExec(self.groupings, self.aggregates,
                                   self.child).schema

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        pipe = child_pipes[0]
        cap = pipe.capacity
        env = pipe.env()
        key_tvs = [C.evaluate(g, env) for g in self.groupings]

        codes, validities, cards = [], [], []
        for tv, mn, rg in zip(key_tvs, self.key_mins, self.key_ranges):
            # range compression: measured min/range make the clip a
            # no-op for every live row (the measurement ran over these
            # exact arrays); pack_codes adds the null slot per key
            codes.append(jnp.clip(tv.data.astype(jnp.int64) - mn, 0,
                                  rg - 1))
            validities.append(tv.validity)
            cards.append(int(rg))
        seg, num_segments = K.pack_codes(codes, validities, cards)
        seg = seg.astype(jnp.int32)
        num_segments = max(1, int(num_segments))

        _, agg_calls = rewrite_agg_outputs(self.groupings, self.aggregates)
        agg_tvs = [P._compute_agg(a, env, seg, pipe.mask, num_segments,
                                  cap)
                   for a in agg_calls]

        # LOCAL partials: each device keeps its own groups (no psum) —
        # the downstream exchange routes them to the final merge
        out_mask = K.seg_count(seg, pipe.mask, num_segments) > 0
        nullable = [v is not None for v in validities]
        unpacked = K.unpack_code(jnp.arange(num_segments), cards, nullable)
        out_keys = []
        for (code, valid), tv, mn in zip(unpacked, key_tvs,
                                         self.key_mins):
            data = (code + mn).astype(C._jnp_dtype(tv.dtype))
            out_keys.append(TV(data, valid, tv.dtype, tv.dictionary))
        agg_exec = P.HashAggregateExec(self.groupings, self.aggregates,
                                       self.child)
        return agg_exec._finalize(out_keys, agg_tvs, out_mask,
                                  num_segments)

    def node_string(self):
        return (f"DistHashPartialAgg[keys="
                f"[{', '.join(map(str, self.groupings))}], "
                f"domain={tuple(self.key_ranges)}]")

    def plan_key(self):
        return ("DistHashPartialAgg",
                tuple(E.expr_key(g) for g in self.groupings),
                tuple(E.expr_key(a) for a in self.aggregates),
                self.key_mins, self.key_ranges, self.child.plan_key())


# ---- distributed join -------------------------------------------------------


def join_output_schema(left: Schema, right: Schema, how: str) -> Schema:
    return P.JoinExec(P._SchemaOnly(left), P._SchemaOnly(right), how,
                      (), ()).schema


def packed_join_keys(lpipe: Pipe, rpipe: Pipe,
                     left_keys: Tuple[E.Expression, ...],
                     right_keys: Tuple[E.Expression, ...],
                     mins, ranges):
    """Pack equi-join keys into one int64 per row using STATIC per-key
    min/range stats (host-supplied from a stats pass — the AQE runtime
    statistics pattern, reference: adaptive/AdaptiveSparkPlanExec.scala:247).
    Strings pack via trace-time unified dictionaries. Collision-free by
    construction, unlike hashing. ``mins is None`` switches to the
    hash-combined fallback (wide int64 ranges); callers must then verify
    candidate pairs by exact key equality. Returns
    (lkey, lvalid, rkey, rvalid, prepped) where prepped holds the
    translated per-key arrays for verification."""
    hashed = mins is None
    lenv, renv = lpipe.env(), rpipe.env()
    lks = [C.evaluate(k, lenv) for k in left_keys]
    rks = [C.evaluate(k, renv) for k in right_keys]
    lcomb = jnp.zeros((lpipe.capacity,), dtype=jnp.int64)
    rcomb = jnp.zeros((rpipe.capacity,), dtype=jnp.int64)
    lvalid = jnp.ones((lpipe.capacity,), dtype=jnp.bool_)
    rvalid = jnp.ones((rpipe.capacity,), dtype=jnp.bool_)
    prepped = []
    for ki, (lt, rt) in enumerate(zip(lks, rks)):
        if isinstance(lt.dtype, T.StringType) or isinstance(rt.dtype, T.StringType):
            _, (tl, tr) = C.unify_dictionaries(
                (lt.dictionary or (), rt.dictionary or ()))
            ld = jnp.asarray(tl)[lt.data] if len(lt.dictionary or ()) else lt.data
            rd = jnp.asarray(tr)[rt.data] if len(rt.dictionary or ()) else rt.data
        else:
            ld = lt.data.astype(jnp.int64)
            rd = rt.data.astype(jnp.int64)
        prepped.append((ld, rd))
        if not hashed:
            mn, rg = mins[ki], ranges[ki]
            lcomb = lcomb * rg + jnp.clip(ld - mn, 0, rg - 1)
            rcomb = rcomb * rg + jnp.clip(rd - mn, 0, rg - 1)
        if lt.validity is not None:
            lvalid = lvalid & lt.validity
        if rt.validity is not None:
            rvalid = rvalid & rt.validity
    if hashed:
        lcomb, rcomb = P._hash_keys([p[0] for p in prepped],
                                    [p[1] for p in prepped])
    return lcomb, lvalid, rcomb, rvalid, prepped


@dataclass(eq=False)
class TopKeyExec(P.PhysicalPlan):
    """Per-device heavy-hitter probe: the most frequent key tuple in
    the device's local shard, with its local count (one output row per
    device). The detection pass for AQE skew SPLIT — the reference
    detects skew from shuffle-partition SIZES
    (adaptive/OptimizeSkewedJoin.scala:37); here row distribution is
    uniform by construction (row-sliced shards), so the hot KEY VALUE
    is detected instead and the executor splits the join around it."""

    keys: Tuple[E.Expression, ...]
    child: P.PhysicalPlan
    traceable = True

    def children(self):
        return (self.child,)

    @property
    def schema(self) -> Schema:
        cs = self.child.schema
        fields = []
        for i, k in enumerate(self.keys):
            inner = E.strip_alias(k)
            dictionary = None
            if isinstance(inner, E.Col) and inner.col_name in cs:
                dictionary = cs.field(inner.col_name).dictionary
            fields.append(Field(f"__hk{i}", k.data_type(cs), True,
                                dictionary))
        fields.append(Field("__cnt", T.INT64, nullable=False))
        return Schema(tuple(fields))

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        pipe = child_pipes[0]
        cap = pipe.capacity
        env = pipe.env()
        key_tvs = [C.evaluate(k, env) for k in self.keys]
        spipe, sorted_keys, seg, _ = P.sorted_groups(pipe, key_tvs)
        cnt = K.seg_count(seg, spipe.mask, cap, sorted_seg=True)
        best = jnp.argmax(cnt)
        reps = P.first_group_keys(sorted_keys, seg, spipe.mask, cap, cap,
                                  sorted_seg=True)
        cols: Dict[str, TV] = {}
        order = []
        for i, tv in enumerate(reps):
            nm = f"__hk{i}"
            cols[nm] = TV(tv.data[best][None],
                          None if tv.validity is None
                          else tv.validity[best][None],
                          tv.dtype, tv.dictionary)
            order.append(nm)
        cols["__cnt"] = TV(cnt[best][None].astype(jnp.int64), None,
                           T.INT64, None)
        order.append("__cnt")
        return Pipe(cols, jnp.ones((1,), jnp.bool_), order)

    def node_string(self):
        return f"TopKey[{', '.join(map(str, self.keys))}]"

    def plan_key(self):
        return ("TopKey", tuple(E.expr_key(k) for k in self.keys),
                self.child.plan_key())


@dataclass(eq=False)
class JoinCountExec(P.PhysicalPlan):
    """Stats pass: per-device equi-join match count (capacity sizing for
    JoinApplyExec). Output: one int64 per device."""

    left: P.PhysicalPlan
    right: P.PhysicalPlan
    left_keys: Tuple[E.Expression, ...]
    right_keys: Tuple[E.Expression, ...]
    mins: Tuple[int, ...]
    ranges: Tuple[int, ...]
    broadcast: bool
    traceable = True

    def children(self):
        return (self.left, self.right)

    @property
    def schema(self) -> Schema:
        return Schema((Field("cnt", T.INT64, nullable=False),))

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        lpipe, rpipe = child_pipes
        if self.broadcast:
            rpipe = X.broadcast_gather(rpipe)
        lkey, lvalid, rkey, rvalid, _ = packed_join_keys(
            lpipe, rpipe, self.left_keys, self.right_keys,
            self.mins, self.ranges)
        rng = K.build_join_ranges(rkey, rpipe.mask & rvalid,
                                  lkey, lpipe.mask & lvalid)
        cnt = jnp.where(lpipe.mask & lvalid, rng.counts, 0).sum(
            dtype=jnp.int64)
        return Pipe({"cnt": TV(cnt[None], None, T.INT64, None)},
                    jnp.ones((1,), jnp.bool_), ["cnt"])

    def plan_key(self):
        return ("JoinCount", tuple(E.expr_key(k) for k in self.left_keys),
                tuple(E.expr_key(k) for k in self.right_keys),
                self.mins, self.ranges, self.broadcast,
                self.left.plan_key(), self.right.plan_key())


@dataclass(eq=False)
class JoinApplyExec(P.PhysicalPlan):
    """Per-device equi-join with a STATIC pair capacity (host-synced from
    JoinCountExec). After a hash exchange both sides of a key group are
    co-resident, so device-local sorted-build + searchsorted ranges +
    vectorized pair expansion produce exactly the reference's shuffled
    hash join semantics (ShuffledHashJoinExec.scala:38) — or, with
    broadcast=True, the broadcast hash join (BroadcastHashJoinExec.scala:40)."""

    left: P.PhysicalPlan
    right: P.PhysicalPlan
    how: str
    left_keys: Tuple[E.Expression, ...]
    right_keys: Tuple[E.Expression, ...]
    condition: Optional[E.Expression]
    mins: Tuple[int, ...]
    ranges: Tuple[int, ...]
    pair_capacity: int
    broadcast: bool
    traceable = True

    def children(self):
        return (self.left, self.right)

    @property
    def schema(self) -> Schema:
        return join_output_schema(self.left.schema, self.right.schema,
                                  self.how)

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        lpipe, rpipe = child_pipes
        how = self.how
        if self.broadcast:
            rpipe = X.broadcast_gather(rpipe)
        if how == "cross":
            return self._cross(lpipe, rpipe)

        lkey, lvalid, rkey, rvalid, prepped = packed_join_keys(
            lpipe, rpipe, self.left_keys, self.right_keys,
            self.mins, self.ranges)
        hashed = self.mins is None
        ranges = K.build_join_ranges(rkey, rpipe.mask & rvalid,
                                     lkey, lpipe.mask & lvalid)

        if how in ("left_semi", "left_anti") and self.condition is None \
                and not hashed:
            has_match = ranges.counts > 0
            keep = lpipe.mask & (has_match if how == "left_semi"
                                 else ~has_match)
            return Pipe(lpipe.cols, keep, lpipe.order)

        cap = self.pair_capacity
        p_idx, b_idx, pair_mask = K.expand_join_pairs(ranges, cap)
        if hashed:
            pair_mask = pair_mask & P._verify_key_pairs(
                prepped, p_idx, b_idx, cap)

        # pair env always carries BOTH sides so semi/anti conditions can
        # reference the inner relation (names match Join.schema dedup)
        pair_names = P._pair_names(lpipe.order, rpipe.order)
        lnames = list(lpipe.order)
        cols: Dict[str, TV] = {}
        order: List[str] = []
        for out_name, src_name in zip(pair_names[:len(lnames)], lnames):
            tv = lpipe.cols[src_name]
            cols[out_name] = TV(
                tv.data[p_idx],
                None if tv.validity is None else tv.validity[p_idx],
                tv.dtype, tv.dictionary)
            order.append(out_name)
        for out_name, src_name in zip(pair_names[len(lnames):],
                                      rpipe.order):
            tv = rpipe.cols[src_name]
            cols[out_name] = TV(
                tv.data[b_idx],
                None if tv.validity is None else tv.validity[b_idx],
                tv.dtype, tv.dictionary)
            order.append(out_name)

        pair_ok = pair_mask
        if self.condition is not None:
            ctv = C.evaluate(self.condition, Env(cols, cap))
            pair_ok = pair_ok & ctv.data & ctv.valid_or_true(cap)

        if how == "inner":
            return Pipe(cols, pair_ok, order)

        matched = K.seg_count(p_idx, pair_ok, lpipe.capacity) > 0
        if how == "left_semi":
            return Pipe(lpipe.cols, lpipe.mask & matched, lpipe.order)
        if how == "left_anti":
            return Pipe(lpipe.cols, lpipe.mask & ~matched, lpipe.order)
        matched_b = (K.seg_count(b_idx, pair_ok, rpipe.capacity) > 0
                     if how in ("right", "full") else None)

        mask = pair_ok
        if how in ("left", "full"):
            cols, mask, order, _ = P.append_unmatched_left(
                cols, mask, order, lpipe, matched)
        if how in ("right", "full"):
            if self.broadcast:
                raise AssertionError(
                    "right/full outer join must not broadcast the build side")
            cols, mask, order, _ = P.append_unmatched_right(
                cols, mask, order, lpipe, rpipe, matched_b)
        return Pipe(cols, mask, order)

    def _cross(self, lpipe: Pipe, rpipe: Pipe) -> Pipe:
        """pair_capacity = per-device left capacity * global live right
        rows (host-computed)."""
        cap = self.pair_capacity
        rn = max(1, cap // max(1, lpipe.capacity))
        j = jnp.arange(cap)
        p_idx = jnp.clip(j // rn, 0, lpipe.capacity - 1)
        rperm = K.compaction_permutation(rpipe.mask)
        b_idx = rperm[jnp.clip(j % rn, 0, rpipe.capacity - 1)]
        live_r = jnp.cumsum(rpipe.mask.astype(jnp.int64))[-1]
        pair_mask = lpipe.mask[p_idx] & ((j % rn) < live_r)

        out_schema = self.schema
        cols: Dict[str, TV] = {}
        order: List[str] = []
        for out_f, src_name in zip(out_schema.fields[:len(lpipe.order)],
                                   lpipe.order):
            tv = lpipe.cols[src_name]
            cols[out_f.name] = TV(
                tv.data[p_idx],
                None if tv.validity is None else tv.validity[p_idx],
                tv.dtype, tv.dictionary)
            order.append(out_f.name)
        for out_f, src_name in zip(out_schema.fields[len(lpipe.order):],
                                   rpipe.order):
            tv = rpipe.cols[src_name]
            cols[out_f.name] = TV(
                tv.data[b_idx],
                None if tv.validity is None else tv.validity[b_idx],
                tv.dtype, tv.dictionary)
            order.append(out_f.name)
        if self.condition is not None:
            ctv = C.evaluate(self.condition, Env(cols, cap))
            pair_mask = pair_mask & ctv.data & ctv.valid_or_true(cap)
        return Pipe(cols, pair_mask, order)

    def node_string(self):
        ks = ", ".join(f"{l}={r}" for l, r in zip(self.left_keys,
                                                  self.right_keys))
        tag = "broadcast" if self.broadcast else "partitioned"
        return f"DistJoin[{self.how}, {tag}, ({ks}), cond={self.condition}]"

    def plan_key(self):
        return ("JoinApply", self.how,
                tuple(E.expr_key(k) for k in self.left_keys),
                tuple(E.expr_key(k) for k in self.right_keys),
                None if self.condition is None else E.expr_key(self.condition),
                self.mins, self.ranges, self.pair_capacity, self.broadcast,
                self.left.plan_key(), self.right.plan_key())


@dataclass(eq=False)
class DistJoinBoundary(P.PhysicalPlan):
    """Planner marker: a join that the executor lowers into (exchange) +
    stats + count + apply stage programs. Not traceable — it is a stage
    boundary, exactly where the reference's DAGScheduler cuts stages
    (DAGScheduler.scala:1355 submitStage at ShuffleDependency edges)."""

    left: P.PhysicalPlan
    right: P.PhysicalPlan
    how: str
    left_keys: Tuple[E.Expression, ...]
    right_keys: Tuple[E.Expression, ...]
    condition: Optional[E.Expression]
    traceable = False

    def children(self):
        return (self.left, self.right)

    @property
    def schema(self) -> Schema:
        if self.how in ("left_semi", "left_anti"):
            return self.left.schema
        return join_output_schema(self.left.schema, self.right.schema,
                                  self.how)

    def node_string(self):
        return f"JoinBoundary[{self.how}]"

    def plan_key(self):
        return ("JoinBoundary", self.how,
                tuple(E.expr_key(k) for k in self.left_keys),
                tuple(E.expr_key(k) for k in self.right_keys),
                None if self.condition is None else E.expr_key(self.condition),
                self.left.plan_key(), self.right.plan_key())
