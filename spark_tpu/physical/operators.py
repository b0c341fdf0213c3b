"""Physical operators.

Analogue of the reference's SparkPlan operator tier (reference:
sql/core/.../execution/basicPhysicalOperators.scala ProjectExec:42
FilterExec:216 RangeExec:412, aggregate/HashAggregateExec.scala:47,
SortExec.scala:40, joins/ShuffledHashJoinExec.scala:38 +
HashedRelation.scala, limit.scala) — re-architected for XLA:

- Operators are either **traceable** (pure static-shape functions that
  compose into one jitted XLA program — the whole-stage-codegen analogue,
  reference WholeStageCodegenExec.scala:627, with XLA playing Janino) or
  **blocking** (need a host sync to size their output: general hash
  aggregation, joins). The executor fuses maximal traceable subtrees.
- A pipeline carries ``(cols: {name: TV}, row_mask)``; filters flip mask
  bits, projections rebuild the dict — shapes never change mid-stage.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_tpu import trace as _trace
from spark_tpu import types as T
from spark_tpu.columnar.batch import Batch, BatchData, ColumnData
from spark_tpu.expr import compiler as C
from spark_tpu.expr import expressions as E
from spark_tpu.expr.compiler import TV, Env
from spark_tpu.physical import kernels as K
from spark_tpu.types import Field, Schema


class Pipe:
    """Trace-time pipeline state flowing through fused operators.

    ``rows_bound``, when set, is a static upper bound on the TOTAL live
    rows across the whole mesh — tighter than ``d * capacity`` when the
    pipe was padded to a worst-case shape (fused spans pad their output
    to the capacity-ladder worst while carrying far fewer live rows).
    Chained fused spans use it to size their ladder from real row
    counts instead of the upstream padding, which is what keeps a
    k-span chain's buffers at O(total rows) rather than O(d^k * rows).
    Row-preserving operators (Project, Filter) thread it through; any
    operator that can grow row counts simply drops it, which is always
    safe (consumers fall back to d * capacity)."""

    __slots__ = ("cols", "mask", "order", "rows_bound")

    def __init__(self, cols: Dict[str, TV], mask: jnp.ndarray,
                 order: Sequence[str],
                 rows_bound: Optional[int] = None):
        self.cols = cols
        self.mask = mask
        self.order = list(order)
        self.rows_bound = rows_bound

    @property
    def capacity(self) -> int:
        return int(self.mask.shape[0])

    def env(self) -> Env:
        return Env(self.cols, self.capacity, self.mask)

    @classmethod
    def from_batch_data(cls, schema: Schema, data: BatchData) -> "Pipe":
        cols = {}
        for f, cd in zip(schema.fields, data.columns):
            d = cd.data
            want = C._jnp_dtype(f.dtype)
            if d.ndim == 1 and d.dtype != want \
                    and jnp.issubdtype(d.dtype, jnp.integer) \
                    and jnp.issubdtype(want, jnp.integer):
                # a column the device holds narrower than its schema
                # says (batch.from_numpy narrow_transfer: a resident
                # scan's, a streamed chunk's): widen it at trace entry,
                # where the convert fuses into its consumers
                d = d.astype(want)
            cols[f.name] = TV(d, cd.validity, f.dtype, f.dictionary)
        return cls(cols, data.row_mask, schema.names)

    def to_batch(self) -> Batch:
        fields = []
        cds = []
        for name in self.order:
            tv = self.cols[name]
            fields.append(Field(name, tv.dtype,
                                nullable=tv.validity is not None,
                                dictionary=tv.dictionary))
            cds.append(ColumnData(tv.data, tv.validity))
        return Batch(Schema(tuple(fields)),
                     BatchData(tuple(cds), self.mask))


class PhysicalPlan:
    """Base physical operator."""

    def children(self) -> Tuple["PhysicalPlan", ...]:
        return ()

    @property
    def schema(self) -> Schema:
        raise NotImplementedError

    #: True when ``trace`` composes into a fused jit program.
    traceable: bool = False

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        raise NotImplementedError(f"{type(self).__name__} is not traceable")

    def execute_blocking(self, child_batches: List[Batch]) -> Batch:
        """Eager execution with host syncs allowed."""
        pipes = [Pipe.from_batch_data(b.schema, b.data) for b in child_batches]
        return self.trace(pipes).to_batch()

    def map_children(self, fn) -> "PhysicalPlan":
        """This node with ``fn`` applied to every field that holds a
        plan; the node itself when ``fn`` changed none of them."""
        new = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if isinstance(v, PhysicalPlan):
                nv = fn(v)
                if nv is not v:
                    new[f.name] = nv
        return dataclasses.replace(self, **new) if new else self

    def stats_key(self) -> tuple:
        """Identity for adaptive runtime stats: plan structure + leaf
        array ids (jax arrays are immutable, so id-equality implies
        data-equality — stats recorded for these exact arrays can be
        replayed as static trace constants). Returns (key, arrays): the
        cache weakrefs ``arrays`` and self-evicts when any dies, so a
        recycled id can never alias a live entry.

        Memoized per plan instance: executes call this from several
        walks (_replay_compactions, _bind_adaptive, _maybe_compact) and
        each computation re-traverses the whole subtree. Plan nodes are
        rebuilt per execution and leaves are immutable, so the memo
        cannot go stale within an instance's life."""
        cached = self.__dict__.get("_stats_key_memo")
        if cached is not None:
            return cached
        scans: List["BatchScanExec"] = []

        def collect(p: PhysicalPlan) -> None:
            if isinstance(p, BatchScanExec):
                if not p.aux:  # derived data, not identity
                    scans.append(p)
                return
            for c in p.children():
                collect(c)

        collect(self)
        pins = tuple(cd.data for s in scans for cd in s.batch.data.columns)
        ids = tuple(id(a) for a in pins)
        out = ((self.plan_key(), ids), pins)
        self.__dict__["_stats_key_memo"] = out
        return out

    def tree_string(self, indent: int = 0) -> str:
        line = "  " * indent + self.node_string()
        return "\n".join([line] + [c.tree_string(indent + 1)
                                   for c in self.children()])

    def node_string(self) -> str:
        return type(self).__name__

    def plan_key(self) -> tuple:
        """Structural cache key for fused-stage jit caching."""
        return (type(self).__name__,) + tuple(
            c.plan_key() for c in self.children())

    def has_blocking_exprs(self) -> bool:
        """Any host-only expression (arrow UDF) in THIS node's fields —
        such an operator must run on the eager path regardless of its
        own traceable flag."""
        import dataclasses as _dc

        def scan(v) -> bool:
            if isinstance(v, E.Expression):
                return E.contains_blocking(v)
            if isinstance(v, tuple):
                return any(scan(x) for x in v)
            return False

        try:
            fields = _dc.fields(self)
        except TypeError:
            return False
        return any(scan(getattr(self, f.name)) for f in fields)

    def __repr__(self):
        return self.tree_string()


# ---- leaves ----------------------------------------------------------------


def scan_plan_key(kind: str, capacity: int, schema: Schema,
                  data: BatchData, dict_id=None) -> tuple:
    """A leaf scan's part of a stage's identity, for both engines' leaf
    types and the executable store: the capacity, each column's logical
    dtype AND the dtype the device holds it in (a scan keeps an
    int64-backed column whose values fit as int32, ``from_numpy``; the
    program that widens it is another program than the one that reads
    int64), and the dictionaries — by ``hash`` in this process, by
    ``dict_id`` (a content digest) across processes."""
    if dict_id is None:
        dict_id = hash(tuple(f.dictionary for f in schema.fields))
    return (kind, capacity,
            tuple((f.name, repr(f.dtype), cd.data.dtype)
                  for f, cd in zip(schema.fields, data.columns)),
            dict_id)


@dataclass(eq=False)
class BatchScanExec(PhysicalPlan):
    """Scan over an in-memory device batch (+ input port index for fused
    stages). Analogue of LocalTableScanExec / columnar scan output."""

    batch: Batch
    #: aux scans carry DERIVED device data (cached join indexes) fully
    #: determined by the real leaves — excluded from stats_key identity
    aux: bool = False
    traceable = True

    @property
    def schema(self) -> Schema:
        return self.batch.schema

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        raise AssertionError("leaf scan is fed by the stage runner")

    def node_string(self):
        return f"BatchScan{list(self.schema.names)}"

    def plan_key(self):
        return scan_plan_key("BatchScan", self.batch.capacity,
                             self.batch.schema, self.batch.data)


@dataclass(eq=False)
class RangeExec(PhysicalPlan):
    """On-device iota (reference: basicPhysicalOperators.scala
    RangeExec:412; RangeBenchmark 12,110 M rows/s is the number to beat —
    here the whole range is one fused XLA iota that usually never
    materializes)."""

    start: int
    end: int
    step: int
    col_name: str = "id"
    traceable = True

    @property
    def num_rows(self) -> int:
        if self.step == 0:
            return 0
        n = (self.end - self.start + self.step - (1 if self.step > 0 else -1))
        return max(0, n // self.step)

    @property
    def schema(self) -> Schema:
        return Schema((Field(self.col_name, T.INT64, nullable=False),))

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        n = self.num_rows
        cap = K.bucket(n)
        ids = self.start + jnp.arange(cap, dtype=jnp.int64) * self.step
        mask = jnp.arange(cap) < n
        return Pipe({self.col_name: TV(ids, None, T.INT64, None)}, mask,
                    [self.col_name])

    def plan_key(self):
        return ("Range", self.start, self.end, self.step, self.col_name)


# ---- pipelined unary ops ----------------------------------------------------


@dataclass(eq=False)
class ProjectExec(PhysicalPlan):
    exprs: Tuple[E.Expression, ...]
    child: PhysicalPlan
    traceable = True

    def children(self):
        return (self.child,)

    @property
    def schema(self) -> Schema:
        cs = self.child.schema
        fields = []
        for e in self.exprs:
            dt = e.data_type(cs)
            if isinstance(dt, T.MapType):
                # maps decompose into '#keys'/'#vals' array components
                # plus their length companions (types.MapType)
                nullable = e.nullable(cs)
                for comp, el in ((T.map_keys_col(e.name), dt.key),
                                 (T.map_vals_col(e.name), dt.value)):
                    fields.append(Field(comp, T.ArrayType(el), nullable))
                    fields.append(Field(T.array_len_col(comp), T.INT32,
                                        nullable=False))
                continue
            inner = E.strip_alias(e)
            dictionary = None
            if isinstance(inner, E.Col) and inner.col_name in cs:
                dictionary = cs.field(inner.col_name).dictionary
            fields.append(Field(e.name, dt, e.nullable(cs), dictionary))
            if isinstance(dt, T.ArrayType):
                # hidden per-row length companion (types.ArrayType)
                fields.append(Field(T.array_len_col(e.name), T.INT32,
                                    nullable=False))
        return Schema(tuple(fields))

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        pipe = child_pipes[0]
        env = pipe.env()
        cols = {}
        order = []

        def add_array(name, tv):
            cols[name] = tv
            order.append(name)
            ln = T.array_len_col(name)
            cols[ln] = TV(
                (tv.lengths if tv.lengths is not None
                 else jnp.full((pipe.capacity,),
                               tv.data.shape[1] if tv.data.ndim > 1
                               else 0, dtype=jnp.int32)),
                None, T.INT32, None)
            order.append(ln)

        for e in self.exprs:
            try:
                dt = e.data_type(self.child.schema)
            except Exception:
                dt = None
            if isinstance(dt, T.MapType):
                ktv, vtv = C.evaluate_map_pair(e, env)
                add_array(T.map_keys_col(e.name), ktv)
                add_array(T.map_vals_col(e.name), vtv)
                continue
            tv = C.evaluate(e, env)
            if isinstance(tv.dtype, T.ArrayType):
                add_array(e.name, tv)
                continue
            cols[e.name] = tv
            order.append(e.name)
        return Pipe(cols, pipe.mask, order, rows_bound=pipe.rows_bound)

    def node_string(self):
        return f"Project[{', '.join(str(e) for e in self.exprs)}]"

    def plan_key(self):
        return ("Project", tuple(E.expr_key(e) for e in self.exprs),
                self.child.plan_key())


@dataclass(eq=False)
class FilterExec(PhysicalPlan):
    condition: E.Expression
    child: PhysicalPlan
    traceable = True

    def children(self):
        return (self.child,)

    @property
    def schema(self) -> Schema:
        return self.child.schema

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        pipe = child_pipes[0]
        tv = C.evaluate(self.condition, pipe.env())
        keep = tv.data & tv.valid_or_true(pipe.capacity)
        return Pipe(pipe.cols, pipe.mask & keep, pipe.order,
                    rows_bound=pipe.rows_bound)

    def node_string(self):
        return f"Filter[{self.condition}]"

    def plan_key(self):
        return ("Filter", E.expr_key(self.condition), self.child.plan_key())


@dataclass(eq=False)
class SortExec(PhysicalPlan):
    """Global sort: chained stable argsorts (reference: SortExec.scala:40
    backed by UnsafeExternalSorter/RadixSort.java:25 — XLA's on-device
    sort replaces both)."""

    orders: Tuple[E.SortOrder, ...]
    child: PhysicalPlan
    traceable = True

    def children(self):
        return (self.child,)

    @property
    def schema(self) -> Schema:
        return self.child.schema

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        pipe = child_pipes[0]
        env = pipe.env()
        keys = []
        for o in self.orders:
            tv = C.evaluate(o.child, env)
            keys.append(K.SortKey(tv.data, tv.validity, o.ascending,
                                  o.nulls_first_resolved))
        perm = K.lexsort_permutation(keys, pipe.mask)
        cols = {
            name: TV(tv.data[perm],
                     None if tv.validity is None else tv.validity[perm],
                     tv.dtype, tv.dictionary)
            for name, tv in pipe.cols.items()
        }
        return Pipe(cols, pipe.mask[perm], pipe.order)

    def node_string(self):
        return f"Sort[{', '.join(map(str, self.orders))}]"

    def plan_key(self):
        return ("Sort",
                tuple((E.expr_key(o.child), o.ascending,
                       o.nulls_first_resolved) for o in self.orders),
                self.child.plan_key())


@dataclass(eq=False)
class LimitExec(PhysicalPlan):
    """Keep first n live rows (reference: limit.scala GlobalLimitExec)."""

    n: int
    child: PhysicalPlan
    offset: int = 0
    traceable = True

    def children(self):
        return (self.child,)

    @property
    def schema(self) -> Schema:
        return self.child.schema

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        pipe = child_pipes[0]
        return Pipe(pipe.cols, K.limit_mask(pipe.mask, self.n, self.offset),
                    pipe.order)

    def node_string(self):
        return f"Limit[{self.n}]"

    def plan_key(self):
        return ("Limit", self.n, self.offset, self.child.plan_key())


@dataclass(eq=False)
class ExpandExec(PhysicalPlan):
    """One output block per projection, stacked (reference:
    execution/ExpandExec.scala:1): capacity = child capacity x G,
    statically shaped — no sizing sync, fuses with the aggregation
    above it (the ROLLUP/CUBE path is one XLA program end to end)."""

    projections: Tuple[Tuple[E.Expression, ...], ...]
    names: Tuple[str, ...]
    child: PhysicalPlan
    traceable = True

    def children(self):
        return (self.child,)

    @functools.cached_property
    def schema(self) -> Schema:
        from spark_tpu.plan import logical as L

        return L.Expand(self.projections, self.names,
                        _SchemaOnly(self.child.schema)).schema

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        pipe = child_pipes[0]
        env = pipe.env()
        n = pipe.capacity
        out_schema = self.schema
        cols: Dict[str, TV] = {}
        for i, name in enumerate(self.names):
            out_f = out_schema.fields[i]
            tvs = [C.evaluate(proj[i], env) for proj in self.projections]
            if isinstance(out_f.dtype, T.StringType):
                union, tables = C.unify_dictionaries(
                    tuple(tv.dictionary or () for tv in tvs))
                datas = [(jnp.asarray(tb)[tv.data]
                          if len(tv.dictionary or ()) else tv.data)
                         for tv, tb in zip(tvs, tables)]
                dictionary: Optional[Tuple[str, ...]] = union
            else:
                datas = [C._cast_data(tv.data, tv.dtype, out_f.dtype)
                         for tv in tvs]
                dictionary = None
            data = jnp.concatenate(datas)
            validity = None
            if any(tv.validity is not None for tv in tvs):
                validity = jnp.concatenate(
                    [tv.valid_or_true(n) for tv in tvs])
            cols[name] = TV(data, validity, out_f.dtype, dictionary)
        mask = jnp.concatenate([pipe.mask] * len(self.projections))
        return Pipe(cols, mask, list(self.names))

    def node_string(self):
        return f"Expand[{len(self.projections)} sets]"

    def plan_key(self):
        return ("Expand",
                tuple(tuple(E.expr_key(e) for e in p)
                      for p in self.projections),
                self.names, self.child.plan_key())


@dataclass(eq=False)
class GenerateExec(PhysicalPlan):
    """Sized row expansion for explode/posexplode (reference:
    execution/GenerateExec.scala:1): one output row per live array
    element, parent columns replicated by gather — the exact shape of
    the join pair expansion, so it reuses the same offsets+searchsorted
    kernel and the same adaptive capacity-replay discipline (_GEN_STATS
    records the bucketed element total for these leaves; re-executions
    trace with a static capacity, no sizing sync)."""

    generator: E.Expression  # E.Explode
    out_name: str
    pos_name: Optional[str]
    child: PhysicalPlan
    adaptive: Optional[int] = None

    def children(self):
        return (self.child,)

    @property
    def traceable(self) -> bool:  # type: ignore[override]
        return self.adaptive is not None

    @functools.cached_property
    def schema(self) -> Schema:
        from spark_tpu.plan import logical as L

        return L.Generate(self.generator, self.out_name, self.pos_name,
                          _SchemaOnly(self.child.schema)).schema

    def _expand(self, pipe: Pipe, cap: int, tv=None) -> Pipe:
        if tv is None:
            tv = C.evaluate(self.generator.child, pipe.env())
        if tv.lengths is None or tv.data.ndim != 2:
            raise NotImplementedError("explode over a non-array value")
        ok = pipe.mask & tv.valid_or_true(pipe.capacity)
        counts = jnp.where(ok, tv.lengths.astype(jnp.int64), 0)
        offsets = jnp.cumsum(counts) - counts
        total = offsets[-1] + counts[-1]
        j = jnp.arange(cap)
        p = K.searchsorted(offsets, j, side="right") - 1
        p = jnp.clip(p, 0, pipe.capacity - 1)
        k = j - offsets[p]
        out_mask = j < total
        cols: Dict[str, TV] = {}
        order: List[str] = []
        for name in pipe.order:
            src = pipe.cols[name]
            cols[name] = TV(
                src.data[p],
                None if src.validity is None else src.validity[p],
                src.dtype, src.dictionary,
                None if src.lengths is None else src.lengths[p])
            order.append(name)
        if self.pos_name is not None:
            cols[self.pos_name] = TV(k.astype(jnp.int32), None, T.INT32,
                                     None)
            order.append(self.pos_name)
        el = jnp.take_along_axis(
            tv.data[p], jnp.clip(k, 0, tv.data.shape[1] - 1)[:, None],
            axis=1)[:, 0]
        cols[self.out_name] = TV(el, None, tv.dtype.element,
                                 tv.dictionary)
        order.append(self.out_name)
        return Pipe(cols, out_mask, order)

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        return self._expand(child_pipes[0], self.adaptive)

    def execute_blocking(self, child_batches: List[Batch]) -> Batch:
        pipe = Pipe.from_batch_data(child_batches[0].schema,
                                    child_batches[0].data)
        tv = C.evaluate(self.generator.child, pipe.env())
        if tv.lengths is None:
            raise NotImplementedError("explode over a non-array value")
        ok = pipe.mask & tv.valid_or_true(pipe.capacity)
        total = int(jax.device_get(jnp.sum(
            jnp.where(ok, tv.lengths.astype(jnp.int64), 0))))
        cap = K.bucket(total)
        sk = self.stats_key()
        if sk not in _GEN_STATS:
            _GEN_STATS.put(sk, cap)
        return self._expand(pipe, cap, tv).to_batch()

    def node_string(self):
        return f"Generate[{self.generator} AS {self.out_name}]"

    def plan_key(self):
        return ("Generate", E.expr_key(self.generator), self.out_name,
                self.pos_name, self.child.plan_key())


@dataclass(eq=False)
class _SchemaOnly(PhysicalPlan):
    """Wrap a schema as a plan-shaped object for schema composition."""

    wrapped: Schema
    traceable = False

    @property
    def schema(self) -> Schema:
        return self.wrapped


@dataclass(eq=False)
class CompactExec(PhysicalPlan):
    """Gather live rows to the front and truncate to a recorded bucketed
    capacity — planned at the query root from output-size stats
    (planner._OUTPUT_STATS) so the host fetch moves ``bucket(live)``
    rows instead of the full pipeline capacity: a 10-row result is not
    fetched at a 32k capacity. AQE-style output coalescing (reference analogue:
    CoalesceShufflePartitions.scala). Stable compaction preserves sorted
    row order."""

    child: PhysicalPlan
    cap: int
    traceable = True

    def children(self):
        return (self.child,)

    @property
    def schema(self) -> Schema:
        return self.child.schema

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        pipe = child_pipes[0]
        if self.cap >= pipe.capacity:
            return pipe
        idx = K.compaction_permutation(pipe.mask)[: self.cap]
        cols: Dict[str, TV] = {}
        for name in pipe.order:
            tv = pipe.cols[name]
            cols[name] = TV(
                tv.data[idx],
                None if tv.validity is None else tv.validity[idx],
                tv.dtype, tv.dictionary)
        return Pipe(cols, pipe.mask[idx], pipe.order)

    def node_string(self):
        return f"Compact[{self.cap}]"

    def plan_key(self):
        # TRANSPARENT: adaptive stats recorded on a blocking run (where
        # the executor compacts between stages invisibly) must still be
        # found when the replayed plan carries explicit CompactExec
        # nodes. The stage cache distinguishes compaction via
        # planner._adaptive_snapshot instead.
        return self.child.plan_key()


@dataclass(eq=False)
class SampleExec(PhysicalPlan):
    fraction: float
    seed: int
    child: PhysicalPlan
    traceable = True

    def children(self):
        return (self.child,)

    @property
    def schema(self) -> Schema:
        return self.child.schema

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        pipe = child_pipes[0]
        key = jax.random.PRNGKey(self.seed)
        u = jax.random.uniform(key, (pipe.capacity,))
        return Pipe(pipe.cols, pipe.mask & (u < self.fraction), pipe.order)

    def plan_key(self):
        return ("Sample", self.fraction, self.seed, self.child.plan_key())


@dataclass(eq=False)
class UnionExec(PhysicalPlan):
    left: PhysicalPlan
    right: PhysicalPlan
    traceable = True

    def children(self):
        return (self.left, self.right)

    @property
    def schema(self) -> Schema:
        return self.left.schema

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        lp, rp = child_pipes
        cols = {}
        order = []
        for lname, rname in zip(lp.order, rp.order):
            lt = lp.cols[lname]
            rt = rp.cols[rname]
            out_dt = lt.dtype if type(lt.dtype) is type(rt.dtype) \
                else T.common_type(lt.dtype, rt.dtype)
            ld, rd = lt.data, rt.data
            dictionary = None
            if isinstance(out_dt, T.StringType):
                union, (tl, tr) = C.unify_dictionaries(
                    (lt.dictionary or (), rt.dictionary or ()))
                ld = jnp.asarray(tl)[lt.data] if len(lt.dictionary or ()) else lt.data
                rd = jnp.asarray(tr)[rt.data] if len(rt.dictionary or ()) else rt.data
                dictionary = union
            else:
                ld = C._cast_data(ld, lt.dtype, out_dt)
                rd = C._cast_data(rd, rt.dtype, out_dt)
            data = jnp.concatenate([ld, rd])
            if lt.validity is None and rt.validity is None:
                validity = None
            else:
                validity = jnp.concatenate([
                    lt.valid_or_true(lp.capacity), rt.valid_or_true(rp.capacity)])
            cols[lname] = TV(data, validity, out_dt, dictionary)
            order.append(lname)
        mask = jnp.concatenate([lp.mask, rp.mask])
        return Pipe(cols, mask, order)

    def plan_key(self):
        return ("Union", self.left.plan_key(), self.right.plan_key())


# ---- aggregation ------------------------------------------------------------

_DIRECT_CARDINALITY_LIMIT = 1 << 22  # packed-key segment count bound


def _agg_primitives(agg: E.AggregateExpression) -> List[str]:
    if isinstance(agg, E.Sum):
        return ["sum"]
    if isinstance(agg, E.Count):
        return ["count"]
    if isinstance(agg, E.Avg):
        return ["sum", "count"]
    if isinstance(agg, E.Min):
        return ["min"]
    if isinstance(agg, E.Max):
        return ["max"]
    if isinstance(agg, E.StddevVariance):
        return ["count", "sum", "sumsq"]
    if isinstance(agg, E.First):
        return ["first"]
    raise NotImplementedError(f"aggregate {agg!r}")


def rewrite_agg_outputs(
    groupings: Tuple[E.Expression, ...],
    aggregates: Tuple[E.Expression, ...],
) -> Tuple[Tuple[E.Expression, ...], List[E.AggregateExpression]]:
    """Rewrite output expressions so aggregate calls become __agg{i} col
    refs and grouping subtrees become __key{j} col refs; returns the
    rewritten outputs plus the distinct aggregate calls (the physical
    aggregation list). Analogue of the planner's PhysicalAggregation
    pattern (reference: planning/patterns.scala)."""
    agg_calls: List[E.AggregateExpression] = []
    agg_keys: List[tuple] = []
    grouping_keys = [E.expr_key(g) for g in groupings]

    def rewrite(e: E.Expression) -> E.Expression:
        """Top-down: a whole subtree matching a grouping / aggregate is
        replaced before descending (descending first would corrupt
        aggregate children that reference grouping columns)."""
        sk = E.expr_key(e)
        for j, gk in enumerate(grouping_keys):
            if sk == gk:
                return E.Col(f"__key{j}")
        if isinstance(e, E.AggregateExpression):
            for i, k in enumerate(agg_keys):
                if k == sk:
                    return E.Col(f"__agg{i}")
            agg_calls.append(e)
            agg_keys.append(sk)
            return E.Col(f"__agg{len(agg_calls) - 1}")
        if isinstance(e, E.Alias):
            return E.Alias(rewrite(e.child), e.alias_name)
        # generic rebuild with rewritten expression-valued fields
        new_fields = {}
        changed = False
        for fl in dataclasses.fields(e):
            v = getattr(e, fl.name)
            if isinstance(v, E.Expression):
                nv = rewrite(v)
                changed |= nv is not v
                new_fields[fl.name] = nv
            elif isinstance(v, tuple) and any(
                    isinstance(x, (E.Expression, tuple)) for x in v):
                nv_list = []
                for x in v:
                    if isinstance(x, E.Expression):
                        nx = rewrite(x)
                        changed |= nx is not x
                        nv_list.append(nx)
                    elif isinstance(x, tuple):
                        nx = tuple(rewrite(y) if isinstance(y, E.Expression)
                                   else y for y in x)
                        changed |= nx != x
                        nv_list.append(nx)
                    else:
                        nv_list.append(x)
                new_fields[fl.name] = tuple(nv_list)
            else:
                new_fields[fl.name] = v
        return dataclasses.replace(e, **new_fields) if changed else e

    outputs = []
    for e in aggregates:
        name = e.name
        ne = rewrite(e)
        if ne.name != name:
            ne = E.Alias(ne, name)
        outputs.append(ne)
    return tuple(outputs), agg_calls


def group_key_codes(key_tvs: List[TV]):
    """Small-int codes + cardinalities for direct (packed) grouping.
    Raises AssertionError when a key has no trace-time cardinality."""
    codes, validities, cards = [], [], []
    for tv in key_tvs:
        if isinstance(tv.dtype, T.BooleanType):
            codes.append(tv.data.astype(jnp.int32))
            validities.append(tv.validity)
            cards.append(2)
        elif isinstance(tv.dtype, T.StringType) and tv.dictionary is not None:
            codes.append(tv.data)
            validities.append(tv.validity)
            cards.append(max(1, len(tv.dictionary)))
        else:
            raise AssertionError(
                "direct agg path needs trace-time key cardinality")
    return codes, validities, cards


def sorted_groups(pipe: Pipe, key_tvs: List[TV]):
    """Sort rows by grouping keys and assign change-flag group ids.
    Returns (sorted_pipe, sorted_key_tvs, seg_ids, num_groups_traced).

    The rows themselves are sorted, not a permutation of them: ONE stable
    ``lax.sort`` whose keys are ``~mask`` (live rows first) and, per
    grouping key, its validity (nulls first) and its data with null rows
    zeroed — ``K.lexsort_permutation``'s order bit for bit, ties in row
    order — carries every 1-D column array as an operand: a random gather
    is the chip's slowest operation (13-21 ns a row on the v5e; PERF.md,
    PR 38) and the sort moves its operands together. A 2-D column cannot
    ride (the operands share one shape): the sort carries a row index as
    well and only such a column is gathered by it. XLA drops the operands
    whose outputs nothing reads."""
    n = pipe.mask.shape[0]
    operands = [~pipe.mask]
    at: Dict[int, int] = {}       # id(array) -> index of its sorted self

    def ride(a) -> None:
        at.setdefault(id(a), len(operands))
        operands.append(a)

    for tv in key_tvs:
        if tv.validity is None:
            ride(tv.data)
        else:
            ride(tv.validity)
            operands.append(jnp.where(tv.validity, tv.data,
                                      jnp.zeros((), tv.data.dtype)))
    num_keys = len(operands)
    wide = set()                  # ids of 2-D arrays the sort cannot carry
    for tv in [*pipe.cols.values(), *key_tvs]:
        for a in (tv.data, tv.validity):
            if a is None or id(a) in at:
                continue
            if a.ndim == 1:
                ride(a)
            else:
                wide.add(id(a))
    if wide:
        ride(jnp.arange(n, dtype=jnp.int32))
    _trace.built("sort", site="group_rows", rows=int(n), keys=num_keys,
                 operands=len(operands), gathered=len(wide))
    out = jax.lax.sort(tuple(operands), num_keys=num_keys, is_stable=True)
    perm = out[-1] if wide else None

    def moved(a):
        if a is None:
            return None
        return out[at[id(a)]] if id(a) in at else a[perm]

    def take(tv: TV) -> TV:
        return TV(moved(tv.data), moved(tv.validity), tv.dtype,
                  tv.dictionary)

    spipe = Pipe({name: take(tv) for name, tv in pipe.cols.items()},
                 ~out[0], pipe.order)
    sorted_keys = [take(tv) for tv in key_tvs]
    seg, ng = K.group_ids_from_sorted(
        [(tv.data, tv.validity) for tv in sorted_keys], spipe.mask)
    return spipe, sorted_keys, seg, ng


def first_group_keys(sorted_keys: List[TV], seg, mask, num_segments: int,
                     capacity: int, sorted_seg: bool = False) -> List[TV]:
    """Representative (first-row) key values per group."""
    out = []
    for tv in sorted_keys:
        data, found = K.seg_first(tv.data, seg, mask, num_segments, capacity,
                                  sorted_seg)
        if tv.validity is None:
            valid = None
        else:
            vdata, _ = K.seg_first(tv.validity, seg, mask, num_segments,
                                   capacity, sorted_seg)
            valid = vdata & found
        out.append(TV(data, valid, tv.dtype, tv.dictionary))
    return out


def _distinct_mask_cached(env: Env, child: E.Expression, tv: TV, seg,
                          ok) -> "jnp.ndarray":
    """distinct_first_mask memoized per (env, child expr): N DISTINCT
    aggregates over one column share a single (seg, value) lexsort."""
    cache = getattr(env, "_distinct_cache", None)
    if cache is None:
        cache = {}
        env._distinct_cache = cache
    key = E.expr_key(child)
    if key not in cache:
        cache[key] = K.distinct_first_mask(tv.data, seg, ok)
    return cache[key]


def decimal_sum_type(dt: "T.DecimalType") -> "T.DecimalType":
    """Sum widens decimals by 10 integral digits (Sum.scala)."""
    return T.bounded_decimal(dt.precision + 10, dt.scale)


def decimal_avg(total, cnt, dt: "T.DecimalType"):
    """Exact decimal average from a scaled-int sum and a count:
    (sum * 10^(s'-s)) / count with HALF_UP rounding, result scale s+4
    (Average.scala). Shared by the single-device and mesh paths."""
    out_dt = T.bounded_decimal(dt.precision + 4, dt.scale + 4)
    num = total * (10 ** (out_dt.scale - dt.scale))
    cc = jnp.maximum(cnt, 1)
    data = jnp.sign(num) * ((jnp.abs(num) + cc // 2) // cc)
    return data, out_dt


class _LocalMerge:
    """How one chip turns a segment's partial aggregate into its total:
    it already is, so the argument comes back and nothing is traced.
    The mesh's counterpart (``parallel/operators.py::_MeshMerge``) puts
    a collective at each of these points."""

    @staticmethod
    def sum(x):
        return x

    min = max = one_copy = sum

    @staticmethod
    def first(data, found, vfirst):
        """(value, validity) of First from the segment's first row, its
        ``found`` flag and that row's validity (None: not nullable)."""
        return data, found if vfirst is None else found & vfirst


def _compute_agg(agg: E.AggregateExpression, env: Env, seg, mask,
                 num_segments: int, capacity: int,
                 sorted_seg: bool = False, merge=_LocalMerge) -> TV:
    """Compute one aggregate over segments: THE evaluator of both
    engines. Nulls in the input are excluded per SQL semantics; a group
    with no valid input yields NULL (except count). ``sorted_seg`` marks
    monotone segment ids (the sort-agg path) unlocking the cumsum-based
    kernels — scatter-add is pathologically slow on TPU (see
    kernels.py). ``merge`` makes a local reduction global: the identity
    on one chip; on the mesh (``PSumAggExec``) psum / pmin / pmax — the
    partial->final two-phase plan (reference: aggregate/AggUtils.scala:33
    map-side combine + shuffled merge) collapsed into a single program
    with an ICI collective as the phase boundary."""
    if isinstance(agg, E.Count) and agg.child is None:
        cnt = merge.sum(K.seg_count(seg, mask, num_segments, sorted_seg))
        return TV(cnt, None, T.INT64, None)

    child = agg.child  # type: ignore[attr-defined]
    tv = C.evaluate(child, env)
    ok = mask & tv.valid_or_true(capacity)
    if getattr(agg, "distinct", False):
        # DISTINCT: keep one ok row per (group, value). On the mesh,
        # local dedup + psum is exact ONLY when equal values are
        # co-resident; the planner guarantees it by hash-exchanging on
        # the distinct child (MeshExecutor._plan_aggregate) before
        # PSumAggExec runs.
        ok = ok & _distinct_mask_cached(env, agg.child, tv, seg, ok)
    cnt = merge.sum(K.seg_count(seg, ok, num_segments, sorted_seg))
    # dedup keeps >= 1 head per non-empty group, so post-dedup positivity
    # matches pre-dedup — one count serves both
    any_valid = cnt > 0

    def total(x):
        """The merged per-segment sum of ``x`` over the ok rows."""
        return merge.sum(K.seg_sum(x, seg, ok, num_segments, sorted_seg))

    if isinstance(agg, E.Count):
        return TV(cnt, None, T.INT64, None)
    if isinstance(agg, E.Sum):
        if isinstance(tv.dtype, T.DecimalType):
            # exact scaled-int64 sum (reference: Sum.scala resultType)
            return TV(total(tv.data), any_valid,
                      decimal_sum_type(tv.dtype), None)
        out_dt = T.INT64 if tv.dtype.is_integral else tv.dtype
        s = total(tv.data.astype(C._jnp_dtype(out_dt)))
        return TV(s, any_valid, out_dt, None)
    if isinstance(agg, E.Avg):
        if isinstance(tv.dtype, T.DecimalType):
            data, out_dt = decimal_avg(total(tv.data), cnt, tv.dtype)
            return TV(data, any_valid, out_dt, None)
        s = total(tv.data.astype(jnp.float64))
        return TV(s / jnp.maximum(cnt, 1), any_valid, T.FLOAT64, None)
    if isinstance(agg, E.Min):
        m = merge.min(K.seg_min(tv.data, seg, ok, num_segments, sorted_seg))
        return TV(m, any_valid, tv.dtype, tv.dictionary)
    if isinstance(agg, E.Max):
        m = merge.max(K.seg_max(tv.data, seg, ok, num_segments, sorted_seg))
        return TV(m, any_valid, tv.dtype, tv.dictionary)
    if isinstance(agg, E.StddevVariance):
        x = tv.data.astype(jnp.float64)
        c = cnt.astype(jnp.float64)
        s = total(x)
        s2 = total(x * x)
        m2 = jnp.maximum(s2 - (s * s) / jnp.maximum(c, 1.0), 0.0)
        kind = agg.kind
        denom = c - 1.0 if kind.endswith("_samp") else c
        var = m2 / jnp.maximum(denom, 1.0)
        data = jnp.sqrt(var) if kind.startswith("stddev") else var
        enough = c >= (2.0 if kind.endswith("_samp") else 1.0)
        return TV(data, any_valid & enough, T.FLOAT64, None)
    if isinstance(agg, E.First):
        use = ok if agg.ignore_nulls else mask
        data, found = K.seg_first(tv.data, seg, use, num_segments, capacity,
                                  sorted_seg)
        vfirst = None if tv.validity is None else K.seg_first(
            tv.valid_or_true(capacity), seg, use, num_segments, capacity,
            sorted_seg)[0]
        data, valid = merge.first(data, found, vfirst)
        return TV(data, valid, tv.dtype, tv.dictionary)
    if merge is not _LocalMerge:
        # Percentile and Collect need a group's rows in one place
        raise NotImplementedError(f"distributed aggregate {agg!r}")
    if isinstance(agg, E.Percentile):
        # EXACT per-group percentile: one (group, value) lexsort, then a
        # rank gather vectorized over all groups — same device sort
        # every blocking aggregate pays, so no reason to approximate
        # (reference: aggregate/ApproximatePercentile.scala:81)
        q = float(agg.percentage)
        perm = K.lexsort_permutation(
            [K.SortKey(seg, None, True, True),
             K.SortKey(tv.data, tv.validity, True, True)], ok)
        svals = tv.data[perm]
        starts = jnp.cumsum(cnt) - cnt
        hi_cap = capacity - 1
        if agg.interpolate:
            fvals = C._cast_data(svals, tv.dtype, T.FLOAT64)
            pos = q * (cnt - 1).astype(jnp.float64)
            lo = jnp.floor(pos).astype(jnp.int64)
            hi = jnp.ceil(pos).astype(jnp.int64)
            frac = pos - lo.astype(jnp.float64)
            vlo = fvals[jnp.clip(starts + lo, 0, hi_cap)]
            vhi = fvals[jnp.clip(starts + hi, 0, hi_cap)]
            return TV(vlo + (vhi - vlo) * frac, any_valid, T.FLOAT64,
                      None)
        rank = jnp.clip(jnp.ceil(q * cnt).astype(jnp.int64) - 1, 0,
                        jnp.maximum(cnt - 1, 0))
        data = svals[jnp.clip(starts + rank, 0, hi_cap)]
        return TV(data, any_valid, tv.dtype, tv.dictionary)
    if isinstance(agg, E.Collect):
        import jax as _jax

        if isinstance(seg, _jax.core.Tracer):
            raise NotImplementedError(
                "collect_list/collect_set have a data-dependent output "
                "width (the largest group) — blocking execution only")
        if agg.unique:
            ok = ok & _distinct_mask_cached(env, agg.child, tv, seg, ok)
        keys = [K.SortKey(seg, None, True, True)]
        if agg.unique:
            keys.append(K.SortKey(tv.data, tv.validity, True, True))
        perm = K.lexsort_permutation(keys, ok)  # stable: keeps row order
        svals = tv.data[perm]
        cnt = K.seg_count(seg, ok, num_segments, sorted_seg)
        starts = jnp.cumsum(cnt) - cnt
        width = max(int(jnp.max(cnt)) if num_segments else 0, 1)
        idx = starts[:, None] + jnp.arange(width)[None, :]
        data2 = svals[jnp.clip(idx, 0, capacity - 1)]
        return TV(data2, None, T.ArrayType(tv.dtype), tv.dictionary,
                  cnt.astype(jnp.int32))
    raise NotImplementedError(f"aggregate {agg!r}")


@dataclass(eq=False)
class GroupCountExec(PhysicalPlan):
    """The number of groups of ``groupings`` among the child's live
    rows, as one row: the count that sizes the sort-based aggregate's
    output. The planner runs it as a stage of its own on the first
    execution of such an aggregate (``planner._sized_aggregate``): the
    keys are sorted once here to be counted and once more by the
    aggregate built for the count, which costs a tenth of a second on
    the chip where the same work run op by op compiled some 550
    programs (2,093 s cold at SF10; PERF.md, PR 35)."""

    groupings: Tuple[E.Expression, ...]
    child: PhysicalPlan
    traceable = True

    def children(self):
        return (self.child,)

    @property
    def schema(self) -> Schema:
        return Schema((Field("groups", T.INT64, nullable=False),))

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        pipe = child_pipes[0]
        key_tvs = [C.evaluate(g, pipe.env()) for g in self.groupings]
        # XLA drops the sort's operands that nobody reads here
        _, _, _, ng = sorted_groups(pipe, key_tvs)
        return Pipe({"groups": TV(ng.astype(jnp.int64)[None], None, T.INT64,
                                  None)},
                    jnp.ones((1,), dtype=jnp.bool_), ["groups"])

    def node_string(self):
        return f"GroupCount[keys=[{', '.join(map(str, self.groupings))}]]"

    def plan_key(self):
        return ("GroupCount", tuple(E.expr_key(g) for g in self.groupings),
                self.child.plan_key())


@dataclass(eq=False)
class HashAggregateExec(PhysicalPlan):
    """Group-by aggregation (reference: HashAggregateExec.scala:47 +
    TungstenAggregationIterator.scala:82 over BytesToBytesMap.java).

    Two device strategies, chosen from trace-time metadata:
    - **direct**: every grouping key has trace-time cardinality (string
      dictionary / boolean) -> mixed-radix pack to dense group ids ->
      segment reductions. No sort, no sync, fully fusable.
    - **sort**: sort rows by keys, change-flag cumsum assigns group ids,
      host-sync the group count to size the output (the one 'spill to
      host control' point, analogue of the hash-map fallback-to-sort in
      ObjectHashAggregateExec).
    """

    groupings: Tuple[E.Expression, ...]
    aggregates: Tuple[E.Expression, ...]
    child: PhysicalPlan
    #: bound by the planner from _AGG_STATS: observed group count, which
    #: makes the sort-based path traceable with a static output capacity
    adaptive: Optional[int] = None

    def children(self):
        return (self.child,)

    @property
    def sorted_slots(self) -> Optional[int]:
        """``adaptive`` in 256-slot buckets: all a stage keeps of it (the
        live groups are counted on the device), so what the stage cache
        tells two bindings apart by. The slots traced are
        ``K.group_slots`` of it and of the rows' capacity, which the
        stage's scans and joins' stats, also in its key, decide."""
        return (None if self.adaptive is None
                else K.bucket(max(1, self.adaptive), 256))

    def _collects(self) -> bool:
        return any(isinstance(a, E.Collect)
                   for e in self.aggregates
                   for a in E.collect_aggregates(e))

    @property
    def traceable(self) -> bool:  # type: ignore[override]
        if self._collects():
            return False  # output width = largest group: blocking only
        return self._static_direct_ok() or self.adaptive is not None

    @property
    def wants_group_count(self) -> bool:
        """Blocks for want of its group count alone: a stage once the
        planner has counted (``planner._sized_aggregate``)."""
        return (bool(self.groupings) and self.adaptive is None
                and not self._collects() and not self._static_direct_ok()
                and not self.has_blocking_exprs())

    def _static_direct_ok(self) -> bool:
        """Can we guarantee the direct path from schema info alone?"""
        return self.static_slots() is not None

    def static_slots(self) -> Optional[int]:
        """The direct path's slots, every key's values and its null
        packed, where the schema alone gives them (a dictionary string,
        a boolean); None where it does not or they pass the limit."""
        cs = self.child.schema
        total = 1
        for g in self.groupings:
            dt = g.data_type(cs)
            if isinstance(dt, T.BooleanType):
                total *= 3
            elif isinstance(dt, T.StringType):
                inner = E.strip_alias(g)
                if not (isinstance(inner, E.Col) and inner.col_name in cs
                        and cs.field(inner.col_name).dictionary is not None):
                    return None
                total *= len(cs.field(inner.col_name).dictionary) + 1
            else:
                return None
            if total > _DIRECT_CARDINALITY_LIMIT:
                return None
        return total

    @property
    def schema(self) -> Schema:
        cs = self.child.schema
        fields = []
        for e in self.aggregates:
            inner = E.strip_alias(e)
            dictionary = None
            if isinstance(inner, E.Col) and inner.col_name in cs:
                dictionary = cs.field(inner.col_name).dictionary
            elif isinstance(inner, (E.Min, E.Max, E.First)):
                c = E.strip_alias(inner.child)
                if isinstance(c, E.Col) and c.col_name in cs:
                    dictionary = cs.field(c.col_name).dictionary
            dt = e.data_type(cs)
            fields.append(Field(e.name, dt, e.nullable(cs), dictionary))
            if isinstance(dt, T.ArrayType):
                # hidden per-row length companion (types.ArrayType)
                fields.append(Field(T.array_len_col(e.name), T.INT32,
                                    nullable=False))
        return Schema(tuple(fields))

    # -- shared epilogue ------------------------------------------------------

    def _built(self, strategy: str, key_tvs: List[TV], rows: int, k: int,
               groups: Optional[int]) -> None:
        """The ``group_by`` build event: what this aggregate was built
        as (never recorded by an execution of a compiled stage)."""
        _trace.built("group_by", strategy=strategy,
                     keys=[str(tv.data.dtype) for tv in key_tvs],
                     rows=int(rows), k=int(k), groups=groups)

    def _finalize(self, key_tvs: List[TV], agg_tvs: List[TV],
                  out_mask: jnp.ndarray, num_segments: int) -> Pipe:
        outputs, _ = rewrite_agg_outputs(self.groupings, self.aggregates)
        cols = {f"__key{j}": tv for j, tv in enumerate(key_tvs)}
        cols.update({f"__agg{i}": tv for i, tv in enumerate(agg_tvs)})
        env = Env(cols, num_segments)
        out_cols = {}
        order = []
        for e in outputs:
            tv = C.evaluate(e, env)
            out_cols[e.name] = tv
            order.append(e.name)
            if isinstance(tv.dtype, T.ArrayType):
                ln = T.array_len_col(e.name)
                out_cols[ln] = TV(
                    (tv.lengths if tv.lengths is not None
                     else jnp.full((num_segments,),
                                   tv.data.shape[1] if tv.data.ndim > 1
                                   else 0, dtype=jnp.int32)),
                    None, T.INT32, None)
                order.append(ln)
        return Pipe(out_cols, out_mask, order)

    # -- direct (packed-key) path --------------------------------------------

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        pipe = child_pipes[0]
        if not self._static_direct_ok():
            return self._trace_sorted(pipe)
        return self._trace_direct(pipe)

    def _trace_direct(self, pipe: Pipe, merge=_LocalMerge) -> Pipe:
        """Dense group ids from trace-time key cardinalities, then
        segment reductions. ``merge`` as in ``_compute_agg``: under the
        mesh's (``PSumAggExec``) every device reduces its rows, the
        collectives make the result global and one copy of it is kept."""
        env = pipe.env()
        cap = pipe.capacity
        key_tvs = [C.evaluate(g, env) for g in self.groupings]
        codes, validities, cards = group_key_codes(key_tvs)

        if not key_tvs:
            seg = jnp.zeros((cap,), dtype=jnp.int32)
            num_segments = 1
        else:
            seg, num_segments = K.pack_codes(codes, validities, cards)
            seg = seg.astype(jnp.int32)

        self._built("direct", key_tvs, cap, num_segments, None)
        _, agg_calls = rewrite_agg_outputs(self.groupings, self.aggregates)
        agg_tvs = [_compute_agg(a, env, seg, pipe.mask, num_segments, cap,
                                merge=merge)
                   for a in agg_calls]

        group_present = merge.sum(
            K.seg_count(seg, pipe.mask, num_segments)) > 0
        if not key_tvs:
            out_mask = jnp.ones((1,), dtype=jnp.bool_)
            out_keys: List[TV] = []
        else:
            out_mask = group_present
            nullable = [v is not None for v in validities]
            unpacked = K.unpack_code(jnp.arange(num_segments), cards, nullable)
            out_keys = []
            for (code, valid), tv in zip(unpacked, key_tvs):
                data = code.astype(C._jnp_dtype(tv.dtype))
                out_keys.append(TV(data, valid, tv.dtype, tv.dictionary))
        return self._finalize(out_keys, agg_tvs, merge.one_copy(out_mask),
                              max(1, num_segments))

    # -- sort-based path ------------------------------------------------------
    #
    # Two halves, each under a scope of its own inside the operator's
    # (trace.INNER_SCOPES), so a profile tells the sort from the sums.
    # The direct path opens neither: its operations stay under
    # spark.HashAggregateExec.

    def _group_sort(self, pipe: Pipe, key_tvs: List[TV]):
        with _trace.inner_scope("GroupSort"):
            return sorted_groups(pipe, key_tvs)

    def _group_sum(self, spipe: Pipe, sorted_keys: List[TV], seg, n_groups,
                   num_segments: int, groups: int) -> Pipe:
        """Every aggregate over the sorted group ids ``seg`` and the
        groups' first keys, into ``num_segments`` slots (sized from the
        ``groups`` observed) of which the first ``n_groups`` (traced, or
        the same count on the host) are live."""
        cap = spipe.capacity
        self._built("sorted", sorted_keys, cap, num_segments, groups)
        with _trace.inner_scope("GroupSum"):
            env = spipe.env()
            _, agg_calls = rewrite_agg_outputs(self.groupings,
                                               self.aggregates)
            agg_tvs = [_compute_agg(a, env, seg, spipe.mask, num_segments,
                                    cap, sorted_seg=True)
                       for a in agg_calls]
            out_keys = first_group_keys(sorted_keys, seg, spipe.mask,
                                        num_segments, cap, sorted_seg=True)
        out_mask = jnp.arange(num_segments) < n_groups
        return self._finalize(out_keys, agg_tvs, out_mask, num_segments)

    def _trace_sorted(self, pipe: Pipe) -> Pipe:
        """Sort-based aggregation with STATIC output capacity from
        adaptive stats (the group count observed on the first, blocking
        execution of these exact leaf arrays) — no host sync, fusable;
        ``K.group_slots`` of them."""
        key_tvs = [C.evaluate(g, pipe.env()) for g in self.groupings]
        spipe, sorted_keys, seg, ng = self._group_sort(pipe, key_tvs)
        # ng stays on device
        return self._group_sum(spipe, sorted_keys, seg, ng,
                               K.group_slots(self.adaptive, pipe.capacity),
                               self.adaptive)

    def execute_blocking(self, child_batches: List[Batch]) -> Batch:
        pipe = Pipe.from_batch_data(child_batches[0].schema,
                                    child_batches[0].data)
        if self.traceable:
            return self.trace([pipe]).to_batch()
        env = pipe.env()
        cap = pipe.capacity
        key_tvs = [C.evaluate(g, env) for g in self.groupings]

        if key_tvs:
            spipe, sorted_keys, seg, ng = self._group_sort(pipe, key_tvs)
            n_groups = max(1, int(ng))  # host sync: output sizing
            _AGG_STATS.put(self.stats_key(), n_groups)
            return self._group_sum(spipe, sorted_keys, seg, n_groups,
                                   K.bucket(n_groups, 256),
                                   n_groups).to_batch()

        # no key and not traceable (a Collect): one group of every row
        num_segments = K.bucket(1, 256)
        seg = jnp.zeros((cap,), dtype=jnp.int32)
        _, agg_calls = rewrite_agg_outputs(self.groupings, self.aggregates)
        agg_tvs = [_compute_agg(a, env, seg, pipe.mask, num_segments, cap)
                   for a in agg_calls]
        out_mask = jnp.arange(num_segments) < 1
        return self._finalize([], agg_tvs, out_mask, num_segments).to_batch()

    def node_string(self):
        return (f"HashAggregate[keys=[{', '.join(map(str, self.groupings))}], "
                f"out=[{', '.join(str(e) for e in self.aggregates)}]]")

    def plan_key(self):
        return ("HashAggregate",
                tuple(E.expr_key(g) for g in self.groupings),
                tuple(E.expr_key(a) for a in self.aggregates),
                self.child.plan_key())


# ---- join ------------------------------------------------------------------


def _hash_keys(lds, rds):
    """Hash-combine multiple key columns into one int64 per row. Shifted
    right by 2 so the max value is 2^62-1 — strictly below the int64
    sentinel build_join_ranges uses for dead rows."""
    lh = K.hash64(lds[0])
    rh = K.hash64(rds[0])
    for ld, rd in zip(lds[1:], rds[1:]):
        lh = K.hash_combine(lh, ld)
        rh = K.hash_combine(rh, rd)
    return ((lh >> jnp.uint64(2)).astype(jnp.int64),
            (rh >> jnp.uint64(2)).astype(jnp.int64))


def _verify_key_pairs(prepped, p_idx, b_idx, cap):
    """Exact key equality for hash-matched pairs."""
    ok = jnp.ones((cap,), dtype=jnp.bool_)
    for ld, rd in prepped:
        ok = ok & (ld[p_idx] == rd[b_idx])
    return ok


def _pair_names(left_names, right_names) -> List[str]:
    """Joined-pair column names (delegates to the canonical dedup)."""
    return E.dedup_pair_names(left_names, right_names)


#: Adaptive join statistics (the AQE analogue, reference:
#: adaptive/AdaptiveSparkPlanExec.scala:247): the first execution of a
#: join sizes it in compiled programs (planner._size_join) and records
#: key-packing ranges + whether the build side matched each probe row at
#: most once (a ``JoinStats``). Keyed on plan
#: structure AND the identity of the leaf device arrays — jax arrays are
#: immutable, so identical ids imply identical data, making the cached
#: stats sound. With stats present, PK-FK joins become fully traceable
#: (output capacity = probe capacity) and fuse into one XLA program with
#: zero host syncs — the difference between ~6 and ~2 device->host
#: round trips per TPC-H query.
#: Gate for adaptive-stats RECORDING (reads stay enabled). The chunked
#: out-of-HBM executor runs hundreds of single-shot plans whose leaf
#: arrays never recur; recording them costs a blocking host sync per
#: plan and floods the LRU caches with dead-weakref entries that evict
#: live queries' stats. A ContextVar, not a module global: the chunk
#: pipeline (physical/pipeline.py) runs producer threads concurrently
#: with the consumer's merge loop, and the consumer's disabled window
#: must neither leak into nor be clobbered by another thread.
import contextvars as _contextvars

_STATS_RECORDING = _contextvars.ContextVar("stats_recording",
                                           default=True)


class stats_recording_disabled:
    """Context manager: suppress adaptive-stat recording (and the host
    syncs that feed it) for single-shot plan executions."""

    def __enter__(self):
        self._token = _STATS_RECORDING.set(False)

    def __exit__(self, *exc):
        _STATS_RECORDING.reset(self._token)
        return False


def stats_recording() -> bool:
    return _STATS_RECORDING.get()


class _AdaptiveStatsCache:
    """Bounded stats cache whose keys embed id() of leaf device arrays.

    An id can be recycled after its array is garbage-collected, which
    would silently replay stale stats (wrong clip ranges -> wrong join
    results). Entries therefore hold WEAKREFS to the arrays and are
    evicted the moment any referenced array dies — no HBM is pinned, and
    a recycled id can never alias a live entry. LRU-bounded as well."""

    def __init__(self, maxsize: int = 256):
        from collections import OrderedDict

        self._data: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._maxsize = maxsize

    def _alive(self, key) -> bool:
        v = self._data.get(key)
        if v is None:
            return False
        _, refs = v
        if any(r() is None for r in refs):
            del self._data[key]
            return False
        return True

    def get(self, key_and_pins):
        key, _ = key_and_pins
        if not self._alive(key):
            return None
        self._data.move_to_end(key)
        return self._data[key][0]

    def put(self, key_and_pins, value) -> None:
        import weakref

        if not _STATS_RECORDING.get():
            return
        key, pins = key_and_pins
        try:
            refs = tuple(weakref.ref(a) for a in pins)
        except TypeError:
            return  # non-weakref-able leaf: safer to skip caching
        # sweep entries whose leaves died: they can never be hit again
        # (stats_key embeds array ids) but would otherwise pin their
        # values — for _JoinIndexCache that is real HBM — indefinitely
        dead = [k for k, (_, rs) in self._data.items()
                if any(r() is None for r in rs)]
        for k in dead:
            del self._data[k]
        self._data[key] = (value, refs)
        self._data.move_to_end(key)
        while len(self._data) > self._maxsize:
            self._data.popitem(last=False)

    def __contains__(self, key_and_pins) -> bool:
        return self._alive(key_and_pins[0])

    def __len__(self) -> int:
        return len(self._data)


_JOIN_STATS = _AdaptiveStatsCache()


class JoinStats(NamedTuple):
    """What a join's first execution records and later ones bind
    (``JoinExec.adaptive``): the key packing (``"hash"``, or per key its
    (offset, range)), whether each side's keys match at most once, the
    capacity of a sized expansion (None where membership alone is asked),
    whether the counts came from the compiled sizing programs and the
    key a multi-key join is looked up by."""

    packing: Any
    unique_build: bool
    unique_probe: bool
    cap: Optional[int]
    sized: bool = False
    #: where the build side is unique on one key alone, that key's index
    #: (``planner._lookup``); the other keys are tested on the row it
    #: finds (``K.lookup_join_ranges``)
    lookup: Optional[int] = None


class _JoinIndexCache(_AdaptiveStatsCache):
    """Join-index cache bounded by pinned DEVICE BYTES, not entry count:
    a lineitem-scale index holds ~100 MB of HBM while a dimension-table
    index is a few KB, so a count LRU either starves breadth or risks
    HBM. Values are (orient, index_batch, tables_batch|None). Note an
    evicted index is only re-recorded by a future BLOCKING run (new leaf
    arrays); until then the join still executes correctly through the
    live build_join_ranges path, just without the speedup."""

    def __init__(self, max_bytes: int = 1 << 30):
        super().__init__(maxsize=1 << 62)
        self._max_bytes = max_bytes

    @staticmethod
    def _nbytes(value) -> int:
        _, ib, tb = value
        total = 0
        for b in (ib, tb):
            if b is None:
                continue
            for cd in b.data.columns:
                total += cd.data.size * cd.data.dtype.itemsize
        return total

    def put(self, key_and_pins, value) -> None:
        super().put(key_and_pins, value)
        total = sum(self._nbytes(v) for v, _ in self._data.values())
        while total > self._max_bytes and len(self._data) > 1:
            _, (v, _) = self._data.popitem(last=False)
            total -= self._nbytes(v)


#: Cached join build indexes (kernels.make_join_index outputs, wrapped
#: as aux Batches); leaf weakrefs evict entries when their data dies.
_JOIN_INDEX = _JoinIndexCache()

#: Observed explode output capacity per (plan, leaf-ids) — same replay
#: discipline as _JOIN_STATS (GenerateExec).
_GEN_STATS = _AdaptiveStatsCache()

#: Adaptive aggregation statistics: observed group count per
#: (plan, leaf-array-ids) — lets the sort-based aggregation path trace
#: with a static output capacity on re-execution (same AQE idea as
#: _JOIN_STATS; reference: AdaptiveSparkPlanExec.scala:247).
_AGG_STATS = _AdaptiveStatsCache()


@dataclass(eq=False)
class JoinExec(PhysicalPlan):
    """Equi-join via sorted-build + searchsorted ranges (reference:
    ShuffledHashJoinExec.scala:38 / BroadcastHashJoinExec.scala:40 +
    HashedRelation.scala — rebuilt without hash tables, see
    kernels.build_join_ranges). An inner/left/semi/anti equi-join is
    sized on its first execution in compiled programs (``JoinSizeExec``,
    ``JoinIndexExec``: planner._size_join) and traceable from then on,
    its packing, uniqueness and output capacity bound from _JOIN_STATS;
    the others block on every execution (host-synced match count)."""

    left: PhysicalPlan
    right: PhysicalPlan
    how: str
    left_keys: Tuple[E.Expression, ...]
    right_keys: Tuple[E.Expression, ...]
    condition: Optional[E.Expression] = None
    #: bound by the planner from _JOIN_STATS: tuple of per-key (mn, rg)
    adaptive: Optional[tuple] = None
    #: bound by the planner from _JOIN_INDEX: aux scans over the cached
    #: build-side sort permutation / sorted key / dense lo+cnt tables
    #: (kernels.make_join_index). Excluded from plan_key/stats_key —
    #: they are derived data; the stage cache distinguishes their
    #: presence via planner._adaptive_snapshot.
    index_scan: Optional[PhysicalPlan] = None
    table_scan: Optional[PhysicalPlan] = None
    #: orientation the cached index was built for: 'fwd' = build on the
    #: right (every path but swap), 'rev' = build on the left (swap)
    index_orient: Optional[str] = None

    @property
    def traceable(self) -> bool:
        if self.adaptive is None:
            return False
        unique_build, unique_probe = self.adaptive[1], self.adaptive[2]
        if unique_build and self.how in ("inner", "left", "left_semi",
                                         "left_anti"):
            return True
        # sides of an INNER join are symmetric: a unique probe side can
        # play the build role (output capacity = right capacity)
        if unique_probe and self.how == "inner":
            return True
        # sized expansion: the first run recorded the bucketed output
        # capacity for THESE leaf arrays, so even a many-to-many join
        # replays as one static-shape traced program (no sizing sync)
        cap = self.adaptive[3] if len(self.adaptive) > 3 else None
        if cap is not None and self.how in ("inner", "left",
                                            "left_semi", "left_anti"):
            return True
        # semi/anti membership without condition/hash sizes itself
        return (self.how in ("left_semi", "left_anti")
                and self.condition is None and self.adaptive[0] != "hash")

    @property
    def sized_in_programs(self) -> bool:
        """An equi-join of the kinds whose first execution is sized in
        compiled programs (``planner._size_join``) and traced from then
        on, where stats are recorded."""
        return (bool(self.left_keys)
                and self.how in ("inner", "left", "left_semi", "left_anti")
                and not self.has_blocking_exprs())

    @property
    def wants_join_size(self) -> bool:
        """Blocks for want of its sizes alone: a stage once the planner
        has sized it (``planner._size_join``). Where stats are not
        recorded (the chunked tiers' single-shot plans, whose packings
        differ chunk by chunk) the join runs ``execute_blocking``."""
        return (self.adaptive is None and self.sized_in_programs
                and stats_recording())

    def output_capacity(self, lcap: int, rcap: int) -> int:
        """Rows of the traced join's output over sides of these
        capacities: what every operator above it runs at."""
        st = self.adaptive
        strat, _ = self._strategy(st[1], st[2], st[3], lcap, rcap)
        if strat == "swap":
            return rcap
        if strat != "expand" or self.how in ("left_semi", "left_anti"):
            return lcap
        # a left join's expansion appends its unmatched left rows
        return st[3] + (lcap if self.how == "left" else 0)

    def children(self):
        out = (self.left, self.right)
        if self.index_scan is not None:
            out += (self.index_scan,)
        if self.table_scan is not None:
            out += (self.table_scan,)
        return out

    def _strategy(self, unique_build: bool, unique_probe: bool,
                  sized_cap, lcap: int, rcap: int):
        """Traced-join strategy and the orientation its ranges need.
        Chosen by OUTPUT capacity (see trace()); shared with the
        blocking recorder so the cached index matches the orientation
        the next trace will pick. Returns (strat, 'fwd'|'rev')."""
        if self.how == "inner":
            cands = []
            if unique_build:
                cands.append((lcap, 0, "build"))
            if unique_probe:
                cands.append((rcap, 1, "swap"))
            if sized_cap is not None:
                cands.append((sized_cap * 2, 2, "expand"))
            if not cands:
                return None, "fwd"
            strat = min(cands)[2]
            return strat, ("rev" if strat == "swap" else "fwd")
        if unique_build:
            return "build", "fwd"
        if sized_cap is None:
            return "member", "fwd"
        return "expand", "fwd"

    def _indexed_ranges(self, build_key, build_ok, probe_key, probe_ok,
                        child_pipes: List[Pipe], want: str, codes=None):
        """Join ranges via the cached index when one with the right
        orientation is bound; the live build_join_ranges otherwise; the
        dense row and code tables of ``K.lookup_join_ranges`` where the
        join is looked up by one key (``codes``: the other keys' codes,
        probe side and build side). Records the ``join`` build event: the
        rung this traced join was BUILT from (``table``: dense lo / cnt
        lookup; ``index``: cached perm + sorted key, searched; ``live``:
        sort + search inside the program; ``lookup``: the tables set
        inside the program, no sort), the keys it looked up and those it
        tested on the row it found (``looked_up``, ``tested``: ``l=r``
        each), once per trace and never per execution."""

        lcap, rcap = child_pipes[0].capacity, child_pipes[1].capacity
        keys = [f"{lk}={rk}" for lk, rk in zip(self.left_keys,
                                               self.right_keys)]
        lookup = getattr(self.adaptive, "lookup", None)

        def built(rung: str) -> None:
            _trace.built("join", rung=rung, how=self.how, orient=want,
                         build_rows=int(build_key.shape[0]),
                         probe_cap=int(probe_key.shape[0]),
                         capacity=self.output_capacity(lcap, rcap),
                         sized=bool(getattr(self.adaptive, "sized", False)),
                         looked_up=[k for i, k in enumerate(keys)
                                    if lookup in (None, i)],
                         tested=[k for i, k in enumerate(keys)
                                 if lookup not in (None, i)])

        if codes is not None:
            built("lookup")
            return K.lookup_join_ranges(
                build_key, build_ok, codes[1], probe_key, probe_ok,
                codes[0], self.adaptive[0][lookup][1])

        if self.index_scan is not None and len(child_pipes) > 2 \
                and self.index_orient == want:
            ipipe = child_pipes[2]
            perm = ipipe.cols["perm"].data
            skey = ipipe.cols["skey"].data
            # layout guard: the index is positional, recorded against
            # the build side as the blocking run saw it (possibly
            # compacted). If the corresponding _COMPACT_STATS entry was
            # independently evicted, the traced build pipe rides at a
            # DIFFERENT capacity — replaying the index would gather
            # arbitrary rows. A recorded compaction always changes the
            # capacity, so shape equality is the invariant.
            if perm.shape[0] == build_key.shape[0]:
                lo_t = cnt_t = None
                if self.table_scan is not None and len(child_pipes) > 3:
                    tpipe = child_pipes[3]
                    lo_t = tpipe.cols["lo"].data
                    cnt_t = tpipe.cols["cnt"].data
                built("index" if lo_t is None else "table")
                return K.ranges_from_index(perm, skey, lo_t, cnt_t,
                                           probe_key, probe_ok)
        built("live")
        return K.build_join_ranges(build_key, build_ok,
                                   probe_key, probe_ok)

    @property
    def schema(self) -> Schema:
        if self.how in ("left_semi", "left_anti"):
            return self.left.schema
        lf = list(self.left.schema.fields)
        rf = list(self.right.schema.fields)
        if self.how in ("left", "full"):
            rf = [dataclasses.replace(f, nullable=True) for f in rf]
        if self.how in ("right", "full"):
            lf = [dataclasses.replace(f, nullable=True) for f in lf]
        names = E.dedup_pair_names([f.name for f in lf],
                                   [f.name for f in rf])
        out = [dataclasses.replace(f, name=n)
               for f, n in zip(lf + rf, names)]
        return Schema(tuple(out))

    # -- key normalization ----------------------------------------------------

    def _combined_keys(self, lpipe: Pipe, rpipe: Pipe):
        """Evaluate equi-join keys on both sides and pack them into one
        int64 key per row; strings go through a unified dictionary, ints
        through range compression (host-sync min/max stats)."""
        lenv, renv = lpipe.env(), rpipe.env()
        lks = [C.evaluate(k, lenv) for k in self.left_keys]
        rks = [C.evaluate(k, renv) for k in self.right_keys]

        lcomb = jnp.zeros((lpipe.capacity,), dtype=jnp.int64)
        rcomb = jnp.zeros((rpipe.capacity,), dtype=jnp.int64)
        lvalid = jnp.ones((lpipe.capacity,), dtype=jnp.bool_)
        rvalid = jnp.ones((rpipe.capacity,), dtype=jnp.bool_)

        # phase 1: per-key data + deferred min/max stats, fetched with ONE
        # host sync for ALL int keys (each int(...) is a full blocking
        # round trip, and multi-key joins paid it twice per key)
        prepped = []  # (ld, rd, rg_or_None, stat_index_or_None)
        stats = []
        for lt, rt in zip(lks, rks):
            if isinstance(lt.dtype, T.StringType) or isinstance(rt.dtype, T.StringType):
                union, (tl, tr) = C.unify_dictionaries(
                    (lt.dictionary or (), rt.dictionary or ()))
                ld = jnp.asarray(tl)[lt.data] if len(lt.dictionary or ()) else lt.data
                rd = jnp.asarray(tr)[rt.data] if len(rt.dictionary or ()) else rt.data
                prepped.append((ld, rd, max(1, len(union)), None))
            else:
                ld = lt.data.astype(jnp.int64)
                rd = rt.data.astype(jnp.int64)
                lm = jnp.where(lpipe.mask & lt.valid_or_true(lpipe.capacity),
                               ld, jnp.iinfo(jnp.int64).max)
                rm = jnp.where(rpipe.mask & rt.valid_or_true(rpipe.capacity),
                               rd, jnp.iinfo(jnp.int64).max)
                lo = jnp.minimum(jnp.min(lm), jnp.min(rm))
                l_hi = jnp.where(lpipe.mask & lt.valid_or_true(lpipe.capacity),
                                 ld, jnp.iinfo(jnp.int64).min)
                r_hi = jnp.where(rpipe.mask & rt.valid_or_true(rpipe.capacity),
                                 rd, jnp.iinfo(jnp.int64).min)
                hi = jnp.maximum(jnp.max(l_hi), jnp.max(r_hi))
                prepped.append((ld, rd, None, len(stats)))
                stats.append((lo, hi))
        fetched = jax.device_get(stats) if stats else []

        total_range = 1
        packing: List[Tuple[int, int]] = []
        overflow = False
        for ld, rd, rg, si in prepped:
            mn = 0
            if rg is None:
                mn, mx = int(fetched[si][0]), int(fetched[si][1])
                if mn > mx:
                    mn, mx = 0, 0
                rg = mx - mn + 1
            if total_range * rg > (1 << 62):  # incl. single wide key
                overflow = True
                break
            lcomb = lcomb * rg + jnp.clip(ld - mn, 0, rg - 1)
            rcomb = rcomb * rg + jnp.clip(rd - mn, 0, rg - 1)
            total_range *= rg
            packing.append((mn, rg))
        if overflow:
            # exact range packing impossible (e.g. two hash-like int64
            # ids): hash-combine the keys and VERIFY pairs after
            # expansion (reference: HashedRelation.scala:208 — probe by
            # hash, confirm by key equality)
            lcomb, rcomb = _hash_keys([p[0] for p in prepped],
                                      [p[1] for p in prepped])
            packing = "hash"  # type: ignore[assignment]
        for lt, rt in zip(lks, rks):
            if lt.validity is not None:
                lvalid = lvalid & lt.validity
            if rt.validity is not None:
                rvalid = rvalid & rt.validity
        if packing != "hash":
            packing = tuple(packing)
        return lcomb, lvalid, rcomb, rvalid, packing, \
            [(p[0], p[1]) for p in prepped]

    # -- traced path (adaptive, unique-build) ---------------------------------

    def key_is_string(self) -> List[bool]:
        """Per key pair: packed by its unified dictionary (static), not
        by an observed range."""
        ls, rs = self.left.schema, self.right.schema
        return [isinstance(lk.data_type(ls), T.StringType)
                or isinstance(rk.data_type(rs), T.StringType)
                for lk, rk in zip(self.left_keys, self.right_keys)]

    def key_ranges(self, lpipe: Pipe, rpipe: Pipe) -> List[Tuple]:
        """Per key pair, traced: the least and greatest key among the
        live rows of either side whose key is valid — what its packing
        is taken from (``planner._size_join``); a dictionary string key
        reads (0, its unified dictionary's last code)."""
        lenv, renv = lpipe.env(), rpipe.env()
        out = []
        for lk, rk in zip(self.left_keys, self.right_keys):
            lt, rt = C.evaluate(lk, lenv), C.evaluate(rk, renv)
            if isinstance(lt.dtype, T.StringType) \
                    or isinstance(rt.dtype, T.StringType):
                union, _ = C.unify_dictionaries(
                    (lt.dictionary or (), rt.dictionary or ()))
                out.append((jnp.int64(0), jnp.int64(max(1, len(union)) - 1)))
                continue
            lo, hi = jnp.iinfo(jnp.int64).max, jnp.iinfo(jnp.int64).min
            for pipe, tv in ((lpipe, lt), (rpipe, rt)):
                live = pipe.mask & tv.valid_or_true(pipe.capacity)
                d = tv.data.astype(jnp.int64)
                lo = jnp.minimum(lo, jnp.min(jnp.where(live, d, lo)))
                hi = jnp.maximum(hi, jnp.max(jnp.where(live, d, hi)))
            out.append((lo, hi))
        return out

    def _traced_keys(self, lpipe: Pipe, rpipe: Pipe, packing=None,
                     lookup=None):
        """Key packing with STATIC per-key (mn, rg) from adaptive stats —
        no host syncs, so the join fuses into the surrounding program.
        Sound because the planner only binds stats recorded for these
        exact (immutable) leaf arrays. packing == 'hash' reproduces the
        hash-combined fallback; callers must then verify pairs. Where
        the join is looked up by one key (``lookup``), that key alone is
        packed and the others into a code each side, returned last as
        (probe's, build's); None otherwise. ``packing`` and ``lookup``
        are the bound stats' unless given (the sizing programs run
        before any is bound)."""
        lenv, renv = lpipe.env(), rpipe.env()
        lks = [C.evaluate(k, lenv) for k in self.left_keys]
        rks = [C.evaluate(k, renv) for k in self.right_keys]
        lcomb = jnp.zeros((lpipe.capacity,), dtype=jnp.int64)
        rcomb = jnp.zeros((rpipe.capacity,), dtype=jnp.int64)
        lvalid = jnp.ones((lpipe.capacity,), dtype=jnp.bool_)
        rvalid = jnp.ones((rpipe.capacity,), dtype=jnp.bool_)
        if packing is None:
            packing = self.adaptive[0]
            lookup = getattr(self.adaptive, "lookup", None)
        hashed = packing == "hash"
        lcode = rcode = 0
        prepped = []
        for ki, (lt, rt) in enumerate(zip(lks, rks)):
            if isinstance(lt.dtype, T.StringType) \
                    or isinstance(rt.dtype, T.StringType):
                union, (tl, tr) = C.unify_dictionaries(
                    (lt.dictionary or (), rt.dictionary or ()))
                ld = (jnp.asarray(tl)[lt.data]
                      if len(lt.dictionary or ()) else lt.data)
                rd = (jnp.asarray(tr)[rt.data]
                      if len(rt.dictionary or ()) else rt.data)
                mn, rg = 0, max(1, len(union))
            else:
                ld = lt.data.astype(jnp.int64)
                rd = rt.data.astype(jnp.int64)
                if not hashed:
                    mn, rg = packing[ki]
            prepped.append((ld, rd))
            if not hashed:
                if isinstance(lt.dtype, T.StringType) \
                        or isinstance(rt.dtype, T.StringType):
                    rg = max(rg, packing[ki][1])
                if lookup is None or ki == lookup:
                    lcomb = lcomb * rg + jnp.clip(ld - mn, 0, rg - 1)
                    rcomb = rcomb * rg + jnp.clip(rd - mn, 0, rg - 1)
                else:
                    lcode = lcode * rg + jnp.clip(ld - mn, 0, rg - 1)
                    rcode = rcode * rg + jnp.clip(rd - mn, 0, rg - 1)
            if lt.validity is not None:
                lvalid = lvalid & lt.validity
            if rt.validity is not None:
                rvalid = rvalid & rt.validity
        if hashed:
            lcomb, rcomb = _hash_keys([p[0] for p in prepped],
                                      [p[1] for p in prepped])
        codes = None if lookup is None else (lcode, rcode)
        return lcomb, lvalid, rcomb, rvalid, hashed, prepped, codes

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        """Unique-build join as a pure gather: each probe row has at most
        one match (adaptive stats proved it), so output capacity equals
        probe capacity and no sizing sync is needed. This is the PK-FK
        fast path every TPC-H join takes after the first execution."""
        lpipe, rpipe = child_pipes[:2]
        unique_build, unique_probe = self.adaptive[1], self.adaptive[2]
        sized_cap = self.adaptive[3] if len(self.adaptive) > 3 else None
        lcomb, lvalid, rcomb, rvalid, hashed, prepped, codes = \
            self._traced_keys(lpipe, rpipe)
        # strategy choice by OUTPUT CAPACITY: every op downstream of
        # this join (further joins, aggregation, sort) runs at the
        # capacity chosen here, so a selective join must shrink the
        # pipeline even when a gather-style join is locally cheaper.
        # (Profiled: q3's swapped join emitted at lineitem's 3.05M
        # capacity and the group-by sort-aggregated 3M rows for a
        # 32k-pair join — 1.2 s of gathers/sorts for a ~250 ms query.)
        # Expansion pays the pairs' run-start scatter and running max
        # + pair mask, so it must be ~2x smaller to win.
        strat, _ = self._strategy(unique_build, unique_probe, sized_cap,
                                  lpipe.capacity, rpipe.capacity)
        if strat == "swap":
            return self._trace_swapped(lpipe, rpipe, lcomb, lvalid,
                                       rcomb, rvalid, hashed, prepped,
                                       child_pipes)
        if strat == "expand":
            ranges = self._indexed_ranges(rcomb, rpipe.mask & rvalid,
                                          lcomb, lpipe.mask & lvalid,
                                          child_pipes, "fwd", codes)
            return self._pairs_pipe(lpipe, rpipe, ranges, hashed,
                                    prepped, sized_cap)
        if strat == "member":
            # semi/anti without condition/hash: membership only, no
            # expansion needed at any capacity
            ranges = self._indexed_ranges(rcomb, rpipe.mask & rvalid,
                                          lcomb, lpipe.mask & lvalid,
                                          child_pipes, "fwd", codes)
            has = ranges.counts > 0
            keep = lpipe.mask & (has if self.how == "left_semi"
                                 else ~has)
            return Pipe(lpipe.cols, keep, lpipe.order)
        # strat == 'build': unique-build gather at probe capacity
        ranges = self._indexed_ranges(rcomb, rpipe.mask & rvalid,
                                      lcomb, lpipe.mask & lvalid,
                                      child_pipes, "fwd", codes)
        has = ranges.counts > 0
        b_idx = ranges.build_perm[
            jnp.clip(ranges.lo, 0, ranges.build_perm.shape[0] - 1)]
        if hashed:
            p_idx = jnp.arange(lpipe.capacity)
            has = has & _verify_key_pairs(prepped, p_idx, b_idx,
                                          lpipe.capacity)
        if self.how in ("left_semi", "left_anti") and self.condition is None:
            keep = lpipe.mask & (has if self.how == "left_semi" else ~has)
            return Pipe(lpipe.cols, keep, lpipe.order)
        pair_names = _pair_names(lpipe.order, rpipe.order)
        n_l = len(lpipe.order)
        cols: Dict[str, TV] = {}
        order: List[str] = []
        for out_name, src in zip(pair_names[:n_l], lpipe.order):
            cols[out_name] = lpipe.cols[src]
            order.append(out_name)
        for out_name, src in zip(pair_names[n_l:], rpipe.order):
            tv = rpipe.cols[src]
            validity = tv.valid_or_true(rpipe.capacity)[b_idx] & has
            cols[out_name] = TV(tv.data[b_idx], validity, tv.dtype,
                                tv.dictionary)
            order.append(out_name)
        pair_ok = lpipe.mask & has
        if self.condition is not None:
            env = Env(cols, lpipe.capacity)
            ctv = C.evaluate(self.condition, env)
            pair_ok = pair_ok & ctv.data & ctv.valid_or_true(lpipe.capacity)
        if self.how == "left_semi":
            return Pipe(lpipe.cols, pair_ok, lpipe.order)
        if self.how == "left_anti":
            return Pipe(lpipe.cols, lpipe.mask & ~pair_ok, lpipe.order)
        if self.how == "inner":
            return Pipe(cols, pair_ok, order)
        # left outer: keep every live left row, NULL right side where the
        # (condition-passing) match is absent
        for out_name in pair_names[n_l:]:
            tv = cols[out_name]
            validity = tv.valid_or_true(lpipe.capacity) & pair_ok
            cols[out_name] = TV(tv.data, validity, tv.dtype, tv.dictionary)
        return Pipe(cols, lpipe.mask, order)

    def _trace_swapped(self, lpipe: Pipe, rpipe: Pipe, lcomb, lvalid,
                       rcomb, rvalid, hashed=False, prepped=(),
                       child_pipes=()) -> Pipe:
        """Inner join with a unique LEFT side: build on the left, stream
        the right; each right row gathers its single left match."""
        ranges = self._indexed_ranges(lcomb, lpipe.mask & lvalid,
                                      rcomb, rpipe.mask & rvalid,
                                      list(child_pipes), "rev")
        has = ranges.counts > 0
        l_idx = ranges.build_perm[
            jnp.clip(ranges.lo, 0, lpipe.capacity - 1)]
        if hashed:
            # verify with sides swapped: left is the build being gathered
            swapped = [(rd, ld) for ld, rd in prepped]
            has = has & _verify_key_pairs(
                swapped, jnp.arange(rpipe.capacity), l_idx,
                rpipe.capacity)
        pair_names = _pair_names(lpipe.order, rpipe.order)
        n_l = len(lpipe.order)
        cols: Dict[str, TV] = {}
        order: List[str] = []
        for out_name, src in zip(pair_names[:n_l], lpipe.order):
            tv = lpipe.cols[src]
            validity = tv.valid_or_true(lpipe.capacity)[l_idx] & has
            cols[out_name] = TV(tv.data[l_idx], validity, tv.dtype,
                                tv.dictionary)
            order.append(out_name)
        for out_name, src in zip(pair_names[n_l:], rpipe.order):
            cols[out_name] = rpipe.cols[src]
            order.append(out_name)
        pair_ok = rpipe.mask & has
        if self.condition is not None:
            env = Env(cols, rpipe.capacity)
            ctv = C.evaluate(self.condition, env)
            pair_ok = pair_ok & ctv.data & ctv.valid_or_true(rpipe.capacity)
        return Pipe(cols, pair_ok, order)

    def index_layout_stable(self, orient: str) -> bool:
        """May the build side of ``orient`` keep a recorded index? The
        index is POSITIONAL, so the build side's row layout must be the
        same in the execution that recorded it and in every traced one
        that replays it. Joins and adaptive aggregations emit different
        layouts on their blocking vs traced paths (expansion order vs
        gather order), so a build subtree containing one is not indexed
        — the trace falls back to live build_join_ranges. A join sized
        in compiled programs (``sized_in_programs``) is the exception:
        its first execution runs the trace every later one runs, bound
        to the same stats, so it lays its rows out the same way."""

        def stable(p: PhysicalPlan) -> bool:
            if isinstance(p, HashAggregateExec) or (
                    isinstance(p, JoinExec) and not p.sized_in_programs):
                return False
            return all(stable(c) for c in p.children())

        return stable(self.left if orient == "rev" else self.right)

    def execute_blocking(self, child_batches: List[Batch]) -> Batch:
        """The join run operation by operation, its output sized by a
        host sync: right and full outer joins, condition-only and cross
        joins, and the single-shot plans of the chunked tiers (which
        record no stats); every other join is sized in compiled
        programs on its first execution and traced (``wants_join_size``)."""
        lpipe = Pipe.from_batch_data(child_batches[0].schema,
                                     child_batches[0].data)
        rpipe = Pipe.from_batch_data(child_batches[1].schema,
                                     child_batches[1].data)
        how = self.how

        if how == "cross" and self.condition is None:
            return self._cross(lpipe, rpipe)
        if not self.left_keys:
            # condition-only join: chunked nested loop instead of
            # materializing all L*R pairs at once (reference:
            # BroadcastNestedLoopJoinExec; VERDICT r2 weak #4 — q19-class
            # plans used to OOM/hang here)
            return self._nested_loop(lpipe, rpipe, how)

        lkey, lvalid, rkey, rvalid, packing, prepped = self._combined_keys(
            lpipe, rpipe)
        hashed = packing == "hash"
        # probe = left, build = right (left-side row order is preserved,
        # matching streamed-side semantics)
        ranges = K.build_join_ranges(rkey, rpipe.mask & rvalid,
                                     lkey, lpipe.mask & lvalid)
        if how in ("left_semi", "left_anti") and self.condition is None \
                and not hashed:
            has_match = ranges.counts > 0
            keep = lpipe.mask & (has_match if how == "left_semi"
                                 else ~has_match)
            return Pipe(lpipe.cols, keep, lpipe.order).to_batch()
        total = int(ranges.counts.sum())  # host sync: output sizing
        return self._pairs_pipe(lpipe, rpipe, ranges, hashed, prepped,
                                K.relative_bucket(total)).to_batch()

    def _pairs_pipe(self, lpipe: Pipe, rpipe: Pipe, ranges, hashed,
                    prepped, cap: int) -> Pipe:
        """General match expansion at a STATIC capacity — pure jnp, so
        it runs identically as the blocking tail and as the fused
        sized-expansion trace."""
        how = self.how
        p_idx, b_idx, pair_mask = K.expand_join_pairs(ranges, cap)

        # The pair environment always carries BOTH sides (with '#2'
        # dedup names) so semi/anti join conditions can reference the
        # inner relation; the output schema narrows afterwards.
        pair_names = _pair_names(lpipe.order, rpipe.order)
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
        if hashed:
            # hash probe: confirm candidate pairs by exact key equality
            pair_ok = pair_ok & _verify_key_pairs(prepped, p_idx, b_idx,
                                                  cap)
        if self.condition is not None:
            env = Env(cols, cap)
            ctv = C.evaluate(self.condition, env)
            pair_ok = pair_ok & ctv.data & ctv.valid_or_true(cap)

        if how == "inner":
            return Pipe(cols, pair_ok, order)

        # matched flags must be computed on the ORIGINAL pair arrays,
        # before any unmatched-row appends change the capacity
        matched = K.seg_count(p_idx, pair_ok, lpipe.capacity) > 0
        matched_b = (K.seg_count(b_idx, pair_ok, rpipe.capacity) > 0
                     if how in ("right", "full") else None)
        if how == "left_semi":
            return Pipe(lpipe.cols, lpipe.mask & matched, lpipe.order)
        if how == "left_anti":
            return Pipe(lpipe.cols, lpipe.mask & ~matched, lpipe.order)

        if how in ("left", "full"):
            out = append_unmatched_left(cols, pair_ok, order, lpipe, matched)
            cols, pair_ok, order, cap = out
        if how in ("right", "full"):
            out = append_unmatched_right(
                cols, pair_ok, order, lpipe, rpipe, matched_b)
            cols, pair_ok, order, cap = out
        return Pipe(cols, pair_ok, order)

    def _nested_loop(self, lpipe: Pipe, rpipe: Pipe, how: str) -> Batch:
        """Condition-only join evaluated in fixed-size left-chunks of
        bounded pair count. Fixed chunk shapes mean one XLA dispatch
        compile serves every chunk; surviving pair indices are pulled to
        host per chunk (this is the blocking path) and gathered once at
        the end."""
        lcap = lpipe.capacity
        rcap = rpipe.capacity
        rn = int(np.asarray(rpipe.mask).sum())  # host sync: build size
        rperm = K.compaction_permutation(rpipe.mask)
        pair_names = _pair_names(lpipe.order, rpipe.order)
        lnames = list(lpipe.order)

        def gather_pairs(p_idx, b_idx) -> Tuple[Dict[str, TV], List[str]]:
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
            return cols, order

        matched_l = np.zeros(lcap, dtype=bool)
        matched_r = np.zeros(rcap, dtype=bool)
        keep_p: List[np.ndarray] = []
        keep_b: List[np.ndarray] = []
        if rn > 0:
            budget = 1 << 22  # pairs per chunk (~32 MB of int64 per col)
            chunk = max(1, min(lcap, budget // rn))
            j = jnp.arange(chunk * rn)
            local_p = j // rn
            b_idx = rperm[j % rn]
            for start in range(0, lcap, chunk):
                p_idx = jnp.clip(local_p + start, 0, lcap - 1)
                pair_ok = (local_p + start < lcap) & lpipe.mask[p_idx]
                if self.condition is not None:
                    cols, _ = gather_pairs(p_idx, b_idx)
                    env = Env(cols, chunk * rn)
                    ctv = C.evaluate(self.condition, env)
                    pair_ok = pair_ok & ctv.data & ctv.valid_or_true(
                        chunk * rn)
                ok = np.asarray(pair_ok)
                idx = np.nonzero(ok)[0]
                if idx.size:
                    ps = np.asarray(p_idx)[idx]
                    bs = np.asarray(b_idx)[idx]
                    matched_l[ps] = True
                    matched_r[bs] = True
                    if how not in ("left_semi", "left_anti"):
                        keep_p.append(ps)
                        keep_b.append(bs)

        ml = jnp.asarray(matched_l)
        if how == "left_semi":
            return Pipe(lpipe.cols, lpipe.mask & ml, lpipe.order).to_batch()
        if how == "left_anti":
            return Pipe(lpipe.cols, lpipe.mask & ~ml, lpipe.order).to_batch()

        all_p = (np.concatenate(keep_p) if keep_p
                 else np.zeros((0,), dtype=np.int64))
        all_b = (np.concatenate(keep_b) if keep_b
                 else np.zeros((0,), dtype=np.int64))
        total = int(all_p.shape[0])
        cap = K.bucket(total)
        pad_p = np.zeros(cap, dtype=np.int64)
        pad_b = np.zeros(cap, dtype=np.int64)
        pad_p[:total] = all_p
        pad_b[:total] = all_b
        p_idx = jnp.asarray(pad_p)
        b_idx = jnp.asarray(pad_b)
        pair_ok = jnp.arange(cap) < total
        cols, order = gather_pairs(p_idx, b_idx)

        if how in ("inner", "cross"):
            return Pipe(cols, pair_ok, order).to_batch()
        if how in ("left", "full"):
            out = append_unmatched_left(cols, pair_ok, order, lpipe, ml)
            cols, pair_ok, order, cap = out
        if how in ("right", "full"):
            out = append_unmatched_right(
                cols, pair_ok, order, lpipe, rpipe, jnp.asarray(matched_r))
            cols, pair_ok, order, cap = out
        return Pipe(cols, pair_ok, order).to_batch()

    def _cross(self, lpipe: Pipe, rpipe: Pipe) -> Batch:
        ln = int(np.asarray(lpipe.mask).sum())
        rn = int(np.asarray(rpipe.mask).sum())
        cap = K.bucket(lpipe.capacity * rn if rn else 1)
        j = jnp.arange(cap)
        rs = max(rn, 1)
        p_idx = j // rs
        # compact right side live rows first
        rperm = K.compaction_permutation(rpipe.mask)
        b_idx = rperm[j % rs]
        pair_mask = (j < lpipe.capacity * rs) & lpipe.mask[
            jnp.clip(p_idx, 0, lpipe.capacity - 1)]
        if rn == 0:  # empty side -> empty cross product
            pair_mask = jnp.zeros_like(pair_mask)
        p_idx = jnp.clip(p_idx, 0, lpipe.capacity - 1)
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
            env = Env(cols, cap)
            ctv = C.evaluate(self.condition, env)
            pair_mask = pair_mask & ctv.data & ctv.valid_or_true(cap)
        return Pipe(cols, pair_mask, order).to_batch()

    def node_string(self):
        ks = ", ".join(f"{l}={r}" for l, r in zip(self.left_keys,
                                                  self.right_keys))
        return f"Join[{self.how}, ({ks}), cond={self.condition}]"

    def plan_key(self):
        return ("Join", self.how,
                tuple(E.expr_key(k) for k in self.left_keys),
                tuple(E.expr_key(k) for k in self.right_keys),
                None if self.condition is None else E.expr_key(self.condition),
                self.left.plan_key(), self.right.plan_key())


def packed_domain(packing) -> Optional[int]:
    """Keys a join's packing can give (the product of its ranges); None
    for hashed keys."""
    if packing == "hash":
        return None
    domain = 1
    for _, rg in packing:
        domain *= rg
    return domain


@dataclass(eq=False)
class _JoinSizing(PhysicalPlan):
    """A program over a join's two sides that sizes the join; run as a
    stage of its own on the join's first execution (planner._size_join)
    and never bound into a query's plan. Its children are the join's."""

    join: JoinExec
    packing: Any = None
    #: the key the join is looked up by (``JoinStats.lookup``)
    lookup: Optional[int] = None
    traceable = True

    def children(self):
        return (self.join.left, self.join.right)

    def map_children(self, fn) -> "PhysicalPlan":
        join = self.join.map_children(fn)
        return self if join is self.join else dataclasses.replace(
            self, join=join)

    @property
    def schema(self) -> Schema:
        raise NotImplementedError("a sizing program's schema is its trace's")

    def _keys(self, child_pipes: List[Pipe]):
        """(build key, build ok, probe key, probe ok, domain, codes)
        packed by ``packing``: the right side is built, the left probes;
        ``codes`` as ``JoinExec._traced_keys``, and the domain the
        looked-up key's where there is one."""
        lpipe, rpipe = child_pipes
        lkey, lvalid, rkey, rvalid, _, _, codes = self.join._traced_keys(
            lpipe, rpipe, self.packing, self.lookup)
        domain = (packed_domain(self.packing) if self.lookup is None
                  else self.packing[self.lookup][1])
        return (rkey, rpipe.mask & rvalid, lkey, lpipe.mask & lvalid,
                domain, codes)

    def plan_key(self):
        return (type(self).__name__, self.packing, self.lookup,
                self.join.plan_key())


def _one_row(cols: Dict[str, Any]) -> Pipe:
    named = {n: TV(jnp.asarray(v, jnp.int64)[None], None, T.INT64, None)
             for n, v in cols.items()}
    return Pipe(named, jnp.ones((1,), dtype=jnp.bool_), list(named))


@dataclass(eq=False)
class JoinSizeExec(_JoinSizing):
    """One row that sizes ``join``. Without a packing, each key's range
    (``lo<i>``, ``hi<i>``: JoinExec.key_ranges), which the host packs the
    keys by; with one, the join's ``matches``, the most matches of one
    probe row (``probe_max``) and of one build row (``build_max``):
    K.join_counts, two scatter-adds over a dense key domain. Looked up
    by one key (``lookup``), the two most are that key's and the matches
    the full keys' (``K.lookup_join_ranges``)."""

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        if self.packing is None:
            cols = {}
            for i, (lo, hi) in enumerate(self.join.key_ranges(*child_pipes)):
                cols[f"lo{i}"], cols[f"hi{i}"] = lo, hi
            return _one_row(cols)
        rkey, rok, lkey, lok, domain, codes = self._keys(child_pipes)
        matches, probe_max, build_max = K.join_counts(rkey, rok, lkey, lok,
                                                      domain)
        if codes is not None:
            # the full keys' matches: the looked-up key's, tested
            matches = K.lookup_join_ranges(
                rkey, rok, codes[1], lkey, lok, codes[0],
                domain).counts.astype(jnp.int64).sum()
        return _one_row({"matches": matches, "probe_max": probe_max,
                         "build_max": build_max})

    def node_string(self):
        return f"JoinSize[{self.join.node_string()}]"


@dataclass(eq=False)
class JoinIndexExec(_JoinSizing):
    """The reusable part of ``join``'s sorted build (K.make_join_index)
    over the side ``orient`` builds ('fwd': the right, 'rev': the left):
    its sort permutation ``perm`` and sorted packed key ``skey``, at the
    build side's capacity. The dense tables over the key domain are
    K.dense_join_tables of ``skey``."""

    orient: str = "fwd"

    def trace(self, child_pipes: List[Pipe]) -> Pipe:
        rkey, rok, lkey, lok, _, _ = self._keys(child_pipes)
        bkey, bok = (lkey, lok) if self.orient == "rev" else (rkey, rok)
        perm, skey, _, _ = K.make_join_index(bkey, bok, None)
        return Pipe({"perm": TV(perm, None, T.INT32, None),
                     "skey": TV(skey, None, T.INT64, None)},
                    jnp.ones((perm.shape[0],), dtype=jnp.bool_),
                    ["perm", "skey"])

    def node_string(self):
        return f"JoinIndex[{self.orient}, {self.join.node_string()}]"

    def plan_key(self):
        return super().plan_key() + (self.orient,)


def append_unmatched_left(cols, pair_ok, order, lpipe, matched):
    """Append left rows with no (condition-passing) match; right side NULL.

    Shared by the single-device JoinExec and the mesh JoinApplyExec
    (reference contract: joins/ShuffledHashJoinExec.scala:38 fullOuterJoin
    buildSideOrFullOuterJoin — unmatched stream rows padded with nulls).
    """
    lcap = lpipe.capacity
    n_l = len(lpipe.order)
    extra_mask = lpipe.mask & ~matched
    new_cols: Dict[str, TV] = {}
    for i, name in enumerate(order):
        tv = cols[name]
        if i < n_l:
            src = lpipe.cols[lpipe.order[i]]
            data = jnp.concatenate([tv.data, src.data])
            validity = None
            if tv.validity is not None or src.validity is not None:
                validity = jnp.concatenate([
                    tv.valid_or_true(tv.data.shape[0]),
                    src.valid_or_true(lcap)])
        else:
            data = jnp.concatenate(
                [tv.data, jnp.zeros((lcap,), dtype=tv.data.dtype)])
            validity = jnp.concatenate([
                tv.valid_or_true(tv.data.shape[0]),
                jnp.zeros((lcap,), dtype=jnp.bool_)])
        new_cols[name] = TV(data, validity, tv.dtype, tv.dictionary)
    mask = jnp.concatenate([pair_ok, extra_mask])
    return new_cols, mask, order, int(mask.shape[0])


def append_unmatched_right(cols, pair_ok, order, lpipe, rpipe, matched_b):
    """Append right rows with no (condition-passing) match; left side NULL."""
    rcap = rpipe.capacity
    n_l = len(lpipe.order)
    extra_mask = rpipe.mask & ~matched_b
    new_cols: Dict[str, TV] = {}
    cur_cap = cols[order[0]].data.shape[0]
    for i, name in enumerate(order):
        tv = cols[name]
        if i < n_l:
            data = jnp.concatenate(
                [tv.data, jnp.zeros((rcap,), dtype=tv.data.dtype)])
            validity = jnp.concatenate([
                tv.valid_or_true(cur_cap),
                jnp.zeros((rcap,), dtype=jnp.bool_)])
        else:
            src = rpipe.cols[rpipe.order[i - n_l]]
            data = jnp.concatenate([tv.data, src.data])
            validity = None
            if tv.validity is not None or src.validity is not None:
                validity = jnp.concatenate([
                    tv.valid_or_true(cur_cap), src.valid_or_true(rcap)])
        new_cols[name] = TV(data, validity, tv.dtype, tv.dictionary)
    mask = jnp.concatenate([pair_ok, extra_mask])
    return new_cols, mask, order, int(mask.shape[0])
