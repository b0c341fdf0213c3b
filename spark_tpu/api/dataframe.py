"""DataFrame: lazy logical-plan builder + actions (reference:
sql/core/src/main/scala/org/apache/spark/sql/Dataset.scala — collect:3432
withAction:4173; python surface python/pyspark/sql/dataframe.py).

A DataFrame is (session, logical plan). Transformations build new plans;
actions run optimize -> physical plan -> stage-fused execution
(QueryExecution.scala:55 pipeline analogue, see physical/planner.py).
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple, Union

from spark_tpu import trace
from spark_tpu.api.row import Row
from spark_tpu.columnar.batch import Batch
from spark_tpu.expr import expressions as E
from spark_tpu.plan import logical as L
from spark_tpu.types import Schema

ColumnOrName = Union[E.Expression, str]


def _c(c: ColumnOrName) -> E.Expression:
    return c if isinstance(c, E.Expression) else E.Col(c)


def _order(c: ColumnOrName) -> E.SortOrder:
    e = _c(c)
    if isinstance(e, E.SortOrder):
        return e
    return E.SortOrder(e, ascending=True)


class DataFrame:
    def __init__(self, session, plan: L.LogicalPlan):
        self._session = session
        self._plan = plan

    # ---- metadata ----------------------------------------------------------

    @property
    def schema(self) -> Schema:
        return self._plan.schema

    @property
    def columns(self) -> List[str]:
        return list(self._plan.schema.names)

    @property
    def sparkSession(self):
        return self._session

    def explain(self, extended: bool = False,
                mode: Optional[str] = None) -> None:
        from spark_tpu.plan.optimizer import optimize
        from spark_tpu.physical.planner import plan_physical

        if mode == "lint" or extended == "lint":
            # static plan analysis without executing (reference:
            # Dataset.explain(mode) ExplainMode, Dataset.scala:590 —
            # "lint" is this engine's extra mode)
            from spark_tpu import analysis

            conf = self._session.conf if self._session is not None \
                else None
            print(analysis.analyze(self._plan, conf).format())
            return
        print("== Logical Plan ==")
        print(self._plan.tree_string())
        opt = optimize(self._plan)
        if extended:
            print("== Optimized Logical Plan ==")
            print(opt.tree_string())
        print("== Physical Plan ==")
        print(plan_physical(opt).tree_string())

    def _with(self, plan: L.LogicalPlan) -> "DataFrame":
        return DataFrame(self._session, plan)

    # ---- transformations ---------------------------------------------------

    def select(self, *cols: ColumnOrName) -> "DataFrame":
        if not cols:
            cols = tuple(self.columns)
        exprs: List[E.Expression] = []
        for c in cols:
            if isinstance(c, str) and c == "*":
                exprs.extend(E.Col(n) for n in self.columns)
            else:
                exprs.append(_c(c))
        return self._with(L.project_with_windows(tuple(exprs), self._plan))

    def selectExpr(self, *exprs: str) -> "DataFrame":
        from spark_tpu.sql.parser import parse_projection

        parsed = [parse_projection(s, self._plan.schema) for s in exprs]
        return self._with(L.project_with_windows(tuple(parsed), self._plan))

    def filter(self, condition: Union[E.Expression, str]) -> "DataFrame":
        if isinstance(condition, str):
            from spark_tpu.sql.parser import parse_expression

            condition = parse_expression(condition)
        return self._with(L.Filter(condition, self._plan))

    where = filter

    def withColumn(self, name: str, col: E.Expression) -> "DataFrame":
        exprs = []
        replaced = False
        for n in self.columns:
            if n == name:
                exprs.append(E.Alias(col, name))
                replaced = True
            else:
                exprs.append(E.Col(n))
        if not replaced:
            exprs.append(E.Alias(col, name))
        return self._with(L.project_with_windows(tuple(exprs), self._plan))

    def withColumnRenamed(self, old: str, new: str) -> "DataFrame":
        exprs = tuple(
            E.Alias(E.Col(n), new) if n == old else E.Col(n)
            for n in self.columns)
        return self._with(L.Project(exprs, self._plan))

    def drop(self, *names: str) -> "DataFrame":
        drop = set(names)
        exprs = tuple(E.Col(n) for n in self.columns if n not in drop)
        return self._with(L.Project(exprs, self._plan))

    def alias(self, name: str) -> "DataFrame":
        return self._with(L.SubqueryAlias(name, self._plan))

    def distinct(self) -> "DataFrame":
        return self._with(L.Distinct(self._plan))

    def dropDuplicates(self, subset: Optional[Sequence[str]] = None) -> "DataFrame":
        if subset is None:
            return self.distinct()
        keys = tuple(E.Col(n) for n in subset)
        outs = tuple(
            E.Col(n) if n in set(subset) else E.Alias(E.First(E.Col(n)), n)
            for n in self.columns)
        return self._with(L.Aggregate(keys, outs, self._plan))

    drop_duplicates = dropDuplicates

    def limit(self, n: int) -> "DataFrame":
        return self._with(L.Limit(n, self._plan))

    def offset(self, n: int) -> "DataFrame":
        return self._with(L.Limit(1 << 62, self._plan, offset=n))

    def sort(self, *cols: ColumnOrName, ascending=None) -> "DataFrame":
        orders = [_order(c) for c in cols]
        if ascending is not None:
            flags = ([ascending] * len(orders)
                     if isinstance(ascending, bool) else list(ascending))
            orders = [
                E.SortOrder(o.child, asc, o.nulls_first)
                for o, asc in zip(orders, flags)
            ]
        return self._with(L.Sort(tuple(orders), self._plan))

    orderBy = sort

    def union(self, other: "DataFrame") -> "DataFrame":
        return self._with(L.Union(self._plan, other._plan))

    unionAll = union

    def unionByName(self, other: "DataFrame") -> "DataFrame":
        reordered = other.select(*[E.Col(n) for n in self.columns])
        return self._with(L.Union(self._plan, reordered._plan))

    def sample(self, fraction: float, seed: int = 42) -> "DataFrame":
        return self._with(L.Sample(fraction, seed, self._plan))

    def repartition(self, num_partitions: int, *cols: ColumnOrName) -> "DataFrame":
        return self._with(L.Repartition(
            num_partitions, tuple(_c(c) for c in cols), self._plan))

    def coalesce(self, num_partitions: int) -> "DataFrame":
        return self._with(L.Repartition(num_partitions, (), self._plan))

    def join(self, other: "DataFrame", on=None, how: str = "inner") -> "DataFrame":
        how = {"outer": "full", "full_outer": "full", "fullouter": "full",
               "leftouter": "left", "left_outer": "left",
               "rightouter": "right", "right_outer": "right",
               "semi": "left_semi", "leftsemi": "left_semi",
               "anti": "left_anti", "leftanti": "left_anti"}.get(how, how)
        if how not in L.JOIN_TYPES:
            raise ValueError(f"unsupported join type {how!r}")
        if on is None:
            return self._with(L.Join(self._plan, other._plan, "cross", (), ()))
        if isinstance(on, str):
            on = [on]
        if isinstance(on, (list, tuple)) and on and isinstance(on[0], str):
            lkeys = tuple(E.Col(n) for n in on)
            rkeys = tuple(E.Col(n) for n in on)
            joined = L.Join(self._plan, other._plan, how, lkeys, rkeys)
            if how in ("left_semi", "left_anti"):
                return self._with(joined)
            # name-based join keeps ONE copy of the join columns (Spark
            # semantics, Dataset.join(usingColumns)); the right-side copy
            # appears as 'name#2' after the Join.schema dedup
            on_set = set(on)
            right_start = len(self._plan.schema.names)
            joined_names = list(joined.schema.names)
            # the right copy of join column `k` is `k` or `k#2` post-dedup
            right_copy = {}
            for i, n in enumerate(joined_names):
                base = n[:-2] if n.endswith("#2") else n
                if i >= right_start and base in on_set:
                    right_copy[base] = n
            keep = []
            for i, n in enumerate(joined_names):
                if i >= right_start and n in right_copy.values():
                    continue
                if i < right_start and n in on_set and how in ("right", "full"):
                    # usingColumns full outer merges the key columns
                    keep.append(E.Alias(
                        E.Coalesce((E.Col(n), E.Col(right_copy[n]))), n))
                else:
                    keep.append(E.Col(n))
            return self._with(L.Project(tuple(keep), joined))
        # Column expression: extract equi conjuncts
        cond = on
        lnames = set(self._plan.schema.names)
        rnames = set(other._plan.schema.names)
        lkeys_l: List[E.Expression] = []
        rkeys_l: List[E.Expression] = []
        residual: List[E.Expression] = []
        from spark_tpu.plan.optimizer import split_conjuncts, combine_conjuncts

        for c in split_conjuncts(cond):
            if isinstance(c, E.Cmp) and c.op == "==":
                lr, rr = c.left.references(), c.right.references()
                if lr <= lnames and rr <= rnames:
                    lkeys_l.append(c.left)
                    rkeys_l.append(c.right)
                    continue
                if lr <= rnames and rr <= lnames:
                    lkeys_l.append(c.right)
                    rkeys_l.append(c.left)
                    continue
            residual.append(c)
        res = combine_conjuncts(residual) if residual else None
        if not lkeys_l and how == "inner":
            return self._with(L.Join(self._plan, other._plan, "cross", (), (),
                                     condition=res))
        return self._with(L.Join(self._plan, other._plan, how,
                                 tuple(lkeys_l), tuple(rkeys_l), res))

    def crossJoin(self, other: "DataFrame") -> "DataFrame":
        return self._with(L.Join(self._plan, other._plan, "cross", (), ()))

    def groupBy(self, *cols: ColumnOrName) -> "GroupedData":
        return GroupedData(self, tuple(_c(c) for c in cols))

    groupby = groupBy

    def rollup(self, *cols: ColumnOrName) -> "GroupedData":
        """Hierarchical subtotals (reference: Dataset.rollup ->
        ResolveGroupingAnalytics/ExpandExec)."""
        return GroupedData(self, tuple(_c(c) for c in cols), "rollup")

    def cube(self, *cols: ColumnOrName) -> "GroupedData":
        """All subtotal combinations (reference: Dataset.cube)."""
        return GroupedData(self, tuple(_c(c) for c in cols), "cube")

    def agg(self, *exprs: E.Expression) -> "DataFrame":
        return self.groupBy().agg(*exprs)

    def __getitem__(self, item):
        if isinstance(item, str):
            return E.Col(item)
        if isinstance(item, E.Expression):
            return self.filter(item)
        raise TypeError(item)

    def __getattr__(self, name: str):
        if name.startswith("_"):
            raise AttributeError(name)
        if name in self._plan.schema:
            return E.Col(name)
        raise AttributeError(name)

    # ---- actions -----------------------------------------------------------

    def _execute(self, materialize=None):
        """Run the plan to a device Batch; with ``materialize`` (a
        callable of the Batch: rows, pandas, arrow) return what it
        makes of the batch instead, from inside the root span — the
        fetch is the one place the host waits for the device, so
        ``query.execute`` covers the query and not only its enqueue."""
        from spark_tpu import deadline, recovery

        # root span when standalone; child when a connect server /
        # scheduler ticket already carries a trace for this query.
        # same shape for resilience context: an ambient deadline /
        # retry budget (scheduler ticket, connect request) is kept;
        # standalone, a default deadline is minted from
        # spark.tpu.deadline.defaultTimeoutS and a fresh per-query
        # retry budget is bound so every retry seam below draws from
        # ONE pool instead of multiplying per-layer caps
        conf = self._session.conf if self._session is not None else None
        # the span is outermost: what the wrappers cost is the root's
        # own time
        with trace.span("query.execute",
                        plan=type(self._plan).__name__), \
                deadline.bind_default(conf), \
                recovery.bind_default_budget(conf):
            batch = self._execute_traced()
            return batch if materialize is None else materialize(batch)

    def _execute_traced(self):
        from spark_tpu import metrics

        if self._session is not None:
            self._session._ensure_active()
            # submit-time static analysis gate: no-op at the default
            # level=off; raises PlanAnalysisError at level=error when
            # the plan carries error-level diagnostics
            from spark_tpu.analysis import maybe_gate

            with trace.span("query.analysis"):
                maybe_gate(self._plan, self._session.conf)
        metrics.query_start(self._plan.node_string())
        from spark_tpu.physical.planner import execute_logical_on

        def run(plan, optimize=True):
            # no session: the one-chip planner
            return execute_logical_on(self._session, plan, optimize)

        def run_full(plan):
            """Engine run with the out-of-HBM chunking decision applied
            — also used to materialize cached plans so a cached big
            aggregate chunks instead of OOMing. Whole-batch OOM degrades
            through the chunked tier at a halved device budget
            (recovery.run_plan_with_oom_degradation) instead of
            failing."""
            if self._session is None:
                return run(plan)
            from spark_tpu.plan.optimizer import optimize as opt
            from spark_tpu.recovery import run_plan_with_oom_degradation

            with trace.span("query.optimize"):
                lp = opt(plan)
            svc = self._session.compile_service
            if svc is not None:
                # compile-service routing: with background compile on,
                # serve through the chunked tier while the fused
                # executable compiles off-thread (byte-identical
                # either way)
                return svc.execute_plan(
                    lp, self._session.conf,
                    lambda p: run(p, optimize=False))
            return run_plan_with_oom_degradation(
                lp, self._session.conf,
                lambda p: run(p, optimize=False))

        plan = self._plan
        if self._session is not None:
            from spark_tpu.recovery import run_stage_with_recovery
            from spark_tpu.scheduler import admission
            from spark_tpu.storage import pin_scope

            svc = self._session.compile_service
            if svc is not None:
                # journal the served plan (+ SQL text when this frame
                # came from session.sql) for the pre-warm replay
                svc.note_served(self._plan,
                                sql=getattr(self, "_sql_text", None))

            # pin_scope: every MemoryStore entry this query reads
            # (cached plans, auto-cached scans) is held against
            # eviction until the query finishes
            with trace.span("storage.pin"), pin_scope():
                with trace.span("mview.probe"):
                    plan = self._session.cache_manager.apply(
                        plan, run_full)
                # lineage recompute on transient environment failure
                # (reference: DAGScheduler.scala:1762 stage resubmission)
                out = run_stage_with_recovery(
                    lambda: run_full(plan), conf=self._session.conf,
                    label=type(self._plan).__name__)
                # the next admission of this query (submit_query
                # estimates the RAW plan) uses measured, not static, bytes
                admission.note_query_peak(self._plan, "raw")
                return out
        return run_full(plan)

    def collect(self) -> List[Row]:
        return self._execute(_collect_rows)

    @property
    def isStreaming(self) -> bool:
        from spark_tpu.streaming.execution import StreamingSource

        return bool(L.collect_nodes(self._plan, StreamingSource))

    @property
    def writeStream(self):
        from spark_tpu.streaming.readwriter import DataStreamWriter

        return DataStreamWriter(self)

    def withWatermark(self, col_name: str, delay) -> "DataFrame":
        from spark_tpu.streaming.readwriter import with_watermark

        return with_watermark(self, col_name, delay)

    def toPandas(self):
        return self._execute(Batch.to_pandas)

    @property
    def na(self):
        """Null handling (reference: DataFrameNaFunctions.scala)."""
        from spark_tpu.api.na_stat import DataFrameNaFunctions

        return DataFrameNaFunctions(self)

    @property
    def stat(self):
        """Statistics (reference: DataFrameStatFunctions.scala)."""
        from spark_tpu.api.na_stat import DataFrameStatFunctions

        return DataFrameStatFunctions(self)

    def dropna(self, how: str = "any", thresh=None, subset=None):
        return self.na.drop(how, thresh, subset)

    def fillna(self, value, subset=None):
        return self.na.fill(value, subset)

    def replace(self, to_replace, value=None, subset=None):
        return self.na.replace(to_replace, value, subset)

    def describe(self, *cols: str):
        from spark_tpu.api.na_stat import describe

        return describe(self, list(cols) or None)

    summary = describe

    def corr(self, col1: str, col2: str, method: str = "pearson") -> float:
        return self.stat.corr(col1, col2, method)

    def cov(self, col1: str, col2: str) -> float:
        return self.stat.cov(col1, col2)

    def approxQuantile(self, col, probabilities, relativeError=0.0):
        return self.stat.approxQuantile(col, probabilities, relativeError)

    def crosstab(self, col1: str, col2: str):
        return self.stat.crosstab(col1, col2)

    def freqItems(self, cols, support: float = 0.01):
        return self.stat.freqItems(cols, support)

    def sampleBy(self, col: str, fractions, seed: int = 42):
        return self.stat.sampleBy(col, fractions, seed)

    @property
    def rdd(self):
        """Bridge to the RDD tier: collected Rows, partitioned over the
        default parallelism (reference: Dataset.rdd — the escape hatch
        out of the columnar engine)."""
        return self._session.sparkContext.parallelize(self.collect())

    def toArrow(self):
        from spark_tpu.columnar.arrow import to_arrow

        return self._execute(to_arrow)

    def count(self) -> int:
        agg = L.Aggregate((), (E.Alias(E.Count(None), "count"),), self._plan)
        rows = self._with(agg)._execute(Batch.to_pylist)
        return int(rows[0]["count"])

    def first(self) -> Optional[Row]:
        rows = self.limit(1).collect()
        return rows[0] if rows else None

    def head(self, n: int = 1):
        rows = self.limit(n).collect()
        return rows[0] if n == 1 and rows else rows

    def take(self, n: int) -> List[Row]:
        return self.limit(n).collect()

    def isEmpty(self) -> bool:
        return len(self.take(1)) == 0

    def show(self, n: int = 20, truncate: bool = True) -> None:
        rows = self.limit(n).collect()
        names = self.columns
        cells = [[_fmt(r[c], truncate) for c in names] for r in rows]
        widths = [
            max(len(str(nm)), *(len(row[i]) for row in cells)) if cells
            else len(str(nm))
            for i, nm in enumerate(names)
        ]
        sep = "+" + "+".join("-" * w for w in widths) + "+"
        print(sep)
        print("|" + "|".join(str(nm).ljust(w)
                             for nm, w in zip(names, widths)) + "|")
        print(sep)
        for row in cells:
            print("|" + "|".join(v.ljust(w) for v, w in zip(row, widths)) + "|")
        print(sep)

    @property
    def write(self):
        from spark_tpu.io.readwriter import DataFrameWriter

        return DataFrameWriter(self)

    def createOrReplaceTempView(self, name: str) -> None:
        self._session.catalog._register_view(name, self._plan)

    def cache(self) -> "DataFrame":
        """Mark this plan cached (lazy — materialized on first use and
        reused by ANY query containing it; reference: CacheManager.scala
        / InMemoryRelation)."""
        if self._session is not None:
            self._session.cache_manager.add(self._plan)
        return self

    persist = cache

    def unpersist(self) -> "DataFrame":
        if self._session is not None:
            self._session.cache_manager.drop(self._plan)
        return self

    def checkpoint(self, eager: bool = True) -> "DataFrame":
        """Durable checkpoint: Parquet under spark.checkpoint.dir,
        lineage truncated (reference: Dataset.checkpoint →
        ReliableCheckpointRDD)."""
        from spark_tpu.recovery import checkpoint_dataframe

        return checkpoint_dataframe(self, eager=eager)

    def localCheckpoint(self, eager: bool = True) -> "DataFrame":
        """In-memory lineage truncation (reference:
        Dataset.localCheckpoint → LocalCheckpointRDD)."""
        df = self.cache()
        if eager:
            df.count()
        return df


def _collect_rows(batch: Batch) -> List[Row]:
    """``collect()``'s host materialisation: one ``query.rows`` span
    from the fetched planes to the Rows."""
    fetched = batch.fetch_host()
    with trace.span("query.rows"):
        return [Row.from_dict(d) for d in batch.rows_from_host(*fetched)]


def _fmt(v, truncate: bool) -> str:
    s = "NULL" if v is None else str(v)
    if truncate and len(s) > 20:
        s = s[:17] + "..."
    return s


class GroupedData:
    """Result of groupBy/rollup/cube (reference:
    sql/core/.../RelationalGroupedDataset.scala)."""

    def __init__(self, df: DataFrame, keys: Tuple[E.Expression, ...],
                 mode: str = "groupby"):
        self._df = df
        self._keys = keys
        self._mode = mode

    def agg(self, *exprs: E.Expression) -> DataFrame:
        outs = tuple(self._keys) + tuple(exprs)
        if self._mode != "groupby":
            from spark_tpu.plan.grouping import (cube_sets,
                                                 grouping_sets_aggregate,
                                                 rollup_sets)

            sets = (rollup_sets(len(self._keys))
                    if self._mode == "rollup"
                    else cube_sets(len(self._keys)))
            plan, _ = grouping_sets_aggregate(
                self._df._plan, self._keys, sets, outs)
            return self._df._with(plan)
        return self._df._with(
            L.Aggregate(self._keys, outs, self._df._plan))

    def _simple(self, fn, cols: Tuple[str, ...]) -> DataFrame:
        names = cols or tuple(
            n for n in self._df.columns
            if self._df.schema.field(n).dtype.is_numeric
            and not any(k.name == n for k in self._keys))
        aggs = tuple(E.Alias(fn(E.Col(n)), f"{fn.__name__.lower()}({n})")
                     for n in names)
        return self.agg(*aggs)

    def sum(self, *cols: str) -> DataFrame:  # noqa: A003
        return self._simple(E.Sum, cols)

    def avg(self, *cols: str) -> DataFrame:
        return self._simple(E.Avg, cols)

    mean = avg

    def min(self, *cols: str) -> DataFrame:  # noqa: A003
        return self._simple(E.Min, cols)

    def max(self, *cols: str) -> DataFrame:  # noqa: A003
        return self._simple(E.Max, cols)

    def applyInPandasWithState(self, func, outputStructType,
                               stateStructType=None,
                               outputMode: str = "append",
                               timeoutConf: str = "NoTimeout") -> DataFrame:
        """Arbitrary stateful per-group streaming transform (reference:
        python/pyspark/sql/pandas/group_ops.py applyInPandasWithState →
        FlatMapGroupsWithStateExec). ``func(key_tuple, pandas_df,
        GroupState) -> pandas_df``; start the returned DataFrame with
        writeStream. ``stateStructType`` accepted for surface parity
        (state is pickled whole). ``timeoutConf='ProcessingTimeTimeout'``
        enables state.setTimeoutDuration(ms): groups whose deadline
        passes with no new data are invoked with an empty frame and
        state.hasTimedOut=True (reference:
        FlatMapGroupsWithStateExec.scala:373 timeout processing)."""
        from spark_tpu.streaming.groups import FlatMapGroupsWithState
        from spark_tpu.types import Schema, parse_ddl_schema

        out_schema = (outputStructType
                      if isinstance(outputStructType, Schema)
                      else parse_ddl_schema(outputStructType))
        key_names = []
        for k in self._keys:
            inner = E.strip_alias(k)
            if not isinstance(inner, E.Col):
                raise NotImplementedError(
                    "applyInPandasWithState keys must be plain columns")
            key_names.append(inner.col_name)
        if timeoutConf not in ("NoTimeout", "ProcessingTimeTimeout"):
            raise NotImplementedError(
                "timeoutConf: NoTimeout | ProcessingTimeTimeout "
                "(event-time timeouts not implemented)")
        node = FlatMapGroupsWithState(
            tuple(key_names), func, out_schema, self._df._plan,
            timeout_conf=timeoutConf)
        return DataFrame(self._df._session, node)

    def count(self) -> DataFrame:
        return self.agg(E.Alias(E.Count(None), "count"))
