"""File scan source + pushdown translation.

Role of the reference's FileSourceScanExec + format readers (reference:
sql/core/.../execution/DataSourceScanExec.scala:506,
datasources/parquet/VectorizedParquetRecordReader.java:1,
FileSourceStrategy.scala:1). The TPU build replaces the JVM vectorized
decoders with pyarrow.dataset (multi-file scans, hive partition
discovery, column projection, predicate-based file/row-group pruning and
exact row filtering), then ships Arrow columns to device HBM through
columnar/arrow.from_arrow.

Pushdown surface (DSv2 SupportsPushDownFilters/RequiredColumns analogue):
the optimizer calls ``translate_filters`` to split a predicate into a
pyarrow dataset expression (pushed — pruned at the file/row-group level
AND applied exactly by the scan) and a residual kept in the plan.
"""

from __future__ import annotations

import datetime
from typing import Any, Dict, List, Optional, Sequence, Tuple

import pyarrow as pa
import pyarrow.dataset as pads

from spark_tpu import types as T
from spark_tpu.columnar.batch import Batch
from spark_tpu.expr import expressions as E
from spark_tpu.types import Field, Schema


def _pa_schema_from_schema(schema: Schema) -> pa.Schema:
    from spark_tpu.columnar.arrow import dtype_to_arrow_type

    return pa.schema([
        pa.field(f.name, dtype_to_arrow_type(f.dtype), nullable=f.nullable)
        for f in schema.fields
    ])


def _schema_from_pa(pa_schema: pa.Schema) -> Schema:
    from spark_tpu.columnar.arrow import arrow_type_to_dtype

    return Schema(tuple(
        Field(f.name, arrow_type_to_dtype(f.type), nullable=f.nullable)
        for f in pa_schema
    ))


# ---- predicate translation --------------------------------------------------


class _Untranslatable(Exception):
    pass


def _literal_value(e: E.Expression):
    if isinstance(e, E.Literal):
        return e.value
    raise _Untranslatable


def _coerce_literal(v, col_name: str, dtypes):
    """Adapt a python literal to the column's storage type for pyarrow:
    a float literal against a DECIMAL column must become a Decimal
    scalar (arrow refuses decimal-vs-double comparisons: 'Precision is
    not great enough'). str(float) round-trips the short literals SQL
    texts contain, so 0.05 means exactly 0.05."""
    if dtypes is None or not isinstance(v, (int, float)):
        return v
    dt = dtypes.get(col_name)
    if isinstance(dt, T.DecimalType):
        import decimal

        return decimal.Decimal(str(v))
    return v


def _translate(e: E.Expression, dtypes=None) -> "pads.Expression":
    """Our Expression -> pyarrow.dataset Expression; raises
    _Untranslatable for anything the scan layer cannot evaluate.
    ``dtypes`` ({col: DataType}, optional) enables storage-aware literal
    coercion at actual read time."""
    import pyarrow.compute as pc

    if isinstance(e, E.Cmp):
        if isinstance(e.left, E.Col):
            name, v, op = e.left.col_name, _literal_value(e.right), e.op
        elif isinstance(e.right, E.Col):
            flip = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}
            name, v = e.right.col_name, _literal_value(e.left)
            op = flip.get(e.op, e.op)
        else:
            raise _Untranslatable
        if v is None:
            raise _Untranslatable
        f = pc.field(name)
        v = _coerce_literal(v, name, dtypes)
        return {"==": f == v, "!=": f != v, "<": f < v,
                "<=": f <= v, ">": f > v, ">=": f >= v}[op]
    if isinstance(e, E.In) and isinstance(e.child, E.Col):
        if any(v is None for v in e.values):
            raise _Untranslatable
        vals = [_coerce_literal(v, e.child.col_name, dtypes)
                for v in e.values]
        return pc.field(e.child.col_name).isin(vals)
    if isinstance(e, E.IsNull) and isinstance(e.child, E.Col):
        return pc.field(e.child.col_name).is_null()
    if isinstance(e, E.Not):
        inner = e.child
        if isinstance(inner, E.IsNull) and isinstance(inner.child, E.Col):
            return ~pc.field(inner.child.col_name).is_null()
        return ~_translate(inner, dtypes)
    if isinstance(e, E.And):
        return _translate(e.left, dtypes) & _translate(e.right, dtypes)
    if isinstance(e, E.Or):
        return _translate(e.left, dtypes) | _translate(e.right, dtypes)
    raise _Untranslatable


def translate_filters(
    conjuncts: Sequence[E.Expression],
) -> Tuple[List[E.Expression], List[E.Expression]]:
    """Split conjuncts into (pushable, residual). A conjunct is pushable
    when ``_translate`` fully understands it."""
    pushed: List[E.Expression] = []
    residual: List[E.Expression] = []
    for c in conjuncts:
        try:
            _translate(c)
            pushed.append(c)
        except _Untranslatable:
            residual.append(c)
    return pushed, residual


def _selects_a_category(filters: Tuple[E.Expression, ...]) -> bool:
    """Does a pushed conjunct keep the rows of some string values
    (``col = 'v'``, ``col IN ('v', ...)``): a market segment, a region,
    a brand? How many rows that keeps is the data's to say, a little
    different for every value and every load of the table, so the scan
    takes ``relative_bucket``'s capacity and the programs compiled for
    one value or load are found again for the next (ROADMAP N1)."""
    for f in filters:
        if isinstance(f, E.Cmp) and f.op == "==":
            lits = [s for s in (f.left, f.right) if isinstance(s, E.Literal)]
            if len(lits) == 1 and isinstance(lits[0].value, str):
                return True
        if isinstance(f, E.In) and f.values \
                and all(isinstance(v, str) for v in f.values):
            return True
    return False


def _filters_to_pads(
    filters: Tuple[E.Expression, ...],
    dtypes=None,
) -> Optional["pads.Expression"]:
    if not filters:
        return None
    out = _translate(filters[0], dtypes)
    for c in filters[1:]:
        out = out & _translate(c, dtypes)
    return out


# ---- the source -------------------------------------------------------------


class FileSource:
    """A lazily-opened multi-file scan (one table = one source).

    ``fmt`` is 'parquet' | 'csv' | 'json'. Hive-style partition
    directories are auto-discovered for parquet (partition columns become
    ordinary columns and participate in predicate pushdown = partition
    pruning, reference: PartitioningUtils.scala / PartitioningAwareFileIndex).
    """

    def __init__(self, fmt: str, paths: Sequence[str],
                 schema: Optional[Schema] = None,
                 options: Optional[Dict[str, Any]] = None):
        self.fmt = fmt
        self.paths = list(paths)
        self._schema = schema
        self.options = dict(options or {})
        self._dataset: Optional[pads.Dataset] = None
        self._cache: Dict[tuple, Batch] = {}
        self._count_cache: Dict[tuple, int] = {}
        #: per-(columns, filters) materialization counts, driving
        #: auto-cache promotion into the session MemoryStore
        self._read_counts: Dict[tuple, int] = {}

    # -- dataset / schema ----------------------------------------------------

    def _fingerprint(self) -> tuple:
        """Freshness token over the underlying files ((path, mtime_ns,
        size) tuples) so a re-read after a rewrite never serves stale
        cached batches, and the memoized pyarrow dataset (which pins its
        discovered file list) is rebuilt (round-2 advisor finding).
        The walk itself is shared with the serve result cache and the
        materialized-view delta detector (io/fingerprint.py) so all
        three invalidate identically."""
        from spark_tpu.io.fingerprint import stat_paths

        return stat_paths(self.paths)

    def _broadcast_change(self) -> None:
        """A rewrite/append was just DETECTED on this source: append a
        ``source_changed`` record to the active session's fleet
        invalidation log (if one exists) so every replica's TTL'd
        fingerprint probe and cached results for these paths drop now
        instead of waiting out the TTL. Strictly best-effort — reads
        never depend on it."""
        try:
            from spark_tpu.api.session import SparkSession

            sess = SparkSession.getActiveSession()
            log = getattr(sess, "serve_invalidation_log", None) \
                if sess is not None else None
            if log is not None:
                log.append("source_changed", self.paths)
        except Exception:
            pass

    def _open(self) -> pads.Dataset:
        fp = self._fingerprint()
        if getattr(self, "_fp", None) != fp:
            # underlying files changed: drop dataset + batch/count caches
            # (store entries key on the fingerprint, so they simply
            # stop matching and age out LRU)
            first = not hasattr(self, "_fp")
            self._dataset = None
            self._cache.clear()
            self._count_cache.clear()
            self._read_counts.clear()
            self._fp = fp
            if not first:
                self._broadcast_change()
        if self._dataset is not None:
            return self._dataset
        kwargs: Dict[str, Any] = {}
        if self.fmt == "parquet":
            kwargs["format"] = "parquet"
            kwargs["partitioning"] = "hive"
        elif self.fmt == "csv":
            import pyarrow.csv as pacsv

            header = str(self.options.get("header", "true")).lower() == "true"
            delim = self.options.get("sep", self.options.get("delimiter", ","))
            read_opts = {}
            if not header:
                if self._schema is not None:
                    # real names up front so projection/predicate pushdown
                    # and column_types see the declared schema
                    read_opts["column_names"] = list(self._schema.names)
                else:
                    read_opts["autogenerate_column_names"] = True
            parse_opts = pacsv.ParseOptions(delimiter=delim)
            convert = {}
            if self._schema is not None:
                convert["column_types"] = {
                    f.name: _pa_schema_from_schema(
                        Schema((f,)))[0].type
                    for f in self._schema.fields}
            fmt = pads.CsvFileFormat(
                parse_options=parse_opts,
                read_options=pacsv.ReadOptions(**read_opts),
                convert_options=pacsv.ConvertOptions(**convert)
                if convert else None)
            kwargs["format"] = fmt
            if str(self.options.get("partitioning", "")) == "hive":
                kwargs["partitioning"] = "hive"
        elif self.fmt == "json":
            kwargs["format"] = "json"
            if str(self.options.get("partitioning", "")) == "hive":
                kwargs["partitioning"] = "hive"
        elif self.fmt == "orc":
            # pyarrow's C++ ORC reader — the vectorized-decoder tier the
            # reference reaches via Java ORC (OrcColumnarBatchReader)
            kwargs["format"] = "orc"
            kwargs["partitioning"] = "hive"
        else:
            raise ValueError(f"unsupported format {self.fmt!r}")
        if self._schema is not None and self.fmt == "parquet":
            kwargs["schema"] = _pa_schema_from_schema(self._schema)
        # pyarrow accepts a directory only as a scalar path, not in a list
        src = self.paths[0] if len(self.paths) == 1 else self.paths
        self._dataset = pads.dataset(src, **kwargs)
        return self._dataset

    @property
    def schema(self) -> Schema:
        if self._schema is None:
            self._schema = _schema_from_pa(self._open().schema)
        return self._schema

    def _dtypes(self) -> Dict[str, Any]:
        """{column: engine DataType} for storage-aware literal coercion
        in pushed filters (decimal columns vs float literals)."""
        return {f.name: f.dtype for f in self.schema.fields}

    # -- scanning ------------------------------------------------------------

    def _session_store(self):
        """(MemoryStore, auto-cache threshold) of the active session;
        (None, 0) outside a session or with auto-caching disabled."""
        from spark_tpu.api.session import SparkSession

        sess = SparkSession.getActiveSession()
        store = getattr(sess, "memory_store", None) if sess else None
        if store is None:
            return None, 0
        from spark_tpu import conf as CF

        try:
            thr = int(sess.conf.get(CF.STORAGE_AUTOCACHE_THRESHOLD))
        except Exception:
            thr = 0
        return (store, thr) if thr > 0 else (None, 0)

    def _store_key(self, key) -> tuple:
        # fingerprint in the key: a rewritten file misses naturally and
        # the stale entry ages out LRU
        return ("scan", self.fmt, tuple(self.paths), self._fp, key)

    def read(self, columns: Optional[Tuple[str, ...]] = None,
             filters: Tuple[E.Expression, ...] = ()) -> Batch:
        """Materialize the scan to a device Batch, reading only
        ``columns`` and pruning/filtering by ``filters`` (exact).

        Hot scans are auto-cached: once the same (columns, filters)
        projection has materialized ``spark.tpu.storage.autoCacheThreshold``
        times, its device batch is promoted into the session's
        HBM-resident MemoryStore (byte-accounted, LRU-evictable, pinned
        while the running query reads it), and repeat queries skip
        parquet decode + dictionary encode + host->device transfer."""
        import time as _time

        from spark_tpu import metrics
        from spark_tpu.columnar.arrow import from_arrow
        from spark_tpu.physical.kernels import relative_bucket

        ds = self._open()  # first: freshness check may clear the cache
        key = (columns, tuple(E.expr_key(f) for f in filters))
        self._read_counts[key] = self._read_counts.get(key, 0) + 1
        store, threshold = self._session_store()
        skey = self._store_key(key) if store is not None else None
        if store is not None:
            hit = store.get(skey, pin=True)
            if hit is not None:
                return hit
        # auto-cache promotion is optional work: under fleet brownout
        # the scan still serves (and store hits above still hit), it
        # just stops PROMOTING new entries into HBM
        hot = (store is not None
               and self._read_counts[key] >= threshold
               and metrics.brownout_level() == 0)
        hit = self._cache.get(key)
        if hit is not None:
            self._cache[key] = self._cache.pop(key)  # LRU touch
            if hot and store.put(skey, hit, pin=True):
                self._cache.pop(key, None)  # now owned by the store
            return hit
        t0 = _time.perf_counter()
        table = ds.to_table(
            columns=list(columns) if columns is not None else None,
            filter=_filters_to_pads(filters, self._dtypes()))
        t1 = _time.perf_counter()
        # dict-encode + host->device transfer; an int64-backed column
        # whose values fit int32 is RESIDENT as int32 (what the column
        # holds decides, once per scan: the batch is cached below) and
        # every stage widens it at trace entry (Pipe.from_batch_data)
        batch = from_arrow(table, narrow_transfer=True,
                           capacity=(relative_bucket(table.num_rows)
                                     if _selects_a_category(filters)
                                     else None))
        # wait for the transfer, or transfer_ms is an enqueue time
        batch.block_until_ready()
        t2 = _time.perf_counter()
        metrics.record("scan", fmt=self.fmt, rows=table.num_rows,
                       decode_ms=round((t1 - t0) * 1e3, 2),
                       transfer_ms=round((t2 - t1) * 1e3, 2),
                       narrowed=batch.narrowed(),
                       resident_bytes=batch.device_nbytes())
        if hot and store.put(skey, batch, pin=True):
            return batch
        # bounded LRU: parameterized pushed filters must not pin an
        # unbounded number of device-resident batches
        while len(self._cache) >= 4:
            self._cache.pop(next(iter(self._cache)))
        self._cache[key] = batch
        return batch

    def count_rows(self, filters: Tuple[E.Expression, ...] = ()) -> int:
        """Row count without materializing (drives the out-of-HBM
        chunking decision). Memoized per filter set — the decision runs
        on every execution of an aggregate-over-scan query."""
        ds = self._open()  # freshness check may clear the count cache
        key = tuple(E.expr_key(f) for f in filters)
        hit = self._count_cache.get(key)
        if hit is None:
            hit = ds.count_rows(
                filter=_filters_to_pads(filters, self._dtypes()))
            self._count_cache[key] = hit
        return hit

    def iter_batches(self, columns: Optional[Tuple[str, ...]] = None,
                     filters: Tuple[E.Expression, ...] = (),
                     rows_per_chunk: int = 1 << 20):
        """Stream the scan as bounded arrow tables WITHOUT materializing
        the whole dataset — host RAM is the staging tier for
        larger-than-HBM execution (reference spill analogue:
        ExternalSorter.scala:93; here the data never needed to be
        device-resident in the first place)."""
        import pyarrow as pa

        ds = self._open()
        pending: list = []
        n = 0
        for rb in ds.to_batches(
                columns=list(columns) if columns is not None else None,
                filter=_filters_to_pads(filters, self._dtypes()),
                batch_size=rows_per_chunk):
            if rb.num_rows == 0:
                continue
            pending.append(rb)
            n += rb.num_rows
            while n >= rows_per_chunk:
                # emit EXACTLY rows_per_chunk rows (remainder carries
                # over): every chunk then pads to ONE static capacity,
                # so the whole stream reuses a single compiled program
                # — varying chunk sizes meant a fresh XLA compile per
                # chunk (~minutes each on TPU at SF100)
                tbl = pa.Table.from_batches(pending)
                yield tbl.slice(0, rows_per_chunk)
                rest = tbl.slice(rows_per_chunk)
                pending = rest.to_batches() if rest.num_rows else []
                n = rest.num_rows
        if pending:
            yield pa.Table.from_batches(pending)

    def __repr__(self):
        return f"{self.fmt}:{','.join(self.paths)}"
