"""Device-side columnar batch.

The TPU-native analogue of the reference's ColumnarBatch / ColumnVector
surface (reference: sql/catalyst/src/main/java/org/apache/spark/sql/
vectorized/ColumnarBatch.java:30, ColumnVector.java) and of the Tungsten
row format it replaces (UnsafeRow.java:57).

Design (TPU-first, not a port):

- A batch has a *static* row capacity. Live rows are tracked with a
  boolean ``row_mask`` instead of a dynamic length, so every operator is
  shape-stable under ``jax.jit`` — filters flip mask bits, they never
  compact. This is the static-shape discipline XLA needs; the reference
  has no peer (JVM rows are fully dynamic).
- Per-column nulls are separate boolean validity arrays (Arrow-style),
  `None` meaning "all valid".
- Strings are int32 dictionary codes; the dictionary itself lives on the
  host in the Schema, never on device.

``BatchData`` is a pytree (NamedTuples of arrays) so whole query
pipelines jit end-to-end; ``Schema`` travels on the host beside it.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from spark_tpu import trace
from spark_tpu.types import Field, Schema

# jitted column-packers for single-transfer host fetches, keyed on
# (capacity, per-array kind/dtype signature); a mesh's packers
# (parallel/sharded.py::_mesh_packer) on (mesh, that signature)
_PACKER_CACHE: dict = {}

# one spare thread for overlapping the float-plane fetch with the
# int-plane fetch in Batch.fetch_host (hides one fetch's latency)
import concurrent.futures as _cf

_FETCH_POOL = _cf.ThreadPoolExecutor(max_workers=1)


def pack(ints, flts):
    """The packers' program: an int64 and a float64 plane."""
    iplane = jnp.stack([x.astype(jnp.int64) for x in ints])
    fplane = (jnp.stack([x.astype(jnp.float64) for x in flts])
              if flts else jnp.zeros((0, 0), jnp.float64))
    return iplane, fplane


def _local_packer(sig):
    """The jitted packer of one device's arrays of signature ``sig``."""
    packer = _PACKER_CACHE.get(sig)
    if packer is None:
        import jax

        packer = _PACKER_CACHE[sig] = jax.jit(pack)
    return packer


class ColumnData(NamedTuple):
    """Device arrays for one column: dense values + optional validity."""

    data: jnp.ndarray
    validity: Optional[jnp.ndarray]  # bool[capacity]; None = all valid

    def valid_mask(self, capacity: int) -> jnp.ndarray:
        if self.validity is None:
            return jnp.ones((capacity,), dtype=jnp.bool_)
        return self.validity


class BatchData(NamedTuple):
    """Device half of a batch: column arrays + live-row mask.

    All arrays share the same leading (and only) dimension: the static
    row capacity. ``row_mask[i]`` False means row i does not exist
    (filtered out or padding) — distinct from SQL NULL.
    """

    columns: Tuple[ColumnData, ...]
    row_mask: jnp.ndarray  # bool[capacity]

    @property
    def capacity(self) -> int:
        return int(self.row_mask.shape[0])


class Batch:
    """Host-level pairing of a Schema with BatchData, the unit the
    executor passes between stages. Thin — all compute goes through the
    physical operators, which consume (schema, data) and are jitted."""

    __slots__ = ("schema", "data", "__weakref__")

    def __init__(self, schema: Schema, data: BatchData):
        assert len(schema) == len(data.columns), (
            f"schema arity {len(schema)} != data arity {len(data.columns)}"
        )
        self.schema = schema
        self.data = data

    @property
    def capacity(self) -> int:
        return self.data.capacity

    def num_valid_rows(self) -> int:
        return int(np.asarray(self.data.row_mask).sum())

    def column(self, name: str) -> ColumnData:
        return self.data.columns[self.schema.index(name)]

    def __repr__(self) -> str:
        return f"Batch({self.schema}, capacity={self.capacity})"

    def device_nbytes(self) -> int:
        """Device bytes held by this batch (values + validity + mask) —
        the unit of the out-of-HBM prefetch byte budget."""
        total = self.data.row_mask.size * self.data.row_mask.dtype.itemsize
        for cd in self.data.columns:
            total += cd.data.size * cd.data.dtype.itemsize
            if cd.validity is not None:
                total += cd.validity.size * cd.validity.dtype.itemsize
        return int(total)

    def narrowed(self) -> int:
        """How many columns the device holds narrower than their schema
        dtype (``from_numpy``'s ``narrow_transfer``)."""
        return sum(
            cd.data.ndim == 1
            and cd.data.dtype.itemsize < np.dtype(f.dtype.np_dtype).itemsize
            for f, cd in zip(self.schema.fields, self.data.columns))

    def block_until_ready(self) -> "Batch":
        """Wait for all pending host->device transfers of this batch's
        arrays. The pipeline producer calls this so a chunk's transfer
        completes on the PRODUCER thread (overlapped with the consumer's
        device compute) instead of lazily serializing into the
        consumer's next dispatch."""
        try:
            self.data.row_mask.block_until_ready()
            for cd in self.data.columns:
                cd.data.block_until_ready()
                if cd.validity is not None:
                    cd.validity.block_until_ready()
        except AttributeError:
            pass  # non-jax arrays (tests) have no block_until_ready
        except RuntimeError as e:
            # a deleted buffer is benign (chunk already consumed); any
            # other RuntimeError is a real transfer/allocation failure
            # and must surface here, on the producer thread
            if "deleted" not in str(e).lower():
                raise
        return self

    # ---- host materialization -------------------------------------------

    def fetch_host(self):
        """Move the WHOLE batch to host in one device->host transfer.

        Returns (mask: np.bool_[cap], [(data, validity|None)] per column,
        numpy). Per-array fetches pay one device->host round trip EACH,
        so an 8-column result costs 8 of them. Here a tiny jitted packer
        casts every column (+mask/validity) into at most two (k,
        capacity) planes, each fetched with a single transfer, then
        host-side views restore the dtypes. (On a directly attached
        v5e a result of one integer plane costs 0.34-0.43 ms of
        ``fetch.copy`` an execution, PERF.md section 5; whether two
        planes beat per-array fetches there has no probe yet,
        ROADMAP.md A8.)

        Spans: ``query.fetch`` is the whole of it (its self time: the
        packer's dispatch and the host-side views); ``device.wait`` is
        the host blocked until everything enqueued before it is done
        (the copies are already in flight, as they were when
        ``np.asarray`` did both), and ``fetch.copy`` what is left of
        the copies once the sources are ready."""
        with trace.span("query.fetch"):
            return self._fetch_host()

    def _fetch_host(self):
        return self._fetch_packed(self.data, _local_packer)

    @staticmethod
    def _fetch_packed(data: BatchData, packer_for, **copy_fields):
        """``fetch_host`` of ``data``, packed by ``packer_for(sig)``
        (``sig``: the capacity and each array's plane and dtype).
        Arrays sharded over a mesh take a packer whose planes stay
        sharded (``parallel/sharded.py::MeshResult``): the copies then
        gather the shards. ``copy_fields`` go on the ``fetch.copy``
        span."""
        import jax

        cols = data.columns
        # two planes (value-preserving casts only, no 64-bit
        # bitcasts): ints/bools stack as int64, floats stack as float64
        plan = [("i", 0, jnp.bool_)]  # (plane, slot, dtype) for mask
        int_arrays = [data.row_mask]
        flt_arrays = []
        extra_arrays = []  # 2D array columns: fetched individually
        for cd in cols:
            if cd.data.ndim > 1:
                plan.append(("x", len(extra_arrays), cd.data.dtype))
                extra_arrays.append(cd.data)
            elif jnp.issubdtype(cd.data.dtype, jnp.floating):
                plan.append(("f", len(flt_arrays), cd.data.dtype))
                flt_arrays.append(cd.data)
            else:
                plan.append(("i", len(int_arrays), cd.data.dtype))
                int_arrays.append(cd.data)
            if cd.validity is not None:
                plan.append(("i", len(int_arrays), jnp.bool_))
                int_arrays.append(cd.validity)
        sig = (data.capacity, tuple((p, str(d)) for p, _, d in plan))
        packer = packer_for(sig)
        iplane, fplane = packer(tuple(int_arrays), tuple(flt_arrays))
        # start the copies behind the programs that make their sources,
        # then wait: a wait with no copy in flight costs this chip a round
        # trip of its own (0.12 ms on the v5e, PERF.md) before the copy's
        pending = [iplane] + ([fplane] if fplane.size else []) \
            + extra_arrays
        for x in pending:
            if hasattr(x, "copy_to_host_async"):  # a 2D column may be numpy
                x.copy_to_host_async()
        with trace.span("device.wait"):
            jax.block_until_ready(pending)
        with trace.span("fetch.copy", **copy_fields):
            if fplane.size:
                # fetch the two planes CONCURRENTLY: device_get walks
                # the tree serially and each blocking transfer pays a
                # full round trip, so two overlapped fetches cost ~one
                fut = _FETCH_POOL.submit(np.asarray, fplane)
                ih = np.asarray(iplane)
                fh = fut.result()
            else:
                # all-integer batch (e.g. decimal money results): do
                # NOT fetch the empty float plane — even a zero-size
                # device_get pays a round trip
                ih = np.asarray(iplane)
                fh = np.zeros((0, 0), dtype=np.float64)

            xh = [np.asarray(a) for a in extra_arrays]  # one RTT each

        def restore(plane, slot, dt):
            if plane == "x":
                return xh[slot]
            row = ih[slot] if plane == "i" else fh[slot]
            if dt == jnp.bool_:
                return row.astype(bool)
            return row

        mask = restore(*plan[0])
        out = []
        i = 1
        for cd in cols:
            values = restore(*plan[i])
            i += 1
            valid = None
            if cd.validity is not None:
                valid = restore(*plan[i])
                i += 1
            out.append((values, valid))
        return mask, out

    def to_pylist(self) -> list:
        """Materialize live rows as a list of dicts (decoding string
        dictionaries and dates). For tests and `.collect()`."""
        fetched = self.fetch_host()
        with trace.span("query.rows"):
            return self.rows_from_host(*fetched)

    def rows_from_host(self, mask, host_cols) -> list:
        """``fetch_host()``'s planes -> a list of dicts, one per live
        row (``to_pylist`` without the fetch)."""
        import datetime

        from spark_tpu.types import (ArrayType, DateType, DecimalType,
                                     StringType, TimestampType,
                                     array_len_col)

        out_rows: list = []
        cols = []
        by_name = {f.name: hc for f, hc in zip(self.schema.fields,
                                               host_cols)}
        hidden = {array_len_col(f.name) for f in self.schema.fields
                  if isinstance(f.dtype, ArrayType)}
        out_fields = [f for f in self.schema.fields
                      if f.name not in hidden]
        for f in out_fields:
            cdata, cvalid = by_name[f.name]
            data = cdata[mask]
            valid = (
                np.ones(len(data), dtype=bool)
                if cvalid is None
                else cvalid[mask]
            )
            if isinstance(f.dtype, ArrayType):
                comp = by_name.get(array_len_col(f.name))
                lens = (comp[0][mask] if comp is not None
                        else np.full(len(data), data.shape[1]))

                def el(x):
                    if isinstance(f.dtype.element, StringType):
                        d = f.dictionary or ()
                        return d[x] if 0 <= x < len(d) else None
                    if isinstance(f.dtype.element, DecimalType):
                        import decimal as _d

                        return _d.Decimal(int(x)).scaleb(
                            -f.dtype.element.scale)
                    return x.item() if hasattr(x, "item") else x

                vals = [
                    [el(x) for x in row[:int(ln)]] if v else None
                    for row, ln, v in zip(data, lens, valid)
                ]
            elif isinstance(f.dtype, StringType):
                dictionary = f.dictionary or ()
                vals = [
                    dictionary[c] if (v and 0 <= c < len(dictionary)) else None
                    for c, v in zip(data, valid)
                ]
            elif isinstance(f.dtype, DateType):
                epoch = datetime.date(1970, 1, 1)
                vals = [
                    epoch + datetime.timedelta(days=int(d)) if v else None
                    for d, v in zip(data, valid)
                ]
            elif isinstance(f.dtype, TimestampType):
                epoch = datetime.datetime(1970, 1, 1)
                vals = [
                    epoch + datetime.timedelta(microseconds=int(d)) if v else None
                    for d, v in zip(data, valid)
                ]
            elif isinstance(f.dtype, DecimalType):
                import decimal as _decimal

                s = f.dtype.scale
                vals = [
                    _decimal.Decimal(int(d)).scaleb(-s) if v else None
                    for d, v in zip(data, valid)
                ]
            else:
                vals = [d.item() if v else None for d, v in zip(data, valid)]
            cols.append(vals)
        # pair '#keys'/'#vals' components back into map dicts
        # (types.MapType decomposition)
        from spark_tpu.types import map_base_name, map_keys_col, \
            map_vals_col

        idx = {f.name: j for j, f in enumerate(out_fields)}
        emit: list = []  # (output name, column index | (kj, vj))
        for j, f in enumerate(out_fields):
            base = map_base_name(f.name)
            if base is not None and map_keys_col(base) in idx \
                    and map_vals_col(base) in idx:
                if f.name.endswith("#keys"):
                    emit.append((base, (j, idx[map_vals_col(base)])))
                continue  # '#vals' rides with its '#keys' sibling
            emit.append((f.name, j))
        n_rows = len(cols[0]) if cols else 0
        for i in range(n_rows):
            row = {}
            for name, j in emit:
                if isinstance(j, tuple):
                    kj, vj = j
                    ks, vs = cols[kj][i], cols[vj][i]
                    row[name] = None if ks is None else dict(zip(ks, vs))
                else:
                    row[name] = cols[j][i]
            out_rows.append(row)
        return out_rows

    def to_pandas(self):
        import pandas as pd

        rows = self.to_pylist()
        return pd.DataFrame(rows, columns=list(self.schema.names))


def round_capacity(n: int, multiple: int = 1024) -> int:
    """Round row count up to a bucketed capacity so jit caches hit across
    similar-sized inputs (analogue of recompile avoidance; the reference
    has no static-shape constraint)."""
    if n <= 0:
        return multiple
    return ((n + multiple - 1) // multiple) * multiple


def from_numpy(
    schema: Schema,
    arrays: Sequence[np.ndarray],
    validities: Optional[Sequence[Optional[np.ndarray]]] = None,
    capacity: Optional[int] = None,
    narrow_transfer: bool = False,
) -> Batch:
    """Build a device batch from host numpy columns, padding to capacity.

    ``narrow_transfer`` keeps a 1-D int64-backed column (decimal,
    bigint) whose observed values fit int32 as int32 ON THE DEVICE:
    every stage widens it back to the schema's dtype at trace entry
    (Pipe.from_batch_data), the convert fuses into its consumers, and
    the chip is spared the split of an int64 *parameter* into the u32
    pairs it computes with (paid on every execution of a resident scan;
    PERF.md, PR 31) besides half the host->device bytes (the out-of-HBM
    tiers stream tens of GB through this path). What decides is the
    column's min and max, nothing else; an empty column stays as its
    schema says."""
    n = int(arrays[0].shape[0]) if arrays else 0
    for a in arrays:
        assert a.shape[0] == n, "all columns must have equal length"
    cap = capacity if capacity is not None else round_capacity(n)
    assert cap >= n
    if validities is None:
        validities = [None] * len(arrays)

    cols = []
    for f, arr, val in zip(schema.fields, arrays, validities):
        np_dt = arr.dtype if arr.ndim > 1 else f.dtype.np_dtype
        if narrow_transfer and arr.ndim == 1 \
                and np.dtype(np_dt) == np.int64 and n > 0 \
                and -(1 << 31) <= int(arr.min()) \
                and int(arr.max()) < (1 << 31):
            np_dt = np.int32
        shape = (cap,) + tuple(arr.shape[1:])
        padded = np.zeros(shape, dtype=np_dt)
        # one cast into place (values known to fit), no temporary
        np.copyto(padded[:n], arr, casting="unsafe")
        v = None
        if val is not None:
            pv = np.zeros((cap,), dtype=bool)
            pv[:n] = val
            v = jnp.asarray(pv)
        cols.append(ColumnData(jnp.asarray(padded), v))
    row_mask = np.zeros((cap,), dtype=bool)
    row_mask[:n] = True
    return Batch(schema, BatchData(tuple(cols), jnp.asarray(row_mask)))


def empty_batch(schema: Schema, capacity: int = 1024) -> Batch:
    return from_numpy(
        schema, [np.zeros((0,), dtype=f.dtype.np_dtype) for f in schema.fields],
        capacity=capacity,
    )
