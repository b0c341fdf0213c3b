"""Sharded columnar batches: the distributed dataset representation.

The analogue of an RDD's partition set materialized in a BlockManager
(reference: core/.../rdd/RDD.scala, storage/BlockManager.scala:172) —
but instead of N partition objects scattered over executor JVM heaps,
a ShardedBatch is ONE logical set of flat device arrays laid out as
``(D * per_device_capacity,)`` and sharded over the mesh's ``data``
axis, so device d owns the contiguous slice d. XLA sees global arrays,
shard_map programs see the local slice — partition-count independence
falls out of the sharding instead of a partitioner class.

Row order convention: the flat array order IS the global row order.
Range-partitioned (sorted) outputs therefore read back correctly by
construction; unordered inputs are dealt round-robin for balance.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_tpu import trace
from spark_tpu.columnar.batch import (_PACKER_CACHE, Batch, BatchData,
                                       ColumnData, _local_packer, pack)
from spark_tpu.parallel.mesh import DATA_AXIS, mesh_size
from spark_tpu.physical.kernels import bucket
from spark_tpu.types import Schema


class ShardedBatch:
    """schema + BatchData whose arrays are (D*cap,) sharded on ``data``."""

    __slots__ = ("schema", "data", "mesh", "per_device_capacity",
                 "sorted_by")

    def __init__(self, schema: Schema, data: BatchData, mesh: Mesh,
                 sorted_by=None):
        self.schema = schema
        self.data = data
        self.mesh = mesh
        d = mesh_size(mesh)
        total = int(data.row_mask.shape[0])
        assert total % d == 0, (total, d)
        self.per_device_capacity = total // d
        #: global order guarantee, or None: a tuple of
        #: (column_name, ascending, nulls_first) the FLAT ROW ORDER of
        #: this batch already satisfies across the whole mesh (e.g. the
        #: sort-based aggregation rung's range-partitioned, locally
        #: sorted output). Consumers (the executor's sort/range-
        #: exchange elision) may skip a global sort whose orders are a
        #: prefix-compatible match; purely advisory — dropping it is
        #: always correct.
        self.sorted_by = sorted_by

    @property
    def capacity(self) -> int:
        return int(self.data.row_mask.shape[0])

    def num_valid_rows(self) -> int:
        return int(np.asarray(self.data.row_mask).sum())

    @classmethod
    def from_batch(cls, batch: Batch, mesh: Mesh,
                   per_device_capacity: Optional[int] = None,
                   ) -> "ShardedBatch":
        """Split rows into contiguous blocks (device d owns source rows
        [d*p, (d+1)*p)) so the flat-order convention holds from the
        start — limit/first/show agree with the single-device engine.
        Source batches are live-prefix-packed (from_arrow/from_numpy), so
        contiguous blocks are also balanced; re-balancing of filtered
        intermediates is RoundRobinExchangeExec's job."""
        d = mesh_size(mesh)
        n = batch.capacity
        p = per_device_capacity or bucket(math.ceil(n / d), 128)
        src = np.arange(min(n, d * p))
        dest = src

        mask_np = np.zeros((d * p,), dtype=bool)
        mask_np[dest] = np.asarray(batch.data.row_mask)[src]
        sharding = NamedSharding(mesh, P(DATA_AXIS))

        cols = []
        for cd in batch.data.columns:
            data_np = np.zeros((d * p,), dtype=np.asarray(cd.data).dtype)
            data_np[dest] = np.asarray(cd.data)[src]
            validity = None
            if cd.validity is not None:
                v = np.zeros((d * p,), dtype=bool)
                v[dest] = np.asarray(cd.validity)[src]
                validity = jax.device_put(v, sharding)
            cols.append(ColumnData(jax.device_put(data_np, sharding),
                                   validity))
        return cls(batch.schema,
                   BatchData(tuple(cols),
                             jax.device_put(mask_np, sharding)),
                   mesh)

    def to_batch(self) -> Batch:
        """Gather to one single-device batch on the mesh's first device.
        Flat order = global row order (the shards' concatenation along
        axis 0, 2-D array columns included).

        ONE batched transfer through the host, whatever the result's
        size: every shard's copy to the host is started at once, numpy
        assembles them, and one ``device_put`` of the whole tree hands
        them to the first device. No array comes to the host and goes
        back on its own, and the devices hold their shards and the one
        copy asked for (a replicated gather would hold D copies). The
        copies are waited for together.

        Span ``fetch.copy`` with ``op=gather``, ``path=host``,
        ``arrays`` and ``bytes``.

        ``MeshResult.data`` calls this for a consumer that needs the
        arrays on a device (``cache()``, ``ml``); rows for the host
        never come this way."""
        leaves, treedef = jax.tree_util.tree_flatten(self.data)
        with trace.span("fetch.copy", op="gather", path="host",
                        arrays=len(leaves),
                        bytes=sum(x.nbytes for x in leaves)):
            for x in leaves:
                x.copy_to_host_async()
            one = jax.device_put([np.asarray(x) for x in leaves],
                                 self.mesh.devices.flat[0])
        return Batch(self.schema, treedef.unflatten(one))

    def __repr__(self):
        return (f"ShardedBatch(D={mesh_size(self.mesh)}, "
                f"per_device={self.per_device_capacity}, "
                f"schema={list(self.schema.names)})")


class MeshResult(Batch):
    """A finished mesh query's result as the ``Batch`` every consumer
    takes, its arrays still sharded over the mesh.

    ``fetch_host`` (``collect``, ``toArrow``, ``toPandas``, the connect
    server) packs ON the mesh, each device its own shard, into planes
    that stay sharded, and the planes' copies to the host are the
    gather: one program, no collective, nothing replicated, one wait
    (span ``fetch.copy`` with ``op=gather``, ``path=planes``,
    ``arrays``, ``bytes``). Flat order = global row order: numpy
    assembles the shards along the row axis.

    A consumer that needs arrays on one device reads ``data``, which
    gathers once (``ShardedBatch.to_batch``) and lets the shards go:
    from then on this is a one-device batch, fetch included. What only
    counts (``capacity``, ``num_valid_rows``, ``device_nbytes``,
    ``narrowed``: the result cache's accounting) reads whichever copy
    is held and gathers nothing."""

    __slots__ = ("_held",)

    def __init__(self, sharded: ShardedBatch):
        self.schema = sharded.schema
        #: (the arrays, the mesh they are sharded over; None once
        #: gathered), swapped as one so that a reader sees a pair
        self._held: Tuple[BatchData, Optional[Mesh]] = (sharded.data,
                                                        sharded.mesh)

    @property
    def data(self) -> BatchData:
        held, mesh = self._held
        if mesh is not None:
            held = ShardedBatch(self.schema, held, mesh).to_batch().data
            self._held = (held, None)
        return held

    def _as_held(self) -> Batch:
        return Batch(self.schema, self._held[0])

    @property
    def capacity(self) -> int:
        return self._held[0].capacity

    def num_valid_rows(self) -> int:
        return self._as_held().num_valid_rows()

    def device_nbytes(self) -> int:
        return self._as_held().device_nbytes()

    def narrowed(self) -> int:
        return self._as_held().narrowed()

    def _fetch_host(self):
        held, mesh = self._held
        if mesh is None:
            return self._fetch_packed(held, _local_packer)
        leaves = jax.tree_util.tree_leaves(held)
        return self._fetch_packed(
            held, functools.partial(_mesh_packer, mesh),
            op="gather", path="planes", arrays=len(leaves),
            bytes=sum(x.nbytes for x in leaves))


def _mesh_packer(mesh: Mesh, sig):
    """``batch.pack`` for arrays of signature ``sig`` sharded over
    ``mesh``, jitted so that its planes stay sharded along the rows.
    Cached beside the one-device packers, under ``(mesh, sig)``."""
    packer = _PACKER_CACHE.get((mesh, sig))
    if packer is None:
        planes = NamedSharding(mesh, P(None, DATA_AXIS))
        # an all-integer result's float plane is empty, and the chip's
        # compiler replicates an empty output whatever it is told
        floats = (planes if any(plane == "f" for plane, _ in sig[1])
                  else NamedSharding(mesh, P()))
        packer = _PACKER_CACHE[mesh, sig] = jax.jit(
            pack, out_shardings=(planes, floats))
        trace.built("gather", mesh=mesh_size(mesh),
                    capacity=sig[0], arrays=len(sig[1]))
    return packer
