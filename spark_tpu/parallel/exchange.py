"""Exchange primitives — shuffle as ICI collectives.

Everything here runs INSIDE a shard_map trace (one device's view, with
the ``data`` axis name in scope). This file is the whole replacement for
the reference's shuffle write/fetch pipeline: sort-based spill files +
Netty chunk fetch (reference: shuffle/sort/SortShuffleManager.scala:73,
UnsafeShuffleWriter.java:173, storage/ShuffleBlockFetcherIterator.scala:86,
common/network-common) becomes: bucket rows into a (D, cap) send tensor
and `lax.all_to_all` it over the interconnect. No files, no serializer,
no fetch scheduler — the collective IS the shuffle.

Static-shape contract: the receive capacity is D * send_capacity (worst
case: everyone routes everything to one device). AQE-style stats can
shrink this between stages (planner._maybe_compact analogue).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_tpu import types as T
from spark_tpu.expr import compiler as C
from spark_tpu.expr.compiler import TV
from spark_tpu.parallel.mesh import DATA_AXIS
from spark_tpu.physical import kernels as K
from spark_tpu.physical.operators import Pipe


def axis_index() -> jnp.ndarray:
    return jax.lax.axis_index(DATA_AXIS)


def axis_size() -> int:
    return jax.lax.axis_size(DATA_AXIS)


# ---- row routing ------------------------------------------------------------


def hash_rows(tvs: Sequence[TV]) -> jnp.ndarray:
    """Full-width avalanche hash of the key columns, one uint64 per
    row. Dictionary codes hash directly — dictionaries are global
    constants, so codes agree across devices. NULL hashes as a fixed
    sentinel, so null keys collide (and co-locate once routed). Shared
    by hash routing (mod D) and the distinct-key sketch (register
    index + leading-zero rank over the SAME hash chain, so equal keys
    produce equal registers on every device)."""
    cap = int(tvs[0].data.shape[0]) if tvs else 0
    h = jnp.zeros((cap,), dtype=jnp.uint64)
    for tv in tvs:
        data = tv.data
        if jnp.issubdtype(data.dtype, jnp.floating):
            # normalize -0.0 == 0.0 before bitcasting
            data = jax.lax.bitcast_convert_type(
                jnp.where(data == 0, 0.0, data).astype(jnp.float64),
                jnp.uint64)
        code = data.astype(jnp.uint64)
        if tv.validity is not None:
            code = jnp.where(tv.validity, code,
                             jnp.uint64(0xA5A5A5A5A5A5A5A5))
        h = K.hash_combine(h, code)
    return h


def hash_target(tvs: Sequence[TV], mask: jnp.ndarray, d: int) -> jnp.ndarray:
    """Device id per row = avalanche hash of the key columns mod D
    (HashPartitioning analogue, reference:
    exchange/ShuffleExchangeExec.scala:275)."""
    if not tvs:
        return jnp.zeros((int(mask.shape[0]),), dtype=jnp.int32)
    return (hash_rows(tvs) % jnp.uint64(d)).astype(jnp.int32)


def range_target(key: TV, ascending: bool, nulls_first: bool, d: int,
                 mask: jnp.ndarray,
                 samples_per_device: int = 128) -> jnp.ndarray:
    """Device id per row for range partitioning: sample local keys,
    all_gather the samples, cut D-1 splitters — every device derives the
    SAME splitters, so no separate sampling job is needed (reference
    needs one: RangePartitioner sketch job,
    core/.../Partitioner.scala + ShuffleExchangeExec.scala:280)."""
    rank_table = None
    if isinstance(key.dtype, T.StringType):
        rank_table = C.string_rank_table(key.dictionary or ())
    y = K.orderable_int64(key.data, key.validity, ascending, nulls_first,
                          rank_table)
    cap = int(mask.shape[0])
    imax = jnp.iinfo(jnp.int64).max
    ys = jnp.sort(jnp.where(mask, y, imax))
    # spread samples over the live prefix; dead rows sample as +inf and
    # only skew splitters when occupancy is very low (AQE re-split later)
    s = min(samples_per_device, cap)
    idx = (jnp.arange(s) * cap) // s
    samples = ys[idx]
    all_samples = jnp.sort(jax.lax.all_gather(samples, DATA_AXIS,
                                              tiled=True))
    total = int(all_samples.shape[0])
    cut_pos = (jnp.arange(1, d) * total) // d
    splitters = all_samples[cut_pos]
    return jnp.searchsorted(splitters, y, side="right").astype(jnp.int32)


def fan_local(target: jnp.ndarray,
              hot: Sequence[int]) -> jnp.ndarray:
    """Skew fan: rows bound for a hot destination stay on their source
    device instead (the local-shuffle-reader move, reference:
    OptimizeShuffleWithLocalShuffleReader.scala:35 — a skewed partition
    is read where it was produced rather than concentrated). Every
    device holds a slice of the hot keys afterwards; a partial-aggregate
    pre-merge plus a second exchange of the (much smaller) merged groups
    restores the final placement."""
    me = axis_index()
    hot_mask = jnp.zeros(target.shape, dtype=bool)
    for h in hot:
        hot_mask = hot_mask | (target == int(h))
    return jnp.where(hot_mask, me.astype(target.dtype), target)


# ---- the collective exchange ------------------------------------------------


def exchange(pipe: Pipe, target: jnp.ndarray,
             slice_capacity: Optional[int] = None,
             out_capacity: Optional[int] = None) -> Pipe:
    """Route each live row to device ``target[row]``. Local capacity cap
    becomes D*cap after the all_to_all. One fused sequence:
    sort-by-destination -> scatter into (D, cap) send buffer ->
    all_to_all over ICI -> flatten.

    Adaptive execution (executor._run_adaptive_exchange) passes measured
    bounds: ``slice_capacity`` shrinks the per-(src,dest) send slice
    from cap to the measured pmax cell count (the all_to_all then moves
    D*slice instead of D*cap elements over ICI), and ``out_capacity``
    compacts the received rows in-trace to the measured pmax incoming
    count. Both are exact upper bounds from the same target computation,
    so no live row is ever dropped, and both transforms are stable
    (order-preserving), so the live-row sequence — and therefore every
    downstream result — is byte-identical to the unbounded exchange."""
    # fault seam: fires at trace time (a failed trace is never cached,
    # so a stage retry re-traces and re-arrives here)
    from spark_tpu import faults

    faults.inject("exchange.all_to_all")
    d = axis_size()
    cap = pipe.capacity
    scap = cap if slice_capacity is None else max(1, min(int(slice_capacity),
                                                         cap))
    live = pipe.mask
    t = jnp.where(live, jnp.clip(target, 0, d - 1), d)  # dead rows -> sentinel
    order = jnp.argsort(t, stable=True)
    st = t[order]
    starts = jnp.searchsorted(st, jnp.arange(d), side="left")
    pos = jnp.arange(cap) - starts[jnp.clip(st, 0, d - 1)]
    # destination slot in the (D, scap) buffer; sentinel rows -> OOB drop
    # (pos >= scap cannot happen for live rows when slice_capacity is a
    # measured bound, but the guard keeps a stale bound safe: overflow
    # drops rather than corrupting a neighbour slice)
    ok = (st < d) & (pos < scap)
    dest = jnp.where(ok, st * scap + pos, d * scap)

    def route(x: jnp.ndarray, fill) -> jnp.ndarray:
        buf = jnp.full((d * scap,), fill, dtype=x.dtype)
        buf = buf.at[dest].set(x[order], mode="drop")
        return jax.lax.all_to_all(buf.reshape(d, scap), DATA_AXIS, 0, 0,
                                  tiled=True).reshape(-1)

    new_mask = route(live, False)
    cols: Dict[str, TV] = {}
    for name in pipe.order:
        tv = pipe.cols[name]
        data = route(tv.data, jnp.zeros((), tv.data.dtype))
        validity = None if tv.validity is None else route(tv.validity, False)
        cols[name] = TV(data, validity, tv.dtype, tv.dictionary)
    out = Pipe(cols, new_mask, pipe.order)
    if out_capacity is not None and int(out_capacity) < d * scap:
        out = compact(out, int(out_capacity))
    return out


def compact(pipe: Pipe, new_capacity: int) -> Pipe:
    """Stable in-trace compaction: live rows to the front (original
    order preserved), then truncate to ``new_capacity`` slots. The bound
    must cover every live row (adaptive stats guarantee it)."""
    perm = K.compaction_permutation(pipe.mask)[: int(new_capacity)]
    cols = {
        name: TV(tv.data[perm],
                 None if tv.validity is None else tv.validity[perm],
                 tv.dtype, tv.dictionary)
        for name, tv in pipe.cols.items()
    }
    return Pipe(cols, pipe.mask[perm], pipe.order)


def broadcast_gather(pipe: Pipe) -> Pipe:
    """Replicate a (small) pipe onto every device via all_gather — the
    broadcast-exchange data plane (reference: TorrentBroadcast.scala:59 +
    BroadcastExchangeExec.scala:78; one ICI all_gather replaces the
    BitTorrent chunk protocol)."""
    def g(x):
        return jax.lax.all_gather(x, DATA_AXIS, tiled=True)

    cols = {
        name: TV(g(tv.data),
                 None if tv.validity is None else g(tv.validity),
                 tv.dtype, tv.dictionary)
        for name, tv in pipe.cols.items()
    }
    return Pipe(cols, g(pipe.mask), pipe.order)


def to_single_partition(pipe: Pipe) -> Pipe:
    """All rows to device 0 (SinglePartition analogue, reference:
    ShuffleExchangeExec.scala:301): gather + mask off non-zero devices.
    Row order across devices is preserved by the tiled gather."""
    g = broadcast_gather(pipe)
    on_zero = jnp.where(axis_index() == 0, g.mask,
                        jnp.zeros_like(g.mask))
    return Pipe(g.cols, on_zero, g.order)


# ---- merged (cross-device) aggregation primitives ---------------------------


def psum(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.psum(x, DATA_AXIS)


def pmin(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.pmin(x, DATA_AXIS)


def pmax(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.pmax(x, DATA_AXIS)
