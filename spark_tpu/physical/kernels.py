"""Device kernels: the Tungsten tier, rebuilt for XLA.

The reference's native-equivalent execution machinery — RadixSort.java:25,
UnsafeExternalSorter.java, BytesToBytesMap.java:67 (hash aggregation),
HashedRelation.scala (join builds) — is pointer-chasing JVM/off-heap
code. None of that survives contact with a TPU. These kernels re-express
the same operations as dense, static-shape XLA programs:

- sort        -> chained stable argsorts (XLA variadic sort on device)
- hash-agg    -> segment reductions over group ids; group ids come either
                 from mixed-radix dictionary codes (trace-time cardinality,
                 no sort, no sync) or from sort + change-flag cumsum
- hash-join   -> sort the build side once, then two `searchsorted`s give
                 every probe row its contiguous match range; expansion to
                 match pairs is a scatter of the ranges' starts and a
                 running max, then gathers a pair (no pointers, no probing)

Everything is mask-carrying: dead rows ride along and are neutralized per
reduction, which keeps shapes static under jit.
"""

from __future__ import annotations

from functools import partial
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_tpu import trace


class SortKey(NamedTuple):
    data: jnp.ndarray
    validity: Optional[jnp.ndarray]  # None = all valid
    ascending: bool = True
    nulls_first: bool = True


def _searchsorted_sort_threshold() -> int:
    """spark.tpu.kernels.searchsortedSortThreshold, from the active
    session's conf when one exists (registry default otherwise). Read
    at trace time; the choice only affects speed, never results, so a
    cached trace with a stale threshold stays correct."""
    from spark_tpu import conf as CF

    try:
        from spark_tpu.api.session import SparkSession

        sess = SparkSession._active
        if sess is not None:
            return int(sess.conf.get(CF.SEARCHSORTED_SORT_THRESHOLD))
    except Exception:
        pass
    return int(CF.SEARCHSORTED_SORT_THRESHOLD.default)


def searchsorted(a: jnp.ndarray, v: jnp.ndarray,
                 side: str = "left") -> jnp.ndarray:
    """Size-aware searchsorted. 'scan' (binary search) costs ~log2(a)
    serialized gather rounds over v — linear in v, nearly free for small
    v but catastrophic for large v (measured v5e: a=1.45M/v=1.2M scan
    564 ms vs sort 27 ms). 'sort' co-sorts the concatenation — linear in
    a+v, so it overpays when v << a (a=6M/v=10k: sort 63 ms vs scan
    1.9 ms). The measured crossover (v * threshold ~ a) sat near 50 on
    v5e and is tunable per deployment via
    spark.tpu.kernels.searchsortedSortThreshold."""
    threshold = _searchsorted_sort_threshold()
    method = ("scan" if v.size < 4096 or v.size * threshold <= a.size
              else "sort")
    if method == "sort":
        _built_sort("searchsorted", a.size + v.size, a.dtype)
    return jnp.searchsorted(a, v, side=side, method=method)


def _built_sort(site: str, rows: int, dtype) -> None:
    """A ``sort`` build event (as seg_sum's): one per XLA sort BUILT,
    inside a stage's trace or eagerly in a blocking run; an execution of
    a compiled stage records none. On the chip's compiler a sort costs
    22-69 s cold (ROADMAP A2), so their count is what a first run pays."""
    trace.built("sort", site=site, rows=int(rows), dtype=str(dtype))


def lexsort_permutation(keys: Sequence[SortKey], row_mask: jnp.ndarray) -> jnp.ndarray:
    """Stable lexicographic sort permutation. Live rows first; within the
    live region rows are ordered by ``keys`` (most significant first) with
    SQL null placement. Replaces RadixSort.java:25 / TimSort — XLA's sort
    is already a tuned parallel sort, we only arrange comparators.
    """
    n = row_mask.shape[0]
    perm = jnp.arange(n)
    for key in reversed(list(keys)):
        d = key.data[perm]
        if key.validity is not None:
            # canonicalize NULL rows' payload BEFORE the data sort:
            # sorting by garbage-under-null would scramble the
            # less-significant key order established by earlier passes
            # (all nulls are equal; their relative order must be
            # whatever the previous keys made it)
            v = key.validity[perm]
            d = jnp.where(v, d, jnp.zeros((), d.dtype))
        _built_sort("lexsort", n, d.dtype)
        idx = jnp.argsort(d, stable=True, descending=not key.ascending)
        perm = perm[idx]
        if key.validity is not None:
            v = key.validity[perm]
            # nulls_first: invalid(False) first -> ascending sort on bool
            _built_sort("lexsort", n, v.dtype)
            idx = jnp.argsort(v, stable=True, descending=not key.nulls_first)
            perm = perm[idx]
    live = row_mask[perm]
    _built_sort("lexsort", n, live.dtype)
    idx = jnp.argsort(~live, stable=True)  # live rows (False) first
    return perm[idx]


def compaction_permutation(row_mask: jnp.ndarray) -> jnp.ndarray:
    """Permutation moving live rows to the front, preserving order."""
    _built_sort("compaction", row_mask.shape[0], row_mask.dtype)
    return jnp.argsort(~row_mask, stable=True)


def group_ids_from_sorted(
    sorted_keys: Sequence[Tuple[jnp.ndarray, Optional[jnp.ndarray]]],
    sorted_mask: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Given key columns already sorted (live rows first), return
    (segment_ids, num_groups). Equal adjacent keys (null==null) share a
    segment; dead rows get the last segment id."""
    n = sorted_mask.shape[0]
    change = jnp.zeros((n,), dtype=jnp.bool_)
    for data, validity in sorted_keys:
        neq = jnp.concatenate(
            [jnp.zeros((1,), jnp.bool_), data[1:] != data[:-1]])
        if validity is not None:
            vneq = jnp.concatenate(
                [jnp.zeros((1,), jnp.bool_), validity[1:] != validity[:-1]])
            # both-null rows compare equal regardless of payload
            both_null = jnp.concatenate(
                [jnp.zeros((1,), jnp.bool_), (~validity[1:]) & (~validity[:-1])])
            neq = (neq & ~both_null) | vneq
        change = change | neq
    change = change & sorted_mask
    seg = jnp.cumsum(change.astype(jnp.int32))
    num_groups = jnp.where(sorted_mask.any(), seg[-1] + 1, 0)
    return seg, num_groups


# ---- segment aggregation ----------------------------------------------------
#
# TPU reality check (v5e, inside a jit with x64 on; tools/probe_seg_sum.py,
# chip runs of PRs 27, 29 and 36). One grouped sum over 6,001,664 rows: XLA
# scatter-add (jax.ops.segment_sum) costs 360 ms on an int64 column (730 at
# K = 100,000), whatever K is: it pays per row. An f64 is a pair of f32
# planes on this chip, so the three f64 limbs an int64 sum was carried as
# until PR 36 cost 1,310-1,340 ms (2,370) — and, floats taking the
# row-ordered scatter, 647 of Q15's 1,118 ms an execution at SF10
# (2,421,760 sorted rows, K = 100,096) where one int64 scatter-add reads
# 168 and the cumsum rung 45 (49-53 with the COUNT beside it, whose bound
# searches the compiler merges with the sum's). At 6,001,664 rows cumsum
# + searchsorted over sorted int64 ids read 8 ms at K = 200 and 75 ms at
# K = 100,000. A dense masked reduction pays per PASS over its column, and
# how many it makes is for this file to say, not the compiler: written as
# one reduction a slot, K minima of an f32 or int32 column are merged into
# one fusion, but a 64-bit reduction is already variadic (a u32 pair under
# the emulation) and stays one fusion a slot, each a read of the column at
# HBM speed — 30 of them were 24 of q1's 40 ms at SF10. Written as one
# variadic reduction for G slots, an int64 sum over 59,990,016 rows reads
# (ms, the split of column and ids into u32 pairs, 5.8, included):
#   K = 6    six passes 13.5   one pass 7.0
#   K = 16   sixteen    26.2   G = 8: 8.4    G = 16: 8.3 (compile 5.0 s)
#   K = 64   sixty-four 87.2   G = 8: 16.1   G = 16: 17.2   G = 32: 20.7
#                              (compile 2.7 s / 6.4 s / 14.7 s)
# and all 64 slots at once do not compile (the operands are materialised:
# 28.66 GB). Past eight int64 slots a pass the K selects a row are the
# cost, not the bytes. COUNT reads the same (K = 64: 43.8 -> 10.2); f32
# MIN at K = 64: 8.9 as the compiler merged it, 7.5 at G = 16. Strategy:
#   - K == 1: plain reduction
#   - K small (<= _MASKED_SEG_LIMIT): dense masked reductions, G slots to
#     a pass: ceil(K / G) reads of the column
#   - monotone seg ids (sort-based aggregation, where rows are already
#     sorted by key): inclusive cumsum + searchsorted segment boundaries
#   - otherwise: scatter-add fallback
# The reference hits the same fork as hash-agg vs sort-agg
# (TungstenAggregationIterator.scala:82 switchToSortBasedAggregation).

_MASKED_SEG_LIMIT = 64
# u32 accumulators a pass of the masked rung carries: G = 16 slots of a
# 32-bit column, 8 of an int64 or f64 one (a u32 pair each). Set from the
# probe above: the fastest that compiles in a few seconds.
_MASKED_PASS_ACCUMULATORS = 16


def _slots_a_pass(dtype) -> int:
    """G: the group slots one pass over a column of ``dtype`` fills."""
    words = max(1, jnp.dtype(dtype).itemsize // 4)
    return max(1, _MASKED_PASS_ACCUMULATORS // words)


def _masked_passes(num_segments: int, dtype) -> int:
    return -(-num_segments // _slots_a_pass(dtype))


def _masked_reduce(data, seg, mask, num_segments: int, combine, init):
    """One variadic reduction per G slots: every slot's selected operand
    ``where(mask & (seg == k), data, init)`` is combined in the same pass
    over ``data``. For exact sums, counts, min and max, whose results
    do not depend on the order of combination."""
    init = jnp.asarray(init, data.dtype)
    g = _slots_a_pass(data.dtype)
    cols = []
    for lo in range(0, num_segments, g):
        ks = range(lo, min(lo + g, num_segments))
        cols += jax.lax.reduce(
            tuple(jnp.where(mask & (seg == k), data, init) for k in ks),
            (init,) * len(ks),
            lambda a, b: tuple(combine(x, y) for x, y in zip(a, b)),
            dimensions=(0,))
    return jnp.stack(cols)


def seg_bounds(seg: jnp.ndarray, num_segments: int):
    """First/last row positions per segment for MONOTONE seg ids."""
    ks = jnp.arange(num_segments, dtype=seg.dtype)
    starts = searchsorted(seg, ks, side="left")
    ends = searchsorted(seg, ks, side="right") - 1
    return starts, ends


def _sorted_seg_sum(masked, seg, num_segments: int):
    csum = jnp.cumsum(masked, dtype=masked.dtype)
    starts, ends = seg_bounds(seg, num_segments)
    n = masked.shape[0]
    e = jnp.clip(ends, 0, n - 1)
    s = jnp.clip(starts, 0, n - 1)
    total = csum[e] - csum[s] + masked[s]
    return jnp.where(ends >= starts, total, jnp.zeros((), masked.dtype))


def _seg_scan(seg, x, combine):
    """Segmented inclusive scan (resets at seg changes); seg monotone."""

    def op(a, b):
        sa, va = a
        sb, vb = b
        return sb, jnp.where(sa == sb, combine(va, vb), vb)

    _, out = jax.lax.associative_scan(op, (seg, x))
    return out


def _sorted_seg_red(masked, seg, num_segments: int, combine):
    run = _seg_scan(seg, masked, combine)
    _, ends = seg_bounds(seg, num_segments)
    return run[jnp.clip(ends, 0, masked.shape[0] - 1)]


def _sum_rung(data, seg, mask, num_segments: int, sorted_seg: bool):
    """(sums, rung) for one column, routed by the dtype it travels as."""
    zero = jnp.zeros((), dtype=data.dtype)
    masked = jnp.where(mask, data, zero)
    if num_segments == 1:
        # global aggregate: a plain reduction beats a 1-segment scatter-add
        # (this is the AggregateBenchmark 'agg w/o group' hot path)
        return jnp.sum(masked)[None], "reduce"
    if jnp.issubdtype(data.dtype, jnp.floating):
        # float addition rounds per combination-tree shape, and every
        # tree-structured reduction here (cumsum difference, masked
        # jnp.sum) takes its shape from the PADDED array length — so a
        # segment's float sum would come out bit-different between the
        # static and the AQE capacity-compacted layouts of the same
        # rows. XLA scatter-add applies updates in row order: the sum
        # depends only on the segment's own rows, byte-stable across
        # layouts (int/decimal sums are exact and keep the fast paths).
        return (jax.ops.segment_sum(masked, seg, num_segments=num_segments),
                "scatter")
    if num_segments <= _MASKED_SEG_LIMIT:
        return (_masked_reduce(data, seg, mask, num_segments, jnp.add, zero),
                "masked")
    if sorted_seg:
        return _sorted_seg_sum(masked, seg, num_segments), "cumsum"
    return (jax.ops.segment_sum(masked, seg, num_segments=num_segments),
            "scatter")


def seg_sum(data, seg, mask, num_segments: int, sorted_seg: bool = False):
    """Grouped sum. The rule of the ladder: EXACT sums (integers, scaled
    decimals) follow the integer rungs — reduce, masked, cumsum, scatter,
    chosen from (num_segments, sorted_seg); only USER FLOATS need row
    order and take the scatter-add whatever K is. int64 addition wraps,
    so every rung returns a group's exact sum whenever that sum fits 64
    bits, the cumsum rung's difference of running totals too."""
    out, rung = _sum_rung(data, seg, mask, num_segments, sorted_seg)
    # trace-time event (as ops/pallas_agg._note): the rung this program
    # was BUILT from; an execution of the compiled stage records nothing
    trace.built("seg_sum", rung=rung, k=int(num_segments),
                rows=int(data.shape[0]), dtype=str(data.dtype),
                passes=(_masked_passes(num_segments, data.dtype)
                        if rung == "masked" else None))
    return out


def seg_count(seg, mask, num_segments: int, sorted_seg: bool = False):
    ones = mask.astype(jnp.int64)
    if num_segments == 1:
        return jnp.sum(ones)[None]
    if num_segments <= _MASKED_SEG_LIMIT:
        return _masked_reduce(ones, seg, mask, num_segments, jnp.add,
                              jnp.zeros((), jnp.int64))
    if not sorted_seg:
        from spark_tpu.ops import maybe_pallas_seg_count

        out = maybe_pallas_seg_count(seg, mask, num_segments)
        if out is not None:
            return out
    if sorted_seg:
        return _sorted_seg_sum(ones, seg, num_segments)
    return jax.ops.segment_sum(ones, seg, num_segments=num_segments)


def seg_min(data, seg, mask, num_segments: int, sorted_seg: bool = False):
    big = _pos_sentinel(data.dtype)
    masked = jnp.where(mask, data, big)
    if num_segments == 1:
        return jnp.min(masked)[None]
    if num_segments <= _MASKED_SEG_LIMIT:
        return _masked_reduce(data, seg, mask, num_segments, jnp.minimum, big)
    if not sorted_seg:
        # 64 < K <= 1024, f32, TPU: the one-pass Pallas streaming
        # reduction (ops/pallas_agg.py)
        from spark_tpu.ops import maybe_pallas_seg_min

        out = maybe_pallas_seg_min(data, seg, mask, num_segments)
        if out is not None:
            return out
    if sorted_seg:
        return _sorted_seg_red(masked, seg, num_segments, jnp.minimum)
    return jax.ops.segment_min(masked, seg, num_segments=num_segments)


def seg_max(data, seg, mask, num_segments: int, sorted_seg: bool = False):
    small = _neg_sentinel(data.dtype)
    masked = jnp.where(mask, data, small)
    if num_segments == 1:
        return jnp.max(masked)[None]
    if num_segments <= _MASKED_SEG_LIMIT:
        return _masked_reduce(data, seg, mask, num_segments, jnp.maximum,
                              small)
    if not sorted_seg:
        from spark_tpu.ops import maybe_pallas_seg_max

        out = maybe_pallas_seg_max(data, seg, mask, num_segments)
        if out is not None:
            return out
    if sorted_seg:
        return _sorted_seg_red(masked, seg, num_segments, jnp.maximum)
    return jax.ops.segment_max(masked, seg, num_segments=num_segments)


def seg_first(data, seg, mask, num_segments: int, capacity: int,
              sorted_seg: bool = False):
    """Value of the first (by position) masked row in each segment."""
    pos = jnp.where(mask, jnp.arange(capacity), capacity)
    if sorted_seg:
        # monotone ids: a segment's first masked row is the first masked
        # position at or after its start, if that lies inside it. One
        # reverse running minimum and not _sorted_seg_red's segmented
        # scan, whose 40 levels of slices and pads the chip's compiler
        # takes five times as long over as over the rest of an
        # aggregate's stage (PERF.md, PR 35). Empty segments, and those
        # with no masked row, read position `capacity`
        following = jax.lax.cummin(pos, reverse=True)
        starts, ends = seg_bounds(seg, num_segments)
        first_pos = following[jnp.clip(starts, 0, capacity - 1)]
        first_pos = jnp.where((ends >= starts) & (first_pos <= ends),
                              first_pos, capacity)
    elif num_segments <= _MASKED_SEG_LIMIT:
        first_pos = _masked_reduce(pos, seg, mask, num_segments,
                                   jnp.minimum, capacity)
    else:
        first_pos = jax.ops.segment_min(pos, seg, num_segments=num_segments)
    idx = jnp.clip(first_pos, 0, capacity - 1)
    return data[idx], first_pos < capacity


def _pos_sentinel(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf, dtype=dtype)
    if dtype == jnp.bool_:
        return jnp.array(True)
    return jnp.array(jnp.iinfo(dtype).max, dtype=dtype)


def _neg_sentinel(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(-jnp.inf, dtype=dtype)
    if dtype == jnp.bool_:
        return jnp.array(False)
    return jnp.array(jnp.iinfo(dtype).min, dtype=dtype)


# ---- mixed-radix key packing ------------------------------------------------


def pack_codes(
    codes: Sequence[jnp.ndarray],
    validities: Sequence[Optional[jnp.ndarray]],
    cardinalities: Sequence[int],
) -> Tuple[jnp.ndarray, int]:
    """Combine per-column small-int codes into one dense int32/int64 group
    id with mixed-radix packing. Each column contributes (cardinality+1)
    states, the extra one encoding NULL. Replaces BytesToBytesMap lookups
    (reference: unsafe/map/BytesToBytesMap.java:497) when cardinalities
    are known at trace time — no hashing, no collisions, no probing.

    Returns (combined_ids, total_cardinality)."""
    total = 1
    combined = None
    for code, validity, card in zip(codes, validities, cardinalities):
        slot = code.astype(jnp.int64)
        if validity is not None:
            slot = jnp.where(validity, slot, card)  # NULL -> extra state
            card = card + 1
        combined = slot if combined is None else combined * card + slot
        total *= card
    assert combined is not None
    return combined, total


def unpack_code(combined: jnp.ndarray, cardinalities: Sequence[int],
                nullable: Sequence[bool]):
    """Inverse of pack_codes: combined id -> per-column (code, validity)."""
    cards = [c + (1 if nl else 0) for c, nl in zip(cardinalities, nullable)]
    out = []
    rem = combined
    for card, orig_card, nl in zip(reversed(cards),
                                   reversed(list(cardinalities)),
                                   reversed(list(nullable))):
        slot = rem % card
        rem = rem // card
        if nl:
            valid = slot < orig_card
            code = jnp.where(valid, slot, 0)
            out.append((code, valid))
        else:
            out.append((slot, None))
    return list(reversed(out))


def distinct_first_mask(data: jnp.ndarray, seg: jnp.ndarray,
                        ok: jnp.ndarray) -> jnp.ndarray:
    """True for the first ok row of each (segment, value) pair.

    DISTINCT-aggregate core (reference rewrite:
    sql/catalyst/.../optimizer/RewriteDistinctAggregates.scala:1 plans a
    two-level Expand+aggregate; here dedup is a device-local sort +
    change-flag scatter, static-shape and jittable): sort rows by
    (segment, value) with dead rows pushed to the back, mark value-group
    heads, scatter the flags back to original row positions. ANDing the
    result into an aggregate's ok-mask makes sum/count/avg see each value
    once per group. Floats compare by canonicalized bit pattern so that
    NaN == NaN for DISTINCT (Spark's NaN normalization,
    NormalizeFloatingNumbers.scala) — float equality would count every
    NaN as a fresh value."""
    n = data.shape[0]
    if jnp.issubdtype(data.dtype, jnp.floating):
        canon = jnp.where(jnp.isnan(data), jnp.nan, data)
        canon = jnp.where(canon == 0.0, 0.0, canon)  # -0.0 -> +0.0
        width = jnp.uint32 if data.dtype == jnp.float32 else jnp.uint64
        data = jax.lax.bitcast_convert_type(canon, width)
    keys = [SortKey(seg, None, True, True), SortKey(data, None, True, True)]
    perm = lexsort_permutation(keys, ok)
    sseg = seg[perm]
    sval = data[perm]
    sok = ok[perm]
    head = jnp.concatenate([
        jnp.ones((1,), jnp.bool_),
        (sseg[1:] != sseg[:-1]) | (sval[1:] != sval[:-1])])
    head = head & sok
    return jnp.zeros((n,), jnp.bool_).at[perm].set(head)


# ---- join ------------------------------------------------------------------


class JoinRanges(NamedTuple):
    """Per-probe-row contiguous match range in the sorted build side."""

    build_perm: jnp.ndarray   # sort permutation of the build side
    lo: jnp.ndarray           # int64[probe_cap]
    hi: jnp.ndarray           # int64[probe_cap]

    @property
    def counts(self) -> jnp.ndarray:
        return self.hi - self.lo


def build_join_ranges(
    build_key: jnp.ndarray,
    build_ok: jnp.ndarray,   # live AND key-valid
    probe_key: jnp.ndarray,
    probe_ok: jnp.ndarray,
) -> JoinRanges:
    """Sorted-build equi-join core (replaces HashedRelation.scala /
    LongToUnsafeRowMap:535): sort build keys with dead/null rows pushed to
    +inf, then two binary searches per probe row give its match range.
    O((B+P) log B) on device, fully vectorized. Expressed through
    make_join_index + ranges_from_index so the live path and the
    cached-index path share ONE sentinel-handling implementation."""
    perm, skey, _, _ = make_join_index(build_key, build_ok, None)
    return ranges_from_index(perm, skey, None, None, probe_key, probe_ok)


#: dense lo/cnt lookup tables are built when the packed key domain is at
#: most this many entries (int32 x2 -> 128 MB @ 16M; orderkey is 6M at
#: SF1 and 15M at SF10, where a probe of the sorted key instead co-sorts
#: some 32M rows twice an execution)
JOIN_TABLE_MAX = 1 << 24


def make_join_index(build_key: jnp.ndarray, build_ok: jnp.ndarray,
                    domain: Optional[int]):
    """Precompute the reusable part of a sorted-build join: the build
    permutation, the sorted (sentinel-masked) key, and — when the packed
    key domain is small enough — dense lo/cnt lookup tables over the
    whole domain. Recorded once on a blocking run and replayed as jit
    ARGUMENTS on later executions (same justification as _JOIN_STATS:
    immutable leaves => deterministic), so steady-state joins skip the
    argsort + searchsorted entirely: probing a dense table is a single
    int32 gather at probe size (measured v5e: 5-19 ms where the co-sort
    searchsorted costs 19-63 ms per side; reference analogue: the
    reusable LongToUnsafeRowMap build, HashedRelation.scala:535).

    Returns (perm int32[bcap], sorted_key[bcap], lo_table|None,
    cnt_table|None) device arrays."""
    sentinel = _pos_sentinel(build_key.dtype)
    masked = jnp.where(build_ok, build_key, sentinel)
    _built_sort("join_index", masked.shape[0], masked.dtype)
    perm = jnp.argsort(masked, stable=True)
    skey = masked[perm]
    lo_t = cnt_t = None
    if domain is not None and 0 < domain <= JOIN_TABLE_MAX:
        lo_t, cnt_t = dense_join_tables(skey, domain)
    return perm.astype(jnp.int32), skey, lo_t, cnt_t


def _key_counts(key: jnp.ndarray, ok: jnp.ndarray, domain: int):
    """(rows of each key in [0, domain), the slot of each row): one
    scatter-add, no sort."""
    slot = jnp.clip(key, 0, domain - 1)
    ok = ok & (key >= 0) & (key < domain)
    return (jnp.zeros((domain,), jnp.int32).at[slot].add(
        ok.astype(jnp.int32)), slot, ok)


def dense_join_tables(sorted_key: jnp.ndarray, domain: int):
    """make_join_index's dense tables over a packed key domain: ``cnt``
    the build rows of each key, ``lo`` where its run starts in the sorted
    key (the rows of smaller keys; dead rows carry the sentinel, past
    every key). A count and its running sum, where a ``searchsorted`` of
    the whole domain co-sorts domain + build rows twice."""
    cnt, _, _ = _key_counts(sorted_key, sorted_key != _pos_sentinel(
        sorted_key.dtype), domain)
    return jnp.cumsum(cnt) - cnt, cnt


def join_counts(build_key, build_ok, probe_key, probe_ok,
                domain: Optional[int]):
    """What sizes an equi-join on packed keys: (its matches, the most
    matches of one probe row, the most matches of one build row). Over a
    dense key domain each side's rows are counted by key in a scatter-add
    and looked up by the other side's keys: no sort, which the chip's
    compiler takes tens of seconds over (ROADMAP A2). Else (hashed keys,
    a domain past the tables') the sorted build of either side."""
    if domain is not None and 0 < domain <= JOIN_TABLE_MAX:
        bcnt, bslot, bok = _key_counts(build_key, build_ok, domain)
        pcnt, pslot, pok = _key_counts(probe_key, probe_ok, domain)
        counts = jnp.where(pok, bcnt[pslot], 0)
        reverse = jnp.where(bok, pcnt[bslot], 0)
    else:
        counts = build_join_ranges(build_key, build_ok, probe_key,
                                   probe_ok).counts
        reverse = build_join_ranges(probe_key, probe_ok, build_key,
                                    build_ok).counts
    return (counts.astype(jnp.int64).sum(), counts.max(initial=0),
            reverse.max(initial=0))


def ranges_from_index(perm: jnp.ndarray, sorted_key: jnp.ndarray,
                      lo_table: Optional[jnp.ndarray],
                      cnt_table: Optional[jnp.ndarray],
                      probe_key: jnp.ndarray,
                      probe_ok: jnp.ndarray) -> JoinRanges:
    """build_join_ranges against a precomputed make_join_index. Dead
    build rows carry the +inf sentinel key, so they sit past every dense
    table entry / real probe key and never match."""
    if lo_table is not None:
        domain = lo_table.shape[0]
        k = jnp.clip(probe_key, 0, domain - 1)
        ok = probe_ok & (probe_key >= 0) & (probe_key < domain)
        lo = jnp.where(ok, lo_table[k].astype(jnp.int64), 0)
        hi = jnp.where(ok, lo + cnt_table[k].astype(jnp.int64), 0)
        return JoinRanges(perm, lo, hi)
    sentinel = _pos_sentinel(sorted_key.dtype)
    lo = searchsorted(sorted_key, probe_key, side="left")
    hi = searchsorted(sorted_key, probe_key, side="right")
    ok = probe_ok & (probe_key != sentinel)
    lo = jnp.where(ok, lo, 0)
    hi = jnp.where(ok, hi, 0)
    return JoinRanges(perm, lo, hi)


def lookup_join_ranges(build_key: jnp.ndarray, build_ok: jnp.ndarray,
                       build_code: jnp.ndarray, probe_key: jnp.ndarray,
                       probe_ok: jnp.ndarray, probe_code: jnp.ndarray,
                       domain: int) -> JoinRanges:
    """JoinRanges of a join looked up by a key in [0, ``domain``) that no
    two live build rows a probe row looks up share, its other keys
    packed into an int32 ``code`` each side: two dense tables over the
    domain, set by scatters of the build rows (each key's row, and its
    code), no sort. A probe row matches where its key has a row and that
    row's code is its own, so the ranges hold the full keys' matches.
    ``build_perm`` is the row table and ``lo`` the probe's key: a match's
    build row is ``build_perm[lo]``, as from a sorted index."""
    at = jnp.where(build_ok & (build_key >= 0) & (build_key < domain),
                   build_key, domain)
    row = jnp.full((domain,), -1, jnp.int32).at[at].set(
        jnp.arange(build_key.shape[0], dtype=jnp.int32), mode="drop")
    code = jnp.full((domain,), -1, jnp.int32).at[at].set(
        build_code.astype(jnp.int32), mode="drop")
    key = jnp.clip(probe_key, 0, domain - 1)
    ok = (probe_ok & (probe_key >= 0) & (probe_key < domain)
          & (code[key] == probe_code.astype(jnp.int32)))
    return JoinRanges(row, key, key + ok)


#: a join's expansion searches, instead of scattering every probe row,
#: from this many probe rows a pair: on the v5e the scatter cost ~7.6 ms
#: at 1,572,864 probe rows whatever the pairs, a binary search ~0.19 us
#: a pair, and the two met near 34 (tools/probe_expand.py --crossover)
EXPAND_SEARCH_RATIO = 32


def expand_join_pairs(ranges: JoinRanges, total: int) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Materialize (probe_idx, build_idx, pair_mask) for all match pairs.
    ``total`` is the static output capacity (host-synced count, bucketed).
    Pair j belongs to the probe row p whose exclusive-offset range covers
    j; its build index is the j-offsets[p]'th sorted match, i.e.
    ``build_perm[base[p] + j]`` with ``base = lo - offsets`` constant on
    p's run of pairs. Every capacity is below 2^31, so the offsets and
    positions are int32 (the sum of the counts too: a capacity is sized
    from it).

    ``starts`` (where the probe rows are fewer than ``EXPAND_SEARCH_RATIO``
    times the pairs): every row with a match sets its own index at its
    run's start (rows without one share the next run's start and are
    sent past the capacity instead, so the indices stay unique) and a
    running max carries it over the run — one scatter of probe-length
    rows into pair length, no sort. (Two scatter-adds of the rows' steps,
    one for p and one for ``base``, crash the v5e compiler once a sorted
    aggregate follows the join at TPC-H Q3's SF10 shapes.) ``search``
    (few pairs out of many probe rows): a binary search of every pair,
    log2(probe rows) rounds of gathers over the pairs. Either way
    ``base`` is read a pair."""
    n = ranges.lo.shape[0]
    lo = ranges.lo.astype(jnp.int32)
    counts = ranges.hi.astype(jnp.int32) - lo
    offsets = jnp.cumsum(counts) - counts  # exclusive prefix
    j = jnp.arange(total, dtype=jnp.int32)
    method = "starts" if total * EXPAND_SEARCH_RATIO > n else "search"
    trace.built("expand", method=method, probe_rows=int(n),
                pairs=int(total))
    if method == "starts":
        q = jnp.arange(n, dtype=jnp.int32 if total + n < 2**31 else jnp.int64)
        # past the capacity (mode="drop"): rows without a match, and the
        # starts of runs beyond it
        at = jnp.where(counts > 0, offsets, total + q)
        p = jax.lax.cummax(jnp.zeros(total, q.dtype).at[at].set(
            q, mode="drop", unique_indices=True)).astype(jnp.int32)
    else:
        p = jnp.clip(jnp.searchsorted(offsets, j, side="right",
                                      method="scan") - 1, 0, n - 1)
    build_idx = ranges.build_perm[jnp.clip(
        (lo - offsets)[p] + j, 0, ranges.build_perm.shape[0] - 1)]
    pair_mask = j < counts.sum(dtype=jnp.int32)
    return p, build_idx, pair_mask


def range_compress_keys(
    keys: List[Tuple[np.ndarray, Optional[np.ndarray]]],
    mins: List[int],
    ranges: List[int],
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Pack multiple integer join keys into one int64 via range
    compression (host supplies per-key min/range from lightweight stats).
    Returns (combined_key, all_valid_mask)."""
    combined = jnp.zeros(keys[0][0].shape, dtype=jnp.int64)
    valid = None
    for (data, validity), mn, rg in zip(keys, mins, ranges):
        slot = (data.astype(jnp.int64) - mn)
        slot = jnp.clip(slot, 0, rg - 1)
        combined = combined * rg + slot
        if validity is not None:
            valid = validity if valid is None else (valid & validity)
    if valid is None:
        valid = jnp.ones(combined.shape, dtype=jnp.bool_)
    return combined, valid


# ---- hashing / key encoding (partitioning support) --------------------------


def hash64(x: jnp.ndarray) -> jnp.ndarray:
    """Deterministic 64-bit avalanche mix (splitmix64/xxh64 finalizer
    shape). Role of the reference's Murmur3/XXH64 partitioning hashes
    (common/unsafe hash/, catalyst XXH64.java) — used to route rows to
    mesh devices; must be identical on every device."""
    h = x.astype(jnp.uint64)
    h = (h ^ (h >> 33)) * jnp.uint64(0xFF51AFD7ED558CCD)
    h = (h ^ (h >> 33)) * jnp.uint64(0xC4CEB9FE1A85EC53)
    h = h ^ (h >> 33)
    return h


def hash_combine(h: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Fold another column into a running row hash."""
    return hash64(h ^ (x.astype(jnp.uint64) + jnp.uint64(0x9E3779B97F4A7C15)))


def orderable_int64(
    data: jnp.ndarray,
    validity: Optional[jnp.ndarray],
    ascending: bool = True,
    nulls_first: bool = True,
    rank_table: Optional[np.ndarray] = None,
) -> jnp.ndarray:
    """Encode a sort key column as int64 such that plain integer order ==
    the SQL sort order (direction + null placement). Floats use the IEEE754
    sign-flip bit trick; dictionary-coded strings go through a rank table.
    This is the analogue of Spark's sort-key *prefix* encoding
    (core/.../unsafe/sort/PrefixComparators.java) — but here the whole key
    fits the prefix, because strings are dictionary ranks."""
    if rank_table is not None and len(rank_table):
        # (an empty dictionary, of a scan no row passed, has no rank to
        # gather: its codes order as they are)
        y = jnp.asarray(rank_table, dtype=jnp.int64)[data]
    elif jnp.issubdtype(data.dtype, jnp.floating):
        bits = jax.lax.bitcast_convert_type(
            data.astype(jnp.float64), jnp.uint64)
        sign = (bits >> 63) == 1
        u = jnp.where(sign, ~bits, bits | jnp.uint64(0x8000000000000000))
        y = (u ^ jnp.uint64(0x8000000000000000)).astype(jnp.int64)
    else:
        y = data.astype(jnp.int64)
    if not ascending:
        y = ~y  # bitwise-not reverses integer order without overflow
    if validity is not None:
        imin = jnp.iinfo(jnp.int64).min
        imax = jnp.iinfo(jnp.int64).max
        y = jnp.where(validity, y, imin if nulls_first else imax)
    return y


# ---- misc ------------------------------------------------------------------


def limit_mask(row_mask: jnp.ndarray, n: int, offset: int = 0) -> jnp.ndarray:
    """Keep only live rows with live-rank in [offset, offset+n)."""
    rank = jnp.cumsum(row_mask.astype(jnp.int64)) - 1
    return row_mask & (rank >= offset) & (rank < offset + n)


def take_permutation(data: jnp.ndarray, perm: jnp.ndarray) -> jnp.ndarray:
    return data[perm]


@partial(jax.jit, static_argnums=())
def count_live(row_mask: jnp.ndarray) -> jnp.ndarray:
    return row_mask.sum(dtype=jnp.int64)


def bucket(n: int, multiple: int = 1024) -> int:
    """Round up to a capacity bucket (jit-cache friendliness)."""
    if n <= 0:
        return multiple
    return ((n + multiple - 1) // multiple) * multiple


#: significant bits of ``relative_bucket``: its step is at most an eighth
RELATIVE_BUCKET_BITS = 3


def relative_bucket(n: int, floor: int = 1024) -> int:
    """``n`` rounded up to RELATIVE_BUCKET_BITS significant bits, and
    never to a finer step than ``floor`` (below 16,384 it is
    ``bucket(n)``): the capacity of a count the data moves, a join's
    matches or the rows a scan keeps of some string values
    (``io/datasource.py``). A relative step where ``bucket``'s is
    absolute: a count that
    another seed of the same data moves by a fraction of a percent lands
    where the first one did, and the program compiled for it is found
    again, at under an eighth of padding."""
    n = max(1, int(n))
    step = max(floor, 1 << max(0, n.bit_length() - 1 - RELATIVE_BUCKET_BITS))
    return -(-n // step) * step


def group_slots(groups: int, rows: int) -> int:
    """Output slots of a sort-based aggregate of ``groups`` observed
    groups over ``rows`` rows' capacity: ``bucket(groups, 256)``, or,
    where the groups pass a quarter of the rows, ``rows``. No more groups
    than rows can exist, and a count the data moves (the orders a join
    keeps, by the seed) then changes no program, at most 4x of slots that
    hold a fraction of the execution's work; an aggregate of few groups
    over many rows (100,000 suppliers of 2.4 M lines) keeps its fine
    bucket."""
    return rows if groups * 4 > rows else bucket(max(1, groups), 256)


def key_packing(lo: int, hi: int, quantum: int = 1024) -> Tuple[int, int]:
    """(offset, range) that packs an integer join key observed in
    ``[lo, hi]``: the offset rounded down and the range up to a multiple
    of ``quantum``, so that a bound the data moves a little (one
    customer key more or less) packs, and compiles, the same way."""
    if lo > hi:
        lo = hi = 0
    offset = (lo // quantum) * quantum
    return offset, bucket(hi - offset + 1, quantum)
