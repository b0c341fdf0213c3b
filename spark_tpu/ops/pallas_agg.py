"""Pallas TPU kernels: one-pass segmented (grouped) reductions.

The hot aggregation path in physical/kernels.py handles small group
counts (K <= 64) with masked dense reductions (`_masked_reduce`): one
variadic reduction for G slots, so `ceil(K / G)` passes over the data
column from HBM (G = 8 slots of a 64-bit column, 16 of a 32-bit one;
PR 29). This kernel makes ONE pass whatever K is: the column is
streamed HBM -> VMEM in (block_rows, 128) tiles, per-group partial sums
accumulate in a VMEM-resident (K, 128) lane-parallel accumulator, and
the final cross-lane reduce of the tiny (K, 128) result happens in
plain XLA outside the kernel.

Reference peer: the Tungsten hash-aggregate inner loop
(sql/core/.../aggregate/TungstenAggregationIterator.scala:82 probing
BytesToBytesMap.java:497) — rebuilt as a blocked streaming kernel
because on TPU the accumulator fits VMEM and "probing" is a vector
compare, not a pointer chase.

Constraints (checked by ``pallas_available``): float32 data (TPU
Pallas has no f64; the engine's f64 columns keep the XLA path),
2 <= K <= 1024 (VMEM accumulator budget), data length padded to the
block size by the wrapper. ``interpret`` is an argument that tests pass
(or SPARK_TPU_PALLAS=interpret for a test that goes through the engine);
it is never inferred from the backend. tests/test_pallas_ops.py checks
results in interpret mode against numpy; tests/test_tpu_aot_compile.py
compiles the kernels for a described v5e with x64 on, as the session
runs them.

Not measured on the current chip. The selection in physical/kernels.py
(K <= 64 XLA fused masked reductions, 64 < K <= 1024 this kernel on
TPU, else scatter/sort paths) dates from a device set-up that is gone;
ROADMAP.md A8 / C12 name the probe that would settle it.

Accumulator family (same tiling): Count (``maybe_pallas_seg_count`` —
``pallas_seg_sum`` over the mask with an exact-int epilogue) and
Min/Max (``pallas_seg_minmax`` — sentinel-carried instead of
zero-carried, so masked-out rows and lane padding cannot win the
reduction). The engine sends no float *sum* here: float sums must be
byte-stable across the static and the capacity-compacted layout of the
same rows, which only the row-ordered scatter-add gives
(physical/kernels.seg_sum).
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from spark_tpu import metrics

_BLOCK_ROWS = 64          # (64, 128) tiles: 8k elements per grid step
_LANES = 128
_MAX_K = 1024             # (1024, 128) f32 accumulator = 512 KiB VMEM


def pallas_available(dtype, num_segments: int,
                     platform: Optional[str] = None) -> bool:
    """Whether the Pallas path applies: supported dtype,
    accumulator-friendly K, and a TPU backend. SPARK_TPU_PALLAS=0 turns
    the kernels off; SPARK_TPU_PALLAS=interpret (tests only) runs them
    in interpret mode on whatever backend there is."""
    mode = os.environ.get("SPARK_TPU_PALLAS", "auto")
    if mode == "0":
        return False
    if not (2 <= num_segments <= _MAX_K):
        return False
    if jnp.dtype(dtype) != jnp.dtype(jnp.float32):
        return False
    if mode == "interpret":
        return True
    if platform is None:
        platform = jax.default_backend()
    return platform == "tpu"


# The session runs with jax_enable_x64 on, where a Python int traces as
# int64 — which Mosaic does not lower (RecursionError in its
# convert_element_type rule for a loop index, "failed to legalize
# func.return (i32, i64)" for an index map). Every index the kernels
# handle is therefore typed int32 here, explicitly.

def _tile_index(i):
    return i, jnp.int32(0)


def _acc_index(i):
    return jnp.int32(0), jnp.int32(0)


def _seg_loop(num_segments: int, body) -> None:
    jax.lax.fori_loop(jnp.int32(0), jnp.int32(num_segments), body,
                      jnp.int32(0))


def _kernel(seg_ref, data_ref, mf_ref, acc_ref, *, num_segments: int):
    """One grid step: accumulate this (B, 128) tile's per-group,
    per-lane partial sums into the (K, 128) output accumulator."""
    from jax.experimental import pallas as pl

    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    seg = seg_ref[:]                      # (B, 128) int32
    data = data_ref[:]                    # (B, 128) f32
    mf = mf_ref[:]                        # (B, 128) f32 (0/1 mask)
    masked = data * mf

    def body(k, carry):
        sel = (seg == k).astype(masked.dtype)          # (B, 128)
        part = jnp.sum(sel * masked, axis=0, keepdims=True)  # (1, 128)
        prev = acc_ref[pl.ds(k, 1), :]
        acc_ref[pl.ds(k, 1), :] = prev + part
        return carry

    _seg_loop(num_segments, body)


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "interpret",
                                    "exact_int"))
def pallas_seg_sum(data: jnp.ndarray, seg: jnp.ndarray,
                   mask: jnp.ndarray, num_segments: int,
                   interpret: bool = False,
                   exact_int: bool = False) -> jnp.ndarray:
    """Grouped sum of ``data`` (1-D) by segment id in ONE pass over HBM.
    Rows with mask False (or seg outside [0, K)) contribute nothing.
    Returns float32[num_segments], or int64 when ``exact_int`` (counts:
    per-lane accumulators hold exact integers up to 2^24, so the final
    cross-lane reduce happens in int64)."""
    from jax.experimental import pallas as pl

    n = data.shape[0]
    block = _BLOCK_ROWS * _LANES
    pad = (-n) % block
    f32 = jnp.float32
    d = jnp.pad(data.astype(f32), (0, pad))
    s = jnp.pad(seg.astype(jnp.int32), (0, pad),
                constant_values=num_segments)  # out of range: ignored
    m = jnp.pad(mask.astype(f32), (0, pad))
    rows = (n + pad) // _LANES
    d2 = d.reshape(rows, _LANES)
    s2 = s.reshape(rows, _LANES)
    m2 = m.reshape(rows, _LANES)
    grid = rows // _BLOCK_ROWS

    spec = pl.BlockSpec((_BLOCK_ROWS, _LANES), _tile_index)
    acc = pl.pallas_call(
        functools.partial(_kernel, num_segments=num_segments),
        grid=(grid,),
        in_specs=[spec, spec, spec],
        out_specs=pl.BlockSpec((num_segments, _LANES), _acc_index),
        out_shape=jax.ShapeDtypeStruct((num_segments, _LANES), f32),
        interpret=interpret,
    )(s2, d2, m2)
    if exact_int:
        return acc.astype(jnp.int64).sum(axis=1)
    return acc.sum(axis=1)


def _minmax_kernel(seg_ref, data_ref, mf_ref, acc_ref, *,
                   num_segments: int, is_max: bool):
    """One grid step of the segmented min/max: masked-out rows carry the
    identity sentinel (not zero — zero would win min over positives),
    so padding and dead rows can never beat a live value."""
    from jax.experimental import pallas as pl

    ident = jnp.float32(-jnp.inf if is_max else jnp.inf)
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        acc_ref[:] = jnp.full_like(acc_ref, ident)

    seg = seg_ref[:]                      # (B, 128) int32
    data = data_ref[:]                    # (B, 128) f32
    live = mf_ref[:] > 0                  # (B, 128) bool
    pick = jnp.maximum if is_max else jnp.minimum

    def body(k, carry):
        sel = live & (seg == k)                        # (B, 128)
        cand = jnp.where(sel, data, ident)
        if is_max:
            part = jnp.max(cand, axis=0, keepdims=True)
        else:
            part = jnp.min(cand, axis=0, keepdims=True)
        prev = acc_ref[pl.ds(k, 1), :]
        acc_ref[pl.ds(k, 1), :] = pick(prev, part)
        return carry

    _seg_loop(num_segments, body)


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "is_max",
                                    "interpret"))
def pallas_seg_minmax(data: jnp.ndarray, seg: jnp.ndarray,
                      mask: jnp.ndarray, num_segments: int,
                      is_max: bool = False,
                      interpret: bool = False) -> jnp.ndarray:
    """Grouped min (or max) of ``data`` (1-D) by segment id, one pass
    over HBM. Groups with no live row yield the identity (+inf for min,
    -inf for max) — same convention as the XLA kernels' sentinel, so
    the caller's empty-group handling is path-independent."""
    from jax.experimental import pallas as pl

    n = data.shape[0]
    block = _BLOCK_ROWS * _LANES
    pad = (-n) % block
    f32 = jnp.float32
    d = jnp.pad(data.astype(f32), (0, pad))
    s = jnp.pad(seg.astype(jnp.int32), (0, pad),
                constant_values=num_segments)  # out of range: ignored
    m = jnp.pad(mask.astype(f32), (0, pad))
    rows = (n + pad) // _LANES
    d2 = d.reshape(rows, _LANES)
    s2 = s.reshape(rows, _LANES)
    m2 = m.reshape(rows, _LANES)
    grid = rows // _BLOCK_ROWS

    spec = pl.BlockSpec((_BLOCK_ROWS, _LANES), _tile_index)
    acc = pl.pallas_call(
        functools.partial(_minmax_kernel, num_segments=num_segments,
                          is_max=is_max),
        grid=(grid,),
        in_specs=[spec, spec, spec],
        out_specs=pl.BlockSpec((num_segments, _LANES), _acc_index),
        out_shape=jax.ShapeDtypeStruct((num_segments, _LANES), f32),
        interpret=interpret,
    )(s2, d2, m2)
    # cross-lane reduce outside the kernel: sentinel lanes lose
    return acc.max(axis=1) if is_max else acc.min(axis=1)


# engine-side selection bound: below this the XLA fused multi-reduce
# serves the aggregate (physical/kernels._MASKED_SEG_LIMIT)
MIN_ENGINE_K = 64


def _engine_mode(dtype, num_segments: int, rows: int) -> Optional[bool]:
    """None when the engine should keep its XLA path, else the
    ``interpret`` flag to run the kernel with: False (compiled — the
    only answer outside tests) unless SPARK_TPU_PALLAS=interpret."""
    if num_segments <= MIN_ENGINE_K or rows >= (1 << 31) or \
            not pallas_available(dtype, num_segments):
        return None
    return os.environ.get("SPARK_TPU_PALLAS") == "interpret"


def _note(op: str, num_segments: int, rows: int, interpret: bool) -> None:
    # trace-time event: says which path a query's program was built from
    metrics.record("pallas", op=op, k=int(num_segments), rows=int(rows),
                   interpret=interpret)


def maybe_pallas_seg_count(seg, mask, num_segments: int):
    """Engine entry point for grouped counts (exact int64 result), or
    None (caller keeps the XLA kernels). Per-(group, lane) f32
    accumulators stay exact below 2^24 increments, i.e. up to 2^31 rows
    — beyond any single static batch."""
    interpret = _engine_mode(np.float32, num_segments, seg.shape[0])
    if interpret is None:
        return None
    _note("count", num_segments, seg.shape[0], interpret)
    return pallas_seg_sum(mask.astype(jnp.float32), seg, mask,
                          num_segments, interpret=interpret,
                          exact_int=True)


def _maybe_minmax(data, seg, mask, num_segments: int, is_max: bool):
    interpret = _engine_mode(data.dtype, num_segments, seg.shape[0])
    if interpret is None:
        return None
    _note("max" if is_max else "min", num_segments, seg.shape[0], interpret)
    return pallas_seg_minmax(data, seg, mask, num_segments,
                             is_max=is_max, interpret=interpret)


def maybe_pallas_seg_min(data, seg, mask, num_segments: int):
    """Engine entry point for float32 grouped min: Pallas when it
    qualifies, else None. Empty groups come back +inf, matching the
    XLA sentinel convention in physical/kernels.seg_min."""
    return _maybe_minmax(data, seg, mask, num_segments, False)


def maybe_pallas_seg_max(data, seg, mask, num_segments: int):
    """Engine entry point for float32 grouped max (empty groups -inf)."""
    return _maybe_minmax(data, seg, mask, num_segments, True)
