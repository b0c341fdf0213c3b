"""Chip probe behind kernels.seg_sum's ladder (PR 27; PERF.md section 6).

What one exact (int64 / scaled-decimal) grouped sum costs on the attached
chip at q1's shape, by rung, each inside a jitted function with x64 on:

  chiprun -- python tools/probe_seg_sum.py            # all of it, ~5 min
  chiprun -- python tools/probe_seg_sum.py --small-k  # K <= 64 only

Every variant is written out from primitives here, so the probe reads the
same after the engine's own choice changes. Each line of output is one
JSON object; results are also checked bit for bit against numpy int64.
Refuses to run without a TPU (a CPU time is not a device time).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from spark_tpu.physical import kernels as K  # noqa: E402

ROWS = 6_001_664  # lineitem's 6,000,647 rows in the engine's 1,024 bucket


def _limbs(data, red):
    """Three 21-bit limbs carried as float64, each summed by ``red``."""
    m21 = (1 << 21) - 1
    parts = []
    for sh in (0, 21, 42):
        limb = (data >> sh) & m21 if sh < 42 else data >> 42
        parts.append(red(limb.astype(jnp.float64)).astype(jnp.int64))
    return parts[0] + (parts[1] << 21) + (parts[2] << 42)


def _scatter(x, seg, mask, k):
    masked = jnp.where(mask, x, jnp.zeros((), x.dtype))
    return jax.ops.segment_sum(masked, seg, num_segments=k)


def _masked(x, seg, mask, k):
    return K._masked_reduce(x, seg, mask, k, jnp.sum, jnp.zeros((), x.dtype))


def _cumsum(x, seg, mask, k):
    masked = jnp.where(mask, x, jnp.zeros((), x.dtype))
    return K._sorted_seg_sum(masked, seg, k)


def variants(k, sorted_seg):
    """name -> fn(data, seg, mask): each rung on the int64 column itself
    and on its three f64 limbs."""
    rungs = {"scatter": _scatter}
    if k <= K._MASKED_SEG_LIMIT:
        rungs["masked"] = _masked
    if sorted_seg:
        rungs["cumsum"] = _cumsum
    out = {}
    for name, red in rungs.items():
        out[f"limb_{name}"] = lambda d, s, m, red=red: _limbs(
            d, lambda x: red(x, s, m, k))
        out[f"int64_{name}"] = lambda d, s, m, red=red: red(d, s, m, k)
    return out


def _time(fn, args, reps):
    """(blocking median ms, pipelined mean ms, compile s, result)."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    compile_s = time.perf_counter() - t0
    jax.block_until_ready(fn(*args))
    each = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        each.append((time.perf_counter() - t0) * 1e3)
    t0 = time.perf_counter()
    outs = [fn(*args) for _ in range(reps)]
    jax.block_until_ready(outs)
    piped = (time.perf_counter() - t0) * 1e3 / reps
    return statistics.median(each), piped, compile_s, out


def _reference(data, seg, mask, k):
    ref = np.zeros(k, np.int64)
    np.add.at(ref, seg[mask], data[mask])
    return ref


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--small-k", action="store_true")
    ap.add_argument("--rows", type=int, default=ROWS)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal only: times mean nothing")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.allow_cpu:
        print("probe_seg_sum: no TPU", file=sys.stderr)
        return 2
    print(json.dumps({"device": dev.device_kind, "rows": a.rows}))
    rng = np.random.default_rng(27)
    n = a.rows
    cols = [rng.integers(-(1 << 40), 1 << 40, n, dtype=np.int64)
            for _ in range(5)]
    mask = rng.random(n) < 0.98
    cases = [(6, False), (64, False)]
    if not a.small_k:
        cases += [(200, False), (200, True), (100_000, False),
                  (100_000, True)]
    d_cols = [jnp.asarray(c) for c in cols]
    d_mask = jnp.asarray(mask)
    bad = 0
    for k, sorted_seg in cases:
        seg = rng.integers(0, k, n, dtype=np.int64)
        if sorted_seg:
            seg.sort()
        d_seg = jnp.asarray(seg)
        ref = _reference(cols[0], seg, mask, k)
        for name, fn in variants(k, sorted_seg).items():
            slow = "scatter" in name
            ms, piped, comp, out = _time(jax.jit(fn),
                                         (d_cols[0], d_seg, d_mask),
                                         3 if slow else 20)
            equal = bool(np.array_equal(np.asarray(out), ref))
            bad += not equal
            print(json.dumps({"k": k, "sorted": sorted_seg, "sums": 1,
                              "variant": name, "ms": round(ms, 3),
                              "pipelined_ms": round(piped, 3),
                              "compile_s": round(comp, 2),
                              "bit_equal": equal}), flush=True)
        if k > K._MASKED_SEG_LIMIT:
            continue
        # q1's stage: five sums and a count over one (seg, mask), one jit
        refs = [_reference(c, seg, mask, k) for c in cols]
        for name in ("limb_masked", "int64_masked"):
            one = variants(k, False)[name]

            def five(cs, s, m, one=one):
                return ([one(c, s, m) for c in cs]
                        + [K.seg_count(s, m, k)])

            ms, piped, comp, out = _time(jax.jit(five),
                                         (d_cols, d_seg, d_mask), 20)
            equal = all(np.array_equal(np.asarray(o), r)
                        for o, r in zip(out, refs))
            bad += not equal
            print(json.dumps({"k": k, "sorted": False, "sums": 5,
                              "variant": name, "ms": round(ms, 3),
                              "pipelined_ms": round(piped, 3),
                              "compile_s": round(comp, 2),
                              "bit_equal": equal}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
