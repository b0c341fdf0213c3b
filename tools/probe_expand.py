"""Chip probe behind kernels.expand_join_pairs (PERF.md section 6).

What a join's match expansion costs on the attached chip at the two
shapes of tpch_sf10_q3, each variant alone inside a jitted function with
x64 on, as the engine hands the expansion its ranges (the dense table's
int64 ``lo`` / ``hi``, an int32 build permutation):

  join 1: the customer scan's 327,680 probe rows -> 1,572,864 pairs
          (200,000 rows with orders, 7.8 of them a row; the orders' index
          of 7,775,232 rows)
  join 2: the first join's 1,572,864 rows -> 327,680 pairs (120,000 rows
          with unshipped lines, 1 to 7 of them; lineitem's index)

  python tools/probe_expand.py     # on a one-chip TPU host, ~5 min cold

``--crossover`` instead times ``nonzero`` against ``binary`` at 1,572,864
probe rows and fewer pairs (a tenth down to a 192nd of the rows):
where the expansion stops scattering every probe row and searches.

Variants, every one written out from primitives here, so the probe reads
the same after the engine's own choice changes:

  binary    the expansion's ``search`` method: int32 offsets, a binary
            search (``scan``) of every pair, then ``lo - offsets`` read
            at p: log2(probe rows) rounds of gathers over the pairs
  search    the engine's first expansion: int64 offsets, a co-sorted
            ``searchsorted`` of every pair (its ``sort`` method at these
            shapes), then ``offsets[p]`` and ``lo[p]`` read a pair
  starts    int32 offsets; p and ``lo - offsets`` at p as running sums:
            two scatter-adds of the probe rows' steps at their offsets
            (sorted, duplicates where rows have no match), two cumsums.
            Beside a sorted aggregate at Q3's SF10 shapes the two
            scatter-adds crash the v5e compiler
            (tools/compile_q3_groups_v5e.py)
  stacked   the same steps as ONE scatter-add of [2, n] into [2, pairs]
            and one cumsum along the pairs
  nonzero   the expansion's ``starts`` method: only the rows with a
            match scattered (unique indices, the others sent out of
            range), their row forward-filled by a cummax, and
            ``lo - offsets`` read at it: one int32 gather of a
            probe-length array a pair. (Packing the row and its base in
            one int64 for the cummax reads no such array, but the chip's
            compiler took 53 and 166 s over it at the two shapes, against
            7 s for ``starts``: a cold first execution pays that.)
  hybrid    p as ``nonzero``, ``lo - offsets`` as ``starts``: one
            scatter-add of its steps and a cumsum, no read of it a pair
            (not in the default run)

Each line of output is one JSON object (blocking median ms, pipelined
mean ms, compile s); every variant's pairs are checked bit for bit
against a numpy ``np.repeat`` reference. Refuses to run without a TPU
(a CPU time is not a device time); ``--allow-cpu --scale 0.01``
rehearses it.
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

# (probe rows, pairs, build rows, rows with a match, matches of such a row)
SHAPES = {
    "join1": (327_680, 1_572_864, 7_775_232, 200_000, "poisson"),
    "join2": (1_572_864, 327_680, 32_440_320, 120_000, "lines"),
}
#: join 2's probe rows against fewer pairs: probe rows / pairs of 9.6 to 192
CROSSOVER = {f"ratio{1_572_864 // pairs}": (
    1_572_864, pairs, 32_440_320, pairs * 3 // 8, "lines")
    for pairs in (163_840, 65_536, 32_768, 16_384, 8_192)}
SHAPES.update(CROSSOVER)


def _case(name, scale, rng):
    """(lo int64, hi int64, perm int32, pairs, numpy reference)."""
    n, total, build, matched, kind = SHAPES[name]
    n, total, build, matched = (max(64, int(x * scale))
                                for x in (n, total, build, matched))
    counts = np.zeros(n, np.int64)
    rows = np.sort(rng.choice(n - n // 12, matched, replace=False))
    if kind == "poisson":   # a customer's orders, customers in scan order
        counts[rows] = rng.poisson(7.3, matched)
    else:                   # an order's lines shipped after the date
        counts[rows] = 1 + rng.binomial(6, 0.28, matched)
    over = counts.sum() - total
    while over > 0:         # the capacity holds every match
        top = int(np.argmax(counts))
        cut = min(over, counts[top])
        counts[top] -= cut
        over -= cut
    lo = np.zeros(n, np.int64)
    lo[counts > 0] = rng.integers(0, build - 8, int((counts > 0).sum()))
    if kind == "poisson":
        lo[counts > 0] = np.sort(lo[counts > 0])
    perm = rng.permutation(build).astype(np.int32)
    s = int(counts.sum())
    offsets = np.cumsum(counts) - counts
    ref_p = np.repeat(np.arange(n), counts)
    ref_b = perm[np.repeat(lo - offsets, counts) + np.arange(s)]
    return lo, lo + counts, perm, total, (ref_p, ref_b, s)


def search(perm, lo, hi, total):
    counts = hi - lo
    offsets = jnp.cumsum(counts) - counts
    j = jnp.arange(total)
    p = jnp.searchsorted(offsets, j, side="right", method="sort") - 1
    p = jnp.clip(p, 0, counts.shape[0] - 1)
    pos = lo[p] + (j - offsets[p])
    b = perm[jnp.clip(pos, 0, perm.shape[0] - 1)]
    return p, b, j < offsets[-1] + counts[-1]


def binary(perm, lo, hi, total):
    counts, offsets, base = _int32_runs(lo, hi)
    j = jnp.arange(total, dtype=jnp.int32)
    p = jnp.clip(jnp.searchsorted(offsets, j, side="right", method="scan")
                 - 1, 0, counts.shape[0] - 1)
    return _finish(perm, p, base[p], counts, total)


def _int32_runs(lo, hi):
    lo = lo.astype(jnp.int32)
    counts = hi.astype(jnp.int32) - lo
    offsets = jnp.cumsum(counts) - counts
    return counts, offsets, lo - offsets


def _finish(perm, p, run_base, counts, total):
    j = jnp.arange(total, dtype=jnp.int32)
    b = perm[jnp.clip(run_base + j, 0, perm.shape[0] - 1)]
    return p, b, j < counts.sum(dtype=jnp.int32)


def starts(perm, lo, hi, total):
    counts, offsets, base = _int32_runs(lo, hi)
    n = counts.shape[0]

    def running(steps):
        return jnp.cumsum(jnp.zeros(total, jnp.int32).at[offsets].add(
            steps, mode="drop", indices_are_sorted=True))

    p = running((jnp.arange(n) > 0).astype(jnp.int32))
    return _finish(perm, p, running(jnp.diff(base, prepend=0)), counts,
                   total)


def stacked(perm, lo, hi, total):
    counts, offsets, base = _int32_runs(lo, hi)
    n = counts.shape[0]
    steps = jnp.stack([(jnp.arange(n) > 0).astype(jnp.int32),
                       jnp.diff(base, prepend=0)])
    fill = jnp.zeros((2, total), jnp.int32).at[:, offsets].add(
        steps, mode="drop", indices_are_sorted=True)
    p, run_base = jnp.cumsum(fill, axis=1)
    return _finish(perm, p, run_base, counts, total)


def nonzero(perm, lo, hi, total):
    counts, offsets, base = _int32_runs(lo, hi)
    n = counts.shape[0]
    q = jnp.arange(n, dtype=jnp.int32)
    at = jnp.where(counts > 0, offsets, total + q)     # distinct, dropped
    p = jax.lax.cummax(jnp.zeros(total, jnp.int32).at[at].set(
        q, mode="drop", unique_indices=True))
    return _finish(perm, p, base[p], counts, total)


def hybrid(perm, lo, hi, total):
    counts, offsets, base = _int32_runs(lo, hi)
    n = counts.shape[0]
    q = jnp.arange(n, dtype=jnp.int32)
    at = jnp.where(counts > 0, offsets, total + q)
    p = jax.lax.cummax(jnp.zeros(total, jnp.int32).at[at].set(
        q, mode="drop", unique_indices=True))
    run_base = jnp.cumsum(jnp.zeros(total, jnp.int32).at[offsets].add(
        jnp.diff(base, prepend=0), mode="drop", indices_are_sorted=True))
    return _finish(perm, p, run_base, counts, total)


VARIANTS = {"binary": binary, "hybrid": hybrid, "search": search,
            "starts": starts, "stacked": stacked, "nonzero": nonzero}


def _time(fn, args, reps):
    """(blocking median ms, pipelined mean ms, compile s, result)."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    compile_s = time.perf_counter() - t0
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


def _equal(out, ref, n, build):
    """Live pairs bit-equal to numpy; padding pairs masked and in range."""
    ref_p, ref_b, s = ref
    p, b, mask = (np.asarray(x) for x in out)
    return bool(np.array_equal(p[:s], ref_p) and np.array_equal(b[:s], ref_b)
                and mask[:s].all() and not mask[s:].any()
                and ((p >= 0) & (p < n)).all()
                and ((b >= 0) & (b < build)).all())


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0,
                    help="every size times this (a rehearsal's)")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--variants", default="search,starts,stacked,nonzero")
    ap.add_argument("--crossover", action="store_true",
                    help="nonzero against binary at fewer pairs a row")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal only: times mean nothing")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.allow_cpu:
        print("probe_expand: no TPU", file=sys.stderr)
        return 2
    print(json.dumps({"device": dev.device_kind, "scale": a.scale}),
          flush=True)
    rng = np.random.default_rng(41)
    bad = 0
    shapes, variants = ("join1", "join2"), a.variants.split(",")
    if a.crossover:
        shapes, variants = tuple(CROSSOVER), ["binary", "nonzero"]
    for shape in shapes:
        lo, hi, perm, total, ref = _case(shape, a.scale, rng)
        args = (jnp.asarray(perm), jnp.asarray(lo), jnp.asarray(hi))
        for name in variants:
            fn = jax.jit(VARIANTS[name], static_argnums=3)
            try:
                ms, piped, comp, out = _time(
                    lambda *x: fn(*x, total), args, a.reps)
            except Exception as e:  # a refused variant is a result too
                bad += 1
                print(json.dumps({"shape": shape, "variant": name,
                                  "error": repr(e)[:300]}), flush=True)
                continue
            equal = _equal(out, ref, lo.shape[0], perm.shape[0])
            bad += not equal
            print(json.dumps({
                "shape": shape, "probe_rows": int(lo.shape[0]),
                "pairs": total, "live_pairs": ref[2],
                "matched_rows": int((hi > lo).sum()),
                "build_rows": int(perm.shape[0]), "variant": name,
                "ms": round(ms, 3), "pipelined_ms": round(piped, 3),
                "compile_s": round(comp, 2), "bit_equal": equal}),
                flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
