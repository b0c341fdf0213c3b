"""Chip probe behind kernels.seg_sum's ladder (PR 27, PR 29, PR 36;
PERF.md section 6).

What one exact (int64 / scaled-decimal) grouped sum costs on the attached
chip at q1's shape, by rung, each inside a jitted function with x64 on,
and what the masked rung costs by the number of passes it makes over its
column (K reductions of one slot each against ceil(K / G) variadic
reductions of G slots; int64 sum, count, f32 min; SF1's and SF10's rows):

  chiprun -- python tools/probe_seg_sum.py            # the rungs, ~5 min
  chiprun -- python tools/probe_seg_sum.py --small-k  # K <= 64 only
  chiprun --timeout 1800 -- python tools/probe_seg_sum.py --passes  # ~6 min
  chiprun -- python tools/probe_seg_sum.py --narrow   # PR 31, ~2 min
  chiprun -- python tools/probe_seg_sum.py --carrier  # PR 36, ~3 min
  chiprun -- python tools/probe_seg_sum.py --group-sort  # PR 38, ~2 min

``--narrow``: the K = 6 sum (and q1's five sums and a count) over values
that fit 32 bits, from int64 parameters against int32 parameters widened
first thing in the program, at SF10's rows: what the chip's split of an
int64 parameter into u32 pairs costs an execution, and what a scan that
keeps such a column as int32 saves.

``--carrier``: what the f64-limb carrier of an int64 sum cost where the
engine still used it before PR 36. K == 1 (q6's and q14's global sums):
a plain int64 ``jnp.sum`` against three f64 limb sums, at q14's, q6's
and SF10's rows, a call at a time and looped inside one program (the
device's own time: a call of either costs the host more than the sum). And Q15's aggregate at ``tpch_sf10_q15_revenue``'s own
shape (2,421,760 rows sorted by group, K = 100,096, int32 ids): three
limb scatter-adds, one int64 scatter-add, one int64 cumsum; then the sum
with the COUNT beside it, their segment bounds searched twice or once.

``--group-sort``: the sort half of Q15's aggregate at the cell's shape
(2,421,760 rows, one int64 key of 1..100,000 suppliers, two int64
payloads, 466 dead rows; the three arrive int32 and are widened in the
program, as a narrowed scan's are): (a) ``lexsort_permutation`` and a
gather of every array by it, the engine until PR 38; (b) ONE stable sort
of the rows with the payloads as its operands; (c) one stable argsort of
``(~live, key)`` and one gather a column; each with the change-flag
group ids.

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
ROWS_SF10 = 59_990_016  # SF10's 59,989,771
# the rows behind the pushed date filters of Q14 and Q6 at SF1, and of
# Q15 at SF10 with its group slots (100,000 suppliers in 256-slot buckets)
ROWS_Q14, ROWS_Q6 = 80_896, 121_856
ROWS_Q15, K_Q15, LIVE_Q15 = 2_421_760, 100_096, 2_421_294


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


def _k_passes(x, seg, mask, k, red, init):
    """K reductions of one slot each: K passes over ``x``."""
    fill = jnp.asarray(init, x.dtype)
    return jnp.stack([red(jnp.where(mask & (seg == j), x, fill))
                      for j in range(k)])


def _masked(x, seg, mask, k):
    return _k_passes(x, seg, mask, k, jnp.sum, 0)


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


def _one_pass(x, seg, mask, k, g, combine, init):
    """ceil(k / g) variadic reductions of up to g slots each."""
    init = jnp.asarray(init, x.dtype)
    cols = []
    for lo in range(0, k, g):
        js = range(lo, min(lo + g, k))
        cols += jax.lax.reduce(
            tuple(jnp.where(mask & (seg == j), x, init) for j in js),
            (init,) * len(js),
            lambda a, b: tuple(combine(p, q) for p, q in zip(a, b)),
            dimensions=(0,))
    return jnp.stack(cols)


# op -> (column kind, combine, init, the K-pass form's reduction, numpy's)
PASS_OPS = {
    "sum_int64": ("int64", jnp.add, 0, jnp.sum, np.add),
    "count": ("ones", jnp.add, 0, jnp.sum, np.add),
    "min_f32": ("f32", jnp.minimum, np.inf, jnp.min, np.minimum),
}
PASS_KS = (6, 16, 64)
PASS_GS = (2, 4, 8, 16, 32)


def _pass_variant(op, k, g):
    """fn(data, seg, mask); g == 0 is one reduction a slot (K passes)."""
    kind, combine, init, red, _ = PASS_OPS[op]

    def fn(x, seg, mask):
        if kind == "ones":  # as kernels.seg_count: the mask as int64
            x = mask.astype(jnp.int64)
        if g:
            return _one_pass(x, seg, mask, k, g, combine, init)
        return _k_passes(x, seg, mask, k, red, init)

    return fn


def _pass_reference(op, x, seg, mask, k):
    kind, _, init, _, at = PASS_OPS[op]
    if kind == "ones":
        x = np.ones(len(seg), np.int64)
    ref = np.full(k, init, x.dtype)
    at.at(ref, seg[mask], x[mask])
    return ref


def _measure(line, fn, args, reps, refs):
    """Time one variant and print its line; True if it failed (the
    compiler's refusal is a line too) or differs from ``refs``."""
    try:
        ms, piped, comp, out = _time(jax.jit(fn), args, reps)
    except Exception as e:
        line["error"] = f"{type(e).__name__}: {e}"[:300]
        equal = False
    else:
        equal = all(np.array_equal(np.asarray(o), r)
                    for o, r in zip(out, refs))
        line.update(ms=round(ms, 3), pipelined_ms=round(piped, 3),
                    compile_s=round(comp, 2), bit_equal=equal)
    print(json.dumps(line), flush=True)
    return not equal


def probe_passes(rows_list, reps=20):
    """The masked rung by pass count. Returns the number of wrong or
    failed variants."""
    bad = 0
    for n in rows_list:
        rng = np.random.default_rng(29)
        cols = [rng.integers(-(1 << 40), 1 << 40, n, dtype=np.int64)
                for _ in range(5)]
        f32 = rng.standard_normal(n).astype(np.float32)
        mask = rng.random(n) < 0.98
        d_cols = [jnp.asarray(c) for c in cols]
        d_f32, d_mask = jnp.asarray(f32), jnp.asarray(mask)
        for k in PASS_KS:
            seg = rng.integers(0, k, n, dtype=np.int64)
            d_seg = jnp.asarray(seg)
            for op, (kind, *_rest) in PASS_OPS.items():
                host, dev = ((f32, d_f32) if kind == "f32"
                             else (cols[0], d_cols[0]))
                ref = _pass_reference(op, host, seg, mask, k)
                for g in [0] + sorted({min(g, k) for g in PASS_GS}):
                    one = _pass_variant(op, k, g)
                    bad += _measure(
                        {"rows": n, "op": op, "k": k, "g": g,
                         "passes": -(-k // g) if g else k},
                        lambda x, s, m, one=one: [one(x, s, m)],
                        (dev, d_seg, d_mask), reps, [ref])
            if k != 6:
                continue
            # q1's stage: five sums and a count over one (seg, mask)
            refs = ([_pass_reference("sum_int64", c, seg, mask, k)
                     for c in cols]
                    + [_pass_reference("count", cols[0], seg, mask, k)])
            for g in (0, k):
                def stage(cs, s, m, g=g):
                    return ([_pass_variant("sum_int64", k, g)(c, s, m)
                             for c in cs]
                            + [_pass_variant("count", k, g)(cs[0], s, m)])

                bad += _measure(
                    {"rows": n, "op": "q1_stage", "k": k, "g": g,
                     "passes": 6 * (1 if g else k)},
                    stage, (d_cols, d_seg, d_mask), reps, refs)
    return bad


def probe_narrow(rows, reps=20):
    """One pass of K = 6 slots over columns whose values fit int32, as
    int64 parameters and as int32 parameters the program widens."""
    k = 6
    rng = np.random.default_rng(31)
    # l_extendedprice's range in TPC-H, unscaled: the widest of q1's four
    cols = [rng.integers(0, 10_494_951, rows, dtype=np.int64)
            for _ in range(5)]
    mask = rng.random(rows) < 0.98
    seg = rng.integers(0, k, rows, dtype=np.int64)
    d_seg, d_mask = jnp.asarray(seg), jnp.asarray(mask)
    refs = ([_pass_reference("sum_int64", c, seg, mask, k) for c in cols]
            + [_pass_reference("count", cols[0], seg, mask, k)])
    one = _pass_variant("sum_int64", k, k)
    count = _pass_variant("count", k, k)

    def stage(cs, s, m):
        cs = [c.astype(jnp.int64) for c in cs]
        return [one(c, s, m) for c in cs] + [count(cs[0], s, m)]

    bad = 0
    for param in ("int64", "int32"):
        d_cols = [jnp.asarray(c.astype(param)) for c in cols]
        bad += _measure(
            {"rows": rows, "op": "sum_int64", "k": k, "param": param},
            lambda x, s, m: [one(x.astype(jnp.int64), s, m)],
            (d_cols[0], d_seg, d_mask), reps, refs[:1])
        bad += _measure(
            {"rows": rows, "op": "q1_stage", "k": k, "param": param},
            stage, (d_cols, d_seg, d_mask), reps, refs)
        del d_cols
    return bad


def _looped_us(fn, args, rounds):
    """Microseconds a reduction takes on the device: ``rounds`` of them in
    ONE program, each over the column plus the round's number so that none
    is hoisted, which leaves the host's dispatch (0.2 ms a call, more than
    a small reduction itself) out of the figure."""
    def many(d, m):
        return jax.lax.fori_loop(
            0, rounds, lambda i, acc: acc + fn(d + i, m)[0],
            jnp.zeros((), jnp.int64))

    ms, _piped, _comp, _out = _time(jax.jit(many), args, 5)
    return round(ms * 1e3 / rounds, 2)


def probe_carrier(reps=20):
    """The two shapes that took the f64 limbs until PR 36."""
    bad = 0
    rng = np.random.default_rng(36)
    for n in (ROWS_Q14, ROWS_Q6, ROWS_SF10):
        # a product of two decimal(12,2): at most about 1.05e9 a row
        data = rng.integers(0, 1_050_000_000, n, dtype=np.int64)
        mask = rng.random(n) < 0.98
        ref = [np.sum(data[mask], dtype=np.int64)[None]]
        args = (jnp.asarray(data), jnp.asarray(mask))

        def plain(x, m):
            return jnp.sum(jnp.where(m, x, jnp.zeros((), x.dtype)))[None]

        for name, fn in (("int64_reduce", plain),
                         ("limb_reduce",
                          lambda d, m: _limbs(d, lambda x: plain(x, m)))):
            rounds = 512 if n < ROWS else 8
            bad += _measure({"rows": n, "k": 1, "variant": name,
                             "looped_us": _looped_us(fn, args, rounds),
                             "rounds": rounds},
                            lambda d, m, fn=fn: [fn(d, m)], args, reps, ref)
        del args
    n, k = ROWS_Q15, K_Q15
    data = rng.integers(0, 1_050_000_000, n, dtype=np.int64)
    mask = np.arange(n) < LIVE_Q15          # sorted: live rows first
    seg = np.sort(rng.integers(0, 100_000, n)).astype(np.int32)
    seg[~mask] = seg[LIVE_Q15 - 1]          # dead rows: the last group's id
    refs = [_reference(data, seg, mask, k),
            _reference(np.ones(n, np.int64), seg, mask, k)]
    args = (jnp.asarray(data), jnp.asarray(seg), jnp.asarray(mask))

    def from_bounds(x, bounds):
        """kernels._sorted_seg_sum with the bounds handed in."""
        starts, ends = bounds
        csum = jnp.cumsum(x, dtype=x.dtype)
        e, s = jnp.clip(ends, 0, n - 1), jnp.clip(starts, 0, n - 1)
        return jnp.where(ends >= starts, csum[e] - csum[s] + x[s],
                         jnp.zeros((), x.dtype))

    def count(s, m):
        return _cumsum(m.astype(jnp.int64), s, m, k)

    def shared(d, s, m):
        bounds = K.seg_bounds(s, k)
        return [from_bounds(jnp.where(m, d, jnp.zeros((), d.dtype)), bounds),
                from_bounds(m.astype(jnp.int64), bounds)]

    sums = variants(k, True)
    for name, fn in (
            [(v, lambda d, s, m, v=v: [sums[v](d, s, m)])
             for v in ("limb_scatter", "int64_scatter", "int64_cumsum")]
            + [(f"{v}+count",
                lambda d, s, m, v=v: [sums[v](d, s, m), count(s, m)])
               for v in ("limb_scatter", "int64_cumsum")]
            + [("int64_cumsum+count_shared_bounds", shared)]):
        bad += _measure({"rows": n, "k": k, "sorted": True, "variant": name},
                        fn, args, 3 if "scatter" in name else reps, refs)
    return bad


def probe_group_sort(reps=20):
    """The sort half of the sorted aggregate, three ways. Returns the
    number of wrong or failed variants."""
    n = ROWS_Q15
    rng = np.random.default_rng(38)
    key = rng.integers(1, 100_001, n).astype(np.int32)
    pays = [rng.integers(0, 1_050_000_000, n).astype(np.int32),
            rng.integers(0, 11, n).astype(np.int32)]
    mask = np.ones(n, bool)
    mask[rng.choice(n, 466, replace=False)] = False
    order = np.lexsort((key, ~mask))            # stable: ties in row order
    skey, smask = key[order].astype(np.int64), mask[order]
    change = np.concatenate([[False], skey[1:] != skey[:-1]]) & smask
    seg = np.cumsum(change, dtype=np.int32)
    refs = [skey, smask, seg, np.int32(seg[-1] + 1)] + [
        p[order].astype(np.int64) for p in pays]
    args = (jnp.asarray(key), jnp.asarray(mask),
            [jnp.asarray(p) for p in pays])

    def ids(k, m):
        return K.group_ids_from_sorted([(k, None)], m)

    def lexsort_gather(k, m, ps):
        k, ps = k.astype(jnp.int64), [p.astype(jnp.int64) for p in ps]
        perm = K.lexsort_permutation([K.SortKey(k, None)], m)
        sk, sm = k[perm], m[perm]
        return [sk, sm, *ids(sk, sm)] + [p[perm] for p in ps]

    def one_sort(k, m, ps):
        k, ps = k.astype(jnp.int64), [p.astype(jnp.int64) for p in ps]
        dead, sk, *sps = jax.lax.sort((~m, k, *ps), num_keys=2,
                                      is_stable=True)
        return [sk, ~dead, *ids(sk, ~dead)] + sps

    def argsort_gather(k, m, ps):
        k, ps = k.astype(jnp.int64), [p.astype(jnp.int64) for p in ps]
        _, _, perm = jax.lax.sort((~m, k, jnp.arange(n, dtype=jnp.int32)),
                                  num_keys=2, is_stable=True)
        sk, sm = k[perm], m[perm]
        return [sk, sm, *ids(sk, sm)] + [p[perm] for p in ps]

    bad = 0
    for name, fn in (("lexsort_gather", lexsort_gather),
                     ("one_sort", one_sort),
                     ("argsort_gather", argsort_gather)):
        bad += _measure({"rows": n, "live": int(mask.sum()), "payloads": 2,
                         "variant": name}, fn, args, reps, refs)
    return bad


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
    ap.add_argument("--passes", action="store_true",
                    help="only the masked rung by pass count, at SF1's "
                    "and SF10's rows (or at --rows)")
    ap.add_argument("--narrow", action="store_true",
                    help="only int64 against widened int32 parameters, "
                    "at SF10's rows (or at --rows)")
    ap.add_argument("--carrier", action="store_true",
                    help="only the f64-limb carrier against int64: K == 1 "
                    "at q14's, q6's and SF10's rows, and Q15's sorted "
                    "aggregate at the benchmark's shape")
    ap.add_argument("--group-sort", action="store_true",
                    help="only the sort half of Q15's sorted aggregate, "
                    "three ways, at the benchmark's shape")
    ap.add_argument("--rows", type=int)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal only: times mean nothing")
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.allow_cpu:
        print("probe_seg_sum: no TPU", file=sys.stderr)
        return 2
    if a.group_sort:
        print(json.dumps({"device": dev.device_kind}))
        return 1 if probe_group_sort() else 0
    if a.carrier:
        print(json.dumps({"device": dev.device_kind}))
        return 1 if probe_carrier() else 0
    if a.narrow:
        rows = a.rows or ROWS_SF10
        print(json.dumps({"device": dev.device_kind, "rows": rows}))
        return 1 if probe_narrow(rows) else 0
    if a.passes:
        rows_list = [a.rows] if a.rows else [ROWS, ROWS_SF10]
        print(json.dumps({"device": dev.device_kind, "rows": rows_list}))
        return 1 if probe_passes(rows_list) else 0
    n = a.rows or ROWS
    print(json.dumps({"device": dev.device_kind, "rows": n}))
    rng = np.random.default_rng(27)
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
                        + [_masked(m.astype(jnp.int64), s, m, k)])

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
