#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the engine still starts on the chip.

One process drives the main path once, through the entry points a user
calls (``SparkSession`` -> ``spark.read.parquet`` -> ``spark.sql(...)`` ->
``collect()``), on one TPU at TPC-H SF1 (6M-row lineitem, exact decimal
money columns), and checks every answer against a plain pandas recompute
over the same parquet files that shares nothing with the engine.

    python chip_smoke.py             one chip: device, load, tpch (q1, q6 and
                                     Q15's join-free aggregate, with and
                                     without the max over it), pallas, native
    python chip_smoke.py --chips 4   four chips: ONLY the mesh[4] phase and
                                     its reference
    ... --joins                      also q3 and q5 (see JOIN_QUERIES)
    ... --sf 10                      another scale factor (the benchmark's
                                     SF10: 100,000 suppliers' sums, one by one)

Each phase prints one JSON line; the last line of stdout is
``{"ok": true, "device": {...}}`` with the device as jax reports it. Any
failure raises at once (non-zero exit, no last line). Without a TPU the
script exits 2 before it prints anything: there is no CPU fallback and no
platform override. The times it prints are those of a smoke run (cold
caches, first executions) and not a benchmark.

Importing this module touches neither jax nor the device: tests/test_tpch.py
imports the pandas reference below and holds it to the sqlite oracle.
"""

from __future__ import annotations

import argparse
import datetime
import decimal
import json
import os
import re
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

SF = 1.0                      # TPC-H scale factor of every phase (--sf)
QUERIES = (1, 6)              # the default run: single-table scans + aggregates
# q3 and q5 are left out of the default run BY NAME. Their first
# (blocking) execution compiles 27 and 30 programs that hold an XLA sort
# (argsort / co-sort searchsorted, one per shape and dtype), and the
# chip's compiler takes 22-69 s for each (measured on the v5e, PR 23):
# tens of minutes cold, far past the 1200 s a default run may take.
# ROADMAP.md Queue A (A2) has the fault; --joins runs them.
JOIN_QUERIES = (3, 5)
# Q15's aggregate alone (the view and the max over it: benchmark/queries/
# q15_revenue.sql): join-free, so a default run covers the sort-based
# aggregate, GROUP BY l_suppkey into 10,000 groups at SF1. The benchmark's
# cell compares only the max, one sum of them all; REVENUE_VIEW is the same
# file's derived table alone, every supplier's key and sum, compared
# exactly. Cold, the two texts compile three programs that hold the
# aggregate's two sorts (the keys' argsort and the live rows'): the
# count's stage, the aggregate sized by it and the whole query's stage;
# the view is the sized aggregate's own program, so it compiles nothing
# more (360 s the whole default run on the v5e: PERF.md, PR 35).
REVENUE = "15_revenue"
REVENUE_VIEW = "15_revenue0"
PALLAS_ROWS = 1 << 22         # 4M rows
PALLAS_GROUPS = 200

# ---- the plain reference: pandas over the parquet files ---------------------
#
# Money is carried as exact integer hundredths (every decimal column of
# the generator has scale 2), so sums of price * (1 - discount) are exact
# integers in 1e-4 units and only the final division is floating point.

_EPOCH = datetime.date(1970, 1, 1)


def _days(year: int, month: int, day: int) -> int:
    return (datetime.date(year, month, day) - _EPOCH).days


def _frame(path: str, table: str, columns: Sequence[str]):
    """One table's columns as a pandas frame: decimals as int64
    hundredths, dates as int32 days since the epoch."""
    import pandas as pd
    import pyarrow as pa
    import pyarrow.parquet as pq

    t = pq.read_table(os.path.join(path, f"{table}.parquet"),
                      columns=list(columns))
    cols = {}
    for name, col in zip(t.column_names, t.columns):
        if pa.types.is_decimal(col.type):
            if col.type.scale != 2:
                raise ValueError(f"{table}.{name}: {col.type}, not scale 2")
            cols[name] = np.rint(
                col.cast(pa.float64()).to_numpy() * 100).astype(np.int64)
        elif pa.types.is_date32(col.type):
            cols[name] = col.cast(pa.int32()).to_numpy()
        elif pa.types.is_dictionary(col.type):
            cols[name] = col.cast(col.type.value_type).to_pandas()
        else:
            cols[name] = col.to_pandas()
    return pd.DataFrame(cols)


def ref_q1(path: str) -> List[Tuple]:
    li = _frame(path, "lineitem", [
        "l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
        "l_discount", "l_tax", "l_shipdate"])
    li = li[li.l_shipdate <= _days(1998, 12, 1) - 90]
    li = li.assign(disc_price=li.l_extendedprice * (100 - li.l_discount))
    li = li.assign(charge=li.disc_price * (100 + li.l_tax), n=1)
    g = li.groupby(["l_returnflag", "l_linestatus"], sort=True)[[
        "l_quantity", "l_extendedprice", "disc_price", "charge",
        "l_discount", "n"]].sum()
    out = []
    for (flag, status), r in g.iterrows():
        n = int(r.n)
        out.append((str(flag), str(status),
                    int(r.l_quantity) / 100, int(r.l_extendedprice) / 100,
                    int(r.disc_price) / 1e4, int(r.charge) / 1e6,
                    int(r.l_quantity) / 100 / n,
                    int(r.l_extendedprice) / 100 / n,
                    int(r.l_discount) / 100 / n, n))
    return out


def ref_q3(path: str) -> List[Tuple]:
    cut = _days(1995, 3, 15)
    cust = _frame(path, "customer", ["c_custkey", "c_mktsegment"])
    cust = cust[cust.c_mktsegment == "BUILDING"]
    orders = _frame(path, "orders", ["o_orderkey", "o_custkey",
                                     "o_orderdate", "o_shippriority"])
    orders = orders[orders.o_orderdate < cut]
    li = _frame(path, "lineitem", ["l_orderkey", "l_extendedprice",
                                   "l_discount", "l_shipdate"])
    li = li[li.l_shipdate > cut]
    j = li.merge(orders.merge(cust, left_on="o_custkey",
                              right_on="c_custkey"),
                 left_on="l_orderkey", right_on="o_orderkey")
    j = j.assign(revenue=j.l_extendedprice * (100 - j.l_discount))
    g = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                  as_index=False).revenue.sum()
    g = g.sort_values(["revenue", "o_orderdate"],
                      ascending=[False, True]).head(10)
    return [(int(r.l_orderkey), int(r.revenue) / 1e4,
             _EPOCH + datetime.timedelta(days=int(r.o_orderdate)),
             int(r.o_shippriority)) for r in g.itertuples()]


def ref_q5(path: str) -> List[Tuple]:
    region = _frame(path, "region", ["r_regionkey", "r_name"])
    nation = _frame(path, "nation", ["n_nationkey", "n_name",
                                     "n_regionkey"])
    nation = nation.merge(region[region.r_name == "ASIA"],
                          left_on="n_regionkey", right_on="r_regionkey")
    supp = _frame(path, "supplier", ["s_suppkey", "s_nationkey"])
    cust = _frame(path, "customer", ["c_custkey", "c_nationkey"])
    orders = _frame(path, "orders", ["o_orderkey", "o_custkey",
                                     "o_orderdate"])
    orders = orders[(orders.o_orderdate >= _days(1994, 1, 1))
                    & (orders.o_orderdate < _days(1995, 1, 1))]
    li = _frame(path, "lineitem", ["l_orderkey", "l_suppkey",
                                   "l_extendedprice", "l_discount"])
    j = li.merge(orders, left_on="l_orderkey", right_on="o_orderkey")
    j = j.merge(cust, left_on="o_custkey", right_on="c_custkey")
    j = j.merge(supp, left_on=["l_suppkey", "c_nationkey"],
                right_on=["s_suppkey", "s_nationkey"])
    j = j.merge(nation, left_on="s_nationkey", right_on="n_nationkey")
    j = j.assign(revenue=j.l_extendedprice * (100 - j.l_discount))
    g = j.groupby("n_name", as_index=False).revenue.sum()
    g = g.sort_values("revenue", ascending=False)
    return [(str(r.n_name), int(r.revenue) / 1e4) for r in g.itertuples()]


def ref_q6(path: str) -> List[Tuple]:
    li = _frame(path, "lineitem", ["l_extendedprice", "l_discount",
                                   "l_quantity", "l_shipdate"])
    li = li[(li.l_shipdate >= _days(1994, 1, 1))
            & (li.l_shipdate < _days(1995, 1, 1))
            & (li.l_discount >= 5) & (li.l_discount <= 7)
            & (li.l_quantity < 2400)]
    return [(int((li.l_extendedprice * li.l_discount).sum()) / 1e4,)]


def _money4(units) -> decimal.Decimal:
    return decimal.Decimal(int(units)).scaleb(-4)


def ref_q15_revenue0(path: str) -> List[Tuple]:
    """Every supplier's revenue of the quarter: the exact decimals the
    engine must return (integers in 1e-4 units, no float between)."""
    li = _frame(path, "lineitem", ["l_suppkey", "l_extendedprice",
                                   "l_discount", "l_shipdate"])
    li = li[(li.l_shipdate >= _days(1996, 1, 1))
            & (li.l_shipdate < _days(1996, 4, 1))]
    revenue = (li.l_extendedprice * (100 - li.l_discount)).groupby(
        li.l_suppkey).sum()
    return [(int(k), _money4(v)) for k, v in revenue.items()]


def ref_q15_revenue(path: str) -> List[Tuple]:
    return [(max(v for _k, v in ref_q15_revenue0(path)),)]


#: by TPC-H query number, or by name for a text of this file's own
REFERENCE: Dict[object, Callable[[str], List[Tuple]]] = {
    1: ref_q1, 3: ref_q3, 5: ref_q5, 6: ref_q6, REVENUE: ref_q15_revenue,
    REVENUE_VIEW: ref_q15_revenue0}
#: references in exact decimals: compared with ==, not within a tolerance
EXACT = (REVENUE, REVENUE_VIEW)


# ---- phases -----------------------------------------------------------------


def query_text(q) -> str:
    """A TPC-H query by number, the text the benchmark's cell
    ``tpch_sf10_q15_revenue`` runs, or that text's derived table alone."""
    if q in (REVENUE, REVENUE_VIEW):
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "benchmark", "queries",
                               "q15_revenue.sql")) as f:
            text = f.read()
        if q == REVENUE:
            return text
        return re.search(r"from \((.*)\) revenue0", text, re.S).group(1)
    from spark_tpu.tpch.queries import QUERIES as SQL

    return SQL[q]


def _emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _check(ok: bool, message: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {message}")


def _rows(df) -> List[Tuple]:
    return [tuple(r.asDict().values()) for r in df.collect()]


def phase_load(spark, sf: float, base: Optional[str] = None) -> str:
    """Generate the dataset from its seed and register the eight parquet
    views. Scans are lazy: each query's first execution reads its pruned
    columns onto the device and keeps them there (the ``scan`` events of
    the tpch phase time that; ``phase_resident`` prints what stayed)."""
    from spark_tpu.tpch.gen import ensure_dataset, register_views

    t0 = time.perf_counter()
    path = ensure_dataset(sf, base=base or tempfile.gettempdir())
    gen_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    register_views(spark, path=path)
    register_s = time.perf_counter() - t0
    parquet_bytes = sum(
        os.path.getsize(os.path.join(d, f))
        for d, _dirs, files in os.walk(path) for f in files)
    _emit("load", sf=sf, generate_s=round(gen_s, 2),
          register_views_s=round(register_s, 3),
          parquet_bytes=parquet_bytes)
    return path


def phase_tpch(spark, path: str, queries: Sequence[object],
               executions: int = 3, label: str = "tpch") -> None:
    """Each query ``executions`` times (1: blocking run that records the
    adaptive stats, 2: adaptive re-run, 3: fused steady state), every
    execution's rows against the pandas reference."""
    from spark_tpu import metrics
    from spark_tpu.tpch.oracle import assert_rows_match

    for q in queries:
        t0 = time.perf_counter()
        want = REFERENCE[q](path)
        ref_s = time.perf_counter() - t0
        _check(bool(want), f"q{q}: the reference returned no rows")
        df = spark.sql(query_text(q))
        runs = []
        for i in range(executions):
            cache0 = metrics.compile_cache_stats()
            t0 = time.perf_counter()
            got = _rows(df)
            ms = (time.perf_counter() - t0) * 1e3
            print(f"[chip_smoke] {label} q{q} execution {i + 1}: "
                  f"{ms / 1e3:.1f} s", file=sys.stderr, flush=True)
            if q in EXACT:
                _check(sorted(got) == sorted(want),
                       f"q{q}[execution {i + 1}]: {len(got)} rows are not "
                       f"the reference's {len(want)}, exactly")
            else:
                assert_rows_match(got, want,
                                  label=f"q{q}[execution {i + 1}]")
            evs = metrics.last_query()
            cache1 = metrics.compile_cache_stats()
            runs.append({
                "execution": i + 1, "ms": round(ms, 1),
                "stages": [e["op"] for e in evs if e["kind"] == "stage"],
                "stage_compile_ms": [e["ms"] for e in evs
                                     if e["kind"] == "stage_compile"],
                "scan": [{k: e[k] for k in ("rows", "decode_ms",
                                            "transfer_ms")}
                         for e in evs if e["kind"] == "scan"],
                "compile_cache": {
                    k: cache1[k] - cache0[k] for k in ("hits", "misses")},
            })
        _emit(label, query=f"q{q}", rows=len(want), matches_reference=True,
              reference_s=round(ref_s, 2), executions=runs)
    _emit(label + ".summary", compile_cache=metrics.compile_cache_stats(),
          recovery=metrics.recovery_stats())


def phase_resident(devices) -> Dict[int, int]:
    """What the queries left on the device: live arrays (the scanned
    table columns, mostly) and the allocator's own peak."""
    import jax

    per_device = {d.id: 0 for d in devices}
    for a in jax.live_arrays():
        for s in a.addressable_shards:
            if s.device.id in per_device:
                per_device[s.device.id] += s.data.nbytes
    stats = devices[0].memory_stats() or {}
    _emit("resident", live_array_bytes_per_device=per_device,
          bytes_in_use=stats.get("bytes_in_use"),
          peak_bytes_in_use=stats.get("peak_bytes_in_use"),
          bytes_limit=stats.get("bytes_limit"))
    return per_device


def phase_pallas(spark, rows: int = PALLAS_ROWS,
                 groups: int = PALLAS_GROUPS,
                 want_interpret: bool = False) -> None:
    """One GROUP BY with ``groups`` dictionary-coded groups over an f32
    column: COUNT/MIN/MAX go through the Pallas kernels, SUM keeps the
    row-ordered scatter-add (float sums must be layout-stable). Compared
    with a numpy recompute; the kernel must have run compiled."""
    import pyarrow as pa

    import jax
    import jax.numpy as jnp

    from spark_tpu import metrics
    from spark_tpu.physical import kernels as K

    rng = np.random.default_rng(23)
    codes = rng.integers(0, groups, rows).astype(np.int32)
    v = rng.normal(size=rows).astype(np.float32)
    names = [f"g{i:03d}" for i in range(groups)]
    table = pa.table({
        "k": pa.DictionaryArray.from_arrays(pa.array(codes),
                                            pa.array(names)),
        "v": pa.array(v)})
    spark.createDataFrame(table).createOrReplaceTempView("smoke_f32")
    t0 = time.perf_counter()
    got = _rows(spark.sql(
        "SELECT k, COUNT(*) AS c, SUM(v) AS s, MIN(v) AS lo, MAX(v) AS hi "
        "FROM smoke_f32 GROUP BY k ORDER BY k"))
    ms = (time.perf_counter() - t0) * 1e3

    count = np.bincount(codes, minlength=groups)
    total = np.bincount(codes, weights=v.astype(np.float64),
                        minlength=groups)
    lo = np.full(groups, np.inf, np.float32)
    hi = np.full(groups, -np.inf, np.float32)
    np.minimum.at(lo, codes, v)
    np.maximum.at(hi, codes, v)
    _check([r[0] for r in got] == names, "pallas: group keys differ")
    _check([r[1] for r in got] == count.tolist(), "pallas: COUNT(*) differs")
    np.testing.assert_array_equal([r[3] for r in got], lo, "MIN")
    np.testing.assert_array_equal([r[4] for r in got], hi, "MAX")
    # an f32 sum of ~21k values: order-dependent in the last bits
    np.testing.assert_allclose([r[2] for r in got], total,
                               rtol=1e-4, atol=1e-2, err_msg="SUM")

    ran = [e for e in metrics.last_query() if e["kind"] == "pallas"]
    ops = sorted({e["op"] for e in ran})
    _check(ops == ["count", "max", "min"], f"Pallas path not taken: {ops}")
    _check(all(e["interpret"] is want_interpret for e in ran),
           f"Pallas kernels ran with the wrong interpret flag: {ran}")
    # the same engine kernels, lowered for this device at this shape:
    # the program must hold the Mosaic kernel, not a scatter
    n = ran[0]["rows"]
    text = jax.jit(
        lambda s, m: K.seg_count(s, m, groups)).lower(
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.bool_)).compile().as_text()
    custom_call = "tpu_custom_call" in text
    _check(custom_call or want_interpret,
           "seg_count compiled without a tpu_custom_call")
    _emit("pallas", rows=rows, groups=groups, ms=round(ms, 1),
          matches_numpy=True, pallas_ops=ops, sum_path="scatter-add",
          interpret=want_interpret, tpu_custom_call=custom_call)


def phase_native(so_present_at_start: bool) -> None:
    """Not fatal, but printed: whether the C++ string kernels built."""
    from spark_tpu import native

    ok = native.available()
    _emit("native", available=ok,
          so_present_at_start=so_present_at_start,
          built_from_source_in_this_run=ok and not so_present_at_start)


def phase_mesh(spark, path: str, devices, queries: Sequence[int],
               executions: int = 2) -> None:
    """The queries through MeshExecutor (all_to_all / psum exchanges) on
    a mesh over ``devices``, against the same reference, then: does
    every device hold its share of what is resident?"""
    import jax

    n = len(devices)
    _check(spark.mesh_executor is not None, "not a mesh session")
    phase_tpch(spark, path, queries, executions=executions,
               label=f"mesh[{n}]")
    per_device = phase_resident(devices)
    total = sum(per_device.values())
    _check(total > 0 and all(b >= total / (4 * n)
                             for b in per_device.values()),
           f"devices hold uneven shares of the data: {per_device}")
    sharded = sum(1 for a in jax.live_arrays()
                  if len(a.sharding.device_set) == n
                  and not a.sharding.is_fully_replicated)
    _check(sharded > 0, "no live array is sharded over the mesh")
    _emit(f"mesh[{n}].shards", devices_holding_shards=n,
          arrays_sharded_over_mesh=sharded,
          min_share=round(min(per_device.values()) / total, 3))


def _so_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "spark_tpu", "native", "_strkernels.so")


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the mesh[4] phase and its reference")
    ap.add_argument("--joins", action="store_true",
                    help="also run q3 and q5 (tens of minutes of sort "
                         "compiles when the compile cache is cold)")
    ap.add_argument("--sf", type=float, default=SF,
                    help="TPC-H scale factor of every phase (default 1)")
    args = ap.parse_args(argv)
    queries = tuple(sorted(QUERIES + (JOIN_QUERIES if args.joins else ())))
    if args.chips == 1:
        queries += (REVENUE, REVENUE_VIEW)

    import jax

    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); jax found "
              f"{len(devices)} x {first.platform}", file=sys.stderr)
        sys.exit(2)
    so_present = os.path.exists(_so_path())

    from spark_tpu.api.session import SparkSession

    device = {"platform": first.platform, "kind": first.device_kind,
              "count": len(devices)}
    _emit("device", **device, jax=jax.__version__)
    _emit("queries", run=[f"q{q}" for q in queries],
          left_out=[] if args.joins else [f"q{q}" for q in JOIN_QUERIES],
          why_left_out=None if args.joins else
          "cold first execution compiles ~57 XLA sort programs at 22-69 s "
          "each on this chip (ROADMAP.md A2); --joins runs them")
    builder = SparkSession.builder.appName("chip_smoke")
    if args.chips == 4:
        spark = builder.master("mesh[4]").getOrCreate()
        _emit("session", master="mesh[4]",
              compile_cache_dir=jax.config.jax_compilation_cache_dir)
        path = phase_load(spark, args.sf)
        phase_mesh(spark, path, devices[:4], queries)
    else:
        spark = builder.getOrCreate()
        _emit("session", master="local",
              compile_cache_dir=jax.config.jax_compilation_cache_dir)
        path = phase_load(spark, args.sf)
        phase_tpch(spark, path, queries)
        phase_resident(devices[:1])
        phase_pallas(spark)
        phase_native(so_present)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
