"""The chip's compiler on TPC-H Q3's groups at SF10, without the chip.

``chip_smoke.py``'s ``q3_groups`` (the benchmark's ``tpch_sf10_q3`` text
without ORDER BY / LIMIT) builds one program on its first execution that
the cell never builds: the sorted aggregate of every order's revenue over
both joins. This rebuilds that stage from a SF0.05 run on the CPU, binds
it to the cell's SF10 capacities, and compiles it for a described TPU
v5e (``jax.experimental.topologies``), as tests/test_tpu_aot_compile.py
does for smaller programs. Nothing runs on a device, so nothing is said
about results or times; what the compiler refuses or crashes on (a
SIGSEGV ends this process with 139) it does here as on the chip.

  python tools/compile_q3_groups_v5e.py   # ~5 min, most of it the sort

Prints one JSON line: the stage's compile seconds and the number of
gathers, scatters and sorts in the optimised program.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("SPARK_TPU_JAX_CACHE", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

SMALL_SF = 0.05
# tpch_sf10_q3's capacities (PERF.md section 4): scans, the dense join
# tables' key domains, the joins' pairs, the groups
CUSTOMERS, ORDERS, LINES = 327_680, 7_775_232, 32_440_320
CUSTKEYS, ORDERKEYS = 1_500_160, 15_000_576
JOIN1_PAIRS, JOIN2_PAIRS, GROUPS = 1_572_864, 327_680, 120_000


def _aggregate_stage(spark):
    """(tier, plan, example args) of the last stage with the aggregate
    at its root that the groups' first execution builds."""
    import chip_smoke
    import spark_tpu.compile as compile_pkg

    stages = []
    build = compile_pkg.build_stage_callable

    def capture(tier, plan, trace_fn, example_args, *a, **kw):
        stages.append((tier, plan, example_args))
        return build(tier, plan, trace_fn, example_args, *a, **kw)

    compile_pkg.build_stage_callable = capture
    try:
        chip_smoke.phase_load(spark, SMALL_SF, base=tempfile.gettempdir())
        spark.sql(chip_smoke.query_text(chip_smoke.SHIPPING_GROUPS)).collect()
    finally:
        compile_pkg.build_stage_callable = build
    return [s for s in stages
            if type(s[1]).__name__ == "HashAggregateExec"][-1]


def _at_sf10(plan, args, sharding):
    """The plan with the cell's join capacities, key domains and groups;
    each leaf array at its SF10 capacity (matched by its SF0.05 one)."""
    from spark_tpu.physical import operators as P

    def bind(p):
        if not isinstance(p, P.BatchScanExec):
            p = p.map_children(bind)
        if isinstance(p, P.JoinExec):
            first = "c_custkey" in str(p.left_keys)
            p = dataclasses.replace(p, adaptive=p.adaptive._replace(
                cap=JOIN1_PAIRS if first else JOIN2_PAIRS,
                packing=((0, CUSTKEYS if first else ORDERKEYS),)))
        if isinstance(p, P.HashAggregateExec):
            p = dataclasses.replace(p, adaptive=GROUPS)
        return p

    by_key = {j.adaptive.packing[0][1]: CUSTKEYS if "c_custkey" in str(
        j.left_keys) else ORDERKEYS for j in _joins(plan)}
    rows = sorted({a.shape[0] for a in jax.tree.leaves(args)} - set(by_key))
    assert len(rows) == 3, rows
    # the rest, smallest first: customers, orders, lines
    by_rows = {**dict(zip(rows, (CUSTOMERS, ORDERS, LINES))), **by_key}
    return bind(plan), jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        (by_rows[a.shape[0]],) + a.shape[1:], a.dtype, sharding=sharding),
        args)


def _joins(plan):
    from spark_tpu.physical import operators as P

    out = [plan] if isinstance(plan, P.JoinExec) else []
    for c in plan.children():
        out += _joins(c)
    return out


def main() -> int:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import spark_tpu.compile as compile_pkg
    from spark_tpu.api.session import SparkSession
    from spark_tpu.physical import operators as P, stage

    spark = SparkSession.builder.appName("compile_q3_groups").getOrCreate()
    tier, plan, args = _aggregate_stage(spark)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    plan10, args10 = _at_sf10(plan, args,
                              SingleDeviceSharding(topo.devices[0]))
    traced = []
    build = compile_pkg.build_stage_callable
    compile_pkg.build_stage_callable = (
        lambda tier, plan, fn, *a, **kw: traced.append(fn) or fn)
    try:
        stage.build_stage(tier, plan10, P.BatchScanExec, args10,
                          name="q3_groups_sf10")
    finally:
        compile_pkg.build_stage_callable = build
    jax.config.update("jax_enable_compilation_cache", False)
    t0 = time.perf_counter()
    text = jax.jit(traced[0]).lower(args10).compile().as_text()
    print(json.dumps({
        "stage": "q3_groups_sf10", "compile_s": round(
            time.perf_counter() - t0, 1),
        **{op: len(re.findall(rf" {op}\(", text))
           for op in ("gather", "scatter", "sort")}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
