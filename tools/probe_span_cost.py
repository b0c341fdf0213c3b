"""Chip-host probe: what the span stream costs an execution when it
records (PR 37; PERF.md section 6, beside PR 26's 3.86 us a child span).

``tpch_sf1_q6``'s query as the benchmark runs it (the harness's dataset,
views and text: SQL text -> Python rows, one client), warmed up, then
blocks of ``--block`` executions that alternate ``spark.tpu.trace.enabled``
true and false in one process, so both settings see the same machine,
the same resident table and the same compiled stage:

  chiprun -- python tools/probe_span_cost.py                  # ~2 min
  chiprun -- python tools/probe_span_cost.py --root <a checkout>

prints one JSON line: the median execution with spans recording and
without (ms, over all of a setting's executions), their difference, the
span events an execution records, the difference a span in
microseconds, and ``full_ring_ms``: what ``tier.decide`` and the two
``admission.note`` read an execution once the 4,096-event ring is full. ``--root`` takes the engine and the benchmark from another
checkout of this repository (the parent commit's, unpacked beside this
one), which the probe's own file need not be in. With tracing off no
span event is recorded and the ids are still stamped (tier-1:
test_tracing_off_same_rows_no_span_event). Refuses to run without a TPU:
a CPU's host path is not the chip's host's.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
WORKLOAD = "tpch_sf1_q6"
SEED = 2147483659       # a seed the builders' benchmark runs also use
FULL_RING_SPANS = ("tier.decide", "admission.note")


def ring_medians(events) -> dict:
    """Median ms an execution of the two spans inside ``glue_ms``, over
    the executions the ring holds: after a block the ring is full, as in
    the benchmark's measured window and unlike its traced slice, which
    starts on a ring that warm-up has hardly filled (``admission.note``
    copies the ring, so its cost follows the fill)."""
    by_trace = collections.defaultdict(lambda: collections.defaultdict(float))
    for e in events:
        if e["kind"] == "span" and e["name"] in FULL_RING_SPANS:
            by_trace[e["trace_id"]][e["name"]] += e["ms"]
    return {name: statistics.median(t[name] for t in by_trace.values()
                                    if name in t)
            for name in FULL_RING_SPANS
            if any(name in t for t in by_trace.values())}


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", default=os.path.dirname(HERE),
                    help="checkout whose spark_tpu/ and benchmark/ run")
    ap.add_argument("--block", type=int, default=500,
                    help="executions a block")
    ap.add_argument("--blocks", type=int, default=8,
                    help="blocks in all, alternating on / off")
    ap.add_argument("--sf", type=float,
                    help="scale factor (default: the configuration's)")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse the control flow without a TPU")
    a = ap.parse_args(argv)
    root = os.path.abspath(a.root)
    sys.path[:0] = [root, os.path.join(root, "benchmark")]

    import jax

    device = jax.devices()[0]
    if device.platform != "tpu" and not a.allow_cpu:
        print("probe_span_cost: no TPU", file=sys.stderr)
        return 2

    import harness
    import tpch_gen
    from spark_tpu import metrics
    from spark_tpu.api.session import SparkSession

    cell = harness.Cell(root, WORKLOAD)
    (query,) = cell.queries
    spark = SparkSession.builder.appName("probe_span_cost").getOrCreate()
    path = tpch_gen.ensure_dataset(
        os.path.join(root, "benchmark", ".data"),
        float(cell.config["scale_factor"] if a.sf is None else a.sf),
        SEED, int(cell.config["structure_seed"]))
    tpch_gen.register_views(spark, path)
    query.prepare(path)

    def execute() -> float:
        t0 = time.perf_counter()
        rows = spark.sql(query.text).collect()
        dt = time.perf_counter() - t0
        fault = query.differ(rows)
        if fault is not None:
            raise SystemExit(f"probe_span_cost: wrong rows: {fault}")
        return dt * 1e3

    for _ in range(8):                  # scan, compile, reach steady state
        execute()
    times = {True: [], False: []}
    spans = []
    full_ring = {}
    try:
        for block in range(a.blocks):
            enabled = block % 2 == 0
            spark.conf.set("spark.tpu.trace.enabled", enabled)
            # the switch itself stays outside; with it, count the span
            # events ONE execution leaves in the ring (its query.parse is
            # a trace of its own, so not by trace id)
            mark = metrics.recent(1)[-1]["n"]
            execute()
            count = sum(e["kind"] == "span" and e["n"] > mark
                        for e in metrics.recent(256))
            if enabled:
                spans.append(count)
            elif count:
                raise SystemExit("probe_span_cost: spans with tracing off")
            times[enabled] += [execute() for _ in range(a.block)]
            if enabled:
                full_ring = ring_medians(metrics.recent(4096))
    finally:
        spark.conf.unset("spark.tpu.trace.enabled")
    on = statistics.median(times[True])
    off = statistics.median(times[False])
    per = statistics.median(spans)
    print(json.dumps({
        "probe": "span_cost", "root": root, "workload": WORKLOAD,
        "platform": device.platform, "kind": device.device_kind,
        "executions_a_setting": len(times[True]),
        "on_ms": on, "off_ms": off, "difference_ms": on - off,
        "on_quartiles_ms": statistics.quantiles(times[True], n=4),
        "off_quartiles_ms": statistics.quantiles(times[False], n=4),
        "spans_an_execution": per,
        "us_a_span": (on - off) * 1e3 / per,
        "full_ring_ms": full_ring}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
