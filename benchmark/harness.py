"""The benchmark's harness: one cell, one run, one JSON line.

Everything that belongs to one cell is data found by name (see PERF.md):

    BENCHMARK.json                   workloads (cells) and metrics
    benchmark/configs/<config>.json  the deployment: SF, structure_seed, master
    benchmark/traffic/<mix>.json     loop, clients, queries
    benchmark/queries/<q>.sql|.py    the text, its plain reference, its bytes
    benchmark/layer_metrics/<m>.py   read(ctx) -> number or None
    benchmark/peaks.json             device peaks by device_kind

A later PR adds a cell, a configuration, a mix, a query or a per-layer
metric by adding files and an entry; nothing here is edited for it. From
the program the harness takes only the system under test (``SparkSession``,
``spark.sql(text).collect()``) and its events, spans and counters
(``spark_tpu.metrics``).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import statistics
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import reduce_trace
import reference
import tpch_gen

HERE = os.path.dirname(os.path.abspath(__file__))
WARMUP_MAX = 6            # executions a query may take to reach steady state
TRACE_MIN_EXECUTIONS = 2  # the traced slice: at least this many executions
TRACE_MIN_SECONDS = 1.0   # ... and at least this long


class BenchError(Exception):
    """Something named in the data is unknown or missing."""


# ---- the data ---------------------------------------------------------------


def _load_json(path: str, what: str) -> Any:
    if not os.path.exists(path):
        raise BenchError(f"unknown {what}: no file {path}")
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, what: str):
    if not os.path.exists(path):
        raise BenchError(f"unknown {what}: no file {path}")
    name = "bench_" + os.path.basename(path)[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Query:
    def __init__(self, bench_dir: str, name: str):
        base = os.path.join(bench_dir, "queries", name)
        if not os.path.exists(base + ".sql"):
            raise BenchError(f"unknown query {name!r}: no file {base}.sql")
        with open(base + ".sql") as f:
            self.text = f.read()
        self.name = name
        self.module = _load_module(base + ".py", f"query {name!r}")
        self.tables: Tuple[str, ...] = tuple(self.module.TABLES)
        self.ordered = bool(getattr(self.module, "ORDERED", False))
        self.want: List[Tuple] = []
        self.table_rows = 0

    def prepare(self, path: str) -> None:
        self.want = self.module.reference(path)
        if not self.want:
            raise BenchError(f"query {self.name}: the reference has no rows")
        self.table_rows = sum(reference.table_rows(path, t)
                              for t in self.tables)

    def differ(self, rows: List) -> Optional[str]:
        got = [tuple(r.asDict().values()) for r in rows]
        want = self.want
        if not self.ordered:
            got, want = reference.sorted_rows(got), reference.sorted_rows(want)
        return reference.rows_differ(got, want)


class Cell:
    """One entry of BENCHMARK.json's workloads with everything it names."""

    def __init__(self, root: str, workload: str):
        self.bench_dir = os.path.join(root, "benchmark")
        spec = _load_json(os.path.join(root, "BENCHMARK.json"),
                          "benchmark file")
        cells = {w["name"]: w for w in spec["workloads"]}
        if workload not in cells:
            raise BenchError(f"unknown workload {workload!r}; BENCHMARK.json "
                             f"has {sorted(cells)}")
        self.entry = cells[workload]
        self.chips = int(self.entry["chips"])
        self.config = _load_json(os.path.join(
            self.bench_dir, "configs", self.entry["config"] + ".json"),
            f"configuration {self.entry['config']!r}")
        self.traffic = _load_json(os.path.join(
            self.bench_dir, "traffic", self.entry["traffic"] + ".json"),
            f"traffic mix {self.entry['traffic']!r}")
        for key, known in (("loop", ("closed",)),
                           ("literals", ("validation",)),
                           ("order", ("round_robin",))):
            if self.traffic.get(key) not in known:
                raise BenchError(
                    f"traffic mix {self.entry['traffic']!r}: {key} "
                    f"{self.traffic.get(key)!r} is not implemented "
                    f"(known: {list(known)})")
        self.clients = int(self.traffic["clients"])
        self.queries = [Query(self.bench_dir, q)
                        for q in self.traffic["queries"]]

        def mine(metric):
            return workload in metric.get("workloads", [workload])

        self.end_to_end = [m for m in spec["end_to_end"] if mine(m)]
        self.per_layer = [m for m in spec["per_layer"] if mine(m)]
        for m in self.end_to_end:
            if m["name"] not in END_TO_END:
                raise BenchError(f"unknown end-to-end metric {m['name']!r}")
        self.readers = {
            m["name"]: _load_module(os.path.join(
                self.bench_dir, "layer_metrics", m["name"] + ".py"),
                f"per-layer metric {m['name']!r}").read
            for m in self.per_layer}

    def peaks(self, device_kind: str) -> Dict:
        table = _load_json(os.path.join(self.bench_dir, "peaks.json"),
                           "table of peaks")
        if device_kind not in table:
            raise BenchError(f"unknown device_kind {device_kind!r}: "
                             f"peaks.json has {sorted(table)}")
        return table[device_kind]


# ---- events, spans and counters of the program ------------------------------


class Events:
    """The program's metrics ring holds 4096 events, so it is read after
    every execution and not once at the end."""

    def __init__(self, metrics):
        self._metrics = metrics
        last = metrics.recent(1)
        self._seen = last[-1]["n"] if last else -1

    def new(self, last: int = 4096) -> List[Dict]:
        """Events since the previous call, among the ring's ``last``."""
        out = [e for e in self._metrics.recent(last) if e["n"] > self._seen]
        if out:
            self._seen = out[-1]["n"]
        return out

    def lookups(self) -> int:
        c = self._metrics.compile_cache_stats()
        return c["hits"] + c["misses"]


# ---- one run ----------------------------------------------------------------


class Execution:
    __slots__ = ("query", "t0", "t_sql", "t1", "rows", "error")

    def __init__(self, query: Query):
        self.query = query
        self.t0 = self.t_sql = self.t1 = 0.0
        self.rows: Optional[List] = None
        self.error: Optional[str] = None


def _execute(spark, query: Query, annotate: Optional[Callable] = None
             ) -> Execution:
    """SQL text -> Python rows, timed on the host clock. ``collect()``
    brings the rows to the host, so the timing is device-synchronised."""
    ex = Execution(query)
    ex.t0 = time.perf_counter()
    try:
        if annotate is None:
            ex.rows = spark.sql(query.text).collect()
        else:
            with annotate("bench.sql"):
                df = spark.sql(query.text)
            ex.t_sql = time.perf_counter()
            with annotate("bench.collect"):
                ex.rows = df.collect()
    except Exception as e:  # an execution that raised is a failed request
        ex.error = repr(e)
    ex.t1 = time.perf_counter()
    return ex


def _closed_loop(spark, cell: Cell, seconds: float) -> Tuple[List[Execution],
                                                              float]:
    """``clients`` callers, each sending its next query when the last one
    has answered. New executions start until ``seconds`` have passed; the
    ones in flight complete and count. Returns them and the measured
    seconds (start of the window to the end of the last execution)."""
    start = time.perf_counter()
    lanes: List[List[Execution]] = [[] for _ in range(cell.clients)]

    def client(lane: List[Execution], offset: int) -> None:
        i = offset
        while time.perf_counter() - start < seconds:
            lane.append(_execute(spark, cell.queries[i % len(cell.queries)]))
            i += 1

    if cell.clients == 1:
        client(lanes[0], 0)
    else:
        threads = [threading.Thread(target=client, args=(lane, k))
                   for k, lane in enumerate(lanes)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    done = [ex for lane in lanes for ex in lane]
    return done, max(ex.t1 for ex in done) - start


def _check(executions: List[Execution]) -> Tuple[bool, int, Optional[str]]:
    """(correct, failed, first fault) of the executions' rows against the
    reference."""
    failed, first = 0, None
    correct = True
    for ex in executions:
        if ex.error is not None:
            failed += 1
            first = first or f"{ex.query.name} raised {ex.error}"
            continue
        diff = ex.query.differ(ex.rows)
        if diff is not None:
            correct = False
            first = first or f"{ex.query.name}: {diff}"
    return correct and failed == 0, failed, first


def _quantile95(values: List[float]) -> float:
    return statistics.quantiles(values, n=20, method="inclusive")[18]


END_TO_END: Dict[str, Callable[[Dict], float]] = {
    "setup_s": lambda w: w["setup_s"],
    "query_ms": lambda w: statistics.median(w["latency_ms"]),
    "query_p95_ms": lambda w: _quantile95(w["latency_ms"]),
    "rows_per_s": lambda w: w["table_rows_done"] / w["measured_s"],
}


def _live_bytes(devices) -> Dict[int, int]:
    """Live array bytes per device (chip_smoke.py::phase_resident)."""
    import jax

    per_device = {d.id: 0 for d in devices}
    for a in jax.live_arrays():
        for s in a.addressable_shards:
            if s.device.id in per_device:
                per_device[s.device.id] += s.data.nbytes
    return per_device


def _set_up(cell: Cell, spark, metrics, seed: int, data_root: str,
            t_start: float) -> Dict:
    """Dataset, views, reference rows and warm-up; everything the window
    and the readers need from it. All of it is ``setup_s``."""
    # 1: the dataset, from the benchmark's own generator
    path = tpch_gen.ensure_dataset(
        data_root, float(cell.config["scale_factor"]), seed,
        int(cell.config["structure_seed"]))
    tpch_gen.register_views(spark, path)
    # 2: the plain reference's rows
    for q in cell.queries:
        q.prepare(path)
        print(f"[benchmark] {q.name} reference, first row: {q.want[0]}",
              file=sys.stderr, flush=True)
    # 3: warm-up, until an execution neither looks a program up in the
    # persistent cache nor compiles a stage
    events = Events(metrics)
    cache0 = metrics.compile_cache_stats()
    setup_events: List[Dict] = []
    warmup: List[Execution] = []
    for q in cell.queries:
        for i in range(WARMUP_MAX):
            lookups = events.lookups()
            ex = _execute(spark, q)
            if ex.error is not None:
                raise BenchError(f"warm-up of {q.name} raised {ex.error}")
            new = events.new()
            setup_events += new
            warmup.append(ex)
            print(f"[benchmark] warm-up {q.name} execution {i + 1}: "
                  f"{ex.t1 - ex.t0:.3f} s", file=sys.stderr, flush=True)
            if events.lookups() == lookups and not any(
                    e["kind"] == "stage_compile" for e in new):
                break
        else:
            raise BenchError(f"{q.name} still compiles after {WARMUP_MAX} "
                             "executions")
    cache1 = metrics.compile_cache_stats()
    return {
        "data_path": path, "events": events, "warmup": warmup,
        "setup_events": setup_events,
        "setup_cache": {k: cache1[k] - cache0[k] for k in cache1},
        "first_exec_s": warmup[0].t1 - warmup[0].t0,
        "setup_s": time.perf_counter() - t_start,
    }


def _measure(cell: Cell, spark, setup: Dict, seconds: float
             ) -> Tuple[List[Execution], Dict]:
    """The measured window and the cell's end-to-end metrics."""
    events = setup["events"]
    lookups = events.lookups()
    done, measured_s = _closed_loop(spark, cell, seconds)
    print(f"[benchmark] window: {len(done)} executions in {measured_s:.3f} "
          f"s, {events.lookups() - lookups} compile-cache lookups",
          file=sys.stderr)
    ok = [ex for ex in done if ex.error is None]
    window = {
        "setup_s": setup["setup_s"],
        "latency_ms": [(ex.t1 - ex.t0) * 1e3 for ex in ok],
        "table_rows_done": sum(ex.query.table_rows for ex in ok),
        "measured_s": measured_s,
    }
    return done, {"metrics": {
        m["name"]: {"value": END_TO_END[m["name"]](window), "unit": m["unit"]}
        for m in cell.end_to_end}}


def _trace(cell: Cell, spark, setup: Dict, devices, peaks: Dict,
           host_ops: bool) -> Tuple[List[Execution], Dict]:
    """A profiler trace of a short steady slice, reduced, and the cell's
    per-layer metrics from their readers."""
    import jax

    trace_dir = os.path.join(cell.bench_dir, ".trace", cell.entry["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0   # host spans are the bench.* ones
    events = setup["events"]
    lookups = events.lookups()
    done: List[Execution] = []
    slice_events: List[Dict] = []
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(reduce_trace.SLICE):
            t0 = time.perf_counter()
            while (len(done) < TRACE_MIN_EXECUTIONS
                   or time.perf_counter() - t0 < TRACE_MIN_SECONDS):
                q = cell.queries[len(done) % len(cell.queries)]
                done.append(_execute(spark, q, jax.profiler.TraceAnnotation))
                slice_events += events.new(last=256)
    finally:
        jax.profiler.stop_trace()
    reduced = reduce_trace.reduce(
        reduce_trace.load(reduce_trace.newest_xplane(trace_dir),
                          host_ops=host_ops),
        chips=1 if host_ops else cell.chips)
    ctx = {
        "cell": cell, "chips": cell.chips, "peaks": peaks,
        "executions": done, "slice_events": slice_events,
        "slice_cache_lookups": events.lookups() - lookups,
        "trace": reduced, "live_bytes": _live_bytes(devices),
        "memory_stats": [d.memory_stats() or {} for d in devices],
        **{k: setup[k] for k in ("data_path", "setup_events", "setup_cache",
                                 "first_exec_s", "setup_s")},
    }
    values = {m["name"]: (cell.readers[m["name"]](ctx), m["unit"])
              for m in cell.per_layer}
    return done, {
        # a reader that finds nothing to read returns None: left out
        "metrics": {name: {"value": value, "unit": unit}
                    for name, (value, unit) in values.items()
                    if value is not None},
        "device": {"busy_s": reduced["busy_s"],
                   "window_s": reduced["window_s"]},
        "breakdown": {"device_ops": reduced["device_ops"],
                      "idle_gaps": reduced["idle_gaps"]}}


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, root: str, data_root: Optional[str] = None,
        require_tpu: bool = True) -> Dict:
    """One run of one cell; returns the object of the last line."""
    cell = Cell(root, workload)

    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise BenchError(f"needs a TPU: jax found {devices[0].platform}")
    if len(devices) < cell.chips:
        raise BenchError(f"cell {workload} needs {cell.chips} chip(s): jax "
                         f"found {len(devices)}")
    peaks = cell.peaks(devices[0].device_kind)
    used = devices[:cell.chips]

    from spark_tpu import metrics
    from spark_tpu.api.session import SparkSession

    builder = SparkSession.builder.appName("benchmark." + workload)
    if cell.config.get("master"):
        builder = builder.master(cell.config["master"])
    for key, value in (cell.config.get("session_conf") or {}).items():
        builder = builder.config(key, value)
    spark = builder.getOrCreate()

    setup = _set_up(cell, spark, metrics, seed,
                    data_root or os.path.join(cell.bench_dir, ".data"),
                    t_start)
    if trace:
        done, out = _trace(cell, spark, setup, used, peaks,
                           host_ops=not require_tpu)
    else:
        done, out = _measure(cell, spark, setup, seconds)
    # after the window: every execution's rows against the reference
    correct, failed, first = _check(setup["warmup"] + done)
    if first is not None:
        print(f"[benchmark] first fault: {first}", file=sys.stderr)
    stats = [d.memory_stats() or {} for d in used]
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                       for s in stats),
              **out.pop("device", {})}
    return {"correct": correct, "attempted": len(done), "failed": failed,
            **out, "device": device}


def main(argv: List[str], t_start: float, root: Optional[str] = None,
         **kwargs) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = root or os.path.dirname(HERE)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace), t_start=t_start, root=root, **kwargs)
    except BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0
