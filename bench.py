"""Headline benchmark: TPC-H q1/q3/q5 wall-clock on the real TPU chip.

This is the scored metric (BASELINE.md: TPC-H wall-clock vs Spark CPU
``local[*]``, result parity; harness model: the reference's
sql/core/src/test/.../benchmark/TPCDSQueryBenchmark.scala:86). Honesty
requirements (round-2 verdict #2):

- inputs are Parquet-written, Parquet-read, device-resident columnar
  batches fed to the jitted stages as ARGUMENTS — the physical plan is
  asserted to contain real data leaves, so XLA cannot constant-fold the
  query away (the round-1/2 bench measured a precomputed constant);
- per-query wall-clock covers the full execute path including blocking
  operators and host syncs, after one warm-up run (compile caches warm,
  matching the reference benchmark's N-iteration protocol);
- implied scan bandwidth is asserted to be below the chip's HBM
  bandwidth — a result faster than physically possible means the
  benchmark is broken, and fails loudly.

Baseline: Spark CPU local[*] is NOT runnable in this image (no JVM), so
``vs_baseline`` uses a documented per-query estimate for Spark 3.5 on a
modern server CPU at SF1, calibrated from the reference's checked-in
benchmark files (AggregateBenchmark-jdk17-results.txt:10 — 2,250 M
simple rows/s ungrouped; TPCDSQueryBenchmark-jdk17-results.txt:5,17,29 —
TPC-DS SF1 q1/q3/q5 = 1178/431/2026 ms on Azure Xeon). TPC-H SF1
estimates used here: q1=900 ms (6M-row scan + 8-expression grouped agg;
Spark's measured grouped-agg rate is far below the ungrouped 2,250 M/s),
q3=700 ms, q5=1100 ms (3- and 6-way joins at SF1, TPC-DS q3/q5-class).
These deliberately favour Spark; treat vs_baseline as indicative, the
absolute ms as the record.
"""

import contextlib
import json
import os
import signal
import sys
import time

import numpy as np

SF = float(os.environ.get("BENCH_SF", "1.0"))
# SF>10 runs out-of-HBM (host-streamed chunks): one timed pass, no
# median protocol — a single q1 pass at SF100 is minutes of parquet IO
N_ITER = int(os.environ.get("BENCH_ITERS", "5" if SF <= 10 else "1"))
# BENCH_FULL=1: additionally time ALL 22 TPC-H queries (the BASELINE.md
# target metric is the full suite; q1/q3/q5 stay the headline line)
FULL = os.environ.get("BENCH_FULL", "0") == "1"
# Peak HBM bandwidth in GB/s by jax's ``device_kind``. Source: Google
# Cloud documentation, "TPU v5e" system architecture (16 GB HBM2e at
# 819 GB/s per chip). A device that is not in the table is an error,
# not a default.
PEAK_HBM_GBPS = {"TPU v5 lite": 819.0}  # how a v5e chip names itself
HBM_GBPS = None  # set by main() from the device it found

# Per-query wall-clock cap. A query that hangs (or an SF that turns out
# to be hours of parquet IO) records {"error": "timeout"} and the run
# moves on — the final JSON stays valid and covers every other query,
# instead of the whole process dying to the harness's timeout(1) with
# no parseable output at all.
QUERY_TIMEOUT_S = float(os.environ.get("BENCH_QUERY_TIMEOUT",
                                       "600" if SF <= 10 else "1200"))
# BENCH_PARTIAL_PATH=<file>: a snapshot is written there after every
# query so even a SIGKILL leaves the completed queries' numbers on disk.
# Unset (the default), nothing is written.
PARTIAL_PATH = os.environ.get("BENCH_PARTIAL_PATH", "")

# Global wall-clock budget for the WHOLE bench process. The harness
# runs bench under an external timeout; hitting that kills the process
# (rc=124) with at most the partial snapshot on disk. Budgeting inside the
# process instead skips remaining phases (marked in the JSON) so the
# final complete document always prints. 0 disables.
WALL_BUDGET_S = float(os.environ.get("BENCH_WALL_BUDGET", "3300"))
_WALL_T0 = time.time()

# BENCH_CACHED=0 skips the HBM-store cached-mode report
CACHED_MODE = os.environ.get("BENCH_CACHED", "1") == "1"

# BENCH_ADAPTIVE=0 skips the adaptive-execution A/B phase (off vs on
# timing + byte-identity + padding-ratio report; needs BENCH_MASTER=
# mesh[N] to actually engage — single-device sessions have no exchange
# stages to re-plan and report {"skipped": ...})


def _wall_remaining() -> float:
    if WALL_BUDGET_S <= 0:
        return float("inf")
    return WALL_BUDGET_S - (time.time() - _WALL_T0)


def _query_deadline(cap_s: float = None) -> float:
    """Per-query alarm, never longer than what the wall budget has
    left (so the last query degrades to a marked timeout instead of
    blowing the whole process budget). ``cap_s`` tightens the cap below
    QUERY_TIMEOUT_S for auxiliary phases (see PHASE_BUDGET_S)."""
    base = QUERY_TIMEOUT_S
    if cap_s is not None:
        base = min(base, cap_s)
    rem = _wall_remaining()
    if rem == float("inf"):
        return base
    return max(1.0, min(base, rem))


# Per-phase deadline caps. Before these, every auxiliary A/B phase ran
# under the full QUERY_TIMEOUT_S (600s at SF<=10): two slow phases
# could eat 1200s of a 3300s wall budget and starve everything after
# them into "skipped" markers. The headline queries keep the full cap;
# the A/B phases are all sub-minute in the common case and get a cap
# sized ~3x their observed worst case instead.
PHASE_BUDGET_S = {
    "cached": 180.0, "adaptive": 240.0, "serving": 240.0,
    "serve": 240.0, "fleet": 240.0, "mview": 180.0, "agg": 420.0,
    "join": 420.0, "trace": 150.0, "slo": 300.0, "fusion": 240.0,
}


def _phase_deadline(phase: str) -> float:
    return _query_deadline(cap_s=PHASE_BUDGET_S.get(phase))


class _QueryTimeout(Exception):
    pass


@contextlib.contextmanager
def _deadline(seconds: float):
    """Raise _QueryTimeout in the main thread after ``seconds``."""
    if seconds <= 0 or not hasattr(signal, "setitimer"):
        yield
        return

    def _alarm(signum, frame):
        raise _QueryTimeout(f"query exceeded {seconds:.0f}s")

    prev = signal.signal(signal.SIGALRM, _alarm)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0.0)
        signal.signal(signal.SIGALRM, prev)


def _snapshot(payload: dict) -> None:
    if not PARTIAL_PATH:
        return
    with open(PARTIAL_PATH, "w") as f:
        json.dump(payload, f)

# documented Spark CPU local[*] SF1 estimates (see module docstring)
BASELINE_MS = {1: 900.0, 3: 700.0, 5: 1100.0}

# BENCH_MVIEW=0 skips the materialized-view refresh A/B (K appended
# micro-batches x M readers, spark.tpu.mview.incremental off vs on;
# refresh latency + device executions + byte-identity land under
# 'mview' in the result JSON)
MVIEW_MODE = os.environ.get("BENCH_MVIEW", "1") == "1"

# BENCH_AGG=0 skips the adaptive-aggregation A/B (low-NDV / high-NDV /
# skewed group-bys, spark.tpu.adaptive.agg.enabled off vs on; timing +
# byte-identity digest + per-strategy pick counts land under 'agg' in
# the result JSON; needs BENCH_MASTER=mesh[N] to engage)
AGG_MODE = os.environ.get("BENCH_AGG", "1") == "1"

# BENCH_JOIN=0 skips the hybrid-hash-join A/B (an out-of-core join run
# at the full memory budget, at 1/8 of it through the grant-driven
# hybrid join's planned spilling, and through the old reactive OOM
# ladder; replan counts + spill bytes + timing + byte-identity land
# under 'join' in the result JSON)
JOIN_MODE = os.environ.get("BENCH_JOIN", "1") == "1"

# BENCH_TRACE=0 skips the tracing-overhead A/B (q1/q3 timed with the
# span layer off vs always-on vs 10%-sampled; overhead % + byte-identity
# + the host/device/queue/transfer breakdown of one traced q3 land
# under 'trace' in the result JSON)
TRACE_MODE = os.environ.get("BENCH_TRACE", "1") == "1"

# BENCH_FUSION=0 skips the whole-query fusion A/B (q3/q5-shaped
# multi-exchange plans timed staged vs fused under adaptive execution;
# total latency, host/queue trace breakdown before/after, fused span
# counts and byte-identity land under 'fusion' in the result JSON;
# needs BENCH_MASTER=mesh[N] to engage)
FUSION_MODE = os.environ.get("BENCH_FUSION", "1") == "1"

# BENCH_FLEET=0 skips the fleet scaling sweep (QPS vs replica count on
# NON-cacheable unique-plan traffic over a sharded dataset with
# shard-ownership routing on; per-cell byte-identity against the
# 1-replica cell lands under 'fleet' in the result JSON)
FLEET_MODE = os.environ.get("BENCH_FLEET", "1") == "1"

# BENCH_SLO=0 skips the SLO serving A/B (needs --concurrency): the
# golden q1/q3/q5 mix under ~2x closed-loop overload with per-query
# deadlines, FIFO vs SLO mode (EDF + reject-at-admission); successful-
# within-SLO counts, p99, shed counts and byte-identity land under
# 'slo' in the result JSON
SLO_MODE = os.environ.get("BENCH_SLO", "1") == "1"


# robustness events worth surfacing in the result JSON: a benchmark run
# that silently retried stages or degraded to the chunked tier is not
# measuring what the headline number claims
_ROBUSTNESS_KINDS = ("stage_retry", "chunk_retry", "fault_injected",
                     "fault_recovered", "degraded_to_chunked")


def _robustness_counters() -> dict:
    from spark_tpu import metrics

    counts = {k: 0 for k in _ROBUSTNESS_KINDS}
    for ev in metrics.recent(4096):
        kind = ev.get("kind")
        if kind in counts:
            counts[kind] += 1
    return counts


def _shuffle_block() -> dict:
    """Per-query shuffle observability: exchange count, rows actually
    sent over ICI, buffer bytes, padding ratio (dead slots the static
    capacity contract shipped anyway), and any adaptive decisions —
    for the execution that just finished (metrics.last_query)."""
    from spark_tpu import metrics, tracing

    try:
        prof = tracing.exchange_profile(metrics.last_query())
    except Exception:
        return {}
    return {
        "exchanges": prof["exchanges"],
        "rows_sent": prof["rows_sent"],
        "buffer_bytes": prof["buffer_bytes"],
        "padding_ratio": prof["padding_ratio"],
        "aqe": prof["decisions"],
    }


def _query_bytes(plan, conf) -> int:
    """Bytes of live column data in the plan's scan leaves — the
    minimum the query must touch; used for the bandwidth bound. When the
    plan will execute out-of-HBM, the estimate comes from scan row
    counts (physically planning it would materialize the big scans)."""
    from spark_tpu.physical import chunked as CH
    from spark_tpu.plan import logical as L

    if CH.find_chunkable(plan, conf) is not None:
        total = 0
        for s in L.collect_nodes(plan, L.UnresolvedScan):
            total += CH._est_scan(s) or 0
        assert total, "no data leaves: benchmark would constant-fold"
        return total

    from spark_tpu.physical import operators as P
    from spark_tpu.physical.planner import plan_physical

    scans = []

    def collect(p):
        if isinstance(p, P.BatchScanExec):
            scans.append(p)
            return
        for c in p.children():
            collect(c)

    collect(plan_physical(plan))
    assert scans, "no data leaves: benchmark would constant-fold"
    total = 0
    for s in scans:
        for cd in s.batch.data.columns:
            total += cd.data.size * cd.data.dtype.itemsize
    return total


def _percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, dtype=np.float64), q)) \
        if values else 0.0


def _run_serving(spark, concurrency: int, queries: dict,
                 rounds: int = 2) -> dict:
    """Concurrent-clients serving mode: N closed-loop client threads
    each replay the golden query mix ``rounds`` times through the
    multi-tenant scheduler (spark_tpu/scheduler/). Every result is
    checked byte-identical against a serial reference run — a serving
    number from a scheduler that corrupts results under concurrency
    would be worse than no number. Reports QPS, p50/p95 end-to-end
    latency, and p50/p95 admission queue-wait."""
    import threading

    from spark_tpu.scheduler import QueryScheduler

    # serial reference (also the warm-up: compiles once, off the clock)
    ref = {q: spark.sql(sql).toArrow() for q, sql in queries.items()}

    sched = QueryScheduler(spark)
    lock = threading.Lock()
    latencies, waits, mismatched, errors = [], [], [], []

    def client(idx: int) -> None:
        for _ in range(rounds):
            for qnum in sorted(queries):
                sql = queries[qnum]
                t0 = time.perf_counter()
                try:
                    ticket = sched.submit_query(
                        lambda sql=sql: spark.sql(sql),
                        description=f"serving q{qnum} client{idx}")
                    tbl = ticket.result()
                except Exception as e:
                    with lock:
                        errors.append(f"q{qnum}: {type(e).__name__}: {e}")
                    continue
                lat_ms = (time.perf_counter() - t0) * 1e3
                ok = tbl.equals(ref[qnum])
                with lock:
                    latencies.append(lat_ms)
                    waits.append(ticket.queue_wait_ms())
                    if not ok:
                        mismatched.append(qnum)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall_s = time.perf_counter() - t0
    sched.stop()
    total = len(latencies)
    return {
        "concurrency": concurrency,
        "rounds": rounds,
        "queries_completed": total,
        "errors": errors[:10],
        "wall_s": round(wall_s, 2),
        "qps": round(total / wall_s, 2) if wall_s else 0.0,
        "p50_ms": round(_percentile(latencies, 50), 1),
        "p95_ms": round(_percentile(latencies, 95), 1),
        "queue_wait_p50_ms": round(_percentile(waits, 50), 1),
        "queue_wait_p95_ms": round(_percentile(waits, 95), 1),
        "byte_identical_to_serial": not mismatched and not errors,
        "mismatched_queries": sorted(set(mismatched)),
    }


def _run_slo_ab(spark, concurrency: int,
                duration_s: float = 6.0,
                slo_multiplier: float = 3.0) -> dict:
    """SLO serving A/B (ROADMAP item 5 acceptance): the golden q1/q3/q5
    mix driven closed-loop at ~2x overload (clients >> workers), each
    query carrying its own deadline (the stated SLO: ``slo_multiplier``
    x that query's warm serial latency), once through the plain FIFO
    scheduler and once with spark.tpu.slo.enabled — per-plan latency
    prediction, EDF ordering, and reject-at-admission. Both arms run
    the same fixed wall-clock window, so the within-SLO counts are
    directly comparable goodput. The claim under test: the SLO arm
    serves MORE queries successfully WITHIN their deadlines (doomed
    queries are shed in milliseconds at admission instead of rotting in
    the queue and making every other query late; tight-deadline queries
    jump the EDF queue instead of waiting behind long scans) and its
    successes meet the stated SLO at p99. Every completed result is
    checked byte-identical against a serial reference — shedding may
    drop queries, it must never change bytes."""
    import threading

    from spark_tpu import metrics
    from spark_tpu.scheduler import QueryScheduler
    from spark_tpu.slo.edf import InfeasibleDeadline
    from spark_tpu.tpch.queries import QUERIES

    queries = {q: QUERIES[q] for q in (1, 3, 5)}
    # serial reference (also the warm-up: compiles once, off the clock)
    ref = {q: spark.sql(sql).toArrow() for q, sql in queries.items()}
    run_ms = {}
    for q, sql in queries.items():
        t0 = time.perf_counter()
        spark.sql(sql).toArrow()
        run_ms[q] = (time.perf_counter() - t0) * 1e3
    deadline_ms = {q: slo_multiplier * v for q, v in run_ms.items()}
    workers = 2
    n_clients = max(2 * workers, concurrency)

    def arm(slo_on: bool) -> dict:
        conf = spark.conf
        conf.set("spark.tpu.scheduler.maxConcurrency", workers)
        conf.set("spark.tpu.scheduler.queueDepth", 64)
        conf.set("spark.tpu.slo.enabled", slo_on)
        if slo_on:
            conf.set("spark.tpu.slo.targetP99Ms",
                     max(deadline_ms.values()))
            # predictions come from warm serial observations but the
            # measured window runs contended; the margin sheds
            # marginal admissions so what IS admitted finishes inside
            # its deadline (the sizing guidance the README documents)
            conf.set("spark.tpu.slo.rejectMargin", 1.5)
            metrics.reset_slo()
        sched = None
        try:
            sched = QueryScheduler(spark)
            # train off the clock — identical protocol both arms (the
            # SLO arm's latency model learns each query's fingerprint;
            # the FIFO arm just re-warms the same caches)
            for q, sql in queries.items():
                for _ in range(2):
                    sched.submit_query(
                        lambda sql=sql: spark.sql(sql),
                        sql=sql).result(QUERY_TIMEOUT_S)
            time.sleep(0.1)  # let the trailing observations land
            lock = threading.Lock()
            lat, ratios, mismatched, errors = [], [], [], []
            within = [0]
            rejected = [0]
            missed = [0]
            t_end = time.perf_counter() + duration_s

            def client(idx: int) -> None:
                i = 0
                order = sorted(queries)
                while time.perf_counter() < t_end:
                    qnum = order[(idx + i) % len(order)]
                    i += 1
                    sql = queries[qnum]
                    t0 = time.perf_counter()
                    try:
                        t = sched.submit_query(
                            lambda sql=sql: spark.sql(sql),
                            deadline_s=deadline_ms[qnum] / 1e3,
                            sql=sql,
                            description=f"slo q{qnum} c{idx}")
                        tbl = t.result(QUERY_TIMEOUT_S)
                    except InfeasibleDeadline:
                        with lock:
                            rejected[0] += 1
                        # the shed cost the client microseconds; a real
                        # caller backs off for its SLO window instead
                        # of hammering admission in a tight loop
                        time.sleep(deadline_ms[qnum] / 1e3)
                        continue
                    except Exception as e:
                        with lock:
                            missed[0] += 1
                            errors.append(
                                f"q{qnum}: {type(e).__name__}")
                        continue
                    ms = (time.perf_counter() - t0) * 1e3
                    okq = tbl.equals(ref[qnum])
                    with lock:
                        lat.append(ms)
                        ratios.append(ms / deadline_ms[qnum])
                        if not okq:
                            mismatched.append(qnum)
                        if ms <= deadline_ms[qnum]:
                            within[0] += 1

            threads = [threading.Thread(target=client, args=(i,),
                                        daemon=True)
                       for i in range(n_clients)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall_s = time.perf_counter() - t0
        finally:
            if sched is not None:
                sched.stop()
            conf.unset("spark.tpu.scheduler.maxConcurrency")
            conf.unset("spark.tpu.scheduler.queueDepth")
            conf.unset("spark.tpu.slo.enabled")
            if slo_on:
                conf.unset("spark.tpu.slo.targetP99Ms")
                conf.unset("spark.tpu.slo.rejectMargin")
        offered = len(lat) + rejected[0] + missed[0]
        # typed deadline outcomes (late death under FIFO, early shed
        # under SLO) are EXPECTED under overload and reported above;
        # byte-identity is about the bytes actually served
        return {
            "policy": "EDF+reject" if slo_on else "FIFO",
            "offered": offered,
            "completed": len(lat),
            "within_slo": within[0],
            "within_slo_per_s": round(within[0] / wall_s, 2)
            if wall_s else 0.0,
            "rejected_at_admission": rejected[0],
            "missed_or_failed": missed[0],
            "wall_s": round(wall_s, 2),
            "p50_ms": round(_percentile(lat, 50), 1),
            "p99_ms": round(_percentile(lat, 99), 1),
            # latency normalized by each query's OWN deadline: <= 1.0
            # at p99 means the served stream met the stated SLO
            "p99_slo_ratio": round(_percentile(ratios, 99), 2),
            "byte_identical_to_serial": not mismatched,
            "mismatched_queries": sorted(set(mismatched)),
            "errors": errors[:10],
            **({"slo_counters": metrics.slo_stats()} if slo_on else {}),
        }

    out = {"stated_slo": f"{slo_multiplier:g}x warm serial latency "
                         "per query",
           "deadline_ms": {str(q): round(v, 1)
                           for q, v in deadline_ms.items()},
           "workers": workers, "clients": n_clients,
           "duration_s": duration_s,
           "overload_factor": round(n_clients / workers, 1),
           "serial_run_ms": {str(q): round(v, 1)
                             for q, v in run_ms.items()}}
    out["fifo"] = arm(False)
    if _wall_remaining() <= 10:
        out["slo"] = {"error": "skipped: wall budget exhausted"}
        return out
    out["slo"] = arm(True)
    f, s = out["fifo"], out["slo"]
    out["within_slo_improvement"] = (
        round(s["within_slo"] / f["within_slo"], 2)
        if f.get("within_slo") else
        ("inf" if s.get("within_slo") else 0.0))
    # stated SLO met at p99 when the 99th-percentile served latency,
    # each query normalized by its OWN deadline, lands at-or-under 1.0
    out["meets_stated_slo_p99"] = bool(
        s.get("within_slo", 0) > 0
        and s.get("p99_slo_ratio", 99.0) <= 1.0)
    out["byte_identical"] = (
        f.get("byte_identical_to_serial", False)
        and s.get("byte_identical_to_serial", False))
    return out


def _run_serve_ab(spark, concurrency: int, replicas_n: int,
                  rounds: int = 2) -> dict:
    """Federation-tier A/B (spark_tpu/serve/): the same golden q1/q3/q5
    mix driven over REAL HTTP through the FederationRouter, once with a
    single replica and the result cache off (the pre-federation
    serving path) and once with N replicas and the plan-keyed result
    cache on. Every response is checked byte-identical against a
    serial in-process reference — a QPS number from a cache that
    serves stale or corrupted bytes would be worse than no number."""
    import threading

    from spark_tpu.connect.server import Client
    from spark_tpu.serve import serve_fleet
    from spark_tpu.tpch.queries import QUERIES

    queries = {q: QUERIES[q] for q in (1, 3, 5)}
    # serial reference (also the warm-up: compiles once, off the clock)
    ref = {q: spark.sql(sql).toArrow() for q, sql in queries.items()}

    def drive(n_replicas: int, cache_on: bool) -> dict:
        spark.conf.set("spark.tpu.serve.resultCache.enabled", cache_on)
        cache = getattr(spark, "serve_result_cache", None)
        if cache is not None:
            cache.clear()  # each arm starts cold
        fleet = serve_fleet(spark, replicas=n_replicas)
        lock = threading.Lock()
        latencies, mismatched, errors = [], [], []

        def client(idx: int) -> None:
            c = Client(fleet.url, timeout=QUERY_TIMEOUT_S)
            for _ in range(rounds):
                for qnum in sorted(queries):
                    t0 = time.perf_counter()
                    try:
                        tbl = c.sql(queries[qnum])
                    except Exception as e:
                        with lock:
                            errors.append(
                                f"q{qnum}: {type(e).__name__}: {e}")
                        continue
                    lat_ms = (time.perf_counter() - t0) * 1e3
                    ok = tbl.equals(ref[qnum])
                    with lock:
                        latencies.append(lat_ms)
                        if not ok:
                            mismatched.append(qnum)

        threads = [threading.Thread(target=client, args=(i,),
                                    daemon=True)
                   for i in range(concurrency)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall_s = time.perf_counter() - t0
        fleet.stop()
        total = len(latencies)
        from spark_tpu import metrics as _metrics
        return {
            "replicas": n_replicas,
            "cache": "on" if cache_on else "off",
            "queries_completed": total,
            "errors": errors[:10],
            "wall_s": round(wall_s, 2),
            "qps": round(total / wall_s, 2) if wall_s else 0.0,
            "p50_ms": round(_percentile(latencies, 50), 1),
            "p95_ms": round(_percentile(latencies, 95), 1),
            "byte_identical_to_serial": not mismatched and not errors,
            "mismatched_queries": sorted(set(mismatched)),
            "serve_counters": _metrics.serve_stats(),
        }

    from spark_tpu import metrics as _metrics
    out = {"concurrency": concurrency, "rounds": rounds}
    try:
        _metrics.reset_serve()
        out["one_replica_cache_off"] = drive(1, False)
        if _wall_remaining() <= 10:
            out["fleet_cached"] = {
                "error": "skipped: wall budget exhausted"}
            return out
        _metrics.reset_serve()
        out["fleet_cached"] = drive(replicas_n, True)
        base = out["one_replica_cache_off"]
        fleet = out["fleet_cached"]
        if base.get("qps") and fleet.get("qps"):
            out["qps_speedup"] = round(fleet["qps"] / base["qps"], 2)
        if fleet.get("p95_ms") and base.get("p95_ms"):
            out["p95_reduction"] = round(
                base["p95_ms"] / fleet["p95_ms"], 2)
        out["byte_identical_to_serial"] = (
            base.get("byte_identical_to_serial", False)
            and fleet.get("byte_identical_to_serial", False))
    finally:
        spark.conf.unset("spark.tpu.serve.resultCache.enabled")
        cache = getattr(spark, "serve_result_cache", None)
        if cache is not None:
            cache.clear()
    return out


def _run_fleet_bench(spark, concurrency: int = 4,
                     cells: tuple = (1, 2, 4),
                     tables: int = 4, rows_per_table: int = 50_000,
                     queries_per_table: int = 12) -> dict:
    """Fleet scaling sweep (spark_tpu/serve/ownership.py): QPS vs
    replica count on NON-cacheable traffic — every request is a unique
    plan (a fresh literal), so the result cache never hits and the
    number measures the ownership-routed data plane, not memoization.
    The dataset is sharded across ``tables`` parquet tables so the
    rendezvous map spreads owners across the fleet. Every cell replays
    the SAME seeded query list; cells >1 are checked byte-identical
    against the 1-replica cell per query — a QPS curve that changes
    bytes with the replica count would be worse than no number."""
    import shutil
    import tempfile
    import threading

    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_tpu import metrics as _metrics
    from spark_tpu.connect.server import Client
    from spark_tpu.serve import serve_fleet

    d = tempfile.mkdtemp(prefix="bench_fleet_")
    rng = np.random.default_rng(1234)
    for t in range(tables):
        i = np.arange(rows_per_table)
        pq.write_table(pa.table({
            "s": pa.array((i % 53).astype(np.int64)),
            "v": pa.array(((i * 7919 + t) % 100_003).astype(np.int64)),
        }), os.path.join(d, f"shard{t}.parquet"))
        (spark.read.parquet(os.path.join(d, f"shard{t}.parquet"))
         .createOrReplaceTempView(f"fleet_b{t}"))
    # one seeded unique-literal query list, identical across cells
    cuts = rng.integers(0, 100_003, size=tables * queries_per_table)
    qlist = [
        (f"SELECT s, SUM(v) AS sv, COUNT(*) AS n FROM fleet_b{j % tables} "
         f"WHERE v >= {int(cuts[j])} GROUP BY s")
        for j in range(tables * queries_per_table)]
    spark.conf.set("spark.tpu.serve.ownership.enabled", True)
    spark.conf.set("spark.tpu.serve.resultCache.enabled", True)
    # warm-up off the clock: the query shape compiles ONCE per table;
    # without this the 1-replica cell absorbs all XLA compile time and
    # the scaling curve flatters the fleet
    for t in range(tables):
        spark.sql(qlist[t]).toArrow()
    reference: dict = {}

    def cell(n_replicas: int) -> dict:
        fleet = serve_fleet(spark, replicas=n_replicas)
        lock = threading.Lock()
        latencies, mismatched, errors = [], [], []
        next_q = [0]
        try:
            fleet.router.federation.probe(force=True)  # learn shards

            def worker() -> None:
                c = Client(fleet.url, timeout=QUERY_TIMEOUT_S)
                while True:
                    with lock:
                        j = next_q[0]
                        if j >= len(qlist):
                            return
                        next_q[0] += 1
                    t0 = time.perf_counter()
                    try:
                        tbl = c.sql(qlist[j])
                    except Exception as e:
                        with lock:
                            errors.append(
                                f"q{j}: {type(e).__name__}: {e}")
                        continue
                    lat_ms = (time.perf_counter() - t0) * 1e3
                    with lock:
                        latencies.append(lat_ms)
                        if n_replicas == cells[0]:
                            reference[j] = tbl
                        else:
                            ref = reference.get(j)
                            if ref is None or not tbl.equals(ref):
                                mismatched.append(j)

            threads = [threading.Thread(target=worker, daemon=True)
                       for _ in range(concurrency)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall_s = time.perf_counter() - t0
        finally:
            fleet.stop()
        snap = _metrics.serve_stats()
        return {
            "replicas": n_replicas,
            "queries_completed": len(latencies),
            "errors": errors[:10],
            "wall_s": round(wall_s, 2),
            "qps": round(len(latencies) / wall_s, 2) if wall_s else 0.0,
            "p50_ms": round(_percentile(latencies, 50), 1),
            "p95_ms": round(_percentile(latencies, 95), 1),
            "byte_identical_to_single_replica": (
                not mismatched and not errors),
            "mismatched_queries": sorted(set(mismatched))[:10],
            "cache_hits": snap.get("hits", 0),
            "epoch_mints": snap.get("epoch_mints", 0),
        }

    out = {"concurrency": concurrency,
           "tables": tables, "queries": len(qlist)}
    try:
        for n in cells:
            if _wall_remaining() <= 10:
                out[f"replicas_{n}"] = {
                    "error": "skipped: wall budget exhausted"}
                continue
            _metrics.reset_serve()
            out[f"replicas_{n}"] = cell(n)
        base = out.get(f"replicas_{cells[0]}", {})
        top = out.get(f"replicas_{cells[-1]}", {})
        if base.get("qps") and top.get("qps"):
            out["qps_speedup"] = round(top["qps"] / base["qps"], 2)
        out["byte_identical_to_single_replica"] = all(
            out.get(f"replicas_{n}", {}).get(
                "byte_identical_to_single_replica", False)
            for n in cells[1:])
    finally:
        spark.conf.unset("spark.tpu.serve.ownership.enabled")
        spark.conf.unset("spark.tpu.serve.resultCache.enabled")
        cache = getattr(spark, "serve_result_cache", None)
        if cache is not None:
            cache.clear()
        for t in range(tables):
            spark.catalog.dropTempView(f"fleet_b{t}")
        shutil.rmtree(d, ignore_errors=True)
    return out


def _run_mview_ab(spark, appends: int = 8, readers: int = 3,
                  base_rows: int = 200_000, delta_rows: int = 1_000,
                  n_keys: int = 64) -> dict:
    """Materialized-view refresh A/B (spark_tpu/mview/): a re-mergeable
    aggregate (groupBy(k).sum(v)) cached over a parquet directory, then
    K appended micro-batch files. Arm OFF pins mview.incremental=False
    (every refresh is a full recompute over the whole growing source);
    arm ON merges the delta partials into the HBM-resident batch. Per
    append we time the FIRST read (the refresh) and ``readers-1`` extra
    reads (fresh fingerprint hits), count device plan executions via
    the single-device engine entry point, and keep the Arrow IPC bytes
    of every step so the two arms are checked byte-identical — a fast
    refresh that serves different bytes would be worse than no number."""
    import shutil
    import tempfile

    import pyarrow as pa
    import pyarrow.parquet as pq

    import spark_tpu.api.functions as F
    from spark_tpu import metrics
    from spark_tpu.physical import planner as _planner
    from spark_tpu.serve import result_cache as rc

    def write_part(d: str, name: str, n: int, offset: int) -> None:
        i = np.arange(offset, offset + n)
        pq.write_table(pa.table({
            "k": pa.array([f"k{j % n_keys}" for j in i]),
            "v": pa.array((i % 97).astype(np.int64)),
        }), os.path.join(d, name))

    real_exec = _planner.execute_logical
    execs = [0]

    def counting_exec(plan, optimize=True):
        execs[0] += 1
        return real_exec(plan, optimize)

    def arm(incremental: bool) -> dict:
        d = tempfile.mkdtemp(prefix="bench_mview_")
        spark.conf.set("spark.tpu.mview.enabled", True)
        spark.conf.set("spark.tpu.mview.incremental", incremental)
        spark.cache_manager.clear()
        metrics.reset_mview()
        try:
            write_part(d, "base.parquet", base_rows, 0)
            df = (spark.read.parquet(d).groupBy("k")
                  .agg(F.sum("v").alias("s")))
            df.cache()
            t0 = time.perf_counter()
            df.collect()  # cold materialize (off the A/B clock)
            cold_ms = (time.perf_counter() - t0) * 1e3
            refresh_ms, read_ms, step_bytes = [], [], []
            _planner.execute_logical = counting_exec
            execs[0] = 0
            try:
                for j in range(appends):
                    write_part(d, f"delta{j:04d}.parquet", delta_rows,
                               base_rows + j * delta_rows)
                    t0 = time.perf_counter()
                    tbl = df.toArrow()  # first reader pays the refresh
                    refresh_ms.append((time.perf_counter() - t0) * 1e3)
                    step_bytes.append(rc.table_to_ipc(tbl))
                    for _ in range(max(0, readers - 1)):
                        t0 = time.perf_counter()
                        df.toArrow()  # fingerprint-fresh store hit
                        read_ms.append(
                            (time.perf_counter() - t0) * 1e3)
            finally:
                _planner.execute_logical = real_exec
            stats = metrics.mview_stats()
            return {
                "incremental": incremental,
                "cold_ms": round(cold_ms, 1),
                "refresh_ms_p50": round(
                    _percentile(refresh_ms, 50), 1),
                "refresh_ms_p95": round(
                    _percentile(refresh_ms, 95), 1),
                "refresh_ms_total": round(sum(refresh_ms), 1),
                "read_hit_ms_p50": round(_percentile(read_ms, 50), 1),
                "device_executions": execs[0],
                "incremental_merges": stats["incremental_merges"],
                "full_recomputes": stats["full_recomputes"],
                "_bytes": step_bytes,
            }
        finally:
            spark.cache_manager.clear()
            spark.conf.unset("spark.tpu.mview.incremental")
            spark.conf.unset("spark.tpu.mview.enabled")
            shutil.rmtree(d, ignore_errors=True)

    out = {"appends": appends, "readers": readers,
           "base_rows": base_rows, "delta_rows": delta_rows}
    off = arm(False)
    on = arm(True)
    identical = (len(off["_bytes"]) == len(on["_bytes"])
                 and all(a == b for a, b in
                         zip(off["_bytes"], on["_bytes"])))
    off.pop("_bytes")
    on.pop("_bytes")
    out["recompute_per_append"] = off
    out["incremental"] = on
    out["byte_identical"] = identical
    if on["refresh_ms_total"]:
        out["refresh_speedup"] = round(
            off["refresh_ms_total"] / on["refresh_ms_total"], 2)
    return out


def main():
    import argparse

    import jax

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--concurrency", type=int,
        default=int(os.environ.get("BENCH_CONCURRENCY", "0")),
        help="N>0 adds a serving benchmark: N concurrent client "
             "threads replay the golden q1/q3/q5 mix through the "
             "multi-tenant scheduler; QPS + p50/p95 latency and "
             "queue-wait land under 'serving' in the result JSON")
    ap.add_argument(
        "--serving-rounds", type=int,
        default=int(os.environ.get("BENCH_SERVING_ROUNDS", "2")),
        help="mix replays per serving client")
    ap.add_argument(
        "--replicas", type=int,
        default=int(os.environ.get("BENCH_REPLICAS", "0")),
        help="N>0 adds the federation A/B (needs --concurrency): the "
             "serving mix over real HTTP through the router, 1 replica "
             "cache off vs N replicas with the plan-keyed result cache "
             "on; qps/p50/p95 + byte-identity land under 'serve'")
    args = ap.parse_args()

    from spark_tpu.api.session import SparkSession
    from spark_tpu.plan.optimizer import optimize
    from spark_tpu.plan.subquery import rewrite_subqueries
    from spark_tpu.sql.parser import parse_sql
    from spark_tpu.tpch.gen import ensure_dataset, register_views
    from spark_tpu.tpch.queries import QUERIES

    global HBM_GBPS
    device = jax.devices()[0]
    platform = device.platform
    if platform != "tpu":
        sys.exit(f"bench.py measures the TPU and found platform="
                 f"{platform!r}: refusing to run (no CPU fallback)")
    if device.device_kind not in PEAK_HBM_GBPS:
        sys.exit(f"bench.py has no peak bandwidth for device_kind="
                 f"{device.device_kind!r}: add it to PEAK_HBM_GBPS with "
                 f"its source")
    HBM_GBPS = PEAK_HBM_GBPS[device.device_kind]
    builder = SparkSession.builder
    # BENCH_MASTER=mesh[N] runs the whole benchmark distributed (and
    # makes the adaptive A/B phase meaningful — it needs exchanges)
    master = os.environ.get("BENCH_MASTER", "")
    if master:
        builder = builder.master(master)
    spark = builder.getOrCreate()

    t0 = time.time()
    tmp = ensure_dataset(SF)  # generate-once disk cache
    gen_s = time.time() - t0
    t0 = time.time()
    register_views(spark, path=tmp)
    io_s = time.time() - t0

    results = {}

    # every phase (or query) skipped because the wall budget ran out,
    # by name — the final JSON carries the explicit list so a reader
    # never has to diff the expected phase set against what's present
    wall_skipped = []

    def _budget_skip(phase: str) -> dict:
        wall_skipped.append(phase)
        return {"error": "skipped: wall budget exhausted",
                "phase": phase, "wall_budget_s": WALL_BUDGET_S}

    def _phase_snapshot(**extra) -> None:
        _snapshot({"partial": True, "sf": SF,
                   "queries": {str(k): v for k, v in results.items()},
                   "wall_budget_skipped": list(wall_skipped),
                   "robustness": _robustness_counters(), **extra})

    for qnum in (1, 3, 5):
        if _wall_remaining() <= 5:
            results[qnum] = _budget_skip(f"headline:q{qnum}")
            continue
        print(f"[bench] q{qnum} starting", file=sys.stderr, flush=True)
        try:
            with _deadline(_query_deadline()):
                results[qnum] = _run_headline(spark, qnum)
        except _QueryTimeout as e:
            print(f"[bench] q{qnum} TIMED OUT: {e}",
                  file=sys.stderr, flush=True)
            results[qnum] = {"error": "timeout",
                             "timeout_s": QUERY_TIMEOUT_S}
        except Exception as e:  # record, don't kill the other queries
            print(f"[bench] q{qnum} FAILED: {e}",
                  file=sys.stderr, flush=True)
            results[qnum] = {"error": f"{type(e).__name__}: {e}"}
        _phase_snapshot()


    full = {}
    if FULL:
        budget_s = float(os.environ.get("BENCH_FULL_BUDGET", "1800"))
        sweep_t0 = time.time()
        for qnum in sorted(QUERIES):
            if qnum in results and "ms" in results[qnum]:
                full[qnum] = results[qnum]["ms"]
                continue
            elapsed = time.time() - sweep_t0
            if elapsed > budget_s:
                full[qnum] = f"skipped: sweep budget exhausted (all22:q{qnum})"
                continue
            if _wall_remaining() <= 5:
                wall_skipped.append(f"all22:q{qnum}")
                full[qnum] = f"skipped: wall budget exhausted (all22:q{qnum})"
                continue
            print(f"[bench] q{qnum} (sweep {elapsed:.0f}s)",
                  file=sys.stderr, flush=True)
            try:
                with _deadline(_query_deadline()):
                    df = spark.sql(QUERIES[qnum])
                    df.collect()  # warm-up 1: compile + stats
                    df.collect()  # warm-up 2: adaptive stats bound
                    times = []
                    for _ in range(max(2, N_ITER // 2)):
                        t0 = time.perf_counter()
                        df.collect()
                        times.append((time.perf_counter() - t0) * 1000.0)
                    full[qnum] = round(float(np.median(times)), 1)
            except _QueryTimeout:
                full[qnum] = f"error: timeout after {QUERY_TIMEOUT_S:.0f}s"
            except Exception as e:  # record, don't kill the headline
                full[qnum] = f"error: {type(e).__name__}: {e}"
            _phase_snapshot(
                all22_ms={str(k): v for k, v in full.items()})

    cached = None
    if CACHED_MODE:
        if _wall_remaining() <= 5:
            cached = _budget_skip("cached")
        else:
            print("[bench] cached mode: HBM-resident store re-runs",
                  file=sys.stderr, flush=True)
            try:
                with _deadline(_phase_deadline("cached")):
                    cached = _run_cached(spark, (1, 3, 5))
            except _QueryTimeout:
                cached = {"error": "timeout"}
            except Exception as e:
                cached = {"error": f"{type(e).__name__}: {e}"}
        _phase_snapshot(cached=cached)

    adaptive = None
    if os.environ.get("BENCH_ADAPTIVE", "1") == "1":
        if _wall_remaining() <= 5:
            adaptive = _budget_skip("adaptive")
        else:
            print("[bench] adaptive A/B: spark.tpu.adaptive.enabled "
                  "off vs on", file=sys.stderr, flush=True)
            try:
                with _deadline(_phase_deadline("adaptive")):
                    adaptive = _run_adaptive_compare(spark)
            except _QueryTimeout:
                adaptive = {"error": "timeout"}
            except Exception as e:
                adaptive = {"error": f"{type(e).__name__}: {e}"}
        _phase_snapshot(adaptive=adaptive)

    analysis_overhead = None
    if os.environ.get("BENCH_ANALYSIS", "1") == "1":
        if _wall_remaining() <= 5:
            analysis_overhead = _budget_skip("analysis")
        else:
            print("[bench] analyzer overhead: host-side static "
                  "analysis of the full 22-query suite",
                  file=sys.stderr, flush=True)
            try:
                qnums = sorted(QUERIES) if FULL else (1, 3, 5)
                analysis_overhead = _analysis_overhead(spark, qnums)
            except Exception as e:
                analysis_overhead = {"error": f"{type(e).__name__}: {e}"}
        _phase_snapshot(analysis=analysis_overhead)

    serving = None
    if args.concurrency > 0:
        if _wall_remaining() <= 5:
            serving = _budget_skip("serving")
        else:
            print(f"[bench] serving: {args.concurrency} concurrent "
                  "clients", file=sys.stderr, flush=True)
            try:
                with _deadline(_phase_deadline("serving")):
                    serving = _run_serving(
                        spark, args.concurrency,
                        {q: QUERIES[q] for q in (1, 3, 5)},
                        rounds=args.serving_rounds)
            except Exception as e:
                serving = {"error": f"{type(e).__name__}: {e}"}
        _phase_snapshot(serving=serving)

    slo_ab = None
    if SLO_MODE and args.concurrency > 0:
        if _wall_remaining() <= 5:
            slo_ab = _budget_skip("slo")
        else:
            print(f"[bench] slo A/B: q1/q3/q5 with deadlines at ~2x "
                  f"closed-loop overload, FIFO vs EDF+reject "
                  f"({max(4, args.concurrency)} clients)",
                  file=sys.stderr, flush=True)
            try:
                with _deadline(_phase_deadline("slo")):
                    slo_ab = _run_slo_ab(spark, args.concurrency)
            except _QueryTimeout:
                slo_ab = {"error": "timeout"}
            except Exception as e:
                slo_ab = {"error": f"{type(e).__name__}: {e}"}
        _phase_snapshot(slo=slo_ab)

    serve_ab = None
    if args.replicas > 0 and args.concurrency > 0:
        if _wall_remaining() <= 5:
            serve_ab = _budget_skip("serve")
        else:
            print(f"[bench] serve A/B: 1 replica cache off vs "
                  f"{args.replicas} replicas cache on "
                  f"({args.concurrency} clients over HTTP)",
                  file=sys.stderr, flush=True)
            try:
                with _deadline(_phase_deadline("serve")):
                    serve_ab = _run_serve_ab(
                        spark, args.concurrency, args.replicas,
                        rounds=args.serving_rounds)
            except Exception as e:
                serve_ab = {"error": f"{type(e).__name__}: {e}"}
        _phase_snapshot(serve=serve_ab)

    fleet_bench = None
    if FLEET_MODE:
        if _wall_remaining() <= 5:
            fleet_bench = _budget_skip("fleet")
        else:
            print("[bench] fleet scaling: QPS vs replicas {1,2,4}, "
                  "unique-plan traffic, ownership routing on",
                  file=sys.stderr, flush=True)
            try:
                with _deadline(_phase_deadline("fleet")):
                    fleet_bench = _run_fleet_bench(spark)
            except Exception as e:
                fleet_bench = {"error": f"{type(e).__name__}: {e}"}
        _phase_snapshot(fleet=fleet_bench)

    mview = None
    if MVIEW_MODE:
        if _wall_remaining() <= 5:
            mview = _budget_skip("mview")
        else:
            print("[bench] mview A/B: appended micro-batches, "
                  "spark.tpu.mview.incremental off vs on",
                  file=sys.stderr, flush=True)
            try:
                with _deadline(_phase_deadline("mview")):
                    mview = _run_mview_ab(spark)
            except _QueryTimeout:
                mview = {"error": "timeout"}
            except Exception as e:
                mview = {"error": f"{type(e).__name__}: {e}"}
        _phase_snapshot(mview=mview)

    agg_ab = None
    if AGG_MODE:
        if _wall_remaining() <= 5:
            agg_ab = _budget_skip("agg")
        else:
            print("[bench] agg A/B: low/high-NDV, huge-domain, skewed "
                  "and hot-key group-bys, spark.tpu.adaptive.agg off "
                  "vs on vs forced sort/presplit",
                  file=sys.stderr, flush=True)
            try:
                with _deadline(_phase_deadline("agg")):
                    agg_ab = _run_agg_ab(spark)
            except _QueryTimeout:
                agg_ab = {"error": "timeout"}
            except Exception as e:
                agg_ab = {"error": f"{type(e).__name__}: {e}"}
        _phase_snapshot(agg=agg_ab)

    join_ab = None
    if JOIN_MODE:
        if _wall_remaining() <= 5:
            join_ab = _budget_skip("join")
        else:
            print("[bench] join A/B: grant-driven hybrid hash join at "
                  "full vs 1/8 memory budget vs the old OOM ladder",
                  file=sys.stderr, flush=True)
            try:
                with _deadline(_phase_deadline("join")):
                    join_ab = _run_join_ab(spark)
            except _QueryTimeout:
                join_ab = {"error": "timeout"}
            except Exception as e:
                join_ab = {"error": f"{type(e).__name__}: {e}"}
        _phase_snapshot(join=join_ab)

    trace_ab = None
    if TRACE_MODE:
        if _wall_remaining() <= 5:
            trace_ab = _budget_skip("trace")
        else:
            print("[bench] trace A/B: q1/q3 span layer off vs on vs "
                  "sampled, + host/device/queue breakdown of one q3",
                  file=sys.stderr, flush=True)
            try:
                with _deadline(_phase_deadline("trace")):
                    trace_ab = _run_trace_ab(spark)
            except _QueryTimeout:
                trace_ab = {"error": "timeout"}
            except Exception as e:
                trace_ab = {"error": f"{type(e).__name__}: {e}"}
        _phase_snapshot(trace=trace_ab)

    fusion_ab = None
    if FUSION_MODE:
        if _wall_remaining() <= 5:
            fusion_ab = _budget_skip("fusion")
        else:
            print("[bench] fusion A/B: multi-exchange plans staged vs "
                  "fused (spark.tpu.fusion.enabled off vs on)",
                  file=sys.stderr, flush=True)
            try:
                with _deadline(_phase_deadline("fusion")):
                    fusion_ab = _run_fusion_ab(spark)
            except _QueryTimeout:
                fusion_ab = {"error": "timeout"}
            except Exception as e:
                fusion_ab = {"error": f"{type(e).__name__}: {e}"}
        _phase_snapshot(fusion=fusion_ab)

    # totals cover the queries that finished; failed/timed-out ones are
    # reported per-query and excluded so the JSON stays valid and the
    # headline number stays meaningful (flagged via queries_failed)
    ok = {q: r for q, r in results.items() if "ms" in r}
    total_ms = sum(r["ms"] for r in ok.values())
    vs = (sum(BASELINE_MS[q] for q in ok) * SF / total_ms
          if total_ms else 0.0)
    final = {
        "metric": f"tpch_sf{SF:g}_q1q3q5_total",
        "value": round(total_ms, 1),
        "unit": "ms",
        # warmup is accounted SEPARATELY from the headline value: the
        # metric is steady-state wall-clock
        "warmup_total_s": round(
            sum(r.get("warmup_s", 0.0) for r in ok.values()), 1),
        "vs_baseline": round(vs, 3),
        "platform": platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
        "peak_hbm_gbps": HBM_GBPS,
        "sf": SF,
        "iters": N_ITER,
        "query_timeout_s": QUERY_TIMEOUT_S,
        "queries_failed": sorted(q for q in results if q not in ok),
        "gen_s": round(gen_s, 1),
        "parquet_io_s": round(io_s, 1),
        "baseline": "Spark CPU local[*] SF1 estimate (see bench.py docstring)",
        "robustness": _robustness_counters(),
        "wall_budget_s": WALL_BUDGET_S,
        "wall_used_s": round(time.time() - _WALL_T0, 1),
        "wall_budget_skipped": wall_skipped,
        "queries": {str(k): v for k, v in results.items()},
        **({"cached": cached} if cached is not None else {}),
        **({"adaptive": adaptive} if adaptive is not None else {}),
        **({"serving": serving} if serving is not None else {}),
        **({"slo": slo_ab} if slo_ab is not None else {}),
        **({"serve": serve_ab} if serve_ab is not None else {}),
        **({"fleet": fleet_bench} if fleet_bench is not None else {}),
        **({"mview": mview} if mview is not None else {}),
        **({"agg": agg_ab} if agg_ab is not None else {}),
        **({"join": join_ab} if join_ab is not None else {}),
        **({"trace": trace_ab} if trace_ab is not None else {}),
        **({"fusion": fusion_ab} if fusion_ab is not None else {}),
        **({"analysis": analysis_overhead}
           if analysis_overhead is not None else {}),
        **({"all22_ms": {str(k): v for k, v in full.items()}}
           if full else {}),
    }
    # the complete document also lands at PARTIAL_PATH: a driver that
    # kills the process between here and stdout flush (rc=124 with
    # parsed:null) still finds every completed result on disk
    _snapshot(final)
    print(json.dumps(final), flush=True)
    failed = _failed_phases(final)
    if failed:
        print(f"[bench] FAILED phases: {failed}", file=sys.stderr)
        sys.exit(1)


def _failed_phases(doc, path: str = "") -> list:
    """Every place in the result document where a phase or a query
    recorded an error (a timeout included). Wall-budget skips are
    listed under wall_budget_skipped and are not failures."""
    out = []
    if isinstance(doc, dict):
        err = doc.get("error")
        if isinstance(err, str) and not err.startswith("skipped"):
            out.append(path or "?")
        for k, v in doc.items():
            out.extend(_failed_phases(v, f"{path}.{k}" if path else str(k)))
    elif isinstance(doc, str) and doc.startswith("error:"):
        out.append(path)
    return out


def _run_cached(spark, qnums, rounds: int = 3) -> dict:
    """Cached-mode report: cache() the TPC-H tables into the
    HBM-resident MemoryStore, then time each query cold (first run —
    materializes the cached tables on device) vs warm (store hits:
    no parquet decode, no dictionary encode, no host->device
    transfer). Every run is checked byte-identical against the
    uncached reference. The warm/cold split is the store's headline
    number: warm re-runs of q1/q3/q5 should be several times faster."""
    from spark_tpu.tpch.queries import QUERIES

    ref = {q: spark.sql(QUERIES[q]).toArrow() for q in qnums}
    tables = [spark.table(t) for t in spark.catalog.listTables()]
    for df in tables:
        df.cache()
    out = {}
    try:
        for q in qnums:
            df = spark.sql(QUERIES[q])
            t0 = time.perf_counter()
            cold_tbl = df.toArrow()
            cold_ms = (time.perf_counter() - t0) * 1e3
            warm_times, identical = [], cold_tbl.equals(ref[q])
            for _ in range(rounds):
                t0 = time.perf_counter()
                tbl = df.toArrow()
                warm_times.append((time.perf_counter() - t0) * 1e3)
                identical = identical and tbl.equals(ref[q])
            warm_ms = float(np.median(warm_times))
            out[q] = {
                "cold_ms": round(cold_ms, 1),
                "warm_ms": round(warm_ms, 1),
                "speedup": round(cold_ms / warm_ms, 2) if warm_ms
                else 0.0,
                "byte_identical": bool(identical),
            }
    finally:
        for df in tables:
            df.unpersist()
    out["store"] = spark.memory_store.stats()
    out["memory"] = spark.memory_manager.snapshot()
    return {str(k): v for k, v in out.items()}


def _run_adaptive_compare(spark) -> dict:
    """Adaptive-vs-static A/B over the distributed engine: the two
    exchange-heavy shapes AQE targets (distributed group-by, join +
    group-by), timed with ``spark.tpu.adaptive.enabled`` off then on.
    Results must be byte-identical — a faster wrong answer is not a
    result — and the padding ratio (dead slots shipped over ICI) should
    drop under adaptive capacity compaction. Skipped on single-device
    sessions, where no exchange stage exists to re-plan (run with
    BENCH_MASTER=mesh[N] to engage)."""
    from spark_tpu import metrics

    if getattr(spark, "_mesh", None) is None:
        return {"skipped": "single-device session (no mesh): no "
                           "exchange stages to re-plan"}
    queries = {
        "groupby": "SELECT l_suppkey, sum(l_quantity) AS s, "
                   "count(*) AS c FROM lineitem GROUP BY l_suppkey "
                   "ORDER BY l_suppkey",
        "join_groupby": "SELECT c_custkey, count(*) AS cnt "
                        "FROM customer, orders "
                        "WHERE c_custkey = o_custkey "
                        "GROUP BY c_custkey ORDER BY c_custkey",
    }
    out = {}
    conf = spark.conf
    try:
        for name, sql in queries.items():
            df = spark.sql(sql)

            def timed(adaptive):
                conf.set("spark.tpu.adaptive.enabled", adaptive)
                ref = df.toArrow()  # warm-up: compile off the clock
                t0 = time.perf_counter()
                got = df.toArrow()
                ms = (time.perf_counter() - t0) * 1000.0
                return ref, got, round(ms, 1), _shuffle_block()

            _, off_tbl, off_ms, off_sh = timed(False)
            _, on_tbl, on_ms, on_sh = timed(True)
            out[name] = {
                "off_ms": off_ms,
                "on_ms": on_ms,
                "byte_identical": bool(on_tbl.equals(off_tbl)),
                "padding_ratio_off": off_sh.get("padding_ratio"),
                "padding_ratio_on": on_sh.get("padding_ratio"),
                "buffer_bytes_off": off_sh.get("buffer_bytes"),
                "buffer_bytes_on": on_sh.get("buffer_bytes"),
                "aqe": on_sh.get("aqe", []),
            }
    finally:
        conf.unset("spark.tpu.adaptive.enabled")
    return out


def _run_agg_ab(spark) -> dict:
    """Adaptive-aggregation A/B: the five key distributions the
    strategy switch discriminates — low NDV (hash-partial territory),
    high NDV ~ rows over a packable domain (partial-bypass:
    pre-aggregation shrinks nothing), high NDV over a HUGE domain (the
    sort rung: range exchange + segmented merge, key-ordered output
    elides the downstream orderBy sort), skewed (the reactive skew fan
    territory), and hot-key (one key dominates hard enough that the
    Count-Min sketch elects proactive pre-splitting) — each timed with
    adaptive execution off (the static partial->final plan, exchanges
    fused at worst-case capacity) then fully on (AQE + the aggregation
    strategy switch). Results must be byte-identical; the JSON records
    the digest, per-strategy pick counts (metrics.agg_stats delta), and
    the measured NDV/rows ratio per workload.

    Fourth arm: per-workload FORCED strategies isolate the new rungs
    against the best pre-existing alternative — ``sort`` forced on
    high_ndv (vs the bypass auto used to pick), ``bypass`` forced on
    huge_domain (vs the auto sort pick), and ``bypass``/``sort``
    forced on hot_key (auto presplit vs the raw-row exchanges whose
    hot destination the destination-reactive skew fan has to absorb).
    Skipped on single-device sessions (run with BENCH_MASTER=mesh[N]
    to engage)."""
    import numpy as np
    import pyarrow as pa

    from spark_tpu import metrics
    from spark_tpu.api import functions as F

    if getattr(spark, "_mesh", None) is None:
        return {"skipped": "single-device session (no mesh): no "
                           "partial->final split to adapt"}
    rng = np.random.default_rng(7)
    n = int(os.environ.get("BENCH_AGG_ROWS", "120000"))
    workloads = {
        "low_ndv": rng.integers(0, 64, n),
        "high_ndv": rng.permutation(n).astype(np.int64),
        # near-distinct keys spread over ~1.2e11: beyond both the hash
        # domain limit and sortDomainWidth, so auto lands on the sort
        # rung (and the orderBy("k") below rides its sorted output)
        "huge_domain": rng.permutation(n).astype(np.int64) * 1_000_003,
        "skewed": np.where(rng.random(n) < 0.9, 7,
                           rng.integers(0, 100000, n)),
        # one key carries a third of the rows and the tail is
        # near-distinct over a huge domain: the crossover elects a
        # raw-row exchange (the sort rung), exactly where one hot key
        # overloads a single destination — so the Count-Min estimate
        # drives the pre-split rung instead
        "hot_key": np.where(np.arange(n) % 3 == 0, 7,
                            rng.permutation(n).astype(np.int64)
                            * 1_000_003),
    }
    # fourth arm per workload: forced strategies that pin the baseline
    # the new rung must beat — sort vs the bypass the crossover used
    # to pick on high NDV, and presplit vs the raw-row strategies
    # whose hot destination the reactive skew fan would handle
    forced_arms = {
        "high_ndv": ("sort",), "huge_domain": ("bypass",),
        "skewed": ("partial",), "hot_key": ("bypass", "sort"),
    }
    out = {}
    conf = spark.conf
    try:
        # hot-key threshold at 2x the fair per-device share (the
        # conservative default 4x needs a >50% hot key at 8 devices)
        conf.set("spark.tpu.adaptive.agg.presplitFactor", 2)
        for name, keys in workloads.items():
            if _wall_remaining() <= 30:
                out[name] = {"skipped": "wall budget exhausted"}
                continue
            tbl = pa.table({
                "k": pa.array(keys, pa.int64()),
                "v": pa.array(rng.integers(0, 1000, n), pa.int64()),
            })
            df = (spark.createDataFrame(tbl).groupBy("k")
                  .agg(F.sum("v").alias("s"), F.count("v").alias("c"),
                       F.min("v").alias("mn"), F.max("v").alias("mx"))
                  .orderBy("k"))

            def timed(adaptive_on, agg_on, force=None):
                conf.set("spark.tpu.adaptive.enabled", adaptive_on)
                conf.set("spark.tpu.adaptive.agg.enabled", agg_on)
                if force:
                    conf.set("spark.tpu.adaptive.agg.strategy", force)
                else:
                    conf.unset("spark.tpu.adaptive.agg.strategy")
                df.toArrow()  # warm-up: compile off the clock
                before = metrics.agg_stats()
                t0 = time.perf_counter()
                got = df.toArrow()
                ms = (time.perf_counter() - t0) * 1000.0
                picks = {k: v - before.get(k, 0)
                         for k, v in metrics.agg_stats().items()
                         if v - before.get(k, 0)}
                return got, round(ms, 1), picks

            # four arms: fully static plan / AQE with the static
            # partial->final strategy / AQE + the strategy switch /
            # AQE with a pinned per-workload baseline strategy — so
            # both the switch's own contribution (on top of AQE's
            # capacity compaction) and the new rung's margin over the
            # best pre-existing strategy are visible
            off_tbl, off_ms, _ = timed(False, False)
            _, aqe_ms, _ = timed(True, False)
            on_tbl, on_ms, picks = timed(True, True)
            ev = next((e for e in reversed(metrics.recent(256))
                       if e.get("kind") == "agg"), {})
            forced = {}
            for strat in forced_arms.get(name, ()):
                f_tbl, f_ms, f_picks = timed(True, True, force=strat)
                forced[strat] = {
                    "ms": f_ms,
                    "byte_identical": bool(f_tbl.equals(off_tbl)),
                    "strategy_picks": f_picks,
                }
            out[name] = {
                "rows": n,
                "off_ms": off_ms,
                "aqe_only_ms": aqe_ms,
                "on_ms": on_ms,
                "speedup": round(off_ms / on_ms, 2) if on_ms else None,
                "speedup_vs_aqe": (round(aqe_ms / on_ms, 2)
                                   if on_ms else None),
                "byte_identical": bool(on_tbl.equals(off_tbl)),
                "strategy_picks": picks,
                "ndv_estimate": ev.get("ndv"),
                "ndv_ratio": ev.get("ratio"),
                "hot_keys": ev.get("hot_keys"),
                **({"forced": forced} if forced else {}),
            }
    finally:
        conf.unset("spark.tpu.adaptive.agg.presplitFactor")
        conf.unset("spark.tpu.adaptive.agg.strategy")
        conf.unset("spark.tpu.adaptive.agg.enabled")
        conf.unset("spark.tpu.adaptive.enabled")
    return out


def _run_join_ab(spark) -> dict:
    """Hybrid-hash-join A/B: one out-of-core fact/dim join (SF0.1-ish:
    300k fact rows against a 20k-key dim, both sides over the device
    batch budget so the tier-3 join engages) run three ways —

    - ``hybrid_full``:  hybrid join, full memory budget (the grant
      covers staging: everything stays resident, zero spills);
    - ``hybrid_1_8``:   hybrid join, budget cut to 1/8 of the staged
      bytes (planned spilling: a single pass that spills the
      partitions beyond the grant, still ZERO ladder replans);
    - ``ladder``:       hybrid off and the whole-batch execution killed
      with an injected device OOM — the old reactive path, which pays
      >= 1 ladder replan (a wasted device execution) for the same
      memory pressure.

    Per arm the JSON records wall ms, the recovery replan count, spill
    bytes/partitions, the bytes granted by the unified memory manager,
    and byte-identity against the resident reference run."""
    import shutil
    import tempfile

    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_tpu import metrics

    rng = np.random.default_rng(17)
    n = int(os.environ.get("BENCH_JOIN_ROWS", "300000"))
    ndim = 20_000
    tmp = tempfile.mkdtemp(prefix="bench_join_")
    fact = pa.table({
        "k": pa.array(rng.integers(0, ndim, n), pa.int64()),
        "v": pa.array(rng.integers(0, 100, n), pa.int64()),
    })
    dim = pa.table({
        "dk": pa.array(np.arange(ndim, dtype=np.int64)),
        "w": pa.array((np.arange(ndim) % 997).astype(np.int64)),
    })
    fp = os.path.join(tmp, "fact.parquet")
    dp = os.path.join(tmp, "dim.parquet")
    pq.write_table(fact, fp)
    pq.write_table(dim, dp)
    spark.read.parquet(fp).createOrReplaceTempView("bj_fact")
    spark.read.parquet(dp).createOrReplaceTempView("bj_dim")
    sql = ("select sum(v * w) as s, count(*) as c "
           "from bj_fact join bj_dim on k = dk")
    conf = spark.conf
    staged = fact.nbytes + dim.nbytes
    out = {"rows": n, "staged_bytes": int(staged)}
    try:
        # resident reference (default budget, default batch bytes)
        t0 = time.perf_counter()
        base = [(r.s, r.c) for r in spark.sql(sql).collect()]
        out["resident_ms"] = round((time.perf_counter() - t0) * 1000, 1)

        def arm(budget, hybrid, inject_oom, batch_bytes):
            if batch_bytes is not None:
                conf.set("spark.tpu.maxDeviceBatchBytes", batch_bytes)
            conf.set("spark.tpu.join.hybrid.enabled", hybrid)
            conf.set("spark.tpu.scheduler.hbmBudgetBytes", budget)
            if inject_oom:
                conf.set("spark.tpu.faultInjection.execute.device",
                         "nth:1:oom")
            try:
                metrics.reset_join()
                metrics.reset_recovery()
                t0 = time.perf_counter()
                got = [(r.s, r.c) for r in spark.sql(sql).collect()]
                ms = (time.perf_counter() - t0) * 1000.0
                js = metrics.join_stats()
                return {
                    "wall_ms": round(ms, 1),
                    "replans": metrics.recovery_stats()["replans"],
                    "spill_bytes": js["spill_bytes"],
                    "spilled_partitions": js["spilled_partitions"],
                    "granted_bytes": js["grant_bytes"],
                    "byte_identical": got == base,
                }
            finally:
                conf.unset("spark.tpu.maxDeviceBatchBytes")
                conf.unset("spark.tpu.join.hybrid.enabled")
                conf.unset("spark.tpu.scheduler.hbmBudgetBytes")
                conf.unset("spark.tpu.faultInjection.execute.device")

        # both sides over a 256 KiB batch budget -> tier-3 hybrid join
        out["hybrid_full"] = arm(2 << 30, True, False, 256 * 1024)
        if _wall_remaining() > 5:
            out["hybrid_1_8"] = arm(max(1 << 16, staged // 8), True,
                                    False, 256 * 1024)
        if _wall_remaining() > 5:
            # old path: resident execution dies with OOM, the reactive
            # ladder replans into the chunked tier
            out["ladder"] = arm(max(1 << 16, staged // 8), False,
                                True, None)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def _run_trace_ab(spark) -> dict:
    """Tracing-overhead A/B: q1 and q3 timed (median of 3 warm runs)
    with the span layer off (spark.tpu.trace.enabled=false), always-on
    (the default), and 10%-sampled. The headline number is
    overhead_pct — always-on tracing must stay in the low single
    digits on a warm q1 — and every arm's Arrow output must be
    byte-identical to the untraced run. One fully-traced q3 run is
    then decomposed via tracing.trace_breakdown() into
    host/device/queue/transfer ms so the JSON shows where the wall
    time of a real query actually goes."""
    from spark_tpu import metrics, tracing
    from spark_tpu.tpch.queries import QUERIES

    conf = spark.conf
    out = {}
    try:
        for q in (1, 3):
            df = spark.sql(QUERIES[q])

            def timed(enabled, ratio):
                conf.set("spark.tpu.trace.enabled", enabled)
                conf.set("spark.tpu.trace.sampleRatio", ratio)
                df.toArrow()  # warm-up: compile off the clock
                got, runs = None, []
                for _ in range(3):
                    t0 = time.perf_counter()
                    got = df.toArrow()
                    runs.append((time.perf_counter() - t0) * 1000.0)
                return got, round(sorted(runs)[1], 1)

            off_tbl, off_ms = timed(False, 1.0)
            on_tbl, on_ms = timed(True, 1.0)
            samp_tbl, samp_ms = timed(True, 0.1)
            out[f"q{q}"] = {
                "off_ms": off_ms,
                "on_ms": on_ms,
                "sampled_ms": samp_ms,
                "overhead_pct": (round((on_ms - off_ms) / off_ms * 100, 2)
                                 if off_ms else None),
                "sampled_overhead_pct": (
                    round((samp_ms - off_ms) / off_ms * 100, 2)
                    if off_ms else None),
                "byte_identical": bool(on_tbl.equals(off_tbl)
                                       and samp_tbl.equals(off_tbl)),
            }
        # one fully-traced q3: where did the wall time go?
        conf.set("spark.tpu.trace.enabled", True)
        conf.set("spark.tpu.trace.sampleRatio", 1.0)
        spark.sql(QUERIES[3]).toArrow()
        evs = metrics.last_query()
        bd = tracing.trace_breakdown(evs)
        out["q3_breakdown"] = {
            **{k: round(v, 1) for k, v in bd.items()},
            "spans": sum(1 for e in evs if e.get("kind") == "span"),
            "trace_id": next((e.get("trace_id") for e in evs
                              if e.get("trace_id")), None),
        }
    finally:
        conf.unset("spark.tpu.trace.enabled")
        conf.unset("spark.tpu.trace.sampleRatio")
    return out


def _run_fusion_ab(spark) -> dict:
    """Whole-query fusion A/B: the two multi-exchange shapes the fused
    span targets — a float-sum group-by under a global sort (the q5
    tail: the agg strategy is PINNED by legality, so the capacity
    decision is the only adaptive decision and both exchange+consumer
    pairs fuse) and the same tail behind a join (the q3 shape: the
    broadcast switch stays a host decision and records its bailout,
    the post-join pairs still fuse) — timed with adaptive execution on
    and ``spark.tpu.fusion.enabled`` off (staged: one stats fetch +
    re-trace per exchange) then on (one XLA program, decision on
    device). Workloads are synthesized float columns rather than
    TPC-H SQL because the TPC-H money columns are DECIMAL(12,2) —
    exact int64 aggregates whose strategy crossover is live, which
    correctly bails the whole plan to staged (``agg_strategy``). The
    JSON records total latency AND the trace host/queue components
    before/after: fusion's claim is specifically that inter-stage host
    time goes to ~0 while bytes stay identical. Skipped on
    single-device sessions (run with BENCH_MASTER=mesh[N] to engage)."""
    import numpy as np
    import pyarrow as pa

    from spark_tpu import metrics, tracing

    if getattr(spark, "_mesh", None) is None:
        return {"skipped": "single-device session (no mesh): no "
                           "exchange stages to fuse"}
    rng = np.random.default_rng(11)
    n = int(os.environ.get("BENCH_FUSION_ROWS", "400000"))
    spark.createDataFrame(pa.table({
        "k": pa.array(rng.integers(0, 4000, n), pa.int64()),
        "f": pa.array(rng.random(n) * 100.0, pa.float64()),
    })).createOrReplaceTempView("fusion_fact")
    spark.createDataFrame(pa.table({
        "k2": pa.array(np.arange(4000, dtype=np.int64), pa.int64()),
        "w": pa.array(rng.random(4000), pa.float64()),
    })).createOrReplaceTempView("fusion_dim")
    small = int(os.environ.get("BENCH_FUSION_SMALL_ROWS", "4000"))
    spark.createDataFrame(pa.table({
        "k": pa.array(rng.integers(0, 400, small), pa.int64()),
        "f": pa.array(rng.random(small) * 100.0, pa.float64()),
    })).createOrReplaceTempView("fusion_fact_small")
    queries = {
        "groupby_sort": "SELECT k, sum(f) AS s FROM fusion_fact "
                        "GROUP BY k ORDER BY k",
        "join_groupby_sort": "SELECT k, sum(f) AS s "
                             "FROM fusion_fact, fusion_dim "
                             "WHERE k = k2 GROUP BY k ORDER BY k",
        # the dispatch-bound regime: per-stage fixed costs (program
        # launches, stats readbacks) dominate tiny inputs, which is
        # where collapsing k stages into one program pays most
        "groupby_sort_small": "SELECT k, sum(f) AS s "
                              "FROM fusion_fact_small "
                              "GROUP BY k ORDER BY k",
    }
    out = {"rows": n, "rows_small": small}
    conf = spark.conf
    conf.set("spark.tpu.adaptive.enabled", True)
    try:
        for name, sql in queries.items():
            df = spark.sql(sql)

            def timed(fused):
                conf.set("spark.tpu.fusion.enabled", fused)
                df.toArrow()  # warm-up: compile off the clock
                got, runs = None, []
                for _ in range(3):
                    metrics.reset_fusion()  # stats reflect one run
                    metrics.query_start(f"bench-fusion-{name}")
                    t0 = time.perf_counter()
                    got = df.toArrow()
                    runs.append((time.perf_counter() - t0) * 1000.0)
                evs = metrics.last_query()
                bd = tracing.trace_breakdown(evs)
                # the inter-stage host syncs fusion exists to remove:
                # each exchange.stats span is a stats stage dispatch +
                # D-integer readback + host decision between stages
                syncs = [e for e in evs if e.get("kind") == "span"
                         and e.get("name") == "exchange.stats"]
                bd["stats_syncs"] = len(syncs)
                bd["stats_sync_ms"] = round(
                    sum(float(e.get("ms", 0.0)) for e in syncs), 3)
                return (got, round(sorted(runs)[1], 1), bd,
                        metrics.fusion_stats())

            off_tbl, off_ms, off_bd, _ = timed(False)
            on_tbl, on_ms, on_bd, st = timed(True)
            out[name] = {
                "staged_ms": off_ms,
                "fused_ms": on_ms,
                "speedup": round(off_ms / on_ms, 2) if on_ms else 0.0,
                "byte_identical": bool(on_tbl.equals(off_tbl)),
                "trace_breakdown": {
                    "host_ms_staged": off_bd.get("host_ms"),
                    "host_ms_fused": on_bd.get("host_ms"),
                    "queue_ms_staged": off_bd.get("queue_ms"),
                    "queue_ms_fused": on_bd.get("queue_ms"),
                    "device_ms_staged": off_bd.get("device_ms"),
                    "device_ms_fused": on_bd.get("device_ms"),
                    "stats_syncs_staged": off_bd.get("stats_syncs"),
                    "stats_syncs_fused": on_bd.get("stats_syncs"),
                    "stats_sync_ms_staged": off_bd.get("stats_sync_ms"),
                    "stats_sync_ms_fused": on_bd.get("stats_sync_ms"),
                },
                "fused_programs": st.get("fused_programs", 0),
                "fused_spans": st.get("fused_spans", 0),
                "bailouts": st.get("bailouts", 0),
            }
    finally:
        conf.unset("spark.tpu.adaptive.enabled")
        conf.unset("spark.tpu.fusion.enabled")
    return out


def _analysis_overhead(spark, qnums) -> dict:
    """Per-query static-analyzer overhead (spark_tpu/analysis/):
    builds each query lazily and times analysis.analyze() — host-side
    plan walking only, nothing executes, nothing compiles. This is the
    cost the spark.tpu.analysis.level submit gate would add per query;
    it should be low single-digit ms against multi-second queries."""
    from spark_tpu import analysis
    from spark_tpu.tpch.queries import QUERIES

    out = {}
    for q in sorted(qnums):
        try:
            df = spark.sql(QUERIES[q])
            t0 = time.perf_counter()
            report = analysis.analyze(df._plan, spark.conf)
            ms = (time.perf_counter() - t0) * 1e3
            out[str(q)] = {
                "ms": round(ms, 2),
                "diagnostics": len(report.diagnostics),
                "errors": len(report.errors()),
                "fingerprint_stable": report.fingerprint_stable,
            }
        except Exception as e:
            out[str(q)] = {"error": f"{type(e).__name__}: {e}"}
    ok = [v["ms"] for v in out.values() if "ms" in v]
    out["total_ms"] = round(sum(ok), 2)
    out["max_ms"] = round(max(ok), 2) if ok else 0.0
    return out


def _run_headline(spark, qnum: int) -> dict:
    from spark_tpu import analysis
    from spark_tpu.plan.optimizer import optimize
    from spark_tpu.plan.subquery import rewrite_subqueries
    from spark_tpu.tpch.queries import QUERIES

    df = spark.sql(QUERIES[qnum])
    # static-analyzer overhead for THIS query (host-side, no execution)
    t0 = time.perf_counter()
    analysis.analyze(df._plan, spark.conf)
    analysis_ms = (time.perf_counter() - t0) * 1e3
    lp = optimize(rewrite_subqueries(df._plan))
    nbytes = _query_bytes(lp, spark.conf)

    if SF <= 10:
        t0 = time.time()
        rows1 = df.collect()  # warm-up 1: compiles + read + stats
        rows = df.collect()  # warm-up 2: adaptive join stats bound —
        # PK-FK joins fuse into one XLA program; compiles it
        warm_s = time.time() - t0
        assert rows, f"q{qnum} returned no rows"
        # cross-path parity: the first (blocking) execution and the
        # adaptive traced replay must produce the same result set
        # (the full vs-sqlite oracle parity runs in
        # tests/test_tpch.py at a smaller SF; this guards the fast
        # path at BENCH scale)
        assert len(rows1) == len(rows), \
            f"q{qnum}: traced row count differs"
        for a, b in zip(rows1, rows):
            a = a.asDict() if hasattr(a, "asDict") else a
            b = b.asDict() if hasattr(b, "asDict") else b
            for x, y in zip(a.values(), b.values()):
                if isinstance(x, float):
                    assert abs(x - y) <= 1e-6 * max(1.0, abs(x)), \
                        f"q{qnum}: traced value drift {x} vs {y}"
                else:
                    assert x == y, \
                        f"q{qnum}: traced mismatch {x} vs {y}"

        times = []
        for _ in range(N_ITER):
            t0 = time.perf_counter()
            rows = df.collect()
            times.append((time.perf_counter() - t0) * 1000.0)
    else:
        # out-of-HBM scale: every pass re-streams the dataset, so
        # the first (and only, unless BENCH_ITERS>1) pass IS the
        # honest number — compile time amortizes across hundreds of
        # chunk dispatches inside it
        warm_s = 0.0
        times = []
        for _ in range(N_ITER):
            t0 = time.perf_counter()
            rows = df.collect()
            times.append((time.perf_counter() - t0) * 1000.0)
        assert rows, f"q{qnum} returned no rows"
    ms = float(np.median(times))
    gbps = nbytes / (ms / 1e3) / 1e9
    assert gbps < HBM_GBPS, (
        f"q{qnum}: implied {gbps:.0f} GB/s exceeds HBM bandwidth "
        f"({HBM_GBPS} GB/s) — benchmark is measuring a constant")
    return {
        "ms": round(ms, 1),
        "min_ms": round(min(times), 1),
        "analysis_ms": round(analysis_ms, 2),
        "warmup_s": round(warm_s, 1),
        "rows": len(rows),
        "scan_gb": round(nbytes / 1e9, 3),
        "implied_gbps": round(gbps, 1),
        "vs_spark_cpu_est": round(BASELINE_MS[qnum] * SF / ms, 2),
        "shuffle": _shuffle_block(),
    }


if __name__ == "__main__":
    main()
