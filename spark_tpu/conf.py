"""Typed configuration registry.

Analogue of the reference's ConfigEntry system (reference:
core/src/main/scala/org/apache/spark/internal/config/ConfigEntry.scala:74
and sql/catalyst/.../internal/SQLConf.scala:56) — typed entries with
defaults, docs, and session-local overrides — minus the JVM machinery.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional


@dataclass(frozen=True)
class ConfigEntry:
    key: str
    default: Any
    doc: str
    value_type: Callable[[Any], Any] = lambda x: x


_REGISTRY: Dict[str, ConfigEntry] = {}

#: registered free-form key prefixes (per-pool scheduler keys etc.):
#: prefix -> doc. A key matching a registered prefix is considered
#: declared even though each concrete suffix is user-chosen.
_PREFIXES: Dict[str, str] = {}


def register(key: str, default: Any, doc: str,
             value_type: Callable[[Any], Any] = lambda x: x) -> ConfigEntry:
    entry = ConfigEntry(key, default, doc, value_type)
    _REGISTRY[key] = entry
    return entry


def register_prefix(prefix: str, doc: str) -> str:
    """Declare a free-form key family (e.g. per-pool scheduler keys,
    scanned by prefix). Returns the prefix so callers can keep using it
    as a plain string constant."""
    _PREFIXES[prefix] = doc
    return prefix


def is_registered(key: str) -> bool:
    """True when ``key`` is a declared ConfigEntry or matches a
    registered free-form prefix (the invariant tools/lint_invariants.py
    enforces for every literal conf key in the tree)."""
    return key in _REGISTRY or any(key.startswith(p) for p in _PREFIXES)


# ---- core entries ----------------------------------------------------------

SHUFFLE_PARTITIONS = register(
    "spark.sql.shuffle.partitions", 0,
    "Number of partitions for exchanges; 0 = one per mesh device "
    "(reference default 200: SQLConf.scala:614).", int)

BATCH_CAPACITY_MULTIPLE = register(
    "spark.tpu.batch.capacityMultiple", 1024,
    "Row capacities are rounded up to a multiple of this so jit caches "
    "hit across similar-sized inputs.", int)

BROADCAST_THRESHOLD = register(
    "spark.sql.autoBroadcastJoinThreshold", 8 * 1024 * 1024,
    "Max estimated build-side bytes for broadcast hash join "
    "(reference: SQLConf AUTO_BROADCASTJOIN_THRESHOLD).", int)

SKEW_FACTOR = register(
    "spark.tpu.skewJoin.factor", 5,
    "A distributed join whose hottest device counts more than this "
    "many times the median device's pairs after the hash exchange is "
    "re-planned as a broadcast join over the balanced pre-exchange "
    "distribution (reference: adaptive/OptimizeSkewedJoin.scala:37 "
    "SKEW_JOIN_SKEWED_PARTITION_FACTOR; under SPMD static shapes one "
    "hot device would size EVERY device's pair capacity).", int)

SKEW_MIN_PAIRS = register(
    "spark.tpu.skewJoin.minPairs", 1 << 16,
    "Absolute floor for skew demotion: the hottest device must exceed "
    "this many pairs (the factor alone misfires when most devices have "
    "ZERO pairs, e.g. fewer distinct keys than devices — reference "
    "pairs its factor with SKEW_JOIN_SKEWED_PARTITION_THRESHOLD for "
    "the same reason).", int)

SKEW_MAX_BROADCAST_BYTES = register(
    "spark.tpu.skewJoin.maxBroadcastBytes", 256 * 1024 * 1024,
    "Skew demotion replicates the build side onto every device; skip "
    "it when the build side exceeds this (skew stays slow rather than "
    "risking HBM exhaustion).", int)

CASE_SENSITIVE = register(
    "spark.sql.caseSensitive", False,
    "Whether identifiers are case sensitive (reference: SQLConf.scala).", bool)

REPARTITION_SLACK = register(
    "spark.tpu.exchange.slackFactor", 4,
    "Per-destination capacity slack factor for hash repartition "
    "(all_to_all requires static per-pair sizes).", int)

WAREHOUSE_DIR = register(
    "spark.sql.warehouse.dir", "spark-warehouse",
    "Directory for persistent (saveAsTable) tables (reference: "
    "StaticSQLConf WAREHOUSE_PATH).", str)

CBO_JOIN_REORDER = register(
    "spark.sql.cbo.joinReorder.enabled", True,
    "Reorder maximal inner equi-join clusters greedily by estimated "
    "cardinality (reference: CostBasedJoinReorder.scala:1; here driven "
    "by batch capacities and Parquet metadata, not ANALYZE stats).", bool)

EVENT_LOG_DIR = register(
    "spark.eventLog.dir", "",
    "When set, per-stage execution events are appended as JSONL under "
    "this directory (reference: EventLoggingListener.scala:48).", str)

PIPELINE_DEPTH = register(
    "spark.tpu.pipelineDepth", 2,
    "Out-of-HBM chunk pipeline depth: how many prepared chunks the "
    "background producer (parquet decode + host key filter + "
    "host->device transfer) may run ahead of device compute. 0 runs "
    "the fully serial decode->filter->ship->compute loop; >=1 "
    "overlaps the stages (the ShuffleBlockFetcherIterator in-flight "
    "window, applied to the host->device transfer). Results are "
    "byte-identical at every depth: chunks are consumed in source "
    "order, so the device merge order never changes.", int)

PREFETCH_BYTES_MAX = register(
    "spark.tpu.prefetchBytesMax", 1 << 30,
    "Byte cap on prepared-but-unconsumed pipeline chunks (device bytes "
    "of in-flight prefetch). The producer stalls once in-flight bytes "
    "reach this, whatever the pipeline depth, so prefetch can never "
    "blow host RAM or HBM. At least one chunk is always admitted "
    "(no deadlock on a budget smaller than a single chunk).", int)

# ---- multi-tenant query scheduler (spark_tpu/scheduler/) -------------------

SCHEDULER_MODE = register(
    "spark.scheduler.mode", "FIFO",
    "Query scheduling policy across pools: FIFO (global submit order) "
    "or FAIR (weighted-fair device time across pools; reference: "
    "TaskSchedulerImpl.scala + Pool.scala spark.scheduler.mode).", str)

SCHEDULER_MAX_CONCURRENCY = register(
    "spark.tpu.scheduler.maxConcurrency", 4,
    "Scheduler worker threads: how many queries may run their "
    "host-side stages (parse, optimize, parquet decode) concurrently. "
    "Device execution is additionally gated by HBM admission control.",
    int)

SCHEDULER_QUEUE_DEPTH = register(
    "spark.tpu.scheduler.queueDepth", 64,
    "Bound on queued (not yet dequeued) queries across all pools; a "
    "submit at full queue is rejected immediately (the connect server "
    "answers 429 with Retry-After) instead of growing an unbounded "
    "backlog.", int)

SCHEDULER_HBM_BUDGET = register(
    "spark.tpu.scheduler.hbmBudgetBytes", 2 << 30,
    "Shared device-bytes budget for HBM admission control: a query is "
    "admitted to device execution only while the sum of admitted "
    "queries' estimated footprints fits. A single over-budget query "
    "still admits alone (charged the full budget) and relies on the "
    "chunked/OOM-degradation ladder.", int)

SCHEDULER_RETRY_AFTER = register(
    "spark.tpu.scheduler.retryAfterSeconds", 1.0,
    "Retry-After hint (seconds) returned with a 429 rejection when "
    "the scheduler queue is full.", float)

SCHEDULER_DEFAULT_POOL = register(
    "spark.tpu.scheduler.defaultPool", "default",
    "Pool a query lands in when the submit carries no pool name "
    "(reference: spark.scheduler.pool defaulting).", str)

#: free-form per-pool keys (scanned by prefix):
#:   spark.tpu.scheduler.pool.<name>.weight    (int, default 1)
#:   spark.tpu.scheduler.pool.<name>.minShare  (int, default 0)
SCHEDULER_POOL_PREFIX = register_prefix(
    "spark.tpu.scheduler.pool.",
    "Per-pool FAIR scheduling keys: "
    "spark.tpu.scheduler.pool.<name>.{weight,minShare}.")

# ---- HBM-resident columnar storage (spark_tpu/storage/) --------------------

STORAGE_MAX_BYTES = register(
    "spark.tpu.storage.maxBytes", 1 << 30,
    "Cap on the storage region of the unified HBM budget: total device "
    "bytes the MemoryStore may hold in cached columnar batches. The "
    "effective cap is min(this, hbmBudgetBytes - execution grants) — "
    "storage and execution share spark.tpu.scheduler.hbmBudgetBytes "
    "(reference: spark.memory.fraction / UnifiedMemoryManager).", int)

STORAGE_MIN_BYTES = register(
    "spark.tpu.storage.minBytes", 64 * 1024 * 1024,
    "Protected storage region: execution admission may evict unpinned "
    "cached batches to make room, but never below this many bytes "
    "(reference: spark.memory.storageFraction — the floor storage is "
    "guaranteed against eviction by execution).", int)

STORAGE_AUTOCACHE_THRESHOLD = register(
    "spark.tpu.storage.autoCacheThreshold", 2,
    "Auto-cache hot scans: a (source, columns, filters) scan that has "
    "been materialized this many times in the session is promoted into "
    "the HBM-resident MemoryStore (byte-accounted, LRU-evictable), so "
    "repeat queries skip parquet decode + dictionary encode + "
    "host->device transfer entirely. 0 disables auto-caching; explicit "
    "df.cache() is unaffected.", int)

JIT_STAGE_CACHE_ENTRIES = register(
    "spark.tpu.jit.stageCacheEntries", 512,
    "Entry cap for the fused-stage jit caches (single-device "
    "physical/planner._STAGE_CACHE and distributed "
    "parallel/executor._DIST_STAGE_CACHE). Compiled stage programs "
    "beyond the cap are dropped LRU — an evicted plan recompiles on "
    "next use. Live sizes are published as metrics gauges "
    "jit_cache.<fused|dist>.entries.", int)

# ---- adaptive query execution over the mesh (AQE) --------------------------

ADAPTIVE_ENABLED = register(
    "spark.tpu.adaptive.enabled", False,
    "Adaptive query execution over the ICI mesh (reference: "
    "spark.sql.adaptive.enabled / AdaptiveSparkPlanExec.scala:98): split "
    "the fused SPMD program at exchange boundaries, measure per-device "
    "live counts with one psum/pmax stats stage, then re-trace the "
    "consumer at a compacted bucket-rounded capacity, switch measured-"
    "small join builds to broadcast, and fan skewed destinations over "
    "the partial->final aggregate merge. Results are byte-identical on "
    "or off; the OOM-degradation ladder also retries a failed run with "
    "this forced on before falling back to chunking.", bool)

ADAPTIVE_BROADCAST_THRESHOLD = register(
    "spark.tpu.adaptive.autoBroadcastJoinThreshold", 8 * 1024 * 1024,
    "Max MEASURED build-side bytes (live rows x row width, counted on "
    "device, not the static capacity estimate) for runtime broadcast-"
    "join switching when adaptive execution is on (reference: "
    "DynamicJoinSelection.scala:40 over MapOutputStatistics).", int)

ADAPTIVE_CAPACITY_BUCKET = register(
    "spark.tpu.adaptive.capacityBucket", 1024,
    "Post-exchange capacities are the measured pmax live count rounded "
    "UP to a multiple of this, so adaptive re-traces of the consumer "
    "stage land on a small set of capacities and hit the jit stage "
    "cache instead of recompiling per exact row count (reference "
    "analogue: spark.sql.adaptive.coalescePartitions.*).", int)

ADAPTIVE_SKEW_FACTOR = register(
    "spark.tpu.adaptive.skewedPartitionFactor", 4,
    "A hash-exchange destination whose measured incoming live count "
    "exceeds this many times the median destination's is skewed: its "
    "rows stay on their source device (a local-shuffle-reader fan), get "
    "pre-merged by the partial aggregate, and only the merged groups "
    "re-exchange (reference: OptimizeSkewedJoin.scala "
    "SKEW_JOIN_SKEWED_PARTITION_FACTOR). Only taken when every "
    "aggregate merge is exactly re-applicable (int sum/count/min/max), "
    "so results stay byte-identical.", int)

ADAPTIVE_SKEW_MIN_ROWS = register(
    "spark.tpu.adaptive.skewMinRows", 4096,
    "Absolute floor for the skew fan: the hottest destination must "
    "expect at least this many incoming rows (the factor alone "
    "misfires on tiny exchanges where one extra row looks like 'skew' "
    "— same reason the reference pairs its factor with "
    "SKEW_JOIN_SKEWED_PARTITION_THRESHOLD).", int)

ADAPTIVE_AGG_ENABLED = register(
    "spark.tpu.adaptive.agg.enabled", True,
    "Runtime-adaptive aggregation strategy switching (only active when "
    "spark.tpu.adaptive.enabled is also on): the exchange stats stage "
    "additionally sketches the distinct group-key count (HLL-style "
    "register maxima, one extra pmax fetch) and the executor picks "
    "between the static partial->final path, partial-bypass (NDV ~ "
    "rows: skip the useless pre-aggregation, exchange raw rows by "
    "key), and a hash-partial over runtime-measured packed key codes. "
    "Results are byte-identical across strategies; aggregates whose "
    "partials are order-dependent (float Sum/Min/Max) are pinned to "
    "partial->final (see analysis PLAN-AGG-STRATEGY).", bool)

ADAPTIVE_AGG_STRATEGY = register(
    "spark.tpu.adaptive.agg.strategy", "auto",
    "Aggregation strategy override: 'auto' decides from the runtime "
    "sketch; 'partial', 'bypass', 'hash', 'sort', or 'presplit' force "
    "one strategy (an illegal or unexecutable forced choice falls "
    "back to 'partial' so results stay byte-identical). Test/debug "
    "knob.", str)

ADAPTIVE_AGG_BYPASS_NDV_RATIO = register(
    "spark.tpu.adaptive.agg.bypassNdvRatio", 0.5,
    "Partial-bypass threshold: when the sketched distinct-key estimate "
    "is at least this fraction of the live row count, pre-aggregation "
    "cannot shrink the exchange enough to pay for itself (the "
    "all-distinct pathology of 'Partial Partial Aggregates'), so raw "
    "rows exchange straight to the final aggregate.", float)

ADAPTIVE_AGG_HASH_DOMAIN_LIMIT = register(
    "spark.tpu.adaptive.agg.hashDomainLimit", 1024,
    "Max packed key-code domain (product of measured per-key value "
    "ranges, nulls included) for the hash-partial strategy: the dense "
    "segment accumulator must fit the measured selection table (<= 64 "
    "XLA fused, 64 < K <= 1024 Pallas one-pass; see ops/pallas_agg.py)."
    " Beyond it the sort-based partial wins.", int)

ADAPTIVE_AGG_SKETCH_REGISTERS = register(
    "spark.tpu.adaptive.agg.sketchRegisters", 512,
    "HyperLogLog-style register count for the group-key distinct "
    "sketch in the exchange stats stage (power of two). 512 registers "
    "give ~5% relative error — plenty to separate 'NDV ~ rows' from "
    "'NDV << rows' — and ride the existing stats fetch as one extra "
    "O(registers) int vector.", int)

ADAPTIVE_AGG_SORT_DOMAIN_WIDTH = register(
    "spark.tpu.adaptive.agg.sortDomainWidth", 1 << 20,
    "Sort/hash crossover: a high-NDV grouping (NDV ratio past "
    "bypassNdvRatio) whose measured packed key-code domain exceeds "
    "this width takes the SORT rung — raw rows range-partition by the "
    "leading group key (the stable routing sort inside the tiled "
    "all_to_all doubles as the coarse key sort) and the final "
    "segmented-scan merge emits key-ordered output, which a matching "
    "downstream global sort then skips entirely. Below it the "
    "hash-exchange bypass keeps cheaper routing ('Hash-Based vs. "
    "Sort-Based Group-By-Aggregate', arXiv 2411.13245: sort-merge "
    "grouping wins at high NDV x large key domains, and ordered "
    "output is free).", int)

ADAPTIVE_AGG_PRESPLIT_FACTOR = register(
    "spark.tpu.adaptive.agg.presplitFactor", 4,
    "Hot-KEY pre-split threshold: a group key whose Count-Min "
    "estimated row count exceeds this multiple of the fair per-device "
    "share (rows / D) is salted across ALL devices BEFORE the "
    "exchange — the partial accumulators re-merge exactly through the "
    "ordinary partial->final path — instead of letting one "
    "destination absorb the whole key and fanning it afterwards "
    "(contrast: spark.tpu.adaptive.skewedPartitionFactor reacts to hot "
    "DESTINATIONS after routing).", int)

ADAPTIVE_AGG_PRESPLIT_MIN_ROWS = register(
    "spark.tpu.adaptive.agg.presplitMinRows", 4096,
    "Absolute floor for the hot-key pre-split: the hottest key's "
    "Count-Min estimate must reach this many rows (the factor alone "
    "misfires on tiny inputs — same pairing the skew fan and the "
    "reference's SKEW_JOIN_SKEWED_PARTITION_THRESHOLD use).", int)

ADAPTIVE_AGG_CM_DEPTH = register(
    "spark.tpu.adaptive.agg.cmDepth", 4,
    "Count-Min sketch depth (independent hash rows) for the heavy-"
    "hitter estimate in the exchange stats stage. The estimate is the "
    "min over rows, so it never under-counts; depth d bounds the "
    "over-count tail at ~(1/2)^d confidence per the standard CM "
    "analysis (reference shape: common/sketch CountMinSketch.java).",
    int)

ADAPTIVE_AGG_CM_WIDTH = register(
    "spark.tpu.adaptive.agg.cmWidth", 1024,
    "Count-Min sketch width (counters per row, power of two). "
    "Over-count per estimate is bounded by rows/width in expectation; "
    "1024 counters resolve a >=4096-row hot key in a 120k-row "
    "exchange with slack. Rides the existing stats fetch as depth "
    "extra O(width) int vectors, psum-merged across the mesh.", int)

# ---- whole-query native fusion ---------------------------------------------

FUSION_ENABLED = register(
    "spark.tpu.fusion.enabled", False,
    "Whole-query native fusion (only active when "
    "spark.tpu.adaptive.enabled is also on): adaptive exchange + "
    "consumer pairs whose ONLY host dependency is the stats fetch "
    "(capacity compaction) compile into ONE XLA program — the psum/"
    "pmax stats stay on device and a lax.switch over a precompiled "
    "capacity-bucket ladder replaces the host round-trip, so a multi-"
    "exchange plan runs end-to-end with zero inter-stage host sync "
    "(the Flare thesis, arXiv 1703.08219, XLA-native). Decisions that "
    "genuinely need the host — broadcast-join switching on measured "
    "bytes, skew fan/pre-split, the agg strategy crossover, sort "
    "elision, the OOM ladder — bail out to staged execution with a "
    "typed fusion_bailout event. Results are byte-identical on or "
    "off.", bool)

FUSION_MAX_BUCKET_VARIANTS = register(
    "spark.tpu.fusion.maxBucketVariants", 4,
    "Number of capacity-ladder branches baked into one fused program: "
    "consumer capacities start at spark.tpu.adaptive.capacityBucket "
    "and grow geometrically (x4) up to the static worst case, at most "
    "this many rungs (the last rung is always the worst case, so any "
    "measured count is covered). More variants track the staged "
    "path's measured capacity tighter; fewer keep the fused program "
    "small. Part of the compile-store fingerprint — changing it "
    "recompiles fused spans.", int)

SEARCHSORTED_SORT_THRESHOLD = register(
    "spark.tpu.kernels.searchsortedSortThreshold", 50,
    "physical/kernels.searchsorted picks XLA's O((n+m)log(n+m)) "
    "method='sort' over the O(n*log m) per-row scan when the queries "
    "are large (>= 4096) AND queries*THIS > haystack size; raise it to "
    "prefer sort (wide all-to-all style lookups), lower it toward 0 to "
    "prefer scan (few queries against huge sorted runs).", int)

# ---- AOT compilation service (spark_tpu/compile/) --------------------------

COMPILE_STORE_DIR = register(
    "spark.tpu.compile.store.dir", "",
    "Root directory of the cross-session executable store: serialized "
    "AOT stage executables (entries/) live here, keyed by a stable "
    "plan fingerprint + "
    "capacity/mesh/device-kind, so a fresh session or worker restart "
    "skips XLA entirely. Empty disables cross-session persistence "
    "(the in-process jit stage caches still apply).", str)

COMPILE_STORE_MAX_BYTES = register(
    "spark.tpu.compile.store.maxBytes", 1 << 30,
    "Size bound for the executable store directory; beyond it the "
    "least-recently-used entry files are evicted.", int)

COMPILE_STORE_SERIALIZE = register(
    "spark.tpu.compile.store.serialize", True,
    "Persist freshly compiled stage executables to the store via "
    "jax.experimental.serialize_executable. Off = lookups only (useful "
    "on hosts where XLA executable serialization is unreliable).", bool)

COMPILE_BACKGROUND = register(
    "spark.tpu.compile.background", False,
    "On an executable-cache miss, admit the query anyway: serve the "
    "first request(s) through the chunked tier while the fused "
    "executable compiles on a background thread, then atomically swap "
    "it in for subsequent execution — byte-identical either way. A "
    "background-compile failure pins the plan to the chunked tier "
    "permanently (no swap, no crash).", bool)

COMPILE_CHUNK_FIRST_BUDGET = register(
    "spark.tpu.compile.chunkFirst.budgetBytes", 32 << 20,
    "Shadow spark.tpu.maxDeviceBatchBytes used to force the chunked "
    "tier while the fused executable compiles in the background (the "
    "chunked tier's small per-chunk programs compile in a fraction of "
    "the fused program's time).", int)

COMPILE_HISTORY_PATH = register(
    "spark.tpu.compile.history.path", "",
    "Served-plan history file (JSONL of executed SQL + plan "
    "fingerprints) replayed by the pre-warm pass. Empty defaults to "
    "<store.dir>/plan_history.jsonl when the store is enabled.", str)

COMPILE_HISTORY_MAX_ENTRIES = register(
    "spark.tpu.compile.history.maxEntries", 512,
    "Distinct plans kept in the served-plan history (the file is "
    "compacted beyond roughly twice this many lines).", int)

COMPILE_PREWARM_ENABLED = register(
    "spark.tpu.compile.prewarm.enabled", True,
    "Replay the served-plan history at connect-server start on a "
    "background worker, most-frequent-first, pre-tracing and "
    "pre-compiling stage executables before the first client query "
    "arrives.", bool)

COMPILE_PREWARM_BUDGET_S = register(
    "spark.tpu.compile.prewarm.budgetSeconds", 120.0,
    "Wall-clock budget for the pre-warm replay; remaining history "
    "entries are skipped (marked in the pre-warm report) once it is "
    "spent.", float)

COMPILE_PREWARM_MAX_QUERIES = register(
    "spark.tpu.compile.prewarm.maxQueries", 32,
    "Most-frequent-first cap on how many distinct history plans the "
    "pre-warm pass replays.", int)

COMPILE_PREWARM_WORKERS = register(
    "spark.tpu.compile.prewarm.workers", 1,
    "Worker threads replaying the served-plan history concurrently "
    "during pre-warm. 1 = sequential (deterministic replay order); "
    "more overlaps XLA compiles of independent plans.", int)


# ---- static plan analysis (spark_tpu/analysis/) ----------------------------

ANALYSIS_LEVEL = register(
    "spark.tpu.analysis.level", "off",
    "Pre-execution static plan analysis gate: off (default, no "
    "analysis on the submit path), warn (analyze every submitted plan "
    "and record diagnostics as events/metrics), or error (additionally "
    "raise PlanAnalysisError when an error-level diagnostic fires "
    "before anything touches the device). The same level also governs "
    "conf.set of undeclared keys: warn emits a warning, error raises.",
    str)

ANALYSIS_DIVERGENCE_FACTOR = register(
    "spark.tpu.analysis.divergenceFactor", 16.0,
    "The analyzer's static byte estimate is cross-checked against "
    "AQE's measured-bytes table (scheduler/admission); when the two "
    "disagree by more than this factor in either direction, the plan "
    "gets a PLAN-EST-DIVERGE diagnostic — the cost model is lying to "
    "admission control for this plan shape.", float)

DEBUG_LOCK_ORDER = register(
    "spark.tpu.debug.lockOrder", False,
    "Runtime cross-check of the static lock hierarchy "
    "(spark_tpu/locks.py): when true, every named lock records the "
    "per-thread held-stack on acquire and locks.order_report() exposes "
    "the observed acquisition edges plus any rank inversions or cycles "
    "— the empirical validation of tools/lint_concurrency.py's graph. "
    "Off by default (a global-flag check per acquire either way).",
    bool)

ANALYSIS_ERROR_CODES = register(
    "spark.tpu.analysis.errorCodes", "",
    "Comma-separated diagnostic codes escalated to error level at the "
    "submit-time gate (e.g. 'PLAN-DTYPE-F64,PLAN-RECOMPILE-SHAPE'): a "
    "deployment that must never bake data-dependent shapes into plans "
    "can fail such queries at submit instead of discovering the "
    "recompile storm in production.", str)

MESH_DEVICES = register(
    "spark_tpu.mesh.devices", None,
    "SPMD mesh size requested via SparkSession.builder.master"
    "('mesh[N]'); -1 = all visible devices, None/unset = single-device "
    "execution.", lambda v: v if v is None else int(v))


# ---- scale-out serving tier (spark_tpu/serve/) ----------------------------

SERVE_POLICY = register(
    "spark.tpu.serve.policy", "least_queued",
    "Federation-router replica selection: 'round_robin' cycles "
    "replicas, 'least_queued' picks the replica whose scheduler "
    "reports the fewest queued+running queries at the last health "
    "probe (reference analogue: spark.scheduler.mode for in-process "
    "pools; this is its cross-replica sibling).", str)

SERVE_RESULT_CACHE_ENABLED = register(
    "spark.tpu.serve.resultCache.enabled", False,
    "Serve repeated identical queries from the plan-keyed Arrow "
    "result cache (serve/result_cache.py): keyed by the structural "
    "plan key + scan-source mtime/size fingerprints, single-flight "
    "per key, byte-identical to uncached execution.", bool)

SERVE_RESULT_CACHE_MAX_BYTES = register(
    "spark.tpu.serve.resultCache.maxBytes", 256 * 1024 * 1024,
    "Byte bound for the serve-tier result cache; least-recently-used "
    "entries are evicted past it and a single result larger than the "
    "bound is served but never cached.", int)

SERVE_DISPATCH_RETRIES = register(
    "spark.tpu.serve.dispatchRetries", 3,
    "How many times the federation router re-dispatches one request "
    "to a different replica after a replica connection failure or an "
    "injected serve.dispatch fault before surfacing the error.", int)

SERVE_HEALTH_PROBE_SECONDS = register(
    "spark.tpu.serve.healthProbeSeconds", 0.5,
    "Minimum age of a replica's cached /health snapshot before the "
    "router re-probes it; 0 probes on every dispatch (tests).", float)

SERVE_REPLICAS = register(
    "spark.tpu.serve.replicas", 2,
    "Default replica count for serve_fleet() when the caller does not "
    "pass one explicitly.", int)


# ---- SLO-driven serving (spark_tpu/slo/) ----------------------------------

SLO_ENABLED = register(
    "spark.tpu.slo.enabled", False,
    "Master switch for the SLO subsystem: per-plan latency prediction "
    "(slo/model.py), earliest-feasible-deadline-first scheduling with "
    "reject-at-admission (slo/edf.py), and predictive brownout / "
    "concurrency auto-sizing (slo/controller.py). Off is byte-identical "
    "to the plain FIFO/FAIR scheduler path.", bool)

SLO_TARGET_P99_MS = register(
    "spark.tpu.slo.targetP99Ms", 0.0,
    "Configured p99 latency SLO in milliseconds. When > 0 the "
    "predictive brownout controller enters brownout as soon as the "
    "PREDICTED p99 over the recent window crosses it (before failures "
    "accumulate), and exits once predictions drop back under "
    "exitRatio x target. 0 disables predictive brownout.", float)

SLO_REJECT_ENABLED = register(
    "spark.tpu.slo.rejectEnabled", True,
    "Reject-at-admission (only active under spark.tpu.slo.enabled): a "
    "submit whose predicted completion (queue backlog estimate + "
    "predicted run time) exceeds its deadline raises the typed "
    "InfeasibleDeadline immediately instead of burning queue slots and "
    "device time on a query that is doomed to miss.", bool)

SLO_REJECT_MARGIN = register(
    "spark.tpu.slo.rejectMargin", 1.0,
    "Safety factor on the predicted completion time before the "
    "infeasibility comparison (>1 rejects earlier, <1 gives doubtful "
    "queries the benefit of the doubt).", float)

SLO_MODEL_ALPHA = register(
    "spark.tpu.slo.model.alpha", 0.3,
    "EWMA smoothing factor for the per-plan-fingerprint latency model "
    "components (host/device/queue/transfer ms and input rows); higher "
    "adapts faster, lower is steadier.", float)

SLO_MODEL_PATH = register(
    "spark.tpu.slo.model.path", "",
    "Persistence file (JSONL) for the latency model. Empty defaults to "
    "<compile store root>/slo_model.jsonl beside the plan-history "
    "journal when the store is enabled, so a restarted replica "
    "predicts from its first query; otherwise the model is "
    "in-memory only.", str)

SLO_MODEL_MAX_ENTRIES = register(
    "spark.tpu.slo.model.maxEntries", 512,
    "Distinct plan fingerprints kept by the latency model (LRU beyond "
    "it; the journal is compacted past roughly twice this many lines).",
    int)

SLO_WINDOW_SECONDS = register(
    "spark.tpu.slo.controller.windowSeconds", 30.0,
    "Sliding window over which the controller aggregates predicted "
    "per-query latencies for the predictive-p99 brownout decision.",
    float)

SLO_MIN_PREDICTIONS = register(
    "spark.tpu.slo.controller.minPredictions", 8,
    "Minimum predictions inside the window before the predictive "
    "brownout level may change (a single slow cold query is not a "
    "p99).", int)

SLO_EXIT_RATIO = register(
    "spark.tpu.slo.controller.exitRatio", 0.8,
    "Hysteresis for predictive brownout exit: the level drops back to "
    "0 only once predicted p99 <= exitRatio x targetP99Ms.", float)

SLO_AUTOSIZE_ENABLED = register(
    "spark.tpu.slo.autoConcurrency.enabled", True,
    "Auto-size the scheduler's EFFECTIVE concurrency (only under "
    "spark.tpu.slo.enabled) from observed queue/device-time ratios: "
    "queue-dominated load shrinks the effective worker count toward "
    "autoConcurrency.min (less churn at the device gate), "
    "compute-headroom grows it back toward the configured "
    "maxConcurrency.", bool)

SLO_AUTOSIZE_MIN = register(
    "spark.tpu.slo.autoConcurrency.min", 1,
    "Floor for the auto-sized effective concurrency.", int)


# ---- materialized views (spark_tpu/mview/) --------------------------------

MVIEW_ENABLED = register(
    "spark.tpu.mview.enabled", False,
    "Treat df.cache() of an aggregate over a fingerprinted file source "
    "as a materialized view (spark_tpu/mview/): the cached device "
    "batch is refreshed when the source files change instead of being "
    "served stale, incrementally when the aggregate is exactly "
    "re-mergeable.", bool)

MVIEW_INCREMENTAL = register(
    "spark.tpu.mview.incremental", True,
    "Refresh appended-to views by executing the aggregate over the new "
    "files only and re-merging the partials into the cached batch "
    "(legal only for integer Sum / non-float Min/Max — everything "
    "else full-recomputes). Off = always full recompute; both paths "
    "are byte-identical, this is the A/B switch the on/off sweep "
    "tests flip.", bool)

MVIEW_REFRESH_RETRIES = register(
    "spark.tpu.mview.refreshRetries", 2,
    "Bounded retries of one incremental view refresh after a "
    "transient failure (including injected mview.refresh faults) "
    "before falling back to a full recompute.", int)

MVIEW_SERVE_REPOPULATE = register(
    "spark.tpu.mview.serveRepopulate", True,
    "After a view refresh, proactively re-insert the refreshed "
    "Arrow result into the serve-tier result cache under the NEW "
    "fingerprint key, so federated readers keep hitting cache across "
    "updates instead of cold-missing.", bool)


class RuntimeConf:
    """Session-scoped mutable view over the registry."""

    def __init__(self, overrides: Optional[Dict[str, Any]] = None):
        self._overrides: Dict[str, Any] = dict(overrides or {})

    def get(self, entry_or_key) -> Any:
        key = entry_or_key.key if isinstance(entry_or_key, ConfigEntry) else entry_or_key
        if key in self._overrides:
            return self._overrides[key]
        if key in _REGISTRY:
            return _REGISTRY[key].default
        raise KeyError(f"unknown config key: {key}")

    def set(self, key: str, value: Any) -> None:
        if key in _REGISTRY:
            value = _REGISTRY[key].value_type(value)
        elif not is_registered(key):
            # an undeclared key silently no-ops every read path (get()
            # raises on it) — surface the typo at the level the session
            # asked for (satellite of the static-analysis gate)
            level = str(self._overrides.get(
                ANALYSIS_LEVEL.key, ANALYSIS_LEVEL.default)).lower()
            if level == "error":
                raise KeyError(
                    f"unknown config key: {key} (not a registered "
                    "ConfigEntry or prefix; set "
                    "spark.tpu.analysis.level=warn to tolerate)")
            if level == "warn":
                import warnings

                warnings.warn(
                    f"conf.set of undeclared key {key!r}: not a "
                    "registered ConfigEntry or prefix — reads of it "
                    "will raise KeyError", stacklevel=2)
        self._overrides[key] = value

    def unset(self, key: str) -> None:
        self._overrides.pop(key, None)

    def entries(self) -> Dict[str, Any]:
        out = {k: e.default for k, e in _REGISTRY.items()}
        out.update(self._overrides)
        return out
