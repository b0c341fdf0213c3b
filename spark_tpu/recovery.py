"""Fault tolerance: heartbeats, stage retry, durable checkpoints.

Reference peers:
- stage re-execution from lineage on task loss
  (core/.../scheduler/DAGScheduler.scala:1762 handleTaskCompletion →
  resubmit; TaskSetManager maxTaskFailures) — here the *logical plan is
  the lineage*: re-running a query recomputes every stage from source
  data, so recovery = retry the plan, optionally from a durable
  checkpoint that truncates the lineage;
- executor heartbeats (core/.../HeartbeatReceiver.scala:67) — here a
  driver-side monitor thread that proves the device/backend is still
  answering (a dead TPU host fails the next collective anyway — SPMD
  makes failure detection synchronous — the heartbeat exists to catch
  hangs *between* queries and surface them in the event log);
- reliable checkpoint (core/.../rdd/ReliableCheckpointRDD.scala) —
  ``checkpoint_dataframe`` writes Parquet and replans over the files.

Deliberately NOT rebuilt: per-task speculation and partition-level
re-fetch. A pjit stage is a gang — all shards advance or none do —
so the recovery unit is the stage program, not a task.
"""

from __future__ import annotations

import os
import random
import threading
import time
import uuid
from contextlib import contextmanager, nullcontext
from contextvars import ContextVar
from typing import Callable, Dict, Iterator, Optional

from spark_tpu import locks
from spark_tpu import conf as CF
from spark_tpu import deadline, faults, metrics, trace

STAGE_MAX_ATTEMPTS = CF.register(
    "spark.stage.maxConsecutiveAttempts", 4,
    "Attempts for a stage/query whose failure looks transient "
    "(reference: config/package.scala STAGE_MAX_CONSECUTIVE_ATTEMPTS).",
    int)

CHECKPOINT_DIR = CF.register(
    "spark.checkpoint.dir", "",
    "Durable checkpoint directory for DataFrame.checkpoint() "
    "(reference: SparkContext.setCheckpointDir).", str)

HEARTBEAT_INTERVAL = CF.register(
    "spark.executor.heartbeatInterval", 10.0,
    "Seconds between device liveness probes (reference: "
    "HeartbeatReceiver.scala HEARTBEAT_INTERVAL).", float)

OOM_DEGRADE_ENABLED = CF.register(
    "spark.tpu.oomDegrade.enabled", True,
    "Whole-batch device OOM replans through the chunked out-of-HBM "
    "tier with a halved spark.tpu.maxDeviceBatchBytes (halving again "
    "on repeat OOM) instead of failing — the graceful-degradation "
    "ladder (reference analogue: TungstenAggregationIterator.scala:82 "
    "sort-fallback under memory pressure).", bool)

OOM_DEGRADE_FLOOR = CF.register(
    "spark.tpu.oomDegrade.floorBytes", 1 << 20,
    "Smallest device-batch budget the OOM degradation ladder will try "
    "before giving up and surfacing the original OOM.", int)

RETRY_BUDGET_ENABLED = CF.register(
    "spark.tpu.recovery.retryBudget.enabled", True,
    "Share ONE per-query retry budget across every retry layer (stage "
    "recovery, scheduler admission, chunk pipeline, spill seams, mview "
    "refresh, dispatch re-forward) instead of letting the per-layer "
    "bounds stack multiplicatively under a fault storm.", bool)

RETRY_BUDGET_ATTEMPTS = CF.register(
    "spark.tpu.recovery.retryBudget.attempts", 8,
    "Total re-attempts one query may spend across ALL retry layers "
    "combined. Per-layer bounds still apply individually; this pool "
    "caps their sum.", int)

RETRY_BUDGET_FLOOR = CF.register(
    "spark.tpu.recovery.retryBudget.layerFloor", 1,
    "Re-attempts each layer is guaranteed even after the shared pool "
    "empties, so one retry-hungry layer cannot starve every other "
    "layer of its single recovery chance.", int)

RETRY_BACKOFF_BASE = CF.register(
    "spark.tpu.recovery.retryBudget.backoffBaseS", 0.05,
    "Base of the full-jitter exponential backoff between budgeted "
    "re-attempts (delay ~ uniform[0, min(cap, base * 2^attempt)]).",
    float)

RETRY_BACKOFF_CAP = CF.register(
    "spark.tpu.recovery.retryBudget.backoffCapS", 2.0,
    "Ceiling of the full-jitter backoff between budgeted re-attempts; "
    "every sleep is additionally capped by the caller's remaining "
    "deadline.", float)

# Error-message fragments that indicate the *environment* failed (a
# host dropped out of the collective, the tunnel died, a deadline
# passed) rather than the query being wrong. Only these are retried —
# retrying a genuine bug would just quadruple its latency.
_TRANSIENT_MARKERS = (
    "DEADLINE_EXCEEDED",
    "UNAVAILABLE",
    "ABORTED",
    "connection reset",
    "Connection reset",
    "socket closed",
    "device or resource busy",
    "halted",          # TPU halt: chip needs re-init
    "slice has failed",
)

# exception TYPES that are transient by construction, whatever their
# message says (a "" ConnectionResetError escaped the substring check)
_TRANSIENT_TYPES = (
    ConnectionResetError,
    ConnectionAbortedError,
    BrokenPipeError,
    TimeoutError,
)

_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory")


def _chain(exc: BaseException) -> Iterator[BaseException]:
    """The exception plus its ``__cause__``/``__context__`` chain (a
    wrapped DEADLINE_EXCEEDED must still classify as transient)."""
    seen = set()
    node: Optional[BaseException] = exc
    while node is not None and id(node) not in seen:
        seen.add(id(node))
        yield node
        if node.__cause__ is not None:
            node = node.__cause__
        elif not node.__suppress_context__:
            node = node.__context__
        else:
            node = None


def is_oom(exc: BaseException) -> bool:
    """Device/host memory exhaustion anywhere in the cause chain. OOM
    is deliberately NOT transient — retrying the identical plan would
    exhaust the identical HBM; it routes to the degradation ladder
    (run_plan_with_oom_degradation) instead."""
    for e in _chain(exc):
        if isinstance(e, (faults.InjectedOOMError, MemoryError)):
            return True
        # jaxlib's XlaRuntimeError prefixes the grpc status code; match
        # by type name so jaxlib need not be importable here
        msg = str(e)
        if any(m in msg for m in _OOM_MARKERS):
            return True
    return False


def is_transient(exc: BaseException) -> bool:
    """True when the failure looks like the *environment* failed and
    re-running the same plan can succeed. Inspects exception types and
    the full ``__cause__`` chain, not just ``str(exc)`` — and OOM
    anywhere in the chain wins: it is never transient."""
    if is_oom(exc):
        return False
    for e in _chain(exc):
        # typed carve-outs BEFORE the marker scan: a caller-deadline
        # expiry says "DEADLINE_EXCEEDED" (a transient marker, because
        # a *server-side* grpc deadline is worth one retry) but the
        # CALLER being gone is terminal; likewise a drained retry
        # budget must not be re-retried by an outer layer — its cause
        # chain carries the original UNAVAILABLE-style error and would
        # otherwise classify transient, resurrecting the exact
        # multiplicative stacking the budget exists to remove.
        if isinstance(e, (deadline.DeadlineExceeded,
                          RetryBudgetExhausted)):
            return False
    for e in _chain(exc):
        if isinstance(e, (faults.InjectedTransientError,
                          faults.InjectedDeadlineError)):
            return True
        if isinstance(e, faults.InjectedFault):
            return False  # injected oom/corrupt: typed non-transient
        if isinstance(e, _TRANSIENT_TYPES):
            return True
        msg = str(e)
        if type(e).__name__ == "XlaRuntimeError":
            # status-code prefix, e.g. "ABORTED: collective timed out"
            status = msg.split(":", 1)[0].strip()
            if status in ("DEADLINE_EXCEEDED", "UNAVAILABLE", "ABORTED",
                          "CANCELLED", "INTERNAL"):
                return True
        if any(m in msg for m in _TRANSIENT_MARKERS):
            return True
    return False


class RetryBudgetExhausted(RuntimeError):
    """A retry seam asked for a re-attempt after the query's unified
    retry budget drained past the layer floor. Typed and terminal:
    never transient (is_transient carves it out by type), so outer
    layers surface it instead of re-retrying."""

    def __init__(self, layer: str, budget: Optional["RetryBudget"]):
        snap = budget.snapshot() if budget is not None else \
            {"draws": "?", "attempts": "?", "layers": {}}
        super().__init__(
            f"RETRY_BUDGET_EXHAUSTED at {layer}: "
            f"{snap['draws']} re-attempts spent of "
            f"{snap['attempts']} budgeted "
            f"(per-layer: {snap['layers']})")
        self.layer = layer


class RetryBudget:
    """One per-query pool of re-attempts shared by EVERY retry layer.

    Before this existed, resilience was a stack of independent bounded
    retries — ``serve.dispatchRetries`` x ``scheduler.admit`` re-admits
    x ``chunkRetryAttempts`` x ``spillRetryAttempts`` x
    ``mview.refreshRetries`` — whose worst case is the PRODUCT of the
    bounds under a fault storm. Here every layer draws from one pool:
    the per-query total is the SUM bound ``attempts`` (plus each
    layer's small floor guarantee), whatever the nesting.

    ``draw(layer)`` consumes one re-attempt and returns whether it was
    granted; after the pool drains, a layer that has drawn fewer than
    ``layer_floor`` times is still granted (the floor keeps one
    retry-hungry layer from starving every other layer of its single
    recovery chance). Denials surface as
    :class:`RetryBudgetExhausted` at the seam.

    ``backoff_s(attempt)`` is the shared FULL-JITTER exponential
    backoff — delay ~ uniform[0, min(cap, base * 2^attempt)] — capped
    by the caller's remaining deadline, so no budgeted sleep outlives
    the caller.
    """

    def __init__(self, attempts: int, *, layer_floor: int = 1,
                 backoff_base_s: float = 0.05,
                 backoff_cap_s: float = 2.0,
                 rng: Optional[random.Random] = None):
        self.attempts = max(0, int(attempts))
        self.layer_floor = max(0, int(layer_floor))
        self.backoff_base_s = float(backoff_base_s)
        self.backoff_cap_s = float(backoff_cap_s)
        self._rng = rng if rng is not None else random.Random()
        self._remaining = self.attempts
        self._layers: Dict[str, int] = {}
        self._exhausted_noted = False
        self._lock = locks.named_lock("recovery.retry_budget")

    def draw(self, layer: str) -> bool:
        """Consume one re-attempt for ``layer``; True when granted."""
        with self._lock:
            taken = self._layers.get(layer, 0)
            if self._remaining > 0:
                self._remaining -= 1
                self._layers[layer] = taken + 1
                granted, floored = True, False
            elif taken < self.layer_floor:
                self._layers[layer] = taken + 1
                granted, floored = True, True
            else:
                granted, floored = False, False
            remaining = self._remaining
            note_exhausted = (remaining == 0
                              and not self._exhausted_noted)
            if note_exhausted:
                self._exhausted_noted = True
        if granted:
            metrics.note_retry_budget("draws")
            if floored:
                metrics.note_retry_budget("floor_draws")
        else:
            metrics.note_retry_budget("denials")
        if note_exhausted:
            metrics.note_retry_budget("exhaustions")
        metrics.record("retry_draw", layer=layer, granted=granted,
                       floored=floored, remaining=remaining)
        return granted

    def backoff_s(self, attempt: int) -> float:
        """Full-jitter delay for re-attempt ``attempt``, capped by the
        ambient deadline's remaining time."""
        ceiling = min(self.backoff_cap_s,
                      self.backoff_base_s * (2.0 ** max(0, attempt)))
        return deadline.cap_sleep(self._rng.uniform(0.0, ceiling))

    def sleep(self, attempt: int) -> None:
        time.sleep(self.backoff_s(attempt))

    def snapshot(self) -> Dict:
        with self._lock:
            return {"attempts": self.attempts,
                    "remaining": self._remaining,
                    "draws": sum(self._layers.values()),
                    "layers": dict(self._layers),
                    "layer_floor": self.layer_floor}


_BUDGET: ContextVar[Optional[RetryBudget]] = ContextVar(
    "spark_tpu_retry_budget", default=None)


def current_budget() -> Optional[RetryBudget]:
    """The query's ambient RetryBudget (None outside a budgeted query
    or with spark.tpu.recovery.retryBudget.enabled=false)."""
    return _BUDGET.get()


@contextmanager
def bind_budget(budget: Optional[RetryBudget]):
    """Enter a budget for the dynamic extent (None is a no-op).
    Thread-hopping code captures current_budget() and re-binds on the
    worker — same discipline as trace/deadline contexts."""
    if budget is None:
        yield _BUDGET.get()
        return
    token = _BUDGET.set(budget)
    try:
        yield budget
    finally:
        _BUDGET.reset(token)


def budget_from_conf(conf) -> Optional[RetryBudget]:
    """A fresh per-query budget per the conf (None when disabled)."""
    try:
        if not bool(conf.get(RETRY_BUDGET_ENABLED)):
            return None
        return RetryBudget(
            int(conf.get(RETRY_BUDGET_ATTEMPTS)),
            layer_floor=int(conf.get(RETRY_BUDGET_FLOOR)),
            backoff_base_s=float(conf.get(RETRY_BACKOFF_BASE)),
            backoff_cap_s=float(conf.get(RETRY_BACKOFF_CAP)))
    except Exception:
        return None


@contextmanager
def bind_default_budget(conf):
    """Root-entry helper (DataFrame._execute): bind a fresh budget only
    when none is already active — nested executions (mview refresh,
    cache materialization, recovery re-runs) must share the OUTER
    query's pool; that sharing IS the anti-stacking guarantee."""
    if _BUDGET.get() is not None or conf is None:
        yield _BUDGET.get()
        return
    with bind_budget(budget_from_conf(conf)) as b:
        yield b


def retry_allowed(layer: str) -> bool:
    """THE seam API: every bounded-retry loop in the tree asks this
    before each re-attempt (tools/lint_invariants.py rule 7 enforces
    it). Draws from the ambient budget when one is bound; without one
    (budget disabled, or a bare layer used outside any query) the
    legacy per-layer bound stands alone and the re-attempt is counted
    on the ``legacy_attempts`` A/B counter."""
    b = _BUDGET.get()
    if b is None:
        metrics.note_retry_budget("legacy_attempts")
        return True
    return b.draw(layer)


def backoff_sleep(attempt: int, *, base_s: float = 0.05,
                  cap_s: float = 2.0) -> None:
    """Full-jitter, deadline-capped backoff for seams re-attempting
    WITHOUT an ambient budget (the budget's own backoff_s is preferred
    when bound — it shares the jitter RNG and the configured caps)."""
    b = _BUDGET.get()
    if b is not None:
        b.sleep(attempt)
        return
    ceiling = min(float(cap_s), float(base_s) * (2.0 ** max(0, attempt)))
    time.sleep(deadline.cap_sleep(random.uniform(0.0, ceiling)))


def _grant_planned_chunk(lp, conf):
    """Planned degradation BEFORE execution — the zero-replan path.
    When a MEASURED prior run of this plan shape says its working set
    exceeds what the unified memory manager could currently offer
    (storage pins, shrunken budget), re-plan through the chunked tier
    NOW at the available span instead of letting the device OOM and
    walking the replan ladder. Measured bytes only: static estimates
    are too noisy to pre-chunk on. Returns ``(found, shadow_conf)`` or
    ``(None, None)``."""
    from spark_tpu.physical.chunked import JOIN_HYBRID_ENABLED
    from spark_tpu.scheduler import admission

    try:
        if not bool(conf.get(JOIN_HYBRID_ENABLED)):
            return None, None
        from spark_tpu.api.session import SparkSession

        sess = SparkSession._active
        manager = getattr(sess, "memory_manager", None) \
            if sess is not None else None
        if manager is None:
            return None, None
        measured = admission.measured_plan_bytes(lp)
        if not measured:
            return None, None
        # free-for-execution span; the query's own eventual grant is
        # deliberately not modeled — storage is what it cannot evict
        # past, so that is the planning bound
        with manager.lock:
            avail = manager.budget - manager.storage_bytes()
        if avail <= 0 or int(measured) <= avail:
            return None, None
    except Exception:
        return None, None
    found, shadow = plan_chunk_first(lp, conf, avail)
    if found is None:
        return None, None
    metrics.record("planned_chunked", budget=avail,
                   measured=int(measured))
    return found, shadow


def run_plan_with_oom_degradation(lp, conf, run_fn):
    """Execute an optimized logical plan with the HBM-pressure
    degradation ladder: plans whose scans exceed the device budget run
    chunked as before; a plan whose MEASURED working set exceeds what
    the unified memory manager can currently grant is pre-planned into
    the chunked tier (``planned_chunked`` — zero replans); a
    whole-batch (or chunked) execution that dies with OOM is
    re-planned through ``find_chunkable``/``execute_chunked`` at a
    halved ``spark.tpu.maxDeviceBatchBytes``, halving again on repeat
    down to ``spark.tpu.oomDegrade.floorBytes`` — so memory pressure
    degrades to the out-of-HBM tier instead of failing the query.
    Every ladder replan bumps ``metrics.recovery_stats()['replans']``
    and chains the triggering exception as ``__cause__`` so the final
    error carries the whole replan history. ``run_fn(plan) -> Batch``
    is the raw engine."""
    from spark_tpu.conf import RuntimeConf
    from spark_tpu.physical.chunked import (MAX_DEVICE_BATCH_BYTES,
                                            execute_chunked,
                                            find_chunkable)
    from spark_tpu.scheduler import admission

    try:
        # the tier is decided again on every execution (a plan walk,
        # the scans' estimates, admission's table): the span closes
        # before the engine starts, as query.plan does
        decide = trace.span("tier.decide")
        with decide:
            found = find_chunkable(lp, conf)
            chunk_conf, tier = conf, "chunked"
            if found is None:
                found, chunk_conf = _grant_planned_chunk(lp, conf)
                tier = "resident" if found is None else "planned_chunked"
            decide.attrs["tier"] = tier
        if found is not None:
            return execute_chunked(found, chunk_conf, run_fn)
        # the whole-batch device execution seam
        faults.inject("execute.device", conf)
        out = run_fn(lp)
        # the grant pre-step and the hybrid join look the OPTIMIZED
        # plan up, so a resident run is noted under that key too
        admission.note_query_peak(lp, "optimized")
        return out
    except Exception as e:
        if not (conf.get(OOM_DEGRADE_ENABLED) and is_oom(e)):
            raise
        last = e

    # rung 0: adaptive execution. When the OOM hit with
    # spark.tpu.adaptive.enabled off, retry ONCE with it forced on —
    # exchange-heavy plans OOM on the D x cap receive buffers, and
    # measured post-exchange compaction shrinks exactly those while
    # producing byte-identical results. Cheaper than chunking (no
    # re-decode), so it goes first; a contextvar (not the shadow conf)
    # carries the override because run_fn closes over the SESSION conf.
    from spark_tpu.parallel import executor as _mex

    sess = None
    try:
        from spark_tpu.api.session import SparkSession

        sess = SparkSession._active
    except Exception:
        pass
    adaptive_off = not (_mex.FORCE_ADAPTIVE.get()
                        or bool(conf.get(_mex.CF.ADAPTIVE_ENABLED)))
    if adaptive_off and sess is not None \
            and getattr(sess, "_mesh", None) is not None:
        metrics.note_recovery("replans")
        metrics.record("degraded_to_adaptive", error=repr(last))
        token = _mex.FORCE_ADAPTIVE.set(True)
        try:
            out = run_fn(lp)
            metrics.record("fault_recovered", point="execute.device",
                           how="degraded_to_adaptive")
            return out
        except Exception as e2:
            if not is_oom(e2):
                raise
            if e2.__cause__ is None and e2 is not last:
                e2.__cause__ = last  # replan history rides the chain
            last = e2  # adaptive compaction was not enough: chunk
        finally:
            _mex.FORCE_ADAPTIVE.reset(token)

    budget = int(conf.get(MAX_DEVICE_BATCH_BYTES))
    floor = max(1, int(conf.get(OOM_DEGRADE_FLOOR)))
    # shadow conf: the ladder's shrinking budget must not leak into the
    # session (the next query starts from the configured budget again)
    shadow = RuntimeConf(dict(conf._overrides))
    attempted = False
    while budget // 2 >= floor:
        budget //= 2
        shadow.set(MAX_DEVICE_BATCH_BYTES.key, budget)
        found = find_chunkable(lp, shadow)
        if found is None:
            continue  # still under the halved budget: halve again
        attempted = True
        metrics.note_recovery("replans")
        metrics.record("degraded_to_chunked", budget=budget,
                       error=repr(last))
        try:
            out = execute_chunked(found, shadow, run_fn)
        except Exception as e2:
            if not is_oom(e2):
                raise
            if e2.__cause__ is None and e2 is not last:
                e2.__cause__ = last  # replan history rides the chain
            last = e2  # chunked tier still OOMs: halve again
            continue
        metrics.record("fault_recovered", point="execute.device",
                       how="degraded_to_chunked", budget=budget)
        return out
    metrics.note_recovery("ladder_exhausted")
    if not attempted:
        # no budget made the plan chunkable (e.g. an in-memory relation
        # with no file-backed scan): the ladder has nothing to offer —
        # surface the original typed OOM, not a misleading floor error
        raise last
    raise RuntimeError(
        f"device OOM persisted after degrading the batch budget down "
        f"to the {floor}-byte floor (last: {last!r})") from last


def plan_chunk_first(lp, conf, budget_bytes: int):
    """Plan a forced chunked-tier execution for the background-compile
    path (spark_tpu/compile/service): shrink the device-batch budget on
    a shadow conf so ``find_chunkable`` fires even for plans that fit
    HBM, returning ``(found, shadow_conf)`` ready for
    ``execute_chunked``, or ``(None, None)`` when the plan has no
    chunkable shape. The shadow never leaks into the session conf —
    same idiom as the OOM ladder above."""
    from spark_tpu.conf import RuntimeConf
    from spark_tpu.physical.chunked import (MAX_DEVICE_BATCH_BYTES,
                                            find_chunkable)

    shadow = RuntimeConf(dict(conf._overrides))
    shadow.set(MAX_DEVICE_BATCH_BYTES.key, max(1, int(budget_bytes)))
    found = find_chunkable(lp, shadow)
    if found is None:
        return None, None
    return found, shadow


def run_stage_with_recovery(fn: Callable, *, conf=None, label: str = "stage"):
    """Run ``fn`` (a stage/query execution thunk), retrying transient
    environment failures up to spark.stage.maxConsecutiveAttempts times.
    Each retry recomputes from lineage — ``fn`` must replan from the
    logical plan, not replay captured device buffers. Re-attempts draw
    from the query's unified RetryBudget (retry_allowed) and every
    backoff sleep is capped by the caller's remaining deadline."""
    attempts = int(conf.get(STAGE_MAX_ATTEMPTS)) if conf is not None \
        else STAGE_MAX_ATTEMPTS.default
    last: Optional[BaseException] = None
    for attempt in range(max(1, attempts)):
        try:
            # re-attempts get their own span so a trace waterfall shows
            # time lost to recovery, not just the winning attempt
            rspan = trace.span("fault.retry", point=label,
                               attempt=attempt) if attempt \
                else nullcontext()
            with rspan:
                out = fn()
            if attempt:
                metrics.record("fault_recovered", point=label,
                               how="stage_retry", attempts=attempt)
            return out
        except Exception as e:
            if not is_transient(e):
                raise
            last = e
            metrics.record("stage_retry", label=label, attempt=attempt,
                           error=repr(e))
            if attempt + 1 >= max(1, attempts):
                break
            deadline.check(label)  # the caller may already be gone
            if not retry_allowed(label):
                b = _BUDGET.get()
                raise RetryBudgetExhausted(label, b) from last
            backoff_sleep(attempt, base_s=0.1, cap_s=2.0)
    raise RuntimeError(
        f"{label} failed {attempts} consecutive attempts "
        f"(last: {last!r})") from last


class HeartbeatMonitor:
    """Driver-side liveness probe: a daemon thread runs a trivial device
    computation every interval and records the result in the event log.
    ``healthy()`` is False once a probe fails or the loop stops beating
    (hang detection)."""

    def __init__(self, interval_s: Optional[float] = None):
        self.interval = float(interval_s if interval_s is not None
                              else HEARTBEAT_INTERVAL.default)
        self._stop = threading.Event()
        self._last_ok: Optional[float] = None
        self._last_error: Optional[str] = None
        self._last_err_ts: float = 0.0
        self._thread: Optional[threading.Thread] = None

    def _probe(self) -> None:
        import jax
        import jax.numpy as jnp

        x = jax.device_put(jnp.ones((8,), jnp.float32))
        got = float(jnp.sum(x).block_until_ready())
        if got != 8.0:
            raise RuntimeError(f"heartbeat probe computed {got} != 8.0")

    def _loop(self) -> None:
        while not self._stop.wait(self.interval):
            try:
                self._probe()
                self._last_ok = time.time()
                metrics.record("heartbeat", ok=True)
            except Exception as e:
                self._last_error = repr(e)
                self._last_err_ts = time.time()
                metrics.record("heartbeat", ok=False, error=repr(e))

    def start(self) -> "HeartbeatMonitor":
        if self._thread is None:
            # one immediate synchronous probe so healthy() is meaningful
            # right away
            try:
                self._probe()
                self._last_ok = time.time()
            except Exception as e:
                self._last_error = repr(e)
                self._last_err_ts = time.time()
            self._thread = threading.Thread(
                target=self._loop, name="spark-tpu-heartbeat", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None

    def healthy(self, max_silence_s: Optional[float] = None) -> bool:
        if self._last_ok is None:
            return False
        if self._last_err_ts > self._last_ok:  # failed since last success
            return False
        silence = max_silence_s if max_silence_s is not None \
            else 3 * self.interval
        return (time.time() - self._last_ok) <= silence

    def status(self) -> dict:
        return {"last_ok": self._last_ok, "last_error": self._last_error,
                "interval_s": self.interval}


_CKPT_COUNTER = [0]
_CKPT_LOCK = locks.named_lock("recovery.checkpoint")


def checkpoint_dataframe(df, eager: bool = True):
    """Durable checkpoint: materialize to Parquet under
    spark.checkpoint.dir and return a DataFrame scanning the files —
    lineage truncated, survives the session (reference:
    ReliableCheckpointRDD; RDD.scala:1627)."""
    session = df.sparkSession
    d = str(session.conf.get(CHECKPOINT_DIR) or "")
    if not d:
        raise RuntimeError(
            "set spark.checkpoint.dir (or SparkContext.setCheckpointDir) "
            "before calling checkpoint(); use localCheckpoint() for the "
            "in-memory variant")
    with _CKPT_LOCK:
        _CKPT_COUNTER[0] += 1
        seq = _CKPT_COUNTER[0]
    # the uuid component keeps paths unique across sessions in one pid
    # (the bare counter restarts with the module and collided)
    path = os.path.join(
        d, f"ckpt-{os.getpid()}-{seq}-{uuid.uuid4().hex[:8]}")
    df.write.mode("overwrite").parquet(path)
    out = session.read.parquet(path)
    if eager:
        out.count()
    return out
