"""HBM admission control: a shared device-bytes budget that decides
*when* a query may touch the device and under *what* memory budget.

The footprint estimate reuses the cost model the join reorderer already
trusts (plan/join_reorder.estimate_rows — exact at Parquet/batch
leaves, heuristic above) times the schema row width, taken as the MAX
over plan nodes: the widest intermediate a plan materializes is what
actually presses HBM, not its (often tiny, post-aggregate) output.

Admission is deliberately optimistic at the edges, mirroring the
chunk pipeline's prefetch cap (conf.PREFETCH_BYTES_MAX): a query larger
than the whole budget is still admitted when the device is otherwise
idle — charged the full budget so nothing else co-runs — and relies on
the existing chunked/OOM-degradation ladder
(recovery.run_plan_with_oom_degradation) to survive. Refusing it
outright would make over-budget queries unservable even on an idle
device.
"""

from __future__ import annotations

import threading

from spark_tpu import locks, metrics, trace

from spark_tpu import conf as CF

#: floor on any footprint estimate — below this the estimate noise
#: exceeds the signal and admission decisions would thrash
MIN_ESTIMATE_BYTES = 64 * 1024

#: measured stage footprints from prior executions, keyed by the
#: logical plan's injective structural_key() (adaptive execution's
#: answer to "use measured, not static, plan bytes once stats exist":
#: DataFrame._execute notes the max stage_bytes event of each finished
#: query here; estimate_plan_bytes prefers a recorded measurement over
#: the static row-count estimate). Bounded LRU under a lock —
#: structural keys pin source objects by id, so unbounded growth would
#: also pin dead batches.
_MEASURED_LOCK = locks.named_lock("admission.measured")
_MEASURED_MAX_ENTRIES = 512
_MEASURED: "dict" = {}


def note_measured_bytes(plan, nbytes: int) -> None:
    """Record the measured peak stage footprint of an executed logical
    plan (no-op when the key cannot be computed or the value is
    non-positive)."""
    if nbytes <= 0:
        return
    try:
        key = plan.structural_key()
    except Exception:
        return
    with _MEASURED_LOCK:
        # re-insertion moves the key to the back of the dict (LRU-ish:
        # python dicts preserve insertion order)
        prev = _MEASURED.pop(key, 0)
        _MEASURED[key] = max(int(nbytes), prev)
        while len(_MEASURED) > _MEASURED_MAX_ENTRIES:
            _MEASURED.pop(next(iter(_MEASURED)))


def note_query_peak(plan, key: str) -> None:
    """Note for ``plan`` the peak stage footprint of the query that has
    just run: the largest ``stage_bytes`` event the ring holds since
    its ``query_start``. A query is noted under both of its shapes:
    ``key`` "raw" (DataFrame._execute_traced: the plan
    scheduler.submit_query estimates before execution) and "optimized"
    (recovery, after a resident run: what the grant pre-step and the
    hybrid join see). Span ``admission.note``: ``events`` is how many
    ring events ``last_query()`` handed back, ``bytes`` the peak."""
    try:
        note = trace.span("admission.note", key=key)
        with note:
            events = metrics.last_query()
            peak = max((int(e.get("bytes", 0)) for e in events
                        if e.get("kind") == "stage_bytes"), default=0)
            note.attrs.update(events=len(events), bytes=peak)
            note_measured_bytes(plan, peak)
    except Exception:
        pass  # observability must never fail the query


def measured_plan_bytes(plan):
    """The recorded measurement for this plan shape, or None."""
    try:
        key = plan.structural_key()
    except Exception:
        return None
    with _MEASURED_LOCK:
        return _MEASURED.get(key)


def measured_snapshot() -> dict:
    """Size/total of the measured-footprint table — after a pre-warm
    replay (compile/service) this is populated before the first client
    query, so admission decisions start from measured bytes instead of
    static estimates; the compile service surfaces it in status()."""
    with _MEASURED_LOCK:
        return {"plans": len(_MEASURED),
                "max_bytes": max(_MEASURED.values(), default=0)}


def estimate_plan_bytes(plan, conf) -> int:
    """Estimated device footprint of executing ``plan``: a MEASURED
    peak stage footprint from a prior run of the same plan shape when
    one exists (note_measured_bytes), else max over plan nodes of
    estimated rows x 8-byte columns (x64 engine). Falls back to the
    device batch budget when estimation fails — unknown plans admit
    serially rather than stampeding HBM."""
    from spark_tpu.physical.chunked import MAX_DEVICE_BATCH_BYTES

    measured = measured_plan_bytes(plan)
    if measured is not None:
        return max(MIN_ESTIMATE_BYTES, int(measured))
    try:
        from spark_tpu.plan.join_reorder import estimate_rows

        def node_bytes(node) -> float:
            try:
                width = 8 * max(1, len(node.schema.names))
            except Exception:
                width = 8
            own = estimate_rows(node) * width
            return max([own] + [node_bytes(c) for c in node.children()])

        est = int(node_bytes(plan))
    except Exception:
        est = int(conf.get(MAX_DEVICE_BATCH_BYTES))
    return max(MIN_ESTIMATE_BYTES, est)


def seeded_build_bytes(plan, fallback: int) -> int:
    """Grant request for the hybrid hash join's build staging: the
    MEASURED peak footprint of this plan shape when a prior run (AQE)
    recorded one, else the planner's static estimate passed as
    ``fallback``. Deliberately does NOT fall through to the device
    batch budget the way estimate_plan_bytes does — an unknown join
    should request what the planner believes, not a 5 GiB default that
    would evict the whole cache for nothing."""
    measured = measured_plan_bytes(plan)
    if measured is not None and measured > 0:
        return max(MIN_ESTIMATE_BYTES, int(measured))
    return max(MIN_ESTIMATE_BYTES, int(fallback))


class AdmissionController:
    """Byte-budget gate over the EXECUTION side of the unified
    storage/execution memory manager (storage/unified.py — the
    UnifiedMemoryManager analogue). When the serving session holds an
    HBM-resident MemoryStore, admission and cached storage share one
    budget: an admission that does not fit first evicts unpinned cached
    batches down to the protected ``spark.tpu.storage.minBytes``
    region. ``fits``/``acquire`` are lock-protected; the scheduler
    holds its own condition around them, so the controller itself never
    blocks."""

    def __init__(self, budget_bytes: int, manager=None):
        from spark_tpu.storage.unified import UnifiedMemoryManager

        self._m = manager if manager is not None \
            else UnifiedMemoryManager(budget_bytes)

    @property
    def budget(self) -> int:
        return self._m.budget

    @property
    def manager(self):
        """The shared UnifiedMemoryManager (storage attaches here)."""
        return self._m

    def charge_for(self, nbytes: int) -> int:
        """What an admission of ``nbytes`` costs: capped at the whole
        budget so an over-budget query can still admit alone."""
        return self._m.charge_for(nbytes)

    def fits(self, nbytes: int) -> bool:
        return self._m.fits_execution(nbytes)

    def acquire(self, nbytes: int) -> int:
        """Charge the budget (evicting unpinned storage if needed);
        returns the charge to pass to release(). Caller must have
        checked fits() under the scheduler lock."""
        return self._m.acquire_execution(nbytes)

    def release(self, charge: int) -> None:
        self._m.release_execution(charge)

    def snapshot(self) -> dict:
        return self._m.snapshot()
