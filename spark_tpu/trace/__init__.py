"""End-to-end hierarchical query tracing (spark_tpu/trace/).

The span analogue of the reference's TaskMetrics/SQLMetrics + event-log
replay: every query gets a ``trace_id``, and every unit of work —
connect request, router dispatch, scheduler queue/admit/run, plan
analysis, the tier decision, compile-store probe, each stage's enqueue,
the host's wait on the device, exchange stats fetch, pipeline chunk decode/transfer, fault retry, result-cache/
mview/storage probe — opens a child span under a contextvar-carried
parent. Spans land in the existing metrics ring/JSONL as ``span``
events, and the active (trace_id, span_id, parent_id) triple is stamped
onto EVERY event ``metrics.record()`` emits, so flat events (stage,
exchange, fault_injected, ...) attribute to the query that caused them
even under the concurrent scheduler — positional slicing survives only
as a fallback for id-less events.

Context crosses threads explicitly (scheduler tickets and the chunk
pipeline producer capture ``current()`` and re-enter it) and crosses
processes via the ``X-SparkTpu-Trace`` header (``header_value()`` /
``from_header()``), so one trace spans client -> federation router ->
replica -> scheduler -> stages.

Every sampled span is also a ``jax.profiler.TraceAnnotation`` named
``spark.<name>``: a profiler session (``tracing.trace(dir)``, the
benchmark's traced slice) then holds the span tree on ``/host:CPU``,
on the same clock as the device's operations, and an idle stretch of
the device can be given to the span that covered it
(benchmark/span_times.py). With no profiler session an annotation is
an atomic load.

Cost discipline: id stamping is always on (one contextvar read per
event). Span *events* and annotations obey ``spark.tpu.trace.enabled``
and the ``spark.tpu.trace.sampleRatio`` knob — the sampling decision
is made once at root creation and inherited, so a trace is either
complete or absent, never partial. A child span costs under 4 us
(ids from a process-wide counter, a ``__slots__`` context manager,
the event written straight into the ring); PERF.md has the reading.
Tracing never touches data: results are byte-identical with tracing
on or off.

Every span name must be declared in ``SPAN_NAMES`` below —
tools/lint_invariants.py rule 6 enforces the same discipline conf keys
and fault points get.
"""

from __future__ import annotations

import itertools
import os
import random
import threading
import time
from contextlib import contextmanager
from typing import Any, Iterator, NamedTuple, Optional

import jax
from jax.profiler import TraceAnnotation

from spark_tpu import conf as CF
from spark_tpu import metrics

TRACE_ENABLED = CF.register(
    "spark.tpu.trace.enabled", True,
    "Record hierarchical span events for every unit of query work "
    "(connect request, dispatch, queue, stage, chunk, ...). Ids are "
    "stamped on events regardless; this only gates span events.", bool)

TRACE_SAMPLE_RATIO = CF.register(
    "spark.tpu.trace.sampleRatio", 1.0,
    "Fraction of traces that record span events (decided once at root "
    "creation, inherited fleet-wide via X-SparkTpu-Trace). Lower it "
    "when span-heavy paths (per-chunk pipeline spans) matter.", float)

TRACE_HEADER = "X-SparkTpu-Trace"

#: central registry of legal span names (lint_invariants rule 6:
#: every ``trace.span("<name>", ...)`` literal must appear here)
SPAN_NAMES = frozenset({
    "connect.client",       # client side of one HTTP request
    "connect.request",      # replica/server handling of one request
    "router.dispatch",      # federation routing of one request
    "router.forward",       # one forward attempt to one replica
    "scheduler.queue",      # submit -> admitted (queue + admission gate)
    "scheduler.run",        # prepare + execute on a scheduler worker
    "query.parse",          # SparkSession.sql: text -> resolved plan
    "query.execute",        # DataFrame._execute (root when standalone):
                            # the whole query, host materialisation
                            # (fetch + rows) included
    "query.analysis",       # static plan analysis + submit gate
    "query.optimize",       # logical optimisation at execution time
    "query.plan",           # logical -> physical, scan-cache lookup,
                            # compaction replay, adaptive binding
    "compile.probe",        # AOT executable-store lookup
    "stage.run",            # one physical stage (host glue + device)
    "stage.fused",          # whole-query fused span: multi-exchange
                            # plan as ONE XLA program, zero host sync
    "stage.dispatch",       # the jitted call alone: flatten + enqueue
    "query.fetch",          # Batch.fetch_host: device -> host, whole
    "device.wait",          # host blocked until the enqueued work is done:
                            # fetch_host, and (op="readback") the mesh
                            # engine's reads of a mask between stages
    "fetch.copy",           # what is left of the device -> host copies
    "query.rows",           # decode dictionaries/dates/decimals, build rows
    "exchange.stats",       # AQE host round-trip fetching device stats
    "agg.decide",           # adaptive-agg sketch fetch + strategy pick
    "agg.sort",             # sort rung: range exchange + sorted merge
    "agg.presplit",         # hot-key pre-split: salted exchange + merge
    "pipeline.decode",      # chunk pipeline: one chunk decode+filter
    "pipeline.transfer",    # chunk pipeline: one chunk host->device
    "fault.retry",          # one recovery re-attempt after a fault
    "result_cache.probe",   # serve-tier plan-keyed result cache probe
    "serve.epoch",          # ownership epoch mint + fleet broadcast
    "serve.invalidate",     # one invalidation-log record applied
    "mview.probe",          # materialized-view / cache-manager probe
    "storage.pin",          # HBM pin-scope around query execution
    "tier.decide",          # resident / chunked / planned_chunked, taken
                            # before the engine runs (recovery.py)
    "admission.note",       # the query's peak stage_bytes, read back from
                            # the ring for the scheduler's admission table
    "join.size",            # a join's first execution: its compiled sizing
                            # programs (key ranges, matches, index);
                            # how, rows (matches), capacity (of its output)
    "join.partition",       # hybrid hash join: grant + partition pass
    "join.spill",           # hybrid hash join: one spill write/read
    "slo.admit",            # SLO feasibility check at submit time
    "slo.observe",          # fold a finished query into the SLO model
})


#: kinds of the build events: one per program piece BUILT (inside the
#: trace of a stage, or eagerly in a blocking run), never one per
#: execution of a compiled stage. lint_invariants rule 6 holds every
#: ``trace.built("<kind>", ...)`` literal to this set.
BUILD_EVENTS = frozenset({
    "seg_sum",   # kernels.seg_sum: rung (reduce/masked/cumsum/scatter),
                 # k, rows, dtype, passes
    "join",      # JoinExec.trace: rung (table/index/live), how,
                 # orientation, build rows, probe capacity
    "sort",      # one per XLA sort built; site, rows, and dtype
                 # (kernels.py) or, for site group_rows (the rows of a
                 # sorted aggregate, operators.sorted_groups), keys (sort
                 # keys), operands (arrays that ride the sort) and
                 # gathered (2-D columns gathered by its row index)
    "gather",    # parallel/sharded.py: one per mesh packer built (the
                 # program a MeshResult's fetch leaves the mesh by);
                 # mesh, capacity, arrays
    "group_by",  # HashAggregateExec: one per aggregate built; strategy
                 # (direct/sorted), keys (their dtypes as they travel),
                 # rows (capacity), k (num_segments), groups (the
                 # _AGG_STATS count k was sized from; None when direct)
    "expand",    # kernels.expand_join_pairs: one per match expansion
                 # built; method (starts/search), probe_rows, pairs
})

#: prefix of the ``jax.named_scope`` round each operator's ``trace()``
#: in a fused stage: device operations carry ``spark.<Operator>`` in
#: their ``op_name`` (lowered text, HLO metadata, the profiler's trace)
SCOPE_PREFIX = "spark."


#: scopes INSIDE an operator's own, for the halves of one operator that
#: a trace must tell apart. A device operation is booked to its
#: innermost ``spark.*`` scope (benchmark/op_scopes.py::scope_of), so an
#: inner scope takes its operations away from the operator's reading.
#: lint_invariants rule 6 holds every ``trace.inner_scope("<name>")``
#: literal to this set.
INNER_SCOPES = frozenset({
    "GroupSort",  # sort-based aggregate: the one sort of its rows by
                  # the grouping keys, the change-flag group ids
    "GroupSum",   # sort-based aggregate: the aggregates over the sorted
                  # group ids and the groups' first keys
})


def built(kind: str, **fields: Any) -> None:
    """Record a build event (``BUILD_EVENTS``) in the metrics ring."""
    metrics.record(kind, **fields)


def operator_scope(plan: Any):
    """``jax.named_scope("spark.<Operator>")`` for ``plan.trace()``.
    Names only: the compiled program and its cache keys are the same
    with and without it."""
    return jax.named_scope(SCOPE_PREFIX + type(plan).__name__)


def inner_scope(name: str):
    """``jax.named_scope("spark.<name>")`` for one of ``INNER_SCOPES``,
    opened inside an operator's ``trace()``. Names only, as
    ``operator_scope``."""
    return jax.named_scope(SCOPE_PREFIX + name)


class SpanContext(NamedTuple):
    trace_id: str
    span_id: str
    parent_id: Optional[str]
    sampled: bool

    def header(self) -> str:
        """Wire form for ``X-SparkTpu-Trace`` (traceparent-shaped:
        trace-span-flags)."""
        return f"{self.trace_id}-{self.span_id}-{int(self.sampled)}"


# ids without a system call apiece: a generator of this module's own,
# seeded from the OS once (and again in a forked child). A trace id is 64
# random bits. A span id is a per-process random prefix, which keeps two
# replicas that serve one trace from minting the same id, and a
# process-wide counter in decimal (its digits are hex digits too, which
# is all the wire form asks).
_RNG = random.Random()
_SPAN_PREFIX = f"{_RNG.getrandbits(24):06x}"
_SPAN_COUNTER = itertools.count(1)   # next() is atomic under the GIL


def _reseed_ids() -> None:
    global _SPAN_PREFIX
    _RNG.seed()
    _SPAN_PREFIX = f"{_RNG.getrandbits(24):06x}"


os.register_at_fork(after_in_child=_reseed_ids)


def _new_trace_id() -> str:
    return f"{_RNG.getrandbits(64):016x}"


def _new_span_id() -> str:
    return f"{_SPAN_PREFIX}{next(_SPAN_COUNTER)}"


def current() -> Optional[SpanContext]:
    """The active span context on this thread (None outside any trace)."""
    return metrics.trace_context()


def current_trace_id() -> Optional[str]:
    ctx = metrics.trace_context()
    return ctx.trace_id if ctx is not None else None


def _conf():
    from spark_tpu.api.session import SparkSession

    sess = SparkSession._active
    return None if sess is None else sess.conf


def _sample_root() -> bool:
    """Sampling decision for a NEW trace root."""
    conf = _conf()
    try:
        enabled = bool(conf.get(TRACE_ENABLED)) if conf is not None \
            else bool(TRACE_ENABLED.default)
        ratio = float(conf.get(TRACE_SAMPLE_RATIO)) if conf is not None \
            else float(TRACE_SAMPLE_RATIO.default)
    except Exception:
        enabled, ratio = True, 1.0
    if not enabled or ratio <= 0.0:
        return False
    if metrics.brownout_level() > 0:
        # fleet brownout sheds NEW trace sampling before any query:
        # in-flight traces finish, fresh roots go unsampled
        return False
    return ratio >= 1.0 or random.random() < ratio


_ANNOTATION = {name: "spark." + name for name in SPAN_NAMES}

# the hot path's globals, bound once
_CTX = metrics._TRACE_CTX
_clock = time.perf_counter
# a span reads one clock: ``t0`` is perf_counter plus this offset to the
# epoch, taken again at every trace root, so a child's interval lies
# inside its parent's to the nanosecond and a long-lived process follows
# the wall clock's corrections
_epoch_offset = time.time() - time.perf_counter()
_thread_ident = threading.get_ident
_new_context = tuple.__new__
# is a profiler session collecting? (the atomic load a TraceMe makes
# itself; asked first, a span outside any session builds no annotation)
_profiling = getattr(TraceAnnotation, "is_enabled", lambda: True)


class span:
    """Open one unit of work as a child of the ambient span (or as a
    new trace root when none is active): ``with trace.span(name,
    **attrs) as ctx``. A sampled span is a ``TraceAnnotation``
    ``spark.<name>`` round its body, and on exit a ``span`` event in
    the metrics ring/JSONL with trace_id/span_id/parent_id, start time
    ``t0`` (epoch s), ``ms`` and the attrs; an unsampled one only
    carries the ids. Root exit also flushes the buffered JSONL writer
    so a finished query is always on disk."""

    __slots__ = ("name", "attrs", "ctx", "_token", "_annotation", "_p0")

    def __init__(self, name: str, **attrs: Any):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> SpanContext:
        global _epoch_offset
        parent = _CTX.get()
        if parent is None:
            ctx = SpanContext(_new_trace_id(), _new_span_id(), None,
                              _sample_root())
            _epoch_offset = time.time() - _clock()
        else:
            # the hot path: _new_span_id() and SpanContext(...) without
            # their two calls
            ctx = _new_context(SpanContext, (
                parent[0], f"{_SPAN_PREFIX}{next(_SPAN_COUNTER)}",
                parent[1], parent[3]))
        self.ctx = ctx
        self._token = _CTX.set(ctx)
        if ctx[3]:
            if _profiling():
                name = self.name
                self._annotation = annotation = TraceAnnotation(
                    _ANNOTATION.get(name) or "spark." + name)
                annotation.__enter__()
            else:
                self._annotation = None
            self._p0 = _clock()
        return ctx

    def __exit__(self, exc_type, exc, tb) -> None:
        ctx = self.ctx
        _CTX.reset(self._token)
        if ctx[3]:
            p0 = self._p0
            dt = _clock() - p0
            if self._annotation is not None:
                self._annotation.__exit__(None, None, None)
            t0 = p0 + _epoch_offset
            ev = {"ts": t0 + dt, "kind": "span", "name": self.name,
                  "ms": dt * 1e3, "t0": t0,
                  "tid": _thread_ident() % 10_000_000,
                  "trace_id": ctx[0], "span_id": ctx[1],
                  "parent_id": ctx[2]}
            if exc is not None:
                ev["error"] = repr(exc)
            if self.attrs:
                ev.update(self.attrs)
            metrics.append(ev)
        if ctx[2] is None:
            # trace root closed: a query just finished end-to-end
            metrics.flush_log()


@contextmanager
def attach(ctx: Optional[SpanContext]) -> Iterator[None]:
    """Re-enter a captured span context on another thread (scheduler
    worker, pipeline producer) or adopt a remote parent decoded from
    ``X-SparkTpu-Trace``. No span event is recorded — children opened
    inside do that."""
    if ctx is None:
        yield
        return
    token = metrics.set_trace_context(ctx)
    try:
        yield
    finally:
        metrics.reset_trace_context(token)


def from_header(value: Optional[str]) -> Optional[SpanContext]:
    """Decode ``X-SparkTpu-Trace``; malformed values are dropped (a bad
    peer must not break serving)."""
    if not value:
        return None
    parts = value.strip().split("-")
    if len(parts) != 3 or not parts[0] or not parts[1]:
        return None
    if not all(c in "0123456789abcdef" for c in parts[0] + parts[1]):
        return None
    return SpanContext(parts[0], parts[1], None, parts[2] == "1")


def header_value() -> Optional[str]:
    """Wire form of the current context (None outside any trace)."""
    ctx = metrics.trace_context()
    return ctx.header() if ctx is not None else None
