"""Asynchronous chunk pipeline for out-of-HBM execution.

The serial chunk loop (decode chunk -> filter host-side -> ship ->
compute -> repeat) leaves the TPU idle during every decode/transfer and
the host idle during every device step. This module is the producer/consumer overlap Spark's
shuffle fetch path gets from ShuffleBlockFetcherIterator's in-flight
request window (core/.../storage/ShuffleBlockFetcherIterator.scala:78):
a background producer thread pulls the next chunks off the parquet
stream, applies the host-side semi/Bloom key filters, narrows them, and
initiates the host->device transfer, while the caller thread merges the
previous chunks' partials on device.

Determinism: ONE producer thread feeding a FIFO queue, consumed in
source order — the device merge order is identical to the serial loop
at every depth, so float results are byte-identical (the acceptance
contract of tests/test_out_of_core.py's depth-sweep tests).

Bounds: ``spark.tpu.pipelineDepth`` caps the number of prepared chunks
in flight; ``spark.tpu.prefetchBytesMax`` caps their bytes (the
producer stalls before decoding the next chunk once in-flight bytes
reach the budget — at least one chunk is always admitted so a budget
smaller than a chunk degrades to serial instead of deadlocking).

``depth == 0`` runs the classic serial loop on the caller thread with
the same staging/timers, so the two paths share one code shape.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Iterable, Iterator, Optional

from spark_tpu import locks
from spark_tpu import conf as CF
from spark_tpu import deadline, faults, metrics, trace
from spark_tpu.metrics import PipelineStats

CHUNK_RETRY_ATTEMPTS = CF.register(
    "spark.tpu.chunkRetryAttempts", 3,
    "Bounded attempts for one chunk's decode/prepare/transfer in the "
    "out-of-HBM pipeline before the failure is relayed to the consumer "
    "(reference analogue: ShuffleBlockFetcherIterator retrying one "
    "block fetch instead of failing the stage).", int)

_SENTINEL = object()


class _Err:
    """Producer-side exception carrier (re-raised on the consumer)."""

    def __init__(self, exc: BaseException):
        self.exc = exc


class ChunkPipeline:
    """Bounded producer/consumer pipeline over an iterator of work items.

    ``source`` yields raw work items (arrow tables, partition ids);
    pulling the next item is timed as the *decode* stage. ``prepare``
    turns one item into a consumable result (timing its own filter/
    transfer stages against ``stats``) or returns None to skip the item
    (empty / fully filtered chunk). ``nbytes_of(prepared)`` feeds the
    in-flight byte budget.

    With ``depth >= 1`` the producer thread starts at construction, so
    chunk decode can overlap work the caller does before it starts
    consuming (e.g. sidecar materialization). Iterate the pipeline to
    consume results in source order.
    """

    def __init__(self, source: Iterable[Any],
                 prepare: Callable[[Any], Optional[Any]],
                 *, depth: int, byte_budget: int,
                 stats: PipelineStats,
                 nbytes_of: Optional[Callable[[Any], int]] = None,
                 conf=None):
        self._source = iter(source)
        self._prepare = prepare
        self._depth = max(0, int(depth))
        self._budget = max(1, int(byte_budget))
        self._stats = stats
        self._nbytes = nbytes_of or (lambda prepared: 0)
        self._conf = conf
        self._retry_attempts = max(1, int(
            conf.get(CHUNK_RETRY_ATTEMPTS) if conf is not None
            else CHUNK_RETRY_ATTEMPTS.default))
        self._thread: Optional[threading.Thread] = None
        # capture the caller's span context so producer-side chunk
        # spans (pipeline.decode/transfer) join the query's trace even
        # though they run on the background thread; the caller's
        # deadline and retry budget cross the same thread boundary so
        # producer-side retries stay bounded by the query's pool and
        # stop when the caller's window closes
        self._trace_ctx = metrics.trace_context()
        self._deadline = deadline.current()
        from spark_tpu import recovery

        self._retry_budget = recovery.current_budget()
        if self._depth >= 1:
            self._queue: queue.Queue = queue.Queue(maxsize=self._depth)
            self._cond = locks.named_condition("pipeline.cond")
            self._inflight_bytes = 0
            self._inflight_chunks = 0
            self._stop = False
            self._thread = threading.Thread(
                target=self._produce, daemon=True, name="chunk-pipeline")
            self._thread.start()

    # ---- shared pull/prepare step with bounded per-chunk retry -------------

    def _next_prepared(self) -> Any:
        """Pull the next item and prepare it, retrying an individual
        chunk's decode/prepare/transfer up to chunkRetryAttempts times
        on transient failures before relaying the error — so one
        dropped transfer costs one chunk retry, not the whole query.
        Returns ``(prepared, size)``, ``None`` for a skipped chunk, or
        ``_SENTINEL`` at end of source.

        Retry safety: a generator that raised is exhausted, so a
        decode-phase failure is only retryable when it is an injected
        fault (which fires *before* the source is touched); once the
        item is in hand, ``prepare`` is pure and always retryable.
        """
        from spark_tpu import recovery

        st = self._stats
        item: Any = _SENTINEL  # sentinel doubles as "not yet pulled"
        for attempt in range(self._retry_attempts):
            try:
                if item is _SENTINEL:
                    with trace.span("pipeline.decode"), \
                            st.timed("decode"):
                        faults.inject("pipeline.decode", self._conf)
                        nxt = next(self._source, _SENTINEL)
                    if nxt is _SENTINEL:
                        return _SENTINEL
                    item = nxt
                with trace.span("pipeline.transfer"):
                    faults.inject("pipeline.transfer", self._conf)
                    prepared = self._prepare(item)
                if attempt:
                    metrics.record("fault_recovered", point="pipeline",
                                   how="chunk_retry", attempts=attempt)
                if prepared is None:
                    return None
                return (prepared, self._nbytes(prepared))
            except Exception as e:
                retryable = recovery.is_transient(e) and (
                    item is not _SENTINEL
                    or isinstance(e, faults.InjectedFault))
                if not retryable or attempt + 1 >= self._retry_attempts:
                    raise
                deadline.check("pipeline.chunk")
                if not recovery.retry_allowed("pipeline.chunk"):
                    raise recovery.RetryBudgetExhausted(
                        "pipeline.chunk", recovery.current_budget()) from e
                metrics.record("chunk_retry", attempt=attempt + 1,
                               error=repr(e))
                time.sleep(deadline.cap_sleep(
                    min(0.05 * 2 ** attempt, 0.5)))
        raise AssertionError("unreachable")  # loop always returns/raises

    # ---- serial path (depth == 0) -----------------------------------------

    def _iter_serial(self) -> Iterator[Any]:
        st = self._stats
        while True:
            got = self._next_prepared()
            if got is _SENTINEL:
                return
            if got is None:
                continue
            prepared, size = got
            st.note_inflight(size, 1)
            yield prepared

    # ---- threaded path -----------------------------------------------------

    def _produce(self) -> None:
        from spark_tpu import recovery

        with trace.attach(self._trace_ctx), \
                deadline.bind(self._deadline), \
                recovery.bind_budget(self._retry_budget):
            self._produce_traced()

    def _produce_traced(self) -> None:
        st = self._stats
        try:
            while True:
                # byte-budget gate BEFORE decoding the next chunk: once
                # in-flight bytes reach the budget, prefetch pauses
                # (but one chunk is always admitted)
                t0 = time.perf_counter()
                with self._cond:
                    while (not self._stop
                           and self._inflight_chunks > 0
                           and self._inflight_bytes >= self._budget):
                        # notify-driven: the consumer notifies on every
                        # chunk release and close(); the timeout is a
                        # liveness backstop only
                        self._cond.wait(0.5)
                    if self._stop:
                        return
                waited = (time.perf_counter() - t0) * 1e3
                if waited > 0.05:
                    st.add("stall_producer", waited)
                got = self._next_prepared()
                if got is _SENTINEL:
                    break
                if got is None:
                    continue
                prepared, size = got
                with self._cond:
                    self._inflight_bytes += size
                    self._inflight_chunks += 1
                    st.note_inflight(self._inflight_bytes,
                                     self._inflight_chunks)
                self._put((prepared, size))
            self._put(_SENTINEL)
        except BaseException as e:  # noqa: BLE001 — relayed to consumer
            # a full queue is the steady state of an active pipeline, so
            # the error must be relayed with the stop-aware blocking put:
            # it delivers to an active consumer and bails out via _stop
            # if the consumer abandoned the iterator
            self._put(_Err(e))

    def _put(self, obj: Any) -> None:
        """queue.put that stays responsive to consumer abandonment."""
        t0 = time.perf_counter()
        while True:
            with self._cond:
                if self._stop:
                    return
            try:
                self._queue.put(obj, timeout=0.1)
                waited = (time.perf_counter() - t0) * 1e3
                if waited > 0.1:
                    self._stats.add("stall_producer", waited)
                return
            except queue.Full:
                continue

    def _iter_threaded(self) -> Iterator[Any]:
        st = self._stats
        try:
            while True:
                t0 = time.perf_counter()
                got = self._queue.get()
                waited = (time.perf_counter() - t0) * 1e3
                if waited > 0.05:
                    st.add("stall_consumer", waited)
                if got is _SENTINEL:
                    return
                if isinstance(got, _Err):
                    raise got.exc
                prepared, size = got
                try:
                    yield prepared
                finally:
                    with self._cond:
                        self._inflight_bytes -= size
                        self._inflight_chunks -= 1
                        self._cond.notify_all()
        finally:
            self.close()

    def __iter__(self) -> Iterator[Any]:
        if self._depth == 0:
            return self._iter_serial()
        return self._iter_threaded()

    def close(self) -> None:
        """Stop the producer (idempotent; called automatically when the
        consuming iterator finishes or is abandoned)."""
        if self._thread is None:
            return
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        # drain so a producer blocked on put() can observe _stop
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5.0)
