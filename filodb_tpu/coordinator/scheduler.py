"""Bounded shared query scheduler (reference QueryScheduler.scala:29-73 —
one instrumented ForkJoinPool shared by all query execution, sized to the
host, so N concurrent queries cannot each grab the device/compile pipeline
at once).

Semantics:
- at most ``parallelism`` queries execute concurrently; up to ``max_queued``
  more wait for a slot;
- beyond that, submission fails fast with :class:`QueryRejected` (the HTTP
  edge maps it to 503, matching Prometheus' overload behavior);
- a query whose caller stops waiting (deadline) keeps its worker only until
  the next ``ctx.check_deadline()`` between plan nodes, then aborts — device
  work in flight cannot be interrupted, exactly the reference's cooperative
  cancellation model.
"""

from __future__ import annotations

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as FutureTimeout

from ..metrics import REGISTRY, span
from ..query.exec.transformers import QueryDeadlineExceeded, QueryError


class QueryRejected(QueryError):
    """Admission control: pool and queue are full."""


class SingleFlight:
    """Coalesce concurrent IDENTICAL queries into one execution.

    Dashboards fan the same panel query out N times within milliseconds;
    without coalescing each copy pays its own staging lookup + kernel
    launch + render. The first arrival for a key becomes the leader and
    executes; followers that arrive while it runs share its result (and its
    exception). In-flight only — nothing is cached after completion, so a
    shared answer is exactly as fresh as the followers' own execution would
    have been. Compatible-query batching beyond exact identity happens
    below this layer: the mesh stage cache shares staged blocks and window
    matrices across queries that differ only in function/aggregation.

    Caveat: a follower whose deadline exceeds the leader's inherits the
    leader's deadline failure; identical queries almost always carry
    identical deadlines (same dashboard), so this trade is taken for the
    16x fan-out win."""

    def __init__(self):
        self._lock = threading.Lock()
        self._flights: dict = {}

    def run(self, key, fn, timeout_s: float):
        from concurrent.futures import Future

        with self._lock:
            fut = self._flights.get(key)
            leader = fut is None
            if leader:
                fut = Future()
                self._flights[key] = fut
        if not leader:
            REGISTRY.counter("filodb_queries_coalesced").inc()
            try:
                with span("coalesce:wait") as sp:
                    return fut.result(timeout=timeout_s)
            except FutureTimeout:
                REGISTRY.counter("filodb_queries_deadline_exceeded").inc()
                raise QueryDeadlineExceeded(
                    f"query exceeded deadline: {timeout_s:.1f}s (coalesced)"
                ) from None
            finally:
                # the follower's wait for the leader's execution: a clock
                # per caller beside the counter (the leader books nothing)
                REGISTRY.histogram(
                    "filodb_query_wait_seconds", kind="coalesced"
                ).observe(sp.seconds)
        try:
            result = fn()
        except BaseException as e:
            with self._lock:
                self._flights.pop(key, None)
            fut.set_exception(e)
            raise
        # deregister BEFORE resolving: an arrival after completion must run
        # its own flight (sharing is for concurrent queries, never a cache)
        with self._lock:
            self._flights.pop(key, None)
        fut.set_result(result)
        return result


class QueryScheduler:
    def __init__(self, parallelism: int | None = None, max_queued: int = 64):
        self.parallelism = parallelism or min(8, os.cpu_count() or 4)
        self.max_queued = max_queued
        self._pool = ThreadPoolExecutor(
            max_workers=self.parallelism, thread_name_prefix="filodb-query"
        )
        # slots = running + queued; acquired non-blocking at submission
        self._slots = threading.BoundedSemaphore(self.parallelism + max_queued)
        self._in_flight = 0
        self.peak_in_flight = 0
        self._lock = threading.Lock()
        # the two hops' clocks, looked up once: every request books both
        self._hops = {kind: REGISTRY.histogram("filodb_query_wait_seconds",
                                               kind=kind)
                      for kind in ("queued", "handback")}

    @property
    def in_flight(self) -> int:
        return self._in_flight

    def run(self, fn, deadline_s: float, phases=None):
        """Run ``fn()`` on the shared pool; wait at most ``deadline_s``.
        Raises QueryRejected when saturated, QueryError on deadline.

        The two thread hops are clocked per caller into
        ``filodb_query_wait_seconds``: ``kind="queued"`` from the submit
        until a worker starts ``fn``, ``kind="handback"`` from the worker's
        last statement until this caller runs again; both are also phase
        ``queue`` of ``phases`` (the query's PhaseRecorder) when given. The
        caller's whole wait is the span ``sched:run``."""
        if not self._slots.acquire(blocking=False):
            REGISTRY.counter("filodb_queries_rejected").inc()
            raise QueryRejected(
                f"query rejected: {self.parallelism} running + {self.max_queued} queued"
            )

        def hop(kind: str, since_ns: int) -> None:
            seconds = (time.perf_counter_ns() - since_ns) / 1e9
            self._hops[kind].observe(seconds)
            if phases is not None:
                phases.add("queue", seconds)

        done_ns = []  # the worker's stamp as it finishes

        def _job():
            hop("queued", submitted_ns)
            with self._lock:
                self._in_flight += 1
                self.peak_in_flight = max(self.peak_in_flight, self._in_flight)
            try:
                return fn()
            finally:
                with self._lock:
                    self._in_flight -= 1
                self._slots.release()
                done_ns.append(time.perf_counter_ns())

        try:
            with span("sched:run"):
                submitted_ns = time.perf_counter_ns()
                fut = self._pool.submit(_job)
                return fut.result(timeout=deadline_s)
        except FutureTimeout:
            # the worker aborts at its next check_deadline(); stop waiting now
            if fut.cancel():
                # never started: _job's finally will not run — free the slot
                self._slots.release()
            REGISTRY.counter("filodb_queries_deadline_exceeded").inc()
            raise QueryDeadlineExceeded(
                f"query exceeded deadline: {deadline_s:.1f}s"
            ) from None
        finally:
            if done_ns:  # the caller is running again
                hop("handback", done_ns[0])

    def shutdown(self):
        self._pool.shutdown(wait=False, cancel_futures=True)
