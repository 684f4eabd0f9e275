"""Fault-tolerance layer for distributed scatter-gather execution
(reference analogs: ShardStatus ADT + ingestion-error damper treat shard
failure as first-class state; PromQlRemoteExec ships sttp retries; the
query circuit-breaker limits in filodb-defaults.conf).

Three cooperating pieces, all consulted by
:meth:`NonLeafExecPlan.execute_children` via :func:`dispatch_child`:

- :class:`RetryPolicy` — exponential backoff + deterministic jitter for
  remote child plans. Budgets derive from ``QueryContext.deadline_s``: a
  retry sequence never sleeps past the query deadline, and the dispatch
  layer itself waits for each attempt no longer than the endpoint's budget
  (:func:`_run_bounded`) — the one who waits bounds the wait, whatever the
  transport does with its own timeout. The budget is the remaining deadline,
  or an equal share of it while sibling replicas remain, so a silent
  replica costs one short attempt and the sibling still has time to answer.
- :class:`CircuitBreaker` / :class:`BreakerRegistry` — per-endpoint
  closed -> open -> half-open breaker with a failure-rate threshold over a
  sliding outcome window and a cooldown before half-open probing. State
  transitions are recorded in :mod:`filodb_tpu.metrics`.
- :func:`dispatch_child` — the one choke point child execution flows
  through. ``QueryContext.dispatcher`` (e.g. the seeded
  :class:`~filodb_tpu.testkit.FaultInjector`) wraps the raw call; the
  breaker + retry discipline layers on top for ``is_remote`` children, so
  injected faults exercise exactly the production retry/breaker path.

Error classification: an exception retries only when transport-shaped
(``ConnectionError``/``TimeoutError``/``OSError`` or a ``retryable=True``
attribute, e.g. UNAVAILABLE RemoteExecError); it counts against the
endpoint's breaker when retryable or marked ``endpoint_failure=True``.
Typed query errors (bad PromQL, limits) do neither — a bad query is not a
sick peer.
"""

from __future__ import annotations

import random
import threading
import time
from collections import deque
from concurrent import futures
from dataclasses import dataclass
from typing import Callable

from .exec.transformers import QueryDeadlineExceeded, QueryError

_STATE_CLOSED = "closed"
_STATE_OPEN = "open"
_STATE_HALF_OPEN = "half_open"


class CircuitOpenError(QueryError):
    """Dispatch refused: the endpoint's breaker is open (fail-fast). The
    HTTP edge maps this to 503 like other unavailability."""


class AttemptTimedOut(QueryError):
    """The endpoint gave no sign of life in the whole budget the dispatch
    layer had for it. That is peer-health evidence (unlike the transport's
    own DEADLINE_EXCEEDED, which reflects whatever budget the origin had
    left): it counts against the breaker and fails over to a sibling. Not
    retryable — the budget it would retry in is the one it just spent."""

    endpoint_failure = True


def is_retryable(exc: BaseException) -> bool:
    """Transport-shaped failures worth another attempt."""
    return isinstance(exc, (ConnectionError, TimeoutError, OSError)) or bool(
        getattr(exc, "retryable", False)
    )


def is_endpoint_failure(exc: BaseException) -> bool:
    """Failures that count against the endpoint's breaker (peer health),
    as opposed to query-shaped errors the peer answered correctly."""
    return is_retryable(exc) or bool(getattr(exc, "endpoint_failure", False))


# ---------------------------------------------------------------------------
# retry policy
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class RetryPolicy:
    """Exponential backoff + jitter, budgeted by the query deadline.

    ``seed`` makes the jitter sequence deterministic (chaos tests);
    ``sleep`` is injectable so tests can record/skip real waiting.
    """

    max_attempts: int = 3  # total tries, including the first
    base_backoff_s: float = 0.1
    multiplier: float = 2.0
    max_backoff_s: float = 2.0
    jitter: float = 0.5  # fraction of each backoff that is randomized
    seed: int | None = None
    sleep: Callable[[float], None] = time.sleep

    def backoff_s(self, retry_index: int, rng) -> float:
        b = min(self.base_backoff_s * self.multiplier**retry_index, self.max_backoff_s)
        if self.jitter <= 0:
            return b
        return b * (1.0 - self.jitter) + b * self.jitter * rng.random()

    def rng(self):
        return random.Random(self.seed) if self.seed is not None else random


DEFAULT_RETRY_POLICY = RetryPolicy()


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------


class CircuitBreaker:
    """closed -> open -> half-open breaker over a sliding outcome window.

    Opens when, among the last ``window`` outcomes (and at least
    ``min_calls`` of them), the failure rate reaches ``failure_rate``.
    After ``cooldown_s`` it admits up to ``half_open_max`` probe calls:
    a probe success re-closes, a probe failure re-opens (fresh cooldown).
    """

    def __init__(self, endpoint: str, window: int = 16, failure_rate: float = 0.5,
                 min_calls: int = 4, cooldown_s: float = 15.0,
                 half_open_max: int = 1, clock: Callable[[], float] = time.monotonic):
        self.endpoint = endpoint
        self.failure_rate = failure_rate
        self.min_calls = min_calls
        self.cooldown_s = cooldown_s
        self.half_open_max = half_open_max
        self._clock = clock
        self._outcomes: deque[bool] = deque(maxlen=window)
        self._state = _STATE_CLOSED
        self._opened_at = 0.0
        self._half_open_inflight = 0
        self._lock = threading.Lock()

    # -- state ------------------------------------------------------------

    def state(self) -> str:
        with self._lock:
            self._tick()
            return self._state

    def _tick(self) -> None:
        """Lock held: open -> half-open once the cooldown elapses."""
        if self._state == _STATE_OPEN and (
            self._clock() - self._opened_at >= self.cooldown_s
        ):
            self._transition(_STATE_HALF_OPEN)
            self._half_open_inflight = 0

    def _transition(self, state: str) -> None:
        if state == self._state:
            return
        from ..metrics import record_breaker_transition

        record_breaker_transition(self.endpoint, self._state, state)
        self._state = state

    # -- consultation ------------------------------------------------------

    def allow(self) -> bool:
        """May a call be dispatched now? Half-open admits only probes."""
        with self._lock:
            self._tick()
            if self._state == _STATE_CLOSED:
                return True
            if self._state == _STATE_HALF_OPEN and self._half_open_inflight < self.half_open_max:
                self._half_open_inflight += 1
                return True
            return False

    def record_neutral(self) -> None:
        """The call completed with a query-shaped error — the peer answered,
        but it is not success evidence either. Frees a half-open probe slot
        so a typed error during probing cannot wedge the breaker."""
        with self._lock:
            if self._state == _STATE_HALF_OPEN and self._half_open_inflight > 0:
                self._half_open_inflight -= 1

    def record_success(self) -> None:
        with self._lock:
            if self._state == _STATE_HALF_OPEN:
                self._transition(_STATE_CLOSED)
                self._outcomes.clear()
                self._half_open_inflight = 0
            else:
                self._outcomes.append(True)

    def record_failure(self) -> None:
        with self._lock:
            self._tick()
            if self._state == _STATE_HALF_OPEN:
                self._opened_at = self._clock()
                self._transition(_STATE_OPEN)
                self._half_open_inflight = 0
                return
            self._outcomes.append(False)
            n = len(self._outcomes)
            fails = n - sum(self._outcomes)
            if (
                self._state == _STATE_CLOSED
                and n >= self.min_calls
                and fails / n >= self.failure_rate
            ):
                self._opened_at = self._clock()
                self._transition(_STATE_OPEN)
                self._outcomes.clear()


class BreakerRegistry:
    """One breaker per endpoint, created on demand with shared settings."""

    def __init__(self, clock: Callable[[], float] = time.monotonic, **breaker_kw):
        self._clock = clock
        self._kw = breaker_kw
        self._breakers: dict[str, CircuitBreaker] = {}
        self._lock = threading.Lock()

    def breaker_for(self, endpoint: str) -> CircuitBreaker:
        with self._lock:
            br = self._breakers.get(endpoint)
            if br is None:
                br = CircuitBreaker(endpoint, clock=self._clock, **self._kw)
                self._breakers[endpoint] = br
            return br

    def states(self) -> dict[str, str]:
        with self._lock:
            breakers = list(self._breakers.values())
        return {b.endpoint: b.state() for b in breakers}

    def reset(self) -> None:
        with self._lock:
            self._breakers.clear()


# process-wide default registry (server deployments may build their own with
# tuned thresholds via PlannerParams.breakers)
GLOBAL_BREAKERS = BreakerRegistry()


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


def child_warning(child, exc: BaseException) -> dict:
    """Structured warning for a child lost under allow_partial_results."""
    w = {
        "plan": type(child).__name__,
        "args": child.args_str(),
        "error": f"{type(exc).__name__}: {exc}",
    }
    shard = getattr(child, "shard_num", None)
    if shard is not None:
        w["shard"] = int(shard)
    endpoint = getattr(child, "endpoint", None)
    if endpoint:
        w["endpoint"] = endpoint
    return w


def dispatch_child(child, ctx):
    """Execute one child plan under the fault-tolerance policy.

    The context's ``dispatcher`` (fault-injection hook) wraps the raw
    execution; remote children additionally consult their endpoint's
    circuit breaker and retry transient failures within the remaining
    deadline budget.
    """
    dispatcher = getattr(ctx, "dispatcher", None)
    if dispatcher is not None:
        base = dispatcher.dispatch
    else:
        def base(c, x):
            return c.execute(x)

    if not getattr(child, "is_remote", False):
        return base(child, ctx)
    endpoint = getattr(child, "endpoint", None) or type(child).__name__
    siblings = tuple(getattr(child, "sibling_endpoints", ()) or ())
    if siblings and hasattr(child, "with_endpoint"):
        return _dispatch_with_failover(child, ctx, base, endpoint, siblings)
    res = call_with_retries(lambda: base(child, ctx), ctx, endpoint,
                            ctx.remaining_deadline_s())
    _note_endpoint(ctx, endpoint)
    return res


def _note_endpoint(ctx, endpoint: str) -> None:
    """Record the serving endpoint on the query's observatory annotations so
    the querylog entry (and /api/v1/query_profile) shows who answered."""
    obs = getattr(ctx, "obs", None)
    if obs is None:
        return
    eps = obs.setdefault("endpoints", [])
    if endpoint not in eps:
        eps.append(endpoint)


def _dispatch_with_failover(child, ctx, base, endpoint, siblings):
    """Replica failover: a breaker-open or endpoint-failure result on one
    replica is a ROUTING signal — re-pin the leg to the next sibling replica
    (same plan, same shard subset) before allow_partial_results is even
    considered. Non-endpoint errors (real query errors) raise immediately:
    a sibling would answer the same way.

    Each candidate's budget is an equal share of what is left of the
    deadline among the candidates not yet tried (the last one gets all of
    it): a replica that never answers costs its share, not the query."""
    from ..metrics import record_replica_failover, record_replica_selection

    cands = (endpoint,) + tuple(s for s in siblings if s != endpoint)
    last_exc = None
    for i, ep in enumerate(cands):
        c = child if i == 0 else child.with_endpoint(ep)
        try:
            res = call_with_retries(lambda: base(c, ctx), ctx, ep,
                                    ctx.remaining_deadline_s() / (len(cands) - i))
        except CircuitOpenError as e:
            last_exc = e
            if i + 1 < len(cands):
                record_replica_failover(ep, "breaker_open")
                continue
            raise
        except Exception as e:  # noqa: BLE001 — classified below
            last_exc = e
            if is_endpoint_failure(e) and i + 1 < len(cands):
                record_replica_failover(ep, "endpoint_failure")
                continue
            raise
        record_replica_selection("primary" if i == 0 else "sibling")
        _note_endpoint(ctx, ep)
        return res
    raise last_exc


def _run_bounded(fn, budget_s: float, endpoint: str):
    """Run one attempt where it can be left behind — a daemon thread that
    sets a Future — and wait for it no longer than ``budget_s``. A transport
    whose call never returns (a gRPC stream wedged on a connection its peer
    was closing ignores both its timeout and ``cancel()``) then costs the
    caller its budget and the process nothing at exit. The thread-locals a
    plan node reads (active span, stats, phases) are re-bound in the thread,
    as ``execute_children`` does for its pool workers. An attempt left behind
    that answers later still writes its stats and warnings to the context:
    nobody reads its result, and QueryStats are best-effort across legs."""
    from ..metrics import (
        activate, activate_phases, activate_stats,
        current_phases, current_span, current_stats,
    )

    fut: futures.Future = futures.Future()
    sp, stats, phases = current_span(), current_stats(), current_phases()

    def attempt():
        try:
            with activate(sp), activate_stats(stats), activate_phases(phases):
                fut.set_result(fn())
        except BaseException as e:  # noqa: BLE001 — re-raised by the waiter
            fut.set_exception(e)

    threading.Thread(target=attempt, name="filodb-attempt", daemon=True).start()
    # not fut.result(timeout=): fn's own TimeoutError would read as ours
    futures.wait((fut,), timeout=budget_s)
    if not fut.done():
        raise AttemptTimedOut(
            f"no answer from endpoint {endpoint} within {budget_s:.1f}s")
    return fut.result()


def call_with_retries(fn, ctx, endpoint: str, budget_s: float):
    """Run ``fn`` with breaker consultation + budgeted backoff retries, all
    within ``budget_s`` from now (the endpoint's share of the deadline).

    Retry and breaker events annotate the active span (the dispatching merge
    node's — each ATTEMPT produces its own child span via the child's
    execute, so per-endpoint counters live one level up where they
    aggregate), making them visible in EXPLAIN ANALYZE output and the
    slow-query log."""
    from ..metrics import current_span, record_remote_retry

    policy: RetryPolicy = getattr(ctx, "retry_policy", None) or DEFAULT_RETRY_POLICY
    registry: BreakerRegistry = getattr(ctx, "breakers", None) or GLOBAL_BREAKERS
    breaker = registry.breaker_for(endpoint)
    sp = current_span()
    rng = policy.rng()
    give_up_at = time.monotonic() + budget_s
    attempt = 0
    while True:
        ctx.check_deadline()
        if not breaker.allow():
            if sp is not None:
                opens = sp.tags.setdefault("breaker_open", [])
                if endpoint not in opens:
                    opens.append(endpoint)
            raise CircuitOpenError(f"circuit breaker open for endpoint {endpoint}")
        state = breaker.state()
        if state != _STATE_CLOSED and sp is not None:
            sp.tags.setdefault("breaker_state", {})[endpoint] = state
        try:
            res = _run_bounded(fn, give_up_at - time.monotonic(), endpoint)
        except Exception as e:  # noqa: BLE001 — classified below
            if is_endpoint_failure(e):
                breaker.record_failure()
            else:
                # typed query error: the peer answered — release any
                # half-open probe slot without a state transition
                breaker.record_neutral()
            if isinstance(e, AttemptTimedOut) and ctx.remaining_deadline_s() <= 0:
                # the silent endpoint had the whole deadline: that is the
                # query's timeout, never a lost child a partial may absorb
                raise QueryDeadlineExceeded(
                    f"query exceeded deadline: {e}") from e
            if not is_retryable(e):
                raise
            attempt += 1
            if attempt >= policy.max_attempts:
                raise
            if breaker.state() == _STATE_OPEN:
                # this failure (or a sibling's) just opened the breaker:
                # surface the REAL transport error now rather than sleeping
                # into a CircuitOpenError that would mask it
                raise
            backoff = policy.backoff_s(attempt - 1, rng)
            if backoff >= give_up_at - time.monotonic():
                # sleeping would outlive the endpoint's budget: surface the
                # last transport error now instead of burning it
                raise
            record_remote_retry(endpoint)
            if sp is not None:
                retries = sp.tags.setdefault("retries", {})
                retries[endpoint] = retries.get(endpoint, 0) + 1
            policy.sleep(backoff)
            continue
        breaker.record_success()
        return res
