"""Query dispatch scheduler: cross-query micro-batching + per-tenant
admission control — the layer between the planner and the fused engine
(ROADMAP "cross-query batching + admission control for high-QPS serving";
Storyboard's workload-aware sharing of precomputed aggregate work across
queries, Tailwind's explicit dispatch/admission layer in front of the
accelerator — PAPERS.md).

Two cooperating pieces:

- :class:`DispatchScheduler` — a micro-batching dispatcher for
  ``FusedAggregateExec`` kernel launches. Concurrent fused queries hitting
  the SAME device-resident superblock with the same grid/epilogue signature
  (the coalescing key) collect for a short window (config
  ``query.batch_window_ms``) and launch as ONE batched kernel — jax.vmap
  over the per-query dynamics (window length, offset, q, group-by variant)
  on the existing fused programs (ops/aggregations.fused_batched_scalar /
  fused_batched_hist) — then the stacked ``[Q, G, J]`` partials fan back
  out to each waiting query. Identical dispatch specs dedup onto one lane
  (the single-flight discipline of filodb_tpu/singleflight.py applied at
  the lane level: N identical specs share one future, never N lanes), and
  identical FULL queries never reach this layer at all — the engine-level
  SingleFlight (coordinator.scheduler, ``coalesce_identical``) already
  shares one execution among them. Lanes group per window triple for
  executable stability, but a SEALING leader re-merges every still-open
  group that is compatible on everything else (``FusedRequest.merge_key``)
  into one mixed-window launch — the ops layer's u_map machinery routes
  each lane to its own window, bit-parity per lane — counted in
  ``filodb_batch_merged_windows_total{family}``. The first arrival for a key leads: it
  holds the window open (bounded by ``max_batch``), executes, and
  distributes; a batch-path failure falls back to per-lane unbatched
  execution so batching is strictly an optimization, never a correctness
  risk.

- :class:`AdmissionController` — per-tenant token-bucket rate +
  concurrency quotas (config ``query.tenant_quotas``, tenants resolved via
  :func:`filodb_tpu.metering.tenant_of_plan`) and a global queue-depth
  bound, consulted by the QueryEngine BEFORE execution. Over-quota queries
  shed with :class:`AdmissionRejected`, which the HTTP edge maps to
  429 + ``Retry-After`` (plus a structured warning in the error envelope)
  and the gRPC edge to an in-band typed error frame + retry-after call
  metadata. A shed REMOTE child carries ``endpoint_failure=True`` so
  sustained shedding opens the origin's circuit breaker for that peer
  (query/faults.py), and under ``allow_partial_results`` merge nodes
  degrade it exactly like a faulted child — structured warning, survivors
  served.

Tenant label cardinality is bounded by the same ``MAX_TENANT_PAIRS``
overflow-bucket cap the metering counters use
(:func:`filodb_tpu.metering.bounded_tenant_pair`).
"""

from __future__ import annotations

import math
import threading
import time
from collections import deque
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FutureTimeout
from dataclasses import dataclass
from typing import Any, Callable

from ..metrics import REGISTRY, span
from .exec.transformers import QueryDeadlineExceeded, QueryError

# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------


class AdmissionRejected(QueryError):
    """Query shed by admission control (over-quota tenant or a saturated
    global queue). HTTP: 429 + ``Retry-After: <retry_after_s>``; gRPC: the
    ``AdmissionRejected`` in-band error frame + ``x-filodb-retry-after``
    metadata.

    Peer-health classification (query/faults.py): NOT retryable within the
    same dispatch (retrying before ``retry_after_s`` would defeat the
    shed), but it IS endpoint-failure evidence — a peer shedding our
    scatter legs is overloaded, and sustained shedding should open its
    breaker so the origin backs off for the cooldown instead of hammering
    it."""

    retryable = False
    endpoint_failure = True

    def __init__(self, message: str, retry_after_s: float = 1.0,
                 ws: str = "unknown", ns: str = "unknown",
                 outcome: str = "shed_rate",
                 predicted_cost_s: float = 0.0):
        super().__init__(message)
        self.retry_after_s = float(retry_after_s)
        self.ws = ws
        self.ns = ns
        self.outcome = outcome
        self.predicted_cost_s = float(predicted_cost_s)

    def warning(self) -> dict:
        """The structured warning shape riding error envelopes and partial
        results (mirrors faults.child_warning)."""
        return {
            "reason": "admission_rejected",
            "outcome": self.outcome,
            "ws": self.ws,
            "ns": self.ns,
            "retry_after_s": round(self.retry_after_s, 3),
            "predicted_cost_s": round(self.predicted_cost_s, 6),
            "error": str(self),
        }


class TokenBucket:
    """Classic token bucket with an injectable clock (deterministic
    tests). ``rate`` tokens/second refill up to ``burst``; ``try_take``
    returns 0.0 on success or the seconds until enough tokens accrue.

    Tokens are unit-agnostic: admission runs its buckets in
    device-seconds (``try_take(predicted_cost_s)``), so an expensive
    query drains proportionally more than a cheap one. A cost above the
    bucket capacity is clamped TO the capacity — the request admits after
    a full drain-and-refill rather than starving forever, and the
    returned wait is therefore always an achievable drain time (the
    Retry-After contract: shed, wait the advertised seconds, admit).
    ``min_burst`` floors the capacity (1.0 = one legacy query token)."""

    def __init__(self, rate: float, burst: float,
                 clock: Callable[[], float] = time.monotonic,
                 min_burst: float = 1.0):
        self.rate = float(rate)
        self.burst = max(float(burst), float(min_burst))
        self._clock = clock
        self._tokens = self.burst
        self._last = clock()
        self._lock = threading.Lock()

    def _refill(self, now: float) -> None:
        if now > self._last:
            self._tokens = min(
                self.burst, self._tokens + (now - self._last) * self.rate
            )
            self._last = now

    def try_take(self, cost: float = 1.0) -> float:
        with self._lock:
            c = min(max(float(cost), 0.0), self.burst)
            now = self._clock()
            self._refill(now)
            # nanosecond-of-device-time tolerance: refill accumulates
            # float error at large clock values, and the Retry-After
            # contract (shed, wait the advertised seconds, admit) must
            # not fail by one ulp of (now - last) * rate
            if self._tokens >= c - 1e-9:
                self._tokens = max(self._tokens - c, 0.0)
                return 0.0
            if self.rate <= 0:
                return float("inf")
            return (c - self._tokens) / self.rate

    def balance(self) -> float:
        with self._lock:
            self._refill(self._clock())
            return self._tokens


@dataclass(frozen=True)
class TenantQuota:
    """Per-tenant admission quota. Buckets run in DEVICE-SECONDS (the
    cost model's currency): ``rate_device_s`` device-seconds/second
    refill up to ``burst_device_s``. Legacy query-count quotas
    (``rate``/``burst``) are still accepted and converted at bucket-build
    time via the cost model's flat prior — since the default per-query
    cost IS that prior, an unconfigured deployment's admission decisions
    are unchanged by the unit conversion. ``rate``/``rate_device_s`` <= 0
    disables the token bucket; ``max_concurrent`` <= 0 disables the
    concurrency cap."""

    rate: float = 0.0  # legacy: queries/second refill
    burst: float = 0.0  # legacy: capacity in queries; <= 0 -> max(rate, 1)
    max_concurrent: int = 0
    rate_device_s: float = 0.0  # device-seconds/second refill (preferred)
    burst_device_s: float = 0.0  # capacity in device-seconds

    @classmethod
    def from_config(cls, cfg: dict) -> "TenantQuota":
        return cls(
            rate=float(cfg.get("rate", 0.0) or 0.0),
            burst=float(cfg.get("burst", 0.0) or 0.0),
            max_concurrent=int(cfg.get("max_concurrent", 0) or 0),
            rate_device_s=float(cfg.get("rate_device_s", 0.0) or 0.0),
            burst_device_s=float(cfg.get("burst_device_s", 0.0) or 0.0),
        )

    def device_rate(self, prior_cost_s: float) -> float:
        """Refill rate in device-seconds/second (legacy queries/s × the
        family prior when no native device-second rate is configured)."""
        if self.rate_device_s > 0:
            return self.rate_device_s
        return self.rate * prior_cost_s

    def device_burst(self, prior_cost_s: float) -> float:
        if self.burst_device_s > 0:
            return self.burst_device_s
        if self.rate_device_s > 0:
            return max(self.rate_device_s, prior_cost_s)
        q_burst = self.burst if self.burst > 0 else max(self.rate, 1.0)
        return q_burst * prior_cost_s


class _TenantState:
    __slots__ = ("bucket", "quota", "in_flight", "shed")

    def __init__(self, quota: TenantQuota | None, clock,
                 prior_cost_s: float = 1.0):
        self.quota = quota
        self.bucket = None
        if quota is not None and (quota.rate > 0 or quota.rate_device_s > 0):
            self.bucket = TokenBucket(
                quota.device_rate(prior_cost_s),
                quota.device_burst(prior_cost_s), clock,
                # capacity floor = ONE prior-priced query, not one legacy
                # token: device-second bursts are fractions of 1.0
                min_burst=prior_cost_s,
            )
        self.in_flight = 0
        self.shed = 0


class AdmissionController:
    """Per-tenant token-bucket rate/concurrency quotas + a global
    queue-depth bound, in front of query execution.

    ``quotas`` maps ``"ws/ns"`` keys (or ``"*"`` for the default applied to
    every tenant without an explicit entry — including ``unknown``) to
    quota dicts ``{"rate", "burst", "max_concurrent"}``. ``max_queued``
    bounds admitted-and-unfinished queries process-wide (0 = unbounded).
    Shedding outcomes are counted in
    ``filodb_admission_total{outcome,ws,ns}`` with the metering module's
    overflow-bucket cardinality cap; per-tenant token balances and shed
    counts are inspectable at ``GET /debug/scheduler``."""

    def __init__(self, quotas: dict | None = None, max_queued: int = 0,
                 clock: Callable[[], float] = time.monotonic,
                 retry_after_default_s: float = 1.0,
                 prior_cost_s: float | None = None):
        from .costmodel import DEFAULT_PRIOR_COST_S

        self._quotas = {
            k: (q if isinstance(q, TenantQuota) else TenantQuota.from_config(q))
            for k, q in (quotas or {}).items()
        }
        self.max_queued = int(max_queued)
        self._clock = clock
        self.retry_after_default_s = float(retry_after_default_s)
        # the legacy-quota conversion rate AND the default price of a
        # query admitted without a prediction — one constant, so counting
        # queries and counting prior-priced device-seconds are identical
        self.prior_cost_s = max(
            float(prior_cost_s if prior_cost_s is not None
                  else DEFAULT_PRIOR_COST_S), 1e-6)
        self._states: dict[str, _TenantState] = {}
        self._in_flight = 0
        self._shed_total = 0
        self._lock = threading.Lock()

    def _quota_for(self, key: str) -> TenantQuota | None:
        return self._quotas.get(key) or self._quotas.get("*")

    def _state(self, key: str) -> _TenantState:
        st = self._states.get(key)
        if st is None:
            st = self._states[key] = _TenantState(
                self._quota_for(key), self._clock, self.prior_cost_s
            )
        return st

    def _count(self, outcome: str, ws: str, ns: str) -> None:
        REGISTRY.counter(
            "filodb_admission", outcome=outcome, ws=ws, ns=ns
        ).inc()

    def admit(self, ws: str, ns: str, cost_s: float | None = None):
        """Admit or shed one query for tenant (ws, ns), draining the
        tenant's device-second bucket by ``cost_s`` (the cost model's
        prediction; the flat prior when the caller has none). Returns a
        context manager holding the tenant + global concurrency slots;
        raises :class:`AdmissionRejected` with the bucket's ACTUAL
        predicted drain time as ``Retry-After`` when the query must
        shed."""
        from ..metering import bounded_tenant_pair

        cost = (float(cost_s) if cost_s is not None and cost_s > 0
                else self.prior_cost_s)
        ws, ns = bounded_tenant_pair(ws, ns)
        key = f"{ws}/{ns}"
        with self._lock:
            st = self._state(key)
            quota = st.quota
            if (quota is not None and quota.max_concurrent > 0
                    and st.in_flight >= quota.max_concurrent):
                st.shed += 1
                self._shed_total += 1
                self._count("shed_concurrency", ws, ns)
                raise AdmissionRejected(
                    f"tenant {key} at max_concurrent="
                    f"{quota.max_concurrent}",
                    retry_after_s=self.retry_after_default_s,
                    ws=ws, ns=ns, outcome="shed_concurrency",
                )
            if self.max_queued > 0 and self._in_flight >= self.max_queued:
                st.shed += 1
                self._shed_total += 1
                self._count("shed_queue", ws, ns)
                raise AdmissionRejected(
                    f"query queue depth {self._in_flight} at bound "
                    f"{self.max_queued}",
                    retry_after_s=self.retry_after_default_s,
                    ws=ws, ns=ns, outcome="shed_queue",
                )
            if st.bucket is not None:
                charge = cost
                if quota is not None and quota.rate_device_s <= 0:
                    # legacy query-count quota: never charge LESS than one
                    # prior-priced query — the operator said "N queries/s"
                    # and a swarm of model-priced cheap queries must not
                    # turn that into thousands/s; an expensive query still
                    # drains proportionally MORE than one
                    charge = max(cost, self.prior_cost_s)
                wait_s = st.bucket.try_take(charge)
                if wait_s > 0:
                    st.shed += 1
                    self._shed_total += 1
                    self._count("shed_rate", ws, ns)
                    raise AdmissionRejected(
                        f"tenant {key} over device-second quota "
                        f"({st.bucket.rate:g} dev-s/s; query predicted "
                        f"{cost:g} dev-s)",
                        # the bucket's computed drain time IS the hint —
                        # waiting it out admits by construction (regression
                        # tested in tests/test_costmodel.py)
                        retry_after_s=min(
                            wait_s, 60.0
                        ) if wait_s != float("inf")
                        else self.retry_after_default_s,
                        ws=ws, ns=ns, outcome="shed_rate",
                        predicted_cost_s=cost,
                    )
            st.in_flight += 1
            self._in_flight += 1
        self._count("admitted", ws, ns)
        return _Admitted(self, key)

    def _release(self, key: str) -> None:
        with self._lock:
            st = self._states.get(key)
            if st is not None and st.in_flight > 0:
                st.in_flight -= 1
            self._in_flight = max(0, self._in_flight - 1)

    def snapshot(self) -> dict:
        """The /debug/scheduler rendering: global depth + per-tenant token
        balances, in-flight counts and shed totals."""
        with self._lock:
            tenants = {
                key: {
                    "in_flight": st.in_flight,
                    "shed": st.shed,
                    "tokens": (round(st.bucket.balance(), 3)
                               if st.bucket is not None else None),
                    "rate": st.quota.rate if st.quota else None,
                    "rate_device_s": (round(st.bucket.rate, 6)
                                      if st.bucket is not None else None),
                    "burst_device_s": (round(st.bucket.burst, 6)
                                       if st.bucket is not None else None),
                    "max_concurrent": (st.quota.max_concurrent
                                       if st.quota else None),
                }
                for key, st in self._states.items()
            }
            return {
                "in_flight": self._in_flight,
                "max_queued": self.max_queued,
                "shed_total": self._shed_total,
                "unit": "device_seconds",
                "prior_cost_s": self.prior_cost_s,
                "tenants": tenants,
            }


class _Admitted:
    """Held concurrency slot; releases on exit (success or failure)."""

    __slots__ = ("_ctl", "_key")

    def __init__(self, ctl: AdmissionController, key: str):
        self._ctl = ctl
        self._key = key

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._ctl._release(self._key)
        return False


# ---------------------------------------------------------------------------
# per-key recurrence ring (standing-query promotion feed)
# ---------------------------------------------------------------------------


class KeyStatsRing:
    """Bounded per-key recurrence/age ring over fused-dispatch coalescing
    keys. The scheduler's only per-key state used to be the OPEN batch
    group, dropped the moment the group sealed — recurrence (the signal
    that millions of users watch the SAME dashboard) was thrown away every
    batch window. The ring RETAINS it: one LRU-bounded entry per
    normalized key with a cumulative count, first/last-seen wall clocks, a
    short deque of recent observation times (the promotion-burst window)
    and the latest descriptor (promql, grid shape, live-edge lag) the
    standing-query promoter needs to re-register the query
    (standing/registry.py), beside the FIRST one: whether the end has moved
    since tells a range that follows the clock from one that stands still.
    Observed on EVERY fused dispatch — batching enabled or not — so
    promotion works on latency-critical deployments that keep
    ``batch_window_ms`` at 0. Exposed at ``/debug/standing``
    alongside the promoted/demoted registry state."""

    RECENT_MAX = 32  # per-entry burst window (>= any sane promote_min_count)

    __slots__ = ("max_entries", "_entries", "_lock", "_clock")

    def __init__(self, max_entries: int = 512,
                 clock: Callable[[], float] = time.time):
        self.max_entries = max(int(max_entries), 1)
        self._entries: dict[Any, dict] = {}  # insertion-ordered (LRU)
        self._lock = threading.Lock()
        self._clock = clock

    def observe(self, key, desc: dict | None = None) -> None:
        """Count one recurrence of ``key``; ``desc`` (latest wins) carries
        whatever the promoter needs to act on the key."""
        now = self._clock()
        with self._lock:
            e = self._entries.pop(key, None)
            if e is None:
                e = {
                    "count": 0,
                    "first_s": now,
                    "recent": deque(maxlen=self.RECENT_MAX),
                    "desc": None,
                    "first_desc": None,
                }
            e["count"] += 1
            e["last_s"] = now
            e["recent"].append(now)
            if desc is not None:
                e["desc"] = desc
                if e["first_desc"] is None:
                    e["first_desc"] = desc
            self._entries[key] = e  # move-to-back = most recent
            while len(self._entries) > self.max_entries:
                self._entries.pop(next(iter(self._entries)))

    @staticmethod
    def _copy(e: dict) -> dict:
        # ``recent`` rendered as an immutable tuple: observe() keeps
        # appending to the live deque from query threads, and iterating a
        # deque mid-mutation raises — callers only ever see copies
        return {
            "count": e["count"],
            "first_s": e["first_s"],
            "last_s": e["last_s"],
            "recent": tuple(e["recent"]),
            "desc": e.get("desc"),
            "first_desc": e.get("first_desc"),
        }

    def entries(self) -> list[tuple[Any, dict]]:
        """(key, entry-copy) pairs, most-recently-seen last. Copies taken
        under the ring's lock — safe to iterate while observe() keeps
        mutating the live entries."""
        with self._lock:
            return [(k, self._copy(e)) for k, e in self._entries.items()]

    def get(self, key) -> dict | None:
        with self._lock:
            e = self._entries.get(key)
            return self._copy(e) if e is not None else None

    def snapshot(self, limit: int = 64) -> list[dict]:
        """The /debug/standing rendering: newest-first, descriptors
        included, recent-burst deques rendered as their span."""
        now = self._clock()
        out = []
        items = self.entries()
        for key, e in reversed(items[-limit:] if limit else items):
            recent = e["recent"]
            out.append({
                "key": repr(key),
                "count": e["count"],
                "age_s": round(now - e["first_s"], 3),
                "idle_s": round(now - e["last_s"], 3),
                "recent": len(recent),
                "recent_span_s": (
                    round(recent[-1] - recent[0], 3) if len(recent) > 1
                    else 0.0
                ),
                "desc": e.get("desc"),
            })
        return out

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


# ---------------------------------------------------------------------------
# micro-batching dispatch
# ---------------------------------------------------------------------------


@dataclass
class FusedRequest:
    """One fused-kernel dispatch wish: everything the batched program needs
    from this query, plus the unbatched fallback. Built by
    ``FusedAggregateExec`` AFTER superblock resolution and group-id
    memoization, so batching composes with (and never bypasses) limits,
    stats accounting and cache maintenance — only the kernel launch itself
    is shared."""

    block: Any  # the (super)block object — group identity AND data source
    func: str
    kind: str  # "agg" | "topk" | "quantile" | "hist"
    epilogue: tuple  # scalar static epilogue; () for hist
    gids_dev: Any  # [S_pad] device group ids (trash group = G)
    G: int  # this lane's real group count
    qv: float  # quantile q / hist_quantile q; 0.0 otherwise
    params: Any  # RangeParams (start/step/window are the vmapped dynamics)
    j_pad: int
    is_counter: bool
    is_delta: bool
    mesh: Any = None
    mesh_desc: tuple | None = None
    les_dev: Any = None  # hist bucket bounds (device)
    hist_q: bool = False  # hist lane wants the quantile epilogue
    run_single: Callable[[], Any] = None
    timeout_s: float = 60.0
    # the cost model's device-second prediction for the owning query
    # (0.0 = unpriced): feeds the scheduler's decayed queue-cost
    # accumulator, which drives the adaptive batch window
    predicted_cost_s: float = 0.0
    # stamped by the executing leader (DispatchScheduler._execute) BEFORE
    # the future resolves: the group's actual kernel-launch wall seconds.
    # The waiting caller subtracts it from its total wait to split queue
    # time from launch time in the query-phase decomposition
    # (FusedAggregateExec._dispatch_fused). Batched lanes all carry the
    # SHARED launch duration (the launch is indivisible); a coalesced
    # duplicate lane's own request object stays None.
    exec_seconds: float | None = None
    # stamped alongside exec_seconds from the leader thread's
    # obs.kernels.last_dispatch(): the executable that actually served this
    # lane (batched lanes share ONE executable by construction) and
    # whether that launch compiled — the engine folds both into the
    # query's cost record (executable_key / compile_miss)
    executable_key: str | None = None
    compile_miss: bool | None = None

    def family(self) -> str:
        return self.kind

    def g_bucket(self) -> int:
        """Power-of-two bucket of this lane's group count. Part of the
        coalescing key: the batched program's static group axis is the
        group MAX, so one high-cardinality ``by (instance)`` lane would
        poison every cheap ``sum()`` lane in its group with a [G_max, J]
        output — bucketing keeps heavy and light group-bys in separate
        batches (and gives the compiler a handful of stable group widths
        instead of one per distinct G). The SAME rounding the batched
        kernels apply to their lane/window axes — one definition, or keys
        and kernel widths drift."""
        from ..ops.aggregations import _pow2

        return _pow2(self.G)

    def group_key(self) -> tuple:
        """Coalescing key: block identity + grid signature + epilogue
        family statics. Lanes in one group share the block OBJECT (verified
        again at execute time — ``id`` alone could alias across GC), the
        grid triple, the kernel variant selectors and the epilogue's static
        shape; per-query q and group-by variant ride the batch axis.

        The grid triple (start/step/window) is deliberately IN the key:
        the batched programs support mixed windows per launch (the u_map
        machinery in ops/aggregations), but live group compositions
        fluctuate with load, and every distinct lane->window pattern is a
        distinct XLA executable — pinning one window per group collapses
        the static composition space to a handful of pow2 widths, which is
        what keeps steady-state serving out of the compiler. Queries with
        near-miss windows still share everything that matters — the staged
        superblock (range alignment, planner._fused_raw_range) and each
        other's group-by epilogues within their window's group — and the
        SEALING leader re-merges compatible window-groups (merge_key) into
        one mixed-window launch, so one batch still serves them all; the
        pow2 lane/window padding keeps the merged composition space
        bounded."""
        p = self.params
        return (
            id(self.block), self.func, self.kind, self.epilogue, self.j_pad,
            p.start_ms, p.step_ms, p.window_ms,
            self.g_bucket(), self.is_counter, self.is_delta, self.hist_q,
            self.mesh_desc,
        )

    def merge_key(self) -> tuple:
        """Window-group compatibility key: group_key MINUS the grid triple.
        Groups agreeing on everything but (start, step, window) run the
        SAME batched program shape with the lane->unique-window map doing
        the routing (ops/aggregations._unique_windows) — the sealing
        leader absorbs them into one mixed-window launch, bit-parity per
        lane (each lane's subgraph is the exact single-query computation
        over its own window)."""
        return (
            id(self.block), self.func, self.kind, self.epilogue, self.j_pad,
            self.g_bucket(), self.is_counter, self.is_delta, self.hist_q,
            self.mesh_desc,
        )

    def lane_key(self) -> tuple:
        """Dedup key WITHIN a group: requests agreeing on every per-query
        dynamic share one lane (and one kernel output slice) — the
        single-flight discipline at lane granularity."""
        p = self.params
        return (p.start_ms, p.step_ms, p.num_steps, p.window_ms,
                float(self.qv), id(self.gids_dev), self.G)

    def take(self, stacked, i: int):
        """Lane ``i``'s view of the stacked batch output, shaped exactly
        like ``run_single``'s return."""
        if self.kind == "topk":
            return stacked[0][i], stacked[1][i]
        return stacked[i][: self.G]


def _run_batch(requests: list[FusedRequest]) -> list:
    """ONE batched kernel launch for the whole group; returns per-request
    outputs in run_single's shape."""
    from ..ops import aggregations as AGG

    r0 = requests[0]
    for r in requests[1:]:
        if r.block is not r0.block:
            # id-reuse alias after GC, or a superblock swap mid-window:
            # batching different blocks would serve wrong data — bail to
            # the per-lane fallback
            raise RuntimeError("batch group spans distinct blocks")
    # canonical lane order: a recurring batch composition must build ONE
    # stacked-input memo entry (ops/aggregations._batched_stacks) no matter
    # which query happened to arrive first this round
    order = sorted(range(len(requests)),
                   key=lambda i: requests[i].lane_key())
    # static group axis = the group's (shared) pow2 bucket, not the exact
    # max: stable compile widths; lanes slice their own [:G_i]
    g_max = max(r.g_bucket() for r in requests)
    lanes = [(requests[i].gids_dev, requests[i].qv, requests[i].params)
             for i in order]
    if r0.kind == "hist":
        out = AGG.fused_batched_hist(
            r0.func, r0.block, lanes, g_max, r0.j_pad, r0.les_dev,
            r0.hist_q, r0.is_delta, mesh=r0.mesh,
        )
    else:
        out = AGG.fused_batched_scalar(
            r0.func, r0.epilogue, r0.block, lanes, g_max, r0.j_pad,
            r0.is_counter, r0.is_delta, mesh=r0.mesh,
        )
    results: list = [None] * len(requests)
    for pos, i in enumerate(order):
        results[i] = requests[i].take(out, pos)
    return results


class _Group:
    # a group is "sealed" exactly when it is no longer in the scheduler's
    # _open table (removed under the lock) — joins and seal can never race.
    # ``stolen`` marks a group absorbed into another leader's mixed-window
    # batch (set under the same lock): its own leader must NOT execute —
    # its lanes' futures are settled by the absorbing leader.
    __slots__ = ("lanes", "closed", "last_join", "mkey", "stolen")

    def __init__(self, mkey: tuple = ()):
        self.lanes: dict[tuple, tuple[FusedRequest, Future]] = {}
        self.closed = threading.Event()
        self.last_join = time.monotonic()
        self.mkey = mkey
        self.stolen = False


class DispatchScheduler:
    """Micro-batching dispatcher (see module docstring).

    ``window_ms`` is the collection window the group leader holds open
    (0 = batching disabled: every dispatch runs unbatched, byte-identical
    to the pre-scheduler behavior). ``max_batch`` closes a group early.
    ``waiter`` is injectable for deterministic tests: it receives the
    group's close event and the window seconds and returns when the window
    ends (default: ``event.wait(window_s)``).

    **Adaptive window** (``window_cap_ms`` > ``window_ms`` > 0): the
    effective window tracks the decayed sum of predicted device-seconds
    recently submitted (``FusedRequest.predicted_cost_s``) — it widens
    toward the cap when predicted queue cost is high (batching pays) and
    collapses toward zero when the pipe is idle (latency wins; a lone
    query dispatches immediately). ``load_ref_cost_s`` is the queue cost
    that saturates the window at its cap. Without a cap the configured
    window is a constant, exactly the pre-adaptive behavior.

    **Pre-warm**: a QueryEngine registers a prewarmer closure; each
    ``prewarm_tick`` scans the recurrence ring for keys hot enough to be
    worth compiling ahead of demand (``prewarm_min_count`` observations;
    ANY live recompile-storm annotation from the kernel registry lowers
    the bar to 1 — shape churn means cold executables are about to be
    hot) and runs each once in the background, off the serving path, so
    the first real dispatch finds a warm jit cache."""

    def __init__(self, window_ms: float = 0.0, max_batch: int = 32,
                 waiter: Callable[[threading.Event, float], Any] | None = None,
                 key_ring_max: int = 512, window_cap_ms: float = 0.0,
                 load_ref_cost_s: float = 0.25,
                 prior_cost_s: float | None = None,
                 prewarm_min_count: int = 3,
                 clock: Callable[[], float] = time.monotonic):
        from .costmodel import DEFAULT_PRIOR_COST_S

        self.base_window_s = max(float(window_ms), 0.0) / 1e3
        self.window_cap_s = max(float(window_cap_ms), 0.0) / 1e3
        self.adaptive = self.window_cap_s > self.base_window_s > 0
        self.load_ref_cost_s = max(float(load_ref_cost_s), 1e-6)
        self.prior_cost_s = max(
            float(prior_cost_s if prior_cost_s is not None
                  else DEFAULT_PRIOR_COST_S), 1e-6)
        self.max_batch = max(int(max_batch), 1)
        self._waiter = waiter
        self._open: dict[tuple, _Group] = {}
        self._lock = threading.Lock()
        self._queued = 0
        # decayed predicted-queue-cost accumulator (device-seconds within
        # the last ~tau): its own lock so the window property never nests
        # under the group lock
        self._clock = clock
        self._load_lock = threading.Lock()
        self._load_tau_s = 2.0
        self._load_cost_s = 0.0
        self._load_stamp = clock()
        # pre-warm state: engine-registered executor + once-per-key memo
        self._prewarm_exec: Callable[[dict], Any] | None = None
        self._prewarmed: dict = {}
        self.prewarm_min_count = max(int(prewarm_min_count), 1)
        # per-key recurrence/age ring (standing-query promotion feed):
        # retained across batch close, observed on every fused dispatch
        # whether batching is enabled or not (window_ms 0 keeps the ring
        # alive with batching off)
        self.key_ring = KeyStatsRing(key_ring_max)
        # cumulative introspection counters (/debug/scheduler); the
        # Prometheus families are the operator-facing copies
        self.stats = {
            "queries": 0, "batched": 0, "solo": 0, "fallback": 0,
            "coalesced": 0, "dispatches": 0, "merged_windows": 0,
            "prewarmed": 0,
        }

    def observe_key(self, key, desc: dict | None = None) -> None:
        """Record one recurrence of a fused-dispatch key in the retained
        ring (called by FusedAggregateExec for every fused dispatch — the
        batching path and the plain unbatched path alike)."""
        self.key_ring.observe(key, desc)

    @property
    def enabled(self) -> bool:
        return self.base_window_s > 0

    @property
    def window_s(self) -> float:
        """The EFFECTIVE collection window: the configured constant, or —
        adaptive mode — the cap scaled by how loaded the queue looks
        (decayed predicted cost / ``load_ref_cost_s``, clamped to 1)."""
        if not self.adaptive:
            return self.base_window_s
        frac = min(self._load() / self.load_ref_cost_s, 1.0)
        return self.window_cap_s * frac

    def _load(self) -> float:
        """Decayed predicted queue cost (device-seconds), read-side."""
        with self._load_lock:
            dt = self._clock() - self._load_stamp
            decay = math.exp(-dt / self._load_tau_s) if dt > 0 else 1.0
            return self._load_cost_s * decay

    def _note_load(self, cost_s: float) -> None:
        with self._load_lock:
            now = self._clock()
            dt = now - self._load_stamp
            if dt > 0:
                self._load_cost_s *= math.exp(-dt / self._load_tau_s)
                self._load_stamp = now
            self._load_cost_s += max(float(cost_s), 0.0)

    # -- executable pre-warm ------------------------------------------------

    def register_prewarmer(self, fn: Callable[[dict], Any]) -> None:
        """Install the closure that traces+compiles one recurrence-ring
        descriptor off the serving path. First registration wins: several
        engines can share one scheduler, and the primary serving engine
        (constructed first) is the one whose executables matter."""
        if self._prewarm_exec is None:
            self._prewarm_exec = fn

    def prewarm_tick(self, limit: int = 2, storms: dict | None = None) -> list:
        """One background pre-warm pass: pick up to ``limit`` ring keys
        that look about-to-be-hot and run each through the registered
        executor once. ``storms`` (kernel-registry recompile-storm
        annotations; fetched live when None) lower the recurrence bar to
        a single observation — when shapes are churning, every key's
        executable is suspect. Returns the keys warmed this tick."""
        if self._prewarm_exec is None:
            return []
        if storms is None:
            from ..obs.kernels import KERNELS

            storms = KERNELS.storm_annotations()
        min_count = 1 if storms else self.prewarm_min_count
        picks = []
        for key, e in self.key_ring.entries():
            if key in self._prewarmed or e["count"] < min_count:
                continue
            desc = e.get("desc")
            if not desc or not desc.get("promql"):
                continue
            picks.append((key, desc))
            if len(picks) >= max(int(limit), 1):
                break
        warmed = []
        for key, desc in picks:
            self._prewarmed[key] = True
            while len(self._prewarmed) > 4 * self.key_ring.max_entries:
                self._prewarmed.pop(next(iter(self._prewarmed)))
            # a root span: the key's whole query, on no request's trace
            outcome = "ok"
            with span("prewarm:key", promql=desc["promql"]) as sp:
                try:
                    self._prewarm_exec(desc)
                except Exception:  # noqa: BLE001 — pre-warm is advisory
                    outcome = "error"
                sp.tags["outcome"] = outcome
            REGISTRY.histogram("filodb_prewarm_seconds").observe(sp.seconds)
            REGISTRY.counter("filodb_prewarm", outcome=outcome).inc()
            if outcome == "ok":
                self.stats["prewarmed"] += 1
                warmed.append(key)
        return warmed

    def dispatch(self, request: FusedRequest):
        """Submit one fused dispatch; returns its kernel output (leader
        executes for the whole group, followers share)."""
        if not self.enabled:
            return request.run_single()
        # feed the adaptive window's queue-cost signal (unpriced requests
        # count at the flat prior, so load tracks arrival rate even before
        # the cost model has evidence)
        self._note_load(request.predicted_cost_s
                        if request.predicted_cost_s > 0
                        else self.prior_cost_s)
        fam = request.family()
        key = request.group_key()
        lane = request.lane_key()
        with self._lock:
            self.stats["queries"] += 1
            group = self._open.get(key)
            leader = group is None
            if leader:
                group = _Group(mkey=request.merge_key())
                self._open[key] = group
            have = group.lanes.get(lane)
            group.last_join = time.monotonic()
            if have is None:
                fut = Future()
                group.lanes[lane] = (request, fut)
                self._queued += 1
            else:
                fut = have[1]
                self.stats["coalesced"] += 1
            if len(group.lanes) >= self.max_batch:
                group.closed.set()
        REGISTRY.counter("filodb_batch_queries", family=fam).inc()
        REGISTRY.gauge("filodb_batch_queue_depth").set(float(self._queued))
        from ..metrics import current_span

        sp = current_span()
        if sp is not None:
            sp.tags["batch_role"] = "leader" if leader else "follower"
        if leader:
            if self._waiter is not None:
                self._waiter(group.closed, self.window_s)
            else:
                self._collect(group)
            merged = 0
            with self._lock:
                if group.stolen:
                    # a compatible window-group's leader absorbed this
                    # group into its mixed-window batch while we waited —
                    # it owns our lanes' futures now; just await ours
                    lanes = None
                else:
                    if self._open.get(key) is group:
                        del self._open[key]
                    lanes = list(group.lanes.values())
                    # stickier composition: absorb still-open groups that
                    # agree on everything but the window triple
                    # (merge_key) into THIS launch — the batched programs'
                    # u_map machinery routes each lane to its own window,
                    # bit-parity per lane. Those groups' waiting clients
                    # get answered by this (earlier) dispatch. max_batch
                    # bounds the MERGED launch too: it caps unrolled
                    # program width and stacked-output HBM, and absorbing
                    # past it would rebuild exactly the oversized
                    # executables the bound exists to prevent.
                    for k2 in [k for k, g in self._open.items()
                               if g.mkey == group.mkey]:
                        g2 = self._open[k2]
                        if len(lanes) + len(g2.lanes) > self.max_batch:
                            continue
                        del self._open[k2]
                        g2.stolen = True
                        g2.closed.set()
                        lanes.extend(g2.lanes.values())
                        merged += 1
                    self._queued -= len(lanes)
                    self.stats["merged_windows"] += merged
            if lanes is not None:
                if merged:
                    REGISTRY.counter(
                        "filodb_batch_merged_windows", family=fam
                    ).inc(merged)
                REGISTRY.gauge("filodb_batch_queue_depth").set(
                    float(self._queued)
                )
                self._execute(fam, lanes)
        try:
            return fut.result(timeout=max(request.timeout_s, 0.001))
        except FutureTimeout:
            raise QueryDeadlineExceeded(
                f"query exceeded deadline: {request.timeout_s:.1f}s waiting "
                "on batched dispatch"
            ) from None

    def _collect(self, group: _Group) -> None:
        """Leader-side collection: hold the window open until it elapses,
        the group hits max_batch (closed event), or joins go QUIET — no new
        lane for a quarter-window. The quiescence close is what keeps the
        window from being a flat latency tax: after a shared batch
        completes, its clients resubmit within milliseconds of each other,
        so the next round's group fills almost at once and dispatches
        immediately instead of idling out the rest of the window; a
        sporadic lone query likewise waits only the gap, not the window."""
        # capture the effective window ONCE: in adaptive mode the property
        # moves with load, and a leader must hold a consistent deadline
        w = self.window_s
        deadline = time.monotonic() + w
        gap = w / 4
        while True:
            now = time.monotonic()
            if group.closed.is_set() or now >= deadline:
                return
            idle = now - group.last_join
            if idle >= gap:
                return
            group.closed.wait(min(deadline - now, gap - idle))

    @staticmethod
    def _stamp_executable(reqs) -> None:
        """Copy the leader thread's last-dispatch identity (the executable
        registry's thread-local capture) onto the lane request(s) BEFORE
        their futures resolve — the waiting engines' threads never saw the
        launch, so the key must ride the request like exec_seconds."""
        from ..obs.kernels import KERNELS

        info = KERNELS.last_dispatch()
        if not info:
            return
        for req in reqs:
            req.executable_key = info.get("executable_key")
            req.compile_miss = info.get("compile_miss")

    def _execute(self, fam: str, lanes: list) -> None:
        """Leader-side group execution: one batched launch for Q>1 lanes,
        the plain unbatched dispatch for a solo group, per-lane unbatched
        fallback if the batched path fails."""
        if len(lanes) == 1:
            # solo group: the plain unbatched dispatch, errors propagated
            # as-is (re-running a deterministic failure would double the
            # device work exactly when the device is least healthy)
            outcome = "solo"
            req, fut = lanes[0]
            t0 = time.perf_counter()
            try:
                out = req.run_single()
                req.exec_seconds = time.perf_counter() - t0
                self._stamp_executable((req,))
                fut.set_result(out)
            except Exception as e:  # noqa: BLE001 — delivered to the caller
                req.exec_seconds = time.perf_counter() - t0
                fut.set_exception(e)
        else:
            outcome = "batched"
            results = None
            t0 = time.perf_counter()
            try:
                results = _run_batch([req for req, _ in lanes])
            except QueryError as e:
                # typed query errors (limits) are real answers — propagate
                for _, fut in lanes:
                    fut.set_exception(e)
                return
            except Exception:  # noqa: BLE001 — batching must not lose queries
                outcome = "fallback"
            if results is None:
                for req, fut in lanes:
                    t1 = time.perf_counter()
                    try:
                        out = req.run_single()
                        req.exec_seconds = time.perf_counter() - t1
                        self._stamp_executable((req,))
                        fut.set_result(out)
                    except Exception as e:  # noqa: BLE001
                        req.exec_seconds = time.perf_counter() - t1
                        fut.set_exception(e)
            else:
                # exec_seconds stamped BEFORE the futures resolve so a
                # woken waiter always reads its final value; every lane
                # carries the shared (indivisible) launch duration
                batch_s = time.perf_counter() - t0
                for req, _ in lanes:
                    req.exec_seconds = batch_s
                self._stamp_executable([req for req, _ in lanes])
                for (_, fut), res in zip(lanes, results):
                    fut.set_result(res)
        with self._lock:
            self.stats[outcome] += 1
            self.stats["dispatches"] += 1
        REGISTRY.counter(
            "filodb_batch_dispatches", family=fam, outcome=outcome
        ).inc()

    def snapshot(self) -> dict:
        """The /debug/scheduler rendering: window config, live queue state
        and cumulative batching outcomes."""
        # the window property takes the load lock — read it OUTSIDE the
        # group lock (neither is reentrant)
        eff_ms = self.window_s * 1e3
        load = self._load()
        with self._lock:
            out = {
                "window_ms": eff_ms,
                "base_window_ms": self.base_window_s * 1e3,
                "window_cap_ms": self.window_cap_s * 1e3,
                "adaptive": self.adaptive,
                "load_cost_s": round(load, 6),
                "max_batch": self.max_batch,
                "open_groups": len(self._open),
                "queued_lanes": self._queued,
                **{k: v for k, v in self.stats.items()},
            }
        out["standing_keys"] = len(self.key_ring)
        return out
