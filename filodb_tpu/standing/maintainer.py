"""Standing-query maintainer: delta-refreshed dashboards + recording rules.

The engine between the dispatch scheduler and the fused engine (ROADMAP
"standing-query engine: delta-maintained dashboards at fan-out scale").
:class:`StandingEngine` owns:

- **promotion** — a promoter scans the dispatch scheduler's retained
  per-key recurrence ring (:class:`~filodb_tpu.query.scheduler.KeyStatsRing`)
  and promotes hot live-edge keys into registered standing queries, with
  hysteresis: promotion needs a BURST (``promote_min_count`` recurrences
  inside ``promote_window_s``) from an end that FOLLOWS the clock (within
  ``promote_live_lag_ms`` of it, and seen to advance since the key was
  first sighted), demotion needs a long idle
  (``demote_idle_s``) with zero subscribers — the two thresholds never
  chase each other. Nondecomposable epilogues are remembered as demoted so
  the promoter never flaps on them.

- **delta maintenance** — each registered query keeps its ``[G, J]``
  aggregation partials warm. A refresh classifies what ingest did since the
  partials were computed via the shard effect log
  (``ingest_effects_interval_since``): disjoint → serve retained with ZERO
  dispatches; a live-edge append → re-dispatch ONLY the step suffix whose
  windows reach the appended interval, through the same fused program over
  the same superblock (which extends in place under the append — PR 6),
  and splice (``ops/aggregations.splice_partials``). The delta path is
  bit-equal to full re-evaluation (the per-step independence argument in
  ops/aggregations.py, pinned by tests/test_standing.py across
  regular/jitter/holes grids and under concurrent extension). Epilogues
  that cannot splice per step (topk, quantile, fused histogram_quantile)
  demote to full re-dispatch, counted
  ``filodb_fused_fallback_total{reason="standing_nondecomposable"}``.

- **push fan-out** — every refresh renders its payload ONCE and the
  :class:`~filodb_tpu.standing.hub.SubscriptionHub` fans the same bytes to
  every SSE subscriber (api/http.py ``/api/v1/standing/subscribe``).

- **recording rules** — a standing query with a ``rule_name`` writes its
  newest closed steps back into the memstore as a real series
  (``rule_name{group labels}``), evaluated on ``eval_interval_s`` ticks —
  the recording-rules engine the ROADMAP said falls out for free.

- **alerting rules** — a standing query with an ``alert_sink`` feeds the
  newest closed step's per-group column to the alerting state machine
  (obs/alerting.py) after every refresh: the alert condition is evaluated
  from the partials the maintainer already keeps, never a separate
  dispatch plane.

Refreshes bypass admission control (they are the system's own standing
obligation, not ad-hoc tenant load) but their resources ARE attributed: the
owning tenant (resolved from the query's selector filters at registration)
is charged wall/kernel/staged-bytes through the same
``filodb_tenant_*_total`` counters ad-hoc queries pay into, and retained
partials are a first-class ledger kind
(``filodb_device_bytes{kind="standing_state"}``).
"""

from __future__ import annotations

import json
import logging
import math
import threading
import time

import numpy as np

from ..metrics import REGISTRY, record_fused_fallback
from .hub import SubscriptionHub
from .registry import StandingQuery, StandingRegistry, _new_qid

log = logging.getLogger("filodb_tpu.standing")

DEFAULTS = {
    "enabled": True,
    "promote_min_count": 8,
    "promote_window_s": 120.0,
    "promote_live_lag_ms": 120_000,
    "demote_idle_s": 600.0,
    "demote_retry_s": 3600.0,
    "max_standing": 64,
    "max_subscribers": 64,
    "refresh_debounce_ms": 250,
    "key_ring_max": 512,
    "default_span_ms": 1_800_000,
    "align_ms": 300_000,
    "tick_s": 0.5,
    # serve ordinary query_range calls matching a registered standing
    # query straight from its retained matrix (path=standing:serve)
    "serve_range": True,
}


class StandingEngine:
    """Registry + maintainer + promoter + hub, bound to one QueryEngine."""

    def __init__(self, engine, config: dict | None = None, hub=None,
                 clock=time.time):
        cfg = {**DEFAULTS, **(config or {})}
        self.cfg = cfg
        self.engine = engine
        self.dataset = engine.dataset
        self.clock = clock
        params = engine.planner.params
        sched = getattr(params, "dispatch_scheduler", None)
        if sched is None:
            # batching may be off (window 0) — the scheduler still exists
            # so the recurrence ring observes every fused dispatch
            from ..query.scheduler import DispatchScheduler

            sched = DispatchScheduler(
                params.batch_window_ms, params.batch_max,
                key_ring_max=int(cfg["key_ring_max"]),
            )
            params.dispatch_scheduler = sched
        self.scheduler = sched
        self.registry = StandingRegistry(int(cfg["max_standing"]))
        self.hub = hub or SubscriptionHub(int(cfg["max_subscribers"]))
        self.align_ms = int(cfg["align_ms"])
        self.debounce_s = float(cfg["refresh_debounce_ms"]) / 1e3
        # qid -> {(cache, sb_key)} pinned against eviction for that
        # standing query; reconciled after each dispatch so a rolled
        # aligned range does not leave its predecessor pinned forever
        self._sb_pins: dict[str, set] = {}
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._thread: threading.Thread | None = None
        self._listening: list = []  # (shard, cb) pairs for teardown

    # -- registration ------------------------------------------------------

    def register(self, promql: str, step_ms: int, span_ms: int | None = None,
                 source: str = "manual", key=None, rule_name: str | None = None,
                 eval_interval_s: float | None = None,
                 alert_sink=None) -> StandingQuery:
        """Register one standing query. Probes the planned exec to decide
        the maintenance mode: ``delta`` (fused aggregate with a spliceable
        epilogue) or ``full`` (nondecomposable epilogue or a plan shape the
        fused engine doesn't serve — every refresh re-dispatches, counted
        in the fallback taxonomy). Raises on unparseable PromQL or a full
        registry."""
        from ..ops import aggregations as AGG
        from ..query.exec.plans import FusedAggregateExec

        step_ms = max(int(step_ms), 1)
        span_ms = int(span_ms if span_ms else self.cfg["default_span_ms"])
        span_ms = max(span_ms - span_ms % step_ms, step_ms)
        now_ms = int(self.clock() * 1000)
        end = now_ms - now_ms % step_ms
        ex, _plan, tenant = self._materialize(
            promql, end - span_ms, end, step_ms
        )
        mode, mode_reason = "full", "not_fused"
        window_ms = offset_ms = 0
        if isinstance(ex, FusedAggregateExec):
            window_ms, offset_ms = ex.window_ms, ex.offset_ms
            if AGG.standing_delta_eligible(ex.op, ex.params,
                                           ex.hist_quantile):
                mode, mode_reason = "delta", None
            else:
                mode_reason = "standing_nondecomposable"
        sq = StandingQuery(
            qid=_new_qid(), promql=promql, dataset=self.dataset,
            step_ms=step_ms, span_ms=span_ms, source=source, key=key,
            mode=mode, mode_reason=mode_reason, ws=tenant[0], ns=tenant[1],
            rule_name=rule_name, eval_interval_s=eval_interval_s,
            alert_sink=alert_sink,
            window_ms=window_ms, offset_ms=offset_ms,
        )
        self.registry.add(sq)
        if key is not None:
            self.registry.forget_demoted(key)
        REGISTRY.counter("filodb_standing_promotions",
                         event="promote" if source == "promoted"
                         else "register").inc()
        self._wake.set()
        return sq

    def unregister(self, qid: str, reason: str = "unregistered"):
        sq = self.registry.remove(qid)
        if sq is None:
            return None
        self._sb_pins.pop(qid, None)
        cache = getattr(self.engine.memstore, "_superblock_cache", None)
        if cache is not None:
            cache.unpin_owner(qid)  # release superblock eviction pins
        self.hub.close(qid)
        if sq.source == "promoted":
            self.registry.note_demoted(sq.key, reason)
        REGISTRY.counter("filodb_standing_promotions", event="demote").inc()
        return sq

    def get(self, qid: str) -> StandingQuery | None:
        return self.registry.get(qid)

    # -- refresh (the delta path) ------------------------------------------

    def _materialize(self, promql: str, start_ms: int, end_ms: int,
                     step_ms: int):
        """(exec plan, logical plan, (ws, ns)) for one evaluation grid."""
        from ..metering import tenant_of_plan
        from ..query.promql import query_range_to_logical_plan

        plan = query_range_to_logical_plan(
            promql, start_ms / 1000.0, end_ms / 1000.0, step_ms / 1000.0,
            self.engine.planner.params.lookback_ms,
        )
        return self.engine.planner.materialize(plan), plan, tenant_of_plan(plan)

    def _pin_raw_range(self, ex, aligned: tuple) -> None:
        """Pin a fused exec's staging range to the standing query's aligned
        (lo, hi): every refresh of every grid position then resolves to ONE
        superblock cache entry — the warm entry live-edge appends EXTEND in
        place — instead of staging a fresh near-identical superblock per
        refresh. Staging a superset is safe (result windows derive from
        query params, planner._fused_raw_range's argument)."""
        ex.raw_start_ms, ex.raw_end_ms = aligned

    def _aligned_raw(self, ex) -> tuple:
        """Quantized staging range: lo floors to the alignment; hi floors
        then adds TWO alignment periods — at least one full period of
        live-edge headroom, so the pinned range (and with it the
        superblock cache key and the retained partials) stays stable while
        the grid end advances within one alignment bucket. The range rolls
        — and the standing state resets — once per ``align_ms`` of wall
        time; every refresh in between is delta or retained."""
        a = self.align_ms
        return (ex.raw_start_ms - ex.raw_start_ms % a,
                ex.raw_end_ms - ex.raw_end_ms % a + 2 * a)

    def _execute(self, ex, owner: str | None = None):
        """Run one (suffix or full) dispatch on the engine's context —
        admission is bypassed (standing work is the server's own standing
        obligation), attribution is not (caller meters the tenant)."""
        from ..obs.querylog import PhaseRecorder

        ctx = self.engine.context()
        ctx.standing_refresh = True  # keep maintainer dispatches out of the ring
        pinned: list = []
        if owner is not None:
            # pin whatever superblock key(s) the dispatch resolves to
            # against ad-hoc eviction; stale pins (rolled aligned range)
            # are released below, the rest on unregister
            def _pin(cache, key, _o=owner, _l=pinned):
                cache.pin(key, _o)
                _l.append((cache, key))

            ctx.superblock_pin_sink = _pin
        # phase capture for the refresh's querylog record: the maintainer
        # calls the exec tree outside the HTTP/engine entry points, so it
        # attaches the recorder itself (stage/dispatch decompose as usual)
        ctx.phases = PhaseRecorder()
        res = ex.execute(ctx)
        if owner is not None and pinned:
            # reconcile: new pins are already held, so dropping the ones
            # this dispatch did NOT touch never leaves a gap
            new = set(pinned)
            for cache, key in self._sb_pins.get(owner, set()) - new:
                cache.unpin(key, owner)
            self._sb_pins[owner] = new
        return ctx, res

    def refresh(self, sq: StandingQuery, now_ms: int | None = None,
                force_full: bool = False) -> bytes | None:
        """One refresh: classify ingest since the retained partials were
        computed, re-dispatch the minimal step suffix (or nothing), splice,
        render once, fan out, write back rule series. Returns the rendered
        payload (None when the refresh errored)."""
        t0 = time.perf_counter()
        if now_ms is None:
            now_ms = int(self.clock() * 1000)
        with sq.lock:
            if sq.removed:
                # unregister won the race: its ledger credit is final —
                # touching state here would re-grow what was freed
                return None
            try:
                payload, outcome, ctx, evalv = self._refresh_locked(
                    sq, now_ms, force_full
                )
            except Exception as e:  # noqa: BLE001 — maintenance must not die
                sq.stats["errors"] += 1
                sq.last_error = f"{type(e).__name__}: {e}"
                REGISTRY.counter("filodb_standing_refreshes",
                                 outcome="error").inc()
                if sq.alert_sink is not None:
                    # the alert rule's condition could not be evaluated
                    # this interval — surfaced in the alerting health
                    # family, not just the standing one
                    REGISTRY.counter(
                        "filodb_alert_eval_failures",
                        rule=getattr(sq.alert_sink, "rule", "unknown"),
                    ).inc()
                log.exception("standing refresh failed: %s", sq.promql)
                self._observe_querylog(sq, "error", None,
                                       time.perf_counter() - t0,
                                       status="error",
                                       error=f"{type(e).__name__}: {e}")
                return None
            sq.last_error = None
        elapsed = time.perf_counter() - t0
        sq.last_eval_duration_s = elapsed
        REGISTRY.counter("filodb_standing_refreshes", outcome=outcome).inc()
        REGISTRY.histogram("filodb_standing_refresh_seconds").observe(elapsed)
        if ctx is not None:
            from ..metering import record_tenant_query

            record_tenant_query(
                sq.ws, sq.ns, elapsed, ctx.stats.kernel_ns / 1e9,
                ctx.stats.bytes_staged,
            )
        # query-observatory record (obs/querylog.py): refreshes used to
        # bypass the querylog entirely (the maintainer calls the exec tree
        # outside the engine's HTTP entry points), leaving the busiest
        # recurring work invisible to the observatory — every refresh now
        # publishes a cost record under path standing:delta|standing:full
        self._observe_querylog(sq, outcome, ctx, elapsed)
        if payload is not None:
            self.hub.publish(sq.qid, payload)
        if sq.alert_sink is not None and evalv is not None:
            # feed the newest closed step to the alerting state machine —
            # OUTSIDE sq.lock (the sink writes ALERTS back through the
            # ingest path, which pokes the append listeners)
            try:
                sq.alert_sink(sq, evalv[0], evalv[1])
            except Exception:  # noqa: BLE001 — alerting must not kill refresh
                log.exception("alert sink failed: %s", sq.promql)
        return payload

    def _observe_querylog(self, sq: StandingQuery, outcome: str, ctx,
                          elapsed_s: float, status: str = "ok",
                          error: str | None = None) -> None:
        """One exemplar-level cost record per refresh. Path vocabulary:
        ``standing:delta`` covers the delta-maintained dispositions
        (suffix-only re-dispatch AND the zero-dispatch retained serve),
        ``standing:full`` the full re-evaluations (nondecomposable/unfused
        demotions and grid resets); an ERRORED refresh is labeled by the
        query's registered maintenance mode — the plane that was being
        attempted — so delta-path failures never masquerade as full
        refreshes in path-filtered dashboards (status=error tells the
        rest). The record carries the same executable_key/compile_miss
        join the ad-hoc path gets — the fused suffix dispatch stamped
        them on the context's obs annotations."""
        from ..obs.querylog import QUERY_LOG, PhaseRecorder

        phases = getattr(ctx, "phases", None) if ctx is not None else None
        if phases is None:
            phases = PhaseRecorder()
        info = dict(getattr(ctx, "obs", None) or {}) if ctx is not None else {}
        if status == "error":
            delta = sq.mode == "delta"
        else:
            delta = outcome in ("delta", "retained")
        info["path"] = "standing:delta" if delta else "standing:full"
        retained = sq.retained
        result_series = int(retained.shape[0]) if retained is not None else 0
        result_samples = int(retained.size) if retained is not None else 0
        # unique per refresh (sq.seq does not advance on retained serves):
        # the ring's id index must never alias two refreshes' records
        serial = int(sq.stats.get("refreshes", 0)) + int(
            sq.stats.get("errors", 0)
        )
        QUERY_LOG.publish(
            query_id=f"{sq.qid}:{serial}", dataset=sq.dataset,
            promql=sq.promql, ws=sq.ws, ns=sq.ns, step_ms=sq.step_ms,
            span_ms=sq.span_ms, start_s=sq.grid_start_ms / 1000.0,
            end_s=sq.grid_end_ms / 1000.0, phases=phases,
            elapsed_s=elapsed_s,
            stats=ctx.stats if ctx is not None else None,
            path_info=info, result_series=result_series,
            result_samples=result_samples, status=status, error=error,
        )

    def _refresh_locked(self, sq: StandingQuery, now_ms: int,
                        force_full: bool):
        from ..ops import aggregations as AGG
        from ..query.exec.plans import FusedAggregateExec

        step = sq.step_ms
        end = now_ms - now_ms % step
        start = end - sq.span_ms
        J = (end - start) // step + 1
        if sq.mode != "delta":
            return self._refresh_full(sq, start, end, J)
        ex, _plan, _tenant = self._materialize(sq.promql, start, end, step)
        if not isinstance(ex, FusedAggregateExec):
            # the plan stopped being fusable (e.g. config flipped
            # fused_aggregate off): demote this query to full mode and
            # release its delta state — full refreshes never read it, and
            # a dead [G, J] array must not stay resident and
            # ledger-counted for the query's lifetime
            sq.mode, sq.mode_reason = "full", "not_fused"
            self._drop_state(sq)
            return self._refresh_full(sq, start, end, J)
        aligned = self._aligned_raw(ex)
        self._pin_raw_range(ex, aligned)
        shard_nums = tuple(ex.shard_nums)
        memstore = self.engine.memstore
        # versions read BEFORE the dispatch: anything landing mid-dispatch
        # classifies as dirty next refresh — conservative, never stale
        versions_now = tuple(
            memstore.shard(sq.dataset, s).version for s in shard_nums
        )
        reset = (force_full or sq.retained is None or sq.versions is None
                 or sq.raw_range != aligned or sq.shard_nums != shard_nums
                 or len(sq.versions) != len(shard_nums))
        dirty_lo = None
        if not reset:
            for s, vold in zip(shard_nums, sq.versions):
                reason, lo, _hi = memstore.shard(
                    sq.dataset, s
                ).ingest_effects_interval_since(vold, aligned[0], aligned[1])
                if reason in ("full_clear", "log_truncated"):
                    reset = True
                    break
                if reason == "overlap":
                    dirty_lo = lo if dirty_lo is None else min(dirty_lo, lo)
        retained = None
        if not reset:
            shift = (start - sq.grid_start_ms) // step
            if shift < 0:
                reset = True  # clock moved backwards: state is ahead of now
            else:
                retained = AGG.shift_partials(sq.retained, int(shift), J)
                # first NEW step (beyond the old grid end)
                k_new = (sq.grid_end_ms - start) // step + 1
                k_new = min(max(int(k_new), 0), J)
                # first step whose window can contain the appended samples:
                # window j = (out_t - offset - window, out_t - offset], so
                # the append interval [dirty_lo, ...] reaches every step
                # with out_t >= dirty_lo + offset
                if dirty_lo is None:
                    k_dirty = J
                else:
                    k_dirty = math.ceil(
                        (dirty_lo + sq.offset_ms - start) / step
                    )
                    k_dirty = min(max(int(k_dirty), 0), J)
                k0 = min(k_new, k_dirty)
        if reset:
            k0 = 0
            retained = None
        ctx = None
        if k0 >= J and retained is not None:
            # fully warm: the appended data (if any) was provably disjoint
            # from every window AND the grid did not advance (k_new >= J),
            # so the content is byte-identical to the last refresh — ZERO
            # dispatches, and no render/publish either: re-pushing an
            # identical frame on every disjoint-ingest wake would make
            # JSON encode the dominant standing-engine cost. Only the
            # version vector commits (so the same effects aren't
            # re-classified next time).
            sq.versions = versions_now
            sq.stats["refreshes"] += 1
            sq.stats["retained"] += 1
            sq.stats["steps_retained"] += J
            REGISTRY.counter("filodb_standing_steps", kind="retained").inc(J)
            sq.last_refresh_s = self.clock()
            evalv = None
            if sq.alert_sink is not None:
                # the condition still gets its evaluation tick even when
                # zero dispatches ran — absence must resolve alerts
                evalv = self._eval_col(sq.retained, sq.labels,
                                       sq.grid_end_ms)
            return None, "retained", None, evalv
        else:
            if k0 > 0:
                # the delta dispatch: ONLY the touched suffix re-computes,
                # through the same fused program over the same superblock
                ex_d, _p, _t = self._materialize(
                    sq.promql, start + k0 * step, end, step
                )
                if isinstance(ex_d, FusedAggregateExec):
                    self._pin_raw_range(ex_d, aligned)
                else:  # plan shape changed underfoot — recompute fully
                    ex_d, k0 = ex, 0
            else:
                ex_d = ex
            ctx, res = self._execute(ex_d, owner=sq.qid)
            fresh, fresh_labels = self._grid_arrays(res, J - k0)
            if k0 > 0 and sq.labels != fresh_labels:
                # the group set changed (restage with new/removed series
                # raced the classification): the spliced halves would
                # disagree on the group axis — redo the whole grid. The
                # discarded suffix dispatch's resources still attribute:
                # its stats merge into the context the caller meters.
                prev = ctx
                ctx, res = self._execute(ex, owner=sq.qid)
                ctx.stats.merge(prev.stats)
                fresh, fresh_labels = self._grid_arrays(res, J)
                k0 = 0
                retained = None
            if k0 > 0:
                retained = AGG.splice_partials(retained, fresh, k0)
                labels = sq.labels
                outcome = "delta"
                sq.stats["delta"] += 1
            else:
                retained = fresh
                labels = fresh_labels
                outcome = "reset" if reset else "full"
                sq.stats["reset" if reset else "full"] += 1
            sq.stats["steps_computed"] += J - k0
            sq.stats["steps_retained"] += k0
            REGISTRY.counter("filodb_standing_steps",
                             kind="computed").inc(J - k0)
            if k0:
                REGISTRY.counter("filodb_standing_steps",
                                 kind="retained").inc(k0)
        old_nb = sq.state_nbytes()
        sq.retained = retained
        sq.labels = labels
        sq.grid_start_ms, sq.grid_end_ms = start, end
        sq.raw_range = aligned
        sq.versions = versions_now
        sq.shard_nums = shard_nums
        sq.seq += 1
        sq.stats["refreshes"] += 1
        sq.last_refresh_s = self.clock()
        self.registry.account_state(old_nb, sq.state_nbytes())
        payload = self._render(sq, start, end, J, retained, labels or [])
        if sq.rule_name:
            self._write_rule(sq, start, end, J, retained, labels or [])
        evalv = None
        if sq.alert_sink is not None:
            evalv = self._eval_col(retained, labels, end)
        return payload, outcome, ctx, evalv

    def _drop_state(self, sq: StandingQuery) -> None:
        """Release a query's retained delta state (caller holds sq.lock):
        credit the ledger and clear the arrays + coverage markers."""
        nb = sq.state_nbytes()
        if nb:
            self.registry.account_state(nb, 0)
        sq.retained = None
        sq.labels = None
        sq.versions = None
        sq.raw_range = None

    def _refresh_full(self, sq: StandingQuery, start: int, end: int, J: int):
        """Full re-dispatch refresh for nondecomposable/unfusable standing
        queries — the clean demotion path: the query stays registered and
        served by push, it just pays the whole grid each refresh (counted
        in the fused-fallback taxonomy when the epilogue is why)."""
        if sq.mode_reason == "standing_nondecomposable":
            record_fused_fallback("standing_nondecomposable")
        ex, _plan, _tenant = self._materialize(sq.promql, start, end,
                                               sq.step_ms)
        ctx, res = self._execute(ex, owner=sq.qid)
        from ..api import promjson as PJ

        data = PJ.render_matrix(res)
        sq.grid_start_ms, sq.grid_end_ms = start, end
        sq.seq += 1
        sq.stats["refreshes"] += 1
        sq.stats["full"] += 1
        sq.stats["steps_computed"] += J
        REGISTRY.counter("filodb_standing_steps", kind="computed").inc(J)
        sq.stats["renders"] += 1
        sq.last_refresh_s = self.clock()
        payload = json.dumps({
            "id": sq.qid, "seq": sq.seq, "dataset": sq.dataset, **data,
        }).encode()
        sq.last_payload = payload
        if sq.rule_name and res.grids:
            g = res.grids[0]
            self._write_rule(
                sq, start, end, J,
                np.asarray(g.values_np(), dtype=np.float32), list(g.labels),
            )
        evalv = None
        if sq.alert_sink is not None:
            vals, labels = self._grid_arrays(res, J)
            evalv = self._eval_col(vals, labels, end)
        return payload, "full", ctx, evalv

    @staticmethod
    def _eval_col(vals, labels, end_ms: int):
        """``(end_ms, [(labels, value), ...])`` for the newest closed step
        — the alert sink's input. NaN entries are absent series (a
        comparison filtered them out, or the window is empty): absence is
        what RESOLVES an alert, so they are dropped, not forwarded."""
        vec = []
        if vals is not None and vals.size and labels:
            col = vals[:, -1]
            for gi, lbl in enumerate(labels):
                v = float(col[gi])
                if not math.isnan(v):
                    vec.append((dict(lbl), v))
        return (int(end_ms), vec)

    @staticmethod
    def _grid_arrays(res, num_steps: int):
        """([G, num_steps] float32 copy, [G] labels) from a QueryResult —
        an empty selection is a zero-group grid, not an error."""
        if not res.grids:
            return np.zeros((0, num_steps), np.float32), []
        g = res.grids[0]
        vals = np.array(g.values_np(), dtype=np.float32, copy=True)
        if vals.shape[1] < num_steps:  # defensive: never under-fill
            pad = np.full((vals.shape[0], num_steps - vals.shape[1]),
                          np.nan, np.float32)
            vals = np.concatenate([vals, pad], axis=1)
        return vals[:, :num_steps], list(g.labels)

    def _render(self, sq: StandingQuery, start: int, end: int, J: int,
                retained, labels) -> bytes:
        """ONE materialization per refresh: the payload every subscriber
        receives (and the SSE initial frame) is rendered exactly once."""
        from ..api import promjson as PJ
        from ..query.rangevector import Grid, QueryResult

        vals = retained if retained is not None else np.zeros(
            (0, J), np.float32
        )
        res = QueryResult(grids=[Grid(list(labels), start, sq.step_ms, J,
                                      vals)])
        data = PJ.render_matrix(res)
        payload = json.dumps({
            "id": sq.qid, "seq": sq.seq, "dataset": sq.dataset, **data,
        }).encode()
        sq.last_payload = payload
        sq.stats["renders"] += 1
        return payload

    def _write_rule(self, sq: StandingQuery, start: int, end: int, J: int,
                    vals, labels) -> None:
        """Recording-rule write-back: the newest CLOSED steps (those not
        yet written) land as real samples of ``rule_name{group labels}``
        through the production ingest path — the rule's output is then
        queryable, flushable and downsample-able like any series."""
        from ..core.records import gauge_batch
        from ..core.schemas import METRIC_TAG

        first = max(sq.last_rule_write_ms + sq.step_ms, start)
        if sq.last_rule_write_ms <= 0:
            first = end  # first eval writes the newest step, no backfill
        if first > end or vals is None or not len(labels):
            sq.last_rule_write_ms = max(sq.last_rule_write_ms, end)
            return
        recs = []
        for j in range((first - start) // sq.step_ms, J):
            t = start + j * sq.step_ms
            col = vals[:, j]
            for gi, lbl in enumerate(labels):
                v = float(col[gi])
                if not math.isnan(v):
                    tags = {k: v2 for k, v2 in dict(lbl).items()
                            if k not in (METRIC_TAG, "__name__")}
                    recs.append((tags, int(t), v))
        if recs:
            try:
                n = self.engine.memstore.ingest_routed(
                    sq.dataset, gauge_batch(sq.rule_name, recs),
                    spread=self.engine.planner.params.spread,
                )
                REGISTRY.counter("filodb_standing_rule_samples").inc(n)
            except Exception:  # noqa: BLE001 — quota/cardinality shed
                log.exception("recording-rule write-back failed: %s",
                              sq.rule_name)
        sq.last_rule_write_ms = end

    def current_payload(self, qid: str) -> bytes | None:
        sq = self.registry.get(qid)
        return sq.last_payload if sq is not None else None

    # -- edge serving (ordinary query_range from retained state) -----------

    def serve_range(self, promql: str, start_s: float, end_s: float,
                    step_s: float):
        """Answer an ordinary ``query_range`` from a registered standing
        query's retained matrix — the ROADMAP leftover: only SSE
        subscribers rode standing state before. Returns a QueryResult
        (querylog record attached under path ``standing:serve``) when a
        delta-maintained query matches promql + step and its retained grid
        covers the requested range phase-aligned; None otherwise (the
        caller falls through to the engine). A grid that has fallen behind
        the requested end refreshes first — the delta path makes that a
        suffix-only (often zero-dispatch) catch-up."""
        if not self.cfg.get("serve_range", True):
            return None
        t0 = time.perf_counter()
        step_ms = max(int(round(step_s * 1000)), 1)
        start_ms = int(round(start_s * 1000))
        end_ms = int(round(end_s * 1000))
        if start_ms % step_ms or (end_ms - start_ms) % step_ms:
            return None  # phase-misaligned with the standing grid
        sq = None
        for cand in self.registry.list():
            if (cand.promql == promql and cand.step_ms == step_ms
                    and cand.mode == "delta"):
                sq = cand
                break
        if sq is None:
            return None
        if sq.retained is None or end_ms > sq.grid_end_ms:
            self.refresh(sq)  # catch the grid up to now before slicing
        from ..query.rangevector import Grid, QueryResult

        with sq.lock:
            if (sq.removed or sq.retained is None or sq.labels is None
                    or start_ms < sq.grid_start_ms
                    or end_ms > sq.grid_end_ms
                    or (start_ms - sq.grid_start_ms) % step_ms):
                return None
            j0 = (start_ms - sq.grid_start_ms) // step_ms
            j1 = (end_ms - sq.grid_start_ms) // step_ms
            vals = np.array(sq.retained[:, j0:j1 + 1], copy=True)
            labels = [dict(lbl) for lbl in sq.labels]
        J = j1 - j0 + 1
        res = QueryResult(grids=[Grid(labels, start_ms, step_ms, J, vals)])
        sq.stats["serves"] = sq.stats.get("serves", 0) + 1
        from ..obs.querylog import QUERY_LOG, PhaseRecorder

        res.query_log = QUERY_LOG.publish(
            query_id=_new_qid(), dataset=sq.dataset, promql=promql,
            ws=sq.ws, ns=sq.ns, step_ms=step_ms,
            span_ms=end_ms - start_ms, start_s=start_ms / 1000.0,
            end_s=end_ms / 1000.0, phases=PhaseRecorder(),
            elapsed_s=time.perf_counter() - t0,
            path_info={"path": "standing:serve"},
            result_series=len(labels), result_samples=int(vals.size),
        )
        return res

    # -- promotion / demotion ----------------------------------------------

    def promote_tick(self, now_s: float | None = None) -> int:
        """Scan the recurrence ring; promote keys that burst. Returns the
        number promoted (the unit tests drive this directly)."""
        from ..ops import aggregations as AGG

        if now_s is None:
            now_s = self.clock()
        cfg = self.cfg
        n_min = int(cfg["promote_min_count"])
        promoted = 0
        for key, e in self.scheduler.key_ring.entries():
            desc = e.get("desc") or {}
            promql = desc.get("promql")
            if not promql or desc.get("dataset") != self.dataset:
                continue
            if self.registry.by_key(key) is not None:
                continue
            reason = self.registry.demoted_reason(key)
            if reason == "standing_nondecomposable":
                continue  # sticky: the epilogue will never decompose
            if reason is not None:
                at = self.registry.demoted.get(key, {}).get("at_s", 0)
                if now_s - at < float(cfg["demote_retry_s"]):
                    continue
                self.registry.forget_demoted(key)
            recent = list(e["recent"])
            if len(recent) < n_min:
                continue
            if recent[-1] - recent[-n_min] > float(cfg["promote_window_s"]):
                continue
            if abs(desc.get("end_lag_ms", 1e18)) > float(
                cfg["promote_live_lag_ms"]
            ):
                continue  # historical scan, not a live-edge dashboard
            first = e.get("first_desc") or desc
            if not desc.get("end_ms", 0) > first.get("end_ms", 0):
                # near the edge, but never seen to move: a fixed range
                # looked at soon after it ended (a pinned panel, a load
                # generator over the newest scrape). No append will reach
                # what a standing query would keep for it
                continue
            if not AGG.standing_delta_eligible(
                desc.get("op", ""), desc.get("params", ()),
                desc.get("hist_quantile"),
            ):
                # remember, count, never flap
                self.registry.note_demoted(key, "standing_nondecomposable")
                record_fused_fallback("standing_nondecomposable")
                REGISTRY.counter("filodb_standing_promotions",
                                 event="demote").inc()
                continue
            if len(self.registry.list()) >= self.registry.max_standing:
                # transient capacity, not a property of the KEY: don't
                # remember it as demoted (that would block this hot key
                # for demote_retry_s after slots free) — just retry on a
                # later tick
                log.warning("standing registry full; promotion of %s "
                            "deferred", promql)
                continue
            try:
                self.register(
                    promql, desc["step_ms"],
                    span_ms=desc.get("span_ms"), source="promoted", key=key,
                )
                promoted += 1
            except Exception as exc:  # noqa: BLE001 — unparseable/invalid
                log.warning("standing promotion failed for %s: %s",
                            promql, exc)
                self.registry.note_demoted(key, "error")
        return promoted

    def demote_tick(self, now_s: float | None = None) -> int:
        """Demote auto-promoted queries whose recurrence went quiet AND
        that nobody subscribes to (hysteresis: the idle bound is far above
        the promotion window, so promote/demote can never oscillate)."""
        if now_s is None:
            now_s = self.clock()
        idle_s = float(self.cfg["demote_idle_s"])
        demoted = 0
        for sq in self.registry.list():
            if sq.source != "promoted":
                continue
            e = self.scheduler.key_ring.get(sq.key)
            last = e["last_s"] if e is not None else sq.created_s
            if now_s - max(last, sq.created_s) <= idle_s:
                continue
            if self.hub.count(sq.qid) > 0:
                continue
            self.unregister(sq.qid, reason="idle")
            demoted += 1
        return demoted

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop.clear()
        # append notifications: wake signals only — correctness derives
        # from the effect log at refresh time
        for sh in self.engine.memstore.shards(self.dataset):
            cb = self._on_append
            sh.add_append_listener(cb)
            self._listening.append((sh, cb))
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="filodb-standing"
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()
        for sh, cb in self._listening:
            sh.remove_append_listener(cb)
        self._listening.clear()
        if self._thread is not None:
            self._thread.join(timeout=2)
        for sq in self.registry.list():
            self.hub.close(sq.qid)

    def _on_append(self, _dataset, _shard, _lo, _hi, _full) -> None:
        self._wake.set()

    def _run(self) -> None:
        tick = float(self.cfg["tick_s"])
        last_promo = 0.0
        while not self._stop.is_set():
            woke = self._wake.wait(tick)
            if self._stop.is_set():
                return
            if woke:
                self._wake.clear()
                if self.debounce_s > 0:
                    # debounce: let the scrape burst land before refreshing
                    self._stop.wait(self.debounce_s)
            now_s = self.clock()
            for sq in self.registry.list():
                try:
                    if (sq.rule_name or sq.alert_sink is not None) \
                            and sq.eval_interval_s:
                        # rules evaluate on their own clock, not per append
                        if now_s - sq.last_refresh_s >= sq.eval_interval_s:
                            self.refresh(sq)
                    elif woke and (now_s - sq.last_refresh_s
                                   >= self.debounce_s):
                        self.refresh(sq)
                except Exception:  # noqa: BLE001
                    log.exception("standing maintenance failed")
            if now_s - last_promo >= 2.0:
                last_promo = now_s
                try:
                    self.promote_tick(now_s)
                    self.demote_tick(now_s)
                except Exception:  # noqa: BLE001
                    log.exception("standing promotion scan failed")

    # -- introspection -----------------------------------------------------

    def snapshot(self) -> dict:
        """The /debug/standing rendering: registry + demotions + the
        scheduler's retained recurrence ring + subscriber counts."""
        return {
            **self.registry.snapshot(),
            "subscribers": self.hub.snapshot(),
            "key_ring": self.scheduler.key_ring.snapshot(),
        }

    def rules_payload(self) -> dict:
        """Prometheus ``/api/v1/rules`` shape for the registered recording
        rules (one synthetic ``standing`` group holds the file-less,
        runtime-registered ones; file-backed rules are listed by
        obs/alerting.py's rules_payload instead)."""
        from ..obs.alerting import rfc3339

        rl = self.registry.rules()
        rules = [{
            "name": sq.rule_name,
            "query": sq.promql,
            "health": "err" if sq.last_error else "ok",
            "lastError": sq.last_error or "",
            "evaluationTime": float(sq.last_eval_duration_s),
            "lastEvaluation": rfc3339(int(sq.last_refresh_s * 1000)),
            "type": "recording",
            "labels": {},
        } for sq in rl]
        if not rules:
            return {"groups": []}
        return {"groups": [{
            "name": "standing", "file": "", "interval": 0,
            "evaluationTime": sum(
                float(sq.last_eval_duration_s) for sq in rl
            ),
            "lastEvaluation": rfc3339(
                int(max(sq.last_refresh_s for sq in rl) * 1000)
            ),
            "rules": rules,
        }]}
