"""Structured query log + per-phase latency decomposition — the query
observatory's exemplar plane (doc/observability.md "Query observatory").

Aggregate counters (PR 7's ledger / tenant totals) answer "how much did
tenant X cost this hour"; the slow-query ring answers "show me the worst
offenders". Neither answers the questions the ROADMAP's cost-model and
workload-chosen-rollup items need: *what did each query actually cost, and
where did its time go?* Every executed query therefore emits ONE compact
cost record — query id (= trace id), tenant, normalized PromQL fingerprint
(the dispatch scheduler's recurrence key shape), grid shape, the path
taken (fused / fallback reason / reference tree, batched or not, grid
class), per-phase wall times, scan/staging/cache stats and result size —
into a bounded in-memory ring served at ``GET /debug/querylog`` and
``GET /api/v1/query_profile?id=``.

The same capture feeds:

- ``filodb_query_phase_seconds{phase,dataset}`` histograms with trace-id
  exemplars (beside ``filodb_query_latency_seconds``), so
  ``histogram_quantile(0.99, rate(..._bucket{phase="render"}[5m]))``
  answers through the fused ``_system`` path once self-scrape ingests it;
- per-tenant/per-path cumulative aggregates
  (``filodb_tenant_phase_seconds_total{phase,ws,ns}``,
  ``filodb_query_path_total{path,dataset}``) that ride the same
  self-scrape into ``_system``;
- the SLO burn-rate recording rules (obs/slo.py).

The phase taxonomy is :data:`filodb_tpu.metrics.QUERY_PHASES` — the ONE
canonical set, linted by tools/check_spans.py (every fused execution path
emits each engine phase exactly once; unknown phase names are rejected
here at runtime and there statically, mirroring the fused-fallback reason
taxonomy).

Overhead contract: capture is host-side metadata only — no device sync is
added anywhere (the warm canonical query stays exactly ONE kernel dispatch
with capture enabled; asserted in tests/test_querylog.py).
"""

from __future__ import annotations

import contextlib
import hashlib
import threading
import time
from collections import deque

from ..metrics import QUERY_PHASES, REGISTRY, STAGE_PARTS

_PHASE_SET = frozenset(QUERY_PHASES)
_PART_SET = frozenset(STAGE_PARTS)

# phases measured inside the engine; transfer/render are added by the
# serving edge after the engine returns, and ``other`` is the computed
# residual — the invariant (tests/test_querylog.py) is
# sum(ENGINE_PHASES + other) == engine duration.
ENGINE_PHASES = ("parse_plan", "admission", "queue", "stage", "group",
                 "dispatch")
EDGE_PHASES = ("transfer", "render")


class PhaseRecorder:
    """Lock-cheap per-query phase accumulator. One instance rides the
    QueryContext (``ctx.phases``) and is re-bound per thread by
    ``ExecPlan.execute`` via :func:`filodb_tpu.metrics.activate_phases`,
    so pool workers and the batch scheduler attribute to the right query
    without threading a context through every ops/ signature."""

    __slots__ = ("seconds", "parts", "_lock")

    def __init__(self):
        self.seconds: dict[str, float] = {}
        # parts of the ``stage`` phase (metrics.STAGE_PARTS), booked by
        # ``span(..., part=...)``: sum(parts) <= seconds["stage"]
        self.parts: dict[str, float] = {}
        self._lock = threading.Lock()

    def add(self, phase: str, seconds: float) -> None:
        if phase not in _PHASE_SET:
            raise ValueError(
                f"unknown query phase {phase!r} (canonical set: "
                f"{sorted(_PHASE_SET)})"
            )
        with self._lock:
            self.seconds[phase] = (
                self.seconds.get(phase, 0.0) + max(float(seconds), 0.0)
            )

    def add_part(self, part: str, seconds: float) -> None:
        if part not in _PART_SET:
            raise ValueError(
                f"unknown stage part {part!r} (canonical set: "
                f"{sorted(_PART_SET)})"
            )
        with self._lock:
            self.parts[part] = (
                self.parts.get(part, 0.0) + max(float(seconds), 0.0)
            )

    @contextlib.contextmanager
    def phase(self, name: str):
        """Time a block into ``name`` (the engine-side capture primitive
        for phases that don't already run under a span)."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add(name, time.perf_counter() - t0)

    def snapshot(self) -> dict[str, float]:
        with self._lock:
            return dict(self.seconds)

    def parts_snapshot(self) -> dict[str, float]:
        with self._lock:
            return dict(self.parts)

    def total(self) -> float:
        with self._lock:
            return sum(self.seconds.values())


def promql_fingerprint(dataset: str, promql: str, step_ms: int,
                       span_ms: int) -> str:
    """Stable fingerprint of the NORMALIZED query: dataset + PromQL text +
    grid shape (step, span), with the sliding live-edge start/end
    normalized away — the same shape the dispatch scheduler's recurrence
    ring keys on, so a dashboard panel re-issuing ``end=now`` every 15 s
    is ONE fingerprint. This is the join key the future cost model and
    Storyboard-style rollup chooser train on."""
    raw = f"{dataset}\x00{promql}\x00{int(step_ms)}\x00{int(span_ms)}"
    return hashlib.sha1(raw.encode()).hexdigest()[:16]


def observe_phase(dataset: str, phase: str, seconds: float,
                  trace_id: str | None = None) -> None:
    """One phase observation into the operator-facing histogram
    (``filodb_query_phase_seconds{phase,dataset}``) with a trace-id
    exemplar — the bucket a spiking phase lands in links straight to its
    query-log record (same id)."""
    if phase not in _PHASE_SET:
        raise ValueError(f"unknown query phase {phase!r}")
    REGISTRY.histogram(
        "filodb_query_phase_seconds", phase=phase, dataset=dataset
    ).observe(float(seconds), exemplar={"trace_id": trace_id} if trace_id
              else None)


def _record_tenant_phases(ws: str, ns: str, phases: dict[str, float]) -> None:
    """Cumulative per-tenant phase seconds
    (``filodb_tenant_phase_seconds_total{phase,ws,ns}``), cardinality
    bounded by the metering overflow-bucket cap — the per-tenant half of
    the ``_system`` phase aggregates."""
    from ..metering import bounded_tenant_pair

    ws, ns = bounded_tenant_pair(ws, ns)
    for phase, s in phases.items():
        if s > 0.0:
            REGISTRY.counter(
                "filodb_tenant_phase_seconds", phase=phase, ws=ws, ns=ns
            ).inc(float(s))


class QueryLogRing:
    """Bounded ring of per-query cost records, newest last; lock-cheap
    (one mutex around a deque + an id index — the record itself is built
    outside the lock). Mirrors SlowQueryLog's concurrency contract:
    ``record`` vs ``configure`` resize races are safe, ``entries`` returns
    copies newest-first."""

    def __init__(self, max_entries: int = 512):
        self._max = max(int(max_entries), 1)
        self._entries: deque = deque()
        self._by_id: dict[str, dict] = {}
        self._lock = threading.Lock()

    def configure(self, max_entries: int) -> None:
        with self._lock:
            self._max = max(int(max_entries), 1)
            self._evict_locked()

    def _evict_locked(self) -> None:
        while len(self._entries) > self._max:
            gone = self._entries.popleft()
            if self._by_id.get(gone.get("id")) is gone:
                del self._by_id[gone["id"]]

    def record(self, entry: dict) -> dict:
        with self._lock:
            self._entries.append(entry)
            qid = entry.get("id")
            if qid:
                self._by_id[qid] = entry
            self._evict_locked()
        return entry

    @staticmethod
    def _copy(e: dict) -> dict:
        # records are finished in place by the serving edge
        # (finish_serving) — readers must get copies, nested mutable
        # fields included
        out = dict(e)
        for k in ("phases_ms", "stage_parts_ms", "stats", "result", "grid"):
            if isinstance(out.get(k), dict):
                out[k] = dict(out[k])
        return out

    def get(self, query_id: str) -> dict | None:
        with self._lock:
            e = self._by_id.get(query_id)
            return self._copy(e) if e is not None else None

    def entries(self, limit: int | None = None) -> list[dict]:
        """Newest first; ``limit`` caps the page (0 = empty, not all)."""
        with self._lock:
            out = [self._copy(e) for e in reversed(self._entries)]
        if limit is None:
            return out
        return out[: max(int(limit), 0)]

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._by_id.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    # -- record lifecycle --------------------------------------------------

    def publish(self, *, query_id: str, dataset: str, promql: str,
                ws: str, ns: str, step_ms: int, span_ms: int,
                start_s: float, end_s: float,
                phases: PhaseRecorder, elapsed_s: float,
                stats=None, path_info: dict | None = None,
                result_series: int = 0, result_samples: int = 0,
                status: str = "ok", error: str | None = None,
                predicted_cost_s: float | None = None,
                realized_cost_s: float | None = None) -> dict:
        """Build + ring one query's cost record and feed the aggregate
        planes (phase histograms with trace-id exemplars, per-tenant phase
        counters, per-path counter). The engine calls this once per
        EXECUTION at the query's origin (coalesced followers share the
        leader's record; remote-child legs don't publish — the origin
        accounts the whole query, mirroring tenant metering)."""
        ph = phases.snapshot()
        parts = phases.parts_snapshot()
        # the residual: engine wall time the named phases don't cover
        # (transformer folding, result assembly, scatter overhead) — makes
        # the engine-phase sum equal wall time by construction
        other = max(float(elapsed_s) - sum(ph.values()), 0.0)
        if other > 0.0:
            ph["other"] = ph.get("other", 0.0) + other
        info = path_info or {}
        path = info.get("path", "tree")
        entry = {
            "id": query_id,
            "time": time.time(),
            "dataset": dataset,
            "promql": promql,
            "fingerprint": promql_fingerprint(dataset, promql, step_ms,
                                              span_ms),
            "ws": ws,
            "ns": ns,
            "grid": {
                "start_s": round(float(start_s), 3),
                "end_s": round(float(end_s), 3),
                "step_ms": int(step_ms),
                "steps": (int((end_s - start_s) * 1000 // step_ms) + 1
                          if step_ms > 0 else 1),
            },
            "path": path,
            "fallback_reason": info.get("fallback"),
            "grid_class": info.get("grid_class"),
            "batched": info.get("batched"),
            # kernel-observatory join (obs/kernels.py): the executable that
            # served the fused dispatch and whether that launch compiled —
            # the cost model joins phase data to kernel identity through
            # this key (/debug/kernels indexes by it)
            "executable_key": info.get("executable_key"),
            "compile_miss": info.get("compile_miss"),
            # replicated shard plane: the remote endpoint(s) that served the
            # query's scatter legs — a failover shows up as the sibling's
            # endpoint here (and in /api/v1/query_profile)
            "endpoint": ",".join(info["endpoints"]) if info.get("endpoints") else None,
            "status": status,
            "error": error,
            # cost-model plane (query/costmodel.py): what admission PRICED
            # this execution at vs. what the device actually charged —
            # the pair every prediction-quality surface joins on
            "predicted_cost_s": (round(float(predicted_cost_s), 6)
                                 if predicted_cost_s is not None else None),
            "realized_cost_s": (round(float(realized_cost_s), 6)
                                if realized_cost_s is not None else None),
            "duration_ms": round(float(elapsed_s) * 1e3, 3),
            "phases_ms": {k: round(v * 1e3, 3) for k, v in ph.items()},
            # where ``stage`` went (metrics.STAGE_PARTS; empty on a warm
            # superblock hit): sum <= phases_ms["stage"]
            "stage_parts_ms": {k: round(v * 1e3, 3)
                               for k, v in parts.items()},
            "stats": {
                "series_scanned": getattr(stats, "series_scanned", 0),
                "samples_scanned": getattr(stats, "samples_scanned", 0),
                "bytes_staged": getattr(stats, "bytes_staged", 0),
                "kernel_ms": round(getattr(stats, "kernel_ns", 0) / 1e6, 3),
                "cache_hits": getattr(stats, "cache_hits", 0),
                "cache_misses": getattr(stats, "cache_misses", 0),
                "cache_extends": getattr(stats, "cache_extends", 0),
            },
            "result": {"series": int(result_series),
                       "samples": int(result_samples), "bytes": None},
        }
        for phase, s in ph.items():
            observe_phase(dataset, phase, s, trace_id=query_id)
        for part, s in parts.items():
            REGISTRY.histogram(
                "filodb_stage_part_seconds", part=part, dataset=dataset
            ).observe(s)
        _record_tenant_phases(ws, ns, ph)
        REGISTRY.counter("filodb_query_path", path=path,
                         dataset=dataset).inc()
        return self.record(entry)

    def finish_serving(self, entry: dict, transfer_s: float, render_s: float,
                       body_bytes: int | None = None,
                       code: int | None = None,
                       render_format: str | None = None) -> None:
        """Edge-side completion: fold the serving phases (device→host
        transfer, encode+write) into the record and the aggregate planes.
        Histograms/tenant counters observe for EVERY caller (each
        coalesced follower pays its own render); the record itself is
        finished FIRST-WINS — followers sharing the leader's record must
        not accumulate their renders into its phase sums."""
        dataset = entry.get("dataset", "")
        qid = entry.get("id")
        for phase, s in (("transfer", transfer_s), ("render", render_s)):
            observe_phase(dataset, phase, s, trace_id=qid)
        _record_tenant_phases(entry.get("ws", "unknown"),
                              entry.get("ns", "unknown"),
                              {"transfer": transfer_s, "render": render_s})
        with self._lock:
            ph = entry.get("phases_ms")
            if isinstance(ph, dict) and "render" not in ph:
                ph["transfer"] = round(float(transfer_s) * 1e3, 3)
                ph["render"] = round(float(render_s) * 1e3, 3)
                if body_bytes is not None:
                    entry.setdefault("result", {})["bytes"] = int(body_bytes)
                if code is not None:
                    entry["code"] = int(code)
                if render_format is not None:
                    # which encoder tier served the body (native/numpy JSON
                    # fragments, arrow peer frames) — joins the record to
                    # filodb_render_seconds{format}
                    entry["render_format"] = render_format


QUERY_LOG = QueryLogRing()
