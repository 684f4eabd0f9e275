"""Metrics, tracing, profiling (reference aux subsystems, SURVEY.md §5:
core/.../metrics/FilodbMetrics.scala Kamon facade + OTel export;
Kamon spans threading ExecPlan.execute; standalone SimpleProfiler.java:19
sampling profiler).

- ``Registry``: counters / gauges / histograms with Prometheus text
  exposition (served at /metrics by the HTTP API), plus scrape-time
  collectors for gauges that must be refreshed on demand.
- ``span`` / ``Span`` / ``TraceContext``: real tracing. Spans carry
  (trace_id, span_id, parent_id) plus tags and per-node QueryStats; the
  context is explicitly capturable (``current_span``) and re-activatable
  (``activate``) so a trace survives thread-pool hops, and serializable
  (``Span.to_dict`` / ``from_dict``) so remote children return their span
  trees in-band and the origin stitches them under the dispatching span.
  ``span`` is also the ONE place host walls are booked (``phase=``,
  ``part=``) and the one place the program writes to a profiler trace:
  every span holds a ``jax.profiler.TraceAnnotation`` of its own name, so
  under a profiler session the span tree lies on the device trace's clock.
- ``SlowQueryLog``: ring buffer of queries exceeding a configured
  threshold, each entry carrying the rendered trace tree (served at
  /debug/slow_queries and counted in /metrics).
- ``SamplingProfiler``: periodic stack sampler over all threads (the
  SimpleProfiler analog) with top-of-stack aggregation.
"""

from __future__ import annotations

import bisect
import contextlib
import random
import sys
import threading
import time
import traceback
from collections import Counter, deque
from dataclasses import dataclass, field

from jax.profiler import TraceAnnotation


class Counter_:
    __slots__ = ("value", "_lock")

    def __init__(self):
        self.value = 0.0
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0):
        with self._lock:
            self.value += amount


class Gauge:
    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def set(self, v: float):
        self.value = v


class Histogram:
    """Fixed-bucket latency histogram (seconds)."""

    BOUNDS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)

    def __init__(self):
        self.counts = [0] * (len(self.BOUNDS) + 1)
        self.sum = 0.0
        self.total = 0
        # last exemplar per bucket: (labels dict, value, unix_ts) — rendered
        # on OpenMetrics bucket lines so a spiking latency bucket links
        # straight to its trace (and through it the slow-query log)
        self.exemplars: list = [None] * (len(self.BOUNDS) + 1)
        self._lock = threading.Lock()

    def observe(self, v: float, exemplar: dict | None = None):
        i = bisect.bisect_left(self.BOUNDS, v)
        with self._lock:
            self.counts[i] += 1
            self.sum += v
            self.total += 1
            if exemplar:
                self.exemplars[i] = (dict(exemplar), float(v), time.time())


class MicroHistogram(Histogram):
    """Histogram with sub-millisecond bounds for host paths that complete in
    microseconds (index lookups): the standard bounds start at 1ms and would
    collapse the whole distribution into the first bucket."""

    BOUNDS = (5e-6, 1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4,
              1e-3, 5e-3, 2.5e-2, 0.1, 0.5)


def escape_label_value(v) -> str:
    """Prometheus text-format label escaping: backslash, double-quote and
    newline must be escaped or the exposition line is unparseable."""
    return str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def escape_help(v: str) -> str:
    """# HELP line escaping (backslash and newline only, per the spec)."""
    return str(v).replace("\\", "\\\\").replace("\n", "\\n")


# help text per metric family (the *registered* name: counters WITHOUT the
# _total suffix the exposition appends). tools/check_metrics.py lints that
# every family emitted in code is documented in doc/observability.md —
# this table feeds the # HELP lines of the same families.
HELP_TEXTS: dict[str, str] = {
    "filodb_queries": "Queries served, per dataset (coalesced followers included).",
    "filodb_query_latency_seconds": "End-to-end query latency.",
    "filodb_queries_coalesced": "Callers that rode on an identical in-flight query's execution (single-flight followers).",
    "filodb_slow_queries": "Queries over the slow-query threshold (see /debug/slow_queries).",
    "filodb_breaker_transitions": "Circuit-breaker state transitions per endpoint.",
    "filodb_breaker_state": "Breaker state per endpoint: 0 closed, 0.5 half-open, 1 open.",
    "filodb_remote_retries": "Remote-child dispatch retries per endpoint.",
    "filodb_partial_results": "Queries answered with merged partials (children lost).",
    "filodb_shard_reassignments": "Shard reassignment outcomes from ingestion errors.",
    "filodb_fused_fallback": "Fused single-dispatch aggregates delegated to the reference tree, by reason.",
    "filodb_group_reduce": "Fused scalar dispatches by the form of their cross-series reduction (wide = exact int32 pieces, plain = f32 segment reduce).",
    "filodb_fused_dispatch": "Fused launches by the range body that ran (after any degradation) and the grid class of the block it ran on (regular|jitter|holes|irregular): an irregular grid runs off the mxu > jitter > masked ladder, on pallas or general.",
    "filodb_pallas_lane_tiles": "Lane tiles (128 samples of a series tile) under the steps of the Pallas gather-scan's launches: kind=scanned the tiles the steps read, kind=resident the tiles of the rows before them; scanned / resident near 2 / (T / 128) says the narrow scan engaged, 1 that every step read whole rows.",
    "filodb_hist_rescale_series": "Series of base-2 exponential histograms a fused launch merged onto their group's scale, by how: rescaled = downscaled onto a coarser group scale, native = already at it.",
    "filodb_hist_merge": "Fused launches over base-2 exponential histograms by how the group sum ran: onehot = a 0/1 membership product on the MXU (up to 128 groups, trash group included), segment = a segment_sum.",
    "filodb_ingest_scheme_refused": "Histogram rows refused at ingest because their bucket scheme is explicit against a base-2 partition or base-2 against an explicit one.",
    "filodb_ingest_scheme_moved": "Base-2 histogram partitions whose scheme a batch widened at ingest (range grown or scale lowered: the join of the two, fit to 160 buckets).",
    "filodb_ingest_scheme_merged": "Base-2 histogram rows whose own scheme was finer than their partition's, merged down onto it exactly at ingest (a delta exporter's point at the finest scale its interval fits).",
    "filodb_hist_window": "Fused launches over base-2 exponential histograms by how the range body read each window: edges = the two samples at its edges (a cumulative column's rate family, last), sums = every sample in it (a delta column's rate / increase, sum_over_time).",
    "filodb_hist_edges": "Fused launches over base-2 exponential histograms of a cumulative column's rate family by how the range body read each window's increase: product = one +-1 product of the [J, T] edge matrix with the block on the MXU (every value of the block a whole number below 2^23), gather = the samples at the window's edges taken along T.",
    "filodb_hist_epilogue": "Fused launches over base-2 exponential histograms by how the merge onto each group's scheme and the group sum ran: pallas = one Pallas kernel with its intermediates in VMEM (an accelerator, or FILODB_PALLAS=1; the onehot sum; a VMEM plan that fits), xla = the two 0/1 products of bf16 pieces in HBM.",
    "filodb_stage_cache_insert_dropped": "Staged blocks not cached because ingest effects touched their range.",
    "filodb_superblock_maintenance": "Version-stale superblock maintenance outcomes (revalidate|extend|extend_abort|restage).",
    "filodb_downsample_claims": "Distributed-downsample claim lifecycle events.",
    "filodb_kernel_dispatch_seconds": "ops/ kernel dispatch latency, per kernel.",
    "filodb_shard_partitions": "Live partitions per shard.",
    "filodb_shard_rows_ingested": "Rows ingested per shard.",
    "filodb_shard_rows_skipped": "Rows skipped per shard.",
    "filodb_shard_partitions_evicted": "Partitions evicted per shard.",
    "filodb_shard_chunks_flushed": "Chunks flushed per shard.",
    "filodb_tenant_ts_total": "Total series per tenant (ws/ns).",
    "filodb_tenant_ts_active": "Actively ingesting series per tenant (ws/ns).",
    "filodb_tenant_queries": "Queries attributed to the tenant resolved from query filters.",
    "filodb_admission": "Admission-control outcomes per tenant (admitted|shed_rate|shed_concurrency|shed_queue).",
    "filodb_batch_queries": "Fused dispatches submitted to the cross-query batching scheduler, per epilogue family.",
    "filodb_batch_dispatches": "Batching-scheduler group executions per family and outcome (batched|solo|fallback).",
    "filodb_batch_merged_windows": "Compatible window-groups re-merged into one mixed-window batched launch, per family.",
    "filodb_batch_queue_depth": "Fused dispatches currently collecting in open batch windows.",
    "filodb_tenant_query_seconds": "Wall-clock query seconds per tenant.",
    "filodb_tenant_kernel_seconds": "Device kernel-dispatch seconds per tenant.",
    "filodb_tenant_bytes_staged": "Bytes staged to device per tenant.",
    "filodb_device_bytes": "Live device bytes per ledger kind (staged_block|superblock|compile_cache|standing_state|rollup).",
    "filodb_device_alloc": "Ledger debits (entries pinned) per kind.",
    "filodb_device_alloc_bytes": "Bytes debited to the device ledger per kind.",
    "filodb_device_free": "Ledger credits per kind and reason (evict|invalidate|replace|drop).",
    "filodb_device_free_bytes": "Bytes credited back to the device ledger per kind and reason.",
    "filodb_device_leaked_bytes": "Bytes held by ledger accounts whose cache died without releasing.",
    "filodb_self_scrapes": "Self-scrape cycles into the _system dataset.",
    "filodb_self_scrape_samples": "Samples ingested into the _system dataset by the self-scraper.",
    "filodb_standing_queries": "Registered standing queries by maintenance mode (delta|full).",
    "filodb_standing_refreshes": "Standing-query refreshes by outcome (retained|delta|full|reset|error).",
    "filodb_standing_refresh_seconds": "Standing-query refresh latency (classify + dispatch + render + fan-out).",
    "filodb_standing_steps": "Standing-query grid steps per refresh disposition (computed|retained).",
    "filodb_standing_subscribers": "Live push subscribers across all standing queries.",
    "filodb_standing_pushes": "Per-subscriber payload deliveries (sent) and stall drops (dropped).",
    "filodb_standing_promotions": "Standing-query lifecycle events (register|promote|demote).",
    "filodb_standing_rule_samples": "Samples written back into the memstore by recording rules.",
    "filodb_query_phase_seconds": "Per-phase query latency decomposition (parse_plan|admission|queue|stage|group|dispatch|transfer|render|other).",
    "filodb_stage_part_seconds": "Where the stage phase went, per execution (lookup|gather|assemble|h2d_shard|readback|concat|h2d_super|scheme); the parts sum to at most the stage phase.",
    "filodb_stage_h2d_bytes": "Bytes a cold stage uploaded to the device, by part (h2d_shard = per-shard blocks, h2d_super = a host-assembled superblock, a masked sidecar, the le vector).",
    "filodb_stage_d2h_bytes": "Bytes a cold stage read back from device-resident staged arrays that have no host mirror (the first np.asarray of each).",
    "filodb_stage_mirror_bytes": "Bytes of host mirrors (kept for in-place append repairs) by site (shard = a shard's staged block, super = a superblock) and what became of them (aliased = the staged arrays themselves, no copy; copied = explicit copies, CPU backend; deferred = not made at a device assembly; materialized = made at a deferred mirror's first extension).",
    "filodb_stage_gather_series": "Series a cold stage gathered from a shard, by the path that staged them (native = a histogram selection, one table of chunk segments and one pass of libfilodbstage; python = samples_in_range a series and the numpy pad: scalar columns, no library, arrays the pass cannot read in place, an empty selection).",
    "filodb_superblock_assembled": "Superblocks built, by where their arrays were concatenated (device = from the shards' device-resident blocks, nothing uploaded again; host = concatenated on the host and uploaded).",
    "filodb_query_wait_seconds": "Per-caller wait for another thread, by kind (coalesced = a follower of an identical in-flight query; queued = submitted to the query pool until a worker starts it; handback = the worker is done until the caller runs again).",
    "filodb_http_request_seconds": "Handler wall of a query route, entry to return, per caller (route = query_range|query).",
    "filodb_transfer_ready_seconds": "Per-caller wait for the device to finish the query's program at the serving edge; the transfer phase less this is the copy back.",
    "filodb_render_write_seconds": "Per-caller wall of writing a query response (status line, headers, body) to the socket; the render phase less this is encoding.",
    "filodb_query_path": "Queries by execution path (fused|fallback|tree|standing:delta|standing:full|standing:serve) per dataset.",
    "filodb_tenant_phase_seconds": "Per-phase query wall seconds attributed to the tenant (ws/ns).",
    "filodb_tenant_query_latency_seconds": "End-to-end query latency per tenant (the latency-SLO feed).",
    "filodb_http_responses": "HTTP API responses by status code and class (2xx|4xx|shed|5xx|stream_abort).",
    "filodb_render_seconds": "Result-body encode seconds per format (json-native|json-numpy JSON tiers, arrow peer frames).",
    "filodb_response_bytes": "Uncompressed result-body bytes sent per format (json|arrow).",
    "filodb_render_stream_stalls": "Streamed-render encoder waits on a device->host block (D2H the double-buffer failed to hide).",
    "filodb_querylog_entries": "Query-log ring depth (exemplar-level cost records retained).",
    "filodb_index_lookup_seconds": "Part-key index lookup latency by matcher cost class (eq|in|prefix|regex|neg).",
    "filodb_xla_compiles": "XLA compile events per kernel family (a dispatch that grew the jit cache).",
    "filodb_xla_compile_seconds": "Wall seconds spent in dispatches that compiled (trace+compile inclusive), per kernel family.",
    "filodb_xla_recompile_storms": "Recompile storms detected per kernel family (same family re-lowering past the threshold inside the window; /debug/kernels names the unstable dimension).",
    "filodb_xla_executables": "Live executables in the kernel observatory's registry.",
    "filodb_kernel_exec_dispatches": "Kernel dispatches accounted by the executable registry, per family.",
    "filodb_compile_cache_hits": "Compile-cache hits by tier (in_process = warm jit cache, persistent = compile deserialized from the on-disk XLA cache).",
    "filodb_compile_cache_misses": "Compile-cache misses by tier (in_process = a compile happened, persistent = a fresh trace wrote a new on-disk entry).",
    "filodb_index_postings_bytes": "Host posting-bitmap footprint of the part-key index, per shard.",
    "filodb_index_dictionary_size": "Distinct (label, value) dictionary entries in the part-key index, per shard.",
    "filodb_rollup_entries": "Registered rollup entries (selector x resolution summary blocks) per dataset.",
    "filodb_rollup_maintenance": "Rollup maintainer outcomes (add|build|fold|rebuild|retire|error).",
    "filodb_rollup_serves": "Queries served from rollup blocks instead of raw samples, by kind (window|agg|hist_quantile).",
    "filodb_rollup_chooser": "Workload-chooser decisions (add|retire) over querylog fingerprints.",
    "filodb_superblock_pinned_bytes": "Superblock cache bytes pinned by standing queries (skipped by eviction).",
    "filodb_replica_selection": "Remote dispatches by which replica served (primary|sibling).",
    "filodb_replica_failovers": "Dispatches re-pinned away from a replica endpoint, by reason (breaker_open|endpoint_failure).",
    "filodb_replica_acks": "Per-replica ingest fan-out append outcomes (ok|error|skipped).",
    "filodb_replica_watermark_ms": "Per shard+replica ingest lag watermark (max acked sample timestamp, ms).",
    "filodb_rebalance": "Live shard rebalance outcomes (clean|replayed|rebuilt|damped|failed).",
    "filodb_rebalance_standing_moves": "Standing queries re-registered on a shard's new owner after a rebalance.",
    "filodb_alerts": "Alerting rules/labelsets by state (inactive|pending|firing).",
    "filodb_alert_eval_seconds": "Alert-rule evaluation latency (state machine + write-back per tick).",
    "filodb_alert_eval_failures": "Alert-rule evaluation failures, per rule (refresh errors included).",
    "filodb_alert_notify": "Alert notification deliveries per receiver and outcome (ok|retry|error|breaker_open).",
    "filodb_costmodel_error_ratio": "Cost-model prediction quality per completed query: max(predicted/realized, realized/predicted) device-seconds.",
    "filodb_prewarm": "Executable pre-warm attempts by outcome (ok|error): recurrence-ring keys trace+compiled off the serving path.",
    "filodb_prewarm_seconds": "Wall of one pre-warmed key (the prewarm:key span): a whole query executed off the serving path.",
    "filodb_prewarm_phase_seconds": "Where a pre-warmed key's wall went, by query phase; a pre-warm books here and never into filodb_query_phase_seconds or filodb_stage_part_seconds.",
    "filodb_ingest_seconds": "Wall of one routed ingest call (the ingest:routed span: shard split plus every owned shard's ingest), per dataset.",
    "filodb_startup_seconds": "Process start to a serving server, by stage (import = process start until FiloServer is entered; backend = compile cache, distributed runtime and the first jax.devices(); store = memstore, engines and recovery; listen = until the port is bound and the background loops run). Set once a start.",
    "process_start_time_seconds": "Start time of the process since the unix epoch, in seconds (from /proc/self/stat).",
}


class Registry:
    def __init__(self):
        self._metrics: dict[tuple[str, tuple], object] = {}
        # scrape-time collectors: keyed callbacks run at the top of expose()
        # to refresh gauges that mirror live state (per-shard stats etc.) —
        # ONE exposition path instead of handlers hand-rolling text
        self._collectors: dict[str, object] = {}
        self._help: dict[str, str] = {}
        self._lock = threading.Lock()

    def register_collector(self, key: str, fn) -> None:
        """Register (or replace) a zero-arg callback invoked at scrape time
        before rendering. Keyed so re-created servers replace, not stack."""
        with self._lock:
            self._collectors[key] = fn

    def unregister_collector(self, key: str) -> None:
        with self._lock:
            self._collectors.pop(key, None)

    def _get(self, cls, name: str, labels: dict | None):
        key = (name, tuple(sorted((labels or {}).items())))
        with self._lock:
            m = self._metrics.get(key)
            if m is None:
                m = cls()
                self._metrics[key] = m
            return m

    def counter(self, name: str, **labels) -> Counter_:
        return self._get(Counter_, name, labels)

    def gauge(self, name: str, **labels) -> Gauge:
        return self._get(Gauge, name, labels)

    def histogram(self, name: str, **labels) -> Histogram:
        return self._get(Histogram, name, labels)

    def micro_histogram(self, name: str, **labels) -> MicroHistogram:
        """Histogram with µs-scale buckets (one family must use ONE bucket
        layout consistently — pick this or :meth:`histogram`, never both)."""
        return self._get(MicroHistogram, name, labels)

    def remove(self, name: str, **labels) -> bool:
        """Drop one series (a vanished tenant's gauges must not be exposed
        forever — TenantIngestionMetering ages them out on publish).
        Returns True when the series existed."""
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            return self._metrics.pop(key, None) is not None

    def remove_matching(self, name: str, predicate) -> int:
        """Drop every series of ``name`` whose label dict satisfies
        ``predicate``; returns the count removed."""
        with self._lock:
            gone = [
                k for k in self._metrics
                if k[0] == name and predicate(dict(k[1]))
            ]
            for k in gone:
                del self._metrics[k]
        return len(gone)

    def counter_samples(self, *families: str) -> dict[str, float]:
        """Rendered ``family{labels} -> value`` for the named counter
        families — the public snapshot surface for consumers outside this
        module (the test suites) so they never couple to the private
        storage layout."""
        out: dict[str, float] = {}
        with self._lock:
            for (name, labels), m in self._metrics.items():
                if name in families and isinstance(m, Counter_):
                    lbl = ",".join(f"{k}={v}" for k, v in labels)
                    out[f"{name}{{{lbl}}}"] = m.value
        return out

    def describe(self, name: str, help_text: str) -> None:
        """Register/override help text for a metric family (exposed as the
        ``# HELP`` line; defaults come from :data:`HELP_TEXTS`)."""
        with self._lock:
            self._help[name] = str(help_text)

    def _render_exemplar(self, ex) -> str:
        labels, value, ts = ex
        inner = ",".join(
            f'{k}="{escape_label_value(v)}"' for k, v in labels.items()
        )
        return f" # {{{inner}}} {value:g} {ts:.3f}"

    def expose(self, openmetrics: bool = False) -> str:
        """Prometheus text exposition of everything registered, with
        ``# HELP``/``# TYPE`` per family. ``openmetrics=True`` renders
        OpenMetrics 1.0 instead: family names lose the ``_total`` suffix in
        metadata lines, histogram bucket lines carry trace-id exemplars,
        and the payload ends with ``# EOF``."""
        with self._lock:
            collectors = list(self._collectors.values())
        for fn in collectors:
            try:
                fn()
            except Exception:  # noqa: BLE001 — a sick collector must not kill /metrics
                pass
        lines = []
        with self._lock:
            items = sorted(self._metrics.items(), key=lambda kv: kv[0][0])
            help_map = dict(self._help)
        seen_families: set[str] = set()

        def header(name: str, mtype: str):
            # text format 0.0.4 names counter families WITH the _total
            # suffix samples carry; OpenMetrics strips it
            family = (
                name if (openmetrics or mtype != "counter") else f"{name}_total"
            )
            if family in seen_families:
                return
            seen_families.add(family)
            help_text = help_map.get(name, HELP_TEXTS.get(name))
            if help_text:
                lines.append(f"# HELP {family} {escape_help(help_text)}")
            lines.append(f"# TYPE {family} {mtype}")

        for (name, labels), m in items:
            lbl = (
                "{" + ",".join(f'{k}="{escape_label_value(v)}"' for k, v in labels) + "}"
                if labels else ""
            )
            if isinstance(m, Counter_):
                header(name, "counter")
                lines.append(f"{name}_total{lbl} {m.value:g}")
            elif isinstance(m, Gauge):
                header(name, "gauge")
                # six digits unless they lose the value (an epoch time)
                text = f"{m.value:g}"
                if float(text) != m.value:
                    text = repr(float(m.value))
                lines.append(f"{name}{lbl} {text}")
            elif isinstance(m, Histogram):
                header(name, "histogram")
                base = [f'{k}="{escape_label_value(v)}"' for k, v in labels]
                cum = 0
                for i, (b, c) in enumerate(zip(m.BOUNDS, m.counts)):
                    cum += c
                    inner = ",".join(base + [f'le="{b:g}"'])
                    ex = m.exemplars[i] if openmetrics else None
                    suffix = self._render_exemplar(ex) if ex else ""
                    lines.append(f"{name}_bucket{{{inner}}} {cum}{suffix}")
                inner = ",".join(base + ['le="+Inf"'])
                ex = m.exemplars[-1] if openmetrics else None
                suffix = self._render_exemplar(ex) if ex else ""
                lines.append(f"{name}_bucket{{{inner}}} {m.total}{suffix}")
                lines.append(f"{name}_sum{lbl} {m.sum:g}")
                lines.append(f"{name}_count{lbl} {m.total}")
        if openmetrics:
            lines.append("# EOF")
        return "\n".join(lines) + "\n"


REGISTRY = Registry()

# -- fault-tolerance instrumentation ----------------------------------------
# (query/faults.py circuit breakers + remote retries; reference Kamon
# counters around PromQlRemoteExec / ShardHealthStats)

_BREAKER_STATE_VALUE = {"closed": 0.0, "half_open": 0.5, "open": 1.0}


def record_breaker_transition(endpoint: str, from_state: str, to_state: str) -> None:
    """Count a circuit-breaker state transition and expose the current
    state as a gauge (0 closed, 0.5 half-open, 1 open)."""
    REGISTRY.counter(
        "filodb_breaker_transitions", endpoint=endpoint,
        frm=from_state, to=to_state,
    ).inc()
    REGISTRY.gauge("filodb_breaker_state", endpoint=endpoint).set(
        _BREAKER_STATE_VALUE.get(to_state, -1.0)
    )


def record_remote_retry(endpoint: str) -> None:
    REGISTRY.counter("filodb_remote_retries", endpoint=endpoint).inc()


def record_partial_result(dataset: str) -> None:
    """A query answered with merged partials (some children lost)."""
    REGISTRY.counter("filodb_partial_results", dataset=dataset).inc()


def record_shard_reassignment(shard: int, damped: bool) -> None:
    """ShardManager ingestion-error handling: reassigned vs damper-DOWN,
    per shard so one flapping shard is distinguishable from many."""
    REGISTRY.counter(
        "filodb_shard_reassignments", shard=str(shard),
        outcome="down" if damped else "moved",
    ).inc()


# -- replicated shard plane (coordinator/replication.py) ---------------------


def record_replica_selection(which: str) -> None:
    """A remote dispatch served by its primary replica or a sibling."""
    REGISTRY.counter("filodb_replica_selection", which=which).inc()


def record_replica_failover(endpoint: str, reason: str) -> None:
    """A dispatch re-pinned away from a replica endpoint (breaker_open =
    routed around before calling; endpoint_failure = failed then moved)."""
    REGISTRY.counter(
        "filodb_replica_failovers", endpoint=endpoint, reason=reason,
    ).inc()


def record_replica_ack(outcome: str) -> None:
    """Ingest fan-out append outcome for one (shard, replica) leg."""
    REGISTRY.counter("filodb_replica_acks", outcome=outcome).inc()


def record_replica_watermark(shard: int, node: str, ts_ms: int) -> None:
    """Lag watermark: the max sample timestamp a replica has acked. A
    recovering replica serves queries only behind this mark."""
    REGISTRY.gauge(
        "filodb_replica_watermark_ms", shard=str(shard), node=node,
    ).set(float(ts_ms))


def record_rebalance(outcome: str) -> None:
    """Live shard rebalance: clean (effect log proved no concurrent
    ingest), replayed (tail re-replayed), rebuilt (full log replay),
    damped, or failed."""
    REGISTRY.counter("filodb_rebalance", outcome=outcome).inc()


def record_rebalance_standing_move() -> None:
    REGISTRY.counter("filodb_rebalance_standing_moves").inc()


# -- query-phase taxonomy ----------------------------------------------------

# the ONE canonical per-query phase set (doc/observability.md "Query
# observatory"). Mirrors FUSED_FALLBACK_REASONS: tools/check_spans.py lints
# every phase literal in the package against this tuple, and
# obs/querylog.PhaseRecorder rejects unknown names at runtime — a typo'd
# phase must fail loudly, never mint an undashboarded series.
#
# - parse_plan  — PromQL parse + logical-plan build + materialize
# - admission   — admission-control gate + batch-window queue wait
# - queue       — the two thread hops of the bounded query pool
#                 (coordinator/scheduler.QueryScheduler.run): submitted
#                 until a worker starts the plan, and the worker done until
#                 the caller runs again
# - stage       — superblock resolution (cache hit / extend / build+upload)
# - group       — group ids of the block's series for the aggregation's
#                 by/without (memoized on the block: ~0 on a hit; a miss
#                 regroups every label set and uploads an [S] int32)
# - dispatch    — the kernel launch itself (batched or solo)
# - transfer    — device→host result pull at the serving edge
# - render      — response encoding + write at the serving edge
# - other       — engine residual (everything the named phases don't cover,
#                 computed at query end so the phase sum equals wall time)
QUERY_PHASES = (
    "parse_plan", "admission", "queue", "stage", "group", "dispatch",
    "transfer", "render", "other",
)

# the ONE canonical set of parts of the ``stage`` phase, in the order a cold
# fused query pays them (``span(..., part=...)``; linted and refused at
# runtime exactly as the phases are). A part is booked only under a span
# that books ``stage``, and nested parts book exclusive time, so
# sum(parts) <= stage; what is left is bookkeeping between the parts.
#
# - lookup     — part-key index lookups, per shard
# - gather     — a histogram column: the table of chunk segments, one row a
#                chunk or write buffer in range (addresses and bounds; no
#                array is read). Any other stage: the per-partition
#                ``samples_in_range`` loop (chunk decode)
# - assemble   — a histogram column: the native pass over that table
#                (search, cast, pad: every element of [S, T, B] written
#                once) and the grid classification. Any other stage: the
#                numpy pad into [S, T(, B)]. And bucket-scheme unify, labels
# - h2d_shard  — per-shard block: ``device_put`` (and, on the CPU backend
#                only, the host mirror copies: elsewhere the staged arrays
#                are the mirrors)
# - readback   — taking staged arrays on the host (``ST.read_back``): a
#                mirror is an attribute away; an array without one is a D2H
#                copy the first time, and waits for the upload it reads
# - concat     — row-concatenate the shard blocks on the host: the whole
#                superblock, or only what the grid classification reads
#                when the device assembles it; and the deferred mirrors'
#                host copy at a superblock's first extension
# - h2d_super  — the superblock onto the device: assembled there from the
#                shards' blocks, or uploaded; and the ``le`` vector's upload
# - scheme     — base-2 exponential histograms only: the superblock's [S]
#                int32 scale / offset / n sidecars built from the partitions'
#                schemes and put on the device
STAGE_PARTS = (
    "lookup", "gather", "assemble", "h2d_shard", "readback", "concat",
    "h2d_super", "scheme",
)
_STAGE_PART_SET = frozenset(STAGE_PARTS)


# the executing query's PhaseRecorder (obs/querylog.py), activated per
# thread by ExecPlan.execute exactly like the QueryStats attribution
# target below: spans tagged with phase= and the fused dispatch path bump
# it without threading a context object through every signature
_phases_local = threading.local()


@contextlib.contextmanager
def activate_phases(rec):
    """Bind ``rec`` (an obs.querylog.PhaseRecorder, or None for a no-op)
    as this thread's phase-attribution target. Nests/restores like
    ``activate_stats``."""
    prev = getattr(_phases_local, "rec", None)
    _phases_local.rec = rec
    try:
        yield
    finally:
        _phases_local.rec = prev


def current_phases():
    return getattr(_phases_local, "rec", None)


# -- tracing ----------------------------------------------------------------

_trace_local = threading.local()


def new_trace_id() -> str:
    """Sixteen hex digits, at least one of them a letter that no number
    holds: the profiler parses an annotation's ``trace_id`` stat, and an id
    of decimal digits comes back as an int, ``12345e6789012345`` as inf —
    the spans of that request then join nothing on the trace (1 id in 800)."""
    while True:
        i = "%016x" % random.getrandbits(64)
        if i.strip("0123456789e"):
            return i


# span ids need to be distinct within a trace, not unguessable: 64 random
# bits at a tenth of a uuid4's cost (the hot path opens tens of spans)
new_span_id = new_trace_id


@dataclass(frozen=True)
class TraceContext:
    """The portable identity of an active span: what crosses thread pools
    (by reference, via ``current_span``/``activate``) and process
    boundaries (by value, via gRPC call metadata / HTTP headers)."""

    trace_id: str
    span_id: str
    parent_id: str | None = None

    # wire names, shared by the gRPC metadata keys and HTTP headers
    TRACE_ID_HEADER = "X-FiloDB-Trace-Id"
    PARENT_SPAN_HEADER = "X-FiloDB-Parent-Span"


@dataclass
class Span:
    name: str
    start_ns: int
    end_ns: int = 0
    children: list = field(default_factory=list)
    trace_id: str = ""
    span_id: str = field(default_factory=new_span_id)
    parent_id: str | None = None
    # free-form annotations (retries, breaker states, lost children, plan
    # args); must stay JSON-serializable — they cross the wire in to_dict()
    tags: dict = field(default_factory=dict)
    # per-node QueryStats delta (series/samples scanned, bytes staged, ...)
    stats: dict = field(default_factory=dict)

    @property
    def duration_ms(self) -> float:
        return (self.end_ns - self.start_ns) / 1e6

    @property
    def seconds(self) -> float:
        """The closed span's wall: what a caller observes into a histogram
        after the ``with`` block, so the span and the metric are one clock."""
        return (self.end_ns - self.start_ns) / 1e9

    def context(self) -> TraceContext:
        return TraceContext(self.trace_id, self.span_id, self.parent_id)

    def tree(self, depth=0) -> str:
        line = f"{'  ' * depth}{self.name}: {self.duration_ms:.2f}ms"
        if self.stats:
            brief = " ".join(f"{k}={v}" for k, v in self.stats.items() if v)
            if brief:
                line += f" [{brief}]"
        out = [line]
        for c in self.children:
            out.append(c.tree(depth + 1))
        return "\n".join(out)

    def to_dict(self) -> dict:
        """JSON form: the EXPLAIN ANALYZE / slow-query-log rendering and the
        in-band cross-node trace payload (durations, never raw clocks — the
        perf counters of two processes do not compare)."""
        d = {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "duration_ms": round(self.duration_ms, 3),
        }
        if self.tags:
            d["tags"] = self.tags
        if self.stats:
            d["stats"] = self.stats
        d["children"] = [c.to_dict() for c in self.children]
        return d

    @classmethod
    def from_dict(cls, d: dict, trace_id: str | None = None,
                  parent_id: str | None = None) -> "Span":
        """Rebuild a span tree from its wire form. ``trace_id``/``parent_id``
        override the remote identifiers so a grafted subtree joins the LOCAL
        trace (the stitch rewrites linkage; durations are preserved)."""
        s = cls(str(d.get("name", "remote")), 0)
        s.end_ns = int(float(d.get("duration_ms", 0.0)) * 1e6)
        s.trace_id = trace_id if trace_id is not None else str(d.get("trace_id", ""))
        s.span_id = str(d.get("span_id") or new_span_id())
        s.parent_id = parent_id if parent_id is not None else d.get("parent_id")
        s.tags = dict(d.get("tags") or {})
        s.stats = dict(d.get("stats") or {})
        s.children = [
            cls.from_dict(c, trace_id=s.trace_id, parent_id=s.span_id)
            for c in (d.get("children") or [])
        ]
        return s


_UNSET = object()


class span:
    """Nested timing spans (Kamon.runWithSpan analog), as a context manager
    yielding the :class:`Span`. The thread-local current span is the default
    parent; an explicit ``parent=`` Span wires a span into a trace across
    thread hops (a worker thread has no thread-local context — the submitter
    captures ``current_span()`` and either passes it here or re-activates it
    via ``activate``). The root span of a thread is retrievable via
    current_trace().

    ``phase=`` additionally attributes the span's wall time to the active
    query's phase decomposition (QUERY_PHASES; the recorder bound via
    ``activate_phases``) — the query-observatory capture point for phases
    that already run under a span (e.g. ``fused:stage``).

    ``part=`` books the wall under a part of the phase that ENCLOSES the
    span (STAGE_PARTS; an unknown name raises). Only a span running under a
    ``phase="stage"`` span on this thread books; elsewhere (the reference
    tree stages without a phase) it is a plain span. A part span nested in
    another books its own wall and the outer one books the rest.

    Every span also holds a ``jax.profiler.TraceAnnotation`` of its name
    carrying its ``trace_id``: a no-op in jax's C++ without a profiler
    session; with one, the span is a host event on the profile's clock."""

    __slots__ = ("_s", "_cur", "_phase", "_part", "_outer_phase",
                 "_outer_inner_ns", "_ann")

    def __init__(self, name: str, parent=_UNSET, phase: str | None = None,
                 part: str | None = None, **tags):
        if part is not None and part not in _STAGE_PART_SET:
            raise ValueError(
                f"unknown stage part {part!r} (canonical set: "
                f"{sorted(_STAGE_PART_SET)})"
            )
        self._phase, self._part = phase, part
        self._cur = cur = getattr(_trace_local, "current", None)
        eff_parent = cur if cur is not None else (
            None if parent is _UNSET else parent)
        self._s = s = Span(name, 0)
        if tags:
            s.tags.update(tags)
        if eff_parent is not None:
            s.trace_id = eff_parent.trace_id
            s.parent_id = eff_parent.span_id
            # list.append is atomic under the GIL: children may attach from
            # concurrent pool threads re-activating the same parent
            eff_parent.children.append(s)
        else:
            s.trace_id = new_trace_id()

    def __enter__(self) -> "Span":
        s = self._s
        if s.parent_id is None:
            _trace_local.root = s
        _trace_local.current = s
        if self._phase is not None:
            self._outer_phase = getattr(_trace_local, "phase", None)
            _trace_local.phase = self._phase
        if self._part is not None:
            self._outer_inner_ns = getattr(_trace_local, "part_inner_ns", 0)
            _trace_local.part_inner_ns = 0
        self._ann = TraceAnnotation(s.name, trace_id=s.trace_id)
        self._ann.__enter__()
        s.start_ns = time.perf_counter_ns()
        return s

    def __exit__(self, *exc) -> None:
        s = self._s
        s.end_ns = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        _trace_local.current = self._cur
        wall_ns = s.end_ns - s.start_ns
        if self._phase is not None:
            _trace_local.phase = self._outer_phase
            rec = current_phases()
            if rec is not None:
                rec.add(self._phase, wall_ns / 1e9)
        if self._part is not None:
            inner_ns = _trace_local.part_inner_ns
            _trace_local.part_inner_ns = self._outer_inner_ns + wall_ns
            if getattr(_trace_local, "phase", None) == "stage":
                rec = current_phases()
                if rec is not None:
                    rec.add_part(self._part, (wall_ns - inner_ns) / 1e9)


@contextlib.contextmanager
def activate(span_obj: Span | None):
    """Re-activate a captured span as this thread's current trace context —
    the cross-thread propagation primitive (``execute_children`` captures the
    dispatching span and re-activates it inside pool workers so child spans
    attach under the right parent instead of starting orphan traces)."""
    if span_obj is None:
        yield
        return
    prev = getattr(_trace_local, "current", None)
    prev_root = getattr(_trace_local, "root", None)
    _trace_local.current = span_obj
    _trace_local.root = span_obj
    try:
        yield
    finally:
        _trace_local.current = prev
        _trace_local.root = prev_root


def current_span() -> Span | None:
    """The innermost active span on this thread (the capture point for
    cross-thread and cross-node propagation)."""
    return getattr(_trace_local, "current", None)


def current_trace() -> Span | None:
    return getattr(_trace_local, "root", None)


def trace_to_dict(trace) -> dict | None:
    """Normalize a QueryResult.trace (local Span or already-rendered dict
    from a remote peer) to its JSON form."""
    if trace is None:
        return None
    return trace.to_dict() if isinstance(trace, Span) else trace


# -- slow-query log ---------------------------------------------------------


class SlowQueryLog:
    """Ring buffer of queries that exceeded the slow-query threshold, each
    entry carrying the PromQL, duration, QueryStats and the rendered trace
    tree (served at /debug/slow_queries; counted as
    filodb_slow_queries_total in /metrics)."""

    def __init__(self, max_entries: int = 64):
        self._entries: deque = deque(maxlen=max_entries)
        self._lock = threading.Lock()

    def configure(self, max_entries: int) -> None:
        with self._lock:
            self._entries = deque(self._entries, maxlen=max(1, int(max_entries)))

    def record(self, promql: str, duration_s: float, dataset: str = "",
               trace=None, stats: dict | None = None,
               query_id: str | None = None) -> None:
        entry = {
            "time": time.time(),
            "dataset": dataset,
            "promql": promql,
            "duration_s": round(float(duration_s), 6),
            "stats": stats or {},
            "trace": trace_to_dict(trace),
        }
        if query_id:
            # link to the query observatory: the same execution's
            # exemplar-level cost record (obs/querylog.py) is one GET away
            # instead of a disjoint debug surface
            entry["query_id"] = query_id
            entry["profile"] = f"/api/v1/query_profile?id={query_id}"
        with self._lock:
            self._entries.append(entry)
        REGISTRY.counter("filodb_slow_queries", dataset=dataset).inc()

    def entries(self) -> list[dict]:
        """Newest first."""
        with self._lock:
            return list(reversed(self._entries))

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()


SLOW_QUERY_LOG = SlowQueryLog()


# the ONE fused-fallback reason taxonomy (doc/perf.md's fallback table
# documents each entry; tools/check_metrics.py lints code and table against
# each other). Tree-fallback reasons delegate to the reference scatter
# tree; the grid_* entries are DEGRADED-KERNEL reasons — the dispatch
# stays one fused program, it just lost its jitter-tolerant fast variant.
FUSED_FALLBACK_REASONS = frozenset({
    "partial_results", "dispatcher", "mixed_schemas", "hist_scheme",
    "hist_op", "hist_func", "hist_quantile_scalar", "mesh_unsupported",
    "grid_jitter", "grid_holes", "standing_nondecomposable",
    "rollup_ineligible", "stage_span",
})


def record_fused_fallback(reason: str) -> None:
    """A FusedAggregateExec delegated to its reference scatter tree at
    runtime — or, for the ``grid_*`` reasons, degraded a jittered/holey
    grid to the general fused kernel. Exposed as
    ``filodb_fused_fallback_total{reason=...}`` so operators see
    fused-path coverage at aggregate level (the reason was previously only
    a span tag, visible per-query only); doc/perf.md documents the reason
    taxonomy, and an unknown reason label is a bug caught here rather than
    minted as an undashboarded series."""
    if reason not in FUSED_FALLBACK_REASONS:
        reason = "unknown"
    REGISTRY.counter("filodb_fused_fallback", reason=reason).inc()


ROLLUP_EVENTS = frozenset({"add", "build", "fold", "rebuild", "retire",
                           "error"})


def record_rollup_event(event: str) -> None:
    """Rollup-maintainer lifecycle accounting, exposed as
    ``filodb_rollup_maintenance_total{event=...}`` (doc/perf.md "Sketch
    rollup tier"). Same closed-taxonomy discipline as
    :func:`record_fused_fallback` — an unknown event collapses to
    ``unknown`` instead of minting an undashboarded series."""
    if event not in ROLLUP_EVENTS:
        event = "unknown"
    REGISTRY.counter("filodb_rollup_maintenance", event=event).inc()


def record_rollup_serve(kind: str) -> None:
    """A query was served from rollup blocks (querylog ``path=rollup``),
    by serve kind: ``window`` (per-series range function), ``agg`` (fused
    aggregate over moments or merged sketches), ``hist_quantile``
    (classic-histogram bucket fold from counter rollups)."""
    REGISTRY.counter("filodb_rollup_serves", kind=kind).inc()


def record_rollup_chooser(action: str) -> None:
    """Workload-chooser decision: ``add`` (a repeatedly-seen long-range
    fingerprint earned a rollup) or ``retire`` (an idle rollup was
    dropped)."""
    REGISTRY.counter("filodb_rollup_chooser", action=action).inc()


def record_stage_insert_drop(reason: str) -> None:
    """A freshly staged block was NOT inserted into the shard staging cache
    because ingest effects since its stage provably-or-possibly touched its
    range. Exposed as ``filodb_stage_cache_insert_dropped_total{reason}``
    (reasons: overlap | full_clear | log_truncated); a sustained non-zero
    rate under fine-grained ingest is the cache-starvation signal the
    interval-aware insert re-check exists to eliminate for disjoint-range
    ingest (doc/observability.md)."""
    REGISTRY.counter("filodb_stage_cache_insert_dropped", reason=reason).inc()


def record_superblock_event(outcome: str) -> None:
    """Superblock cache maintenance outcome under ingest, exposed as
    ``filodb_superblock_maintenance_total{outcome}``:

    - ``revalidate`` — ingest since the entry was built was provably
      disjoint from its range; the entry was re-stamped and served as-is
    - ``extend`` — overlapping live-edge appends were absorbed by extending
      the device superblock in place (the single-dispatch path survives)
    - ``extend_abort`` — an extension raced a conflicting ingest and was
      discarded
    - ``restage`` — extension preconditions failed; full rebuild paid"""
    REGISTRY.counter("filodb_superblock_maintenance", outcome=outcome).inc()


def record_downsample_claim(event: str) -> None:
    """Distributed-downsample claim lifecycle, exposed as
    ``filodb_downsample_claims_total{event}``: ``steal`` (stale claim
    broken), ``release`` (owner released its own claim), and
    ``tombstone_restored`` (a release found its claim had been stolen and
    re-created mid-release — the renamed tombstone was put back instead of
    deleting the new owner's claim)."""
    REGISTRY.counter("filodb_downsample_claims", event=event).inc()


# -- kernel dispatch instrumentation ----------------------------------------

# the executing query's QueryStats, activated per thread by
# ExecPlan.execute (and re-activated in pool workers through the same
# path): kernel entry points attribute their dispatch seconds to the query
# WITHOUT threading a context object through every ops/ signature
_stats_local = threading.local()


@contextlib.contextmanager
def activate_stats(stats):
    """Bind ``stats`` (a QueryStats) as this thread's attribution target for
    record_kernel_dispatch. Nests/restores like ``activate``."""
    prev = getattr(_stats_local, "stats", None)
    _stats_local.stats = stats
    try:
        yield
    finally:
        _stats_local.stats = prev


def current_stats():
    return getattr(_stats_local, "stats", None)


def record_kernel_dispatch(kernel: str, seconds: float,
                           compiled: bool | None = None,
                           key: dict | None = None) -> None:
    """Latency histogram around an ops/ kernel entry point; ``compiled``
    (a grown jit cache across the call means this dispatch compiled) goes
    on to the kernel observatory, which books the compile-cache counters.
    Also attributes the dispatch seconds to the active query's QueryStats
    (kernel_ns) — the per-query/per-tenant device accounting feed. Pure
    host-side bookkeeping: no device sync is added around the (async)
    dispatch.

    ``key`` (executable-key parts: variant/epilogue/shapes/mesh/batch —
    obs.kernels.KEY_DIMS) additionally feeds the kernel & compile
    observatory's per-executable registry; the family dimension is
    ``kernel`` itself, so the registry and this histogram's ``kernel=``
    label stay the same vocabulary."""
    REGISTRY.histogram("filodb_kernel_dispatch_seconds", kernel=kernel).observe(seconds)
    st = current_stats()
    if st is not None:
        st.bump(kernel_ns=int(seconds * 1e9))
    # kernel & compile observatory (obs/kernels.py): per-executable
    # compile/dispatch/device-cost attribution + recompile-storm detection
    from .obs.kernels import KERNELS

    KERNELS.observe_dispatch(kernel, seconds, compiled=compiled, parts=key)


# -- sampling profiler ------------------------------------------------------


class SamplingProfiler:
    """Periodic all-thread stack sampler (reference SimpleProfiler.java:19,
    launched at server start with config filodb.profiler)."""

    def __init__(self, interval_s: float = 0.01, top_frames: int = 1):
        self.interval_s = interval_s
        self.top_frames = top_frames
        self.samples: Counter = Counter()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def start(self):
        # idempotent: a second start() must not leak the first sampler
        # thread (it would double-count every stack forever)
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop.clear()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def stop(self):
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=1)

    def _run(self):
        me = threading.get_ident()
        while not self._stop.wait(self.interval_s):
            for tid, frame in sys._current_frames().items():
                if tid == me:
                    continue
                stack = traceback.extract_stack(frame, limit=self.top_frames + 4)
                if not stack:
                    continue
                top = stack[-1]
                self.samples[f"{top.name} ({top.filename.rsplit('/', 1)[-1]}:{top.lineno})"] += 1

    def report(self, n: int = 20) -> str:
        total = sum(self.samples.values()) or 1
        lines = [f"{cnt / total * 100:5.1f}%  {name}" for name, cnt in self.samples.most_common(n)]
        return "\n".join(lines)
