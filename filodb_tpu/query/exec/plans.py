"""ExecPlan tree (reference query/exec/ExecPlan.scala — execute:356 runs the
leaf's doExecute then folds transformers; NonLeafExecPlan:674 scatter-gathers
children. Here children run via a dispatcher abstraction so the same tree
shape serves in-process, mesh-sharded, and (later) remote execution).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ...core.filters import ColumnFilter
from ...core.schemas import METRIC_TAG, ColumnType
from ...metrics import REGISTRY, span
from ...ops import aggregations as AGG
from ...ops import staging as ST
from ..rangevector import Grid, QueryResult, QueryStats, RawGrid, ScalarResult
from .transformers import (
    _DROP_NAME_KEEP,
    AbsentFunctionMapper,
    PeriodicSamplesMapper,
    QueryError,
    _strip_metric,
    apply_binop,
)


@dataclass
class QueryContext:
    """Per-query execution context (reference QueryContext/QuerySession)."""

    memstore: Any  # TimeSeriesMemStore
    dataset: str
    max_series: int = 1_000_000
    max_samples: int = 500_000_000
    max_result_bytes: int = 1 << 30
    deadline_s: float = 60.0
    stats: QueryStats = field(default_factory=QueryStats)
    # fault tolerance (query/faults.py): tolerate lost children in merge
    # nodes, collecting structured warnings instead of aborting
    allow_partial_results: bool = False
    warnings: list = field(default_factory=list)
    # dispatch hooks: dispatcher wraps child execution (fault injection),
    # retry_policy/breakers override the defaults for remote children
    dispatcher: Any = None
    retry_policy: Any = None
    breakers: Any = None
    # tracing (metrics.py): the query's root Span. ExecPlan.execute falls
    # back to it as parent when a thread has no active span — the scheduler
    # pool hop between the engine and the root plan node
    trace_root: Any = None
    # cross-query micro-batching (query/scheduler.DispatchScheduler):
    # FusedAggregateExec routes its kernel launch through it so concurrent
    # queries sharing a superblock coalesce into ONE batched dispatch.
    # None (or a disabled scheduler) = the plain unbatched launch.
    dispatch_scheduler: Any = None
    # query observatory (obs/querylog.py): the per-query PhaseRecorder the
    # engine attaches (ExecPlan.execute re-binds it per thread alongside
    # stats) and the free-form path annotations (fused/fallback/batched/
    # grid class) execution drops for the query's cost record
    phases: Any = None
    obs: dict = field(default_factory=dict)
    _start_time: float = field(default_factory=time.monotonic)

    def check_deadline(self) -> None:
        """Enforced between plan nodes (reference per-plan enforcedLimits +
        query timeout)."""
        elapsed = time.monotonic() - self._start_time
        if elapsed > self.deadline_s:
            from .transformers import QueryDeadlineExceeded

            raise QueryDeadlineExceeded(
                f"query exceeded deadline: {elapsed:.1f}s > {self.deadline_s:.1f}s"
            )

    def remaining_deadline_s(self) -> float:
        """Unspent deadline budget — what retries and per-RPC timeouts must
        fit inside (never the full deadline_s)."""
        return max(0.0, self.deadline_s - (time.monotonic() - self._start_time))


class ExecPlan:
    """Base: leaf plans implement do_execute; transformers fold after."""

    transformers: list

    def __init__(self):
        self.transformers = []

    def execute(self, ctx: QueryContext) -> QueryResult:
        from ...metrics import (
            Span, activate_phases, activate_stats, current_span,
        )

        t0 = time.perf_counter_ns()
        ctx.check_deadline()
        # parent: the thread's active span (nested execution, or a pool
        # worker re-activated via metrics.activate), else the query's root
        # span (the engine -> scheduler-pool hop)
        parent = current_span() or ctx.trace_root
        # bind the query's stats as this thread's kernel-attribution target:
        # ops/ dispatch wrappers bump kernel_ns on it without any context
        # threading (pool workers re-enter here per child, so they bind
        # too); the phase recorder binds identically so phase-tagged spans
        # and the fused dispatch path decompose into the right query
        with activate_stats(ctx.stats), \
                activate_phases(getattr(ctx, "phases", None)), \
                span(type(self).__name__, parent=parent) as s:
            args = self.args_str()
            if args:
                s.tags["plan"] = args
            before = ctx.stats.snapshot()
            peer_stats = None
            res = self.do_execute(ctx)
            if res.stats is not ctx.stats and not res.stats.is_empty():
                # a remote child returns the peer's QueryStats in-band:
                # merge them into the query-wide stats exactly once, here,
                # then alias so a parent re-returning this result object
                # cannot double-merge
                peer_stats = res.stats.as_dict()
                ctx.stats.merge(res.stats)
                res.stats = ctx.stats
            rt = res.trace
            if rt is not None and not isinstance(rt, Span):
                # a remote child's span tree (rendered dict): stitch it
                # under this node's span, rewriting linkage into the local
                # trace — the cross-node half of trace propagation
                s.children.append(
                    Span.from_dict(rt, trace_id=s.trace_id, parent_id=s.span_id)
                )
                res.trace = None
            if res.warnings:
                # remote children return their own partial-result warnings
                # in-band; hoist them onto the context so they survive
                # transformer folding (which rebuilds QueryResults) and
                # reach the query's final result. Remote children run on
                # pool threads, so this must be a single atomic extend —
                # dedup happens once at the engine edge.
                ctx.warnings.extend(res.warnings)
            for tr in self.transformers:
                with span(type(tr).__name__) as ts:
                    targs = tr_args(tr)
                    if targs:
                        ts.tags["plan"] = targs
                    res = apply_transformer(tr, res, ctx)
            # remote child: the peer's own stats are exact attribution; local
            # nodes get the (inclusive, best-effort across concurrent
            # siblings) delta of the query-wide stats
            s.stats = peer_stats if peer_stats is not None else ctx.stats.delta_since(before)
        ctx.stats.bump(cpu_ns=time.perf_counter_ns() - t0)
        return res

    def do_execute(self, ctx: QueryContext) -> QueryResult:
        raise NotImplementedError

    def children(self) -> Sequence["ExecPlan"]:
        return ()

    # -- plan printing (reference printTree golden tests) -----------------

    def args_str(self) -> str:
        return ""

    def print_tree(self, level: int = 0) -> str:
        pad = "-" * level
        lines = []
        for tr in reversed(self.transformers):
            lines.append(f"{pad}T~{type(tr).__name__}({tr_args(tr)})")
            pad = "-" * (level + len(lines))
        lines.append(f"{pad}E~{type(self).__name__}({self.args_str()})")
        for c in self.children():
            lines.append(c.print_tree(level + len(lines)))
        return "\n".join(lines)


def tr_args(tr) -> str:
    if isinstance(tr, PeriodicSamplesMapper):
        return f"fn={tr.function} window={tr.window_ms} step={tr.step_ms}"
    return ""


def apply_transformer(tr, res: QueryResult, ctx: QueryContext) -> QueryResult:
    if isinstance(tr, PeriodicSamplesMapper):
        return QueryResult(grids=tr.apply_raw(res.raw_grids), stats=res.stats)
    if isinstance(tr, AbsentFunctionMapper):
        return QueryResult(grids=tr.apply(res.grids), stats=res.stats)
    out_grids = tr.apply(res.grids)
    return QueryResult(grids=out_grids, scalar=res.scalar, stats=res.stats)


# ---------------------------------------------------------------------------
# Leaf: select raw partitions from one shard and stage to device
# ---------------------------------------------------------------------------

# Counter staging is FUNCTION-driven (the reference applies counter correction
# only inside rate-family RangeFunctions — RateFunctions.scala:230 — never at
# the read path; a plain selector over a counter returns raw samples):
#   corrected — reset-corrected minus baseline; only these functions may read it
_CORRECTED_FNS = frozenset({"rate", "increase", "irate"})
#   shifted — raw minus per-series baseline (no correction): shift-invariant
#   functions get exact f32 math even on 1e15-magnitude counters
_SHIFTED_FNS = frozenset({
    "delta", "deriv",
    "stddev_over_time", "stdvar_over_time", "z_score",
    "median_absolute_deviation_over_time",
})
# value-independent functions (count/present/absent_over_time, timestamp)
# deliberately fall through to "raw": they never read staged values, so they
# share the plain-selector block and its cache entry
#   diff — f64-exact adjacent differences: these are pure functions of the
#   diff sequence, and no f32 shift of the values preserves both tiny
#   adjacent changes and a 1e9-magnitude reset cliff
_DIFF_FNS = frozenset({"changes", "resets", "idelta"})
#   everything else (plain selector/last, min/max/sum/avg_over_time,
#   quantile_over_time, ...) stages raw values


def _stage_mode_for_function(func: str | None) -> str:
    """Staging mode for a counter column given the range function that will
    read it (default: raw selector read)."""
    if func in _CORRECTED_FNS:
        return "corrected"
    if func in _SHIFTED_FNS:
        return "shifted"
    if func in _DIFF_FNS:
        return "diff"
    return "raw"


def _counter_stage_mode(transformers) -> str:
    """Pick the staging mode for a counter column from the range function the
    leaf's PeriodicSamplesMapper will apply (default: raw selector read)."""
    func = None
    for tr in transformers:
        if isinstance(tr, PeriodicSamplesMapper):
            func = tr.function
            break
    return _stage_mode_for_function(func)


def staged_block_for(ctx: "QueryContext", shard, ids, cache_key, col_name: str,
                     start_ms: int, end_ms: int, stage_mode: str):
    """Get a shard's HBM-resident staged block for a selection THROUGH the
    shard's staging cache: serve a clean hit, incrementally repair a dirty
    one (ST.append_to_block — live-edge panels pay only the tail), else
    stage fresh and insert under the shard-version check. The ONE cached
    staging path, shared by SelectRawPartitionsExec and the fused
    single-dispatch aggregate's superblock builder so both have identical
    repair/invalidation semantics.

    Cache-key layout ``(filters, start_ms, end_ms, ...)`` is load-bearing:
    the shard's selective invalidation (_invalidate_stage_range) reads
    k[1]/k[2] as the staged range for its overlap check."""
    with shard._lock:
        hit = shard.stage_cache.get(cache_key)
        version_at_stage = shard.version
        claimed = False
        if hit is not None and hit.repairing:
            # another thread is mid-repair: serving its pre-repair block
            # would miss acknowledged samples — restage fresh
            hit = None
        elif hit is not None and hit.dirty:
            dirty_lo = hit.dirty_lo
            hit.dirty = False
            hit.dirty_lo = hit.dirty_hi = None  # interval consumed by repair
            hit.repairing = True
            claimed = True
    if hit is not None and claimed:
        # in-range ingest landed since this block was staged: try the
        # incremental append repair; on failure fall through to a fresh
        # stage. The repair returns a NEW block (old one stays consistent
        # for in-flight readers) swapped in atomically.
        repaired = None
        try:
            repaired = ST.append_to_block(
                shard, hit.block, ids, col_name, end_ms, stage_mode,
                dirty_lo=dirty_lo,
            )
        finally:
            new_nbytes = (ST.staged_nbytes(repaired)
                          if repaired is not None else 0)
            with shard._lock:
                hit.repairing = False
                if repaired is not None:
                    hit.block = repaired
                    if new_nbytes != hit.nbytes:
                        # the repaired block's device arrays may be wider:
                        # keep entry bytes (and with them the ledger and
                        # the eviction budget) true to what is pinned. Only
                        # adjust the ledger while the entry is still CACHED
                        # — a concurrent clear/eviction during the unlocked
                        # repair already credited the old bytes, and this
                        # block is then transient (never ledger-pinned)
                        if shard.stage_cache.get(cache_key) is hit:
                            shard.ledger.free(hit.nbytes, reason="replace")
                            shard.ledger.alloc(new_nbytes)
                        hit.nbytes = new_nbytes
                elif shard.stage_cache.get(cache_key) is hit:
                    # failed (or raised): never leave a stale entry
                    del shard.stage_cache[cache_key]
                    shard.ledger.free(hit.nbytes, reason="drop")
        if repaired is None:
            hit = None
        else:
            ctx.stats.bump(cache_extends=1)
    if hit is not None:
        if not claimed:
            ctx.stats.bump(cache_hits=1)
        return hit.block
    block = ST.stage_from_shard(
        shard, ids, col_name, start_ms, end_ms, mode=stage_mode,
    )
    # true device footprint (ops/staging.staged_nbytes): the SAME number the
    # cache entry, the byte-budget eviction, and the device ledger account
    # — the drift check walks the cache with this exact function
    nbytes = ST.staged_nbytes(block)
    ctx.stats.bump(bytes_staged=nbytes, cache_misses=1)
    with span("stage:h2d_shard", part="h2d_shard"):
        block.to_device(keep_host=True)  # mirrors enable append repair
    ST.book_mirrors("shard", block.mirrored)
    REGISTRY.counter("filodb_stage_h2d_bytes", part="h2d_shard").inc(nbytes)
    # byte-budgeted eviction, oldest entry first (the staging analog of
    # BlockManager reclaim under memory pressure). All cache mutations run
    # under the shard lock (the shard's selective invalidation iterates the
    # dict under it). The insert guard is INTERVAL-AWARE: an ingest that
    # landed mid-stage ran its invalidation before this entry existed, so
    # the entry may only be cached when the shard's effect log PROVES every
    # version bump since version_at_stage was disjoint from the staged
    # range (otherwise sustained fine-grained ingest — many small batches —
    # would drop every insert and starve the cache forever, re-paying full
    # stages despite the selective-invalidation machinery).
    with shard._lock:
        drop_reason = None
        if shard.version != version_at_stage:
            drop_reason = shard._ingest_effects_since_locked(
                version_at_stage, start_ms, end_ms
            )
        if drop_reason is None:
            from ...memstore.shard import StageEntry

            # every shard of the memstore (all datasets) stages onto one
            # device: each gets its slice of the device's stage-cache
            # share, capped by the knob
            budget = ST.device_cache_budget(
                ST.STAGE_CACHE_DEVICE_SHARE
                / max(ctx.memstore.local_shard_count(), 1),
                shard.config.stage_cache_bytes,
            )
            # a racing same-key stage (two queries sharing a leaf selector
            # both missed) may have inserted already: credit its entry or
            # the overwrite below would leak its ledger balance forever
            raced = shard.stage_cache.pop(cache_key, None)
            if raced is not None:
                shard.ledger.free(raced.nbytes, reason="replace")
            used = sum(e.nbytes for e in shard.stage_cache.values())
            while shard.stage_cache and used + nbytes > budget:
                oldest = next(iter(shard.stage_cache))
                evicted = shard.stage_cache.pop(oldest)
                used -= evicted.nbytes
                shard.ledger.free(evicted.nbytes, reason="evict")
            shard.stage_cache[cache_key] = StageEntry(block, nbytes)
            shard.ledger.alloc(nbytes)
    if drop_reason is not None:
        from ...metrics import record_stage_insert_drop

        record_stage_insert_drop(drop_reason)
    return block


class SelectRawPartitionsExec(ExecPlan):
    """reference MultiSchemaPartitionsExec:26 + SelectRawPartitionsExec:161 —
    schema discovery, partition lookup, then staging (rangeVectors analog).

    Produces a QueryResult carrying RawGrids (one per schema found)."""

    def __init__(
        self,
        shard_num: int,
        filters: Sequence[ColumnFilter],
        start_ms: int,
        end_ms: int,
        column: Optional[str] = None,
    ):
        super().__init__()
        self.shard_num = shard_num
        self.filters = tuple(filters)
        self.start_ms = start_ms
        self.end_ms = end_ms
        self.column = column

    def args_str(self) -> str:
        fs = ",".join(f"{f.column}{f.op}{f.value}" for f in self.filters)
        return f"shard={self.shard_num} filters=[{fs}] range=[{self.start_ms},{self.end_ms}]"

    def do_execute(self, ctx: QueryContext) -> QueryResult:
        shard = ctx.memstore.shard(ctx.dataset, self.shard_num)
        pids = shard.lookup_partitions(self.filters, self.start_ms, self.end_ms)
        column_override = None
        hist_bucket_le = None
        if not len(pids):
            # classic-histogram suffix rewrite (reference
            # MultiSchemaPartitionsExec :49-80): m_sum / m_count map to the
            # histogram schema's sum/count columns; m_bucket{le=...} selects
            # one bucket of the native histogram
            rewritten, column_override, hist_bucket_le = _histogram_suffix_rewrite(self.filters)
            if rewritten is not None:
                pids = shard.lookup_partitions(rewritten, self.start_ms, self.end_ms)
        if len(pids) > ctx.max_series:
            raise QueryError(f"query selects {len(pids)} series > limit {ctx.max_series}")
        if shard.odp_store is not None and len(pids):
            shard.odp_page_in(pids, self.start_ms, self.end_ms)
        # group by schema (multi-schema metric support), and base-2
        # exponential histograms apart from explicit ones: a grid of the
        # former carries a scheme a series, one of the latter one ``le``
        by_schema: dict[tuple, list[int]] = {}
        for pid in pids:
            part = shard.partition(int(pid))
            by_schema.setdefault(
                (part.schema.name, part.bucket_scheme is not None), []
            ).append(int(pid))
        mixed = {name for name, b in by_schema if b and (name, False) in by_schema}
        res = QueryResult()
        res.raw_grids = []
        for (schema_name, base2), ids in by_schema.items():
            # long local scans must respect the query deadline between
            # schema groups, not just at plan entry
            ctx.check_deadline()
            parts = [shard.partition(p) for p in ids]
            schema = parts[0].schema
            col_name = self.column or column_override or schema.value_column
            try:
                col = schema.column(col_name)
            except KeyError:
                col_name = schema.value_column
                col = schema.column(col_name)
            is_hist = col.ctype == ColumnType.HISTOGRAM
            is_counter = col.is_counter
            is_delta = col.is_delta
            stage_mode = (
                _counter_stage_mode(self.transformers)
                if is_counter and not is_delta and not is_hist
                else "raw"
            )
            # staging cache: repeated queries over the same selection reuse
            # the HBM-resident decoded block until new data LANDS IN RANGE
            # (the north-star "decoded chunk windows staged to HBM"; the
            # shard invalidates overlapping entries selectively on ingest —
            # shard._invalidate_stage_range — so live scrapes beyond a
            # historical panel's range never force a re-stage).
            cache_key = (
                self.filters, self.start_ms, self.end_ms, col_name, schema_name,
                stage_mode,
            ) + (("base2" if base2 else "explicit",)
                 if schema_name in mixed else ())
            block = staged_block_for(
                ctx, shard, ids, cache_key, col_name, self.start_ms,
                self.end_ms, stage_mode,
            )
            ctx.stats.bump(
                series_scanned=len(ids),
                samples_scanned=ST.staged_samples([block]),
            )
            if ctx.stats.samples_scanned > ctx.max_samples:
                raise QueryError(
                    f"query would scan {ctx.stats.samples_scanned} samples > "
                    f"limit {ctx.max_samples}"
                )
            les = parts[0].bucket_les if is_hist and not base2 else None
            # the schemes the block was staged on: a partition's may have
            # widened since (memstore/partition.take_scheme)
            schemes = block.schemes if is_hist and base2 else None
            labels = [dict(p.tags) for p in parts]
            if is_hist and hist_bucket_le is not None and les is not None:
                # m_bucket{le=...}: slice one bucket into a scalar block
                sliced = _slice_bucket(block, les, hist_bucket_le)
                if sliced is None:
                    continue  # no such bucket
                block, le_str = sliced
                labels = [dict(l, le=le_str) for l in labels]
                is_hist = False
                is_counter = True
            res.raw_grids.append(
                RawGrid(
                    block=block,
                    labels=labels,
                    schema_name=schema_name,
                    value_column=col_name,
                    is_counter=is_counter,
                    is_delta=is_delta,
                    is_histogram=is_hist,
                    les=les if is_hist else None,
                    schemes=schemes if is_hist else None,
                )
            )
        return res


class EmptyResultExec(ExecPlan):
    def do_execute(self, ctx: QueryContext) -> QueryResult:
        return QueryResult()


class ChunkMetaExec(ExecPlan):
    """Chunk metadata debug query (reference SelectChunkInfosExec /
    _filodb_chunkmeta_all): per-series list of resident chunks."""

    def __init__(self, shard_num, filters, start_ms, end_ms):
        super().__init__()
        self.shard_num = shard_num
        self.filters = tuple(filters)
        self.start_ms = start_ms
        self.end_ms = end_ms

    def do_execute(self, ctx: QueryContext) -> QueryResult:
        shard = ctx.memstore.shard(ctx.dataset, self.shard_num)
        pids = shard.lookup_partitions(self.filters, self.start_ms, self.end_ms)
        out = []
        for pid in pids:
            part = shard.partition(int(pid))
            out.append(
                {
                    "labels": dict(part.tags),
                    "schema": part.schema.name,
                    "numChunks": len(part.chunks),
                    "bufferedSamples": part.num_samples() - sum(c.n for c in part.chunks),
                    "chunks": [
                        {"startTime": c.start_ts, "endTime": c.end_ts, "numRows": c.n,
                         "encodedBytes": c.nbytes_encoded}
                        for c in part.chunks_in_range(self.start_ms, self.end_ms)
                    ],
                }
            )
        res = QueryResult(metadata=out)
        res.result_type = "metadata"
        return res


class RawChunkExportExec(ExecPlan):
    """Top-level m[5m] raw export (reference SelectRawPartitionsExec without
    periodic mapping): returns actual samples."""

    def __init__(self, shard_num, filters, start_ms, end_ms, column=None):
        super().__init__()
        self.shard_num = shard_num
        self.filters = tuple(filters)
        self.start_ms = start_ms
        self.end_ms = end_ms
        self.column = column

    def do_execute(self, ctx: QueryContext) -> QueryResult:
        shard = ctx.memstore.shard(ctx.dataset, self.shard_num)
        pids = shard.lookup_partitions(self.filters, self.start_ms, self.end_ms)
        raw = []
        for pid in pids:
            part = shard.partition(int(pid))
            col = self.column or part.schema.value_column
            ts, vals = part.samples_in_range(self.start_ms, self.end_ms, col)
            if len(ts):
                raw.append((dict(part.tags), ts, vals))
        res = QueryResult(raw=raw)
        res.result_type = "matrix"
        return res


def _histogram_suffix_rewrite(filters):
    """m_sum/m_count/m_bucket -> base histogram metric + column/bucket
    selection. Returns (rewritten_filters | None, column | None, le | None)."""
    from ...core.schemas import METRIC_TAG

    metric = None
    for f in filters:
        if f.column == METRIC_TAG and f.op == "=":
            metric = f.value
    if metric is None:
        return None, None, None
    for suffix, col in (("_sum", "sum"), ("_count", "count"), ("_bucket", None)):
        if metric.endswith(suffix):
            base = metric[: -len(suffix)]
            le = None
            out = []
            for f in filters:
                if f.column == METRIC_TAG and f.op == "=":
                    out.append(ColumnFilter(METRIC_TAG, "=", base))
                elif suffix == "_bucket" and f.column == "le" and f.op == "=":
                    le = float("inf") if f.value in ("+Inf", "Inf") else float(f.value)
                else:
                    out.append(f)
            return tuple(out), col, le
    return None, None, None


# ---------------------------------------------------------------------------
# Non-leaf plans
# ---------------------------------------------------------------------------


class NonLeafExecPlan(ExecPlan):
    # merge nodes whose semantics tolerate losing a child under
    # ctx.allow_partial_results (shard/peer partials are mergeable);
    # structural nodes (joins, scalar ops, stitches) keep all-or-nothing
    supports_partial = False

    def __init__(self, child_plans: Sequence[ExecPlan]):
        super().__init__()
        self.child_plans = list(child_plans)

    def children(self):
        return self.child_plans

    @staticmethod
    def _annotate_child_error(child: ExecPlan, e: Exception) -> Exception:
        """Wrap the first child failure with the child's identity so a
        scatter-gather error names its shard/endpoint; the exception TYPE is
        preserved (deadline/rejection still map to their status codes)."""
        note = f"{type(child).__name__}({child.args_str()})"
        msg = str(e.args[0]) if e.args else str(e)
        if note not in msg:
            e.args = (f"{msg} [child {note}]",) + tuple(e.args[1:])
        return e

    def execute_children(self, ctx: QueryContext) -> list[QueryResult]:
        """Children execute in order, EXCEPT network-bound children (remote
        execs mark ``is_remote``) which dispatch concurrently on IO threads —
        the reference runs children as concurrent monix Tasks; here local
        children share the device serially while peer round-trips overlap.

        All execution flows through faults.dispatch_child (fault-injection
        hook + per-endpoint breaker/retries for remote children). The first
        failure cancels remaining in-flight futures and re-raises annotated
        with the child's args_str(); under ctx.allow_partial_results, merge
        nodes (supports_partial) instead record a structured warning per
        lost child and return the survivors."""
        from ...metrics import activate, current_span
        from ..faults import child_warning, dispatch_child
        from .transformers import QueryDeadlineExceeded

        children = self.child_plans
        allow_partial = (
            self.supports_partial and getattr(ctx, "allow_partial_results", False)
        )
        remote_idx = [
            i for i, c in enumerate(children) if getattr(c, "is_remote", False)
        ]
        results: dict[int, QueryResult] = {}
        failures: list[tuple[int, Exception]] = []
        pool = futs = None
        # capture the dispatching span: pool workers have no thread-local
        # trace context, so each re-activates it before executing — child
        # spans attach under this node instead of starting orphan traces
        parent_span = current_span()

        def dispatch_traced(child):
            with activate(parent_span):
                return dispatch_child(child, ctx)

        if remote_idx and len(children) >= 2:
            from concurrent.futures import ThreadPoolExecutor

            pool = ThreadPoolExecutor(max_workers=min(8, len(remote_idx)),
                                      thread_name_prefix="filodb-remote")
            futs = {i: pool.submit(dispatch_traced, children[i])
                    for i in remote_idx}
        try:
            for i, c in enumerate(children):
                if futs is not None and i in futs:
                    continue
                try:
                    results[i] = dispatch_child(c, ctx)
                except QueryDeadlineExceeded as e:
                    # OUR spent budget is a query-level condition, never a
                    # "lost child": a timeout must not degrade into a 200
                    # partial success. But a child's deadline error while
                    # origin budget remains (a peer with a stricter local
                    # deadline) is just a slow child — partial-eligible.
                    if not allow_partial or ctx.remaining_deadline_s() <= 0:
                        raise self._annotate_child_error(c, e)
                    failures.append((i, e))
                except Exception as e:  # noqa: BLE001 — classified below
                    failures.append((i, e))
                    if not allow_partial:
                        raise self._annotate_child_error(c, e)
            if futs is not None:
                from concurrent.futures import as_completed

                fut_to_idx = {f: i for i, f in futs.items()}
                # consume in COMPLETION order: a failed future surfaces
                # immediately instead of blocking behind slower siblings
                for f in as_completed(futs.values()):
                    i = fut_to_idx[f]
                    try:
                        results[i] = f.result()
                    except QueryDeadlineExceeded as e:
                        if not allow_partial or ctx.remaining_deadline_s() <= 0:
                            raise self._annotate_child_error(children[i], e)
                        failures.append((i, e))
                    except Exception as e:  # noqa: BLE001
                        failures.append((i, e))
                        if not allow_partial:
                            raise self._annotate_child_error(children[i], e)
        finally:
            if pool is not None:
                # on error: unstarted futures never run, and nothing here
                # waits for the rest. Every worker comes back within the
                # query's deadline because faults.call_with_retries bounds
                # its own wait for each remote attempt (that is also why
                # as_completed above needs no second clock); a call the
                # transport never ends stays behind on a daemon thread, so
                # these workers cannot hold the interpreter at exit
                pool.shutdown(wait=False, cancel_futures=True)
        if failures:
            if len(failures) == len(children):
                # nothing survived: a fully-failed merge is an error even
                # under allow_partial_results
                i, e = failures[0]
                raise self._annotate_child_error(children[i], e)
            for i, e in failures:
                w = child_warning(children[i], e)
                ctx.warnings.append(w)
                if parent_span is not None:
                    # partial-result drops annotate the merge node's span so
                    # EXPLAIN ANALYZE / the slow-query log show which
                    # children were lost and why
                    parent_span.tags.setdefault("lost_children", []).append(w)
        return [results[i] for i in sorted(results)]


class DistConcatExec(NonLeafExecPlan):
    """Concatenate child results (reference DistConcatExec)."""

    supports_partial = True  # shard-disjoint series: survivors are exact

    def do_execute(self, ctx: QueryContext) -> QueryResult:
        out = QueryResult()
        out.raw_grids = []
        for r in self.execute_children(ctx):
            out.grids.extend(r.grids)
            if getattr(r, "raw_grids", None):
                out.raw_grids.extend(r.raw_grids)
            if r.raw:
                out.raw = (out.raw or []) + r.raw
            if r.scalar is not None:
                out.scalar = r.scalar
            if r.metadata is not None:
                out.metadata = (out.metadata or []) + r.metadata
                out.result_type = r.result_type
        return out


class StitchRvsExec(NonLeafExecPlan):
    """Merge results of time-split children: same series, disjoint step
    ranges (reference StitchRvsExec:177)."""

    def do_execute(self, ctx: QueryContext) -> QueryResult:
        results = self.execute_children(ctx)
        results = [r for r in results if r.grids]
        if not results:
            return QueryResult()
        # build the union step grid
        key_to_row: dict[tuple, dict] = {}
        step = results[0].grids[0].step_ms
        starts = [g.start_ms for r in results for g in r.grids]
        ends = [g.start_ms + (g.num_steps - 1) * g.step_ms for r in results for g in r.grids]
        start, end = min(starts), max(ends)
        nsteps = int((end - start) // step) + 1
        for r in results:
            for g in r.grids:
                v = g.values_np()
                off = int((g.start_ms - start) // step)
                for i, lbls in enumerate(g.labels):
                    key = tuple(sorted(lbls.items()))
                    row = key_to_row.setdefault(key, {"labels": lbls, "vals": np.full(nsteps, np.nan, np.float32)})
                    row["vals"][off : off + g.num_steps] = np.where(
                        np.isnan(row["vals"][off : off + g.num_steps]), v[i], row["vals"][off : off + g.num_steps]
                    )
        labels = [r["labels"] for r in key_to_row.values()]
        vals = np.stack([r["vals"] for r in key_to_row.values()]) if key_to_row else np.zeros((0, nsteps), np.float32)
        return QueryResult(grids=[Grid(labels, start, step, nsteps, vals)])


# ---------------------------------------------------------------------------
# Aggregation
# ---------------------------------------------------------------------------

# ops whose partial state is mergeable across shards: op -> components
_PARTIAL_COMPONENTS = {
    "sum": ("sum",),
    "count": ("count",),
    "min": ("min",),
    "max": ("max",),
    "group": ("group",),
    "avg": ("sum", "count"),
    "stddev": ("sum", "sumsq", "count"),
    "stdvar": ("sum", "sumsq", "count"),
}


def _partial_aggregate(op: str, grids: list[Grid], by, without):
    """Leaf-side map phase: per-grid segment reduce into label groups.
    Returns (group_labels, components dict name -> [G, J] np arrays, grid
    meta). Native-histogram sums additionally carry a "hist" [G, J, B]
    component (reference HistSumRowAggregator)."""
    if not grids:
        return [], {}, None
    meta = grids[0]
    all_labels: list[dict] = []
    hists = [] if any(g.hist is not None for g in grids) else None
    if len(grids) == 1 and hists is None:
        # single-grid fast path: slice on device, never fetch the full
        # [S, J] grid to host — only the [G, J] partials come back
        g = grids[0]
        all_labels = list(g.labels)
        vals = g.values[: g.n_series, : g.num_steps]
    else:
        mats: list[np.ndarray] = []
        for g in grids:
            all_labels.extend(g.labels)
            mats.append(g.values_np())
            if hists is not None:
                h = g.hist_np()
                if h is None:
                    raise QueryError("cannot aggregate histogram and scalar series together")
                hists.append(h)
        J = max(m.shape[1] for m in mats)
        vals = np.full((len(all_labels), J), np.nan, np.float32)
        r = 0
        for m in mats:
            vals[r : r + m.shape[0], : m.shape[1]] = m
            r += m.shape[0]
    gids, group_labels = AGG.group_ids_for(all_labels, list(by) if by else None, list(without) if without else None)
    G = len(group_labels)
    comps: dict[str, np.ndarray] = {}
    need = _PARTIAL_COMPONENTS[op]
    for comp in need:
        if comp == "sumsq":
            out = np.asarray(AGG.segment_aggregate("sum", jnp.asarray(vals) ** 2, gids, G))
        elif comp == "group":
            out = np.asarray(AGG.segment_aggregate("group", vals, gids, G))
        else:
            out = np.asarray(AGG.segment_aggregate(comp, vals, gids, G))
        comps[comp] = out
    if hists is not None and any(g.schemes is not None for g in grids):
        if op != "sum":
            raise QueryError(f"aggregation {op} not supported over native histograms (use sum)")
        comps["hist"], schemes = _sum_base2(grids, hists, gids, G)
        from dataclasses import replace as _replace

        return group_labels, comps, _replace(meta, les=None, schemes=schemes)
    if hists is not None:
        if op != "sum":
            raise QueryError(f"aggregation {op} not supported over native histograms (use sum)")
        from ...core.histograms import unify_schemes

        les_list = [g.les for g in grids if g.les is not None]
        if len(les_list) == len(grids):
            # heterogeneous bucket schemes in one gather: unify onto the
            # union bounds (same rule as the fused superblock concat)
            unified, union, changed = unify_schemes(hists, les_list)
            if changed:
                from dataclasses import replace as _replace

                hists = unified
                meta = _replace(meta, les=union)
        H = np.concatenate(hists, axis=0)  # [S, J, B]
        S, Jh, B = H.shape
        flat = np.asarray(
            AGG.segment_aggregate("sum", jnp.asarray(H.reshape(S, Jh * B)), gids, G)
        )
        comps["hist"] = flat.reshape(G, Jh, B)
    return group_labels, comps, meta


def _sum_base2(grids, hists, gids, G: int):
    """``sum by`` over base-2 exponential histograms on the host tree: every
    series' [J, B] rates remapped onto its group's scheme
    (core.histograms.merge_base2 / base2_index: the smallest scale, the
    joined range — the rule the fused epilogue applies on the device), then
    the same per-column segment sum as the explicit rule. Returns
    ([G, J, W] partials, a Base2Scheme a group)."""
    from ...core.histograms import base2_remap, merge_base2

    if not all(g.schemes is not None for g in grids):
        raise QueryError("histograms with explicit and exponential bucket "
                         "schemes cannot be summed together")
    series = [sc for g in grids for sc in g.schemes]
    gid = [int(x) for x in np.asarray(gids)]
    members: list[list] = [[] for _ in range(G)]
    for sc, gi in zip(series, gid):
        members[gi].append(sc)
    schemes = tuple(merge_base2(m) for m in members)
    width = AGG.pad8(max(sc.width for sc in schemes))
    rows = [h[i] for h, g in zip(hists, grids) for i in range(len(g.labels))]
    H = np.stack([base2_remap(r, sc, schemes[gi], width)
                  for r, sc, gi in zip(rows, series, gid)])
    S, J, W = H.shape
    flat = np.asarray(
        AGG.segment_aggregate("sum", jnp.asarray(H.reshape(S, J * W)), gids, G))
    return flat.reshape(G, J, W), schemes


def _unify_base2_partials(partials):
    """_merge_partials' pre-pass for base-2 partials: a group's rows from
    every partial onto the merge of its schemes, all at one width."""
    from dataclasses import replace as _replace

    from ...core.histograms import base2_remap, merge_base2

    keyed: dict[tuple, list] = {}
    for gl, c, m in partials:
        if "hist" not in c:
            continue
        for gi, lbls in enumerate(gl):
            keyed.setdefault(tuple(sorted(lbls.items())), []).append(m.schemes[gi])
    target = {k: merge_base2(v) for k, v in keyed.items()}
    width = AGG.pad8(max(sc.width for sc in target.values()))
    out = []
    for gl, comps, m in partials:
        if "hist" not in comps:
            out.append((gl, comps, m))
            continue
        keys = [tuple(sorted(l.items())) for l in gl]
        h = comps["hist"]
        comps = dict(comps)
        comps["hist"] = np.stack([
            base2_remap(h[gi], m.schemes[gi], target[k], width)
            for gi, k in enumerate(keys)]) if keys else h
        out.append((gl, comps, _replace(
            m, schemes=tuple(target[k] for k in keys))))
    return out


def _unify_hist_partials(partials):
    """Pre-pass for _merge_partials: shard/peer partials carrying ``hist``
    components on DIFFERENT bucket schemes remap onto the union bounds
    (core.histograms.remap_buckets — the one unification rule, shared with
    the fused superblock concat) so the component-wise merge below adds
    aligned buckets."""
    hist_idx = [
        i for i, (_, comps, m) in enumerate(partials)
        if "hist" in comps and m is not None and m.les is not None
    ]
    base2 = [i for i, (_, comps, m) in enumerate(partials)
             if "hist" in comps and m is not None and m.schemes is not None]
    if base2:
        if hist_idx or len(base2) != sum("hist" in c for _, c, _ in partials):
            raise QueryError("histograms with explicit and exponential bucket "
                             "schemes cannot be summed together")
        return _unify_base2_partials(partials)
    if len(hist_idx) <= 1:
        return partials
    from ...core.histograms import unify_schemes

    unified, union, changed = unify_schemes(
        [partials[i][1]["hist"] for i in hist_idx],
        [partials[i][2].les for i in hist_idx],
    )
    if not changed:
        return partials
    from dataclasses import replace as _replace

    out = list(partials)
    for i, h in zip(hist_idx, unified):
        gl, comps, m = partials[i]
        comps = dict(comps)
        comps["hist"] = h
        out[i] = (gl, comps, _replace(m, les=union))
    return out


def _merge_partials(op: str, partials):
    """Reduce phase: merge shard partials by group label key."""
    key_to: dict[tuple, dict] = {}
    meta = None
    partials = _unify_hist_partials(partials)
    for group_labels, comps, m in partials:
        if m is not None:
            meta = m
        for gi, lbls in enumerate(group_labels):
            key = tuple(sorted(lbls.items()))
            slot = key_to.setdefault(key, {"labels": lbls, "comps": {}})
            if m is not None and m.schemes is not None:
                slot["scheme"] = m.schemes[gi]
            for name, arr in comps.items():
                cur = slot["comps"].get(name)
                row = arr[gi]
                if cur is None:
                    slot["comps"][name] = row.copy()
                else:
                    if name in ("sum", "count", "sumsq", "hist", "sketch"):
                        slot["comps"][name] = np.where(
                            np.isnan(cur), row, np.where(np.isnan(row), cur, cur + row)
                        )
                    elif name == "min":
                        slot["comps"][name] = np.fmin(cur, row)
                    elif name == "max":
                        slot["comps"][name] = np.fmax(cur, row)
                    elif name == "group":
                        slot["comps"][name] = np.fmax(cur, row)
    return key_to, meta


def _present(op: str, key_to, meta) -> QueryResult:
    if meta is None:
        return QueryResult()
    labels, rows, hist_rows = [], [], []
    has_hist = False
    for slot in key_to.values():
        c = slot["comps"]
        if "hist" in c:
            has_hist = True
            hist_rows.append(c["hist"])
            v = np.full(c["hist"].shape[0], np.nan, np.float32)
        elif op in ("sum", "count", "min", "max", "group"):
            v = c[op]
        elif op == "avg":
            v = c["sum"] / c["count"]
        elif op in ("stddev", "stdvar"):
            mean = c["sum"] / c["count"]
            var = c["sumsq"] / c["count"] - mean**2
            var = np.maximum(var, 0.0)
            v = var if op == "stdvar" else np.sqrt(var)
        labels.append(slot["labels"])
        rows.append(v)
    vals = np.stack(rows) if rows else np.zeros((0, meta.num_steps), np.float32)
    hist = np.stack(hist_rows) if has_hist and hist_rows else None
    schemes = None
    if has_hist and meta.schemes is not None:
        schemes = tuple(slot["scheme"] for slot in key_to.values())
    return QueryResult(
        grids=[Grid(labels, meta.start_ms, meta.step_ms, meta.num_steps, vals,
                    hist=hist, les=meta.les if has_hist else None,
                    schemes=schemes)]
    )


@dataclass
class AggregateMapReduce:
    """Transformer form of the map phase, pushed onto shard leaves
    (reference AggregateMapReduce)."""

    op: str
    by: tuple | None
    without: tuple | None

    def apply(self, grids: list[Grid]) -> list[Grid]:
        # emits a "partial grid" whose values are the partial components,
        # encoded as stacked rows with __comp__ labels
        group_labels, comps, meta = _partial_aggregate(self.op, grids, self.by, self.without)
        return partials_to_grids(group_labels, comps, meta)


# component names whose [G, J, B] payload rides the Grid.hist field
_CUBE_COMPS = ("hist", "sketch")


def partials_to_grids(group_labels, comps, meta) -> list[Grid]:
    """Encode per-group partial components as ``__comp__``-labeled grids —
    the ONE wire/in-memory form for mergeable aggregation state, shared by
    the shard map phase, the peer-level PartialAggregate executor, and the
    gRPC result frames (reference: serialized RangeVectorAggregator partial
    AggregateItems)."""
    if meta is None:
        return []
    out = []
    for name, arr in comps.items():
        is_cube = name in _CUBE_COMPS
        out.append(
            Grid(
                [dict(l, __comp__=name) for l in group_labels],
                meta.start_ms,
                meta.step_ms,
                meta.num_steps,
                arr if not is_cube else np.full(arr.shape[:2], np.nan, np.float32),
                hist=arr if is_cube else None,
                les=meta.les if name == "hist" else None,
                schemes=meta.schemes if name == "hist" else None,
            )
        )
    return out


def collect_partials(result: QueryResult, default_op: str):
    """Decode a child's ``__comp__``-labeled grids back into the
    (group_labels, comps, meta) partial form (inverse of
    partials_to_grids). Rows without a __comp__ label are treated as
    already-final values of ``default_op`` — the exact-re-aggregation form
    sum/min/max/group peers return."""
    meta = None
    comp_rows: dict[str, dict[tuple, np.ndarray]] = {}
    labels_by_key: dict[tuple, dict] = {}
    scheme_by_key: dict[tuple, object] = {}
    for g in result.grids:
        if g.les is not None or meta is None:
            meta = g
        v = g.values_np()
        h = g.hist_np()
        for i, l in enumerate(g.labels):
            comp = l.get("__comp__", default_op)
            base = {k: x for k, x in l.items() if k != "__comp__"}
            key = tuple(sorted(base.items()))
            labels_by_key[key] = base
            if g.schemes is not None:
                scheme_by_key[key] = g.schemes[i]
            comp_rows.setdefault(comp, {})[key] = (
                h[i] if comp in _CUBE_COMPS else v[i]
            )
    if meta is None:
        return None
    keys = list(labels_by_key)
    group_labels = [labels_by_key[k] for k in keys]
    comps = {}
    for comp, rows in comp_rows.items():
        proto = next(iter(rows.values()))
        comps[comp] = np.stack([
            rows.get(k, np.full(proto.shape, np.nan, np.float32)) for k in keys
        ])
    if scheme_by_key:
        from dataclasses import replace as _replace

        meta = _replace(meta, les=None,
                        schemes=tuple(scheme_by_key[k] for k in keys))
    return group_labels, comps, meta


class ReduceAggregateExec(NonLeafExecPlan):
    """reference ReduceAggregateExec + RangeVectorAggregator.mapReduce."""

    supports_partial = True  # __comp__ partials merge over any child subset

    def __init__(self, child_plans, op: str, by=None, without=None):
        super().__init__(child_plans)
        self.op = op
        self.by = by
        self.without = without

    def args_str(self) -> str:
        return f"op={self.op} by={self.by} without={self.without}"

    def do_execute(self, ctx: QueryContext) -> QueryResult:
        partials = []
        for r in self.execute_children(ctx):
            # children emit partial grids tagged with __comp__ (rows without
            # the tag are exact-re-aggregation peer results of self.op)
            p = collect_partials(r, self.op)
            if p is not None:
                partials.append(p)
        key_to, meta = _merge_partials(self.op, partials)
        return _present(self.op, key_to, meta)


@dataclass
class SuperblockEntry:
    """One cached cross-shard superblock + everything do_execute needs to
    dispatch on it (SuperblockCache value)."""

    block: Any  # ST.StagedBlock, [ΣS, T] or [ΣS, T, B] device-resident
    labels: list  # [ΣS] per-series label dicts
    is_counter: bool
    is_delta: bool
    samples: int  # scanned sample count (stats/limit accounting; PRE-slice,
    # like the reference path — a le= slice that drops a shard still scanned it)
    max_shard_series: int  # per-shard limit re-enforcement on cache hits
    series: int = 0  # scanned series count (pre-slice, see samples)
    is_hist: bool = False
    les: Any = None  # [B] unified bucket bounds (histogram blocks)
    les_dev: Any = None  # device f32 copy for the fused quantile epilogue
    # incremental-extension inputs (ST.extend_superblock): the resolved
    # value column and staging mode the member blocks were staged with.
    # stage_mode None marks entries that can never extend (le=-sliced
    # bucket superblocks) — they still revalidate on disjoint ingest.
    col_name: str | None = None
    stage_mode: str | None = None
    # base-2 exponential histograms (les None): a Base2Scheme a real row,
    # and the [S_pad] int32 scale / offset / n sidecars on the device
    schemes: tuple | None = None
    scheme_dev: tuple | None = None


def _base2_schemes(parts):
    """A shard's histogram partitions' schemes: None when none is base-2
    (the explicit-bucket rule applies), their Base2Schemes when every one is
    and fits the staged width, False for anything else (a shard mixing
    explicit and base-2 schemes, or a scheme wider than the SDK's
    MaxSize): the fused path falls back with ``hist_scheme``."""
    from ...core.histograms import base2_family

    schemes = [p.bucket_scheme for p in parts]
    if all(s is None for s in schemes):
        return None
    return schemes if base2_family(schemes) else False


def _base2_sidecars(schemes, rows: int):
    """[rows] int32 scale / offset / n of a base-2 block (padding rows: an
    empty scheme at scale 0, in the trash group)."""
    out = np.zeros((3, rows), dtype=np.int32)
    for r, s in enumerate(schemes):
        out[:, r] = (s.scale, s.offset, s.n)
    return out


def _unify_hist_blocks(blocks, block_les):
    """Put per-shard histogram blocks on ONE bucket scheme: the union of the
    shards' ``le`` bounds, missing bounds completed from the nearest lower
    bound (core.histograms.remap_buckets — the same rule the reference
    partial-merge path applies, so fused and reference stay bit-identical).
    Returns (blocks', union_les); blocks with the union scheme pass through
    untouched."""
    from ...core.histograms import remap_buckets, unify_schemes

    vals_in = [v for (v,) in ST.read_back(blocks, "vals")]
    vals_out, union, changed = unify_schemes(vals_in, block_les)
    if not changed:
        return blocks, union
    out = []
    for b, v_in, v_out, l in zip(blocks, vals_in, vals_out, block_les):
        if v_out is v_in:  # already on the union scheme
            out.append(b)
            continue
        ((ts, lens, baseline),) = ST.read_back([b], "ts", "lens", "baseline")
        if baseline.ndim == 2:
            baseline = remap_buckets(baseline, l, union)
        # remapping touches only the bucket axis: the shared regular time
        # grid (the fused shared-window fast path) survives verbatim
        out.append(ST.StagedBlock(
            ts, v_out, lens, b.base_ms, baseline,
            b.n_series, list(b.part_refs), regular_ts=b.regular_ts,
        ))
    return out, union


def _uniform_scheme(parts, les) -> bool:
    """True when every partition in a shard carries the SAME bucket scheme
    (core.histograms.same_scheme). A shard mixing schemes (mid-rollout
    bound change) cannot stage as one [S, T, B] block with a single ``le``
    vector — the fused path must fall back rather than silently attribute
    one scheme's counts to another's bounds."""
    from ...core.histograms import same_scheme

    if les is None:
        return False
    for p in parts[1:]:
        other = p.bucket_les
        if other is None:
            return False
        if other is not les and not same_scheme(other, les):
            return False
    return True


def _slice_bucket(block, les, bucket_le: float):
    """``m_bucket{le=...}``: slice one bucket of a staged [S, T, B] block
    into a scalar counter block — the ONE definition of le-selection
    semantics, shared by the fused builder and SelectRawPartitionsExec.
    Returns (block, le_label) or None when the scheme has no such bound
    (same tolerance as histogram_bucket)."""
    from ...core.histograms import _LE_TOL

    if les is None:
        return None
    les64 = np.asarray(les, dtype=np.float64)
    if np.isinf(bucket_le):
        b_idx = len(les64) - 1
    else:
        hits = np.nonzero(np.abs(les64 - bucket_le) < _LE_TOL)[0]
        b_idx = int(hits[0]) if len(hits) else -1
    if b_idx < 0:
        return None
    ((vals3, baseline),) = ST.read_back([block], "vals", "baseline")
    scalar_vals = np.ascontiguousarray(vals3[..., b_idx])
    sliced = ST.StagedBlock(
        block.ts, scalar_vals, block.lens, block.base_ms,
        baseline[..., b_idx] if baseline.ndim == 2 else baseline,
        block.n_series, block.part_refs, raw=scalar_vals,
        regular_ts=block.regular_ts,
        # a jittered hist block's grid metadata survives the slice so the
        # scalar jitter fused variant stays available for m_bucket{le=...}
        nominal_ts=block.nominal_ts, ts_dev=block.ts_dev,
        maxdev_ms=block.maxdev_ms,
    )
    # the arrays the slice shares with the staged block keep its mirrors
    for mirror in ("h_ts", "h_lens", "h_dev"):
        setattr(sliced, mirror, getattr(block, mirror, None))
    le_str = "+Inf" if np.isinf(les64[b_idx]) else f"{les64[b_idx]:g}"
    return sliced, le_str


def _key_mode(hint, stage_mode: str) -> str:
    """The staging mode a superblock is cached under: the function's, unless
    the column is known (``hint`` = (cumulative counter, delta), learned at
    its first build) to stage raw whatever the function — a gauge, a
    histogram, a delta-temporality counter."""
    if hint is not None and not (hint[0] and not hint[1]):
        return "raw"
    return stage_mode


# aggregation ops the fused single-dispatch path computes exactly as one
# on-device segment reduce (ops/aggregations.fused_range_aggregate)
FUSED_AGG_OPS = frozenset({"sum", "count", "avg", "min", "max"})

# aggregation ops the fused path computes as a device-side EPILOGUE fused
# into the same program (ops/aggregations.fused_topk / fused_quantile):
# only [k, J] / [G, J] arrays ever reach the host
FUSED_EPI_OPS = frozenset({"topk", "bottomk", "quantile"})

# range functions the fused path supports: everything the shape-static range
# kernels compute on device, minus host-path timestamp, per-window sorts,
# absent_over_time (needs the presence reduce, not a value aggregate), and
# arg-taking functions (the planner also rejects function_args)
FUSED_FUNCS = frozenset({
    "rate", "increase", "delta", "irate", "idelta",
    "sum_over_time", "avg_over_time", "count_over_time", "min_over_time",
    "max_over_time", "last", "last_over_time", "first_over_time",
    "present_over_time", "stddev_over_time", "stdvar_over_time", "z_score",
    "changes", "resets", "deriv",
})


def fused_mesh_supported(mesh, op: str, function) -> bool:
    """Whether the mesh-sharded fused program models this aggregate: a 1-D
    device mesh, a fused op (simple aggregates psum their [G, J] partials;
    topk/quantile epilogues combine winner/multiset state across devices),
    and a fused range function. The ONE gate shared by the planner, the
    parallel/ engines' delegation, and FusedAggregateExec's runtime check
    (fallback reason ``mesh_unsupported``)."""
    if mesh is None or len(getattr(mesh, "axis_names", ())) != 1:
        return False
    if op not in FUSED_AGG_OPS and op not in FUSED_EPI_OPS:
        return False
    return function is None or function in FUSED_FUNCS


class FusedAggregateExec(ExecPlan):
    """Single-dispatch cross-shard aggregate (the tentpole of the
    superblock path): ``op by (...) (func(selector[w]))`` over local shards
    executes as ONE compiled program over ONE device-resident superblock —
    O(1) kernel launches instead of O(shards) stage->kernel->partial-merge
    round trips, and only the [G, J] group partials ever reach the host.

    The superblock (ops/staging.concat_blocks) is cached on the memstore
    keyed by the member shards' version vector (ops/staging.SuperblockCache);
    per-shard blocks flow through the SAME cached staging path as
    SelectRawPartitionsExec (staged_block_for), so dirty shards repair
    incrementally via append_to_block before re-concatenation. Label
    grouping memoizes on the superblock (ops/aggregations.group_ids_memo).

    Histogram schemas run the 3-D variant: per-shard ``[S, T, B]`` bucket
    blocks concatenate into one ``[ΣS, T, B]`` superblock (heterogeneous
    ``le`` schemes unified onto the union bounds first,
    core.histograms.remap_buckets) and one compiled hist range_fn ->
    per-bucket segment-sum program returns [G, J, B] partials — or, with
    ``hist_quantile`` set (the planner recognized
    ``histogram_quantile(q, sum by (...) (rate(m_bucket[w])))``), just the
    [G, J] interpolated quantile grid. ``topk``/``bottomk``/``quantile``
    aggregates fuse their epilogue the same way (FUSED_EPI_OPS).

    ``fallback`` is the reference tree
    (ReduceAggregateExec -> N x SelectRawPartitionsExec); execution falls
    back to it — annotating the span with the reason and bumping
    ``filodb_fused_fallback_total{reason=...}`` — for partial-results
    mode, fault-injection dispatchers, mixed schemas, or anything else the
    fused kernel doesn't model (doc/perf.md lists the reason taxonomy). It
    is passed as a zero-arg factory and materialized lazily on first use:
    the happy path must not pay plan-time construction of O(shards) leaves
    it discards (at 128 shards that is exactly the linear cost this node
    removes)."""

    def __init__(self, shard_nums, filters, raw_start_ms: int, raw_end_ms: int,
                 column, op: str, by, without, function,
                 start_ms: int, end_ms: int, step_ms: int, window_ms: int,
                 offset_ms: int, fallback, params=(),
                 hist_quantile: float | None = None, mesh=None):
        super().__init__()
        self.shard_nums = list(shard_nums)
        self.filters = tuple(filters)
        self.raw_start_ms = raw_start_ms
        self.raw_end_ms = raw_end_ms
        self.column = column
        self.op = op
        self.by = by
        self.without = without
        self.function = function  # None = plain selector (lookback last)
        self.start_ms = start_ms
        self.end_ms = end_ms
        self.step_ms = step_ms
        self.window_ms = window_ms
        self.offset_ms = offset_ms
        self.params = tuple(params)  # k for topk/bottomk, q for quantile
        self.hist_quantile = hist_quantile  # fused histogram_quantile(q, ..)
        # 1-D device mesh (parallel.mesh.series_mesh): the superblock's
        # series axis partitions across it and the fused program runs under
        # shard_map — ONE dispatch spanning every device. None = the
        # single-device fused path.
        self.mesh = mesh
        self._fallback_factory = fallback
        self._fallback: ExecPlan | None = None

    @property
    def fallback(self) -> ExecPlan:
        if self._fallback is None:
            self._fallback = self._fallback_factory()
        return self._fallback

    def args_str(self) -> str:
        fs = ",".join(f"{f.column}{f.op}{f.value}" for f in self.filters)
        extra = f" params={self.params}" if self.params else ""
        if self.hist_quantile is not None:
            extra += f" hist_q={self.hist_quantile}"
        if self.mesh is not None:
            extra += f" mesh={self.mesh.devices.size}"
        return (
            f"op={self.op} fn={self.function} by={self.by} "
            f"without={self.without} shards={self.shard_nums} filters=[{fs}]"
            f"{extra}"
        )

    def _fall(self, ctx: QueryContext, reason: str) -> QueryResult:
        from ...metrics import current_span, record_fused_fallback

        s = current_span()
        if s is not None:
            s.tags["fused_fallback"] = reason
        # query-observatory path annotation: the cost record carries WHY
        # this query left the fused path (obs/querylog.py)
        obs = getattr(ctx, "obs", None)
        if obs is not None:
            obs["path"] = "fallback"
            obs["fallback"] = reason
        record_fused_fallback(reason)
        return self.fallback.execute(ctx)

    def num_steps(self) -> int:
        return int((self.end_ms - self.start_ms) // self.step_ms) + 1

    def _unsupported_shape(self, is_hist: bool) -> str | None:
        """Fallback reason when the fused kernels don't model this
        op/function on the resolved schema, or None when fused dispatch can
        proceed. Decided BEFORE the stats bump (the reference tree bumps
        its own scan stats — deciding later would double-count against
        per-request limits) and, on cold builds, before any staging (a
        discarded [ΣS, T, B] build would evict cache entries for nothing)."""
        from ...ops.hist_kernels import FUSED_HIST_FUNCS

        if self.raw_end_ms - self.raw_start_ms > ST.MAX_STAGE_SPAN_MS:
            # staged timestamps are int32 ms offsets from the selector start
            # (ops/staging.py): a wider selection cannot be represented —
            # offsets would wrap and searchsorted over the no-longer-sorted
            # vector silently empties late windows. The reference tree
            # windows over the same staged offsets, so falling back does
            # NOT help; Planner.materialize time-slices such ranges before
            # any exec is built, making this a defense-in-depth guard for
            # plans assembled outside materialize. (Spans this wide are
            # the rollup tier's job.)
            return "stage_span"
        if is_hist:
            # hist kernel models only plain sum over the hist range funcs
            if self.op != "sum" or self.params:
                return "hist_op"
            if (self.function or "last") not in FUSED_HIST_FUNCS:
                return "hist_func"
        elif self.hist_quantile is not None:
            # planner recognized histogram_quantile over this aggregate but
            # the selection resolved to a scalar schema. With ``le`` in the
            # grouping these are CLASSIC bucket series (e.g. a self-scraped
            # *_bucket family in _system): the fused agg computes the
            # by-(le,...) partials as ONE dispatch and the classic
            # interpolation folds them on host (transformers.
            # classic_histogram_quantile — same kernel as the native path).
            # Without ``le`` the shape is unanswerable; the reference tree
            # raises the proper "needs native-histogram input" QueryError.
            if "le" not in tuple(self.by or ()):
                return "hist_quantile_scalar"
        return None

    def _serve_hit(self, ctx: QueryContext, hit: "SuperblockEntry"):
        """Limit + stats enforcement for a cached superblock: limits are
        PER REQUEST (execute_plan narrows them), so a cache hit must never
        serve a query whose limits the build path would have rejected.
        Returns a fallback-reason string instead when this query's op/func
        can't dispatch on the cached block's schema."""
        reason = self._unsupported_shape(hit.is_hist)
        if reason is not None:
            return reason
        if hit.max_shard_series > ctx.max_series:
            raise QueryError(
                f"query selects {hit.max_shard_series} series > limit "
                f"{ctx.max_series}"
            )
        ctx.stats.bump(series_scanned=hit.series or hit.block.n_series,
                       samples_scanned=hit.samples)
        if ctx.stats.samples_scanned > ctx.max_samples:
            raise QueryError(
                f"query would scan {ctx.stats.samples_scanned} samples > "
                f"limit {ctx.max_samples}"
            )
        return hit

    def _superblock(self, ctx: QueryContext, stage_mode: str):
        """SuperblockEntry from the shard-version-keyed superblock cache,
        rebuilding through the per-shard cached staging path on miss.
        Returns a fallback-reason string instead when the selection needs
        the reference tree, or None for an empty selection."""
        cache = getattr(ctx.memstore, "_superblock_cache", None)
        if cache is None:
            cache = ST.SuperblockCache()
            ctx.memstore._superblock_cache = cache
        # resolved-mode keying: for non-counter columns every function
        # stages "raw", so keying purely on the function-derived mode would
        # cache byte-identical superblocks under distinct keys. The schema
        # hint learned on first build collapses them; actual staging modes
        # always re-derive from the live schema.
        hints = getattr(ctx.memstore, "_fused_mode_hints", None)
        if hints is None:
            hints = {}
            ctx.memstore._fused_mode_hints = hints
        hint_key = (ctx.dataset, self.filters, self.column)
        sb_key = self._cache_key(
            ctx, _key_mode(hints.get(hint_key), stage_mode))
        # standing-query refresh contexts carry a pin sink: the maintainer
        # pins the key it resolves to (by standing qid) so ad-hoc eviction
        # storms can't churn the entry its delta refresh extends in place
        pin_sink = getattr(ctx, "superblock_pin_sink", None)
        if pin_sink is not None:
            pin_sink(cache, sb_key)
        versions = tuple(
            ctx.memstore.shard(ctx.dataset, s).version for s in self.shard_nums
        )
        hit = cache.get(sb_key, versions)
        if hit is not None:
            ctx.stats.bump(cache_hits=1)
            return self._serve_hit(ctx, hit)
        # single-flight per key: N identical cold queries must not each
        # concatenate + upload the full superblock (the same duplicate-
        # construction class as the _get_wm / window_matrices races)
        with cache.build_lock(sb_key):
            versions = tuple(
                ctx.memstore.shard(ctx.dataset, s).version
                for s in self.shard_nums
            )
            hit = cache.get(sb_key, versions)
            if hit is not None:
                ctx.stats.bump(cache_hits=1)
                return self._serve_hit(ctx, hit)
            refreshed = self._refresh_superblock(ctx, cache, sb_key, versions)
            if refreshed is not None:
                return refreshed
            return self._build_superblock(
                ctx, stage_mode, cache, versions, hints, hint_key
            )

    def _refresh_superblock(self, ctx: QueryContext, cache, sb_key,
                            versions: tuple):
        """Interval-aware maintenance of a version-stale cached superblock
        (runs under the per-key build lock). Three outcomes, cheapest
        first:

        - every member shard's effects since the entry was stamped were
          provably DISJOINT from the staged range → re-stamp (revalidate)
          and serve the entry untouched — disjoint-range ingest no longer
          evicts superblocks;
        - only overlapping interval effects (live-edge appends) and the
          row set is provably unchanged → EXTEND the device superblock in
          place (_extend_superblock) and serve it — the warm query stays
          one dispatch under live ingest;
        - anything else (new series, eviction, ODP, effect-log truncation,
          extension precondition failure) → return None and let the caller
          pay the full rebuild.

        Returns what do_execute expects from _superblock (an entry, a
        fallback-reason string from _serve_hit, or None for rebuild)."""
        from ...metrics import record_superblock_event

        stale = cache.peek(sb_key)
        if stale is None:
            return None
        old_versions, entry, _ = stale
        if len(old_versions) != len(versions):
            return None
        overlap = False
        for s, ov in zip(self.shard_nums, old_versions):
            shard = ctx.memstore.shard(ctx.dataset, s)
            reason = shard.ingest_effects_since(
                ov, self.raw_start_ms, self.raw_end_ms
            )
            if reason == "overlap":
                overlap = True
            elif reason is not None:
                # full_clear / log_truncated: the entry can never be
                # revalidated or extended, and put() is gated on a stable
                # version vector that sustained ingest keeps moving — drop
                # it now or it pins device + host-mirror bytes forever
                # (eviction only runs inside put).
                cache.drop(sb_key)
                record_superblock_event("restage")
                return None
        if not overlap:
            if cache.revalidate(sb_key, old_versions, versions):
                record_superblock_event("revalidate")
                cache.note(sb_key, "revalidate")
                ctx.stats.bump(cache_hits=1)
                return self._serve_hit(ctx, entry)
            return None
        if entry.stage_mode is None:
            record_superblock_event("restage")
            cache.note(sb_key, "restage")
            return None
        return self._extend_superblock(ctx, cache, sb_key, entry, versions)

    def _extend_superblock(self, ctx: QueryContext, cache, sb_key,
                           entry: "SuperblockEntry", versions: tuple):
        """Absorb overlapping live-edge appends into the cached superblock
        via ST.extend_superblock (append_to_block lifted to the superblock
        level), then commit with versions re-read AFTER the extension and
        the effect log classifying whatever landed mid-extension:

        - nothing in-range → commit at the post-extension vector;
        - interval OVERLAPS only (live-edge appends racing the extension
          reads) → commit at the PRE-extension vector. The extension is
          still internally consistent — _append_to_parts rejects torn
          cross-epoch reads via its uniform-count/timestamp checks BEFORE
          mutating anything, and each series' content is a true prefix of
          its store state re-extendable from its own head — it just may
          not include the racing samples, so the entry stays version-stale
          and the NEXT query extends again from the new head instead of
          the whole cache paying a rebuild storm;
        - full effects (new series, eviction, ODP, truncation) → DROP the
          entry: resident data or the row set may have changed under the
          reads, and the mutated host mirrors must never be served again."""
        from ...metrics import record_superblock_event

        # row-set proof: a fresh lookup per shard must return exactly the
        # entry's part refs, in order. This is the superblock analog of
        # append_to_block's part_refs check — it catches the gap-series
        # hazard (an append BEYOND the range extending a series' index
        # span across it) that version vectors alone cannot distinguish
        # from a plain live-edge append.
        rewritten, _co, bucket_le = _histogram_suffix_rewrite(self.filters)
        if bucket_le is not None:
            # le=-sliced bucket superblocks are built by slicing a staged
            # [S, T, B] block — there is nothing to append onto
            record_superblock_event("restage")
            return None
        refs = []
        for s in self.shard_nums:
            shard = ctx.memstore.shard(ctx.dataset, s)
            pids = shard.lookup_partitions(
                self.filters, self.raw_start_ms, self.raw_end_ms
            )
            if not len(pids) and rewritten is not None:
                pids = shard.lookup_partitions(
                    rewritten, self.raw_start_ms, self.raw_end_ms
                )
            refs.extend((s, int(p)) for p in pids)
        if refs != list(entry.block.part_refs):
            record_superblock_event("restage")
            return None
        try:
            nb = ST.extend_superblock(
                ctx.memstore, ctx.dataset, entry.block, entry.col_name,
                self.raw_end_ms, entry.stage_mode,
                les=entry.les if entry.is_hist else None,
            )
        except Exception:
            cache.drop(sb_key)  # mirrors possibly torn mid-mutation
            record_superblock_event("extend_abort")
            return None
        if nb is None:
            record_superblock_event("restage")
            cache.note(sb_key, "restage")
            return None
        versions_now = tuple(
            ctx.memstore.shard(ctx.dataset, s).version for s in self.shard_nums
        )
        commit_versions = versions_now
        if versions_now != versions:
            for s, ov in zip(self.shard_nums, versions):
                reason = ctx.memstore.shard(ctx.dataset, s).ingest_effects_since(
                    ov, self.raw_start_ms, self.raw_end_ms
                )
                if reason == "overlap":
                    # live-edge appends raced the extension reads: the
                    # extension is consistent (see docstring) but may not
                    # include them — commit STALE at the pre-extension
                    # vector so the next query extends again
                    commit_versions = versions
                elif reason is not None:
                    cache.drop(sb_key)
                    record_superblock_event("extend_abort")
                    return None
        if nb is entry.block:
            # nothing new was readable in range (e.g. the overlapping
            # effect's samples were all dropped as out-of-order, or they
            # landed after the reads): the entry is untouched and valid
            # as-is at the commit vector
            stale = cache.peek(sb_key)
            if stale is not None and stale[1] is entry:
                cache.revalidate(sb_key, stale[0], commit_versions)
            record_superblock_event("revalidate")
            cache.note(sb_key, "revalidate")
            ctx.stats.bump(cache_hits=1)
            return self._serve_hit(ctx, entry)
        samples = int(np.asarray(nb.h_lens).sum())
        new_entry = SuperblockEntry(
            nb, entry.labels, entry.is_counter, entry.is_delta, samples,
            entry.max_shard_series, series=entry.series,
            is_hist=entry.is_hist, les=entry.les, les_dev=entry.les_dev,
            col_name=entry.col_name, stage_mode=entry.stage_mode,
        )
        cache.put(sb_key, commit_versions, new_entry, ST.staged_nbytes(nb))
        record_superblock_event("extend")
        cache.note(sb_key, "extend")
        ctx.stats.bump(cache_extends=1)
        return self._serve_hit(ctx, new_entry)

    def _build_superblock(self, ctx: QueryContext, stage_mode: str, cache,
                          versions, hints, hint_key):
        rewritten, col_override, bucket_le = _histogram_suffix_rewrite(
            self.filters
        )
        blocks, labels, block_les, schemes_all = [], [], [], []
        schema_name = None
        is_counter = is_delta = is_hist = sliced_hist = False
        total = max_shard_series = dropped_samples = 0
        for s in self.shard_nums:
            ctx.check_deadline()
            shard = ctx.memstore.shard(ctx.dataset, s)
            suffixed = False
            with span("stage:lookup", part="lookup"):
                pids = shard.lookup_partitions(
                    self.filters, self.raw_start_ms, self.raw_end_ms
                )
                if not len(pids) and rewritten is not None:
                    # classic-histogram suffix selector (m_sum / m_count /
                    # m_bucket): stage the base histogram schema's columns,
                    # same per-shard rewrite SelectRawPartitionsExec applies
                    pids = shard.lookup_partitions(
                        rewritten, self.raw_start_ms, self.raw_end_ms
                    )
                    suffixed = len(pids) > 0
            if not len(pids):
                continue
            if len(pids) > ctx.max_series:
                # same per-shard limit semantics as SelectRawPartitionsExec
                raise QueryError(
                    f"query selects {len(pids)} series > limit {ctx.max_series}"
                )
            # pre-slice accounting, matching the reference path (it bumps
            # stats and enforces the per-shard limit before le= slicing)
            total += len(pids)
            max_shard_series = max(max_shard_series, len(pids))
            if shard.odp_store is not None:
                shard.odp_page_in(pids, self.raw_start_ms, self.raw_end_ms)
            with span("stage:assemble", part="assemble"):
                parts = [shard.partition(int(p)) for p in pids]
                names = {p.schema.name for p in parts}
            if len(names) > 1 or (schema_name is not None
                                  and names != {schema_name}):
                return "mixed_schemas"
            schema_name = parts[0].schema.name
            schema = parts[0].schema
            col_name = self.column or (suffixed and col_override) \
                or schema.value_column
            try:
                col = schema.column(col_name)
            except KeyError:
                col_name = schema.value_column
                col = schema.column(col_name)
            hist_col = col.ctype == ColumnType.HISTOGRAM
            # op/func support is decidable as soon as the schema resolves —
            # bail before staging uploads a [S, T, B] block only to discard
            # it (a le= slice lands scalar, so it follows the scalar rules)
            reason = self._unsupported_shape(hist_col and bucket_le is None)
            if reason is not None:
                return reason
            base2 = _base2_schemes(parts) if hist_col else None
            if hist_col:
                if base2 is False or (base2 is not None and (
                        bucket_le is not None or self.mesh is not None
                        or any(f is not None for f in block_les))) or (
                        base2 is None and schemes_all):
                    # mixed families in a shard or across shards, a bucket
                    # slice or a mesh: nothing stages one block for it
                    return "hist_scheme"
            is_counter = col.is_counter
            is_delta = col.is_delta
            # histogram columns always stage raw (reference: correction only
            # inside rate-family RangeFunctions; hist kernels window raw
            # cumulative bucket counts directly)
            mode = (
                stage_mode if is_counter and not is_delta and not hist_col
                else "raw"
            )
            cache_key = (
                self.filters, self.raw_start_ms, self.raw_end_ms, col_name,
                schema_name, mode,
            )
            block = staged_block_for(
                ctx, shard, pids, cache_key, col_name, self.raw_start_ms,
                self.raw_end_ms, mode,
            )
            with span("stage:assemble", part="assemble"):
                part_labels = [dict(p.tags) for p in parts]
                les = parts[0].bucket_les if hist_col and base2 is None else None
                uniform = (not hist_col or base2 is not None
                           or _uniform_scheme(parts, les))
            if not uniform:
                # no scheme at all, or partitions WITHIN this shard disagree
                # on bounds: one [S, T, B] block can't represent them (the
                # union remap is per-shard) — keep the pre-fusion behavior
                return "hist_scheme"
            if hist_col and bucket_le is not None:
                # m_bucket{le=...}: slice ONE bucket into a scalar block
                # (same selection semantics as SelectRawPartitionsExec)
                sliced = _slice_bucket(block, les, bucket_le)
                if sliced is None:
                    # no such bucket on this shard: it contributes no rows,
                    # but its series/samples were scanned — count them, as
                    # the reference path does (it bumps before slicing)
                    dropped_samples += ST.staged_samples([block])
                    continue
                block, le_str = sliced
                part_labels = [dict(l, le=le_str) for l in part_labels]
                les = None
                hist_col = False
                sliced_hist = True
                is_counter, is_delta = True, False
            if hist_col != is_hist and blocks:
                return "mixed_schemas"  # scalar + histogram blocks can't mix
            is_hist = hist_col
            if block.vals.ndim != (3 if hist_col else 2):
                return "mixed_schemas"
            blocks.append(block)
            block_les.append(les)
            labels.extend(part_labels)
            if base2 is not None:
                if block.schemes is None:
                    return "hist_scheme"
                schemes_all.extend(block.schemes)
        if schema_name is not None:
            if len(hints) >= 1024:
                hints.clear()  # bounded: hints are one dict lookup to relearn
            # histogram columns always stage raw — including a le= slice of
            # one (sliced AFTER raw staging) — so key them like gauges: one
            # superblock serves every range function over the selector
            hints[hint_key] = (is_counter and not is_hist and not sliced_hist,
                               is_delta)
        if not blocks:
            return None  # empty selection: empty result, not a fallback
        samples = dropped_samples + ST.staged_samples(blocks)
        ctx.stats.bump(series_scanned=total, samples_scanned=samples,
                       cache_misses=1)
        if ctx.stats.samples_scanned > ctx.max_samples:
            raise QueryError(
                f"query would scan {ctx.stats.samples_scanned} samples > "
                f"limit {ctx.max_samples}"
            )
        les = None
        if is_hist and not schemes_all:
            with span("stage:assemble", part="assemble"):
                blocks, les = _unify_hist_blocks(blocks, block_les)
        # live-edge ingest EXTENDS the superblock in place
        # (ST.extend_superblock) instead of paying concat + full re-upload
        # per append — the delta-summation move — through host mirrors that
        # cost nothing until that first extension writes them: a shard
        # block's staged arrays are its mirrors (copies on the CPU backend,
        # whose device_put may alias them), and a superblock's are made at
        # its first extension, from the members' while the stage caches
        # hold them, else by one read-back. A historical panel never pays.
        # With a mesh, the series axis pads to a mesh-divisible ΣS (the
        # existing trash-group masking keeps the extra rows inert) and the
        # arrays pin SHARDED (PartitionSpec(axis) row bands) so the fused
        # program spans every device without a gather; that build is whole
        # on the host, and what it concatenated is its mirror. Without one,
        # blocks straight out of the stage cache are concatenated on the
        # device that holds them and nothing is uploaded again.
        super_block, uploaded = ST.build_superblock(blocks, mesh=self.mesh)
        les_dev = None
        if les is not None:
            with span("stage:h2d_super", part="h2d_super"):
                les_dev = ST.replicated_put(self.mesh)(
                    np.asarray(les, dtype=np.float32))
            uploaded += int(les_dev.nbytes)
        scheme_dev = None
        if schemes_all:
            with span("stage:scheme", part="scheme"):
                side = _base2_sidecars(
                    schemes_all, np.asarray(super_block.lens).shape[0])
                put = ST.series_put(getattr(super_block, "placement", None))
                scheme_dev = tuple(put(a) for a in side)
            uploaded += int(side.nbytes)
        if uploaded:
            REGISTRY.counter("filodb_stage_h2d_bytes",
                             part="h2d_super").inc(uploaded)
        nbytes = ST.staged_nbytes(super_block)

        resolved_mode = (
            stage_mode if is_counter and not is_delta and not is_hist
            else "raw"
        )
        value = SuperblockEntry(
            super_block, labels, is_counter, is_delta, samples,
            max_shard_series, series=total, is_hist=is_hist, les=les,
            les_dev=les_dev,
            col_name=col_name,
            # a base-2 entry never extends in place: its sidecars are the
            # build's (a disjoint ingest still revalidates it)
            stage_mode=None if sliced_hist or schemes_all else resolved_mode,
            schemes=tuple(schemes_all) if schemes_all else None,
            scheme_dev=scheme_dev,
        )
        # versions re-read AFTER staging: an ingest that landed mid-build
        # makes the entry unservable for the next query (version mismatch),
        # so only cache when nothing moved
        versions_now = tuple(
            ctx.memstore.shard(ctx.dataset, s).version for s in self.shard_nums
        )
        if versions_now == versions:
            # under the key the NEXT query of this column looks up: the
            # first build came keyed by its function's mode and has only now
            # learned the column's (hints, above)
            sb_key = self._cache_key(
                ctx, _key_mode(hints.get(hint_key), stage_mode))
            cache.put(sb_key, versions, value, nbytes)
        return value

    def _cache_key(self, ctx: QueryContext, key_mode: str) -> tuple:
        """The superblock cache's key of this plan's selection. Sharded and
        single-device superblocks are distinct entries: placement (and the
        mesh-divisible padding) differs even over the identical selection,
        and engines sharing one memstore may run both."""
        return (
            ctx.dataset, tuple(self.shard_nums), self.filters,
            self.raw_start_ms, self.raw_end_ms, self.column, key_mode,
            self._mesh_desc(),
        )

    def _mesh_desc(self) -> tuple | None:
        """Hashable mesh identity for the batching coalescing key (mirrors
        the superblock cache key's mesh descriptor)."""
        if self.mesh is None:
            return None
        return (self.mesh.axis_names[0],
                tuple(d.id for d in self.mesh.devices.flat))

    def _group_ids(self, got, strip: bool):
        """``(gids_dev, G, group_labels)`` of the superblock's series for
        this aggregation, under phase ``group``: memoized on the block
        object, so a query that built its block pays the regroup of every
        label set and the [S] int32 upload (the builder tags the span
        ``memo="miss"``). Global topk/bottomk group nothing: the all-zeros
        vector of the shared signature, memoized alike."""
        with span("fused:groups", phase="group", memo="hit",
                  series=len(got.labels)):
            if self.op in ("topk", "bottomk") and not got.is_hist:
                return AGG.zero_gids(got.block), 1, None
            return AGG.group_ids_memo(
                got.block, got.labels, self.by, self.without,
                strip_metric=strip,
            )

    def _dispatch_fused(self, ctx: QueryContext, request) -> Any:
        """Route one fused kernel launch through the query dispatch
        scheduler (query/scheduler.py) when the context carries an enabled
        one — concurrent queries sharing this superblock + grid/epilogue
        signature coalesce into ONE batched launch — else run the plain
        unbatched dispatch. Disabled batching is byte-identical to the
        pre-scheduler path. Kernel variants the batched program set does
        not model (AGG.batch_variant_supported: mesh + jitter/masked
        grids, pallas-promoted irregular grids, jittered hist) skip the
        scheduler outright — paying the batch window for a launch that is
        guaranteed to fall back per-lane would be pure added latency."""
        import time as _time

        from ...metrics import current_phases

        sched = getattr(ctx, "dispatch_scheduler", None)
        if sched is not None and hasattr(sched, "observe_key"):
            # recurrence feed for standing-query promotion: every fused
            # dispatch counts, batching enabled or not (the ring is the
            # retained per-key state the batch groups used to drop at
            # close) — see query/scheduler.KeyStatsRing
            self._observe_key(ctx, sched)
        # phase decomposition (obs/querylog.py): time around the launch is
        # split into "admission" (batch-window queue wait — the scheduler
        # stamps the group's actual kernel seconds on the request) and
        # "dispatch" (the launch itself). Pure host-side perf_counter
        # bookkeeping: no device sync is added around the (async) dispatch.
        rec = current_phases()
        obs = getattr(ctx, "obs", None)
        # the cost model's prediction rides the request: the scheduler's
        # adaptive batch window widens/narrows on the decayed sum of these
        request.predicted_cost_s = float(
            getattr(ctx, "predicted_cost_s", 0.0) or 0.0
        )
        t0 = _time.perf_counter()
        if (sched is not None and getattr(sched, "enabled", False)
                and AGG.batch_variant_supported(
                    request.block, request.func, request.kind,
                    request.is_delta, request.mesh)):
            request.timeout_s = ctx.remaining_deadline_s()
            if obs is not None:
                obs["batched"] = True
            out = sched.dispatch(request)
            if rec is not None:
                total = _time.perf_counter() - t0
                exec_s = request.exec_seconds
                if exec_s is not None:
                    exec_s = min(max(float(exec_s), 0.0), total)
                    rec.add("admission", total - exec_s)
                    rec.add("dispatch", exec_s)
                else:
                    # a coalesced duplicate lane: its own request object
                    # never reached the executing leader — the shared wait
                    # is indivisible, attribute it all to dispatch
                    rec.add("dispatch", total)
            if obs is not None and request.executable_key is not None:
                # kernel-observatory join key (obs/kernels.py): the leader
                # stamped the executable that served this lane (a
                # coalesced duplicate lane's own request stays None —
                # mirroring exec_seconds)
                obs["executable_key"] = request.executable_key
                obs["compile_miss"] = request.compile_miss
            return out
        if obs is not None:
            obs.setdefault("batched", False)
        out = request.run_single()
        if rec is not None:
            rec.add("dispatch", _time.perf_counter() - t0)
        if obs is not None:
            # solo path: the launch ran on THIS thread — read the
            # executable identity straight from the registry's capture
            from ...obs.kernels import KERNELS

            info = KERNELS.last_dispatch()
            if info:
                obs["executable_key"] = info.get("executable_key")
                obs["compile_miss"] = info.get("compile_miss")
        return out

    def _observe_key(self, ctx: QueryContext, sched) -> None:
        """Record this dispatch in the scheduler's per-key recurrence ring.
        The key normalizes away the sliding live-edge times (a dashboard
        re-issuing the same panel with a fresh ``end=now`` must count as
        ONE recurring key): dataset + the root span's PromQL + grid shape.
        The descriptor carries what the standing promoter needs to
        re-register the query; ``end_lag_ms`` (wall clock minus the grid
        end) distinguishes live-edge dashboards from historical scans, and
        ``end_ms`` against the key's first sighting one that follows the
        clock from a fixed range that merely ended a moment ago."""
        import time as _time

        if getattr(ctx, "standing_refresh", False):
            # the maintainer's own refresh dispatches must not feed the
            # ring — a standing query would keep itself "hot" forever
            return
        root = getattr(ctx, "trace_root", None)
        promql = root.tags.get("promql") if root is not None else None
        if root is not None and root.parent_id is not None:
            # a remote child's leg: the ORIGIN observes the query once
            return
        key = (
            ctx.dataset, promql, self.step_ms, self.window_ms,
            self.end_ms - self.start_ms,
        ) if promql else (
            ctx.dataset, self.op, self.function, self.filters,
            tuple(self.by or ()), tuple(self.without or ()),
            self.step_ms, self.window_ms, self.end_ms - self.start_ms,
        )
        now_ms = _time.time() * 1000.0
        sched.observe_key(key, {
            "promql": promql,
            "dataset": ctx.dataset,
            "op": self.op,
            "function": self.function,
            "params": self.params,
            "hist_quantile": self.hist_quantile,
            "step_ms": self.step_ms,
            "window_ms": self.window_ms,
            "span_ms": self.end_ms - self.start_ms,
            "end_lag_ms": now_ms - float(self.end_ms),
            "end_ms": float(self.end_ms),
        })

    def do_execute(self, ctx: QueryContext) -> QueryResult:
        from ...ops.kernels import RangeParams, pad_steps
        from ..scheduler import FusedRequest

        if getattr(ctx, "allow_partial_results", False):
            # the fused program is all-or-nothing; partial-results queries
            # need the merge tree's lost-child tolerance
            return self._fall(ctx, "partial_results")
        if getattr(ctx, "dispatcher", None) is not None:
            # a child-dispatch hook (fault injection / chaos harness) only
            # fires on per-child dispatch — run the tree it can intercept
            return self._fall(ctx, "dispatcher")
        if self.mesh is not None and not fused_mesh_supported(
            self.mesh, self.op, self.function
        ):
            # the sharded program models the fused op/function set over a
            # 1-D series mesh; anything else keeps the caller's fallback
            # (the mesh engines' legacy per-shard kernels, or the tree)
            return self._fall(ctx, "mesh_unsupported")
        func = self.function or "last"
        stage_mode = _stage_mode_for_function(self.function)
        with span("fused:stage", phase="stage"):
            got = self._superblock(ctx, stage_mode)
        if isinstance(got, str):
            return self._fall(ctx, got)
        if got is None:
            return QueryResult()
        obs = getattr(ctx, "obs", None)
        if obs is not None:
            # query-observatory path annotations: the fused path served
            # this query, over a superblock of this grid class (metadata
            # reads only — .grid_class never touches device memory)
            obs["path"] = "fused"
            obs["grid_class"] = ST.grid_class(got.block)
        nsteps = self.num_steps()
        params = RangeParams(
            self.start_ms - self.offset_ms, self.step_ms, nsteps,
            self.window_ms,
        )
        strip = self.function is not None and self.function not in _DROP_NAME_KEEP
        gids_dev, G, group_labels = self._group_ids(got, strip)
        if got.scheme_dev is not None:
            return self._execute_base2(ctx, got, gids_dev, G, group_labels,
                                       params, nsteps, strip)
        if got.is_hist:
            # 3-D histogram superblock: per-bucket fused sum (+ optional
            # device-side histogram_quantile interpolation epilogue).
            # op/func support was already vetted (_unsupported_shape) before
            # the superblock's stats bump, on both the hit and build paths.
            with span(f"fused:dispatch:hist_{func}"):
                out = self._dispatch_fused(ctx, FusedRequest(
                    block=got.block, func=func, kind="hist", epilogue=(),
                    gids_dev=gids_dev, G=G,
                    qv=float(self.hist_quantile or 0.0), params=params,
                    j_pad=pad_steps(nsteps), is_counter=False,
                    is_delta=got.is_delta, mesh=self.mesh,
                    mesh_desc=self._mesh_desc(), les_dev=got.les_dev,
                    hist_q=self.hist_quantile is not None,
                    run_single=lambda: AGG.fused_hist_range_aggregate(
                        func, got.block, gids_dev, G, params, got.les_dev,
                        q=self.hist_quantile, is_delta=got.is_delta,
                        mesh=self.mesh,
                    ),
                ))
            if self.hist_quantile is not None:
                # quantile fused on device: [G, J] is all that comes back
                labels = [_strip_metric(l) for l in group_labels]
                return QueryResult(grids=[
                    Grid(labels, self.start_ms, self.step_ms, nsteps, out)
                ])
            placeholder = np.full((G, nsteps), np.nan, np.float32)
            return QueryResult(grids=[
                Grid(group_labels, self.start_ms, self.step_ms, nsteps,
                     placeholder, hist=out, les=got.les)
            ])
        if self.op in ("topk", "bottomk"):
            k = max(int(self.params[0]), 1)
            with span(f"fused:dispatch:{self.op}:{func}"):
                vals_dev, idx_dev = self._dispatch_fused(ctx, FusedRequest(
                    block=got.block, func=func, kind="topk",
                    epilogue=("topk", k, self.op == "bottomk"),
                    gids_dev=gids_dev, G=1, qv=0.0,
                    params=params, j_pad=pad_steps(nsteps),
                    is_counter=got.is_counter, is_delta=got.is_delta,
                    mesh=self.mesh, mesh_desc=self._mesh_desc(),
                    run_single=lambda: AGG.fused_topk(
                        func, got.block, k, self.op == "bottomk", params,
                        is_counter=got.is_counter, is_delta=got.is_delta,
                        mesh=self.mesh,
                    ),
                ))
            return self._present_topk(
                np.asarray(vals_dev)[:, :nsteps],
                np.asarray(idx_dev)[:, :nsteps], got.labels, strip, nsteps,
            )
        if self.op == "quantile":
            q = float(self.params[0])
            with span(f"fused:dispatch:quantile:{func}"):
                out = self._dispatch_fused(ctx, FusedRequest(
                    block=got.block, func=func, kind="quantile",
                    epilogue=("quantile",), gids_dev=gids_dev, G=G, qv=q,
                    params=params, j_pad=pad_steps(nsteps),
                    is_counter=got.is_counter, is_delta=got.is_delta,
                    mesh=self.mesh, mesh_desc=self._mesh_desc(),
                    run_single=lambda: AGG.fused_quantile(
                        func, got.block, gids_dev, G, q, params,
                        is_counter=got.is_counter, is_delta=got.is_delta,
                        mesh=self.mesh,
                    ),
                ))
            return QueryResult(grids=[
                Grid(group_labels, self.start_ms, self.step_ms, nsteps, out)
            ])
        with span(f"fused:dispatch:{func}"):
            out = self._dispatch_fused(ctx, FusedRequest(
                block=got.block, func=func, kind="agg",
                epilogue=("agg", self.op), gids_dev=gids_dev, G=G, qv=0.0,
                params=params, j_pad=pad_steps(nsteps),
                is_counter=got.is_counter, is_delta=got.is_delta,
                mesh=self.mesh, mesh_desc=self._mesh_desc(),
                run_single=lambda: AGG.fused_range_aggregate(
                    func, self.op, got.block, gids_dev, G, params,
                    is_counter=got.is_counter, is_delta=got.is_delta,
                    mesh=self.mesh,
                ),
            ))
        if self.hist_quantile is not None:
            # classic-bucket histogram_quantile (vetted in
            # _unsupported_shape: "le" is in the grouping): the [G, J]
            # by-(le,...) partials from the ONE fused dispatch above pivot
            # into per-group cumulative grids and interpolate with the
            # native path's kernel
            from .transformers import classic_histogram_quantile

            q_labels, q_vals = classic_histogram_quantile(
                self.hist_quantile, group_labels,
                np.asarray(out)[:, :nsteps],
            )
            return QueryResult(grids=[
                Grid([_strip_metric(l) for l in q_labels], self.start_ms,
                     self.step_ms, nsteps, q_vals)
            ])
        return QueryResult(
            grids=[Grid(group_labels, self.start_ms, self.step_ms, nsteps, out)]
        )

    def _execute_base2(self, ctx: QueryContext, got, gids_dev, G: int,
                       group_labels, params, nsteps: int, strip: bool):
        """A superblock of base-2 exponential histograms: each group's
        (scale, offset, K) from its series' sidecars (a device reduction,
        memoised on the block with the group ids), then ONE fused program —
        per-bucket rate at each series' own scheme, the merge onto the
        group's smallest scale, the sum and the quantile on each group's
        bounds (ops/aggregations._base2_epilogue)."""
        from ...ops.kernels import pad_steps
        from ..scheduler import FusedRequest

        func = self.function or "last"
        key = (tuple(self.by) if self.by else None,
               tuple(self.without) if self.without else None, bool(strip))
        with span("fused:groups", phase="group", memo="hit",
                  series=len(got.labels)):
            plan = AGG.base2_group_plan(got.block, gids_dev, G,
                                        got.scheme_dev, key)
        q = self.hist_quantile
        with span(f"fused:dispatch:hist_{func}"):
            out = self._dispatch_fused(ctx, FusedRequest(
                block=got.block, func=func, kind="hist2", epilogue=(),
                gids_dev=gids_dev, G=G, qv=float(q or 0.0), params=params,
                j_pad=pad_steps(nsteps), is_counter=False,
                is_delta=got.is_delta, hist_q=q is not None,
                run_single=lambda: AGG.fused_base2_hist_aggregate(
                    func, got.block, gids_dev, G, params, plan,
                    got.scheme_dev, q=q, is_delta=got.is_delta),
            ))
        if q is not None:
            labels = [_strip_metric(l) for l in group_labels]
            return QueryResult(grids=[
                Grid(labels, self.start_ms, self.step_ms, nsteps, out)])
        placeholder = np.full((G, nsteps), np.nan, np.float32)
        return QueryResult(grids=[
            Grid(group_labels, self.start_ms, self.step_ms, nsteps,
                 placeholder, hist=out, schemes=tuple(plan[2]))])

    def _present_topk(self, vals, idx, labels, strip: bool,
                      nsteps: int) -> QueryResult:
        """Reconstruct Prometheus topk/bottomk rows from the compact [k, J]
        winner set: each surviving series keeps its own labels with values
        only at steps it won (NaN elsewhere) — exactly the ``topk_mask``
        output restricted to rows that survive, built host-side in
        O(k*J)."""
        finite = np.isfinite(vals)
        used = np.unique(idx[finite])
        out_labels, rows = [], []
        for s in used:
            m = (idx == s) & finite
            row = np.full(nsteps, np.nan, np.float32)
            r_i, c_i = np.nonzero(m)
            row[c_i] = vals[r_i, c_i]
            lbls = labels[int(s)]
            out_labels.append(_strip_metric(lbls) if strip else lbls)
            rows.append(row)
        v = (np.stack(rows) if rows
             else np.zeros((0, nsteps), np.float32))
        return QueryResult(grids=[
            Grid(out_labels, self.start_ms, self.step_ms, nsteps, v)
        ])


class RollupServeExec(ExecPlan):
    """Serve a long-range query from rollup summary blocks instead of raw
    samples (doc/perf.md "Sketch rollup tier"): the planner substituted
    this node because the query's step and window are multiples of a
    registered rollup's resolution, so every answer reads O(periods)
    per-period summaries — min/max/sum/count moments, reset-corrected
    counter lasts, and mergeable log-linear sketches — rather than
    O(raw samples). Quantiles evaluate ON DEVICE from the sketch blocks
    (merge-sketches -> rank-scan epilogue, psum-mergeable across a series
    mesh via the same shard_map pattern as the fused histogram path);
    ``histogram_quantile`` over classic bucket counters folds the [G, J]
    per-``le`` rollup rates through the native interpolation kernel.

    The serve is re-validated at RUNTIME against the live entry (the
    maintainer may have rebuilt it, the chooser may have retired it, or
    the watermark may no longer cover a moved live edge): any mismatch
    delegates to ``fallback`` — the exact plan the planner would have
    built without substitution — under the ``rollup_ineligible`` taxonomy
    entry, so results never silently degrade. The querylog ``path`` field
    records ``rollup`` on success."""

    def __init__(self, rollups, rollup_key, filters, function,
                 function_args, start_ms: int, end_ms: int, step_ms: int,
                 window_ms: int, fallback, op=None, by=None, without=None,
                 params=(), hist_quantile: float | None = None, mesh=None):
        super().__init__()
        self.rollups = rollups
        self.rollup_key = rollup_key
        self.filters = tuple(filters)
        self.function = function
        self.function_args = tuple(function_args or ())
        self.start_ms = start_ms
        self.end_ms = end_ms
        self.step_ms = step_ms
        self.window_ms = window_ms
        self.op = op  # None = per-series range function (window kind)
        self.by = by
        self.without = without
        self.params = tuple(params)
        self.hist_quantile = hist_quantile
        self.mesh = mesh
        self._fallback_factory = fallback
        self._fallback: ExecPlan | None = None

    @property
    def fallback(self) -> ExecPlan:
        if self._fallback is None:
            self._fallback = self._fallback_factory()
        return self._fallback

    def args_str(self) -> str:
        fs = ",".join(f"{f.column}{f.op}{f.value}" for f in self.filters)
        extra = f" op={self.op} by={self.by}" if self.op else ""
        if self.hist_quantile is not None:
            extra += f" hist_q={self.hist_quantile}"
        return (
            f"fn={self.function} window={self.window_ms} "
            f"res={self.rollup_key[2]} filters=[{fs}]{extra}"
        )

    def num_steps(self) -> int:
        return int((self.end_ms - self.start_ms) // self.step_ms) + 1

    def _fall(self, ctx: QueryContext, reason: str) -> QueryResult:
        from ...metrics import current_span, record_fused_fallback

        s = current_span()
        if s is not None:
            s.tags["fused_fallback"] = reason
        obs = getattr(ctx, "obs", None)
        if obs is not None:
            obs["path"] = "fallback"
            obs["fallback"] = reason
        record_fused_fallback(reason)
        return self.fallback.execute(ctx)

    def do_execute(self, ctx: QueryContext) -> QueryResult:
        from ...metrics import record_rollup_serve
        from ...ops import sketch as SKETCH

        rollups = self.rollups
        view = None
        if rollups is not None:
            view = rollups.serve_view(
                self.rollup_key, self.function, self.window_ms,
                self.start_ms, self.end_ms, self.step_ms,
            )
        if view is None:
            # entry retired/rebuilt/behind the live edge since plan time:
            # run the exact plan the planner would have built instead
            return self._fall(ctx, "rollup_ineligible")
        entry = view["entry"]
        with span("rollup:stage", phase="stage"):
            dev = rollups.device_arrays(entry)
        S = entry.n_series
        labels = view["labels"]
        if S > ctx.max_series:
            raise QueryError(
                f"query selects {S} series > limit {ctx.max_series}"
            )
        p0, p_lo, p_hi = view["p0"], view["p_lo"], view["p_hi"]
        win_p, step_p = view["win_p"], view["step_p"]
        nsteps = self.num_steps()
        window_s = self.window_ms / 1000.0
        # the whole point: stats record O(periods) summary reads, never
        # the raw sample count the fallback would have scanned
        ctx.stats.bump(series_scanned=S,
                       samples_scanned=S * max(p_hi - p_lo, 0))
        obs = getattr(ctx, "obs", None)
        if obs is not None:
            obs["path"] = "rollup"
            obs["rollup_resolution_ms"] = view["resolution_ms"]
        if S == 0:
            return QueryResult()
        a = p_lo - 1 - p0  # moment-kernel slice start (one lead period)
        n = p_hi - p_lo + 1
        alloc_p = view["alloc_p"]
        _IDENT = {"mn": np.inf, "mx": -np.inf, "sm": 0.0, "cnt": 0.0,
                  "clast": 0.0}

        def msl(name):
            """[S, n] moment slice with index 0 = the lead period. Arrays
            only cover the entry's data edge (alloc_p local periods);
            closed-but-empty periods outside pad with the moment's IDENTITY
            value (the windowed-count mask yields NaN for all-empty
            windows), except ``clast`` which edge-pads so counter diffs
            past the data edge read 0 increase, not a reset to baseline.
            The left lead pad is never read by the window reduction
            (counter shapes require a real lead at eligibility time)."""
            arr = dev[name]
            lo, hi = a, a + n
            s = arr[:, max(lo, 0):min(hi, alloc_p)]
            left, right = max(0, -lo), max(0, hi - alloc_p)
            if not left and not right:
                return s
            parts = []
            if left:
                parts.append(jnp.full((arr.shape[0], left), _IDENT[name],
                                      arr.dtype))
            parts.append(s)
            if right:
                if name == "clast":
                    parts.append(jnp.repeat(arr[:, -1:], right, axis=1))
                else:
                    parts.append(jnp.full((arr.shape[0], right),
                                          _IDENT[name], arr.dtype))
            return jnp.concatenate(parts, axis=1)

        strip = (self.function is not None
                 and self.function not in _DROP_NAME_KEEP)
        if self.op is None:
            # per-series range function
            if self.function == "quantile_over_time":
                q = float(self.function_args[0])
                counts = dev["sketch"][:, p_lo - p0:min(p_hi - p0, alloc_p), :]
                tail = (p_hi - p_lo) - counts.shape[1]
                if tail > 0:  # implicitly-empty closed periods: zero counts
                    counts = jnp.concatenate([
                        counts,
                        jnp.zeros((counts.shape[0], tail, counts.shape[2]),
                                  counts.dtype),
                    ], axis=1)
                starts = jnp.arange(nsteps, dtype=jnp.int32) * step_p
                with span("rollup:dispatch:sketch_quantile",
                          phase="dispatch"):
                    out = SKETCH.rollup_sketch_quantile(
                        counts, dev["centers"], starts, q, win_p
                    )
            else:
                with span(f"rollup:dispatch:{self.function}",
                          phase="dispatch"):
                    out = SKETCH.rollup_moment_range(
                        self.function, msl("mn"), msl("mx"), msl("sm"),
                        msl("cnt"), msl("clast"), win_p, step_p, window_s,
                    )
            record_rollup_serve("window")
            out_labels = [_strip_metric(l) for l in labels] if strip else labels
            return QueryResult(grids=[
                Grid(out_labels, self.start_ms, self.step_ms, nsteps, out)
            ])
        with span("rollup:groups", phase="group", memo="miss", series=S):
            gids_np, group_labels = AGG.group_ids_for(
                labels,
                list(self.by) if self.by else None,
                list(self.without) if self.without else None,
            )
            G = max(len(group_labels), 1)
            gids = jnp.asarray(gids_np)
        if self.op == "quantile":
            q = float(self.params[0])
            mesh = self.mesh
            if mesh is not None and (S == 0 or S % mesh.devices.size):
                mesh = None  # series axis not mesh-divisible: solo dispatch
            with span("rollup:dispatch:agg_sketch_quantile",
                      phase="dispatch"):
                out = SKETCH.rollup_agg_sketch_quantile(
                    self.function, msl("mn"), msl("mx"), msl("sm"),
                    msl("cnt"), msl("clast"), gids, q, G, win_p, step_p,
                    window_s, mesh=mesh,
                )
            record_rollup_serve("agg")
            return QueryResult(grids=[
                Grid(group_labels, self.start_ms, self.step_ms, nsteps, out)
            ])
        with span(f"rollup:dispatch:{self.op}:{self.function}",
                  phase="dispatch"):
            out = SKETCH.rollup_moment_aggregate(
                self.function, self.op, msl("mn"), msl("mx"), msl("sm"),
                msl("cnt"), msl("clast"), gids, G, win_p, step_p, window_s,
            )
        if self.hist_quantile is not None:
            # classic-bucket histogram_quantile: [G, J] per-``le`` rollup
            # rates interpolate through the native path's kernel
            from .transformers import classic_histogram_quantile

            q_labels, q_vals = classic_histogram_quantile(
                self.hist_quantile, group_labels, np.asarray(out)[:, :nsteps]
            )
            record_rollup_serve("hist_quantile")
            return QueryResult(grids=[
                Grid([_strip_metric(l) for l in q_labels], self.start_ms,
                     self.step_ms, nsteps, q_vals)
            ])
        record_rollup_serve("agg")
        return QueryResult(grids=[
            Grid(group_labels, self.start_ms, self.step_ms, nsteps, out)
        ])


class PartialReduceExec(NonLeafExecPlan):
    """Reduce phase WITHOUT the present phase: merges children's partial
    components and re-emits them as ``__comp__``-labeled grids. This is the
    executor of L.PartialAggregate — what a federation peer runs so only
    O(groups) mergeable components cross the wire (reference
    RowAggregator.scala:28,114; AggrOverRangeVectors.scala:224)."""

    supports_partial = True

    def __init__(self, child_plans, op: str, by=None, without=None):
        super().__init__(child_plans)
        self.op = op
        self.by = by
        self.without = without

    def args_str(self) -> str:
        return f"op={self.op} by={self.by} without={self.without}"

    def do_execute(self, ctx: QueryContext) -> QueryResult:
        partials = []
        for r in self.execute_children(ctx):
            p = collect_partials(r, self.op)
            if p is not None:
                partials.append(p)
        key_to, meta = _merge_partials(self.op, partials)
        if meta is None:
            return QueryResult()
        group_labels = [slot["labels"] for slot in key_to.values()]
        names = sorted({n for slot in key_to.values() for n in slot["comps"]})
        comps = {}
        for name in names:
            proto = next(
                slot["comps"][name] for slot in key_to.values()
                if name in slot["comps"]
            )
            comps[name] = np.stack([
                slot["comps"].get(
                    name, np.full(proto.shape, np.nan, np.float32)
                )
                for slot in key_to.values()
            ])
        return QueryResult(grids=partials_to_grids(group_labels, comps, meta))


@dataclass
class SketchMapReduce:
    """Transformer form of the quantile map phase: per-group log-linear
    sketch counts (ops/sketch.py), encoded as a ``__comp__="sketch"`` grid
    whose [G, J, B] counts ride the hist field. Sketches merge by addition
    across shards and peers (reference QuantileRowAggregator's serialized
    t-digests)."""

    by: tuple | None
    without: tuple | None

    def apply(self, grids: list[Grid]) -> list[Grid]:
        from ...ops import sketch as SK

        if not grids:
            return []
        meta = grids[0]
        all_labels = [l for g in grids for l in g.labels]
        mats = [g.values_np()[: g.n_series, : g.num_steps] for g in grids]
        vals = np.concatenate(mats, axis=0) if len(mats) > 1 else mats[0]
        gids, group_labels = AGG.group_ids_for(
            all_labels, list(self.by) if self.by else None,
            list(self.without) if self.without else None,
        )
        counts = np.asarray(
            SK.build_sketch(jnp.asarray(vals), jnp.asarray(gids), len(group_labels))
        )
        return partials_to_grids(group_labels, {"sketch": counts}, meta)


class QuantileMergeExec(NonLeafExecPlan):
    """Root merge for distributed quantile: children return per-group
    sketch partials (SketchMapReduce locally, PartialAggregate on peers);
    merged sketches present via log-linear interpolation. Cross-node
    quantile is approximate (~2.2% relative at SUB=32) exactly like the
    reference's t-digest exchange."""

    supports_partial = True

    def __init__(self, child_plans, q: float, by=None, without=None):
        super().__init__(child_plans)
        self.q = q
        self.by = by
        self.without = without

    def args_str(self) -> str:
        return f"q={self.q} by={self.by} without={self.without}"

    def do_execute(self, ctx: QueryContext) -> QueryResult:
        from ...ops import sketch as SK

        partials = []
        for r in self.execute_children(ctx):
            p = collect_partials(r, "sketch")
            if p is not None:
                partials.append(p)
        key_to, meta = _merge_partials("quantile", partials)
        if meta is None:
            return QueryResult()
        labels, rows = [], []
        for slot in key_to.values():
            counts = slot["comps"].get("sketch")
            if counts is None:
                continue
            labels.append(slot["labels"])
            rows.append(SK.sketch_quantile(counts[None], self.q)[0])
        vals = (np.stack(rows).astype(np.float32) if rows
                else np.zeros((0, meta.num_steps), np.float32))
        return QueryResult(
            grids=[Grid(labels, meta.start_ms, meta.step_ms, meta.num_steps, vals)]
        )


class CountValuesMergeExec(NonLeafExecPlan):
    """Root merge for pushed-down count_values: children's partial count
    rows (CountValuesMapReduce) merge by identical label set with SUM —
    exact because shards own disjoint series."""

    supports_partial = True

    def do_execute(self, ctx: QueryContext) -> QueryResult:
        grids = []
        for r in self.execute_children(ctx):
            grids.extend(r.grids)
        if not grids:
            return QueryResult()
        meta = grids[0]
        J = meta.num_steps
        merged: dict[tuple, np.ndarray] = {}
        keys: dict[tuple, dict] = {}
        for g in grids:
            vals = g.values_np()
            for i, lbls in enumerate(g.labels):
                key = tuple(sorted(lbls.items()))
                row = vals[i, :J]
                have = merged.get(key)
                if have is None:
                    merged[key] = np.array(row, np.float32)
                    keys[key] = lbls
                else:
                    # NaN-aware sum: count + absent = count
                    a, b = have, row
                    both = np.isfinite(a) & np.isfinite(b)
                    only_b = ~np.isfinite(a) & np.isfinite(b)
                    a[both] += b[both]
                    a[only_b] = b[only_b]
        labels = [keys[k] for k in merged]
        v = (np.stack(list(merged.values())) if merged
             else np.zeros((0, J), np.float32))
        return QueryResult(grids=[Grid(labels, meta.start_ms, meta.step_ms, J, v)])


class AggregatePresentExec(NonLeafExecPlan):
    """Root aggregation for non-mergeable ops (topk/bottomk/quantile/
    count_values): children concat full series to the root.

    Scale: topk/bottomk children carry a TopkCandidateFilter map phase (the
    reference TopkRowAggregator k-heap-spill analog) so the root gathers
    O(shards*k) candidate rows, exactly; count_values pushes per-shard
    counting (CountValuesMapReduce + CountValuesMergeExec); quantile scales
    via the mesh sketch path (MeshQuantileExec) when a mesh is configured.
    limitk and aggregates over arbitrary subtrees (joins) still gather the
    full series set (one [S, J] host array, fine through ~1M series x
    moderate steps; ctx.max_series bounds the gather)."""

    def __init__(self, child_plans, op: str, params=(), by=None, without=None):
        super().__init__(child_plans)
        self.op = op
        self.params = params
        self.by = by
        self.without = without

    def args_str(self) -> str:
        return f"op={self.op} params={self.params} by={self.by} without={self.without}"

    def do_execute(self, ctx: QueryContext) -> QueryResult:
        grids: list[Grid] = []
        for r in self.execute_children(ctx):
            grids.extend(r.grids)
        if not grids:
            return QueryResult()
        all_labels = [l for g in grids for l in g.labels]
        J = max(g.values_np().shape[1] for g in grids)
        meta = grids[0]
        vals = np.full((len(all_labels), J), np.nan, np.float32)
        r0 = 0
        for g in grids:
            v = g.values_np()
            vals[r0 : r0 + v.shape[0], : v.shape[1]] = v
            r0 += v.shape[0]
        op = self.op
        if op in _PARTIAL_COMPONENTS:
            # simple agg over an arbitrary subtree (e.g. over a join result);
            # pass grids through directly so histogram buckets survive
            partial = _partial_aggregate(op, grids, self.by, self.without)
            key_to, meta2 = _merge_partials(op, [partial])
            return _present(op, key_to, meta2)
        gids, group_labels = AGG.group_ids_for(
            all_labels, list(self.by) if self.by else None, list(self.without) if self.without else None
        )
        if op in ("topk", "bottomk", "limitk"):
            k = max(int(self.params[0]), 1)
            out_rows = []
            out_labels = []
            for gi in range(len(group_labels)):
                rows = np.nonzero(gids == gi)[0]
                sub = vals[rows]
                if op == "limitk":
                    masked = np.full_like(sub, np.nan)
                    masked[:k] = sub[:k]
                else:
                    masked = np.asarray(AGG.topk_mask(jnp.asarray(sub), min(k, sub.shape[0]), bottom=(op == "bottomk")))
                keep = ~np.all(np.isnan(masked), axis=1)
                for ri, kept in zip(rows, keep):
                    if kept:
                        out_labels.append(all_labels[ri])
                        out_rows.append(masked[np.nonzero(rows == ri)[0][0]])
            v = np.stack(out_rows) if out_rows else np.zeros((0, J), np.float32)
            return QueryResult(grids=[Grid(out_labels, meta.start_ms, meta.step_ms, meta.num_steps, v)])
        if op == "quantile":
            q = float(self.params[0])
            res = np.asarray(
                AGG.segment_quantile(jnp.asarray(vals), jnp.asarray(gids), len(group_labels), np.float32(q))
            )
            return QueryResult(grids=[Grid(group_labels, meta.start_ms, meta.step_ms, meta.num_steps, res)])
        if op == "count_values":
            label = str(self.params[0])
            out_labels, out_rows = [], []
            for gi, gl in enumerate(group_labels):
                counts = AGG.count_values(vals[gids == gi])
                for valstr, row in counts.items():
                    out_labels.append(dict(gl, **{label: valstr}))
                    out_rows.append(row[: meta.num_steps])
            v = np.stack(out_rows).astype(np.float32) if out_rows else np.zeros((0, meta.num_steps), np.float32)
            return QueryResult(grids=[Grid(out_labels, meta.start_ms, meta.step_ms, meta.num_steps, v)])
        raise QueryError(f"unsupported aggregation {op}")
