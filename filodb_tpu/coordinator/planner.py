"""Query planner: LogicalPlan -> ExecPlan (reference L5:
queryplanner/SingleClusterPlanner.scala:55 materialize:310 — shard fan-out,
transformer pushdown onto leaves, aggregate pushdown :1137).

Planning strategy (mirrors the reference):
- selectors fan out one leaf per shard; transformers (periodic samples,
  instant fns, scalar ops) are pushed onto every leaf so they run where the
  data is (on device, per shard block);
- mergeable aggregations (sum/min/max/count/avg/stddev/stdvar/group) push
  their map phase onto the leaves and reduce at the root
  (AggregateMapReduce -> ReduceAggregateExec, the psum path once shards are
  mesh-resident);
- non-mergeable aggregations (topk/quantile/count_values) and joins gather
  full series at the root.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

from ..core.filters import ColumnFilter
from ..query import logical as L
from ..query.exec.joins import (
    BinaryJoinExec,
    ScalarPlanExec,
    ScalarVaryingExec,
    ScalarVectorOpExec,
    SetOperatorExec,
    SubqueryWindowExec,
)
from ..query.exec.plans import (
    _PARTIAL_COMPONENTS,
    AggregateMapReduce,
    AggregatePresentExec,
    DistConcatExec,
    EmptyResultExec,
    ExecPlan,
    QueryContext,
    RawChunkExportExec,
    ReduceAggregateExec,
    SelectRawPartitionsExec,
    StitchRvsExec,
)
from ..query.exec.transformers import (
    AbsentFunctionMapper,
    InstantVectorFunctionMapper,
    LimitFunctionMapper,
    MiscellaneousFunctionMapper,
    PeriodicSamplesMapper,
    QueryError,
    ScalarOperationMapper,
    SortFunctionMapper,
)
from ..query.functions import RANGE_FUNCTIONS
from ..query.promql import query_range_to_logical_plan, query_to_logical_plan


def _filters_to_selector(filters) -> str:
    """Serialize ColumnFilters back to a PromQL matcher set for peers'
    ``match[]`` params."""
    import re as _re

    from ..core.schemas import METRIC_TAG

    parts = []
    for f in filters:
        col = "__name__" if f.column == METRIC_TAG else f.column
        if f.op in ("=", "!=", "=~", "!~"):
            v = str(f.value).replace("\\", "\\\\").replace('"', '\\"')
            parts.append(f'{col}{f.op}"{v}"')
        elif f.op == "in":
            parts.append(f'{col}=~"{"|".join(_re.escape(v) for v in f.value)}"')
    return "{" + ",".join(parts) + "}"


def _scatter_call(thunks, prefix: str):
    """Run peer-call thunks concurrently; yields each result."""
    from concurrent.futures import ThreadPoolExecutor

    if not thunks:
        return
    with ThreadPoolExecutor(max_workers=min(8, len(thunks)),
                            thread_name_prefix=prefix) as pool:
        yield from pool.map(lambda t: t(), thunks)


class TsCardinalitiesExec(ExecPlan):
    """Cardinality scan by shard-key prefix (reference TsCardinalities
    metadata plan / TsCardExec): merges every owned shard's cardinality trie
    and, multi-host, the peers' locally-pinned scans."""

    def __init__(self, prefix: Sequence[str], depth: int | None = None,
                 peers: tuple = (), auth_token: str | None = None):
        super().__init__()
        self.prefix = tuple(prefix)
        self.depth = depth if depth is not None else len(self.prefix) + 1
        self.peers = tuple(peers)
        self.auth_token = auth_token

    def args_str(self) -> str:
        return f"prefix={','.join(self.prefix)} depth={self.depth}"

    def do_execute(self, ctx: QueryContext):
        from ..query.rangevector import QueryResult

        merged: dict[tuple, dict] = {}

        def add(prefix: tuple, ts_count: int, active: int, children: int):
            slot = merged.setdefault(
                prefix, {"prefix": list(prefix), "ts_count": 0, "active": 0, "children": 0}
            )
            slot["ts_count"] += ts_count
            slot["active"] += active
            slot["children"] = max(slot["children"], children)

        for sh in ctx.memstore.shards(ctx.dataset):
            for rec in sh.cardinality.scan(list(self.prefix), self.depth):
                add(rec.prefix, rec.ts_count, rec.active_ts_count, rec.children)
        if self.peers:
            import urllib.parse

            from .planners import fetch_json

            q = f"prefix={urllib.parse.quote(','.join(self.prefix))}&depth={self.depth}"
            plan = L.TsCardinalities(self.prefix, self.depth)
            thunks = []
            for ep in self.peers:  # one pool across BOTH transports
                if ep.startswith("grpc://"):
                    from ..api.grpc_exec import remote_metadata

                    thunks.append(lambda ep=ep: remote_metadata(ep, plan, self.auth_token))
                else:
                    url = f"{ep}/api/v1/cardinality?{q}"
                    thunks.append(lambda url=url: fetch_json(
                        url, auth_token=self.auth_token, local_only=True))
            for data in _scatter_call(thunks, "filodb-card"):
                for rec in data:
                    add(tuple(rec["prefix"]), rec["ts_count"], rec["active"], rec["children"])
        res = QueryResult()
        res.metadata = sorted(merged.values(), key=lambda r: -r["ts_count"])
        res.result_type = "metadata"
        return res


class MetadataExec(ExecPlan):
    """Label values/names & series metadata queries (reference
    MetadataExecPlan execs). With ``peers`` configured (multi-host), the
    same query scatters to every peer (locally pinned) and the disjoint
    per-host answers union — otherwise label/series browsing would silently
    show only this host's shard slice."""

    is_remote = False

    def __init__(self, kind: str, filters, start_ms, end_ms, label: str | None = None,
                 limit=None, peers: tuple = (), auth_token: str | None = None):
        super().__init__()
        self.kind = kind
        self.filters = tuple(filters)
        self.start_ms = start_ms
        self.end_ms = end_ms
        self.label = label
        self.limit = limit
        self.peers = tuple(peers)
        self.auth_token = auth_token

    def _grpc_plan(self):
        if self.kind == "label_values":
            return L.LabelValues(self.label, self.filters, self.start_ms, self.end_ms)
        if self.kind == "label_names":
            return L.LabelNames(self.filters, self.start_ms, self.end_ms)
        return L.SeriesKeysByFilters(self.filters, self.start_ms, self.end_ms)

    def _peer_metadata(self) -> list:
        """Concurrent per-peer fetch on ONE pool across both transports —
        HTTP peers over the shared retrying transport (results normalized
        from __name__ to internal tags), gRPC peers via plan-level
        executePlan (already internal-tag form)."""
        import urllib.parse

        from ..core.schemas import METRIC_TAG
        from .planners import fetch_json

        def http_thunk(url):
            def go():
                data = fetch_json(url, auth_token=self.auth_token, local_only=True)
                if self.kind == "series":
                    return [
                        {(METRIC_TAG if k == "__name__" else k): v for k, v in d.items()}
                        for d in data
                    ]
                return list(data)
            return go

        t = f"start={self.start_ms / 1000}&end={self.end_ms / 1000}"
        match = urllib.parse.quote(_filters_to_selector(self.filters)) if self.filters else None
        thunks = []
        for ep in self.peers:
            if ep.startswith("grpc://"):
                from ..api.grpc_exec import remote_metadata

                plan = self._grpc_plan()
                thunks.append(lambda ep=ep, plan=plan: remote_metadata(ep, plan, self.auth_token))
                continue
            if self.kind == "label_values":
                label = "__name__" if self.label == METRIC_TAG else self.label
                url = f"{ep}/api/v1/label/{urllib.parse.quote(label)}/values?{t}"
                if match:
                    url += f"&match[]={match}"
            elif self.kind == "label_names":
                url = f"{ep}/api/v1/labels?{t}"
                if match:
                    url += f"&match[]={match}"
            else:  # series
                url = f"{ep}/api/v1/series?{t}&match[]={match or urllib.parse.quote('{}')}"
            thunks.append(http_thunk(url))
        out: list = []
        for data in _scatter_call(thunks, "filodb-meta"):
            out.extend(data)
        return out

    def do_execute(self, ctx: QueryContext):
        from ..query.rangevector import QueryResult

        ms = ctx.memstore
        res = QueryResult()
        if self.kind == "label_values":
            vals = ms.label_values(ctx.dataset, self.filters, self.label, self.start_ms, self.end_ms, self.limit)
            if self.peers:
                vals = sorted(set(vals) | set(self._peer_metadata()))
                if self.limit:
                    vals = vals[: self.limit]
            res.metadata = vals
        elif self.kind == "label_names":
            names = ms.label_names(ctx.dataset, self.filters, self.start_ms, self.end_ms)
            if self.peers:
                names = sorted(
                    set(names)
                    | {"_metric_" if n == "__name__" else n for n in self._peer_metadata()}
                )
            res.metadata = names
        elif self.kind == "series":
            series = [dict(t) for t in ms.series(ctx.dataset, self.filters, self.start_ms, self.end_ms, self.limit)]
            if self.peers:
                series.extend(self._peer_metadata())  # shard-disjoint: no dedup needed
                if self.limit:
                    series = series[: self.limit]
            res.metadata = series
        else:
            raise QueryError(f"unknown metadata query {self.kind}")
        res.result_type = "metadata"
        return res


@dataclass
class PlannerParams:
    """Per-planner config (reference PlannerParams / QueryConfig)."""

    spread: int = 3
    lookback_ms: int = 300_000
    max_series: int = 1_000_000
    deadline_s: float = 60.0
    # optional jax.sharding.Mesh: distributed aggregations compile to one
    # psum program over the shard axis instead of host-side merging
    mesh: object | None = None
    # optional lpopt AggRuleProvider: sum-by queries rewrite onto maintained
    # :agg series before planning
    agg_rules: object | None = None
    # total shards in the CLUSTER (the ingest-routing modulus). None = the
    # memstore owns the whole cluster and the modulus is inferred from it;
    # multi-node deployments MUST set this from the ShardMapper so query-side
    # pruning enumerates the same shard group ingest routing used.
    num_shards: int | None = None
    # optional shared QueryScheduler: execution runs on its bounded pool with
    # fail-fast admission + deadline abort (reference QueryScheduler.scala)
    scheduler: object | None = None
    # multi-host scatter: base URLs of PEER processes owning the other shard
    # slices of this cluster. Selector-level subqueries fan out to every peer
    # (reference: ActorPlanDispatcher scatter to peer nodes' QueryActors) and
    # concatenate with the local leaves; peers execute locally-only (the
    # remote exec pins X-FiloDB-Local so scatter never recurses).
    peer_endpoints: tuple = ()
    # bearer token for peer requests (the cluster's http_auth_token)
    remote_auth_token: str | None = None
    # coalesce concurrent IDENTICAL queries into one execution (dashboard
    # fan-out: one kernel launch serves every copy). In-flight sharing only,
    # never a cache — see coordinator.scheduler.SingleFlight.
    coalesce_identical: bool = True
    # single-dispatch cross-shard aggregates (FusedAggregateExec): when every
    # shard is local, `sum|avg|min|max|count by (...) (range_fn(...))`
    # concatenates the per-shard staged blocks into one device-resident
    # superblock and runs ONE compiled range_fn -> segment_aggregate program
    # (doc/perf.md). False forces the reference scatter/partial-merge tree.
    fused_aggregate: bool = True
    # fault tolerance (query/faults.py): default for per-query
    # allow_partial_results (merge nodes tolerate lost shards/peers,
    # tagging results with structured warnings); retry_policy / breakers
    # override the module defaults (None = DEFAULT_RETRY_POLICY /
    # GLOBAL_BREAKERS); dispatcher wraps child execution (fault injection)
    allow_partial_results: bool = False
    retry_policy: object | None = None
    breakers: object | None = None
    dispatcher: object | None = None
    # observability (metrics.py): queries slower than this record their
    # rendered trace tree + PromQL in the global slow-query log
    # (/debug/slow_queries). None disables.
    slow_query_threshold_s: float | None = 10.0
    # cross-query micro-batching (query/scheduler.DispatchScheduler):
    # concurrent fused queries sharing a hot superblock + grid/epilogue
    # signature collect for batch_window_ms and launch as ONE batched
    # kernel (vmap over per-query params). 0 disables — every dispatch
    # runs exactly like the pre-scheduler path. A shared scheduler object
    # may be passed explicitly (server: one per process, shared by the
    # scattering + local engines); else the engine builds one when the
    # window is positive.
    batch_window_ms: float = 0.0
    batch_max: int = 32
    dispatch_scheduler: object | None = None
    # per-tenant admission control (query/scheduler.AdmissionController):
    # consulted BEFORE execution with the tenant resolved from the plan's
    # selector filters (metering.tenant_of_plan); over-quota queries raise
    # AdmissionRejected (HTTP 429 + Retry-After). None = no admission.
    admission: object | None = None
    # sketch rollup tier (downsample/rollup.RollupManager): long-range
    # queries whose step/window are multiples of a registered rollup's
    # resolution substitute O(periods) summary blocks for the raw scan
    # (doc/perf.md "Sketch rollup tier"). None = no substitution; every
    # plan is byte-identical to the pre-rollup planner.
    rollups: object | None = None
    # replicated shard plane (coordinator/replication.ReplicaRouter):
    # selector scatter consults it for per-shard replica endpoints — each
    # dispatch leg pins ONE replica (x-filodb-shards) and carries its
    # sibling endpoints so the dispatch layer can fail over before
    # allow_partial_results is even considered. None = legacy peer scatter.
    replica_router: object | None = None


class SingleClusterPlanner:
    """Plans against the shards of one memstore cluster."""

    def __init__(self, memstore, dataset: str, shard_nums: Sequence[int] | None = None,
                 params: PlannerParams | None = None):
        self.memstore = memstore
        self.dataset = dataset
        self.params = params or PlannerParams()
        self._shards = shard_nums

    def shards_for(self, filters) -> list[int]:
        """Shard fan-out for a selector (reference shardsFromFilters,
        SingleClusterPlanner.scala:424): when every shard-key column is
        constrained by equality filters, only the ``2^spread`` shards the
        ingest router can place those series on are queried; otherwise all
        owned shards are scanned. Pruning with planner spread >= ingest
        spread is always a superset of the shards holding the data (the low
        ``spread`` bits range over the whole group), so a too-large spread is
        safe; configs must never shrink spread below what ingest used."""
        owned = list(self._shards) if self._shards is not None else self.memstore.shard_nums(self.dataset)
        if not filters:
            return owned
        num_shards = self.params.num_shards
        if num_shards is None:
            try:
                num_shards = self.memstore.total_shards(self.dataset)
            except (KeyError, AttributeError):
                all_nums = self.memstore.shard_nums(self.dataset)
                if not all_nums:
                    return owned
                num_shards = max(all_nums) + 1
        if not num_shards:
            return owned
        cand = self._shards_from_filters(filters, num_shards)
        if cand is None:
            return owned
        owned_set = set(owned)
        return [s for s in cand if s in owned_set]

    _MAX_SHARDKEY_COMBOS = 64

    def _shards_from_filters(self, filters, num_shards: int) -> list[int] | None:
        """Candidate shards from shard-key equality filters, or None when the
        filters don't pin every shard-key column (scan-all). Matches the
        ingest-side routing exactly: the shard-key hash fixes the high bits,
        the low ``spread`` bits range over the full 2^spread group."""
        import itertools

        from ..core.schemas import (
            METRIC_TAG, PROM_METRIC_TAG, shard_group, shardkey_hash,
        )

        from ..memstore.index import _LITERAL_ALT

        options = self._options()
        skc = tuple(options.shard_key_columns)
        eq: dict[str, set[str]] = {}
        for f in filters:
            col = METRIC_TAG if f.column == PROM_METRIC_TAG else f.column
            if f.op == "=":
                eq.setdefault(col, set()).add(f.value)
            elif f.op == "in":
                eq.setdefault(col, set()).update(f.value)
            elif (f.op == "=~" and isinstance(f.value, str)
                  and _LITERAL_ALT.match(f.value)):
                # literal-alternation regex on a shard-key column (the
                # Grafana variable-storm shape {_ns_=~"App-1|App-2"}) pins
                # it to an explicit value set exactly like `in` — same
                # dictionary-batched expansion the index applies. An empty
                # alternation part would also match a MISSING tag, which
                # routing can't pin, so it falls back to scan-all.
                parts = f.value.split("|")
                if all(parts):
                    eq.setdefault(col, set()).update(parts)
        keysets = []
        for c in skc:
            vals = eq.get(c)
            if not vals:
                return None
            keysets.append(sorted(vals))
        n_combos = 1
        for ks in keysets:
            n_combos *= len(ks)
        if n_combos > self._MAX_SHARDKEY_COMBOS:
            return None
        shards: set[int] = set()
        for combo in itertools.product(*keysets):
            skh = shardkey_hash(dict(zip(skc, combo)), options)
            shards |= shard_group(skh, self.params.spread, num_shards)
        return sorted(shards)

    def _options(self):
        from ..core.schemas import DatasetOptions

        try:
            return self.memstore.dataset(self.dataset).options
        except KeyError:
            return DatasetOptions()

    # -- entry -----------------------------------------------------------

    def materialize(self, plan: L.LogicalPlan) -> ExecPlan:
        slices = self._wide_range_slices(plan)
        if slices is None:
            return self._materialize(plan)
        # over-wide range: the raw selector span exceeds the staged int32
        # ms-offset representation (ops/staging.MAX_STAGE_SPAN_MS, ~24.8
        # days) — offsets would wrap and every windowing path over the
        # staged block (fused searchsorted precompute, tree kernels alike)
        # silently empties or corrupts late windows. Rollup substitution
        # still gets first refusal over the WHOLE range (summary blocks
        # index by period number, no span limit); only the raw serving —
        # including a rollup serve's runtime fallback — is time-sliced
        # into per-slice staged bases and stitched.
        from ..query.exec.plans import RollupServeExec

        exec_plan = self._materialize(plan)
        if isinstance(exec_plan, RollupServeExec):
            exec_plan._fallback_factory = (
                lambda: self._materialize_sliced(plan, slices)
            )
            return exec_plan
        return self._materialize_sliced(plan, slices)

    def _wide_range_slices(self, plan) -> list[tuple[int, int]] | None:
        """(delta_start_ms, delta_end_ms) trims cutting an over-wide range
        query into slices whose raw selector span each fits the staged
        int32 offset representation — or None when the plan fits as-is (or
        has no range grid to slice along, e.g. instant subqueries)."""
        from ..ops import staging as ST

        raws = L.leaf_raw_series(plan)
        if not raws:
            return None
        raw_lo = min(r.start_ms for r in raws)
        raw_hi = max(r.end_ms for r in raws)
        span = raw_hi - raw_lo
        if span <= ST.MAX_STAGE_SPAN_MS:
            return None
        # grid params live on the topmost periodic node (Aggregate and the
        # function wrappers don't carry times themselves)
        node = plan
        while node is not None and not isinstance(
            getattr(node, "start_ms", None), int
        ):
            node = getattr(node, "inner", None) or getattr(
                node, "vectors", None
            )
        start = getattr(node, "start_ms", None)
        end = getattr(node, "end_ms", None)
        step = getattr(node, "step_ms", None) or 0
        if not isinstance(start, int) or not isinstance(end, int) \
                or step <= 0 or end <= start:
            return None
        # per-slice budget: the window/lookback/offset margins around the
        # grid ride along with EVERY slice
        margin = span - (end - start)
        per = ST.MAX_STAGE_SPAN_MS - margin
        if per < step:
            return None  # window alone overflows; unsliceable
        k = int(per // step) + 1  # steps per slice: (k-1)*step <= per
        n = int((end - start) // step) + 1
        if k >= n:
            return None
        out = []
        for a in range(0, n, k):
            b = min(a + k, n) - 1
            out.append((a * step, (b - (n - 1)) * step))
        return out

    def _materialize_sliced(self, plan, slices) -> ExecPlan:
        children = [
            self._materialize(L.narrow_time(plan, ds, de))
            for ds, de in slices
        ]
        return StitchRvsExec(children)

    def _fanout(self, make_leaf, transformers, filters=None, logical=None) -> ExecPlan:
        leaves = []
        for s in self.shards_for(filters):
            leaf = make_leaf(s)
            leaf.transformers.extend(transformers)
            leaves.append(leaf)
        leaves.extend(self._peer_leaves(logical))
        if not leaves:
            return EmptyResultExec()
        if len(leaves) == 1:
            return leaves[0]
        return DistConcatExec(leaves)

    def _peer_leaves(self, logical) -> list:
        """Multi-host scatter: one locally-pinned remote exec per peer for
        this selector-level subtree. Series are disjoint across hosts (shard
        ownership), so concatenation is exact; upper transformers/aggregates
        apply to the union at this node's parent, identically to local
        leaves."""
        if logical is None:
            return []
        if not isinstance(logical, (L.PeriodicSeries, L.PeriodicSeriesWithWindowing)):
            return []
        router = self.params.replica_router
        if router is not None:
            return self._router_leaves(router, logical)
        if not self.params.peer_endpoints:
            return []
        from ..query.unparse import to_promql
        from .planners import PromQlRemoteExec

        q = None
        leaves = []
        for ep in self.params.peer_endpoints:
            if ep.startswith("grpc://"):
                # binary plan transport (reference executePlan): the logical
                # subtree ships as protobuf — no unparse round-trip
                from ..api.grpc_exec import GrpcPlanRemoteExec

                r = GrpcPlanRemoteExec(
                    ep, logical, auth_token=self.params.remote_auth_token,
                    local_only=True,
                )
            else:
                if q is None:
                    q = to_promql(logical)
                r = PromQlRemoteExec(
                    ep, q, logical.start_ms, logical.end_ms, logical.step_ms or 1,
                    auth_token=self.params.remote_auth_token, local_only=True,
                )
            r.peer_logical = logical  # for aggregate pushdown rewriting
            leaves.append(r)
        return leaves

    def _router_leaves(self, router, logical) -> list:
        """Replica-routed scatter: the router groups non-local shards into
        dispatch legs of (shards, candidate endpoints). Each leg becomes ONE
        shard-pinned remote exec against the selected replica, carrying its
        sibling endpoints for dispatch-layer failover (query/faults.py)."""
        from ..api.grpc_exec import GrpcPlanRemoteExec

        local = set(self.shards_for(None))
        num = getattr(router.plane.mapper, "num_shards", 0)
        remote = [s for s in range(num) if s not in local]
        leaves = []
        for shards, endpoints in router.legs(remote, end_ms=logical.end_ms):
            r = GrpcPlanRemoteExec(
                endpoints[0], logical,
                auth_token=self.params.remote_auth_token,
                local_only=True, shard_subset=shards,
                sibling_endpoints=endpoints[1:],
            )
            r.peer_logical = logical  # for aggregate pushdown rewriting
            leaves.append(r)
        return leaves

    def _materialize(self, p: L.LogicalPlan) -> ExecPlan:
        if isinstance(p, L.PeriodicSeries):
            mapper = PeriodicSamplesMapper(
                p.start_ms, p.end_ms, p.step_ms, None, None, p.lookback_ms, p.offset_ms, p.at_ms
            )
            raw = p.raw
            return self._fanout(
                lambda s: SelectRawPartitionsExec(s, raw.filters, raw.start_ms, raw.end_ms, raw.column),
                [mapper],
                filters=raw.filters,
                logical=p,
            )
        if isinstance(p, L.PeriodicSeriesWithWindowing):
            ts_plan = self._try_time_shard(p)
            if ts_plan is not None:
                return ts_plan
            rollup_plan = self._try_rollup_windowing(p)
            if rollup_plan is not None:
                return rollup_plan
            mapper = PeriodicSamplesMapper(
                p.start_ms, p.end_ms, p.step_ms, p.function, p.window_ms,
                offset_ms=p.offset_ms, at_ms=p.at_ms, args=p.function_args,
            )
            raw = p.raw
            return self._fanout(
                lambda s: SelectRawPartitionsExec(s, raw.filters, raw.start_ms, raw.end_ms, raw.column),
                [mapper],
                filters=raw.filters,
                logical=p,
            )
        if isinstance(p, L.RawSeries):
            # raw chunk export stays host-local (remote read serves peers'
            # raw data from their own processes)
            return self._fanout(
                lambda s: RawChunkExportExec(s, p.filters, p.start_ms, p.end_ms, p.column), [],
                filters=p.filters,
            )
        if isinstance(p, L.Aggregate):
            return self._materialize_aggregate(p)
        if isinstance(p, L.PartialAggregate):
            return self._materialize_partial_aggregate(p)
        if isinstance(p, L.BinaryJoin):
            pushed = self._try_join_pushdown(p)
            if pushed is not None:
                return pushed
            lhs = self._materialize(p.lhs)
            rhs = self._materialize(p.rhs)
            if p.op in ("and", "or", "unless"):
                return SetOperatorExec(lhs, rhs, p.op, p.on, p.ignoring)
            return BinaryJoinExec(
                lhs, rhs, p.op, p.cardinality, p.on, p.ignoring, p.include, p.return_bool
            )
        if isinstance(p, L.ScalarVectorBinaryOperation):
            vec = self._materialize(p.vector)
            sc = p.scalar
            if isinstance(sc, (L.ScalarFixedDoublePlan, L.ScalarTimeBasedPlan, L.ScalarBinaryOperation)):
                # push the mapper onto the vector subtree; scalar evaluated at
                # execution against the vector's own grid
                times = _plan_times(p.vector)
                if times is not None:
                    start, end, step = times
                    nsteps = int((end - start) // step) + 1
                    sexec = ScalarPlanExec(sc, start, step, nsteps)
                    return ScalarVectorOpExec(vec, sexec, p.op, p.scalar_is_lhs, p.return_bool)
                sexec = ScalarPlanExec(sc, getattr(sc, "start_ms", 0), getattr(sc, "step_ms", 1) or 1, 1)
                return ScalarVectorOpExec(vec, sexec, p.op, p.scalar_is_lhs, p.return_bool)
            if isinstance(sc, L.ScalarVaryingDoublePlan):
                sexec = ScalarVaryingExec(self._materialize(sc.inner), sc.function)
                return ScalarVectorOpExec(vec, sexec, p.op, p.scalar_is_lhs, p.return_bool)
            raise QueryError(f"unsupported scalar operand {sc}")
        if isinstance(p, L.ApplyInstantFunction):
            if (
                p.function == "histogram_quantile"
                and len(p.args) == 1
                and isinstance(p.args[0], (int, float))
                and isinstance(p.inner, L.Aggregate)
                and p.inner.op == "sum"
            ):
                # the canonical SRE chain histogram_quantile(q, sum by (le)
                # (rate(m_bucket[w]))): fuse the interpolation epilogue into
                # the single-dispatch aggregate program (doc/perf.md)
                fused = self._try_fused_aggregate(
                    p.inner, hist_quantile=float(p.args[0])
                )
                if fused is not None:
                    return fused
            inner = self._materialize(p.inner)
            inner.transformers.append(InstantVectorFunctionMapper(p.function, p.args))
            return inner
        if isinstance(p, L.ApplyMiscellaneousFunction):
            if p.function == "_filodb_chunkmeta_all":
                from ..query.exec.plans import ChunkMetaExec

                leaves = L.leaf_raw_series(p)
                if len(leaves) != 1:
                    raise QueryError(
                        "_filodb_chunkmeta_all needs exactly one selector, "
                        f"got {len(leaves)}"
                    )
                raw = leaves[0]
                plans = [
                    ChunkMetaExec(s, raw.filters, raw.start_ms, raw.end_ms)
                    for s in self.shards_for(raw.filters)
                ]
                return plans[0] if len(plans) == 1 else DistConcatExec(plans)
            inner = self._materialize(p.inner)
            inner.transformers.append(MiscellaneousFunctionMapper(p.function, p.str_args))
            return inner
        if isinstance(p, L.ApplySortFunction):
            inner = self._materialize(p.inner)
            inner.transformers.append(SortFunctionMapper(p.descending))
            return inner
        if isinstance(p, L.ApplyAbsentFunction):
            inner = self._materialize(p.inner)
            nsteps = int((p.end_ms - p.start_ms) // p.step_ms) + 1 if p.step_ms else 1
            inner.transformers.append(
                AbsentFunctionMapper(p.filters, p.start_ms, p.step_ms or 1, nsteps)
            )
            return inner
        if isinstance(p, L.ApplyLimitFunction):
            inner = self._materialize(p.inner)
            inner.transformers.append(LimitFunctionMapper(p.limit))
            return inner
        if isinstance(p, (L.ScalarFixedDoublePlan, L.ScalarTimeBasedPlan, L.ScalarBinaryOperation)):
            nsteps = int((p.end_ms - p.start_ms) // p.step_ms) + 1 if p.step_ms else 1
            return ScalarPlanExec(p, p.start_ms, p.step_ms or 1, nsteps)
        if isinstance(p, L.ScalarVaryingDoublePlan):
            return ScalarVaryingExec(self._materialize(p.inner), p.function)
        if isinstance(p, L.SubqueryWithWindowing):
            inner = self._materialize(p.inner)
            return SubqueryWindowExec(
                inner, p.function, p.window_ms, p.sub_step_ms,
                p.start_ms, p.end_ms, p.step_ms, p.offset_ms, p.function_args,
            )
        if isinstance(p, L.TopLevelSubquery):
            return self._materialize(p.inner)
        if isinstance(p, L.TsCardinalities):
            return TsCardinalitiesExec(
                p.shard_key_prefix, p.num_groups,
                peers=self.params.peer_endpoints,
                auth_token=self.params.remote_auth_token,
            )
        if isinstance(p, (L.LabelValues, L.LabelNames, L.SeriesKeysByFilters)):
            kind = {"LabelValues": "label_values", "LabelNames": "label_names",
                    "SeriesKeysByFilters": "series"}[type(p).__name__]
            return MetadataExec(
                kind, p.filters, p.start_ms, p.end_ms,
                label=getattr(p, "label", None),
                peers=self.params.peer_endpoints,
                auth_token=self.params.remote_auth_token,
            )
        raise QueryError(f"cannot materialize {type(p).__name__}")

    def _materialize_partial_aggregate(self, p: "L.PartialAggregate") -> ExecPlan:
        """Execute the map phase only and return __comp__-labeled mergeable
        components — what a federation peer runs for a pushed-down
        aggregate (reference partial AggregateItem exchange,
        RowAggregator.scala:28,114)."""
        from ..query.exec.plans import (
            PartialReduceExec,
            SketchMapReduce,
        )

        inner = self._materialize(p.inner)
        if p.op == "quantile":
            mapper = SketchMapReduce(p.by, p.without)
        elif p.op in _PARTIAL_COMPONENTS:
            mapper = AggregateMapReduce(p.op, p.by, p.without)
        else:
            raise QueryError(f"no mergeable partial form for {p.op}")
        if isinstance(inner, DistConcatExec) and not inner.transformers:
            for child in inner.child_plans:
                child.transformers.append(mapper)
            return PartialReduceExec(inner.child_plans, p.op, p.by, p.without)
        inner.transformers.append(mapper)
        return PartialReduceExec([inner], p.op, p.by, p.without)

    def _materialize_aggregate(self, p: L.Aggregate) -> ExecPlan:
        mesh_plan = self._try_mesh_aggregate(p)
        if mesh_plan is not None:
            return mesh_plan
        fused = self._try_fused_aggregate(p)
        if fused is not None:
            return fused
        return self._materialize_aggregate_tree(p)

    def _try_fused_aggregate(self, p: L.Aggregate,
                             hist_quantile: float | None = None):
        """Single-dispatch path: `op by (...) (range_fn(selector[w]))` with
        every shard local plans to a FusedAggregateExec over one
        device-resident superblock (O(1) kernel launches) — including 3-D
        histogram superblocks, fused ``topk``/``bottomk``/``quantile``
        epilogues, and (via ``hist_quantile``) the device-side
        ``histogram_quantile`` interpolation epilogue. Grid SHAPE is not a
        plan-time concern: the dispatch classifies the staged superblock's
        grid (regular | jitter | holes | irregular, staging.grid_class)
        and selects the matching kernel variant — jittered and holey
        scrape grids stay single-dispatch (doc/perf.md "Jitter-tolerant
        fused path"), with the ``grid_jitter``/``grid_holes`` taxonomy
        entries reserved for shapes the jitter variants truly can't model
        (degraded to the general fused kernel, never to the tree). The
        reference scatter tree is built alongside as the runtime fallback
        (partial results, mixed schemas, unsupported hist shapes)."""
        from ..query.exec.plans import (
            FUSED_AGG_OPS,
            FUSED_EPI_OPS,
            FUSED_FUNCS,
            FusedAggregateExec,
        )

        params = self.params
        if not params.fused_aggregate or params.peer_endpoints or params.replica_router is not None:
            return None
        if p.op in FUSED_AGG_OPS:
            if p.params:
                return None
        elif p.op in FUSED_EPI_OPS:
            if len(p.params) != 1 or not isinstance(p.params[0], (int, float)):
                return None
            if p.op in ("topk", "bottomk") and (p.by or p.without):
                # the compact [k, J] device epilogue is global-only; grouped
                # topk keeps the per-shard candidate pre-reduction tree
                return None
        else:
            return None
        inner = p.inner
        if isinstance(inner, L.PeriodicSeriesWithWindowing):
            if (
                inner.function not in FUSED_FUNCS
                or inner.function_args
                or inner.at_ms is not None
            ):
                return None
            func, window = inner.function, inner.window_ms
        elif isinstance(inner, L.PeriodicSeries):
            if inner.at_ms is not None:
                return None
            func, window = None, inner.lookback_ms
        else:
            return None
        shards = self.shards_for(inner.raw.filters)
        if not shards:
            return None
        mesh = None
        if params.mesh is not None:
            # a configured device mesh rides the SAME fused path: the
            # superblock series axis partitions across it and the program
            # runs under shard_map (ONE multi-chip dispatch). Simple
            # aggregates reach here via the mesh engines' delegation
            # (_try_mesh_aggregate wins for them); this branch covers the
            # epilogue ops and fused histogram_quantile, which the legacy
            # mesh kernels never modeled.
            from ..parallel.mesh import series_mesh
            from ..query.exec.plans import fused_mesh_supported

            mesh = series_mesh(params.mesh)
            if not fused_mesh_supported(mesh, p.op, func):
                return None
        if hist_quantile is not None:
            # the fallback must reproduce the WHOLE fused subtree — the
            # aggregate tree plus the histogram_quantile mapper on top
            def fallback():
                tree = self._materialize_aggregate_tree(p)
                tree.transformers.append(
                    InstantVectorFunctionMapper(
                        "histogram_quantile", (hist_quantile,)
                    )
                )
                return tree
        else:
            def fallback():
                return self._materialize_aggregate_tree(p)
        raw_start, raw_end = self._fused_raw_range(
            inner.raw.start_ms, inner.raw.end_ms
        )
        fused = FusedAggregateExec(
            shards, inner.raw.filters, raw_start, raw_end,
            inner.raw.column, p.op, p.by, p.without, func,
            inner.start_ms, inner.end_ms, inner.step_ms or 1, window,
            inner.offset_ms,
            # lazy: the O(shards) reference tree only materializes if a
            # runtime condition actually falls back to it
            fallback=fallback,
            params=p.params,
            hist_quantile=hist_quantile,
            mesh=mesh,
        )
        rollup = self._try_rollup_aggregate(
            p, inner, func, window, hist_quantile, fused, mesh
        )
        return rollup if rollup is not None else fused

    def _try_rollup_aggregate(self, p: "L.Aggregate", inner, func,
                              window_ms: int, hist_quantile, fused, mesh):
        """Rollup substitution over the fused aggregate shape: when a
        registered rollup's resolution divides this query's step AND
        window and its closed coverage spans the grid, the [G, J] answer
        comes from O(periods) summary blocks — moments for
        sum/count/avg/min/max, merged sketches for the quantile epilogue,
        per-``le`` counter rollups for classic histogram_quantile. The
        already-built FusedAggregateExec IS the fallback, so plan-time
        ineligibility (returning None) and runtime ineligibility
        (``rollup_ineligible``) are both bit-identical to today's path."""
        from ..query.exec.plans import RollupServeExec
        from ..downsample.rollup import ROLLUP_AGG_OPS, ROLLUP_FUNCS

        rollups = self.params.rollups
        if rollups is None or func is None or inner.raw.column is not None:
            return None
        if func not in ROLLUP_FUNCS or inner.offset_ms:
            return None
        if hist_quantile is not None:
            # classic bucket series only: the interpolation needs the
            # per-``le`` rate partials in the grouping
            if p.op != "sum" or "le" not in tuple(p.by or ()):
                return None
        elif p.op not in ROLLUP_AGG_OPS and p.op != "quantile":
            return None
        key = rollups.plan(
            self.dataset, inner.raw.filters, func, inner.step_ms or 1,
            window_ms, inner.start_ms, inner.end_ms, inner.offset_ms,
        )
        if key is None:
            return None
        return RollupServeExec(
            rollups, key, inner.raw.filters, func, (),
            inner.start_ms, inner.end_ms, inner.step_ms or 1, window_ms,
            fallback=lambda: fused, op=p.op, by=p.by, without=p.without,
            params=p.params, hist_quantile=hist_quantile, mesh=mesh,
        )

    def _try_rollup_windowing(self, p: "L.PeriodicSeriesWithWindowing"):
        """Rollup substitution for a bare range function (no aggregate):
        ``quantile_over_time`` reads the per-period sketch blocks, the
        moment functions and counter rate/increase read the [S, P]
        moments. Ineligible shapes return None and the caller builds the
        raw mapper+fanout plan exactly as before (bit-identical)."""
        from ..query.exec.plans import (
            RollupServeExec,
            SelectRawPartitionsExec,
        )
        from ..downsample.rollup import ROLLUP_FUNCS

        rollups = self.params.rollups
        if rollups is None or p.raw.column is not None:
            return None
        if (p.function not in ROLLUP_FUNCS or p.at_ms is not None
                or p.offset_ms):
            return None
        if p.function_args and not (
            p.function == "quantile_over_time"
            and len(p.function_args) == 1
            and isinstance(p.function_args[0], (int, float))
        ):
            return None
        key = rollups.plan(
            self.dataset, p.raw.filters, p.function, p.step_ms or 1,
            p.window_ms, p.start_ms, p.end_ms, p.offset_ms,
        )
        if key is None:
            return None

        def fallback():
            mapper = PeriodicSamplesMapper(
                p.start_ms, p.end_ms, p.step_ms, p.function, p.window_ms,
                offset_ms=p.offset_ms, at_ms=p.at_ms, args=p.function_args,
            )
            raw = p.raw
            return self._fanout(
                lambda s: SelectRawPartitionsExec(
                    s, raw.filters, raw.start_ms, raw.end_ms, raw.column
                ),
                [mapper],
                filters=raw.filters,
                logical=p,
            )

        return RollupServeExec(
            rollups, key, p.raw.filters, p.function, p.function_args,
            p.start_ms, p.end_ms, p.step_ms or 1, p.window_ms,
            fallback=fallback,
        )

    # superblock staging-range alignment under cross-query batching: the
    # coalescing key is the superblock itself, but two dashboard panels
    # differing only in window (rate[3m] vs rate[5m]), offset, or the
    # live-edge "end=now" instant derive different raw selector ranges and
    # would stage two byte-near-identical superblocks that can never share
    # a batched launch. Aligning the staged range (start floored, end
    # ceiled) makes them resolve to ONE cached superblock — staging a
    # superset is always safe because result windows derive from the query
    # params (out_t/window), never from block bounds; the wider selection
    # can at most add series whose samples miss every window (NaN rows =
    # absence, same as the reference tree over the same range).
    FUSED_ALIGN_MS = 300_000

    def _fused_raw_range(self, start_ms: int, end_ms: int) -> tuple[int, int]:
        """Quantize a fused exec's staging range when (and only when)
        cross-query batching is enabled — with batching off, plans are
        byte-identical to the pre-scheduler planner."""
        if self.params.batch_window_ms <= 0:
            return start_ms, end_ms
        a = self.FUSED_ALIGN_MS
        return start_ms - start_ms % a, end_ms + (-end_ms) % a

    def _materialize_aggregate_tree(self, p: L.Aggregate) -> ExecPlan:
        inner = self._materialize(p.inner)
        simple = p.op in _PARTIAL_COMPONENTS
        if simple and isinstance(inner, DistConcatExec) and not inner.transformers:
            # push map phase onto each shard subtree (reference agg pushdown
            # SingleClusterPlanner.scala:1137)
            pushed_partial = self._push_peer_aggregate(inner.child_plans, p)
            for child in inner.child_plans:
                if id(child) not in pushed_partial:
                    child.transformers.append(
                        AggregateMapReduce(p.op, p.by, p.without)
                    )
            return ReduceAggregateExec(inner.child_plans, p.op, p.by, p.without)
        if simple and not isinstance(inner, DistConcatExec):
            inner.transformers.append(AggregateMapReduce(p.op, p.by, p.without))
            return ReduceAggregateExec([inner], p.op, p.by, p.without)
        if (p.op in ("topk", "bottomk") and p.params
                and isinstance(inner, DistConcatExec) and not inner.transformers):
            # per-shard candidate pre-reduction (exact; see
            # TopkCandidateFilter): root gathers O(shards*k), not O(series).
            # Peer leaves ship the topk ITSELF (the peer's per-step winners
            # are the exact candidate set) so O(k) rows cross the wire, not
            # the peer's full matching series.
            from ..query.exec.transformers import TopkCandidateFilter

            k = max(int(p.params[0]), 1)
            for child in inner.child_plans:
                if getattr(child, "peer_logical", None) is not None:
                    self._rewrite_peer_leaf(child, p)
                else:
                    child.transformers.append(
                        TopkCandidateFilter(k, p.op == "bottomk", p.by, p.without)
                    )
        elif (p.op == "count_values" and p.params
              and isinstance(inner, DistConcatExec) and not inner.transformers):
            # per-shard counting (exact: disjoint series sum at the root;
            # see CountValuesMapReduce) — O(groups x values) crosses the
            # gather, not O(series). Peers ship count_values itself: their
            # partial count rows merge by sum like local partials.
            from ..query.exec.plans import CountValuesMergeExec
            from ..query.exec.transformers import CountValuesMapReduce

            for child in inner.child_plans:
                if getattr(child, "peer_logical", None) is not None:
                    self._rewrite_peer_leaf(child, p)
                else:
                    child.transformers.append(
                        CountValuesMapReduce(str(p.params[0]), p.by, p.without)
                    )
            return CountValuesMergeExec(inner.child_plans)
        elif (p.op == "quantile" and p.params
              and isinstance(inner, DistConcatExec) and not inner.transformers):
            # distributed quantile over plan-transport peers: everyone ships
            # per-group mergeable sketch counts, O(groups x B) on the wire
            # instead of O(series) raw rows (reference QuantileRowAggregator
            # t-digest exchange). Local-only quantile stays on the exact
            # path below; HTTP peers can't ship sketches (PromQL transport).
            peers = [c for c in inner.child_plans
                     if getattr(c, "peer_logical", None) is not None]
            if peers and all(hasattr(c, "push_aggregate") for c in peers):
                from ..query.exec.plans import QuantileMergeExec, SketchMapReduce

                for child in inner.child_plans:
                    if getattr(child, "peer_logical", None) is not None:
                        child.push_aggregate(L.PartialAggregate(
                            "quantile", child.peer_logical, (), p.by, p.without
                        ))
                    else:
                        child.transformers.append(
                            SketchMapReduce(p.by, p.without)
                        )
                return QuantileMergeExec(
                    inner.child_plans, float(p.params[0]), p.by, p.without
                )
        return AggregatePresentExec([inner], p.op, p.params, p.by, p.without)

    def _rewrite_peer_leaf(self, child, p: "L.Aggregate") -> None:
        """Ship the whole aggregate to a peer leaf instead of its raw
        series (plan-level for gRPC, unparsed PromQL for HTTP)."""
        from ..query.unparse import to_promql

        wrapped = L.Aggregate(p.op, child.peer_logical, p.params, p.by, p.without)
        if hasattr(child, "push_aggregate"):
            child.push_aggregate(wrapped)
        else:
            child.promql = to_promql(wrapped)

    # aggregation ops where re-aggregating per-peer FINAL rows with the
    # same op is exact: sum of sums, min of mins, max of maxes, group of
    # groups — the only pushdown expressible over the PromQL (HTTP)
    # transport. count/avg/stddev over HTTP peers still return raw series.
    _PEER_PUSH_OPS = {"sum", "min", "max", "group"}

    def _push_peer_aggregate(self, children, p: "L.Aggregate") -> set:
        """Rewrite peer remote leaves to ship the aggregate instead of
        every raw series — the cross-host analog of the per-shard map-phase
        pushdown: O(groups) rows over the wire, not O(series).

        Plan-transport (gRPC) peers receive L.PartialAggregate and return
        mergeable __comp__ components, so count/avg/stddev/stdvar push too
        (reference RowAggregator.scala:28,114 AggregateItem exchange);
        PromQL (HTTP) peers can only express the exact-re-aggregation ops
        (_PEER_PUSH_OPS) and ship final rows. Returns the id()s of children
        now returning PARTIAL components (they must not get the local
        AggregateMapReduce transformer — their grids are already partials).
        """
        pushed_partial: set = set()
        if p.params:
            return pushed_partial
        for child in children:
            if getattr(child, "peer_logical", None) is None:
                continue
            if hasattr(child, "push_aggregate") and p.op in _PARTIAL_COMPONENTS:
                child.push_aggregate(L.PartialAggregate(
                    p.op, child.peer_logical, p.params, p.by, p.without
                ))
                pushed_partial.add(id(child))
            elif p.op in self._PEER_PUSH_OPS:
                self._rewrite_peer_leaf(child, p)
        return pushed_partial

    def _try_join_pushdown(self, p: "L.BinaryJoin"):
        """Per-shard binary-join pushdown (reference materializeBinaryJoin
        pushdown, SingleClusterPlanner.scala:640-760, gated there by
        target-schema colocation). The join runs inside each shard and the
        results concatenate — no cross-shard gather of full series.

        Sound ONLY when every pair of series that can match is guaranteed to
        live on the same shard. With our routing
        (shard = f(shard-key hash | partkey-hash low spread bits)) that means:

        - spread == 0: placement is a pure function of the shard-key columns;
        - the matching keys preserve every shard-key column: ``on`` ⊇ shard
          keys, or default matching with ignoring ∩ shard keys = ∅ AND the
          metric column NOT a shard key (default matching ignores __name__,
          so a metric-keyed placement would let cross-metric matches cross
          shards — the reference's target-schema gate is exactly this);
        - plain selector sides, one-to-one or set-op cardinality.

        Beneficiary: datasets sharded purely by (_ws_, _ns_) — the
        target-schema analog — where ``foo_bucket / foo_count`` and error
        ratios join shard-locally."""
        if self.params.spread != 0:
            return None
        if self.params.peer_endpoints or self.params.replica_router is not None:
            return None  # matching pairs may span hosts
        if p.op not in ("and", "or", "unless") and p.cardinality not in (None, "one-to-one"):
            return None
        if not isinstance(p.lhs, (L.PeriodicSeries, L.PeriodicSeriesWithWindowing)):
            return None
        if not isinstance(p.rhs, (L.PeriodicSeries, L.PeriodicSeriesWithWindowing)):
            return None
        options = self._options()
        skc = set(options.shard_key_columns)
        if p.on is not None:
            # explicit on-list (including the empty `on()`) must cover every
            # shard-key column or pairs can cross shards
            if not skc <= set(p.on):
                return None
        else:
            if options.metric_column in skc:
                return None  # default matching ignores the metric name
            if p.ignoring and set(p.ignoring) & skc:
                return None
        shards = sorted(set(self.shards_for(p.lhs.raw.filters))
                        | set(self.shards_for(p.rhs.raw.filters)))
        if len(shards) <= 1:
            return None  # single shard: the root join is already local
        per_shard = []
        for s in shards:
            sub = SingleClusterPlanner(self.memstore, self.dataset, [s], self.params)
            lhs = sub._materialize(p.lhs)
            rhs = sub._materialize(p.rhs)
            if p.op in ("and", "or", "unless"):
                per_shard.append(SetOperatorExec(lhs, rhs, p.op, p.on, p.ignoring))
            else:
                per_shard.append(BinaryJoinExec(
                    lhs, rhs, p.op, p.cardinality, p.on, p.ignoring,
                    p.include, p.return_bool,
                ))
        return DistConcatExec(per_shard)

    def _try_time_shard(self, p: "L.PeriodicSeriesWithWindowing"):
        """Long non-aggregated range queries shard the TIME axis over the
        mesh with a ring halo exchange (parallel/timeshard.py)."""
        mesh = self.params.mesh
        if mesh is None or self.params.peer_endpoints or self.params.replica_router is not None:
            return None
        from ..ops.kernels import SORTED_FUNCS
        from ..parallel.exec import TIME_SHARD_MIN_STEPS, TimeShardRangeExec

        num_steps = int((p.end_ms - p.start_ms) // (p.step_ms or 1)) + 1
        if (
            num_steps < TIME_SHARD_MIN_STEPS
            or p.offset_ms
            or p.at_ms is not None
            or p.function_args
            or p.function in SORTED_FUNCS
            or p.raw.column is not None
        ):
            return None
        # histograms stay on the standard path (plan-time schema peek)
        shards = self.shards_for(p.raw.filters)
        for s in shards:
            pids = self.memstore.shard(self.dataset, s).lookup_partitions(
                p.raw.filters, p.raw.start_ms, p.raw.end_ms, limit=1
            )
            if len(pids):
                part = self.memstore.shard(self.dataset, s).partition(int(pids[0]))
                if part.schema.has_histogram:
                    return None
                break
        is_counter = p.function in ("rate", "increase", "irate")
        return TimeShardRangeExec(
            mesh, shards, p.raw.filters, p.raw.start_ms, p.raw.end_ms,
            p.function, p.start_ms, p.end_ms, p.step_ms, p.window_ms,
            is_counter=is_counter,
        )

    def _try_mesh_aggregate(self, p: L.Aggregate):
        """Mesh path: aggregate-of-range-function compiles to one psum
        program when a device mesh is configured."""
        mesh = self.params.mesh
        if mesh is None or self.params.peer_endpoints or self.params.replica_router is not None:
            # peer scatter runs through the standard leaf fan-out; the mesh
            # single-psum program would aggregate local shards only
            return None
        from ..parallel.exec import MESH_OPS, MeshAggregateExec

        inner = p.inner
        if p.op not in MESH_OPS and p.op != "quantile":
            return None
        if not isinstance(inner, L.PeriodicSeriesWithWindowing):
            return None
        from ..ops.kernels import SORTED_FUNCS

        if (
            inner.offset_ms
            or inner.at_ms is not None
            or inner.function in SORTED_FUNCS
            or inner.function_args
        ):
            return None
        shards = self.shards_for(inner.raw.filters)
        # counter-ness resolved at execution from schemas; assume cumulative
        # counter when the function is the counter family
        is_counter = inner.function in ("rate", "increase", "irate")
        raw_start, raw_end = self._fused_raw_range(
            inner.raw.start_ms, inner.raw.end_ms
        )
        common = dict(
            mesh=mesh, shard_nums=shards, filters=inner.raw.filters,
            raw_start_ms=raw_start, raw_end_ms=raw_end,
            by=p.by, without=p.without, function=inner.function,
            start_ms=inner.start_ms, end_ms=inner.end_ms,
            step_ms=inner.step_ms, window_ms=inner.window_ms,
            is_counter=is_counter,
            # sharded-fused delegation (parallel/exec.py): the mesh engines
            # run the fused superblock kernels under shard_map when the
            # op/function allows, falling back to their legacy per-shard
            # stack (reason mesh_unsupported) otherwise. The delegate's own
            # runtime fallback is the reference tree.
            fused=self.params.fused_aggregate,
            fused_fallback=lambda: self._materialize_aggregate_tree(p),
        )
        axes = set(getattr(mesh, "axis_names", ()))
        if axes == {"shard", "time"}:
            from ..parallel.exec import Mesh2DAggregateExec

            if p.op in ("sum", "count", "avg"):
                return Mesh2DAggregateExec(op=p.op, **common)
            return None
        if "shard" not in axes:
            # e.g. a time-only mesh: the 1D aggregation program psums over
            # 'shard', which doesn't exist there — use the host path
            return None
        if p.op == "quantile":
            from ..parallel.exec import MeshQuantileExec

            return MeshQuantileExec(float(p.params[0]), **common)
        return MeshAggregateExec(op=p.op, **common)


def _plan_times(p: L.LogicalPlan):
    for attr in ("start_ms",):
        if hasattr(p, "start_ms") and hasattr(p, "step_ms") and hasattr(p, "end_ms"):
            return p.start_ms, p.end_ms, p.step_ms or 1
    for f in getattr(p, "__dataclass_fields__", {}):
        v = getattr(p, f)
        if isinstance(v, L.LogicalPlan):
            t = _plan_times(v)
            if t is not None:
                return t
    return None


class QueryEngine:
    """Top-level facade: PromQL string -> executed result (the in-process
    analog of QueryActor -> planner.materialize -> execute)."""

    def __init__(self, memstore, dataset: str, params: PlannerParams | None = None,
                 shard_nums: Sequence[int] | None = None):
        from .scheduler import SingleFlight

        self.memstore = memstore
        self.dataset = dataset
        self.planner = SingleClusterPlanner(memstore, dataset,
                                            shard_nums=shard_nums, params=params)
        self._single_flight = SingleFlight()
        p = self.planner.params
        if p.dispatch_scheduler is None and p.batch_window_ms > 0:
            from ..query.scheduler import DispatchScheduler

            p.dispatch_scheduler = DispatchScheduler(
                p.batch_window_ms, p.batch_max
            )
        if p.dispatch_scheduler is not None:
            # executable pre-warm (query/costmodel plane): the scheduler's
            # background tick traces+compiles about-to-be-hot recurrence
            # keys through this engine, off the serving path
            reg = getattr(p.dispatch_scheduler, "register_prewarmer", None)
            if reg is not None:
                reg(self._prewarm_key)

    def context(self, allow_partial_results: bool | None = None) -> QueryContext:
        params = self.planner.params
        ctx = QueryContext(self.memstore, self.dataset)
        ctx.max_series = params.max_series
        ctx.deadline_s = params.deadline_s
        ctx.allow_partial_results = (
            params.allow_partial_results if allow_partial_results is None
            else bool(allow_partial_results)
        )
        ctx.retry_policy = params.retry_policy
        ctx.breakers = params.breakers
        ctx.dispatcher = params.dispatcher
        ctx.dispatch_scheduler = params.dispatch_scheduler
        return ctx

    def _start_trace(self, ctx, promql: str, trace_id: str | None = None,
                     parent_span_id: str | None = None):
        """Open the query's root span. ``trace_id``/``parent_span_id`` come
        from an upstream origin (gRPC metadata / HTTP headers) so this
        process's spans — and its slow-query entries — join that trace."""
        import time as _time

        from ..metrics import Span, current_span, new_trace_id

        root = Span("query", _time.perf_counter_ns())
        # no upstream trace: the spans the caller has open around the engine
        # (``http:<route>``, ``engine:query_range``) and the query's tree
        # share one id, on the profiler trace too
        cur = current_span()
        root.trace_id = trace_id or (
            cur.trace_id if cur is not None else new_trace_id())
        if cur is not None:
            # a caller's span sees the query's whole tree beneath it;
            # parent_id stays the UPSTREAM linkage (None = this process is
            # the query's origin, which is what publishes its cost record)
            cur.children.append(root)
        root.parent_id = parent_span_id
        root.tags["promql"] = promql
        root.tags["dataset"] = self.dataset
        ctx.trace_root = root
        return root

    def _observe_slow(self, promql: str, elapsed_s: float, res,
                      query_id: str | None = None) -> None:
        """Record queries over the slow-query threshold with their rendered
        trace (the observability substrate for "why was THIS query slow").
        ``query_id`` links the entry to the same execution's query-log
        record (``/api/v1/query_profile?id=``) so the two debug surfaces
        join instead of being disjoint rings."""
        thr = self.planner.params.slow_query_threshold_s
        if thr is None or elapsed_s < thr:
            return
        from ..metrics import SLOW_QUERY_LOG

        SLOW_QUERY_LOG.record(
            promql, elapsed_s, dataset=self.dataset, trace=res.trace,
            stats=res.stats.as_dict() if res.stats is not None else None,
            query_id=query_id,
        )

    def _observe_querylog(self, promql: str, ctx, rec, elapsed_s: float,
                          start_s: float, end_s: float, step_ms: int,
                          res=None, error=None, tenant=None):
        """Publish one exemplar-level cost record for this execution into
        the query observatory (obs/querylog.py): phases, path, stats,
        result size, status. Returns the record (None for remote-child
        legs — the ORIGIN records the whole query exactly once, mirroring
        tenant metering) and attaches it to the result so the serving edge
        can fold in its transfer/render phases."""
        root = getattr(ctx, "trace_root", None)
        if rec is None or root is None or root.parent_id is not None:
            return None
        from ..obs.querylog import QUERY_LOG
        from ..query.scheduler import AdmissionRejected

        ws, ns = (tenant or getattr(ctx, "_tenant", None)
                  or (root.tags.get("ws", "unknown"),
                      root.tags.get("ns", "unknown")))
        status, err = "ok", None
        if error is not None:
            status = ("shed" if isinstance(error, AdmissionRejected)
                      else "error")
            err = f"{type(error).__name__}: {error}"
        result_series = result_samples = 0
        if res is not None:
            for g in res.grids:
                result_series += g.n_series
                result_samples += g.n_series * g.num_steps
            if res.raw is not None:
                result_series += len(res.raw)
                result_samples += sum(len(t) for _, t, _ in res.raw)
        # cost-model plane: what admission priced the query at vs. the
        # device time it actually consumed; the completed record feeds the
        # predictor's online update (EWMA per fingerprint + family)
        predicted = getattr(ctx, "predicted_cost_s", None)
        realized = ctx.stats.kernel_ns / 1e9 if ctx.stats is not None else 0.0
        record = QUERY_LOG.publish(
            query_id=root.trace_id, dataset=self.dataset, promql=promql,
            ws=ws, ns=ns, step_ms=int(step_ms),
            span_ms=max(int((end_s - start_s) * 1000), 0),
            start_s=start_s, end_s=end_s, phases=rec, elapsed_s=elapsed_s,
            stats=ctx.stats, path_info=getattr(ctx, "obs", None),
            result_series=result_series, result_samples=result_samples,
            status=status, error=err,
            predicted_cost_s=predicted,
            realized_cost_s=realized if realized > 0 else None,
        )
        if status == "ok":
            from ..query.costmodel import COST_MODEL

            COST_MODEL.observe(record)
        if res is not None:
            res.query_log = record
        return record

    def _finish(self, res, ctx):
        """Attach per-query stats + partial-result warnings collected on the
        context during scatter-gather (query/faults.py), and close + attach
        the trace root span."""
        res.stats = ctx.stats  # per-query scan/latency stats ride in responses
        root = getattr(ctx, "trace_root", None)
        if root is not None:
            import time as _time

            if not root.end_ns:
                root.end_ns = _time.perf_counter_ns()
            root.stats = ctx.stats.as_dict()
            res.trace = root
        if ctx.warnings:
            from ..metrics import record_partial_result

            # order-preserving dedup: a remote child's warnings can be seen
            # both in its own result and hoisted onto the context
            deduped: list = []
            for w in ctx.warnings:
                if w not in deduped:
                    deduped.append(w)
            res.warnings = deduped
            res.partial = True
            record_partial_result(self.dataset)
        return res

    def query_range(self, promql: str, start_s: float, end_s: float, step_s: float,
                    allow_partial_results: bool | None = None,
                    trace_id: str | None = None,
                    parent_span_id: str | None = None):
        """PromQL range query. Concurrent identical queries coalesce into
        ONE plan+stage+kernel execution (reference: the shared
        QueryScheduler pool, QueryScheduler.scala:29-73, plus single-flight
        result sharing for the dashboard fan-out pattern). Serving metrics
        count every CALLER (followers included), not executions — the
        coalescing factor must not deflate served QPS or the latency
        histogram."""
        from ..metrics import REGISTRY, span

        # each caller's wall inside the engine, a follower's wait for a
        # shared execution included: filodb_query_latency_seconds
        with span("engine:query_range") as sp:
            # resolve the tri-state BEFORE keying: "absent" and "explicitly
            # the engine default" are the same query and must coalesce
            allow_partial = (
                self.planner.params.allow_partial_results
                if allow_partial_results is None
                else bool(allow_partial_results)
            )
            # trace linkage is NOT part of the coalescing key: followers
            # share the leader's execution and therefore its trace tree
            if self.planner.params.coalesce_identical:
                res = self._single_flight.run(
                    (self.dataset, promql, float(start_s), float(end_s),
                     float(step_s), allow_partial),
                    lambda: self._query_range_uncoalesced(
                        promql, start_s, end_s, step_s, allow_partial,
                        trace_id=trace_id, parent_span_id=parent_span_id,
                    ),
                    timeout_s=self.planner.params.deadline_s,
                )
            else:
                res = self._query_range_uncoalesced(
                    promql, start_s, end_s, step_s, allow_partial,
                    trace_id=trace_id, parent_span_id=parent_span_id)
            REGISTRY.counter("filodb_queries", dataset=self.dataset).inc()
            # trace-id exemplar: the OpenMetrics exposition attaches it to
            # the latency bucket this query landed in, so a spiking bucket
            # links straight to its trace / slow-query-log entry
            tid = getattr(res.trace, "trace_id", None) \
                if res.trace is not None else None
            if tid is None and isinstance(res.trace, dict):
                tid = res.trace.get("trace_id")
        REGISTRY.histogram(
            "filodb_query_latency_seconds", dataset=self.dataset
        ).observe(sp.seconds, exemplar={"trace_id": tid} if tid else None)
        return res

    def _meter_tenant(self, plan, ctx, elapsed_s: float) -> None:
        """Attribute the finished query's resources to the tenant resolved
        from its selector filters (metering.py — the admission-control
        accounting), and tag the trace root so ?trace=true shows it.

        Child executions (a parent span rides the request: remote-exec from
        another node, or a peer's scatter leg) only TAG — the origin meters
        the whole query once, from its merged query-wide stats; metering
        here too would double-count every remote child's resources."""
        from ..metering import record_tenant_query, tenant_of_plan

        ws, ns = getattr(ctx, "_tenant", None) or tenant_of_plan(plan)
        root = getattr(ctx, "trace_root", None)
        if root is not None:
            root.tags["ws"] = ws
            root.tags["ns"] = ns
            if root.parent_id is not None:
                return ws, ns
        record_tenant_query(
            ws, ns, elapsed_s, ctx.stats.kernel_ns / 1e9,
            ctx.stats.bytes_staged,
        )
        return ws, ns

    def _query_range_uncoalesced(self, promql: str, start_s: float,
                                 end_s: float, step_s: float,
                                 allow_partial_results: bool | None = None,
                                 trace_id: str | None = None,
                                 parent_span_id: str | None = None):
        import time as _time

        from ..obs.querylog import PhaseRecorder

        rec = PhaseRecorder()
        t0 = _time.perf_counter()
        with rec.phase("parse_plan"):
            plan = query_range_to_logical_plan(
                promql, start_s, end_s, step_s,
                self.planner.params.lookback_ms,
            )
            if self.planner.params.agg_rules is not None:
                from .lpopt import optimize_with_preagg

                plan = optimize_with_preagg(plan,
                                            self.planner.params.agg_rules)
            exec_plan = self.planner.materialize(plan)
        ctx = self.context(allow_partial_results)
        ctx.phases = rec
        self._start_trace(ctx, promql, trace_id, parent_span_id)
        step_ms = int(step_s * 1000)
        try:
            with rec.phase("admission"):
                adm = self._admit(
                    plan, ctx, promql=promql, step_ms=step_ms,
                    span_ms=max(int((end_s - start_s) * 1000), 0),
                )
            with adm:
                res = self._run(exec_plan, ctx)
        except Exception as e:
            # shed / errored queries are cost records too (status =
            # shed|error): the observatory must see what the tenant PAID
            # for, not only what succeeded
            self._observe_querylog(
                promql, ctx, rec, _time.perf_counter() - t0, start_s,
                end_s, step_ms, error=e,
            )
            raise
        self._finish(res, ctx)
        if res.result_type == "matrix" or res.grids:
            res.result_type = "matrix"
        elapsed_s = _time.perf_counter() - t0
        tenant = self._meter_tenant(plan, ctx, elapsed_s)
        record = self._observe_querylog(promql, ctx, rec, elapsed_s,
                                        start_s, end_s, step_ms, res=res,
                                        tenant=tenant)
        self._observe_slow(promql, elapsed_s, res,
                           query_id=record["id"] if record else None)
        return res

    def _admit(self, plan, ctx, promql: str | None = None,
               step_ms: int = 0, span_ms: int = 0):
        """Admission-control gate (query/scheduler.AdmissionController):
        resolve the tenant from the plan's selector filters, PRICE the
        query through the cost model (query/costmodel.py — fingerprint
        EWMA, family prior for cold fingerprints) and claim its
        concurrency/rate slots for the duration of execution, draining the
        tenant's device-second bucket by the prediction. Raises
        AdmissionRejected (HTTP 429 + Retry-After = the bucket's predicted
        drain time) when the tenant is over quota or the global
        queue-depth bound is hit; a no-op context when no controller is
        configured. The prediction + resolved tenant are stashed on the
        context: _observe_querylog stamps ``predicted_cost_s`` onto the
        cost record, and _meter_tenant doesn't walk the plan's leaves a
        second time per query. Coalesced identical-query followers never
        reach this point (they share the leader's execution AND its
        admission slot — sharing an answer costs the tenant nothing)."""
        params = self.planner.params
        cost_s = None
        if promql is not None:
            from ..obs.querylog import promql_fingerprint
            from ..query.costmodel import COST_MODEL, family_of

            steps = (int(span_ms // step_ms) + 1) if step_ms > 0 else 1
            fp = promql_fingerprint(self.dataset, promql, step_ms, span_ms)
            cost_s, source = COST_MODEL.predict(
                fp, steps=steps, family=family_of(promql)
            )
            ctx.predicted_cost_s = cost_s
            ctx.cost_fingerprint = fp
            ctx.cost_source = source
        if params.admission is None:
            import contextlib

            return contextlib.nullcontext()
        from ..metering import tenant_of_plan

        ws, ns = tenant_of_plan(plan)
        ctx._tenant = (ws, ns)
        return params.admission.admit(ws, ns, cost_s=cost_s)

    def _prewarm_key(self, desc: dict) -> None:
        """Background trace+compile of a predicted-hot recurrence key
        (DispatchScheduler.prewarm_tick): run the ring descriptor's query
        end-to-end OFF the serving path — no admission (the server's own
        standing obligation, like maintainer refreshes), no querylog or
        recurrence-ring feedback (``standing_refresh`` flag), no batch
        window — so its executables and superblock are warm before the
        first real poll pays the compile in its p99.

        The execution has a PhaseRecorder of its own, booked to
        ``filodb_prewarm_phase_seconds{phase}`` and nowhere else: what a
        pre-warm stages behind live traffic must not read as the traffic's
        ``stage`` (``filodb_query_phase_seconds``,
        ``filodb_stage_part_seconds``)."""
        import time as _time

        from ..metrics import REGISTRY
        from ..obs.querylog import PhaseRecorder

        promql = desc.get("promql")
        step_ms = int(desc.get("step_ms") or 0)
        span_ms = int(desc.get("span_ms") or 0)
        if not promql or step_ms <= 0 or span_ms <= 0:
            return
        end_s = _time.time() - float(desc.get("end_lag_ms") or 0) / 1e3
        start_s = end_s - span_ms / 1e3
        plan = query_range_to_logical_plan(
            promql, start_s, end_s, step_ms / 1e3,
            self.planner.params.lookback_ms,
        )
        if self.planner.params.agg_rules is not None:
            from .lpopt import optimize_with_preagg

            plan = optimize_with_preagg(plan, self.planner.params.agg_rules)
        exec_plan = self.planner.materialize(plan)
        ctx = self.context()
        ctx.standing_refresh = True  # keep prewarm out of the ring
        # solo-path compile is the one a dashboard's first poll would pay:
        # don't route the warmup through the batch window it exists to dodge
        ctx.dispatch_scheduler = None
        ctx.phases = rec = PhaseRecorder()
        try:
            exec_plan.execute(ctx)
        finally:
            for phase, seconds in rec.snapshot().items():
                REGISTRY.histogram(
                    "filodb_prewarm_phase_seconds", phase=phase
                ).observe(seconds)

    def _run(self, exec_plan, ctx):
        """Execute on the shared bounded scheduler when configured, else
        inline on the caller's thread."""
        sched = self.planner.params.scheduler
        if sched is None:
            from ..metrics import activate

            # the plan's spans hang under the query's root, not under
            # whatever span the caller has open around the engine
            with activate(ctx.trace_root):
                return exec_plan.execute(ctx)
        return sched.run(lambda: exec_plan.execute(ctx),
                         deadline_s=ctx.deadline_s,
                         phases=getattr(ctx, "phases", None))

    def execute_plan(self, plan, deadline_s: float = 0.0, max_series: int = 0,
                     allow_partial_results: bool | None = None,
                     trace_id: str | None = None,
                     parent_span_id: str | None = None):
        """Execute an already-built LogicalPlan — THE entry for plan-level
        remote transports (gRPC ExecutePlan, Flight plan tickets), so every
        transport shares the same pre-agg rewrite, limits, and scheduler
        path as PromQL queries."""
        import time as _time

        from ..obs.querylog import PhaseRecorder

        rec = PhaseRecorder()
        t0 = _time.perf_counter()
        with rec.phase("parse_plan"):
            if self.planner.params.agg_rules is not None:
                from .lpopt import optimize_with_preagg

                plan = optimize_with_preagg(plan,
                                            self.planner.params.agg_rules)
            exec_plan = self.planner.materialize(plan)
        ctx = self.context(allow_partial_results)
        ctx.phases = rec
        if deadline_s:
            ctx.deadline_s = min(ctx.deadline_s, deadline_s)
        if max_series:
            ctx.max_series = min(ctx.max_series, max_series)
        try:
            from ..query.unparse import to_promql

            qname = to_promql(plan)
        except Exception:  # noqa: BLE001 — metadata plans have no PromQL form
            qname = type(plan).__name__
        self._start_trace(ctx, qname, trace_id, parent_span_id)
        times = _plan_times(plan)
        g_start, g_end, g_step = (
            (times[0] / 1000.0, times[1] / 1000.0, times[2])
            if times else (0.0, 0.0, 0)
        )
        try:
            with rec.phase("admission"):
                adm = self._admit(
                    plan, ctx, promql=qname, step_ms=g_step,
                    span_ms=max(int((g_end - g_start) * 1000), 0),
                )
            with adm:
                res = self._run(exec_plan, ctx)
        except Exception as e:
            self._observe_querylog(qname, ctx, rec,
                                   _time.perf_counter() - t0, g_start,
                                   g_end, g_step, error=e)
            raise
        self._finish(res, ctx)
        elapsed_s = _time.perf_counter() - t0
        tenant = self._meter_tenant(plan, ctx, elapsed_s)
        record = self._observe_querylog(qname, ctx, rec, elapsed_s,
                                        g_start, g_end, g_step, res=res,
                                        tenant=tenant)
        self._observe_slow(qname, elapsed_s, res,
                           query_id=record["id"] if record else None)
        return res

    def label_values(self, filters, label: str, start_ms: int, end_ms: int, limit=None):
        """Metadata through the planner so multi-host peers scatter too."""
        plan = L.LabelValues(label, tuple(filters), start_ms, end_ms)
        ep = self.planner.materialize(plan)
        if limit:
            ep.limit = int(limit)
        return ep.execute(self.context()).metadata

    def label_names(self, filters, start_ms: int, end_ms: int):
        ep = self.planner.materialize(L.LabelNames(tuple(filters), start_ms, end_ms))
        return ep.execute(self.context()).metadata

    def series(self, filters, start_ms: int, end_ms: int, limit=None):
        ep = self.planner.materialize(L.SeriesKeysByFilters(tuple(filters), start_ms, end_ms))
        if limit:
            ep.limit = int(limit)
        return ep.execute(self.context()).metadata

    def ts_cardinalities(self, prefix, depth: int | None = None):
        plan = L.TsCardinalities(tuple(prefix), depth if depth is not None else len(tuple(prefix)) + 1)
        return self.planner.materialize(plan).execute(self.context()).metadata

    def query_instant(self, promql: str, time_s: float,
                      allow_partial_results: bool | None = None,
                      trace_id: str | None = None,
                      parent_span_id: str | None = None):
        import time as _time

        from ..obs.querylog import PhaseRecorder

        rec = PhaseRecorder()
        t0 = _time.perf_counter()
        with rec.phase("parse_plan"):
            plan = query_to_logical_plan(promql, time_s,
                                         self.planner.params.lookback_ms)
            exec_plan = self.planner.materialize(plan)
        ctx = self.context(allow_partial_results)
        ctx.phases = rec
        self._start_trace(ctx, promql, trace_id, parent_span_id)
        try:
            with rec.phase("admission"):
                adm = self._admit(plan, ctx, promql=promql)
            with adm:
                res = self._run(exec_plan, ctx)
        except Exception as e:
            self._observe_querylog(promql, ctx, rec,
                                   _time.perf_counter() - t0, time_s,
                                   time_s, 0, error=e)
            raise
        self._finish(res, ctx)
        if res.result_type == "matrix":
            res.result_type = "vector"
        elapsed_s = _time.perf_counter() - t0
        tenant = self._meter_tenant(plan, ctx, elapsed_s)
        record = self._observe_querylog(promql, ctx, rec, elapsed_s,
                                        time_s, time_s, 0, res=res,
                                        tenant=tenant)
        self._observe_slow(promql, elapsed_s, res,
                           query_id=record["id"] if record else None)
        return res
