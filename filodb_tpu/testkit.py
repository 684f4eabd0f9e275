"""Synthetic data generators shared by tests and benchmarks (reference
core/src/test/scala/filodb.core/TestData.scala:27,239 MachineMetricsData —
synthetic machine-metric streams used across every layer's specs), plus the
deterministic fault-injection harness (:class:`FaultInjector`) the chaos
tests drive the query/faults.py retry/breaker/partial-results machinery
with, and the in-process cluster harness (:func:`grpc_cluster`) for
distributed parent -> remote-gRPC-child tests."""

from __future__ import annotations

import random
import threading
import time
from collections import Counter
from dataclasses import dataclass

import numpy as np

from .core.histograms import PROM_DEFAULT, BucketScheme
from .core.records import RecordBatch
from .core.schemas import GAUGE, METRIC_TAG, PROM_COUNTER, PROM_HISTOGRAM, Schema


def kernel_dispatch_total() -> int:
    """Total ``filodb_kernel_dispatch_seconds`` observations so far — the
    ONE definition of the O(1)-dispatch assertion's counter, shared by the
    fused/fused-mesh test suites and the MULTICHIP dryrun (a warm fused
    query must move this by exactly 1)."""
    from .metrics import REGISTRY

    total = 0
    with REGISTRY._lock:
        for (name, _lbls), m in REGISTRY._metrics.items():
            if name == "filodb_kernel_dispatch_seconds":
                total += m.total
    return total


def machine_metrics(
    n_series: int = 100,
    n_samples: int = 720,
    start_ms: int = 1_600_000_000_000,
    interval_ms: int = 10_000,
    metric: str = "heap_usage0",
    ws: str = "demo",
    ns: str = "App-2",
    seed: int = 42,
) -> RecordBatch:
    """Gauge batch: n_series hosts, regular interval, noisy values."""
    rng = np.random.default_rng(seed)
    ts = start_ms + np.arange(n_samples, dtype=np.int64) * interval_ms
    tags = [
        {METRIC_TAG: metric, "_ws_": ws, "_ns_": ns, "instance": f"host-{i}", "job": "machine"}
        for i in range(n_series)
    ]
    all_ts = np.tile(ts, n_series)
    vals = (50 + 20 * rng.standard_normal((n_series, n_samples))).ravel()
    all_tags = [t for t in tags for _ in range(n_samples)]
    return RecordBatch(GAUGE, all_ts, {"value": vals}, all_tags)


def counter_batch(
    n_series: int = 100,
    n_samples: int = 720,
    start_ms: int = 1_600_000_000_000,
    interval_ms: int = 10_000,
    metric: str = "http_requests_total",
    ws: str = "demo",
    ns: str = "App-2",
    seed: int = 7,
    resets: bool = False,
) -> RecordBatch:
    """Counter batch: monotonically increasing, optional resets-to-zero."""
    rng = np.random.default_rng(seed)
    ts = start_ms + np.arange(n_samples, dtype=np.int64) * interval_ms
    incr = rng.uniform(0, 10, size=(n_series, n_samples))
    vals = np.cumsum(incr, axis=1)
    if resets:
        for i in range(n_series):
            k = rng.integers(n_samples // 4, 3 * n_samples // 4)
            vals[i, k:] -= vals[i, k]  # counter restarts at 0
    tags = [
        {METRIC_TAG: metric, "_ws_": ws, "_ns_": ns, "instance": f"host-{i}", "job": "api"}
        for i in range(n_series)
    ]
    all_tags = [t for t in tags for _ in range(n_samples)]
    return RecordBatch(PROM_COUNTER, np.tile(ts, n_series), {"count": vals.ravel()}, all_tags)


def histogram_batch(
    n_series: int = 10,
    n_samples: int = 100,
    start_ms: int = 1_600_000_000_000,
    interval_ms: int = 10_000,
    metric: str = "http_request_latency",
    scheme: BucketScheme = PROM_DEFAULT,
    seed: int = 11,
    schema: Schema = PROM_HISTOGRAM,
) -> RecordBatch:
    """Native cumulative histogram batch: [N, B] bucket counts + sum/count."""
    rng = np.random.default_rng(seed)
    b = scheme.num_buckets
    ts = start_ms + np.arange(n_samples, dtype=np.int64) * interval_ms
    tags = [
        {METRIC_TAG: metric, "_ws_": "demo", "_ns_": "App-2", "instance": f"host-{i}"}
        for i in range(n_series)
    ]
    # per-interval observations land in buckets ~ lognormal; cumulative over time
    incr = rng.poisson(2.0, size=(n_series, n_samples, b)).astype(np.float64)
    incr[..., -1] = incr.sum(-1)  # +Inf bucket grows with everything
    hist = np.cumsum(np.cumsum(incr, axis=2), axis=1)
    count = hist[..., -1]
    total = np.cumsum(rng.uniform(0, 5, size=(n_series, n_samples)) * count / (count + 1), axis=1)
    all_tags = [t for t in tags for _ in range(n_samples)]
    return RecordBatch(
        schema,
        np.tile(ts, n_series),
        {
            "sum": total.ravel(),
            "count": count.ravel(),
            "h": hist.reshape(-1, b),
        },
        all_tags,
        bucket_les=scheme.bounds(),
    )


# ---------------------------------------------------------------------------
# deterministic fault injection (the chaos-test dispatcher)
# ---------------------------------------------------------------------------


class InjectedFault(RuntimeError):
    """A fault raised by :class:`FaultInjector`. Classified like a remote
    transport failure (query/faults.py): retried with backoff and counted
    against the endpoint's circuit breaker."""

    retryable = True
    endpoint_failure = True


@dataclass
class FaultRule:
    """One scheduled fault. ``target`` is substring-matched against the
    child's descriptor — ``ClassName(args_str()) endpoint`` — so rules can
    pin a shard (``"shard=2"``), an endpoint (``"grpc://peer:7777"``), or a
    plan class (``"SelectRawPartitionsExec"``).

    kinds:
      - ``error``   raise :class:`InjectedFault` on every matching dispatch
      - ``latency`` sleep ``latency_s`` then execute normally (stragglers)
      - ``flap``    alternate phases of ``period`` failing dispatches and
                    ``period`` healthy ones (breaker open/re-close drills)

    ``count`` bounds how many matching dispatches the rule applies to
    (None = forever); ``probability`` gates each application through the
    injector's seeded RNG (1.0 = always, fully deterministic)."""

    target: str
    kind: str = "error"
    count: int | None = None
    probability: float = 1.0
    latency_s: float = 0.0
    period: int = 2


class FaultInjector:
    """Seeded dispatcher wrapper injecting failures, latency spikes, and
    flapping per a schedule of :class:`FaultRule`s.

    Installed as ``QueryContext.dispatcher`` (via
    ``PlannerParams.dispatcher``), it sits BELOW the retry/breaker layer in
    query/faults.py, so injected faults exercise exactly the production
    fault-tolerance path. Same seed + same schedule + same query order =>
    same outcomes."""

    def __init__(self, rules, seed: int = 0, sleep=time.sleep):
        self.rules = list(rules)
        self.rng = random.Random(seed)
        self.sleep = sleep
        self.calls: Counter = Counter()      # per-target rule-match counts
        self.injected: Counter = Counter()   # per-target injected faults
        # schedule state is PER RULE, not per target: two rules sharing a
        # target must not corrupt each other's count/flap phases. Guarded by
        # a lock — concurrent remote children dispatch from pool threads,
        # and per-rule counting must stay exact for the schedule to hold.
        self._rule_calls = [0] * len(self.rules)
        self._lock = threading.Lock()

    @staticmethod
    def describe(child) -> str:
        endpoint = getattr(child, "endpoint", "") or ""
        return f"{type(child).__name__}({child.args_str()}) {endpoint}".strip()

    def dispatch(self, child, ctx):
        desc = self.describe(child)
        latency = 0.0
        fault: InjectedFault | None = None
        with self._lock:
            for ri, rule in enumerate(self.rules):
                if rule.target not in desc:
                    continue
                n = self._rule_calls[ri]
                self._rule_calls[ri] += 1
                self.calls[rule.target] += 1
                if rule.count is not None and n >= rule.count:
                    continue
                if rule.probability < 1.0 and self.rng.random() >= rule.probability:
                    continue
                if rule.kind == "latency":
                    latency += rule.latency_s
                    continue
                if rule.kind == "flap" and (n % (2 * rule.period)) >= rule.period:
                    continue  # healthy phase
                self.injected[rule.target] += 1
                fault = InjectedFault(
                    f"injected {rule.kind} for {rule.target!r} (dispatch {n})"
                )
                break
        # act OUTSIDE the lock: a latency spike must not serialize siblings
        if latency:
            self.sleep(latency)
        if fault is not None:
            raise fault
        return child.execute(ctx)


# ---------------------------------------------------------------------------
# in-process distributed cluster (parent -> remote gRPC child)
# ---------------------------------------------------------------------------


def grpc_cluster(batch=None, n_shards: int = 4, owned=(0, 1),
                 dataset: str = "prometheus", spread: int = 2,
                 deadline_s: float = 30.0, **params_kw):
    """Two-node in-process cluster over the gRPC plan transport: a parent
    engine owning ``owned`` shards that scatters every selector to a peer
    engine owning the rest (the distributed scatter-gather path, without
    FiloServer weight). ``batch`` (if given) is routed into BOTH memstores —
    shard ownership splits it across the nodes exactly like production
    ingest routing.

    Returns ``(parent_engine, peer_engine, stop)``; call ``stop()`` to shut
    the peer's gRPC server down. Extra kwargs land on both engines'
    PlannerParams (e.g. slow_query_threshold_s, allow_partial_results)."""
    from .api.grpc_exec import serve_grpc
    from .coordinator.planner import PlannerParams, QueryEngine
    from .core.schemas import Dataset
    from .memstore.memstore import TimeSeriesMemStore

    owned = list(owned)
    peer_shards = [s for s in range(n_shards) if s not in set(owned)]
    ms_parent = TimeSeriesMemStore()
    ms_parent.setup(Dataset(dataset), owned, total_shards=n_shards)
    ms_peer = TimeSeriesMemStore()
    ms_peer.setup(Dataset(dataset), peer_shards, total_shards=n_shards)
    if batch is not None:
        ms_parent.ingest_routed(dataset, batch, spread=spread)
        ms_peer.ingest_routed(dataset, batch, spread=spread)
    common = dict(spread=spread, num_shards=n_shards, deadline_s=deadline_s,
                  **params_kw)
    peer_engine = QueryEngine(ms_peer, dataset, PlannerParams(**common))
    server, port = serve_grpc(peer_engine, port=0)
    parent_engine = QueryEngine(
        ms_parent, dataset,
        PlannerParams(peer_endpoints=(f"grpc://127.0.0.1:{port}",), **common),
    )

    def stop():
        server.stop(grace=0)

    return parent_engine, peer_engine, stop


# ---------------------------------------------------------------------------
# replica topology (replicated shard plane chaos harness)
# ---------------------------------------------------------------------------


@dataclass
class ReplicaNode:
    """One data node of a :func:`replica_cluster`: its memstore + engine +
    gRPC server, and the plane handle they register under."""

    name: str
    memstore: object
    engine: object
    server: object
    endpoint: str
    standing: object = None


class ReplicaCluster:
    """Front coordinator + N replicated data nodes, all in-process.

    ``engine`` is the query edge: it owns NO shards and scatters every
    selector through the ReplicaRouter (one shard-pinned gRPC leg per
    selected replica, siblings attached for dispatch-layer failover).
    ``kill(name)`` stops a node's gRPC server AND reports it to the plane —
    the deterministic chaos primitive."""

    def __init__(self, engine, plane, manager, router, nodes, breakers):
        self.engine = engine
        self.plane = plane
        self.manager = manager
        self.router = router
        self.nodes: dict[str, ReplicaNode] = nodes
        self.breakers = breakers

    def kill(self, name: str) -> None:
        n = self.nodes[name]
        n.server.stop(grace=0)
        self.plane.set_node_down(name)

    def stop(self) -> None:
        for n in self.nodes.values():
            n.server.stop(grace=0)


def replica_cluster(batch=None, n_shards: int = 4, num_nodes: int = 2,
                    num_replicas: int = 2, dataset: str = "prometheus",
                    spread: int = 2, deadline_s: float = 30.0,
                    standing: bool = False, retry_policy=None,
                    **params_kw) -> ReplicaCluster:
    """In-process replicated cluster: ``num_nodes`` data nodes behind a
    front coordinator, replication factor ``num_replicas``.

    With the default 2 nodes / RF 2 / shards_per_node == n_shards, every
    node replicates EVERY shard, so killing one node must serve bit-equal
    results from the survivor. ``batch`` (if given) fans out through the
    ReplicationPlane — the production ingest path, acks and watermarks
    included. ``standing=True`` attaches a StandingEngine per data node so
    rebalance handoff tests can follow standing queries across owners."""
    from .api.grpc_exec import serve_grpc
    from .coordinator.cluster import ShardManager, ShardStatus
    from .coordinator.planner import PlannerParams, QueryEngine
    from .coordinator.replication import ReplicaRouter, ReplicationPlane
    from .core.schemas import Dataset
    from .memstore.memstore import TimeSeriesMemStore
    from .query.faults import BreakerRegistry, RetryPolicy

    manager = ShardManager(n_shards, shards_per_node=n_shards,
                           num_replicas=num_replicas)
    plane = ReplicationPlane(manager, dataset, spread=spread)
    common = dict(spread=spread, num_shards=n_shards, deadline_s=deadline_s,
                  **params_kw)
    nodes: dict[str, ReplicaNode] = {}
    for i in range(num_nodes):
        name = f"node-{i}"
        ms = TimeSeriesMemStore()
        ms.setup(Dataset(dataset), [], total_shards=n_shards)
        engine = QueryEngine(ms, dataset, PlannerParams(**common))
        server, port = serve_grpc(engine, port=0)
        endpoint = f"grpc://127.0.0.1:{port}"
        st = None
        if standing:
            from .standing.maintainer import StandingEngine

            st = StandingEngine(engine)
        plane.add_node(name, ms, endpoint=endpoint, standing=st)
        manager.node_joined(name)
        nodes[name] = ReplicaNode(name, ms, engine, server, endpoint, st)
    # fresh topology: every replica is live from the start
    for s in range(n_shards):
        for node in list(manager.mapper.nodes_of(s)):
            manager.mapper.set_replica(s, node, ShardStatus.ACTIVE)
    if batch is not None:
        plane.append(batch)
    router = ReplicaRouter(plane)
    breakers = BreakerRegistry()
    if retry_policy is None:
        # deterministic + fast: seeded jitter, no real sleeping — chaos
        # outcomes must not depend on wall-clock scheduling
        retry_policy = RetryPolicy(seed=0, sleep=lambda s: None)
    ms_front = TimeSeriesMemStore()
    ms_front.setup(Dataset(dataset), [], total_shards=n_shards)
    front = QueryEngine(
        ms_front, dataset,
        PlannerParams(replica_router=router, breakers=breakers,
                      retry_policy=retry_policy, **common),
    )
    return ReplicaCluster(front, plane, manager, router, nodes, breakers)
