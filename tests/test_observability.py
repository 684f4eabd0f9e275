"""Metrics, cardinality, tracing, profiler tests (model: reference
CardinalityTracker specs + TimeSeriesShardStats assertions)."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from filodb_tpu.api.http import serve_background
from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine
from filodb_tpu.core.schemas import Dataset
from filodb_tpu.memstore.cardinality import CardinalityTracker, QuotaExceededError
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.metrics import (
    REGISTRY,
    SLOW_QUERY_LOG,
    Registry,
    SamplingProfiler,
    Span,
    activate,
    current_span,
    current_trace,
    span,
    trace_to_dict,
)
from filodb_tpu.testkit import counter_batch, grpc_cluster, machine_metrics

pytestmark = pytest.mark.observability

BASE = 1_600_000_000_000


def find_span(tree: dict, name: str) -> dict | None:
    """First span named ``name`` in a rendered trace tree (DFS)."""
    if tree is None:
        return None
    if tree.get("name") == name:
        return tree
    for c in tree.get("children", ()):
        hit = find_span(c, name)
        if hit is not None:
            return hit
    return None


class TestCardinalityTracker:
    def test_counts_by_prefix(self):
        t = CardinalityTracker()
        for i in range(10):
            t.series_created({"_ws_": "demo", "_ns_": f"app-{i % 2}", "_metric_": f"m{i}"})
        assert t.record_of(()).ts_count == 10
        assert t.record_of(("demo",)).ts_count == 10
        assert t.record_of(("demo", "app-0")).ts_count == 5
        assert t.record_of(("demo",)).children == 2

    def test_quota_enforced(self):
        t = CardinalityTracker()
        t.set_quota(("demo", "app"), 3)
        for i in range(3):
            t.series_created({"_ws_": "demo", "_ns_": "app", "_metric_": f"m{i}"})
        with pytest.raises(QuotaExceededError):
            t.series_created({"_ws_": "demo", "_ns_": "app", "_metric_": "m99"})
        # other namespaces unaffected
        t.series_created({"_ws_": "demo", "_ns_": "other", "_metric_": "ok"})

    def test_active_vs_total(self):
        t = CardinalityTracker()
        tags = {"_ws_": "w", "_ns_": "n", "_metric_": "m"}
        t.series_created(tags)
        t.series_stopped(tags)
        rec = t.record_of(("w", "n", "m"))
        assert rec.ts_count == 1 and rec.active_ts_count == 0

    def test_scan_depth(self):
        t = CardinalityTracker()
        for ns in ("a", "b", "c"):
            for i in range(int(ns == "a") * 2 + 1):
                t.series_created({"_ws_": "w", "_ns_": ns, "_metric_": f"m{i}"})
        recs = t.scan(("w",), 2)
        assert [r.prefix[-1] for r in recs][0] == "a"  # sorted by count desc

    def test_save_load(self, tmp_path):
        t = CardinalityTracker()
        t.set_quota(("w",), 100)
        t.series_created({"_ws_": "w", "_ns_": "n", "_metric_": "m"})
        p = str(tmp_path / "card.json")
        t.save(p)
        t2 = CardinalityTracker.load(p)
        assert t2.record_of(("w", "n")).ts_count == 1
        assert t2.quota_of(("w",)) == 100

    def test_shard_integration(self):
        ms = TimeSeriesMemStore()
        ms.setup(Dataset("ds"), [0])
        ms.ingest("ds", 0, machine_metrics(n_series=7, n_samples=5, start_ms=BASE))
        sh = ms.shard("ds", 0)
        assert sh.cardinality.record_of(()).ts_count == 7


class TestMetricsRegistry:
    def test_counter_gauge_histogram_expose(self):
        r = Registry()
        r.counter("reqs", code="200").inc(5)
        r.gauge("up").set(1)
        r.histogram("lat").observe(0.003)
        r.histogram("lat").observe(0.3)
        text = r.expose()
        assert 'reqs_total{code="200"} 5' in text
        assert "up 1" in text
        assert 'lat_bucket{le="0.005"} 1' in text
        assert 'lat_bucket{le="+Inf"} 2' in text
        assert "lat_count 2" in text

    def test_metrics_endpoint(self):
        ms = TimeSeriesMemStore()
        ms.setup(Dataset("prometheus"), [0])
        ms.ingest("prometheus", 0, machine_metrics(n_series=3, n_samples=50, start_ms=BASE))
        engine = QueryEngine(ms, "prometheus")
        engine.query_range("heap_usage0", (BASE + 600_000) / 1000, (BASE + 900_000) / 1000, 60)
        srv, port = serve_background(engine)
        try:
            with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics") as r:
                text = r.read().decode()
            assert "filodb_shard_partitions" in text
            assert "filodb_queries_total" in text
            assert "filodb_query_latency_seconds_bucket" in text
        finally:
            srv.shutdown()

    def test_cardinality_endpoint(self):
        ms = TimeSeriesMemStore()
        ms.setup(Dataset("prometheus"), [0, 1])
        ms.ingest_routed("prometheus", machine_metrics(n_series=10, n_samples=5, start_ms=BASE), spread=1)
        srv, port = serve_background(QueryEngine(ms, "prometheus"))
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/api/v1/cardinality?prefix=demo&depth=2"
            ) as r:
                out = json.loads(r.read())
            assert out["data"][0]["prefix"] == ["demo", "App-2"]
            assert out["data"][0]["ts_count"] == 10
        finally:
            srv.shutdown()


class TestTracing:
    def test_nested_spans(self):
        with span("root") as root:
            with span("child1"):
                time.sleep(0.01)
            with span("child2"):
                pass
        assert len(root.children) == 2
        assert root.duration_ms >= root.children[0].duration_ms
        assert "child1" in root.tree()

    def test_exec_plans_emit_spans(self):
        ms = TimeSeriesMemStore()
        ms.setup(Dataset("prometheus"), [0])
        ms.ingest("prometheus", 0, machine_metrics(n_series=2, n_samples=50, start_ms=BASE))
        engine = QueryEngine(ms, "prometheus")
        with span("query") as root:
            engine.query_range("sum(heap_usage0)", (BASE + 600_000) / 1000, (BASE + 900_000) / 1000, 60)
        # the caller's span holds the engine's (each caller's wall inside
        # the engine), which holds the query's root and its plan nodes
        (engine_span,) = root.children
        assert engine_span.name == "engine:query_range"
        (query_root,) = engine_span.children
        assert query_root.name == "query"
        assert query_root.trace_id == root.trace_id
        names = [c.name for c in query_root.children]
        # default engine plans the aggregate as the fused single-dispatch
        # node; its stage/dispatch phases are child spans
        assert "FusedAggregateExec" in names
        fused = query_root.children[names.index("FusedAggregateExec")]
        child_names = {c.name for c in fused.children}
        assert "fused:stage" in child_names
        assert any(n.startswith("fused:dispatch") for n in child_names)


class TestRegistryEscaping:
    def test_label_values_escaped_per_exposition_spec(self):
        r = Registry()
        r.counter("reqs", path='say "hi"\\there\nnow').inc()
        r.gauge("g", v="a\\b").set(2)
        r.histogram("h", q='"').observe(0.01)
        text = r.expose()
        assert 'reqs_total{path="say \\"hi\\"\\\\there\\nnow"} 1' in text
        assert 'g{v="a\\\\b"} 2' in text
        # no raw (unescaped) newline may survive inside a label value:
        # every exposition SAMPLE line must end in a numeric sample value
        # (# HELP / # TYPE metadata lines are exempt)
        for line in text.strip().splitlines():
            if line.startswith("#"):
                continue
            float(line.rsplit(" ", 1)[1])

    def test_collectors_refresh_at_scrape_time(self):
        r = Registry()
        state = {"n": 1}
        r.register_collector("t", lambda: r.gauge("live_n").set(state["n"]))
        assert "live_n 1" in r.expose()
        state["n"] = 7
        assert "live_n 7" in r.expose()
        # re-registration replaces, never stacks
        r.register_collector("t", lambda: r.gauge("live_n").set(0))
        assert "live_n 0" in r.expose()

    def test_shard_stats_ride_shared_registry(self):
        """The /metrics handler no longer hand-rolls shard lines: gauges are
        refreshed by a scrape-time collector in the ONE registry."""
        ms = TimeSeriesMemStore()
        ms.setup(Dataset("prometheus"), [0])
        ms.ingest("prometheus", 0, machine_metrics(n_series=4, n_samples=5, start_ms=BASE))
        srv, port = serve_background(QueryEngine(ms, "prometheus"))
        try:
            with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics") as resp:
                text = resp.read().decode()
            assert 'filodb_shard_partitions{dataset="prometheus",shard="0"} 4' in text
            # ingest more and re-scrape: the gauge refreshes
            ms.ingest("prometheus", 0, machine_metrics(
                n_series=6, n_samples=5, start_ms=BASE, metric="other_m"))
            with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics") as resp:
                text = resp.read().decode()
            assert 'filodb_shard_partitions{dataset="prometheus",shard="0"} 10' in text
        finally:
            srv.shutdown()


class TestTracePropagation:
    def test_spans_survive_thread_pool_via_activate(self):
        """The cross-thread primitive: a worker re-activating a captured
        span attaches its children under the right parent."""
        from concurrent.futures import ThreadPoolExecutor

        with span("root") as root:
            parent = current_span()

            def work(i):
                with activate(parent):
                    with span(f"child-{i}"):
                        return current_span() is not None

            with ThreadPoolExecutor(max_workers=2) as pool:
                assert all(pool.map(work, range(4)))
        names = sorted(c.name for c in root.children)
        assert names == [f"child-{i}" for i in range(4)]
        assert all(c.trace_id == root.trace_id for c in root.children)
        assert all(c.parent_id == root.span_id for c in root.children)

    def test_execute_children_pool_keeps_spans_parented(self):
        """Remote children dispatch on pool threads; their execute spans
        must land under the merge node's span, not as orphan roots."""
        from filodb_tpu.query.exec.plans import DistConcatExec, ExecPlan, QueryContext
        from filodb_tpu.query.rangevector import QueryResult

        seen = []

        class RemoteStub(ExecPlan):
            is_remote = True

            def __init__(self, endpoint):
                super().__init__()
                self.endpoint = endpoint

            def args_str(self):
                return f"endpoint={self.endpoint}"

            def do_execute(self, ctx):
                seen.append(current_span())
                return QueryResult()

        plan = DistConcatExec([RemoteStub("grpc://a:1"), RemoteStub("grpc://b:1")])
        ctx = QueryContext(None, "ds")
        with span("query") as root:
            plan.execute(ctx)
        concat = next(c for c in root.children if c.name == "DistConcatExec")
        child_names = sorted(c.name for c in concat.children)
        assert child_names == ["RemoteStub", "RemoteStub"]
        # the spans observed INSIDE the workers were real and correctly wired
        assert all(s is not None and s.trace_id == root.trace_id for s in seen)

    def test_distributed_grpc_trace_stitches_single_tree(self):
        """Acceptance: a distributed query through the in-process cluster
        testkit (parent -> remote gRPC child) returns ONE stitched span tree
        with per-node durations and QueryStats."""
        eng, _peer, stop = grpc_cluster(
            counter_batch(n_series=16, n_samples=60, start_ms=BASE),
        )
        try:
            res = eng.query_range(
                "sum(rate(http_requests_total[5m]))",
                BASE / 1000 + 400, BASE / 1000 + 900, 60,
            )
            tree = trace_to_dict(res.trace)
            assert tree["name"] == "query" and tree["trace_id"]
            remote = find_span(tree, "GrpcPlanRemoteExec")
            assert remote is not None, "no remote child span in trace"
            # the peer's tree was stitched IN-BAND under the dispatching span
            peer_root = find_span(remote, "query")
            assert peer_root is not None and peer_root["children"]
            peer_scan = find_span(peer_root, "SelectRawPartitionsExec")
            assert peer_scan is not None
            # stitched spans joined the LOCAL trace
            assert peer_root["trace_id"] == tree["trace_id"]
            assert peer_root["parent_id"] == remote["span_id"]
            # per-node durations + QueryStats annotations
            assert remote["duration_ms"] > 0 and peer_scan["duration_ms"] >= 0
            assert peer_scan["stats"]["series_scanned"] > 0
            assert peer_scan["stats"]["samples_scanned"] > 0
            # peer stats merged into the query-wide stats: all 16 series
            assert res.stats.series_scanned == 16
            local_scan = find_span(tree, "SelectRawPartitionsExec")
            assert local_scan is not None
        finally:
            stop()

    def test_http_trace_param_returns_stitched_tree(self):
        """?trace=true (and explain=analyze) on the HTTP edge returns the
        annotated plan tree for a distributed query."""
        eng, _peer, stop = grpc_cluster(
            counter_batch(n_series=16, n_samples=60, start_ms=BASE),
        )
        srv, port = serve_background(eng)
        try:
            q = ("query=sum(rate(http_requests_total[5m]))"
                 f"&start={BASE / 1000 + 400}&end={BASE / 1000 + 900}&step=60")
            base_url = f"http://127.0.0.1:{port}/api/v1/query_range?{q}"
            plain = json.loads(urllib.request.urlopen(base_url).read())
            assert "trace" not in plain["data"]
            for mode in ("&trace=true", "&explain=analyze"):
                out = json.loads(urllib.request.urlopen(base_url + mode).read())
                tree = out["data"]["trace"]
                remote = find_span(tree, "GrpcPlanRemoteExec")
                assert remote is not None and find_span(remote, "query") is not None
            # stats include the remote slice
            assert out["data"]["stats"]["seriesScanned"] == 16
        finally:
            srv.shutdown()
            stop()

    def test_trace_headers_link_parent_trace(self):
        """An origin's trace identity sent via headers becomes this node's
        trace id / root parent (cross-node linkage over HTTP)."""
        ms = TimeSeriesMemStore()
        ms.setup(Dataset("prometheus"), [0])
        ms.ingest("prometheus", 0, machine_metrics(n_series=2, n_samples=30, start_ms=BASE))
        srv, port = serve_background(QueryEngine(ms, "prometheus"))
        try:
            q = f"query=heap_usage0&start={BASE / 1000 + 300}&end={BASE / 1000 + 500}&step=60&trace=1"
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/api/v1/query_range?{q}",
                headers={"X-FiloDB-Trace-Id": "feedfacefeedface",
                         "X-FiloDB-Parent-Span": "cafecafecafecafe"},
            )
            out = json.loads(urllib.request.urlopen(req).read())
            tree = out["data"]["trace"]
            assert tree["trace_id"] == "feedfacefeedface"
            assert tree["parent_id"] == "cafecafecafecafe"
        finally:
            srv.shutdown()

    def test_span_wire_roundtrip_rewrites_linkage(self):
        with span("peer-root") as s:
            with span("leaf"):
                pass
        grafted = Span.from_dict(s.to_dict(), trace_id="T" * 16, parent_id="P" * 16)
        assert grafted.trace_id == "T" * 16 and grafted.parent_id == "P" * 16
        assert grafted.children[0].trace_id == "T" * 16
        assert grafted.children[0].parent_id == grafted.span_id
        assert abs(grafted.duration_ms - s.duration_ms) < 0.01


class TestSlowQueryLog:
    def test_slow_query_recorded_with_trace(self):
        SLOW_QUERY_LOG.clear()
        ms = TimeSeriesMemStore()
        ms.setup(Dataset("prometheus"), [0, 1])
        ms.ingest_routed("prometheus", machine_metrics(n_series=8, n_samples=50, start_ms=BASE), spread=1)
        engine = QueryEngine(ms, "prometheus",
                             PlannerParams(spread=1, slow_query_threshold_s=0.0))
        engine.query_range("sum(heap_usage0)", (BASE + 600_000) / 1000,
                           (BASE + 900_000) / 1000, 60)
        entries = SLOW_QUERY_LOG.entries()
        assert entries, "threshold 0 must record every query"
        e = entries[0]
        assert e["promql"] == "sum(heap_usage0)"
        assert e["duration_s"] > 0
        assert e["stats"]["series_scanned"] == 8
        assert find_span(e["trace"], "FusedAggregateExec") is not None

    def test_fast_queries_not_recorded(self):
        SLOW_QUERY_LOG.clear()
        ms = TimeSeriesMemStore()
        ms.setup(Dataset("prometheus"), [0])
        ms.ingest("prometheus", 0, machine_metrics(n_series=2, n_samples=20, start_ms=BASE))
        engine = QueryEngine(ms, "prometheus",
                             PlannerParams(slow_query_threshold_s=3600.0))
        engine.query_range("heap_usage0", (BASE + 300_000) / 1000,
                           (BASE + 400_000) / 1000, 60)
        assert SLOW_QUERY_LOG.entries() == []

    def test_debug_endpoint_and_counter(self):
        SLOW_QUERY_LOG.clear()
        ms = TimeSeriesMemStore()
        ms.setup(Dataset("prometheus"), [0])
        ms.ingest("prometheus", 0, machine_metrics(n_series=3, n_samples=30, start_ms=BASE))
        engine = QueryEngine(ms, "prometheus",
                             PlannerParams(slow_query_threshold_s=0.0))
        srv, port = serve_background(engine)
        try:
            engine.query_range("sum(heap_usage0)", (BASE + 300_000) / 1000,
                               (BASE + 600_000) / 1000, 60)
            out = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/slow_queries").read())
            assert out["status"] == "success" and out["data"]
            assert out["data"][0]["trace"] is not None
            with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics") as r:
                text = r.read().decode()
            assert "filodb_slow_queries_total" in text
        finally:
            srv.shutdown()

    def test_ring_buffer_bounded(self):
        from filodb_tpu.metrics import SlowQueryLog

        log = SlowQueryLog(max_entries=3)
        for i in range(10):
            log.record(f"q{i}", 1.0, dataset="d")
        entries = log.entries()
        assert len(entries) == 3
        assert entries[0]["promql"] == "q9"  # newest first


class TestKernelInstrumentation:
    def test_dispatch_histogram_and_jit_counters(self):
        ms = TimeSeriesMemStore()
        ms.setup(Dataset("prometheus"), [0])
        ms.ingest("prometheus", 0, counter_batch(n_series=4, n_samples=60, start_ms=BASE))
        engine = QueryEngine(ms, "prometheus")
        engine.query_range("sum(rate(http_requests_total[5m]))",
                           (BASE + 600_000) / 1000, (BASE + 900_000) / 1000, 60)
        text = REGISTRY.expose()
        # the fused path records ONE dispatch for the whole query
        assert 'filodb_kernel_dispatch_seconds_bucket{kernel="fused_sum_rate"' in text
        assert 'filodb_compile_cache_misses_total{tier="in_process"}' in text
        # a repeat of the same shape must record HITS, not new misses
        hits = REGISTRY.counter("filodb_compile_cache_hits", tier="in_process")
        before = hits.value
        engine.query_range("sum(rate(http_requests_total[5m]))",
                           (BASE + 630_000) / 1000, (BASE + 930_000) / 1000, 60)
        assert hits.value > before
        # the reference tree still records per-kernel dispatches
        ref = QueryEngine(ms, "prometheus", PlannerParams(fused_aggregate=False))
        ref.query_range("sum(rate(http_requests_total[5m]))",
                        (BASE + 600_000) / 1000, (BASE + 900_000) / 1000, 60)
        text = REGISTRY.expose()
        assert 'filodb_kernel_dispatch_seconds_bucket{kernel="rate"' in text
        assert 'filodb_kernel_dispatch_seconds_count{kernel="segment_sum"}' in text


class TestProfiler:
    def test_start_is_idempotent(self):
        prof = SamplingProfiler(interval_s=0.01)
        prof.start()
        t1 = prof._thread
        prof.start()  # must NOT leak a second sampler thread
        assert prof._thread is t1
        prof.stop()
        # restart after stop works
        prof.start()
        t2 = prof._thread
        assert t2 is not t1 and t2.is_alive()
        prof.stop()

    def test_debug_profile_endpoint_gated(self):
        ms = TimeSeriesMemStore()
        ms.setup(Dataset("prometheus"), [0])
        srv, port = serve_background(QueryEngine(ms, "prometheus"))
        try:
            # not wired (config off): 404
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(f"http://127.0.0.1:{port}/debug/profile")
            assert exc.value.code == 404
            # wired (what FiloServer does when filodb.profiler is enabled)
            prof = SamplingProfiler(interval_s=0.005)
            prof.start()
            srv.RequestHandlerClass.profiler_hook = staticmethod(prof.report)
            time.sleep(0.05)
            with urllib.request.urlopen(f"http://127.0.0.1:{port}/debug/profile") as r:
                assert r.status == 200
            prof.stop()
        finally:
            srv.shutdown()

    def test_sampling_profiler_catches_busy_thread(self):
        def busy():
            end = time.time() + 0.4
            while time.time() < end:
                sum(range(1000))

        t = threading.Thread(target=busy)
        prof = SamplingProfiler(interval_s=0.005)
        prof.start()
        t.start()
        t.join()
        prof.stop()
        assert "busy" in prof.report()
