"""gRPC RemoteExec tests (reference analog: query_service.proto RemoteExec
exec/executePlan, ProtoConverters round-trip specs in grpc/src/test)."""

import numpy as np
import pytest

from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine, SingleClusterPlanner
from filodb_tpu.core.filters import ColumnFilter
from filodb_tpu.core.schemas import Dataset
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.query import logical as L
from filodb_tpu.query.promql import query_range_to_logical_plan
from filodb_tpu.query.proto_plan import (
    PlanDecodeError,
    RemoteExecError,
    frames_to_result,
    plan_from_bytes,
    plan_to_bytes,
    result_to_frames,
)
from filodb_tpu.query.rangevector import Grid, QueryResult, QueryStats, ScalarResult
from filodb_tpu.testkit import counter_batch

START = 1_600_000_000_000


class TestPlanProtoRoundtrip:
    @pytest.mark.parametrize("seed", range(40))
    def test_fuzzed_plans_roundtrip(self, seed):
        """Same corpus as the parser differential fuzz: every generated plan
        must survive proto encode/decode exactly (dataclass equality)."""
        import random

        from test_promql_diff_fuzz import gen_expr

        rng = random.Random(seed)
        q = gen_expr(rng)
        plan = query_range_to_logical_plan(q, 1_600_000_400, 1_600_000_900, 60)
        assert plan_from_bytes(plan_to_bytes(plan)) == plan, q

    def test_metadata_plans_roundtrip(self):
        for plan in [
            L.LabelValues("job", (ColumnFilter("job", "=", "api"),), 1, 2),
            L.LabelNames((), 1, 2),
            L.SeriesKeysByFilters((ColumnFilter("x", "=~", "a.*"),), 1, 2),
            L.TsCardinalities(("ws", "ns"), 3),
        ]:
            assert plan_from_bytes(plan_to_bytes(plan)) == plan

    def test_none_vs_empty_tuple_preserved(self):
        """by=None (no grouping) and by=() (group-all-away) are different
        aggregations — the wire must keep them distinct."""
        inner = L.PeriodicSeries(L.RawSeries((), 0, 10), 0, 10, 1)
        for by in (None, ()):
            p = L.Aggregate("sum", inner, by=by, without=None)
            back = plan_from_bytes(plan_to_bytes(p))
            assert back.by == by and back == p

    def test_in_filter_tuple_value(self):
        f = ColumnFilter("job", "in", ("a", "b"))
        p = L.RawSeries((f,), 5, 9)
        assert plan_from_bytes(plan_to_bytes(p)) == p

    def test_unknown_kind_rejected(self):
        from filodb_tpu.api import query_exec_pb2 as pb

        node = pb.PlanNode(kind="os.system")
        with pytest.raises(PlanDecodeError, match="unknown plan kind"):
            plan_from_bytes(node.SerializeToString())

    def test_unknown_field_rejected(self):
        from filodb_tpu.api import query_exec_pb2 as pb

        node = pb.PlanNode(kind="RawSeries")
        f = node.fields.add(name="nope")
        f.value.ival = 1
        with pytest.raises(PlanDecodeError, match="no field"):
            plan_from_bytes(node.SerializeToString())

    def test_missing_required_field_rejected(self):
        from filodb_tpu.api import query_exec_pb2 as pb

        node = pb.PlanNode(kind="Aggregate")  # no op/inner
        with pytest.raises(PlanDecodeError, match="cannot build"):
            plan_from_bytes(node.SerializeToString())


class TestResultFrames:
    def _roundtrip(self, res, **kw):
        return frames_to_result(iter(list(result_to_frames(res, **kw))))

    def test_grid_roundtrip_with_nans_and_chunking(self):
        vals = np.arange(5 * 7, dtype=np.float32).reshape(5, 7)
        vals[1, 3] = np.nan
        labels = [{"_metric_": "m", "i": str(i)} for i in range(5)]
        res = QueryResult(grids=[Grid(labels, START, 60_000, 7, vals)])
        res.stats = QueryStats(series_scanned=5, samples_scanned=35)
        back = self._roundtrip(res, chunk_rows=2)  # forces 3 chunks
        assert back.grids[0].labels == labels
        np.testing.assert_array_equal(back.grids[0].values_np(), vals)
        assert back.stats.series_scanned == 5
        assert back.stats.samples_scanned == 35

    def test_histogram_grid_roundtrip(self):
        les = np.array([0.5, 1.0, float("inf")])
        hist = np.random.default_rng(0).random((3, 4, 3)).astype(np.float32)
        sums = hist.sum(axis=2)
        labels = [{"_metric_": "h", "i": str(i)} for i in range(3)]
        res = QueryResult(grids=[Grid(labels, START, 1000, 4, sums, hist=hist, les=les)])
        back = self._roundtrip(res)
        np.testing.assert_array_equal(back.grids[0].hist_np(), hist)
        np.testing.assert_array_equal(back.grids[0].les, les)

    def test_scalar_and_metadata_roundtrip(self):
        res = QueryResult()
        res.scalar = ScalarResult(START, 1000, 4, np.array([1.0, 2.5, 3.0, 4.0]))
        res.result_type = "scalar"
        back = self._roundtrip(res)
        assert back.result_type == "scalar"
        np.testing.assert_array_equal(back.scalar.values, res.scalar.values)

        res2 = QueryResult()
        res2.metadata = ["a", "b"]
        res2.result_type = "metadata"
        assert self._roundtrip(res2).metadata == ["a", "b"]

    def test_empty_grid(self):
        res = QueryResult(grids=[Grid([], START, 1000, 4, np.zeros((0, 4), np.float32))])
        back = self._roundtrip(res)
        assert back.grids[0].n_series == 0
        assert back.grids[0].values_np().shape == (0, 4)

    def test_truncated_stream_detected(self):
        vals = np.ones((3, 2), np.float32)
        res = QueryResult(grids=[Grid([{"i": "0"}, {"i": "1"}, {"i": "2"}], START, 1000, 2, vals)])
        frames = list(result_to_frames(res, chunk_rows=2))
        # drop the second chunk: series count no longer matches the header
        with pytest.raises(RemoteExecError, match="series"):
            frames_to_result(iter([frames[0], frames[1], frames[-1]]))


def _make_engine(n_series=12, **params):
    ms = TimeSeriesMemStore()
    ms.setup(Dataset("prometheus"), range(4))
    ms.ingest_routed(
        "prometheus",
        counter_batch(n_series=n_series, n_samples=60, start_ms=START),
        spread=2,
    )
    return QueryEngine(ms, "prometheus", PlannerParams(spread=2, num_shards=4, **params))


class TestGrpcServer:
    @pytest.fixture(scope="class")
    def served(self):
        from filodb_tpu.api.grpc_exec import serve_grpc

        engine = _make_engine()
        server, port = serve_grpc(engine, port=0, host="127.0.0.1")
        yield engine, f"grpc://127.0.0.1:{port}"
        server.stop(grace=0)

    def test_exec_promql_matches_local(self, served):
        from filodb_tpu.api.grpc_exec import exec_promql

        engine, ep = served
        q = "sum(rate(http_requests_total[5m]))"
        s, e, st = START + 400_000, START + 900_000, 60_000
        want = engine.query_range(q, s / 1000, e / 1000, st / 1000)
        got = exec_promql(ep, q, s, e, st)
        np.testing.assert_allclose(
            got.grids[0].values_np(), want.grids[0].values_np(), rtol=1e-6
        )
        assert got.stats.series_scanned == want.stats.series_scanned

    def test_exec_instant(self, served):
        from filodb_tpu.api.grpc_exec import exec_promql

        engine, ep = served
        t = START + 600_000
        got = exec_promql(ep, "http_requests_total", 0, t, 0, instant=True)
        want = engine.query_instant("http_requests_total", t / 1000)
        assert got.result_type == "vector"
        assert len(got.grids[0].labels) == len(want.grids[0].labels)

    def test_execute_plan_matches_promql_path(self, served):
        from filodb_tpu.api.grpc_exec import exec_plan_remote, exec_promql

        _, ep = served
        q = "sum by (instance) (rate(http_requests_total[5m]))"
        s, e, st = START + 400_000, START + 900_000, 60_000
        plan = query_range_to_logical_plan(q, s / 1000, e / 1000, st / 1000)
        via_plan = exec_plan_remote(ep, plan)
        via_promql = exec_promql(ep, q, s, e, st)
        key = lambda g: sorted(map(str, g.labels))
        assert key(via_plan.grids[0]) == key(via_promql.grids[0])
        a = via_plan.grids[0].values_np()[np.argsort(key(via_plan.grids[0]))]
        b = via_promql.grids[0].values_np()[np.argsort(key(via_promql.grids[0]))]
        np.testing.assert_allclose(a, b, rtol=1e-6)

    def test_metadata_plan_over_grpc(self, served):
        from filodb_tpu.api.grpc_exec import remote_metadata

        engine, ep = served
        vals = remote_metadata(ep, L.LabelValues("instance", (), START, START + 10**7))
        want = engine.label_values((), "instance", START, START + 10**7)
        assert sorted(vals) == sorted(want) and vals

    def test_query_error_propagates_typed(self, served):
        """In-band error frames re-raise as the LOCAL exception classes so
        the origin's API edge maps remote failures to the same status codes
        as local ones (400 bad query, 503 rejection/timeout)."""
        from filodb_tpu.api.grpc_exec import exec_promql
        from filodb_tpu.query.exec.transformers import QueryError

        _, ep = served
        with pytest.raises(QueryError, match="remote QueryError"):
            exec_promql(ep, "sum(rate(m[5m", START, START + 60_000, 60_000)

    def test_plan_decode_error_propagates(self, served):
        import grpc as grpclib

        from filodb_tpu.api import query_exec_pb2 as pb
        from filodb_tpu.api.grpc_exec import _EXECUTE_PLAN, grpc_target

        _, ep = served
        ch = grpclib.insecure_channel(grpc_target(ep))
        call = ch.unary_stream(
            _EXECUTE_PLAN,
            request_serializer=pb.ExecutePlanRequest.SerializeToString,
            response_deserializer=pb.StreamFrame.FromString,
        )
        from filodb_tpu.query.exec.transformers import QueryError

        req = pb.ExecutePlanRequest(plan=pb.PlanNode(kind="__import__"))
        with pytest.raises(QueryError, match="remote PlanDecodeError"):
            frames_to_result(call(req))
        ch.close()


class TestGrpcAuth:
    def test_token_enforced(self):
        from filodb_tpu.api.grpc_exec import exec_promql, serve_grpc

        engine = _make_engine(n_series=4)
        server, port = serve_grpc(engine, port=0, host="127.0.0.1", auth_token="s3cret")
        ep = f"grpc://127.0.0.1:{port}"
        try:
            with pytest.raises(RemoteExecError, match="UNAUTHENTICATED"):
                exec_promql(ep, "up", START, START + 60_000, 60_000)
            with pytest.raises(RemoteExecError, match="UNAUTHENTICATED"):
                exec_promql(ep, "up", START, START + 60_000, 60_000, auth_token="wrong")
            res = exec_promql(
                ep, "http_requests_total", START, START + 600_000, 60_000,
                auth_token="s3cret",
            )
            assert res.grids
        finally:
            server.stop(grace=0)


class TestGrpcPeerPlanning:
    def test_peer_leaves_use_plan_transport(self):
        """grpc:// peers get GrpcPlanRemoteExec leaves carrying the logical
        subtree; aggregate pushdown replaces it with the wrapped Aggregate."""
        from filodb_tpu.api.grpc_exec import GrpcPlanRemoteExec

        ms = TimeSeriesMemStore()
        ms.setup(Dataset("prometheus"), range(4))
        pl = SingleClusterPlanner(
            ms, "prometheus",
            params=PlannerParams(num_shards=4, peer_endpoints=("grpc://peer:7777",)),
        )
        plan = query_range_to_logical_plan(
            "sum(rate(http_requests_total[5m]))", 1_600_000_400, 1_600_000_900, 60
        )
        tree = pl.materialize(plan)
        remotes = [p for p in _walk(tree) if isinstance(p, GrpcPlanRemoteExec)]
        assert len(remotes) == 1
        # pushdown happened: the peer computes mergeable components
        assert isinstance(remotes[0].logical_plan, L.PartialAggregate)
        assert remotes[0].logical_plan.op == "sum"
        assert remotes[0].local_only

    def test_http_peers_still_use_promql(self):
        from filodb_tpu.coordinator.planners import PromQlRemoteExec

        ms = TimeSeriesMemStore()
        ms.setup(Dataset("prometheus"), range(4))
        pl = SingleClusterPlanner(
            ms, "prometheus",
            params=PlannerParams(num_shards=4, peer_endpoints=("http://peer:9090",)),
        )
        plan = query_range_to_logical_plan("up", 1_600_000_400, 1_600_000_900, 60)
        tree = pl.materialize(plan)
        assert any(isinstance(p, PromQlRemoteExec) for p in _walk(tree))


def _walk(plan):
    yield plan
    for c in plan.children():
        yield from _walk(c)


class TestTwoServerGrpcScatter:
    def test_scattered_query_matches_single_host(self):
        """Two FiloServers, each owning half the shards, scattering over
        gRPC plan transport — same assertion as the HTTP multihost test."""
        from filodb_tpu.server import FiloServer

        base = {"dataset": "prometheus", "shards": 8, "grpc_port": 0,
                "query": {"timeout_s": 30}}
        a = FiloServer({**base, "distributed": {"owned_shards": [0, 1, 2, 3]}})
        b = FiloServer({**base, "distributed": {"owned_shards": [4, 5, 6, 7]}})
        try:
            a.start(port=0)
            b.start(port=0)
            a.engine.planner.params.peer_endpoints = (f"grpc://127.0.0.1:{b.grpc_port}",)
            b.engine.planner.params.peer_endpoints = (f"grpc://127.0.0.1:{a.grpc_port}",)
            for srv in (a, b):
                srv.local_engine = QueryEngine(
                    srv.memstore, srv.dataset,
                    PlannerParams(num_shards=8, deadline_s=30),
                )
                srv._grpc = None  # replaced below with local_engine wired in
            # restart grpc servers with local engines (ports were ephemeral)
            from filodb_tpu.api.grpc_exec import serve_grpc

            ga, pa = serve_grpc(a.engine, port=0, host="127.0.0.1", local_engine=a.local_engine)
            gb, pb_ = serve_grpc(b.engine, port=0, host="127.0.0.1", local_engine=b.local_engine)
            a.engine.planner.params.peer_endpoints = (f"grpc://127.0.0.1:{pb_}",)
            b.engine.planner.params.peer_endpoints = (f"grpc://127.0.0.1:{pa}",)

            batch = counter_batch(n_series=24, n_samples=120, start_ms=START)
            na = a.memstore.ingest_routed("prometheus", batch, spread=3)
            nb = b.memstore.ingest_routed("prometheus", batch, spread=3)
            assert na + nb == 24 * 120 and na > 0 and nb > 0

            ms = TimeSeriesMemStore()
            ms.setup(Dataset("prometheus"), range(8))
            ms.ingest_routed(
                "prometheus",
                counter_batch(n_series=24, n_samples=120, start_ms=START),
                spread=3,
            )
            eng = QueryEngine(ms, "prometheus")
            s, e = START / 1000 + 400, START / 1000 + 1100
            q = "sum(rate(http_requests_total[5m]))"
            want = eng.query_range(q, s, e, 60).grids[0].values_np()
            got = a.engine.query_range(q, s, e, 60).grids[0].values_np()
            np.testing.assert_allclose(got, want, rtol=1e-4)

            # plain selector through B sees all 24 series
            sel = b.engine.query_range("http_requests_total", s, e, 60)
            assert sel.grids and sum(g.n_series for g in sel.grids) == 24
            ga.stop(grace=0)
            gb.stop(grace=0)
        finally:
            a.stop()
            b.stop()


def test_plan_remote_env_token_fallback(monkeypatch):
    """Advisor regression: GrpcPlanRemoteExec must fall back to
    FILODB_REMOTE_TOKEN like PromQlRemoteExec, so token-protected gRPC
    federation authenticates without explicit plumbing."""
    from filodb_tpu.api.grpc_exec import GrpcPlanRemoteExec

    monkeypatch.setenv("FILODB_REMOTE_TOKEN", "env-tok")
    p = GrpcPlanRemoteExec("grpc://h:1", logical_plan=None)
    assert p.auth_token == "env-tok"
    p2 = GrpcPlanRemoteExec("grpc://h:1", logical_plan=None, auth_token="explicit")
    assert p2.auth_token == "explicit"
    monkeypatch.delenv("FILODB_REMOTE_TOKEN")
    assert GrpcPlanRemoteExec("grpc://h:1", logical_plan=None).auth_token is None
