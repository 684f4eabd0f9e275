"""Peer-level mergeable aggregation components (L.PartialAggregate).

Federation used to ship raw series unions for count/avg/stddev/quantile —
O(series) on the wire where the reference exchanges O(groups) mergeable
AggregateItems (RowAggregator.scala:28,114, AggrOverRangeVectors.scala:224,
QuantileRowAggregator's t-digests). gRPC plan-transport peers now receive
PartialAggregate and return __comp__-labeled component grids ((sum,count)
for avg, (sum,sumsq,count) for stddev, log-linear sketch counts for
quantile) that the coordinator merges exactly like local shard partials.
"""

import numpy as np
import pytest

from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine
from filodb_tpu.core.schemas import Dataset
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.query import logical as L
from filodb_tpu.testkit import counter_batch, machine_metrics

START = 1_600_000_000_000


def _walk(plan):
    yield plan
    for c in plan.children():
        yield from _walk(c)


# ---------------------------------------------------------------------------
# plan-level: the peer leaf carries PartialAggregate for component ops


@pytest.mark.parametrize("op", ["count", "avg", "stddev", "stdvar", "sum"])
def test_peer_leaf_ships_partial_aggregate(op):
    from filodb_tpu.api.grpc_exec import GrpcPlanRemoteExec
    from filodb_tpu.query.promql import query_range_to_logical_plan

    ms = TimeSeriesMemStore()
    ms.setup(Dataset("prometheus"), range(4))
    eng = QueryEngine(
        ms, "prometheus",
        PlannerParams(num_shards=4, peer_endpoints=("grpc://127.0.0.1:1",)),
    )
    lp = query_range_to_logical_plan(
        f"{op}(rate(http_requests_total[5m]))",
        START / 1000 + 400, START / 1000 + 1000, 60,
    )
    tree = eng.planner.materialize(lp)
    remotes = [p for p in _walk(tree) if isinstance(p, GrpcPlanRemoteExec)]
    assert remotes, "peer endpoint must produce a plan-transport leaf"
    for r in remotes:
        assert isinstance(r.logical_plan, L.PartialAggregate)
        assert r.logical_plan.op == op


def test_partial_aggregate_proto_roundtrip():
    from filodb_tpu.query.proto_plan import plan_from_bytes, plan_to_bytes

    p = L.PartialAggregate(
        "avg",
        L.RawSeries(filters=(), start_ms=1, end_ms=2),
        (),
        by=("instance",),
        without=None,
    )
    q = plan_from_bytes(plan_to_bytes(p))
    assert q == p


def test_sketch_grid_frames_roundtrip():
    """Quantile sketch cubes (les-less hist payloads, mostly zeros) must
    survive the gRPC frames, including the sparse encoding."""
    from filodb_tpu.query.proto_plan import frames_to_result, result_to_frames
    from filodb_tpu.query.rangevector import Grid, QueryResult

    rng = np.random.default_rng(0)
    G, J, B = 3, 16, 4097
    counts = np.zeros((G, J, B), np.float32)
    # ~100 nonzero bins per (g, j): the realistic sketch shape
    for g in range(G):
        for j in range(J):
            bins = rng.choice(B, 100, replace=False)
            counts[g, j, bins] = rng.integers(1, 50, 100)
    grid = Grid(
        [{"g": str(i), "__comp__": "sketch"} for i in range(G)],
        START, 60_000, J,
        np.full((G, J), np.nan, np.float32),
        hist=counts,
    )
    res = QueryResult(grids=[grid])
    frames = list(result_to_frames(res))
    total = sum(len(f.SerializeToString()) for f in frames)
    dense = G * J * B * 4
    assert total < dense / 4, "sparse cube encoding must beat dense"
    back = frames_to_result(iter(frames))
    np.testing.assert_array_equal(back.grids[0].hist_np(), counts)
    assert back.grids[0].labels == grid.labels


# ---------------------------------------------------------------------------
# wire size: O(groups) components, not O(series) raw rows


def test_partial_wire_size_is_o_groups():
    from filodb_tpu.query.promql import query_range_to_logical_plan
    from filodb_tpu.query.proto_plan import result_to_frames

    ms = TimeSeriesMemStore()
    ms.setup(Dataset("prometheus"), range(4))
    ms.ingest_routed(
        "prometheus",
        machine_metrics(n_series=256, n_samples=60, start_ms=START),
        spread=2,
    )
    eng = QueryEngine(ms, "prometheus", PlannerParams(num_shards=4))
    s, e = START / 1000 + 400, START / 1000 + 580

    def wire_bytes(res):
        return sum(len(f.SerializeToString()) for f in result_to_frames(res))

    # what a partial-pushed peer ships: per-group components
    lp = query_range_to_logical_plan("avg(heap_usage0)", s, e, 60)
    partial = eng.planner.materialize(
        L.PartialAggregate("avg", lp.inner, (), None, None)
    )
    from filodb_tpu.query.exec.plans import PartialReduceExec

    assert isinstance(partial, PartialReduceExec)
    partial_res = eng._run(partial, eng.context())
    comps = {l["__comp__"] for g in partial_res.grids for l in g.labels}
    assert comps == {"sum", "count"}
    # what the raw path ships: every series
    raw_res = eng.query_range("heap_usage0", s, e, 60)
    n_raw = sum(g.n_series for g in raw_res.grids)
    assert n_raw == 256
    pb = wire_bytes(partial_res)
    rb = wire_bytes(raw_res)
    assert pb < rb / 20, f"partials {pb}B must be far under raw {rb}B"


# ---------------------------------------------------------------------------
# end-to-end 2-server parity


class TestTwoServerPartials:
    @pytest.fixture(scope="class")
    def cluster(self):
        from filodb_tpu.api.grpc_exec import serve_grpc
        from filodb_tpu.server import FiloServer

        base = {"dataset": "prometheus", "shards": 8, "grpc_port": 0,
                "query": {"timeout_s": 30}}
        a = FiloServer({**base, "distributed": {"owned_shards": [0, 1, 2, 3]}})
        b = FiloServer({**base, "distributed": {"owned_shards": [4, 5, 6, 7]}})
        a.start(port=0)
        b.start(port=0)
        for srv in (a, b):
            srv.local_engine = QueryEngine(
                srv.memstore, srv.dataset,
                PlannerParams(num_shards=8, deadline_s=30),
            )
        ga, pa = serve_grpc(a.engine, port=0, host="127.0.0.1",
                            local_engine=a.local_engine)
        gb, pb_ = serve_grpc(b.engine, port=0, host="127.0.0.1",
                             local_engine=b.local_engine)
        a.engine.planner.params.peer_endpoints = (f"grpc://127.0.0.1:{pb_}",)
        b.engine.planner.params.peer_endpoints = (f"grpc://127.0.0.1:{pa}",)

        batch = counter_batch(n_series=24, n_samples=120, start_ms=START)
        gauge = machine_metrics(n_series=24, n_samples=120, start_ms=START)
        na = a.memstore.ingest_routed("prometheus", batch, spread=3)
        nb = b.memstore.ingest_routed("prometheus", batch, spread=3)
        a.memstore.ingest_routed("prometheus", gauge, spread=3)
        b.memstore.ingest_routed("prometheus", gauge, spread=3)
        assert na > 0 and nb > 0

        ms = TimeSeriesMemStore()
        ms.setup(Dataset("prometheus"), range(8))
        ms.ingest_routed("prometheus",
                         counter_batch(n_series=24, n_samples=120, start_ms=START),
                         spread=3)
        ms.ingest_routed("prometheus",
                         machine_metrics(n_series=24, n_samples=120, start_ms=START),
                         spread=3)
        oracle = QueryEngine(ms, "prometheus")
        yield a, b, oracle
        ga.stop(grace=0)
        gb.stop(grace=0)
        a.stop()
        b.stop()

    def _grids_map(self, res):
        return {
            tuple(sorted(l.items())): v
            for l, _, v in res.all_series()
        }

    @pytest.mark.parametrize("q", [
        "count(rate(http_requests_total[5m]))",
        "avg(rate(http_requests_total[5m]))",
        "stddev(rate(http_requests_total[5m]))",
        "stdvar(rate(http_requests_total[5m]))",
        "avg by (instance) (heap_usage0)",
        "stddev(heap_usage0)",
    ])
    def test_component_ops_match_single_host(self, cluster, q):
        a, _, oracle = cluster
        s, e = START / 1000 + 400, START / 1000 + 1100
        want = self._grids_map(oracle.query_range(q, s, e, 60))
        got = self._grids_map(a.engine.query_range(q, s, e, 60))
        assert want.keys() == got.keys()
        for k in want:
            w, g = want[k], got[k]
            np.testing.assert_array_equal(np.isnan(w), np.isnan(g), err_msg=q)
            ok = ~np.isnan(w)
            np.testing.assert_allclose(g[ok], w[ok], rtol=1e-4, err_msg=q)

    def test_histogram_sum_rate_matches_single_host(self, cluster):
        """Native-histogram sum across peers: the peer ships per-group
        bucket-cube partials (__comp__=hist riding the hist field), not raw
        bucket series."""
        a, _, oracle = cluster
        from filodb_tpu.testkit import histogram_batch

        for srv in (a.memstore, cluster[1].memstore):
            srv.ingest_routed(
                "prometheus",
                histogram_batch(n_series=12, n_samples=60, start_ms=START),
                spread=3,
            )
        ms_o = oracle.memstore
        ms_o.ingest_routed(
            "prometheus",
            histogram_batch(n_series=12, n_samples=60, start_ms=START),
            spread=3,
        )
        s, e = START / 1000 + 400, START / 1000 + 580
        q = "histogram_quantile(0.9, sum(rate(http_request_latency[5m])))"
        want = self._grids_map(oracle.query_range(q, s, e, 60))
        got = self._grids_map(a.engine.query_range(q, s, e, 60))
        assert want.keys() == got.keys()
        for k in want:
            w, g = want[k], got[k]
            ok = ~np.isnan(w)
            np.testing.assert_allclose(g[ok], w[ok], rtol=1e-4)

    def test_quantile_matches_single_host_within_sketch_error(self, cluster):
        a, _, oracle = cluster
        s, e = START / 1000 + 400, START / 1000 + 1100
        q = "quantile(0.9, heap_usage0)"
        want = self._grids_map(oracle.query_range(q, s, e, 60))
        got = self._grids_map(a.engine.query_range(q, s, e, 60))
        assert want.keys() == got.keys()
        for k in want:
            w, g = want[k], got[k]
            ok = ~np.isnan(w)
            # log-linear sketch: ~2.2% relative bin error at SUB=32
            np.testing.assert_allclose(g[ok], w[ok], rtol=0.05)
