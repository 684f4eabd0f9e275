"""Multi-host runtime tests (reference: multi-jvm specs run N JVMs on one
box — coordinator/src/multi-jvm. Here: N OS processes join one JAX
distributed coordination service on localhost, CPU backend)."""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

from filodb_tpu.parallel.multihost import shards_for_process


class TestShardOwnership:
    def test_contiguous_split(self):
        assert shards_for_process(8, 2, 0) == [0, 1, 2, 3]
        assert shards_for_process(8, 2, 1) == [4, 5, 6, 7]

    def test_uneven_split(self):
        assert shards_for_process(7, 2, 0) == [0, 1, 2, 3]
        assert shards_for_process(7, 2, 1) == [4, 5, 6]

    def test_single_process_owns_all(self):
        assert shards_for_process(4, 1, 0) == [0, 1, 2, 3]


WORKER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    from filodb_tpu.parallel.multihost import init_distributed, make_multihost_mesh, shards_for_process
    ok = init_distributed(sys.argv[1], 2, int(sys.argv[2]))
    assert ok
    assert jax.process_count() == 2
    assert len(jax.devices()) == 4  # 2 procs x 2 local cpu devices
    mesh = make_multihost_mesh()
    assert mesh.devices.size == 4
    # one global psum across both processes
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    import jax.numpy as jnp
    x = jax.device_put(
        np.ones((4, 8), np.float32),
        NamedSharding(mesh, P("shard", None)),
    )
    out = jax.jit(
        jax.shard_map(
            lambda a: jax.lax.psum(a.sum(), "shard"),
            mesh=mesh, in_specs=P("shard", None), out_specs=P()
        )
    )(x)
    assert float(np.asarray(out)) == 32.0
    assert shards_for_process(8) in ([0,1,2,3],[4,5,6,7])
    print("MULTIHOST_OK", jax.process_index())
""")


def test_two_process_psum():
    """Two real processes, one coordination service, one global mesh, one
    cross-process psum. Skips when the sandbox forbids the coordination
    service's TCP listener."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"
    env = {k: v for k, v in os.environ.items() if not k.startswith(("JAX_", "XLA_"))}
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", WORKER, coord, str(i)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate()
            outs.append((p.returncode, out))
    finally:
        for p in procs:  # neither outlives the test, whatever ended it
            p.kill()
    for rc, out in outs:
        if rc != 0 and ("UNAVAILABLE" in out or "Failed to connect" in out or "barrier" in out.lower()):
            pytest.skip(f"sandbox blocks the coordination service: {out[-300:]}")
        if rc != 0 and "Multiprocess computations aren't implemented" in out:
            # capability probe, not an env failure: this jaxlib's CPU
            # backend has no multiprocess collectives (cross-process psum
            # needs a real TPU/GPU backend or a newer CPU collectives
            # build) — the workers DID join the coordination service and
            # build the global mesh before the psum dispatch refused
            pytest.skip(
                "jax CPU backend lacks multiprocess collectives "
                "(XlaRuntimeError: 'Multiprocess computations aren't "
                "implemented on the CPU backend') — needs TPU/GPU or a "
                "CPU build with cross-process collectives"
            )
        assert rc == 0, out[-2000:]
        assert "MULTIHOST_OK" in out


class TestMultiHostServing:
    """Two FiloServer processes (in-process here), each owning half the
    shards, scattering queries to each other over HTTP (the reference's
    cross-node scatter-gather; multi-jvm IngestionAndRecoverySpec shape)."""

    def _start_pair(self):
        from filodb_tpu.server import FiloServer
        from filodb_tpu.testkit import counter_batch

        base_cfg = {"dataset": "prometheus", "shards": 8, "query": {"timeout_s": 30}}
        a = FiloServer({**base_cfg, "distributed": {"owned_shards": [0, 1, 2, 3]}})
        b = FiloServer({**base_cfg, "distributed": {"owned_shards": [4, 5, 6, 7]}})
        pa = a.start(port=0)
        pb = b.start(port=0)
        # wire peers post-start (ports are dynamic in tests)
        from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine

        def add_peer(srv, peer_port):
            srv.engine.planner.params.peer_endpoints = (f"http://127.0.0.1:{peer_port}",)

        add_peer(a, pb)
        add_peer(b, pa)
        # local engines for the X-FiloDB-Local path
        for srv in (a, b):
            srv.local_engine = QueryEngine(
                srv.memstore, srv.dataset,
                PlannerParams(num_shards=8, deadline_s=30),
            )
            srv._http.RequestHandlerClass.local_engine = srv.local_engine
        batch = counter_batch(n_series=24, n_samples=120, start_ms=1_600_000_000_000)
        na = a.memstore.ingest_routed("prometheus", batch, spread=3)
        nb = b.memstore.ingest_routed("prometheus", batch, spread=3)
        return a, b, pa, pb, na, nb

    def test_sharded_ingest_and_scattered_query(self):
        import json as _json
        import urllib.parse
        import urllib.request

        import numpy as np

        from filodb_tpu.coordinator.planner import QueryEngine
        from filodb_tpu.core.schemas import Dataset
        from filodb_tpu.memstore.memstore import TimeSeriesMemStore
        from filodb_tpu.testkit import counter_batch

        a = b = None
        try:
            a, b, pa, pb, na, nb = self._start_pair()
            # ingest routing split the batch across BOTH hosts, no overlap
            total_rows = 24 * 120
            assert na + nb == total_rows and na > 0 and nb > 0

            # baseline: one single-host store with everything
            ms = TimeSeriesMemStore()
            ms.setup(Dataset("prometheus"), range(8))
            ms.ingest_routed(
                "prometheus",
                counter_batch(n_series=24, n_samples=120, start_ms=1_600_000_000_000),
                spread=3,
            )
            eng = QueryEngine(ms, "prometheus")
            start_s, end_s = 1_600_000_400.0, 1_600_001_100.0
            want = eng.query_range(
                "sum(rate(http_requests_total[5m]))", start_s, end_s, 60
            ).grids[0].values_np()

            q = urllib.parse.quote("sum(rate(http_requests_total[5m]))")
            url = (f"http://127.0.0.1:{pa}/api/v1/query_range?query={q}"
                   f"&start={start_s}&end={end_s}&step=60")
            with urllib.request.urlopen(url) as r:
                out = _json.loads(r.read())
            assert out["status"] == "success"
            vals = out["data"]["result"][0]["values"]
            got = np.array([float(v) for _, v in vals])
            np.testing.assert_allclose(got, want[0][: len(got)], rtol=1e-4)

            # plain selector through host B returns ALL 24 series
            q2 = urllib.parse.quote("http_requests_total")
            url2 = (f"http://127.0.0.1:{pb}/api/v1/query_range?query={q2}"
                    f"&start={start_s}&end={end_s}&step=60")
            with urllib.request.urlopen(url2) as r:
                out2 = _json.loads(r.read())
            assert len(out2["data"]["result"]) == 24
        finally:
            for srv in (a, b):
                if srv is not None:
                    srv.stop()


class TestMultiHostMetadataAndPushdown:
    def test_metadata_scatter_and_aggregate_pushdown(self):
        import json as _json
        import urllib.parse
        import urllib.request

        from filodb_tpu.query.promql import query_range_to_logical_plan

        pair = TestMultiHostServing()
        a = b = None
        try:
            a, b, pa, pb, na, nb = pair._start_pair()
            # label values scatter: host A must see instances living on B
            url = f"http://127.0.0.1:{pa}/api/v1/label/instance/values"
            with urllib.request.urlopen(url) as r:
                vals = _json.loads(r.read())["data"]
            assert len(vals) == 24  # every series' instance, both hosts
            # series scatter
            m = urllib.parse.quote("http_requests_total")
            url2 = f"http://127.0.0.1:{pb}/api/v1/series?match[]={m}"
            with urllib.request.urlopen(url2) as r:
                series = _json.loads(r.read())["data"]
            assert len(series) == 24

            # aggregate pushdown: the peer leaf ships sum by, not the selector
            plan = query_range_to_logical_plan(
                "sum(rate(http_requests_total[5m]))", 1_600_000_400, 1_600_001_100, 60)
            ep = a.engine.planner.materialize(plan)
            tree = ep.print_tree()
            assert "PromQlRemoteExec" in tree
            assert "promql=sum(rate(http_requests_total[5m]))" in tree
        finally:
            for srv in (a, b):
                if srv is not None:
                    srv.stop()
