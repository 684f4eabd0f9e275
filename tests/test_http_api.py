"""HTTP API tests (model: reference PrometheusApiRouteSpec)."""

import json
import urllib.request
import urllib.parse

import numpy as np
import pytest

from filodb_tpu.api.http import serve_background
from filodb_tpu.coordinator.planner import QueryEngine
from filodb_tpu.core.schemas import Dataset
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.testkit import counter_batch, machine_metrics

BASE = 1_600_000_000_000
START_S = (BASE + 1_800_000) / 1000
END_S = (BASE + 3_000_000) / 1000


@pytest.fixture(scope="module")
def api():
    ms = TimeSeriesMemStore()
    ms.setup(Dataset("prometheus"), range(4))
    ms.ingest_routed("prometheus", machine_metrics(n_series=10, n_samples=360, start_ms=BASE), spread=2)
    ms.ingest_routed("prometheus", counter_batch(n_series=10, n_samples=360, start_ms=BASE), spread=2)
    engine = QueryEngine(ms, "prometheus")
    srv, port = serve_background(engine)
    yield f"http://127.0.0.1:{port}"
    srv.shutdown()


def get(url):
    with urllib.request.urlopen(url, timeout=60) as r:
        return json.loads(r.read())


def test_query_range_sum_rate(api):
    q = urllib.parse.quote("sum(rate(http_requests_total[5m]))")
    out = get(f"{api}/api/v1/query_range?query={q}&start={START_S}&end={END_S}&step=60")
    assert out["status"] == "success"
    assert out["data"]["resultType"] == "matrix"
    result = out["data"]["result"]
    assert len(result) == 1
    vals = [float(v) for _, v in result[0]["values"]]
    assert all(v > 0 for v in vals)


def test_query_range_metric_name_restored(api):
    q = urllib.parse.quote("heap_usage0")
    out = get(f"{api}/api/v1/query_range?query={q}&start={START_S}&end={END_S}&step=60")
    assert len(out["data"]["result"]) == 10
    assert out["data"]["result"][0]["metric"]["__name__"] == "heap_usage0"


def test_instant_query_vector(api):
    q = urllib.parse.quote("heap_usage0")
    out = get(f"{api}/api/v1/query?query={q}&time={END_S}")
    assert out["data"]["resultType"] == "vector"
    assert len(out["data"]["result"]) == 10
    for item in out["data"]["result"]:
        t, v = item["value"]
        assert t == END_S
        float(v)


def test_instant_scalar(api):
    out = get(f"{api}/api/v1/query?query=42&time={END_S}")
    assert out["data"]["resultType"] == "scalar"
    assert float(out["data"]["result"][1]) == 42.0


def test_labels(api):
    out = get(f"{api}/api/v1/labels")
    assert "__name__" in out["data"] and "instance" in out["data"]


def test_label_values(api):
    out = get(f"{api}/api/v1/label/__name__/values")
    assert "heap_usage0" in out["data"]
    assert "http_requests_total" in out["data"]


def test_series(api):
    q = urllib.parse.quote('heap_usage0{instance="host-1"}')
    out = get(f"{api}/api/v1/series?match[]={q}")
    assert len(out["data"]) == 1
    assert out["data"][0]["__name__"] == "heap_usage0"


def test_bad_query_is_400(api):
    q = urllib.parse.quote("sum(")
    try:
        get(f"{api}/api/v1/query_range?query={q}&start=1&end=2&step=1")
        assert False, "expected 400"
    except urllib.error.HTTPError as e:
        assert e.code == 400
        body = json.loads(e.read())
        assert body["status"] == "error"


def test_health(api):
    out = get(f"{api}/admin/health")
    assert out["status"] == "healthy"
    # the server states where its kernels run, as jax reports it
    import jax

    assert out["platform"] == jax.devices()[0].platform == "cpu"
    assert out["device_kind"] == jax.devices()[0].device_kind
    assert out["device_count"] == len(jax.devices())


def test_ingest_endpoint(api):
    lines = "\n".join(
        json.dumps({"tags": {"__name__": "pushed_metric", "src": "test"}, "ts_ms": BASE + i * 10_000, "value": float(i)})
        for i in range(10)
    )
    req = urllib.request.Request(f"{api}/ingest", data=lines.encode(), method="POST")
    with urllib.request.urlopen(req, timeout=30) as r:
        out = json.loads(r.read())
    assert out["data"]["ingested"] == 10
    q = urllib.parse.quote("pushed_metric")
    res = get(f"{api}/api/v1/query?query={q}&time={(BASE + 100_000) / 1000}")
    assert len(res["data"]["result"]) == 1


def test_ingest_prom_text(api):
    text = """# TYPE pushed_counter counter
pushed_counter{src="push"} 100 1600000000000
pushed_counter{src="push"} 110 1600000015000
pushed_gauge 3.5 1600000000000
"""
    req = urllib.request.Request(f"{api}/ingest/prom", data=text.encode(), method="POST")
    with urllib.request.urlopen(req, timeout=30) as r:
        out = json.loads(r.read())
    assert out["data"]["ingested"] == 3
    q = urllib.parse.quote("pushed_counter")
    res = get(f"{api}/api/v1/query?query={q}&time={1600000100}")
    assert len(res["data"]["result"]) == 1


def test_ingest_influx_http(api):
    lines = "httpm,host=a value=1.5 1600000000000000000\nhttpm,host=b value=2.5 1600000000000000000\n"
    req = urllib.request.Request(f"{api}/ingest/influx", data=lines.encode(), method="POST")
    with urllib.request.urlopen(req, timeout=30) as r:
        out = json.loads(r.read())
    assert out["data"]["ingested"] == 2
    q = urllib.parse.quote("httpm")
    res = get(f"{api}/api/v1/query?query={q}&time={1600000100}")
    assert len(res["data"]["result"]) == 2


class TestPromJsonFormat:
    def test_value_formatting(self):
        from filodb_tpu.api.promjson import _fmt

        assert _fmt(float("nan")) == "NaN"
        assert _fmt(float("inf")) == "+Inf"
        assert _fmt(float("-inf")) == "-Inf"
        assert _fmt(1.5) == "1.5"
        assert _fmt(2.0) == "2.0"

    def test_matrix_rendering_skips_nan_and_restores_name(self):
        from filodb_tpu.api.promjson import render_matrix
        from filodb_tpu.query.rangevector import Grid, QueryResult

        vals = np.array([[1.0, np.nan, 3.0]], dtype=np.float32)
        g = Grid([{"_metric_": "m", "a": "b"}], 1_600_000_000_000, 60_000, 3, vals)
        out = render_matrix(QueryResult(grids=[g]))
        assert out["resultType"] == "matrix"
        series = out["result"][0]
        assert series["metric"] == {"__name__": "m", "a": "b"}
        assert [t for t, _ in series["values"]] == [1_600_000_000.0, 1_600_000_120.0]


def test_label_values_limit_param(api):
    out = get(f"{api}/api/v1/label/instance/values?limit=3")
    assert len(out["data"]) == 3


class TestRenderShapes:
    def test_vector_render_uses_last_nonnan(self):
        from filodb_tpu.api.promjson import render_vector
        from filodb_tpu.query.rangevector import Grid, QueryResult

        vals = np.array([[1.0, 7.0, np.nan]], dtype=np.float32)
        g = Grid([{"_metric_": "m"}], 1_600_000_000_000, 60_000, 3, vals)
        out = render_vector(QueryResult(grids=[g]), 1_600_000_180.0)
        assert out["result"][0]["value"] == [1_600_000_180.0, "7.0"]

    def test_scalar_render(self):
        from filodb_tpu.api.promjson import render_scalar
        from filodb_tpu.query.rangevector import QueryResult, ScalarResult

        res = QueryResult(scalar=ScalarResult(0, 1, 3, np.array([1.0, 2.0, 3.5])))
        out = render_scalar(res, 42.0)
        assert out == {"resultType": "scalar", "result": [42.0, "3.5"]}


def test_duration_step_and_rfc3339_times(api):
    q = urllib.parse.quote("heap_usage0")
    # RFC3339 timestamps (Z form; '+00:00' would need URL-encoding) + "1m" step
    start = "2020-09-13T12:36:40Z"  # 1600000600
    end = "2020-09-13T12:53:20Z"    # 1600001600
    out = get(f"{api}/api/v1/query_range?query={q}&start={start}&end={end}&step=1m")
    assert out["status"] == "success"
    assert len(out["data"]["result"]) == 10
    times = [t for t, _ in out["data"]["result"][0]["values"]]
    assert times[1] - times[0] == 60.0


def test_scalar_arithmetic_instant(api):
    out = get(f"{api}/api/v1/query?query={urllib.parse.quote('2*3+1')}&time=1000")
    assert out["data"]["resultType"] == "scalar"
    assert float(out["data"]["result"][1]) == 7.0


def test_scalar_range_renders_matrix(api):
    out = get(f"{api}/api/v1/query_range?query=5&start=1000&end=1120&step=60")
    res = out["data"]["result"]
    assert out["data"]["resultType"] == "matrix"
    assert len(res) == 1 and len(res[0]["values"]) == 3
    assert all(float(v) == 5.0 for _, v in res[0]["values"])


def test_overload_returns_503():
    """Saturated bounded scheduler -> 503 (reference: query-sched rejection)."""
    import threading
    import urllib.error

    from filodb_tpu.coordinator.planner import PlannerParams
    from filodb_tpu.coordinator.scheduler import QueryScheduler

    ms = TimeSeriesMemStore()
    ms.setup(Dataset("prometheus"), [0])
    ms.ingest("prometheus", 0, machine_metrics(n_series=2, n_samples=60, start_ms=BASE))
    sched = QueryScheduler(parallelism=1, max_queued=0)
    engine = QueryEngine(ms, "prometheus", PlannerParams(scheduler=sched))
    srv, port = serve_background(engine)
    try:
        release = threading.Event()
        # occupy the single slot directly through the scheduler
        t = threading.Thread(target=lambda: sched.run(lambda: release.wait(10), deadline_s=30))
        t.start()
        import time as _t

        _t.sleep(0.1)
        q = urllib.parse.quote("heap_usage0")
        url = f"http://127.0.0.1:{port}/api/v1/query_range?query={q}&start={START_S}&end={END_S}&step=60"
        with pytest.raises(urllib.error.HTTPError) as ei:
            get(url)
        assert ei.value.code == 503
        release.set()
        t.join()
        # slot free again: the same query now succeeds
        out = get(url)
        assert out["status"] == "success"
    finally:
        srv.shutdown()


def test_metadata_from_schemas(api):
    out = get(f"{api}/api/v1/metadata")
    data = out["data"]
    assert data["heap_usage0"][0]["type"] == "gauge"
    assert data["http_requests_total"][0]["type"] == "counter"


def test_exemplars_roundtrip():
    """OpenMetrics exemplars: ingested alongside samples via /ingest/prom,
    served by /api/v1/query_exemplars (Prometheus response shape)."""
    import urllib.request

    ms = TimeSeriesMemStore()
    ms.setup(Dataset("prometheus"), range(4))
    engine = QueryEngine(ms, "prometheus")
    srv, port = serve_background(engine)
    try:
        body = (
            "# TYPE http_requests_total counter\n"
            'http_requests_total{job="api"} 42 1600000000000 '
            '# {trace_id="abc123"} 0.67 1600000000.0\n'
            'http_requests_total{job="api"} 99 1600000060000\n'
        ).encode()
        req = urllib.request.Request(f"http://127.0.0.1:{port}/ingest/prom", data=body)
        with urllib.request.urlopen(req, timeout=30) as r:
            assert json.loads(r.read())["data"]["ingested"] == 2
        q = urllib.parse.quote('http_requests_total{job="api"}')
        out = get(
            f"http://127.0.0.1:{port}/api/v1/query_exemplars?query={q}"
            f"&start=1599999000&end=1600001000"
        )
        assert out["status"] == "success"
        assert len(out["data"]) == 1
        ex = out["data"][0]["exemplars"][0]
        assert ex["labels"] == {"trace_id": "abc123"}
        assert float(ex["value"]) == 0.67
        assert out["data"][0]["seriesLabels"]["job"] == "api"
    finally:
        srv.shutdown()


def test_bearer_auth_and_gzip():
    """Remote-exec hardening: optional bearer auth (401 without it; health
    stays open) and gzip responses for big payloads."""
    import gzip
    import urllib.error

    ms = TimeSeriesMemStore()
    ms.setup(Dataset("prometheus"), [0])
    ms.ingest("prometheus", 0, machine_metrics(n_series=30, n_samples=120, start_ms=BASE))
    engine = QueryEngine(ms, "prometheus")
    srv, port = serve_background(engine, auth_token="s3cret")
    try:
        base_url = f"http://127.0.0.1:{port}"
        # health open, api closed
        assert get(f"{base_url}/admin/health")["status"] == "healthy"
        with pytest.raises(urllib.error.HTTPError) as ei:
            get(f"{base_url}/api/v1/labels")
        assert ei.value.code == 401
        # with token + gzip accepted: compressed matrix response
        q = urllib.parse.quote("heap_usage0")
        req = urllib.request.Request(
            f"{base_url}/api/v1/query_range?query={q}&start={(BASE+400_000)/1000}"
            f"&end={(BASE+1_100_000)/1000}&step=60",
            headers={"Authorization": "Bearer s3cret", "Accept-Encoding": "gzip"},
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            raw = r.read()
            assert r.headers.get("Content-Encoding") == "gzip"
            out = json.loads(gzip.decompress(raw))
        assert len(out["data"]["result"]) == 30
    finally:
        srv.shutdown()


def test_remote_exec_retries_then_succeeds():
    """PromQlRemoteExec retries transient failures with backoff."""
    from filodb_tpu.coordinator.planners import PromQlRemoteExec

    ms = TimeSeriesMemStore()
    ms.setup(Dataset("prometheus"), [0])
    ms.ingest("prometheus", 0, machine_metrics(n_series=3, n_samples=120, start_ms=BASE))
    engine = QueryEngine(ms, "prometheus")
    srv, port = serve_background(engine)
    try:
        ep = PromQlRemoteExec(
            f"http://127.0.0.1:{port}", "heap_usage0",
            BASE + 400_000, BASE + 1_100_000, 60_000,
        )
        calls = {"n": 0}
        # exercise the retry loop itself (first attempt raises inside _fetch)
        import urllib.error
        real_urlopen = urllib.request.urlopen

        def fail_once(*a, **kw):
            if calls["n"] == 0:
                calls["n"] += 1
                raise urllib.error.URLError("transient")
            return real_urlopen(*a, **kw)

        urllib.request.urlopen = fail_once
        try:
            res = ep.execute(engine.context())
        finally:
            urllib.request.urlopen = real_urlopen
        assert sum(g.n_series for g in res.grids) == 3
        assert calls["n"] == 1  # one failure, then success
    finally:
        srv.shutdown()


def test_auth_401_drains_post_body_keepalive():
    """Review regression: a 401 on a keep-alive connection must drain the
    POST body, or the next request on the socket desyncs."""
    import http.client

    ms = TimeSeriesMemStore()
    ms.setup(Dataset("prometheus"), [0])
    engine = QueryEngine(ms, "prometheus")
    srv, port = serve_background(engine, auth_token="tok")
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        body = b"x" * 10_000
        conn.request("POST", "/ingest", body=body)  # no token
        r1 = conn.getresponse()
        assert r1.status == 401
        r1.read()
        # SAME socket: a correctly-drained connection serves the next request
        conn.request("GET", "/admin/health")
        r2 = conn.getresponse()
        assert r2.status == 200
        conn.close()
    finally:
        srv.shutdown()
