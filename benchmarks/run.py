"""Microbenchmark suite (reference jmh/src/main/scala/filodb.jmh/ — the 23
JMH benchmarks, SURVEY.md §6; principal ones mirrored here). Each prints one
JSON line; ``python -m benchmarks.run`` runs all and emits a JSON array.

Unlike bench.py (one end-to-end query workload per run), these
cover the component workloads: encoding, ingestion, index lookups, gateway
parse, planner materialization, query QPS in-memory and under ingest,
histogram queries.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# make `python benchmarks/run.py` work like `python -m benchmarks.run`:
# direct file invocation puts benchmarks/ (not the repo root) on sys.path
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def _bench(fn, n_iters=5, warmup=1):
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(n_iters):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


BASE = 1_600_000_000_000
RESULTS = []


def report(name, value, unit):
    rec = {"metric": name, "value": round(value, 4), "unit": unit}
    RESULTS.append(rec)
    print(json.dumps(rec), flush=True)


def bench_encoding():
    """reference EncodingBenchmark / DoubleVectorSimdBenchmark."""
    from filodb_tpu.core import encodings as E

    rng = np.random.default_rng(0)
    ts = BASE + np.arange(100_000, dtype=np.int64) * 10_000 + rng.integers(-50, 50, 100_000)
    vals = 50 + rng.standard_normal(100_000)
    dt = _bench(lambda: E.encode_int64(ts))
    report("encode_delta_delta_100k", 100_000 / dt / 1e6, "Msamples/s")
    dt = _bench(lambda: E.encode_double(vals))
    report("encode_xor_double_100k", 100_000 / dt / 1e6, "Msamples/s")
    enc = E.encode_double(vals)
    dt = _bench(lambda: E.decode(enc))
    report("decode_xor_double_100k", 100_000 / dt / 1e6, "Msamples/s")
    report("xor_double_bytes_per_sample", enc.nbytes / 100_000, "bytes")


def bench_nan_sum():
    from filodb_tpu import native

    rng = np.random.default_rng(1)
    v = rng.standard_normal(1_000_000)
    v[rng.integers(0, len(v), 1000)] = np.nan
    dt = _bench(lambda: native.nan_sum(v))
    report("native_nan_sum_1m", 1e6 / dt / 1e9, "Gsamples/s")


def bench_ingestion():
    """reference IngestionBenchmark: records/sec into a shard."""
    from filodb_tpu.core.schemas import Dataset
    from filodb_tpu.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.testkit import machine_metrics

    batch = machine_metrics(n_series=1000, n_samples=100, start_ms=BASE)

    def run():
        ms = TimeSeriesMemStore()
        ms.setup(Dataset("b"), [0])
        ms.ingest("b", 0, batch)

    dt = _bench(run, n_iters=3)
    report("ingest_100k_rows", 100_000 / dt / 1e6, "Mrows/s")


def bench_index():
    """reference PartKeyIndexBenchmark: lookups/sec. PartKeyIndex is the
    vectorized posting-bitmap index since ISSUE 14 — these numbers measure
    the new path (the pre-bitmap set-arithmetic numbers live on in
    BENCH_LOCAL history and the retained SetBasedPartKeyIndex oracle)."""
    from filodb_tpu.core.filters import equals, regex
    from filodb_tpu.memstore.index import PartKeyIndex

    idx = PartKeyIndex()
    for i in range(100_000):
        idx.add_partkey(i, {
            "_metric_": f"metric_{i % 100}", "host": f"h{i % 1000}", "dc": f"dc{i % 10}",
        }, 0)
    f_eq = [equals("_metric_", "metric_5"), equals("dc", "dc3")]
    dt = _bench(lambda: [idx.part_ids_from_filters(f_eq, 0, 2**62) for _ in range(100)])
    report("index_equality_lookups", 100 / dt, "lookups/s")
    f_re = [regex("host", "h1.*")]
    dt = _bench(lambda: [idx.part_ids_from_filters(f_re, 0, 2**62) for _ in range(10)])
    report("index_regex_lookups", 10 / dt, "lookups/s")


def bench_index_1m():
    """1M-partkey index at the reference PartKeyIndexBenchmark scale:
    equality vs range-aware regex vs label-values on the NATIVE backend
    (tantivy analog). Bar (VERDICT r4 item 8): prefix regex within ~4x of
    equality at 1M partkeys. FILODB_BENCH_INDEX_SERIES overrides the scale."""
    import os

    from filodb_tpu.core.filters import equals, regex
    from filodb_tpu.memstore.index_native import (
        NativePartKeyIndex,
        native_index_available,
    )

    if not native_index_available():
        return
    n = int(os.environ.get("FILODB_BENCH_INDEX_SERIES", 1_000_000))
    idx = NativePartKeyIndex()
    t0 = time.perf_counter()
    for i in range(n):
        idx.add_partkey(i, {
            "_metric_": f"metric_{i % 1000}", "host": f"h{i % 10_000}",
            "dc": f"dc{i % 10}", "_ws_": "demo", "_ns_": f"ns{i % 20}",
        }, 0)
    report(f"index_build_{n // 1000}k", n / (time.perf_counter() - t0), "keys/s")
    tag = f"{n // 1000}k"
    # ~n/1000 result ids for every probe below, so rates compare the LOOKUP
    # machinery, not differing result sizes
    f_eq = [equals("_metric_", "metric_5")]
    dt = _bench(lambda: [idx.part_ids_from_filters(f_eq, 0, 2**62) for _ in range(50)])
    eq_rate = 50 / dt
    report(f"index_eq_lookups_{tag}", eq_rate, "lookups/s")
    # prefix regex: h123 + h1230..h1239 of 10k host values (~= eq result size)
    f_pre = [regex("host", "h123.*")]
    dt = _bench(lambda: [idx.part_ids_from_filters(f_pre, 0, 2**62) for _ in range(50)])
    pre_rate = 50 / dt
    report(f"index_prefix_regex_lookups_{tag}", pre_rate, "lookups/s")
    report("index_prefix_regex_vs_eq", eq_rate / pre_rate, "x")
    # general anchored regex with a literal prefix + tail match
    f_re = [regex("host", "h12[0-9]?")]
    dt = _bench(lambda: [idx.part_ids_from_filters(f_re, 0, 2**62) for _ in range(50)])
    report(f"index_regex_lookups_{tag}", 50 / dt, "lookups/s")
    dt = _bench(lambda: [idx.label_values([], "_metric_", 0, 2**62) for _ in range(20)])
    report(f"index_label_values_{tag}", 20 / dt, "lookups/s")


def bench_index_bitmap_1m():
    """1M-partkey BITMAP index (the default backend, memstore/postings.py):
    build rate + the probe set bench_index_1m runs on the native backend,
    plus the warm Grafana-storm regex pool the match cache serves
    (doc/perf.md 'Vectorized part-key index'). FILODB_BENCH_INDEX_SERIES
    overrides the scale."""
    import os

    from filodb_tpu.core.filters import equals, regex
    from filodb_tpu.memstore.index import PartKeyIndex

    n = int(os.environ.get("FILODB_BENCH_INDEX_SERIES", 1_000_000))
    idx = PartKeyIndex()
    t0 = time.perf_counter()
    for i in range(n):
        idx.add_partkey(i, {
            "_metric_": f"metric_{i % 1000}", "host": f"h{i % 10_000}",
            "dc": f"dc{i % 10}", "_ws_": "demo", "_ns_": f"ns{i % 20}",
        }, 0)
    tag = f"{n // 1000}k"
    report(f"index_bitmap_build_{tag}", n / (time.perf_counter() - t0), "keys/s")
    f_eq = [equals("_metric_", "metric_5")]
    dt = _bench(lambda: [idx.part_ids_from_filters(f_eq, 0, 2**62) for _ in range(50)])
    report(f"index_bitmap_eq_lookups_{tag}", 50 / dt, "lookups/s")
    f_pre = [regex("host", "h123.*")]
    dt = _bench(lambda: [idx.part_ids_from_filters(f_pre, 0, 2**62) for _ in range(50)])
    report(f"index_bitmap_prefix_regex_lookups_{tag}", 50 / dt, "lookups/s")
    f_re = [regex("host", "h12[0-9]?")]
    dt = _bench(lambda: [idx.part_ids_from_filters(f_re, 0, 2**62) for _ in range(50)])
    report(f"index_bitmap_regex_lookups_{tag}", 50 / dt, "lookups/s")
    # warm 64-pattern pool: the repeated-selector storm the per-label match
    # cache exists for (each pattern still pays OR + extraction per call)
    pool = [[regex("host", f"h1{i:02d}[0-9]?")] for i in range(64)]
    for f in pool:
        idx.part_ids_from_filters(f, 0, 2**62)
    k = [0]

    def storm():
        for _ in range(50):
            idx.part_ids_from_filters(pool[k[0] % 64], 0, 2**62)
            k[0] += 1

    dt = _bench(storm)
    report(f"index_bitmap_regex_pool_lookups_{tag}", 50 / dt, "lookups/s")
    dt = _bench(lambda: [idx.label_values([], "_metric_", 0, 2**62) for _ in range(20)])
    report(f"index_bitmap_label_values_{tag}", 20 / dt, "lookups/s")


def bench_gateway_parse():
    """reference GatewayBenchmark: line-protocol msgs/sec."""
    from filodb_tpu.gateway.parsers import parse_influx_line, parse_prom_text

    lines = [
        f"cpu,host=h{i},dc=dc{i % 3} value={i}.5 1600000000000000000" for i in range(10_000)
    ]
    dt = _bench(lambda: [list(parse_influx_line(l)) for l in lines])
    report("influx_parse", len(lines) / dt / 1e3, "kmsgs/s")
    text = "\n".join(f'm{i}{{h="x{i}"}} {i} 1600000000000' for i in range(10_000))
    dt = _bench(lambda: list(parse_prom_text(text)))
    report("prom_text_parse", 10_000 / dt / 1e3, "kmsgs/s")
    # full ingest-side batch build: native scanner + key memo vs regex path
    from filodb_tpu.gateway.parsers import prom_text_to_batches_and_exemplars

    dt = _bench(lambda: prom_text_to_batches_and_exemplars(text, 0))
    report("prom_text_to_batches", 10_000 / dt / 1e3, "kmsgs/s")


def bench_planner():
    """reference PlannerBenchmark: plans/sec."""
    from filodb_tpu.coordinator.planner import SingleClusterPlanner
    from filodb_tpu.core.schemas import Dataset
    from filodb_tpu.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.query.promql import query_range_to_logical_plan

    ms = TimeSeriesMemStore()
    ms.setup(Dataset("b"), range(8))
    planner = SingleClusterPlanner(ms, "b")
    q = 'sum by (job) (rate(http_requests_total{env="prod",dc=~"us.*"}[5m]))'

    def run():
        for _ in range(100):
            plan = query_range_to_logical_plan(q, 1000, 5000, 15)
            planner.materialize(plan)

    dt = _bench(run)
    report("parse_and_plan", 100 / dt, "plans/s")


def bench_query_in_memory():
    """reference QueryInMemoryBenchmark: 8 shards, 100 series x 720 samples
    (2h @ 10s), sum(rate) range queries."""
    from filodb_tpu.coordinator.planner import QueryEngine
    from filodb_tpu.core.schemas import Dataset
    from filodb_tpu.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.testkit import counter_batch, machine_metrics

    ms = TimeSeriesMemStore()
    ms.setup(Dataset("prometheus"), range(8))
    ms.ingest_routed("prometheus", counter_batch(n_series=100, n_samples=720, start_ms=BASE), spread=3)
    ms.ingest_routed("prometheus", machine_metrics(n_series=100, n_samples=720, start_ms=BASE), spread=3)
    engine = QueryEngine(ms, "prometheus")
    start, end = (BASE + 600_000) / 1000, (BASE + 7_000_000) / 1000

    def q1():
        engine.query_range("sum(rate(http_requests_total[5m]))", start, end, 60)

    q1()  # warm staging cache + jit
    dt = _bench(q1, n_iters=10)
    report("query_sum_rate_100series_qps", 1 / dt, "qps")

    def q2():
        engine.query_range("min_over_time(heap_usage0[5m])", start, end, 60)

    q2()
    dt = _bench(q2, n_iters=10)
    report("query_min_over_time_qps", 1 / dt, "qps")


def bench_query_hicard():
    """reference QueryHiCardInMemoryBenchmark: 8000 series, 2000 queried."""
    from filodb_tpu.coordinator.planner import QueryEngine
    from filodb_tpu.core.schemas import Dataset
    from filodb_tpu.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.testkit import counter_batch

    ms = TimeSeriesMemStore()
    ms.setup(Dataset("prometheus"), range(8))
    for ns in range(4):
        ms.ingest_routed(
            "prometheus",
            counter_batch(n_series=2000, n_samples=120, start_ms=BASE, ns=f"App-{ns}"),
            spread=3,
        )
    engine = QueryEngine(ms, "prometheus")
    start, end = (BASE + 400_000) / 1000, (BASE + 1_100_000) / 1000

    def q():
        engine.query_range('sum(rate(http_requests_total{_ns_="App-1"}[5m]))', start, end, 60)

    q()
    dt = _bench(q, n_iters=5)
    report("query_hicard_2000_of_8000_qps", 1 / dt, "qps")


def bench_histogram_query():
    """reference HistogramQueryBenchmark: sum(rate) + quantile over native
    histograms."""
    from filodb_tpu.coordinator.planner import QueryEngine
    from filodb_tpu.core.schemas import Dataset
    from filodb_tpu.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.testkit import histogram_batch

    ms = TimeSeriesMemStore()
    ms.setup(Dataset("prometheus"), range(4))
    ms.ingest_routed("prometheus", histogram_batch(n_series=100, n_samples=240, start_ms=BASE), spread=2)
    engine = QueryEngine(ms, "prometheus")
    start, end = (BASE + 400_000) / 1000, (BASE + 2_200_000) / 1000

    def q():
        engine.query_range(
            "histogram_quantile(0.9, sum(rate(http_request_latency[5m])))", start, end, 60
        )

    q()
    dt = _bench(q, n_iters=5)
    report("query_hist_quantile_qps", 1 / dt, "qps")


def bench_jitter_query():
    """Regular vs jittered scrape grids on the engine fast paths (VERDICT r2
    weak #2: the irregular-timestamp gap). Reference semantics contract:
    PeriodicSamplesMapper.scala:256 window iterators over arbitrary ts."""
    import jax

    from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine
    from filodb_tpu.core.records import SeriesBatch
    from filodb_tpu.core.schemas import Dataset, METRIC_TAG, PROM_COUNTER, shard_for
    from filodb_tpu.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.parallel.mesh import make_mesh

    import os

    rng = np.random.default_rng(5)
    n = 720
    n_series = int(os.environ.get("FILODB_BENCH_JITTER_SERIES", 4000))
    nominal = BASE + np.arange(n, dtype=np.int64) * 10_000
    start, end = (BASE + 600_000) / 1000, (BASE + 7_000_000) / 1000

    def build(jitter, hole_frac=0.0):
        ms = TimeSeriesMemStore()
        ms.setup(Dataset("prometheus"), range(8))
        incr = rng.uniform(0, 10, size=(n_series, n))
        vals = np.cumsum(incr, axis=1) + 1e9
        for i in range(n_series):
            tags = {METRIC_TAG: "rq_total", "_ws_": "w", "_ns_": "n",
                    "inst": f"h{i}"}
            shard = shard_for(tags, spread=3, num_shards=8)
            ts = nominal
            v = vals[i]
            if jitter:
                ts = nominal + np.rint(
                    rng.uniform(-jitter, jitter, n) * 10_000).astype(np.int64)
            if hole_frac:
                keep = np.ones(n, bool)
                drop = rng.choice(np.arange(1, n - 1),
                                  size=max(1, int(hole_frac * n)),
                                  replace=False)
                keep[drop] = False
                ts, v = ts[keep], v[keep]
            ms.shard("prometheus", shard).ingest_series(
                SeriesBatch(PROM_COUNTER, tags, ts, {"count": v})
            )
        return QueryEngine(ms, "prometheus",
                           PlannerParams(mesh=make_mesh(jax.devices()[:1])))

    results = {}
    for label, jitter, holes in (
        ("regular", 0.0, 0.0), ("jitter1pct", 0.01, 0.0),
        ("jitter5pct", 0.05, 0.0), ("jitter20pct", 0.2, 0.0),
        ("jitter5pct_holes0.5pct", 0.05, 0.005),
    ):
        engine = build(jitter, holes)

        def q():
            r = engine.query_range("sum(rate(rq_total[5m]))", start, end, 60)
            np.asarray(r.grids[0].values_np())

        q()  # warm
        dt = _bench(q, n_iters=10)
        results[label] = dt
        tag = f"{n_series // 1000}k"
        report(f"query_sum_rate_{tag}_{label}_p50", dt * 1e3, "ms")
    report("jitter5pct_vs_regular_ratio",
           results["jitter5pct"] / results["regular"], "x")
    report("jitter_holes_vs_regular_ratio",
           results["jitter5pct_holes0.5pct"] / results["regular"], "x")


ALL = [
    bench_encoding, bench_nan_sum, bench_ingestion, bench_index,
    bench_index_1m, bench_index_bitmap_1m, bench_gateway_parse, bench_planner,
    bench_query_in_memory, bench_query_hicard, bench_histogram_query,
    bench_jitter_query,
]


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("-")]
    only = args[0] if args else None
    isolate = "--no-isolate" not in sys.argv and only is None
    if not isolate:
        exact = any(only == f.__name__ for f in ALL) if only else False
        for fn in ALL:
            if only and (fn.__name__ != only if exact else only not in fn.__name__):
                continue
            fn()
        print(json.dumps(RESULTS))
        return
    # one subprocess per bench: a fresh heap for every measurement, so a
    # memory-heavy bench (the 1M index build) cannot degrade the ones that
    # run after it — numbers of record must not depend on suite order.
    # This parent imports no jax: a chip belongs to one process, and the
    # children (run one at a time) are the ones that need it.
    import subprocess

    for fn in ALL:
        try:
            p = subprocess.run(
                [sys.executable, "-m", "benchmarks.run", fn.__name__,
                 "--no-isolate"],
                capture_output=True, text=True, cwd=_ROOT,
                timeout=int(os.environ.get("FILODB_BENCH_FN_TIMEOUT_S", 1800)),
            )
        except subprocess.TimeoutExpired:
            # a hung bench must not kill the rest of the suite — that is
            # the whole point of isolation
            rec = {"metric": f"FAILED_{fn.__name__}", "value": -1,
                   "unit": "timeout"}
            RESULTS.append(rec)
            print(json.dumps(rec), flush=True)
            continue
        for line in p.stdout.splitlines():
            if not line.startswith("{"):
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict) and "metric" in rec:
                RESULTS.append(rec)
                print(line, flush=True)
        if p.returncode != 0:
            rec = {"metric": f"FAILED_{fn.__name__}", "value": -1,
                   "unit": "error"}
            RESULTS.append(rec)
            print(json.dumps(rec), flush=True)
            sys.stderr.write(p.stderr[-500:] + "\n")
    print(json.dumps(RESULTS))


def bench_mesh_paths():
    """Distributed execution paths (needs >=2 devices; skipped otherwise)."""
    import jax

    if len(jax.devices()) < 2:
        return
    from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine
    from filodb_tpu.core.schemas import Dataset
    from filodb_tpu.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.parallel.mesh import make_mesh
    from filodb_tpu.testkit import counter_batch

    ms = TimeSeriesMemStore()
    ms.setup(Dataset("prometheus"), range(8))
    ms.ingest_routed("prometheus", counter_batch(n_series=400, n_samples=360, start_ms=BASE), spread=3)
    engine = QueryEngine(ms, "prometheus", PlannerParams(mesh=make_mesh()))
    start, end = (BASE + 400_000) / 1000, (BASE + 3_400_000) / 1000

    def q():
        engine.query_range("sum(rate(http_requests_total[5m]))", start, end, 60)

    q()
    dt = _bench(q, n_iters=10)
    report("mesh_sum_rate_qps", 1 / dt, "qps")


ALL.append(bench_mesh_paths)


def bench_serialization():
    """Prom JSON rendering throughput (the serving-edge cost), measured on
    the PRODUCTION bytes path: stream_matrix fragments — exactly what both
    the buffered and chunked-streaming edges send (native row renderer when
    libfilodbrender is built, vectorized numpy tier otherwise)."""
    from filodb_tpu import native as N
    from filodb_tpu.api import promjson as J
    from filodb_tpu.query.rangevector import Grid, QueryResult

    rng = np.random.default_rng(0)
    vals = rng.standard_normal((1000, 120)).astype(np.float32)
    g = Grid([{"_metric_": "m", "i": str(i)} for i in range(1000)],
             BASE, 60_000, 120, vals)
    res = QueryResult(grids=[g])
    dt = _bench(lambda: b"".join(J.stream_matrix(res)))
    report(f"prom_json_render[{J.active_render_format()}]",
           1000 * 120 / dt / 1e6, "Msamples/s")
    if N.render_lib() is not None:
        # numpy tier on the same workload (what an un-built checkout serves)
        orig = N.render_matrix_rows
        N.render_matrix_rows = lambda ts, v: None
        try:
            dt = _bench(lambda: b"".join(J.stream_matrix(res)))
            report("prom_json_render[numpy]", 1000 * 120 / dt / 1e6, "Msamples/s")
        finally:
            N.render_matrix_rows = orig

    from filodb_tpu.api.arrow_edge import result_to_ipc

    dt = _bench(lambda: result_to_ipc(res))
    report("arrow_ipc_render", 1000 * 120 / dt / 1e6, "Msamples/s")

    # gRPC columnar stream frames (query/proto_plan.py): serialize + parse
    from filodb_tpu.query.proto_plan import frames_to_result, result_to_frames

    def grpc_roundtrip():
        wire = [f.SerializeToString() for f in result_to_frames(res)]
        from filodb_tpu.api.query_exec_pb2 import StreamFrame

        return frames_to_result(StreamFrame.FromString(b) for b in wire)

    dt = _bench(grpc_roundtrip)
    report("grpc_frames_roundtrip", 1000 * 120 / dt / 1e6, "Msamples/s")


ALL.append(bench_serialization)


def bench_concurrent_queries():
    """QPS scaling under concurrent clients (VERDICT r4 item 6; reference
    analog: the shared instrumented pool, QueryScheduler.scala:29-73).
    16 clients fan the same dashboard query out; single-flight coalescing
    turns the fan-out into one kernel launch per arrival window, so QPS
    must scale, not flatline. FILODB_BENCH_CONC_SERIES sets the scale
    (default 20k; the bar was stated at 100k)."""
    import os
    import threading
    import time as _t

    from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine
    from filodb_tpu.coordinator.scheduler import QueryScheduler
    from filodb_tpu.core.schemas import Dataset
    from filodb_tpu.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.testkit import counter_batch

    n_series = int(os.environ.get("FILODB_BENCH_CONC_SERIES", 20_000))
    ms = TimeSeriesMemStore()
    ms.setup(Dataset("prometheus"), range(8))
    ms.ingest_routed(
        "prometheus",
        counter_batch(n_series=n_series, n_samples=120, start_ms=BASE),
        spread=3,
    )
    engine = QueryEngine(
        ms, "prometheus",
        PlannerParams(scheduler=QueryScheduler(), deadline_s=120),
    )
    start, end = (BASE + 400_000) / 1000, (BASE + 1_100_000) / 1000
    q = "sum(rate(http_requests_total[5m]))"
    engine.query_range(q, start, end, 60)  # warm staging + jit

    def measure(n_clients: int, seconds: float = 4.0) -> float:
        done = []
        stop = _t.monotonic() + seconds

        def client():
            k = 0
            while _t.monotonic() < stop:
                engine.query_range(q, start, end, 60)
                k += 1
            done.append(k)

        threads = [threading.Thread(target=client) for _ in range(n_clients)]
        t0 = _t.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return sum(done) / (_t.monotonic() - t0)

    qps1 = measure(1)
    qps16 = measure(16)
    tag = f"{n_series // 1000}k"
    report(f"concurrent_qps_1client_{tag}", qps1, "qps")
    report(f"concurrent_qps_16clients_{tag}", qps16, "qps")
    report("concurrent_qps_scaling_1_to_16", qps16 / qps1, "x")


ALL.append(bench_concurrent_queries)


def bench_query_and_ingest():
    """Query QPS while ingestion runs concurrently (reference
    QueryAndIngestBenchmark.scala: 'measure impact of ingestion on
    querying' — ingest invalidates the staging caches, so each query pays a
    re-stage; the ratio against the idle QPS is the contract)."""
    import threading
    import time as _t

    from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine
    from filodb_tpu.core.schemas import Dataset
    from filodb_tpu.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.testkit import counter_batch

    n_series, n_samples = 800, 1080  # the reference's scale (3h @ 10s)
    ms = TimeSeriesMemStore()
    ms.setup(Dataset("prometheus"), range(2))
    ms.ingest_routed(
        "prometheus",
        counter_batch(n_series=n_series, n_samples=n_samples, start_ms=BASE),
        spread=1,
    )
    engine = QueryEngine(ms, "prometheus", PlannerParams(deadline_s=120))
    start = (BASE + 600_000) / 1000
    # live-edge panel: its range covers the ENTIRE incoming stream (the
    # ingester below appends ~100 s of data per batch, up to 100 batches),
    # so every batch lands in-range and invalidates the staging cache —
    # each query during ingest genuinely pays the re-stage
    end = (BASE + n_samples * 10_000 + 100 * 100_000) / 1000
    q = "sum(rate(http_requests_total[5m]))"
    engine.query_range(q, start, end, 60)

    dt_idle = _bench(lambda: engine.query_range(q, start, end, 60), n_iters=5)
    report("query_idle_800x1080_qps", 1 / dt_idle, "qps")

    # pre-generate the ingest stream (the reference notes the pseudorandom
    # producer's CPU pollutes the measurement) and ingest at a DEFINED rate
    # (one 10-sample-per-series batch per 100 ms = 80k samples/s), so the
    # metric is "query cost while a realistic stream ingests", not "query
    # cost while a tight loop saturates the core"
    t0 = BASE + n_samples * 10_000
    batches = [
        counter_batch(n_series=n_series, n_samples=10, start_ms=t0 + i * 100_000)
        for i in range(100)
    ]
    stop = threading.Event()
    ingested = [0]

    def ingester():
        i = 0
        while not stop.is_set():
            ingested[0] += ms.ingest_routed(
                "prometheus", batches[i % len(batches)], spread=1
            )
            i += 1
            stop.wait(0.1)

    # historical query: its range ends BEFORE the live ingest head, so the
    # selective stage-cache invalidation must keep it cached under ingest;
    # its impact ratio uses ITS OWN idle baseline (shorter range — dividing
    # by the live query's idle latency would conflate range length with
    # ingest impact)
    hist_end = (BASE + (n_samples - 60) * 10_000) / 1000
    engine.query_range(q, start, hist_end, 60)
    dt_hist_idle = _bench(
        lambda: engine.query_range(q, start, hist_end, 60), n_iters=5
    )

    th = threading.Thread(target=ingester)
    th.start()
    try:
        t0 = _t.monotonic()
        k = 0
        while _t.monotonic() - t0 < 5.0:
            engine.query_range(q, start, end, 60)
            k += 1
        dt_busy = (_t.monotonic() - t0) / k
        t0 = _t.monotonic()
        k = 0
        while _t.monotonic() - t0 < 5.0:
            engine.query_range(q, start, hist_end, 60)
            k += 1
        dt_hist = (_t.monotonic() - t0) / k
    finally:
        stop.set()
        th.join()
    assert ingested[0] > 0, "ingester must actually run during the window"
    report("query_under_ingest_800x1080_qps", 1 / dt_busy, "qps")
    report("ingest_impact_on_query", dt_busy / dt_idle, "x")
    report("query_historical_under_ingest_qps", 1 / dt_hist, "qps")
    report("ingest_impact_on_historical_query", dt_hist / dt_hist_idle, "x")


ALL.append(bench_query_and_ingest)


def bench_query_on_demand():
    """Queries served ~100% by on-demand paging from the column store
    (reference QueryOnDemandBenchmark.scala: evict everything, query, page
    back in). Every query drops the paged chunks again so each one pays the
    full ODP read."""
    import shutil
    import tempfile

    from filodb_tpu.coordinator.planner import QueryEngine
    from filodb_tpu.core.schemas import Dataset
    from filodb_tpu.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.memstore.shard import StoreConfig
    from filodb_tpu.store.columnstore import LocalColumnStore
    from filodb_tpu.store.flush import FlushCoordinator
    from filodb_tpu.testkit import machine_metrics

    n_series, n_samples = 100, 720  # the reference's scale (2h @ 10s)
    root = tempfile.mkdtemp(prefix="filodb-odp-bench-")
    try:
        store = LocalColumnStore(root)
        ms = TimeSeriesMemStore(
            StoreConfig(max_chunk_size=100, retention_ms=1_000_000)
        )
        ms.setup(Dataset("prometheus"), [0])
        sh = ms.shard("prometheus", 0)
        sh.odp_store = store
        ms.ingest(
            "prometheus", 0,
            machine_metrics(n_series=n_series, n_samples=n_samples, start_ms=BASE),
        )
        FlushCoordinator(ms, store).flush_shard("prometheus", 0)
        # retention keeps only the newest ~100 samples resident: the queried
        # window below is entirely evicted, so every query reads the store
        evict_now = BASE + n_samples * 10_000
        engine = QueryEngine(ms, "prometheus")
        start = (BASE + 600_000) / 1000
        end = start + 55 * 60  # reference queryIntervalMin = 55
        q = "sum(rate(heap_usage0[5m]))"

        def cold_query():
            sh.evict_for_retention(now_ms=evict_now)
            engine.query_range(q, start, end, 60)

        cold_query()
        pages0 = sh.odp_stats_pages
        dt = _bench(cold_query, n_iters=5)
        assert sh.odp_stats_pages > pages0, "queries must actually page in"
        report("query_odp_100x720_qps", 1 / dt, "qps")
    finally:
        shutil.rmtree(root, ignore_errors=True)


ALL.append(bench_query_on_demand)


def bench_render():
    """Native sample-fragment renderer (promrender.cpp), the serving-edge
    hot loop — VERDICT r3 weak #1 bar: >=10 Msamples/s on 2M random-f64
    samples (worst-case shortest-repr values), one warm call."""
    from filodb_tpu import native as N
    from filodb_tpu.api import promjson as J

    rng = np.random.default_rng(7)
    n = 2_000_000
    ts = 1.6e9 + np.arange(n) * 10.0
    vals = rng.uniform(0, 1e9, n)
    vals[::1000] = np.nan
    if N.render_values(ts[:8], vals[:8]) is not None:
        dt = _bench(lambda: N.render_values(ts, vals), n_iters=5)
        report("prom_render_native_2M_random", n / dt / 1e6, "Msamples/s")
        dt = _bench(lambda: N.render_values(ts, np.floor(vals)), n_iters=5)
        report("prom_render_native_2M_integral", n / dt / 1e6, "Msamples/s")
    # pure-Python fallback on a 100k slice (it is ~30x slower)
    m = 100_000

    def py_render():
        keep = ~np.isnan(vals[:m])
        parts = (
            f'[{J._ts3(float(t))},"{J._fmt(v)}"]'
            for t, v in zip(ts[:m][keep], vals[:m][keep])
        )
        return ("[" + ",".join(parts) + "]").encode()

    dt = _bench(py_render, n_iters=3)
    report("prom_render_python_100k_random", m / dt / 1e6, "Msamples/s")


ALL.append(bench_render)


if __name__ == "__main__":
    main()
