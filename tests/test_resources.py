"""Resource ledger & self-telemetry tests (doc/observability.md "Resource
accounting & self-monitoring"):

- device-ledger drift: after a query/ingest/evict soak, every ledger
  account's balance EXACTLY equals a cold walk of its cache's
  staged_nbytes — zero drift — and the warm canonical query still issues
  exactly ONE kernel dispatch with accounting enabled;
- per-tenant attribution round-trip: queries as two tenants accumulate
  tenant counters that sum to the query-wide QueryStats totals;
- /debug/resources and /debug/superblocks return consistent JSON;
- self-scrape proof: rate(filodb_kernel_dispatch_seconds_count[5m]) over
  the _system dataset answers through the standard query API;
- slow-query ring under concurrent record/configure, ordering, threshold
  edge; ?trace=true carries the new resource stats;
- Registry.remove + tenant series aging; HELP/TYPE + OpenMetrics +
  exemplars.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

import numpy as np
import pytest

from filodb_tpu.api.http import serve_background
from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine
from filodb_tpu.core.schemas import Dataset
from filodb_tpu.ledger import LEDGER
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.memstore.shard import StoreConfig
from filodb_tpu.metrics import REGISTRY, Registry, SlowQueryLog
from filodb_tpu.testkit import counter_batch
from filodb_tpu.ops import staging as ST

pytestmark = pytest.mark.observability

BASE = 1_600_000_000_000
N_SAMPLES = 240
HEAD_MS = BASE + N_SAMPLES * 10_000
START = (BASE + 600_000) / 1000
STEP = 60
Q = "sum by (job) (rate(http_requests_total[5m]))"


def _dispatch_total() -> int:
    total = 0
    with REGISTRY._lock:
        for (name, _labels), m in REGISTRY._metrics.items():
            if name == "filodb_kernel_dispatch_seconds":
                total += m.total
    return total


def _counter(name: str, **labels) -> float:
    return REGISTRY.counter(name, **labels).value


def _make_store(n_shards=4, n_series=24, stage_cache_bytes=2 << 30):
    ms = TimeSeriesMemStore(StoreConfig(stage_cache_bytes=stage_cache_bytes))
    ms.setup(Dataset("ds"), list(range(n_shards)))
    ms.ingest_routed(
        "ds", counter_batch(n_series=n_series, n_samples=N_SAMPLES,
                            start_ms=BASE),
        spread=3,
    )
    return ms


def _assert_zero_drift():
    """Every live ledger account's balance equals a cold walk of its cache."""
    report = LEDGER.verify()
    bad = [a for a in report["accounts"]
           if a["actual"] is not None and a["bytes"] != a["actual"]]
    assert not bad, f"ledger drift: {bad}"
    for kind, slot in report["kinds"].items():
        assert slot["drift"] == 0, (kind, slot)


# ---------------------------------------------------------------------------
# device-derived cache budgets (a 16 GB chip must bound what the caches pin)


class TestDeviceCacheBudgets:
    def test_ceilings_alone_where_the_device_reports_no_limit(self):
        from filodb_tpu.ops import staging as ST

        assert ST._device_bytes_limits() == {}  # the CPU backend
        assert ST.SuperblockCache().device_budget() == 8 << 30
        assert ST.device_cache_budget(0.2, 2 << 30) == 2 << 30

    def test_budgets_follow_the_device_and_never_exceed_the_knob(
            self, monkeypatch):
        from filodb_tpu.ops import staging as ST

        dev0 = ST.default_device_key()
        monkeypatch.setattr(ST, "_device_bytes_limits",
                            lambda: {dev0: 16 << 30})
        assert ST.SuperblockCache().device_budget() == int(
            (16 << 30) * ST.SUPERBLOCK_DEVICE_SHARE)
        assert ST.SuperblockCache(max_bytes=1 << 20).device_budget() == 1 << 20
        # both caches together leave room for what they do not count
        assert ST.SUPERBLOCK_DEVICE_SHARE + ST.STAGE_CACHE_DEVICE_SHARE <= 0.6

    def test_superblocks_are_charged_to_the_devices_they_sit_on(
            self, monkeypatch):
        """A sharded entry costs each device its band, not the whole block:
        beside un-sharded entries that nearly fill the default device it
        evicts nothing, while one more un-sharded entry does."""
        import jax
        from types import SimpleNamespace

        from filodb_tpu.ops import staging as ST
        from filodb_tpu.parallel.mesh import make_mesh

        devs = jax.devices()
        assert len(devs) >= 4  # tests/conftest.py's virtual CPU mesh
        mesh = make_mesh(devs[:4])
        unit = 1 << 20
        # every device's budget for this cache: 10 units
        monkeypatch.setattr(ST, "SUPERBLOCK_DEVICE_SHARE", 0.25)
        monkeypatch.setattr(ST, "_device_bytes_limits",
                            lambda: {str(d): 40 * unit for d in devs})

        def entry(placement):
            return SimpleNamespace(block=SimpleNamespace(placement=placement))

        c = ST.SuperblockCache(max_entries=64)
        assert c.device_budget(str(devs[3])) == 10 * unit
        for i in range(4):
            c.put(("single", i), (0,), entry(None), 2 * unit)
        # 8 of 10 units sit on the default device; a sharded 8-unit block
        # adds 2 there and 2 on each of the others
        c.put("sharded", (0,), entry(mesh), 8 * unit)
        assert len(c) == 5
        # the default device is full now: the next un-sharded entry evicts
        # the oldest, on that device's budget alone
        c.put(("single", 4), (0,), entry(None), 2 * unit)
        assert c.peek(("single", 0)) is None and len(c) == 5
        # nothing that exceeds a device's whole budget is ever pinned
        c.put("monster", (0,), entry(None), 11 * unit)
        assert c.peek("monster") is None
        c.put("wide", (0,), entry(mesh), 11 * unit)  # 2.75 a device: fits
        assert c.peek("wide") is not None

    def test_stage_cache_evicts_to_its_slice_of_the_device(self, monkeypatch):
        """With the knob at its 2 GiB default, a small device still bounds
        each shard's staged bytes: distinct ranges evict earlier entries
        instead of piling up, and the ledger stays exact. The device's
        share is divided among the shards of EVERY dataset it serves."""
        from filodb_tpu.ops import staging as ST

        ms = _make_store()
        ms.setup(Dataset("other"), [0, 1, 2, 3])
        eng = QueryEngine(ms, "ds")
        eng.query_range("rate(http_requests_total[5m])", START, START + 600, STEP)
        one = max(sh.ledger.bytes for sh in ms.shards("ds"))
        assert one > 0
        n = ms.local_shard_count()
        assert n == 8
        # a device whose stage share is ~1.5 entries per shard
        dev0 = ST.default_device_key()
        monkeypatch.setattr(
            ST, "_device_bytes_limits",
            lambda: {dev0: int(1.5 * one * n / ST.STAGE_CACHE_DEVICE_SHARE)})
        for i in range(1, 6):
            eng.query_range("rate(http_requests_total[5m])", START + i,
                            START + 600, STEP)
        for sh in ms.shards("ds"):
            assert len(sh.stage_cache) <= 1 and sh.ledger.bytes <= 1.5 * one
        _assert_zero_drift()


# ---------------------------------------------------------------------------
# device-resource ledger


class TestDeviceLedger:
    def test_drift_zero_after_query_ingest_evict_soak(self):
        """Seeded churn across every ledger event class — cold stages,
        cache hits, append repairs, superblock builds/extensions,
        byte-budget evictions, wholesale invalidation — then the ledger
        must agree with a cold walk EXACTLY."""
        # small stage budget: later stages evict earlier entries
        ms = _make_store(stage_cache_bytes=256 * 1024)
        fused = QueryEngine(ms, "ds")
        end = (HEAD_MS + 40 * 10_000) / 1000
        errors: list = []

        def ingester():
            try:
                for b in range(30):
                    ms.ingest_routed(
                        "ds",
                        counter_batch(n_series=24, n_samples=1,
                                      start_ms=HEAD_MS + b * 10_000),
                        spread=3,
                    )
            except Exception as e:  # pragma: no cover
                errors.append(e)

        th = threading.Thread(target=ingester)
        th.start()
        try:
            for i in range(20):
                fused.query_range(Q, START, end, STEP)
                # distinct windows churn distinct cache keys -> evictions
                fused.query_range(
                    "rate(http_requests_total[5m])", START + i, end, STEP
                )
        finally:
            th.join()
        assert not errors, errors
        _assert_zero_drift()
        # retention/headroom-style wholesale invalidation must credit too
        for sh in ms.shards("ds"):
            with sh._lock:
                sh.version += 1
                sh._record_effect(0, 0, True)
                sh._clear_stage_cache()
        _assert_zero_drift()
        for sh in ms.shards("ds"):
            assert sh.ledger.bytes == 0

    def test_gauges_published_at_scrape_time(self):
        ms = _make_store()
        eng = QueryEngine(ms, "ds")
        eng.query_range(Q, START, (BASE + 900_000) / 1000, STEP)
        text = REGISTRY.expose()
        assert 'filodb_device_bytes{kind="staged_block"}' in text
        assert 'filodb_device_bytes{kind="superblock"}' in text
        assert "filodb_device_alloc_bytes_total" in text
        # the gauge equals the walk of the LIVE accounts at scrape time
        _assert_zero_drift()

    def test_warm_query_single_dispatch_with_accounting(self):
        """Accounting must add no per-dispatch host sync: the warm fused
        canonical query stays exactly ONE kernel dispatch."""
        ms = _make_store()
        fused = QueryEngine(ms, "ds")
        end = (BASE + 900_000) / 1000
        fused.query_range(Q, START, end, STEP)  # cold: stage + compile
        fused.query_range(Q, START, end, STEP)  # warm-up second pass
        before = _dispatch_total()
        res = fused.query_range(Q, START, end, STEP)
        assert _dispatch_total() - before == 1
        assert res.stats.cache_hits >= 1  # superblock served from cache
        _assert_zero_drift()

    def test_evicted_superblock_credits_ledger(self):
        ms = _make_store()
        fused = QueryEngine(ms, "ds")
        end = (BASE + 900_000) / 1000
        fused.query_range(Q, START, end, STEP)
        cache = ms._superblock_cache
        assert len(cache) >= 1
        # drop everything through the cache API: balance must return to 0
        with cache._lock:
            keys = list(cache._d)
        for k in keys:
            cache.drop(k)
        assert cache.ledger.bytes == 0
        _assert_zero_drift()


# ---------------------------------------------------------------------------
# per-tenant attribution


class TestTenantAttribution:
    def test_round_trip_two_tenants(self):
        ms = TimeSeriesMemStore()
        ms.setup(Dataset("ds"), list(range(4)))
        for ws, ns, seed in (("tenA", "app1", 3), ("tenB", "app2", 4)):
            ms.ingest_routed(
                "ds",
                counter_batch(n_series=8, n_samples=120, start_ms=BASE,
                              ws=ws, ns=ns, seed=seed),
                spread=3,
            )
        eng = QueryEngine(ms, "ds")
        end = (BASE + 900_000) / 1000
        before = {
            (ws, ns): {
                "q": _counter("filodb_tenant_queries", ws=ws, ns=ns),
                "s": _counter("filodb_tenant_query_seconds", ws=ws, ns=ns),
                "k": _counter("filodb_tenant_kernel_seconds", ws=ws, ns=ns),
                "b": _counter("filodb_tenant_bytes_staged", ws=ws, ns=ns),
            }
            for ws, ns in (("tenA", "app1"), ("tenB", "app2"))
        }
        stats = {}
        for ws, ns in (("tenA", "app1"), ("tenB", "app2")):
            q = (f'sum(rate(http_requests_total{{_ws_="{ws}",'
                 f'_ns_="{ns}"}}[5m]))')
            res1 = eng.query_range(q, START, end, STEP)
            res2 = eng.query_range(q, START + 1, end, STEP)
            stats[(ws, ns)] = [res1.stats, res2.stats]
        for (ws, ns), runs in stats.items():
            b = before[(ws, ns)]
            assert _counter("filodb_tenant_queries", ws=ws, ns=ns) - b["q"] == 2
            # per-tenant counters sum to the query-wide QueryStats totals
            got_bytes = _counter("filodb_tenant_bytes_staged", ws=ws, ns=ns) - b["b"]
            assert got_bytes == sum(r.bytes_staged for r in runs)
            got_kernel = _counter("filodb_tenant_kernel_seconds", ws=ws, ns=ns) - b["k"]
            assert got_kernel == pytest.approx(
                sum(r.kernel_ns for r in runs) / 1e9, rel=1e-6, abs=1e-9
            )
            assert _counter("filodb_tenant_query_seconds", ws=ws, ns=ns) - b["s"] > 0
            assert runs[0].kernel_ns > 0

    def test_unpinned_query_attributes_to_unknown(self):
        ms = _make_store()
        eng = QueryEngine(ms, "ds")
        before = _counter("filodb_tenant_queries", ws="unknown", ns="unknown")
        eng.query_range(Q, START, (BASE + 900_000) / 1000, STEP)
        assert _counter("filodb_tenant_queries", ws="unknown", ns="unknown") \
            == before + 1

    def test_trace_root_tagged_with_tenant(self):
        ms = _make_store()
        eng = QueryEngine(ms, "ds")
        res = eng.query_range(
            'sum(rate(http_requests_total{_ws_="demo",_ns_="App-2"}[5m]))',
            START, (BASE + 900_000) / 1000, STEP,
        )
        assert res.trace.tags.get("ws") == "demo"
        assert res.trace.tags.get("ns") == "App-2"


# ---------------------------------------------------------------------------
# debug endpoints + trace stats over HTTP


def _get_json(url: str) -> dict:
    with urllib.request.urlopen(url) as r:
        return json.loads(r.read())


class TestDebugEndpoints:
    def test_resources_and_superblocks_consistent(self):
        ms = _make_store()
        eng = QueryEngine(ms, "ds")
        end = (BASE + 900_000) / 1000
        eng.query_range(Q, START, end, STEP)
        eng.query_range(Q, START, end, STEP)  # superblock cache hit
        srv, port = serve_background(eng)
        try:
            res = _get_json(f"http://127.0.0.1:{port}/debug/resources")["data"]
            assert set(res) >= {"device_bytes", "kinds", "accounts", "tenants"}
            for kind, slot in res["kinds"].items():
                assert slot["drift"] == 0, (kind, slot)
            assert res["device_bytes"].get("superblock", 0) > 0
            sb = _get_json(f"http://127.0.0.1:{port}/debug/superblocks")["data"]
            assert sb["count"] == len(sb["entries"]) >= 1
            assert sb["bytes"] == sum(e["bytes"] for e in sb["entries"])
            entry = sb["entries"][0]
            assert entry["bytes"] > 0 and entry["hits"] >= 1
            assert "age_s" in entry and "last_outcome" in entry
            # the superblock cache's ledger balance is exactly this bytes
            # sum (the kind-wide device_bytes gauge may also include other
            # live caches in the process, so it can only be >=)
            assert sb["ledger_bytes"] == sb["bytes"]
            assert res["device_bytes"]["superblock"] >= sb["bytes"]
        finally:
            srv.shutdown()

    def test_unknown_dataset_is_400(self):
        ms = _make_store(n_shards=1, n_series=2)
        eng = QueryEngine(ms, "ds")
        srv, port = serve_background(eng)
        try:
            url = (f"http://127.0.0.1:{port}/api/v1/query_range?query="
                   + urllib.parse.quote(Q)
                   + f"&start={START}&end={(BASE + 900_000) / 1000}&step=60")
            # the engine's own dataset name routes to the default engine
            _get_json(url + "&dataset=ds")
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get_json(url + "&dataset=_sytem")  # typo: 400, not wrong data
            assert ei.value.code == 400
        finally:
            srv.shutdown()

    def test_remote_stats_frames_carry_resource_fields(self):
        """The gRPC frame stream round-trips the NEW QueryStats fields
        (kernel_ns + cache events ride the in-band StatsExt frame; the
        StatsFrame proto keeps the 5 classic fields)."""
        from filodb_tpu.query.proto_plan import (frames_to_result,
                                                 result_to_frames)
        from filodb_tpu.query.rangevector import QueryResult, QueryStats

        res = QueryResult()
        res.stats = QueryStats(
            series_scanned=7, samples_scanned=700, cpu_ns=5, bytes_staged=99,
            kernel_ns=123_456, cache_hits=2, cache_misses=1, cache_extends=3,
        )
        got = frames_to_result(list(result_to_frames(res, stats_ext=True)))
        assert got.stats.as_dict() == res.stats.as_dict()
        # origin-opt-in: without the capability flag (an older origin) the
        # StatsExt frame must NOT be emitted — classic fields only
        legacy = frames_to_result(list(result_to_frames(res)))
        assert legacy.stats.kernel_ns == 0
        assert legacy.stats.bytes_staged == 99

    def test_trace_true_carries_resource_stats(self):
        ms = _make_store()
        eng = QueryEngine(ms, "ds")
        srv, port = serve_background(eng)
        try:
            out = _get_json(
                f"http://127.0.0.1:{port}/api/v1/query_range?query="
                + urllib.parse.quote(Q)
                + f"&start={START}&end={(BASE + 900_000) / 1000}&step=60"
                + "&trace=true"
            )["data"]
            st = out["stats"]
            assert st["kernelSeconds"] > 0
            assert st["cacheMisses"] >= 1
            assert {"cacheHits", "cacheExtends"} <= set(st)
            root_stats = out["trace"]["stats"]
            assert root_stats["kernel_ns"] > 0
            assert root_stats["cache_misses"] >= 1
        finally:
            srv.shutdown()


# ---------------------------------------------------------------------------
# self-scrape: the _system dataset


class TestSelfScrape:
    def test_rate_over_system_dataset_through_standard_api(self):
        from filodb_tpu.telemetry import SYSTEM_DATASET, SelfScraper

        ms = _make_store()
        eng = QueryEngine(ms, "ds")
        ms.setup(Dataset(SYSTEM_DATASET), range(4))
        scraper = SelfScraper(ms, interval_s=3600)
        sys_engine = QueryEngine(ms, SYSTEM_DATASET)
        now = int(time.time() * 1000)
        end = (BASE + 900_000) / 1000
        for k in range(5):
            eng.query_range(Q, START + k, end, STEP)  # grow dispatch counts
            n = scraper.scrape_once(now_ms=now + k * 15_000)
            assert n > 0
        srv, port = serve_background(
            eng, dataset_engines={SYSTEM_DATASET: sys_engine}
        )
        try:
            q = "rate(filodb_kernel_dispatch_seconds_count[5m])"
            out = _get_json(
                f"http://127.0.0.1:{port}/api/v1/query_range"
                f"?dataset={SYSTEM_DATASET}&query=" + urllib.parse.quote(q)
                + f"&start={(now + 30_000) / 1000}"
                + f"&end={(now + 60_000) / 1000}&step=15"
            )["data"]
            vals = [
                float(v) for series in out["result"]
                for _, v in series["values"] if v != "NaN"
            ]
            assert vals and max(vals) > 0  # the server's own dispatch rate
        finally:
            srv.shutdown()
        # histogram _count series landed in the counter schema (the parser
        # types histogram-family suffixes as cumulative)
        sh_schemas = {
            p.schema.name
            for sh in ms.shards(SYSTEM_DATASET)
            for p in sh.partitions.values()
            if p.tags.get("_metric_", "").endswith("_count")
        }
        assert sh_schemas <= {"prom-counter"}

    def test_scrape_counters_and_server_config_gate(self):
        from filodb_tpu.telemetry import SYSTEM_DATASET, SelfScraper

        ms = _make_store()
        ms.setup(Dataset(SYSTEM_DATASET), range(4))
        before = _counter("filodb_self_scrapes")
        scraper = SelfScraper(ms, interval_s=3600)
        scraper.scrape_once()
        assert _counter("filodb_self_scrapes") == before + 1
        assert _counter("filodb_self_scrape_samples") > 0

    def test_server_config_gate_end_to_end(self, tmp_path):
        """FiloServer with telemetry.self_scrape_interval_s wires the
        scraper + a _system engine, and ?dataset=_system answers PromQL
        over the server's own metrics through the standard query API."""
        from filodb_tpu.server import FiloServer
        from filodb_tpu.telemetry import SYSTEM_DATASET

        srv = FiloServer({
            "dataset": "ds",
            "shards": 2,
            "store_root": str(tmp_path / "store"),
            "telemetry": {"self_scrape_interval_s": 3600},
        })
        port = srv.start(port=0)
        try:
            assert srv.self_scraper is not None
            assert srv.system_engine is not None
            srv.memstore.ingest_routed(
                "ds",
                counter_batch(n_series=6, n_samples=N_SAMPLES, start_ms=BASE),
                spread=1,
            )
            now = int(time.time() * 1000)
            for k in range(5):
                # grow the server's own kernel-dispatch counts between
                # scrapes via real queries (distinct windows defeat caching)
                _get_json(
                    f"http://127.0.0.1:{port}/api/v1/query_range?query="
                    + urllib.parse.quote(Q)
                    + f"&start={START + k}&end={(BASE + 900_000) / 1000}&step=60"
                )
                assert srv.self_scraper.scrape_once(now_ms=now + k * 15_000) > 0
            q = "rate(filodb_kernel_dispatch_seconds_count[5m])"
            out = _get_json(
                f"http://127.0.0.1:{port}/api/v1/query_range"
                f"?dataset={SYSTEM_DATASET}&query=" + urllib.parse.quote(q)
                + f"&start={(now + 30_000) / 1000}"
                + f"&end={(now + 60_000) / 1000}&step=15"
            )["data"]
            vals = [
                float(v) for series in out["result"]
                for _, v in series["values"] if v != "NaN"
            ]
            assert vals and max(vals) > 0
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# slow-query log ring under concurrency


class TestSlowQueryRing:
    def test_concurrent_record_vs_configure_resize(self):
        log = SlowQueryLog(max_entries=8)
        errors: list = []
        stop = threading.Event()

        def recorder(tid: int):
            try:
                i = 0
                while not stop.is_set():
                    log.record(f"q{tid}-{i}", 1.0, dataset="ds")
                    i += 1
            except Exception as e:  # pragma: no cover
                errors.append(e)

        def resizer():
            try:
                for n in (4, 16, 2, 32, 8) * 10:
                    log.configure(n)
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=recorder, args=(t,)) for t in range(4)]
        rt = threading.Thread(target=resizer)
        for t in threads:
            t.start()
        rt.start()
        rt.join()
        stop.set()
        for t in threads:
            t.join()
        assert not errors, errors
        # final capacity from the last configure call wins
        assert len(log.entries()) <= 8
        log.record("final", 2.0, dataset="ds")
        assert log.entries()[0]["promql"] == "final"  # newest first

    def test_ring_ordering_newest_first(self):
        log = SlowQueryLog(max_entries=3)
        for i in range(7):
            log.record(f"q{i}", float(i), dataset="ds")
        got = [e["promql"] for e in log.entries()]
        assert got == ["q6", "q5", "q4"]

    def test_threshold_edge_records_at_exact_threshold(self):
        """_observe_slow records when elapsed >= threshold (never under)."""
        ms = _make_store(n_shards=1, n_series=2)
        eng = QueryEngine(ms, "ds",
                          PlannerParams(slow_query_threshold_s=0.0))
        from filodb_tpu.metrics import SLOW_QUERY_LOG

        SLOW_QUERY_LOG.clear()
        eng.query_range(Q, START, (BASE + 900_000) / 1000, STEP)
        entries = SLOW_QUERY_LOG.entries()
        assert entries and entries[0]["promql"] == Q
        # entries carry the new resource stats
        assert "kernel_ns" in entries[0]["stats"]
        SLOW_QUERY_LOG.clear()
        off = QueryEngine(ms, "ds",
                          PlannerParams(slow_query_threshold_s=None))
        off.query_range(Q, START, (BASE + 900_000) / 1000, STEP)
        assert not SLOW_QUERY_LOG.entries()


# ---------------------------------------------------------------------------
# registry: remove / aging / HELP-TYPE / OpenMetrics / exemplars


class TestRegistrySeries:
    def test_remove_series(self):
        r = Registry()
        r.gauge("g", a="1").set(5)
        assert 'g{a="1"} 5' in r.expose()
        assert r.remove("g", a="1") is True
        assert 'g{a="1"}' not in r.expose()
        assert r.remove("g", a="1") is False

    def test_tenant_series_age_out_on_publish(self):
        from filodb_tpu.metering import TenantIngestionMetering

        class _Rec:
            def __init__(self, prefix):
                self.prefix = prefix
                self.ts_count = 5
                self.active_ts_count = 3

        class _Card:
            def __init__(self):
                self.recs = [_Rec(("wsX", "nsX")), _Rec(("wsY", "nsY"))]

            def scan(self, prefix, depth):
                return list(self.recs)

        class _Shard:
            cardinality = _Card()

        class _MS:
            def shards(self, ds):
                return [_Shard]

        m = TenantIngestionMetering(_MS(), "ds")
        assert m.publish() == 2
        assert 'filodb_tenant_ts_total{ns="nsX",ws="wsX"}' in REGISTRY.expose()
        _Shard.cardinality.recs = [_Rec(("wsY", "nsY"))]  # wsX vanished
        assert m.publish() == 1
        text = REGISTRY.expose()
        assert 'ws="wsX"' not in text.split("filodb_tenant_ts_total", 1)[-1] \
            .split("\n# ", 1)[0]
        assert 'filodb_tenant_ts_total{ns="nsY",ws="wsY"}' in text

    def test_help_and_type_lines(self):
        r = Registry()
        r.counter("filodb_queries", dataset="ds").inc()
        r.gauge("up").set(1)
        r.histogram("lat").observe(0.2)
        text = r.expose()
        assert "# TYPE filodb_queries_total counter" in text
        assert "# HELP filodb_queries_total " in text
        assert "# TYPE up gauge" in text
        assert "# TYPE lat histogram" in text
        r.describe("up", "custom help")
        assert "# HELP up custom help" in r.expose()

    def test_openmetrics_negotiation_and_exemplars(self):
        r = Registry()
        r.counter("filodb_queries", dataset="ds").inc(3)
        r.histogram("lat").observe(0.003, exemplar={"trace_id": "abc123"})
        om = r.expose(openmetrics=True)
        assert "# TYPE filodb_queries counter" in om  # family w/o _total
        assert "filodb_queries_total{" in om  # sample keeps the suffix
        assert om.rstrip().endswith("# EOF")
        assert '# {trace_id="abc123"} 0.003' in om
        # text format 0.0.4 stays exemplar-free
        assert "trace_id" not in r.expose()

    def test_http_content_negotiation(self):
        ms = _make_store(n_shards=1, n_series=2)
        eng = QueryEngine(ms, "ds")
        eng.query_range(Q, START, (BASE + 900_000) / 1000, STEP)
        srv, port = serve_background(eng)
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/metrics",
                headers={"Accept": "application/openmetrics-text"},
            )
            with urllib.request.urlopen(req) as resp:
                assert "openmetrics-text" in resp.headers["Content-Type"]
                body = resp.read().decode()
            assert body.rstrip().endswith("# EOF")
            with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics") as resp:
                assert "text/plain" in resp.headers["Content-Type"]
                assert "# EOF" not in resp.read().decode()
        finally:
            srv.shutdown()

    def test_latency_histogram_carries_trace_exemplar(self):
        ms = _make_store(n_shards=1, n_series=2)
        eng = QueryEngine(ms, "ds")
        res = eng.query_range(Q, START, (BASE + 900_000) / 1000, STEP)
        om = REGISTRY.expose(openmetrics=True)
        line = next(
            l for l in om.splitlines()
            if l.startswith("filodb_query_latency_seconds_bucket")
            and "trace_id" in l
        )
        assert res.trace.trace_id[:4] in line or "trace_id=" in line
