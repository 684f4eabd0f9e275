"""On-demand paging + ingest/query concurrency tests (model: reference
QueryOnDemandBenchmark workload + PageAlignedBlockManagerConcurrentSpec
discipline: queries racing eviction/ingest must stay correct)."""

import threading

import numpy as np
import pytest

from filodb_tpu.coordinator.planner import QueryEngine
from filodb_tpu.core.schemas import Dataset
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.memstore.shard import StoreConfig
from filodb_tpu.store.columnstore import LocalColumnStore
from filodb_tpu.store.flush import FlushCoordinator
from filodb_tpu.testkit import machine_metrics

BASE = 1_600_000_000_000


class TestOnDemandPaging:
    def test_evicted_chunks_paged_back(self, tmp_path):
        store = LocalColumnStore(str(tmp_path))
        ms = TimeSeriesMemStore(StoreConfig(max_chunk_size=100, retention_ms=1_000_000))
        ms.setup(Dataset("ds"), [0])
        sh = ms.shard("ds", 0)
        sh.odp_store = store
        # 300 samples @10s = 50min of data
        ms.ingest("ds", 0, machine_metrics(n_series=4, n_samples=300, start_ms=BASE))
        FlushCoordinator(ms, store).flush_shard("ds", 0)
        engine = QueryEngine(ms, "ds")
        full_start, full_end = (BASE + 600_000) / 1000, (BASE + 2_400_000) / 1000
        want = engine.query_range("avg(heap_usage0)", full_start, full_end, 60.0)
        want_vals = want.grids[0].values_np().copy()

        # evict everything older than the last ~16 minutes
        dropped = sh.evict_for_retention(now_ms=BASE + 300 * 10_000)
        assert dropped > 0
        # same query: ODP must page evicted chunks back in
        got = engine.query_range("avg(heap_usage0)", full_start, full_end, 60.0)
        assert sh.odp_stats_pages > 0
        np.testing.assert_allclose(got.grids[0].values_np(), want_vals, rtol=1e-5, equal_nan=True)

    def test_no_store_no_paging(self):
        ms = TimeSeriesMemStore(StoreConfig(max_chunk_size=100))
        ms.setup(Dataset("ds"), [0])
        ms.ingest("ds", 0, machine_metrics(n_series=2, n_samples=100, start_ms=BASE))
        sh = ms.shard("ds", 0)
        assert sh.odp_page_in([0], 0, 2**62) == 0


class TestIngestQueryConcurrency:
    def test_concurrent_ingest_and_query(self):
        """reference QueryAndIngestBenchmark shape: queries racing ingest
        must neither crash nor return garbage."""
        ms = TimeSeriesMemStore(StoreConfig(max_chunk_size=100))
        ms.setup(Dataset("ds"), [0])
        ms.ingest("ds", 0, machine_metrics(n_series=5, n_samples=100, start_ms=BASE))
        engine = QueryEngine(ms, "ds")
        errors = []
        stop = threading.Event()

        def ingester():
            i = 1
            while not stop.is_set() and i < 20:
                batch = machine_metrics(n_series=5, n_samples=50, start_ms=BASE + i * 500_000)
                try:
                    ms.ingest("ds", 0, batch)
                except Exception as e:  # noqa: BLE001
                    errors.append(e)
                i += 1

        def querier():
            for _ in range(15):
                if stop.is_set():
                    return
                try:
                    res = engine.query_range(
                        "sum(heap_usage0)", (BASE + 300_000) / 1000, (BASE + 9_000_000) / 1000, 120.0
                    )
                    for g in res.grids:
                        v = g.values_np()
                        m = ~np.isnan(v)
                        if m.any():
                            assert np.isfinite(v[m]).all()
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

        threads = [threading.Thread(target=ingester)] + [
            threading.Thread(target=querier) for _ in range(3)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stop.set()
        assert not errors, errors[:3]

    def test_concurrent_eviction_and_query(self):
        ms = TimeSeriesMemStore(StoreConfig(max_chunk_size=50, retention_ms=600_000))
        ms.setup(Dataset("ds"), [0])
        ms.ingest("ds", 0, machine_metrics(n_series=5, n_samples=400, start_ms=BASE))
        engine = QueryEngine(ms, "ds")
        sh = ms.shard("ds", 0)
        errors = []

        def evicter():
            for k in range(10):
                try:
                    sh.evict_for_retention(now_ms=BASE + 4_000_000 + k * 50_000)
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

        def querier():
            for _ in range(10):
                try:
                    engine.query_range(
                        "avg(heap_usage0)", (BASE + 1_000_000) / 1000, (BASE + 4_000_000) / 1000, 60.0
                    )
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

        threads = [threading.Thread(target=evicter)] + [threading.Thread(target=querier) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors[:3]


def test_concurrent_flush_and_query(tmp_path):
    """Flush (seals buffers, persists, downsamples) racing queries must stay
    correct — the reference's flush-vs-query lock discipline, here via
    immutable chunk snapshots."""
    import threading

    from filodb_tpu.store.flush import FlushCoordinator

    store = LocalColumnStore(str(tmp_path))
    ms = TimeSeriesMemStore(StoreConfig(max_chunk_size=60))
    ms.setup(Dataset("ds"), [0])
    ms.ingest("ds", 0, machine_metrics(n_series=4, n_samples=240, start_ms=BASE))
    engine = QueryEngine(ms, "ds")
    fc = FlushCoordinator(ms, store)
    errors = []

    def flusher():
        for _ in range(5):
            try:
                fc.flush_shard("ds", 0)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

    def querier():
        for _ in range(8):
            try:
                res = engine.query_range(
                    "sum(heap_usage0)", (BASE + 600_000) / 1000, (BASE + 2_000_000) / 1000, 60)
                assert sum(g.n_series for g in res.grids) == 1
            except Exception as e:  # noqa: BLE001
                errors.append(e)

    threads = [threading.Thread(target=flusher)] + [threading.Thread(target=querier) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[:3]
