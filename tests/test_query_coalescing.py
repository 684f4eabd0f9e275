"""Concurrent identical-query coalescing (coordinator.scheduler.SingleFlight)
— the dashboard fan-out path: N copies of the same panel query must cost one
plan+stage+kernel execution (reference: shared QueryScheduler pool,
QueryScheduler.scala:29-73)."""

import threading
import time

import numpy as np
import pytest

from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine
from filodb_tpu.coordinator.scheduler import SingleFlight
from filodb_tpu.core.schemas import Dataset
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.query.exec.transformers import QueryError
from filodb_tpu.testkit import counter_batch

START = 1_600_000_000_000


class TestSingleFlight:
    def test_concurrent_callers_share_one_execution(self):
        sf = SingleFlight()
        calls = []
        gate = threading.Event()

        def slow():
            calls.append(1)
            gate.wait(5)
            return "answer"

        results = []
        threads = [
            threading.Thread(
                target=lambda: results.append(sf.run("k", slow, timeout_s=10))
            )
            for _ in range(8)
        ]
        for t in threads:
            t.start()
        time.sleep(0.3)  # everyone joined the flight
        gate.set()
        for t in threads:
            t.join()
        assert len(calls) == 1
        assert results == ["answer"] * 8

    def test_exception_propagates_to_followers(self):
        sf = SingleFlight()
        gate = threading.Event()

        def boom():
            gate.wait(5)
            raise QueryError("nope")

        errs = []

        def follow():
            try:
                sf.run("k", boom, timeout_s=10)
            except QueryError as e:
                errs.append(str(e))

        threads = [threading.Thread(target=follow) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.2)
        gate.set()
        for t in threads:
            t.join()
        assert errs == ["nope"] * 4

    def test_sequential_calls_never_share(self):
        sf = SingleFlight()
        calls = []
        sf.run("k", lambda: calls.append(1), timeout_s=5)
        sf.run("k", lambda: calls.append(1), timeout_s=5)
        assert len(calls) == 2

    def test_distinct_keys_run_independently(self):
        sf = SingleFlight()
        assert sf.run("a", lambda: 1, timeout_s=5) == 1
        assert sf.run("b", lambda: 2, timeout_s=5) == 2


@pytest.fixture(scope="module")
def engine():
    ms = TimeSeriesMemStore()
    ms.setup(Dataset("prometheus"), range(4))
    ms.ingest_routed(
        "prometheus",
        counter_batch(n_series=32, n_samples=120, start_ms=START),
        spread=2,
    )
    return QueryEngine(ms, "prometheus", PlannerParams(deadline_s=30))


def test_engine_coalesces_identical_queries(engine, monkeypatch):
    import filodb_tpu.coordinator.planner as P

    executions = []
    orig = QueryEngine._query_range_uncoalesced

    def spy(self, *a, **k):
        executions.append(a)
        time.sleep(0.2)  # hold the flight open so followers join
        return orig(self, *a, **k)

    monkeypatch.setattr(QueryEngine, "_query_range_uncoalesced", spy)
    s, e = START / 1000 + 400, START / 1000 + 1100
    q = "sum(rate(http_requests_total[5m]))"
    engine.query_range(q, s, e, 60)  # warm (1 execution)
    results = []
    threads = [
        threading.Thread(
            target=lambda: results.append(engine.query_range(q, s, e, 60))
        )
        for _ in range(6)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(results) == 6
    v0 = results[0].grids[0].values_np()
    for r in results[1:]:
        np.testing.assert_array_equal(r.grids[0].values_np(), v0)
    # 1 warm + far fewer than 6 concurrent executions (usually 1)
    assert len(executions) - 1 <= 2


def test_engine_distinct_queries_not_coalesced(engine, monkeypatch):
    executions = []
    orig = QueryEngine._query_range_uncoalesced

    def spy(self, *a, **k):
        executions.append(a[0])
        return orig(self, *a, **k)

    monkeypatch.setattr(QueryEngine, "_query_range_uncoalesced", spy)
    s, e = START / 1000 + 400, START / 1000 + 1100
    engine.query_range("sum(rate(http_requests_total[5m]))", s, e, 60)
    engine.query_range("count(rate(http_requests_total[5m]))", s, e, 60)
    assert len(executions) == 2


def test_coalescing_can_be_disabled(monkeypatch):
    ms = TimeSeriesMemStore()
    ms.setup(Dataset("prometheus"), range(2))
    eng = QueryEngine(ms, "prometheus",
                      PlannerParams(coalesce_identical=False, deadline_s=30))
    called = []
    monkeypatch.setattr(
        SingleFlight, "run",
        lambda self, *a, **k: called.append(1),
    )
    s, e = START / 1000 + 400, START / 1000 + 500
    eng.query_range("up", s, e, 60)
    assert not called
