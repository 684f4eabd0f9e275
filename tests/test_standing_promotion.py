"""Which recurring keys the standing promoter takes for a live-edge
dashboard (``StandingEngine.promote_tick``): the end of the range has to
FOLLOW the wall clock, not merely lie near it. A fixed range that ended a
moment ago — a load generator repeating one query over the newest scrape, a
panel pinned to an absolute range — recurs like a dashboard and lags like
one for ``promote_live_lag_ms``, but no append will ever reach it: promoting
it restages its superblock over the aligned range in the background, beside
the clients it was meant to relieve, for state nobody reads. (And whether
that happened used to depend on how long the server had been up when the
burst came.)"""

import time

import pytest

from filodb_tpu.coordinator.planner import QueryEngine
from filodb_tpu.core.schemas import Dataset
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.standing import StandingEngine
from filodb_tpu.testkit import counter_batch

pytestmark = pytest.mark.standing

Q = "sum by (instance) (rate(http_requests_total[5m]))"
STEP_MS = 15_000
CFG = {"promote_min_count": 8, "promote_window_s": 120.0,  # as shipped
       "default_span_ms": 600_000}


def _engine():
    ms = TimeSeriesMemStore()
    ms.setup(Dataset("ds"), list(range(4)))
    now_ms = int(time.time() * 1000)
    ms.ingest_routed(
        "ds", counter_batch(n_series=12, n_samples=120,
                            start_ms=now_ms - 1_200_000), spread=3)
    eng = QueryEngine(ms, "ds")
    return eng, StandingEngine(eng, CFG), now_ms


def _poll(eng, end_ms):
    eng.query_range(Q, (end_ms - 600_000) / 1e3, end_ms / 1e3, STEP_MS / 1e3)


def test_the_ring_keeps_the_first_sighting_beside_the_latest():
    eng, se, now_ms = _engine()
    _poll(eng, now_ms - STEP_MS)
    _poll(eng, now_ms)
    (_key, e), = se.scheduler.key_ring.entries()
    assert e["count"] == 2
    assert e["first_desc"]["end_ms"] == now_ms - STEP_MS
    assert e["desc"]["end_ms"] == now_ms
    assert e["desc"]["end_lag_ms"] == pytest.approx(0, abs=60_000)


@pytest.mark.parametrize("name, ends_back_ms, promoted", [
    # a dashboard: every poll ends later than the one before
    ("follows", [7, 6, 5, 4, 3, 2, 1, 0], 1),
    # an end floored to the step: it repeats, then moves
    ("follows_by_steps", [2, 2, 2, 1, 1, 1, 0, 0], 1),
    # the same end every time: near the edge, bursting, standing still
    ("stands_still", [0] * 8, 0),
    ("stands_still_a_minute_back", [4] * 12, 0),
    # it moved once, but there are too few sightings for a burst
    ("too_few", [1, 0], 0),
])
def test_promotion_needs_an_end_that_follows_the_clock(name, ends_back_ms, promoted):
    eng, se, now_ms = _engine()
    for back in ends_back_ms:
        _poll(eng, now_ms - back * STEP_MS)
    assert se.promote_tick() == promoted
    assert len(se.registry.list()) == promoted
    if promoted:
        assert se.registry.list()[0].source == "promoted"


def test_a_still_end_is_not_remembered_as_demoted():
    """Standing still is a property of the traffic so far, not of the key:
    the same panel switched to a live range promotes at its next burst."""
    eng, se, now_ms = _engine()
    for _ in range(8):
        _poll(eng, now_ms - 4 * STEP_MS)
    (key, _e), = se.scheduler.key_ring.entries()
    assert se.promote_tick() == 0
    assert se.registry.demoted_reason(key) is None
    for back in range(7, -1, -1):
        _poll(eng, now_ms - back * STEP_MS // 2)
    assert se.promote_tick() == 1


def test_a_repeated_newest_range_restages_nothing_in_the_background():
    """The shape of the benchmark's ``counters.repeat``: clients repeat one
    range over the newest scrape while the maintainer's loop runs."""
    from filodb_tpu.metrics import REGISTRY

    eng, se, now_ms = _engine()
    before = REGISTRY.counter("filodb_standing_promotions", event="promote").value
    se.start()
    try:
        for _ in range(40):
            _poll(eng, now_ms)
        assert se.promote_tick() == 0
    finally:
        se.stop()
    assert REGISTRY.counter("filodb_standing_promotions",
                            event="promote").value == before
    assert not se.registry.list()
