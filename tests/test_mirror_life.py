"""A host mirror costs nothing until a repair writes it (PERF.md 6, PR 32).

The mirrors are what a live-edge append repair writes in place
(``ST._append_to_parts``). Their life, as held here:

- at a shard's block (``StagedBlock.to_device(keep_host=True)``): where
  ``device_put`` cannot alias host memory the staged numpy arrays ARE the
  mirrors (``aliased``); on the CPU backend they are copies (``copied``).
  Either way a repair equals a fresh restage bit for bit, a reader holding
  the old block keeps its old head and grid, and the old device arrays do
  not change. The CPU backend is made to look like a device with memory of
  its own by uploading from a private copy;
- at a superblock assembled on the device (``ST.build_superblock``): none
  is made (``deferred``); the first live-edge append makes them
  (``materialized``, once per entry) from the members' mirrors while those
  live — also after the members have been repaired past the superblock's
  head — else by one read-back, and then extends, never restages; a walk of
  sliding historical ranges makes none and reads nothing back.

``filodb_stage_mirror_bytes_total{site, how}`` says each. CPU backend, small
shapes. Times nothing.
"""

from __future__ import annotations

import gc

import numpy as np
import pytest

import jax

from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine
from filodb_tpu.core.histograms import PROM_DEFAULT
from filodb_tpu.core.records import SeriesBatch
from filodb_tpu.core.schemas import (Dataset, METRIC_TAG, PROM_COUNTER,
                                     PROM_HISTOGRAM)
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.metrics import REGISTRY
from filodb_tpu.ops import staging as ST
from filodb_tpu.testkit import counter_batch, histogram_batch

BASE = 1_600_000_000_000
INTERVAL = 10_000
N0 = 120  # samples loaded; the head is the next scrape
START = (BASE + 600_000) / 1000
LIVE_END = (BASE + (N0 + 12) * INTERVAL) / 1000  # reaches past the head


def _counter(name: str, **labels) -> float:
    want = set(labels.items())
    with REGISTRY._lock:
        return sum(m.value for (n, ls), m in REGISTRY._metrics.items()
                   if n == name and want <= set(ls))


def _mirror_bytes(site: str) -> dict:
    return {how: _counter("filodb_stage_mirror_bytes", site=site, how=how)
            for how in ("aliased", "copied", "deferred", "materialized")}


def _rows(res) -> dict:
    return {tuple(sorted(lbls.items())): np.asarray(vals)
            for g in res.grids for lbls, vals in zip(g.labels, g.values_np())}


def _assert_same_bits(got, want) -> None:
    a, b = _rows(got), _rows(want)
    assert a.keys() == b.keys() and a
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])


def _assert_close(got, want) -> None:
    a, b = _rows(got), _rows(want)
    assert a.keys() == b.keys() and a
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=2e-5, atol=1e-6,
                                   equal_nan=True)


def _clear_stage_caches(ms, shards) -> None:
    for s in shards:
        sh = ms.shard("ds", s)
        with sh._lock:
            sh._clear_stage_cache()


# -- a shard's block: repair under aliasing -----------------------------------

N_SERIES = 6
KINDS = {
    # kind: (query, grid class)
    "scalar_regular": ("rate(m_ctr[5m])", "regular"),
    "scalar_jittered": ("rate(m_ctr[5m])", "jitter"),
    "histogram_regular": ("histogram_quantile(0.3, rate(m_lat[5m]))", "regular"),
}


class _Feed:
    """``N_SERIES`` series of one shard, one scrape at a time."""

    def __init__(self, kind: str):
        self.kind = kind
        self.rng = np.random.default_rng(3)
        self.last = [None] * N_SERIES
        self.ms = TimeSeriesMemStore()
        self.ms.setup(Dataset("ds"), [0])
        self.scrape(0, N0)

    def scrape(self, first: int, count: int) -> None:
        nominal = BASE + (1 + first + np.arange(count, dtype=np.int64)) * INTERVAL
        b = PROM_DEFAULT.num_buckets
        for i in range(N_SERIES):
            ts = nominal
            if self.kind == "scalar_jittered":
                ts = nominal + np.rint(
                    self.rng.uniform(-0.05, 0.05, count) * INTERVAL).astype(np.int64)
            if self.kind == "histogram_regular":
                incr = self.rng.poisson(2.0, size=(count, b)).astype(np.float64)
                incr[:, -1] = incr.sum(1)
                hist = np.cumsum(np.cumsum(incr, axis=1), axis=0)
                if self.last[i] is not None:
                    hist += self.last[i]
                self.last[i] = hist[-1]
                batch = SeriesBatch(
                    PROM_HISTOGRAM,
                    {METRIC_TAG: "m_lat", "_ws_": "w", "_ns_": "n", "inst": f"h{i}"},
                    ts, {"sum": hist[:, -1] * 0.5, "count": hist[:, -1], "h": hist},
                    bucket_les=PROM_DEFAULT.bounds())
            else:
                vals = np.cumsum(self.rng.uniform(0, 10, count)) + (
                    1e9 if self.last[i] is None else self.last[i])
                self.last[i] = vals[-1]
                batch = SeriesBatch(
                    PROM_COUNTER,
                    {METRIC_TAG: "m_ctr", "_ws_": "w", "_ns_": "n", "inst": f"h{i}"},
                    ts, {"count": vals})
            self.ms.shard("ds", 0).ingest_series(batch)


def _own_memory_put(placement):
    """``ST.series_put`` of a device with memory of its own: the upload never
    shares the numpy memory it was given (the CPU backend's may)."""
    assert placement is None
    return lambda a: jax.device_put(np.array(a, copy=True))


@pytest.mark.parametrize("mirrors", ["aliased", "copied"])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_repair_equals_a_restage_and_leaves_the_old_block_as_it_was(
        kind, mirrors, monkeypatch):
    if mirrors == "aliased":
        monkeypatch.setattr(ST, "_put_may_alias", lambda placement: False)
        monkeypatch.setattr(ST, "series_put", _own_memory_put)
    query, grid = KINDS[kind]
    feed = _Feed(kind)
    shard = feed.ms.shard("ds", 0)
    eng = QueryEngine(feed.ms, "ds", PlannerParams(fused_aggregate=False))
    booked = _mirror_bytes("shard")
    first = eng.query_range(query, START, LIVE_END, 60)
    assert first.grids
    (entry,) = shard.stage_cache.values()
    old = entry.block
    assert ST.grid_class(old) == grid
    # which life the mirrors took, on the block and in /metrics
    other = "copied" if mirrors == "aliased" else "aliased"
    kept = sum(int(m.nbytes) for m in (old.h_ts, old.h_vals, old.h_lens,
                                       old.h_raw, old.h_dev) if m is not None)
    assert old.mirrored == {mirrors: kept, other: 0}
    after = _mirror_bytes("shard")
    assert after[mirrors] == booked[mirrors] + kept
    assert {h: after[h] - booked[h] for h in after if h != mirrors} == {
        other: 0, "deferred": 0, "materialized": 0}
    fields = [f for f in ("ts", "vals", "raw", "lens", "ts_dev")
              if getattr(old, f) is not None]
    held = {f: np.array(getattr(old, f), copy=True) for f in fields}
    head = held["lens"].copy()
    grid_ts = np.array(old.regular_ts if grid == "regular" else old.nominal_ts,
                       copy=True)
    assert (head[:N_SERIES] == head[0]).all() and head[0] < N0

    # the repair waits for the old block's upload before its first write:
    # an aliased mirror is the memory that upload reads
    waited = []
    ready = jax.block_until_ready

    def wait(arrays):
        m = int(head[0])
        waited.append((arrays, old.h_ts[:N_SERIES, m].copy(),
                       old.h_vals[:N_SERIES, m].copy()))
        return ready(arrays)

    monkeypatch.setattr(jax, "block_until_ready", wait)
    feed.scrape(N0, 1)  # one acknowledged scrape, in range
    repaired = eng.query_range(query, START, LIVE_END, 60)
    monkeypatch.setattr(jax, "block_until_ready", ready)
    (arrays, ts_then, vals_then), = [w for w in waited if w[0][0] is old.ts]
    assert arrays == (old.ts, old.vals, old.raw, old.ts_dev)
    assert (ts_then == ST.TS_PAD).all() and not vals_then.any()  # not yet written
    assert (old.h_ts[:N_SERIES, int(head[0])] != ST.TS_PAD).all()  # written since
    assert repaired.stats.cache_extends == 1 and repaired.stats.cache_misses == 0
    (entry,) = shard.stage_cache.values()
    new = entry.block
    assert new is not old and new.h_vals is old.h_vals  # written in place
    assert int(np.asarray(new.lens)[0]) == int(head[0]) + 1
    assert _rows(repaired).keys() == _rows(first).keys()
    assert any((a != b).any() and not np.array_equal(a, b, equal_nan=True)
               for a, b in zip(_rows(repaired).values(), _rows(first).values()))

    # a reader holding the old block: its head, its grid, its device arrays
    np.testing.assert_array_equal(old.h_lens, head)
    np.testing.assert_array_equal(
        old.regular_ts if grid == "regular" else old.nominal_ts, grid_ts)
    for f in fields:
        np.testing.assert_array_equal(np.asarray(getattr(old, f)), held[f], err_msg=f)

    # the same store, staged afresh
    _clear_stage_caches(feed.ms, [0])
    restaged = eng.query_range(query, START, LIVE_END, 60)
    assert restaged.stats.cache_misses == 1
    _assert_same_bits(repaired, restaged)
    (entry,) = shard.stage_cache.values()
    for f in fields:
        got, want = np.asarray(getattr(new, f)), np.asarray(getattr(entry.block, f))
        w = min(got.shape[1], want.shape[1]) if got.ndim > 1 else None
        np.testing.assert_array_equal(got[:, :w] if w else got,
                                      want[:, :w] if w else want, err_msg=f)


def test_the_choice_is_the_platform_of_the_placements_devices():
    class Dev:
        def __init__(self, platform):
            self.platform = platform

    class Mesh:
        def __init__(self, *platforms):
            self.devices = np.array([Dev(p) for p in platforms], dtype=object)

    assert ST._put_may_alias(None) == (jax.devices()[0].platform == "cpu")
    assert ST._put_may_alias(Mesh("cpu", "cpu"))
    assert not ST._put_may_alias(Mesh("tpu", "tpu", "tpu", "tpu"))


# -- a superblock: deferred, then made at the first extension -----------------

N_SHARDS = 4
Q = {"counter": "sum by (job) (rate(http_requests_total[5m]))",
     "hist": "histogram_quantile(0.3, sum by (le) (rate(http_request_latency[5m])))"}


def _store() -> TimeSeriesMemStore:
    ms = TimeSeriesMemStore()
    ms.setup(Dataset("ds"), list(range(N_SHARDS)))
    ms.ingest_routed("ds", counter_batch(n_series=24, n_samples=N0,
                                         start_ms=BASE + INTERVAL), spread=2)
    ms.ingest_routed("ds", histogram_batch(n_series=24, n_samples=N0,
                                           start_ms=BASE + INTERVAL), spread=2)
    return ms


def _scrape(ms, kind: str, k: int) -> None:
    """Scrape ``k`` past the loaded data, of every series (the counters
    restart: a reset at the live edge)."""
    make = counter_batch if kind == "counter" else histogram_batch
    ms.ingest_routed("ds", make(n_series=24, n_samples=1,
                                start_ms=BASE + (1 + N0 + k) * INTERVAL), spread=2)


def _maintenance() -> dict:
    return {o: _counter("filodb_superblock_maintenance", outcome=o)
            for o in ("extend", "restage", "extend_abort")}


def _entry(ms):
    (stored,) = ms._superblock_cache._d.values()
    return stored[1]


@pytest.mark.parametrize("source", ["members", "members_moved_on", "read_back"])
@pytest.mark.parametrize("kind", sorted(Q))
def test_a_superblocks_mirrors_are_made_at_its_first_extension(kind, source):
    ms = _store()
    eng = QueryEngine(ms, "ds")
    ref = QueryEngine(ms, "ds", PlannerParams(fused_aggregate=False))
    query = Q[kind]
    booked, d2h = _mirror_bytes("super"), _counter("filodb_stage_d2h_bytes")
    eng.query_range(query, START, LIVE_END, 60)
    # a histogram column's first build is cached where the second query looks
    stale = eng.query_range(query, START, LIVE_END, 60)
    assert stale.query_log["path"] == "fused"
    block = _entry(ms).block
    waiting = ST._deferred(block)
    assert waiting == [f for f in ("ts", "vals", "raw") if getattr(block, f) is not None]
    assert block.h_ts is None and block.h_vals is None and block.h_raw is None
    spared = sum(int(getattr(block, f).nbytes) for f in waiting)
    built = _mirror_bytes("super")
    assert {h: built[h] - booked[h] for h in built} == {
        "aliased": 0, "copied": 0, "deferred": spared, "materialized": 0}

    if source == "members_moved_on":
        # the reference tree repairs the shards' blocks (same stage-cache
        # keys) past the head the superblock was built at
        _scrape(ms, kind, 0)
        moved = ref.query_range(query, START, LIVE_END, 60)
        assert moved.stats.cache_extends == N_SHARDS
    elif source == "read_back":
        _clear_stage_caches(ms, range(N_SHARDS))  # the members are evicted
        gc.collect()
    if source != "members_moved_on":
        _scrape(ms, kind, 0)
    events = _maintenance()
    fresh = eng.query_range(query, START, LIVE_END, 60)
    assert fresh.query_log["path"] == "fused"
    assert _maintenance() == dict(events, extend=events["extend"] + 1)
    made = _mirror_bytes("super")
    assert {h: made[h] - built[h] for h in made} == {
        "aliased": 0, "copied": 0, "deferred": 0, "materialized": spared}
    assert _counter("filodb_stage_d2h_bytes") == d2h + (
        spared if source == "read_back" else 0)
    assert _rows(fresh).keys() == _rows(stale).keys()
    assert not all(np.array_equal(a, b, equal_nan=True)
                   for a, b in zip(_rows(fresh).values(), _rows(stale).values()))
    _assert_close(fresh, ref.query_range(query, START, LIVE_END, 60))
    extended = _entry(ms).block
    assert extended is not block and ST._deferred(extended) == []

    # every later extension finds them
    _scrape(ms, kind, 1)
    again = eng.query_range(query, START, LIVE_END, 60)
    assert _maintenance() == dict(events, extend=events["extend"] + 2)
    assert _mirror_bytes("super") == made
    assert _counter("filodb_stage_d2h_bytes") == d2h + (
        spared if source == "read_back" else 0)
    assert _entry(ms).block.h_vals is extended.h_vals
    _assert_close(again, ref.query_range(query, START, LIVE_END, 60))

    # the same store staged afresh answers the same, to the bit
    ms._superblock_cache = None
    _clear_stage_caches(ms, range(N_SHARDS))
    _assert_same_bits(again, eng.query_range(query, START, LIVE_END, 60))


@pytest.mark.parametrize("kind", sorted(Q))
def test_a_walk_of_sliding_ranges_makes_no_mirror_and_reads_nothing_back(kind):
    ms = _store()
    eng = QueryEngine(ms, "ds")
    ref = QueryEngine(ms, "ds", PlannerParams(fused_aggregate=False))
    booked, d2h = _mirror_bytes("super"), _counter("filodb_stage_d2h_bytes")
    shard_booked, events = _mirror_bytes("shard"), _maintenance()
    spared = 0
    for step in range(5):
        start = START + 60 * step
        got = eng.query_range(Q[kind], start, start + 300, 60)
        assert got.query_log["path"] == "fused"
        assert got.stats.cache_hits == 0  # every request misses
        blocks = [stored[1].block for stored in ms._superblock_cache._d.values()]
        spared += sum(int(getattr(blocks[-1], f).nbytes)
                      for f in ST._deferred(blocks[-1]))
        assert all(b.h_vals is None and b.h_ts is None for b in blocks)
        _assert_close(got, ref.query_range(Q[kind], start, start + 300, 60))
    after = _mirror_bytes("super")
    assert {h: after[h] - booked[h] for h in after} == {
        "aliased": 0, "copied": 0, "deferred": spared, "materialized": 0}
    assert spared > 0
    assert _counter("filodb_stage_d2h_bytes") == d2h
    assert _maintenance() == events
    # the shards' blocks under it: copies on this backend, never deferred
    shards_after = _mirror_bytes("shard")
    assert shards_after["copied"] > shard_booked["copied"]
    assert {h: shards_after[h] - shard_booked[h]
            for h in ("aliased", "deferred", "materialized")} == {
        "aliased": 0, "deferred": 0, "materialized": 0}
