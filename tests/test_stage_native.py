"""A cold histogram stage is one pass over a shard's chunks (PERF.md 6, PR 35).

``ST.stage_from_shard`` stages a histogram selection from a table of chunk
segments and one native call (``native/stage.cpp``) wherever the library is
loaded and the arrays can be read in place; everything else takes the Python
tier (``samples_in_range`` a series, then ``stage_histogram_series``), which
is also what every native block is held to here, bit for bit: ``ts``,
``vals``, ``lens``, ``baseline``, the grids and ``part_refs``.
``filodb_stage_gather_series_total{how}`` says which path staged how many
series. CPU backend, small shapes. Times nothing.
"""

from __future__ import annotations

import gc
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from filodb_tpu import native
from filodb_tpu.core.histograms import custom_buckets
from filodb_tpu.core.records import SeriesBatch
from filodb_tpu.core.schemas import (Dataset, METRIC_TAG, PROM_COUNTER,
                                     PROM_HISTOGRAM)
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.memstore.partition import ColumnArrays
from filodb_tpu.memstore.shard import StoreConfig
from filodb_tpu.metrics import REGISTRY
from filodb_tpu.ops import staging as ST

BASE = 1_600_000_000_000
INTERVAL = 10_000
CHUNK = 50       # samples a sealed chunk
LOADED = 130     # two sealed chunks and a write buffer of 30
SCHEME = custom_buckets([0.1 * i for i in range(1, 8)])
FIELDS = ("ts", "vals", "lens", "baseline", "regular_ts", "nominal_ts", "ts_dev")

@pytest.fixture
def stage_library():
    if native.stage_lib() is None:
        pytest.skip(f"libfilodbstage: {native.tiers()['filodbstage']}")


needs_native = pytest.mark.usefixtures("stage_library")


def _counter(how: str) -> float:
    with REGISTRY._lock:
        return sum(m.value for (n, ls), m in REGISTRY._metrics.items()
                   if n == "filodb_stage_gather_series" and ("how", how) in ls)


def _staged_by() -> dict:
    return {how: _counter(how) for how in ("native", "python")}


class _Shard:
    """One shard of ``n`` histogram series; series ``i`` has ``lengths[i]``
    scrapes from ``BASE``, jittered by ``jitter`` of an interval."""

    def __init__(self, n: int, lengths=None, jitter: float = 0.0,
                 offset: float = 0.0, seed: int = 5):
        self.rng = np.random.default_rng(seed)
        self.n, self.jitter, self.offset = n, jitter, offset
        self.ms = TimeSeriesMemStore(StoreConfig(max_chunk_size=CHUNK))
        self.ms.setup(Dataset("ds"), [0])
        self.shard = self.ms.shard("ds", 0)
        self.last = [None] * n
        self.count = [0] * n
        for i in range(n):
            self.scrape(i, LOADED if lengths is None else lengths[i])
        self.pids = np.array(sorted(self.shard.partitions))

    def scrape(self, i: int, count: int) -> None:
        b = SCHEME.num_buckets
        first = self.count[i]
        ts = BASE + (first + np.arange(count, dtype=np.int64)) * INTERVAL
        if self.jitter:
            ts = ts + np.rint(self.rng.uniform(-self.jitter, self.jitter, count)
                              * INTERVAL).astype(np.int64)
        incr = self.rng.poisson(2.0, size=(count, b)).astype(np.float64)
        hist = np.cumsum(np.cumsum(incr, axis=1), axis=0) + (
            self.offset if self.last[i] is None else self.last[i])
        self.last[i], self.count[i] = hist[-1], first + count
        self.shard.ingest_series(SeriesBatch(
            PROM_HISTOGRAM,
            {METRIC_TAG: "m_lat", "_ws_": "w", "_ns_": "n", "inst": f"h{i:04d}"},
            ts, {"sum": hist[:, -1] * 0.5, "count": hist[:, -1], "h": hist},
            bucket_les=SCHEME.bounds()))

    def part(self, i: int):
        return self.shard.partition(int(self.pids[i]))

    def stage(self, lo: int, hi: int, mode: str = "corrected"):
        """Scrapes ``lo`` to ``hi`` (both in), by whichever path applies."""
        return ST.stage_from_shard(self.shard, self.pids, "h",
                                   BASE + lo * INTERVAL - INTERVAL // 2,
                                   BASE + hi * INTERVAL + INTERVAL // 2, mode=mode)

    def stage_python(self, lo: int, hi: int, mode: str = "corrected"):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(native, "stage_lib", lambda: None)
            return self.stage(lo, hi, mode)


def _assert_same_block(got, want) -> None:
    for f in FIELDS:
        a, b = getattr(got, f), getattr(want, f)
        assert (a is None) == (b is None), f
        if a is not None:
            assert (a.dtype, a.shape) == (b.dtype, b.shape), f
            assert a.tobytes() == b.tobytes(), f
    assert (got.part_refs, got.n_series, got.base_ms, got.maxdev_ms) == (
        want.part_refs, want.n_series, want.base_ms, want.maxdev_ms)
    assert got.raw is None and want.raw is None


def _natively(fn, n: int):
    """``fn()`` staged ``n`` series, all of them by the native pass."""
    before = _staged_by()
    block = fn()
    after = _staged_by()
    assert (after["native"] - before["native"],
            after["python"] - before["python"]) == (n, 0)
    return block


# -- the block is the Python tier's, bit for bit ------------------------------

RANGES = {
    # name: (first scrape, last scrape) of LOADED = 130 in chunks of 50
    "inside_one_sealed_chunk": (55, 95),
    "two_sealed_chunks": (20, 80),
    "a_chunk_and_the_write_buffer": (70, 129),
    "only_the_write_buffer": (105, 125),
    "everything_and_past_both_ends": (-10, 200),
    "one_sample": (60, 60),
}


@needs_native
@pytest.mark.parametrize("mode", ["raw", "corrected"])
@pytest.mark.parametrize("rng", sorted(RANGES))
def test_native_block_equals_the_python_tiers(rng, mode):
    s = _Shard(7)
    lo, hi = RANGES[rng]
    got = _natively(lambda: s.stage(lo, hi, mode), 7)
    want = s.stage_python(lo, hi, mode)
    _assert_same_block(got, want)
    assert got.regular_ts is not None and int(got.lens[0]) == min(hi, 129) - max(lo, 0) + 1
    assert (np.asarray(got.baseline) != 0).any() == (mode == "corrected")


@needs_native
@pytest.mark.parametrize("n", [1, 7, 1000])
def test_series_and_time_padding(n):
    s = _Shard(n)
    got = _natively(lambda: s.stage(3, 140), n)
    _assert_same_block(got, s.stage_python(3, 140))
    assert got.shape == (ST.pad_series(n), ST.pad_time(127))
    assert (got.lens[n:] == 0).all() and (got.ts[n:] == ST.TS_PAD).all()
    assert not got.vals[n:].any() and not got.vals[:, 127:].any()


SHAPES = {
    # ragged: some series start later, stop earlier, or hold nothing in range
    "ragged_lengths": dict(lengths=[130, 40, 99, 130, 7, 51, 100]),
    "some_rows_empty": dict(lengths=[130, 20, 130, 10, 130, 130, 5]),
    # a near-regular grid: nominal_ts + ts_dev
    "jittered": dict(jitter=0.05),
    # f64 -> f32 rounds: 1e9 + small counts
    "values_that_round": dict(offset=1e9 + 0.3),
}


@needs_native
@pytest.mark.parametrize("mode", ["raw", "corrected"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_ragged_jittered_and_rounding_blocks(shape, mode):
    s = _Shard(7, **SHAPES[shape])
    got = _natively(lambda: s.stage(30, 120, mode), 7)
    want = s.stage_python(30, 120, mode)
    _assert_same_block(got, want)
    if shape == "jittered":
        assert got.nominal_ts is not None and got.ts_dev is not None
    if shape == "some_rows_empty":
        assert sorted(np.asarray(got.lens[:7]).tolist())[:3] == [0, 0, 0]
    if shape == "values_that_round" and mode == "raw":
        exact = np.concatenate([s.part(0).chunks[0].arrays["h"][30:],
                                s.part(0).chunks[1].arrays["h"],
                                s.part(0)._buf["h"][:21]])
        assert (got.vals[0, :91].astype(np.float64) != exact).any()


@needs_native
@pytest.mark.parametrize("mode", ["raw", "corrected"])
def test_an_encoded_only_chunk_is_decoded_and_read_in_place(mode):
    s = _Shard(7)
    want = s.stage_python(20, 129, mode)
    for i in (0, 3, 6):  # int64 after decoding, beside f64 chunks
        s.part(i).chunks[0].drop_decoded(PROM_HISTOGRAM)
        s.part(i).chunks[1 if i else 0].drop_decoded(PROM_HISTOGRAM)
    assert s.part(0).chunks[0].arrays is None
    assert s.part(0).chunks[0].column("h").dtype == np.int64
    got = _natively(lambda: s.stage(20, 129, mode), 7)
    _assert_same_block(got, s.stage_python(20, 129, mode))
    _assert_same_block(got, want)  # the counts are whole: decoding loses nothing


def test_beyond_the_data_is_the_python_tiers_empty_block():
    s = _Shard(7)
    before = _staged_by()
    got = s.stage(500, 600)
    assert _staged_by()["python"] - before["python"] == 7
    _assert_same_block(got, s.stage_python(500, 600))
    assert got.vals.shape == (8, 128, SCHEME.num_buckets) and not got.lens.any()


# -- what the pass holds while it reads ---------------------------------------

@needs_native
def test_a_chunk_evicted_between_table_and_call(monkeypatch):
    s = _Shard(7)
    want = s.stage(20, 129)
    measure = native.stage_measure

    def evict_then_measure(*a):
        for i in range(7):
            p = s.part(i)
            p.chunks[1].drop_decoded(PROM_HISTOGRAM)
            p.mark_flushed(p.chunks[0].end_ts)
            assert p.drop_flushed_chunks() > 0
            p.switch_buffers()  # and the buffer the table named is dropped
        gc.collect()
        junk = [np.full((CHUNK, SCHEME.num_buckets), -1.0) for _ in range(64)]
        del junk
        return measure(*a)

    monkeypatch.setattr(native, "stage_measure", evict_then_measure)
    got = _natively(lambda: s.stage(20, 129), 7)
    _assert_same_block(got, want)
    monkeypatch.undo()
    assert len(s.part(0).chunks) == 2 and s.part(0)._buf is None
    after = s.stage(20, 129)  # what is left: from scrape 50 on
    assert int(after.lens[0]) == 80


@needs_native
@pytest.mark.time_limit(300)
def test_a_seal_racing_the_stage_loses_no_row_and_counts_none_twice():
    """Ingest appends and seals (every 50 scrapes a series) while 200 stages
    read a range that starts in a sealed chunk and ends in what is, when
    the race starts, the write buffer: rows move from buffer to chunk under
    the reader, and every block equals the quiescent one."""
    n = 24
    s = _Shard(n)
    lo, hi = 70, 129
    want = s.stage(lo, hi)
    want_py = s.stage_python(lo, hi)
    _assert_same_block(want, want_py)
    stop = threading.Event()
    failed = []

    def ingest():
        try:
            while not stop.is_set() and s.count[0] < 20_000:
                for i in range(n):
                    s.scrape(i, 7)
        except Exception as e:  # noqa: BLE001 — reported by the test
            failed.append(e)

    t = threading.Thread(target=ingest, daemon=True)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # the two threads change places all the time
    t.start()
    try:
        for k in range(200):
            got = s.stage(lo, hi) if k % 4 else s.stage_python(lo, hi)
            _assert_same_block(got, want)
    finally:
        sys.setswitchinterval(interval)
        stop.set()
        t.join(60)
    assert not t.is_alive() and not failed
    assert s.count[0] > LOADED + CHUNK  # seals did happen
    _assert_same_block(s.stage(lo, hi), want)


# -- which path: what the code observes ---------------------------------------

def test_without_the_library_the_python_tier_stages_and_says_so(monkeypatch):
    s = _Shard(7)
    monkeypatch.setattr(native, "stage_lib", lambda: None)
    before = _staged_by()
    got = s.stage(20, 129)
    after = _staged_by()
    assert (after["native"] - before["native"],
            after["python"] - before["python"]) == (0, 7)
    assert got.vals.shape == (8, 128, SCHEME.num_buckets) and int(got.lens[0]) == 110


def test_a_scalar_column_takes_the_python_tier(monkeypatch):
    s = _Shard(3)
    for i in range(3):
        ts = BASE + np.arange(LOADED, dtype=np.int64) * INTERVAL
        s.shard.ingest_series(SeriesBatch(
            PROM_COUNTER,
            {METRIC_TAG: "m_ctr", "_ws_": "w", "_ns_": "n", "inst": f"c{i}"},
            ts, {"count": np.cumsum(np.ones(LOADED))}))
    counters = np.array([p for p in sorted(s.shard.partitions) if p not in s.pids])
    called = []
    monkeypatch.setattr(native, "stage_measure", lambda *a: called.append(a))
    before = _staged_by()
    block = ST.stage_from_shard(s.shard, counters, "count", BASE, BASE + 10**6,
                                mode="corrected")
    # a histogram's scalar column too: the decision is the column's type
    ST.stage_from_shard(s.shard, s.pids, "sum", BASE, BASE + 10**6, mode="raw")
    after = _staged_by()
    assert not called and block.vals.ndim == 2
    assert (after["native"] - before["native"],
            after["python"] - before["python"]) == (0, 6)


@needs_native
@pytest.mark.parametrize("odd", ["bucket_width", "dtype", "strided"])
def test_arrays_the_pass_cannot_read_in_place_take_the_python_tier(odd):
    s = _Shard(3)
    arrays = s.part(1).chunks[1].arrays
    h = arrays["h"]
    swap = {"bucket_width": np.ascontiguousarray(h[:, :-2]),
            "dtype": h.astype(np.float32),
            "strided": np.asfortranarray(h)}[odd]
    s.part(1).chunks[1].arrays = ColumnArrays({**arrays, "h": swap})
    assert ("h" in s.part(1).chunks[1].arrays.segments) == (odd == "bucket_width")
    before = _staged_by()
    if odd == "bucket_width":  # the Python tier cannot pad two widths either
        with pytest.raises(ValueError):
            s.stage(55, 95, "raw")
    else:
        _assert_same_block(s.stage(55, 95, "raw"), s.stage_python(55, 95, "raw"))
    after = _staged_by()
    assert (after["native"] - before["native"]) == 0
    assert (after["python"] - before["python"]) >= 3


def test_column_arrays_name_only_what_the_pass_reads():
    ts = np.arange(5, dtype=np.int64)
    a = ColumnArrays({"timestamp": ts, "count": np.ones(5), "h": np.ones((5, 3)),
                      "hi": np.ones((5, 3), dtype=np.int64)})
    assert sorted(a.segments) == ["h", "hi"]
    t_addr, v_addr, rows, width, ints = a.segments["hi"]
    assert (t_addr, v_addr) == (ts.ctypes.data, a["hi"].ctypes.data)
    assert (rows, width, ints) == (5, 3, True) and not a.segments["h"][4]
    assert ColumnArrays({"timestamp": ts[::2], "h": np.ones((3, 2))}).segments == {}
    assert ColumnArrays({"h": np.ones((3, 2))}).segments == {}


# -- loaded where the store is made, never inside a query ---------------------

def test_the_library_is_loaded_with_the_store_not_by_a_stage():
    out = subprocess.run(
        [sys.executable, "-c",
         "from filodb_tpu import native\n"
         "from filodb_tpu.ops import staging\n"
         "print(native.LIBS['filodbstage'].status)\n"
         "from filodb_tpu.memstore.memstore import TimeSeriesMemStore\n"
         "TimeSeriesMemStore()\n"
         "print(native.LIBS['filodbstage'].status)"],
        capture_output=True, text=True, timeout=110,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    before, after = out.stdout.strip().splitlines()[-2:]
    assert before == "not loaded" and after != "not loaded"  # a tier, either one
