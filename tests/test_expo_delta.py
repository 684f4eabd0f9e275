"""OpenTelemetry exponential histograms under DELTA temporality, a scheme a
point (configuration ``otel-expo-delta-hists``).

At ingest, the bulk form of a scheme a record (``core.histograms.Base2Rows``):
its join and its remap are the store's helpers', one batch of it stores what
one batch a point stores, a point finer than its series' scheme is merged
down exactly and one outside it widens it. In the program, the base-2 body's
window sums are the plain reference's whole counts bit for bit (one exact
product with each window's 0/1 membership), the factor comes after the sum, every launch books how it read its windows, and the
four panels answer as the reference ``expo_delta_panels`` does to the cell's
limits, fused and tree alike. The generator and the reference stand without
the program. CPU backend, small shapes."""

from __future__ import annotations

import ast
import os

import jax
import numpy as np
import pytest

from benchmarks.chip import (
    expo_delta_histograms, expo_delta_panels, expo_histograms, expo_panels,
    references, result_line,
)
from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine
from filodb_tpu.core.histograms import (
    BASE2_WIDTH, Base2Rows, Base2Scheme, base2_index, base2_remap, fit_base2,
    merge_base2,
)
from filodb_tpu.core.records import RecordBatch, SeriesBatch
from filodb_tpu.core.schemas import METRIC_TAG, OTEL_EXP_DELTA_HISTOGRAM, Dataset
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.metrics import REGISTRY
from filodb_tpu.ops import aggregations as AGG
from filodb_tpu.ops import staging as ST

BASE = 1_600_000_000_000
INTERVAL = 10_000
WINDOW = 300_000
CONFIG = {
    "samples_per_series": 120, "services": 8,
    "median_s": [0.002, 0.5], "sigma": [0.2, 1.5], "per_scrape": [5, 200],
    "timeout": {"services": 1, "share": 0.01, "range_s": [1, 30]},
    "max_buckets": 160, "interval_ms": INTERVAL, "metric": "lat",
}
TRAFFIC = result_line.chip_json("workloads", "expo_delta.repeat.json")
PANELS = [dict(p, query=p["query"].replace("http_server_request_duration_seconds", "lat"))
          for p in TRAFFIC["panels"]]
START_MS = BASE + 400_000
OUT_T = START_MS + np.arange(11, dtype=np.int64) * 60_000
TAGS = {METRIC_TAG: "lat", "_ws_": "w", "_ns_": "n", "instance": "h0"}


def _counter(name: str, **labels) -> float:
    want = set(labels.items())
    with REGISTRY._lock:
        return sum(m.value for (n, ls), m in REGISTRY._metrics.items()
                   if n == name and want <= set(ls))


def _random_schemes(seed: int, R: int = 60) -> list:
    """A delta stream's schemes: scales 2-9 that rise and fall, ranges
    that wander, an empty point among them."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(R):
        s = int(rng.integers(2, 10))
        lo = int(rng.integers(-12, -4)) << s
        out.append(Base2Scheme(s, lo + int(rng.integers(0, 5)), int(rng.integers(1, 161))))
    out[R // 2] = Base2Scheme(20, 0, 0)
    return out


def _counts(schemes, seed: int) -> np.ndarray:
    """[R, 162] whole counts, cumulative over each point's own buckets."""
    rng = np.random.default_rng(seed)
    h = np.zeros((len(schemes), BASE2_WIDTH))
    for r, s in enumerate(schemes):
        c = np.cumsum(rng.poisson(1.5, s.n))
        h[r, 1:s.n + 1] = c
        h[r, s.n + 1] = c[-1] if s.n else 0
    return h


def _rows(schemes) -> Base2Rows:
    return Base2Rows(*zip(*((s.scale, s.offset, s.n) for s in schemes)))


# -- ingest: a scheme a point ------------------------------------------------


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_bulk_form_joins_and_remaps_as_the_helpers_do(seed):
    schemes = _random_schemes(seed)
    rows = _rows(schemes)
    join = rows.join()
    assert join == fit_base2(merge_base2(schemes))
    assert rows.single() is None and _rows(schemes[:1] * 3).single() == schemes[0]
    idx = rows.index(join)
    for r, s in enumerate(schemes):
        assert np.array_equal(idx[r], base2_index(s, join))
    assert np.array_equal(rows[5:9].scale, rows.scale[5:9])
    with pytest.raises(ValueError):
        _rows([Base2Scheme(join.scale - 1, 0, 4)]).index(join)


def _batch(schemes, h, tags=TAGS, t0: int = 0):
    T = len(schemes)
    return RecordBatch(
        OTEL_EXP_DELTA_HISTOGRAM, BASE + (t0 + np.arange(T, dtype=np.int64)) * INTERVAL,
        {"sum": h[:, -1] * 0.01, "count": np.arange(T, dtype=np.float64), "h": h},
        [tags] * T, bucket_schemes=_rows(schemes))


def _store(shards=(0,)):
    ms = TimeSeriesMemStore()
    ms.setup(Dataset("ds"), list(shards))
    return ms


@pytest.mark.parametrize("seed", [4, 5])
def test_one_batch_of_rows_stores_what_one_batch_a_point_stores(seed):
    schemes = _random_schemes(seed)
    h = _counts(schemes, seed)
    bulk, by_point = _store(), _store()
    runs = _batch(schemes, h).group_by_series()
    assert len(runs) == 1 and runs[0].bucket_rows is not None
    assert runs[0].bucket_scheme == fit_base2(merge_base2(schemes))
    merged = _counter("filodb_ingest_scheme_merged")
    assert bulk.ingest_routed("ds", _batch(schemes, h), 0) == len(schemes)
    finer = sum(s.scale > runs[0].bucket_scheme.scale for s in schemes)
    assert finer > 0 and _counter("filodb_ingest_scheme_merged") == merged + finer
    for t, s in enumerate(schemes):
        by_point.shard("ds", 0).ingest_series(SeriesBatch(
            OTEL_EXP_DELTA_HISTOGRAM, TAGS, np.array([BASE + t * INTERVAL]),
            {"sum": h[t:t + 1, -1] * 0.01, "count": np.array([float(t)]),
             "h": h[t:t + 1]}, bucket_scheme=s))
    (a,), (b,) = (m.shard("ds", 0).partitions.values() for m in (bulk, by_point))
    assert a.bucket_scheme == b.bucket_scheme == runs[0].bucket_scheme
    ta, va = a.samples_in_range(BASE, BASE + 10**9, "h")
    tb, vb = b.samples_in_range(BASE, BASE + 10**9, "h")
    assert np.array_equal(ta, tb) and np.array_equal(va, vb)
    want = np.stack([base2_remap(h[t], s, a.bucket_scheme) for t, s in enumerate(schemes)])
    assert np.array_equal(va, want)


def test_a_finer_point_is_merged_down_and_one_outside_widens_the_join():
    first = [Base2Scheme(3, -40, 60), Base2Scheme(4, -70, 90), Base2Scheme(3, -30, 20)]
    h = _counts(first, 7)
    ms = _store()
    shard = ms.shard("ds", 0)
    ms.ingest_routed("ds", _batch(first, h), 0)
    (part,) = shard.partitions.values()
    join = part.bucket_scheme
    assert join == Base2Scheme(3, -40, 60)
    # finer and inside: merged down onto the join, no move
    fine = Base2Scheme(6, -300, 100)
    row = _counts([fine], 8)
    moved, merged = _counter("filodb_ingest_scheme_moved"), _counter("filodb_ingest_scheme_merged")
    ms.ingest_routed("ds", _batch([fine], row, t0=3), 0)
    assert part.bucket_scheme == join
    assert _counter("filodb_ingest_scheme_moved") == moved
    assert _counter("filodb_ingest_scheme_merged") == merged + 1
    _, got = part.samples_in_range(BASE + 3 * INTERVAL, BASE + 3 * INTERVAL, "h")
    assert np.array_equal(got[0], base2_remap(row[0], fine, join))
    assert got[0][-1] == row[0][fine.width - 1]  # every observation kept
    # outside the join: it widens, and every earlier point reads on the new one
    wide = Base2Scheme(3, 10, 30)
    row2 = _counts([wide], 9)
    ms.ingest_routed("ds", _batch([wide], row2, t0=4), 0)
    new = fit_base2(merge_base2([join, wide]))
    assert part.bucket_scheme == new != join
    assert _counter("filodb_ingest_scheme_moved") == moved + 1
    _, vals = part.samples_in_range(BASE, BASE + 10**9, "h")
    want = [base2_remap(r, s, new) for r, s in zip(
        np.concatenate([h, row, row2]), first + [fine, wide])]
    assert np.array_equal(vals, np.stack(want))


def test_rows_ride_the_shard_split():
    schemes = _random_schemes(6, R=20)
    h = _counts(schemes, 6)
    tags = [dict(TAGS, instance=f"h{i % 5}") for i in range(20)]
    batch = RecordBatch(OTEL_EXP_DELTA_HISTOGRAM, BASE + np.arange(20, dtype=np.int64),
                        {"h": h}, tags, bucket_schemes=_rows(schemes))
    split = batch.shard_split(2, 4)
    assert len(split) > 1
    for sub in split.values():
        assert isinstance(sub.bucket_schemes, Base2Rows)
        for i in range(len(sub)):
            j = int(np.nonzero(batch.timestamps == sub.timestamps[i])[0][0])
            assert (sub.bucket_schemes.scale[i], sub.bucket_schemes.offset[i],
                    sub.bucket_schemes.n[i]) == (schemes[j].scale, schemes[j].offset,
                                                 schemes[j].n)


# -- the generator and the reference, without the program ---------------------


@pytest.fixture(scope="module")
def data():
    return expo_delta_histograms.make(CONFIG, 64, np.random.default_rng(2147420001), BASE)


@pytest.mark.parametrize("name", ["expo_delta_panels.py", "expo_delta_histograms.py"])
def test_the_reference_imports_nothing_of_the_program(name):
    tree = ast.parse(open(os.path.join(result_line.ROOT, "benchmarks", "chip", name)).read())
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    mods = [getattr(n, "module", None) or n.names[0].name for n in top]
    assert not any(m.startswith("filodb_tpu") for m in mods), (name, mods)


def test_each_point_is_its_own_intervals_aggregation(data):
    w = data.row_width
    assert (w >= 2).all() and (w <= BASE2_WIDTH).all()
    empty = w == 2
    assert empty.any() and (data.row_scale[empty] == 20).all()
    S, T = w.shape
    for i in range(0, S, 7):
        for t in range(T):
            row = data.hist[i, t]
            assert row[0] == 0 and not row[w[i, t]:].any()
            if w[i, t] > 2:  # the range is the interval's: first and last bucket used
                assert row[1] > 0 and row[w[i, t] - 1] == row[w[i, t] - 2] > row[w[i, t] - 3]
    moves = np.diff(data.row_scale.astype(np.int64), axis=1)
    assert (moves > 0).any() and (moves < 0).any()  # the scale rises and falls
    assert data.buckets == pytest.approx(data.width.mean())


def test_each_series_is_held_on_the_join_of_its_points(data):
    for i in range(data.n_series):
        pts = [Base2Scheme(int(a), int(b), int(c) - 2) for a, b, c in
               zip(data.row_scale[i], data.row_offset[i], data.row_width[i])]
        assert Base2Scheme(int(data.scale[i]), int(data.offset[i]),
                           int(data.width[i]) - 2) == fit_base2(merge_base2(pts))
    assert (data.row_scale > data.scale[:, None]).mean() > 0.5  # most points merge down


def test_a_window_is_the_sum_of_its_points(data):
    win = expo_delta_panels.window_sums(data, OUT_T, WINDOW)
    for i in (0, 17, 63):
        s = Base2Scheme(int(data.scale[i]), int(data.offset[i]), int(data.width[i]) - 2)
        on = np.stack([base2_remap(data.hist[i, t].astype(np.float64), Base2Scheme(
            int(data.row_scale[i, t]), int(data.row_offset[i, t]), int(data.row_width[i, t]) - 2),
            s, BASE2_WIDTH) for t in range(data.hist.shape[1])])
        for j, t in enumerate(OUT_T):
            inside = (data.ts > t - WINDOW) & (data.ts <= t)
            assert inside.sum() == 30
            assert np.array_equal(win[i, j, :s.width], on[inside].sum(0)[:s.width])
    same = expo_delta_panels.reference(data, OUT_T, WINDOW, PANELS[2], quantize=lambda x: x)
    want = expo_delta_panels.reference(data, OUT_T, WINDOW, PANELS[2])
    assert all(np.array_equal(want[k], same[k], equal_nan=True) for k in want)


# -- the program against the reference ------------------------------------------


class _Fleet:
    def __init__(self, data):
        self.data = data
        self.ms = TimeSeriesMemStore()
        self.ms.setup(Dataset("prometheus"), list(range(4)))
        assert data.load(self.ms, 2) == data.n_samples
        self.fused = QueryEngine(self.ms, "prometheus")
        self.tree = QueryEngine(self.ms, "prometheus", PlannerParams(fused_aggregate=False))

    def answer(self, engine, query) -> dict:
        res = engine.query_range(query, START_MS / 1000, OUT_T[-1] / 1000, 60)
        out = {}
        for g in res.grids:
            v = g.values_np()
            for i, labels in enumerate(g.labels):
                out[frozenset((k, x) for k, x in labels.items()
                              if k != "__name__" and x != "")] = v[i].astype(np.float64)
        return out


@pytest.fixture(scope="module")
def fleet(data):
    return _Fleet(data)


@pytest.mark.parametrize("panel", PANELS, ids=lambda p: p["name"])
def test_the_fused_program_is_the_reference(fleet, panel):
    fell = _counter("filodb_fused_fallback", reason="hist_scheme")
    sums = _counter("filodb_hist_window", form="sums")
    got = fleet.answer(fleet.fused, panel["query"])
    assert _counter("filodb_fused_fallback", reason="hist_scheme") == fell
    assert _counter("filodb_hist_window", form="sums") == sums + 1
    want = expo_delta_panels.reference(fleet.data, OUT_T, WINDOW, panel)
    c = references.compare(got, want)
    assert c["malformed"] == 0 and c["absent_mismatch"] == 0
    assert c["rel_err"] <= panel["rel_err_limit"] / 10, c


@pytest.mark.parametrize("panel", PANELS, ids=lambda p: p["name"])
def test_the_fused_program_and_the_tree_agree(fleet, panel):
    """The tree sums each series' f32 rate a shard at a time: the same
    rule, another order of f32 sums."""
    c = references.compare(fleet.answer(fleet.tree, panel["query"]),
                           fleet.answer(fleet.fused, panel["query"]))
    assert c["malformed"] == 0 and c["absent_mismatch"] == 0
    assert c["rel_err"] <= 1e-4, c


def _staged(data):
    """The fleet on one shard, staged as the fused path stages it."""
    ms = TimeSeriesMemStore()
    ms.setup(Dataset("prometheus"), [0])
    assert data.load(ms, 0) == data.n_samples
    shard = ms.shard("prometheus", 0)
    pids = np.array(sorted(shard.partitions))
    block = ST.stage_from_shard(shard, pids, "h", int(data.ts[0]), int(data.ts[-1]),
                                mode="raw")
    rows = [int(shard.partitions[p].tags["instance"].split("-")[1]) for p in pids]
    return block, rows


def _window_grid(block, func):
    body = AGG.FUSED_BODIES["hist_shared"]
    j_pad = 16
    wins = body.windows(block, START_MS - block.base_ms, 60_000, j_pad, WINDOW, None)
    return body.base2_grid(func, body.rows(block), wins,
                           *body.statics(block, j_pad, False, True))


def test_the_window_sums_are_the_references_whole_counts(data):
    block, rows = _staged(data)
    assert block.schemes is not None and block.regular_ts is not None
    win = expo_delta_panels.window_sums(data, OUT_T, WINDOW)
    sums, factor = (np.asarray(a) for a in _window_grid(block, "increase"))
    J = len(OUT_T)
    for b, r in enumerate(rows):
        w = int(data.width[r])
        assert block.schemes[b] == Base2Scheme(int(data.scale[r]), int(data.offset[r]), w - 2)
        assert np.array_equal(sums[b, :J, :w], win[r, :, :w])
    assert (factor == 1).all()


def test_the_window_sum_is_one_exact_product(data):
    """On the chip a DEFAULT f32 product keeps 8 bits of an operand: the
    window sum's one dot asks for HIGHEST, which keeps every bit of a
    count, and no running sum over T is left."""
    block, _rows_ = _staged(data)
    body = AGG.FUSED_BODIES["hist_shared"]
    wins = body.windows(block, START_MS - block.base_ms, 60_000, 16, WINDOW, None)
    text = jax.jit(lambda rows, w: body.base2_grid("rate", rows, w, True)).lower(
        body.rows(block), wins).as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert len(dots) == 1 and "HIGHEST" in dots[0], dots
    assert "reduce_window" not in text and "cumsum" not in text


def test_the_factor_comes_after_the_sum(data):
    """``rate`` hands the epilogue the same whole-count window sums as
    ``increase`` and ``sum_over_time``, and 1 / window beside them; the
    group sum is then exact and scaled once."""
    block, _rows_ = _staged(data)
    inc, one = _window_grid(block, "increase")
    over, one2 = _window_grid(block, "sum_over_time")
    rate, factor = _window_grid(block, "rate")
    for a in (over, rate):
        assert np.array_equal(np.asarray(a), np.asarray(inc), equal_nan=True)
    assert (np.asarray(one) == 1).all() and (np.asarray(one2) == 1).all()
    f = np.asarray(factor)
    assert f.shape == (16,) and np.allclose(f, 1 / 300, rtol=1e-6)
    q = "sum by (service, le) ({}(lat_bucket[5m]))"
    fl = _Fleet(data)
    by = {}
    for func in ("increase", "rate"):
        (g,) = fl.fused.query_range(q.format(func), START_MS / 1000, OUT_T[-1] / 1000,
                                    60).grids
        by[func] = (g.schemes, np.asarray(g.hist_np()))
    (si, hi), (sr, hr) = by["increase"], by["rate"]
    assert si == sr
    whole = hi[np.isfinite(hi)]
    assert (whole == np.round(whole)).all() and whole.max() > 1000
    assert np.array_equal(hr, (hi * f[0]).astype(np.float32), equal_nan=True)


def test_every_launch_books_how_it_read_its_windows(fleet):
    """A cumulative fleet's launches read the window edges, a delta fleet's
    sum every point."""
    cum = expo_histograms.make(dict(CONFIG, warmup_scrapes=10), 16,
                               np.random.default_rng(3), BASE)
    ms = TimeSeriesMemStore()
    ms.setup(Dataset("prometheus"), [0, 1])
    cum.load(ms, 1)
    before = {f: _counter("filodb_hist_window", form=f) for f in ("edges", "sums")}
    q = PANELS[0]["query"]
    got = _Fleet.answer(None, QueryEngine(ms, "prometheus"), q)
    assert _counter("filodb_hist_window", form="edges") == before["edges"] + 1
    want = expo_panels.reference(cum, OUT_T, WINDOW, PANELS[0])
    assert references.compare(got, want)["rel_err"] <= 1e-6
    fleet.answer(fleet.fused, q)
    assert _counter("filodb_hist_window", form="sums") == before["sums"] + 1
    assert _counter("filodb_hist_window", form="edges") == before["edges"] + 1
