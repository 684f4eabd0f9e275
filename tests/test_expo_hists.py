"""Base-2 exponential histograms with a scale and an offset a series (PR 40).

The merge rule (``core.histograms``: the smallest scale, the joined index
range; exact for cumulative counts) against hand-worked cases; the scheme
through the store; the ragged native stage pass bit for bit the Python
tier's; and the fused program — one superblock of ragged rows, the merge on
the device, a quantile on each group's bounds — against the plain reference
(``benchmarks/chip/expo_panels.py``) and the tree. CPU backend, small
shapes. Times nothing.
"""

from __future__ import annotations

import numpy as np
import pytest

from benchmarks.chip import expo_histograms, expo_panels, references
from filodb_tpu import native
from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine
from filodb_tpu.core.histograms import (
    BASE2_WIDTH, Base2Scheme, base2_index, base2_remap, custom_buckets,
    merge_base2, union_les, unify_schemes,
)
from filodb_tpu.core.records import RecordBatch, SeriesBatch
from filodb_tpu.core.schemas import METRIC_TAG, PROM_HISTOGRAM, Dataset
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.memstore.shard import StoreConfig
from filodb_tpu.metrics import REGISTRY
from filodb_tpu.ops import staging as ST
from filodb_tpu.query.exec.transformers import QueryError

BASE = 1_600_000_000_000
INTERVAL = 10_000


def _counter(name: str, **labels) -> float:
    want = set(labels.items())
    with REGISTRY._lock:
        return sum(m.value for (n, ls), m in REGISTRY._metrics.items()
                   if n == name and want <= set(ls))


def _cumulative(idx: np.ndarray, s: Base2Scheme) -> np.ndarray:
    """[width] cumulative counts on ``s`` of observations at bucket index
    ``idx`` (at s's scale)."""
    c = np.bincount(idx - s.offset, minlength=s.n)
    return np.concatenate([[0], np.cumsum(c), [c.sum()]]).astype(np.float64)


# -- the merge rule, by hand ---------------------------------------------------

CASES = {
    # name: (schemes, merged, index of each scheme's columns on the merge)
    "downscale_by_one": (
        [Base2Scheme(3, 10, 4), Base2Scheme(2, 4, 3)], Base2Scheme(2, 4, 3),
        [[0, 0, 2, 4, 5], [0, 1, 2, 3, 4]]),
    "downscale_by_three_negative_offsets": (
        [Base2Scheme(5, -17, 9), Base2Scheme(2, -3, 1)], Base2Scheme(2, -3, 2),
        [[0, 1, 9, 10], [0, 1, 1, 2]]),
    "disjoint_ranges": (
        [Base2Scheme(3, 0, 2), Base2Scheme(3, 10, 2)], Base2Scheme(3, 0, 12),
        [[0] + [1] + [2] * 11 + [3], [0] + [0] * 10 + [1, 2, 3]]),
    "only_a_zero_bucket": (
        [Base2Scheme(4, 0, 0), Base2Scheme(6, 8, 3)], Base2Scheme(4, 2, 1),
        [[0, 0, 1], [0, 3, 4]]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_merge_rule_by_hand(case):
    schemes, merged, idx = CASES[case]
    assert merge_base2(schemes) == merged
    for s, want in zip(schemes, idx):
        assert base2_index(s, merged).tolist() == want


def test_a_scheme_merged_with_itself_is_itself():
    s = Base2Scheme(3, -40, 117)
    assert merge_base2([s, s]) == s
    idx = base2_index(s, s, width=s.width + 5)
    assert idx.tolist() == list(range(s.width)) + [s.n + 1] * 5


def test_upscaling_is_refused():
    with pytest.raises(ValueError):
        base2_index(Base2Scheme(1, 0, 4), Base2Scheme(2, 0, 8))


@pytest.mark.parametrize("scale", range(9))
def test_one_series_at_every_scale_downscales_exactly(scale):
    """Counts of one set of observations at scale 8, downscaled to
    ``scale`` by the rule, equal the counts taken at ``scale`` directly."""
    rng = np.random.default_rng(scale)
    x = rng.normal(-3.0, 2.0, 5_000)  # log2 of the observations
    idx8 = np.ceil(x * 2.0 ** 8).astype(np.int64) - 1
    fine = Base2Scheme(8, int(idx8.min()), int(idx8.max() - idx8.min() + 1))
    d = 8 - scale
    coarse_idx = idx8 >> d
    coarse = Base2Scheme(scale, int(coarse_idx.min()),
                         int(coarse_idx.max() - coarse_idx.min() + 1))
    assert merge_base2([fine, coarse]) == coarse
    np.testing.assert_array_equal(
        base2_remap(_cumulative(idx8, fine), fine, coarse),
        _cumulative(coarse_idx, coarse))


def test_bounds_are_the_schemes():
    s = Base2Scheme(2, -3, 4)
    les = s.les()
    assert les[0] == 0 and np.isinf(les[-1]) and len(les) == s.width
    np.testing.assert_allclose(les[1:-1], 2.0 ** (np.arange(-2, 2) / 4), rtol=1e-15)


def test_the_explicit_union_rule_is_unchanged():
    """Two explicit schemes still unify on the union of their bounds, a
    missing bound taking the nearest lower bound's count; a block already
    on the union passes through as the same object."""
    a_les = custom_buckets([0.1, 1, 5]).bounds()
    b_les = custom_buckets([0.2, 1, 5]).bounds()
    a = np.array([[1.0, 2.0, 3.0, 4.0]])
    b = np.array([[10.0, 20.0, 30.0, 40.0]])
    out, union, changed = unify_schemes([a, b], [a_les, b_les])
    assert changed and union.tolist()[:-1] == [0.1, 0.2, 1, 5]
    assert out[0].tolist() == [[1.0, 1.0, 2.0, 3.0, 4.0]]
    assert out[1].tolist() == [[0.0, 10.0, 20.0, 30.0, 40.0]]
    same, u2, changed2 = unify_schemes([a, a], [a_les, a_les])
    assert not changed2 and same[0] is a and np.array_equal(u2, union_les([a_les]))


# -- the scheme through the store ----------------------------------------------


def _series(i: int, scheme: Base2Scheme, ts, counts, metric="lat"):
    tags = {METRIC_TAG: metric, "_ws_": "w", "_ns_": "n", "service": f"s{i % 2}",
            "instance": f"h{i}"}
    return SeriesBatch(PROM_HISTOGRAM, tags, ts,
                       {"sum": counts[:, -1] * 0.01, "count": counts[:, -1],
                        "h": counts}, bucket_scheme=scheme)


def test_the_scheme_rides_the_store_and_an_explicit_one_is_refused():
    ms = TimeSeriesMemStore()
    ms.setup(Dataset("ds"), [0])
    ts = BASE + np.arange(6, dtype=np.int64) * INTERVAL
    s_a, s_b = Base2Scheme(3, -4, 5), Base2Scheme(1, 2, 2)
    tags = [{METRIC_TAG: "lat", "_ws_": "w", "_ns_": "n", "instance": f"h{i}"}
            for i in range(2)]
    rows = [(i, t) for i in range(2) for t in ts]
    counts = np.ones((len(rows), 7))  # only grouped and split here
    batch = RecordBatch(
        PROM_HISTOGRAM, np.array([t for _, t in rows]),
        {"sum": np.ones(len(rows)), "count": np.ones(len(rows)), "h": counts},
        [tags[i] for i, _ in rows], bucket_schemes=[(s_a, s_b)[i] for i, _ in rows])
    sb = batch.group_by_series()
    assert [x.bucket_scheme for x in sb] == [s_a, s_b]
    assert np.array_equal(sb[1].bucket_les, s_b.les())
    split = batch.shard_split(0, 1)[0]
    assert split.bucket_schemes == batch.bucket_schemes
    shard = ms.shard("ds", 0)
    shard.ingest_series(_series(0, s_a, ts, np.ones((6, s_a.width))))
    shard.ingest_series(_series(1, s_b, ts, np.ones((6, s_b.width))))
    parts = sorted(shard.partitions.values(), key=lambda p: p.tags["instance"])
    assert [p.bucket_scheme for p in parts] == [s_a, s_b]
    assert np.array_equal(parts[0].bucket_les, s_a.les())
    before = _counter("filodb_ingest_scheme_refused")
    later = BASE + 6 * INTERVAL + np.arange(2, dtype=np.int64) * INTERVAL
    explicit = _series(0, None, later, np.ones((2, 7)))  # le bounds, no scheme
    explicit.bucket_les = s_a.les()
    assert shard.ingest_series(explicit) == 0  # another scheme moves it: test_expo_growth
    assert _counter("filodb_ingest_scheme_refused") == before + 2
    assert parts[0].num_samples() == 6


def test_the_downsampler_carries_the_scheme():
    from filodb_tpu.downsample.downsampler import ShardDownsampler

    ms = TimeSeriesMemStore(StoreConfig(max_chunk_size=60))
    ms.setup(Dataset("ds"), [0])
    s = Base2Scheme(2, -8, 6)
    ts = BASE + np.arange(120, dtype=np.int64) * INTERVAL
    counts = np.cumsum(np.ones((120, 8)), axis=0)
    ms.shard("ds", 0).ingest_series(_series(0, s, ts, counts))
    d = ShardDownsampler(ms, "ds", periods_ms=(300_000,))
    part = next(iter(ms.shard("ds", 0).partitions.values()))
    part.switch_buffers()
    assert d.downsample_chunks(0, part, part.chunks) > 0
    out = next(iter(ms.shard("ds_5m", 0).partitions.values()))
    assert out.bucket_scheme == s and np.array_equal(out.bucket_les, s.les())


# -- the ragged stage: native pass == Python tier, bit for bit -------------------


def _stage_library():
    if native.stage_lib() is None:
        pytest.skip(f"libfilodbstage: {native.tiers()['filodbstage']}")


def _ragged_shard(schemes, n=130, chunk=50):
    ms = TimeSeriesMemStore(StoreConfig(max_chunk_size=chunk))
    ms.setup(Dataset("ds"), [0])
    rng = np.random.default_rng(3)
    ts = BASE + np.arange(n, dtype=np.int64) * INTERVAL
    for i, s in enumerate(schemes):
        c = np.cumsum(np.cumsum(rng.poisson(2.0, (n, s.n)), axis=1), axis=0)
        top = c[:, -1:] if s.n else np.zeros((n, 1))
        counts = np.concatenate([np.zeros((n, 1)), c, top], axis=1)
        ms.shard("ds", 0).ingest_series(_series(i, s, ts, counts))
    shard = ms.shard("ds", 0)
    return shard, np.array(sorted(shard.partitions))


def _same_block(got, want):
    for f in ("ts", "vals", "lens", "baseline", "regular_ts"):
        a, b = getattr(got, f), getattr(want, f)
        assert (a is None) == (b is None), f
        if a is not None:
            assert (a.dtype, a.shape) == (b.dtype, b.shape) and a.tobytes() == b.tobytes(), f


def _both_tiers(shard, pids, lo, hi, mode):
    got = ST.stage_from_shard(shard, pids, "h", lo, hi, mode=mode)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(native, "stage_lib", lambda: None)
        want = ST.stage_from_shard(shard, pids, "h", lo, hi, mode=mode)
    return got, want


@pytest.mark.parametrize("mode", ["raw", "corrected"])
@pytest.mark.parametrize("span", [(0, 129), (55, 95), (70, 129)])
def test_ragged_native_stage_equals_the_python_tier(span, mode):
    _stage_library()
    schemes = [Base2Scheme(3, -20, 160), Base2Scheme(2, 5, 3), Base2Scheme(5, -90, 77),
               Base2Scheme(0, 0, 0), Base2Scheme(4, 17, 12)]
    shard, pids = _ragged_shard(schemes)
    before = _counter("filodb_stage_gather_series", how="native")
    got, want = _both_tiers(shard, pids, BASE + span[0] * INTERVAL,
                            BASE + span[1] * INTERVAL, mode)
    assert _counter("filodb_stage_gather_series", how="native") == before + len(schemes)
    _same_block(got, want)
    assert got.vals.shape[2] == BASE2_WIDTH
    for r, s in enumerate(schemes):  # each row at its own width, zeros behind
        assert not np.asarray(got.vals)[r, :, s.width:].any()


def test_the_hist_cells_twelve_bucket_blocks_stage_as_before():
    """An explicit 12-bucket scheme (the hist cells'): one width, no
    padding, native == Python tier bit for bit."""
    _stage_library()
    ms = TimeSeriesMemStore(StoreConfig(max_chunk_size=50))
    ms.setup(Dataset("ds"), [0])
    from filodb_tpu.core.histograms import PROM_DEFAULT

    rng = np.random.default_rng(4)
    ts = BASE + np.arange(130, dtype=np.int64) * INTERVAL
    for i in range(9):
        c = np.cumsum(np.cumsum(rng.poisson(2.0, (130, 12)), axis=1), axis=0)
        ms.shard("ds", 0).ingest_series(SeriesBatch(
            PROM_HISTOGRAM, {METRIC_TAG: "m", "_ws_": "w", "_ns_": "n", "i": str(i)},
            ts, {"sum": c[:, -1] * 0.1, "count": c[:, -1] * 1.0, "h": c * 1.0},
            bucket_les=PROM_DEFAULT.bounds()))
    shard = ms.shard("ds", 0)
    pids = np.array(sorted(shard.partitions))
    got, want = _both_tiers(shard, pids, BASE, BASE + 129 * INTERVAL, "raw")
    _same_block(got, want)
    assert got.vals.shape[2] == 12


# -- the fused program against the reference and the tree ----------------------

CONFIG = {
    "samples_per_series": 120, "warmup_scrapes": 60, "services": 4,
    "median_s": [0.002, 0.5], "sigma": [0.2, 1.5], "per_scrape": [5, 200],
    "timeout": {"services": 1, "share": 0.01, "range_s": [1, 30]},
    "max_buckets": 160, "interval_ms": INTERVAL, "metric": "lat",
}
PANELS = [
    {"name": "fleet_p99", "q": 0.99,
     "query": "histogram_quantile(0.99, sum by (le) (rate(lat_bucket[5m])))"},
    {"name": "fleet_p50", "q": 0.5,
     "query": "histogram_quantile(0.5, sum by (le) (rate(lat_bucket[5m])))"},
    {"name": "service_p99", "q": 0.99, "by": ["service", "le"],
     "query": "histogram_quantile(0.99, sum by (service, le) (rate(lat_bucket[5m])))"},
    {"name": "service_p90", "q": 0.9, "by": ["service", "le"],
     "query": "histogram_quantile(0.9, sum by (service, le) (rate(lat_bucket[5m])))"},
]
START_MS = BASE + 400_000
OUT_T = START_MS + np.arange(11, dtype=np.int64) * 60_000


class _Fleet:
    def __init__(self, seed: int, n: int = 24):
        self.data = expo_histograms.make(CONFIG, n, np.random.default_rng(seed), BASE)
        self.ms = TimeSeriesMemStore()
        self.ms.setup(Dataset("prometheus"), list(range(4)))
        assert self.data.load(self.ms, 2) == self.data.n_samples
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


_FLEETS: dict = {}


def _fleet(seed: int) -> _Fleet:
    if seed not in _FLEETS:
        _FLEETS[seed] = _Fleet(seed)
    return _FLEETS[seed]


@pytest.mark.parametrize("seed", [11, 12, 13])
@pytest.mark.parametrize("panel", PANELS, ids=lambda p: p["name"])
def test_the_fused_program_is_the_reference(seed, panel):
    f = _fleet(seed)
    assert len(set(f.data.scale.tolist())) >= 2  # a merge across scales is exercised
    fell = _counter("filodb_fused_fallback", reason="hist_scheme")
    rescaled = _counter("filodb_hist_rescale_series", how="rescaled")
    got = f.answer(f.fused, panel["query"])
    assert _counter("filodb_fused_fallback", reason="hist_scheme") == fell
    assert _counter("filodb_hist_rescale_series", how="rescaled") > rescaled
    want = expo_panels.reference(f.data, OUT_T, 300_000, panel)
    c = references.compare(got, want)
    assert c["malformed"] == 0 and c["absent_mismatch"] == 0
    assert c["rel_err"] <= 1e-6, c


@pytest.mark.parametrize("panel", PANELS, ids=lambda p: p["name"])
def test_the_fused_program_and_the_tree_agree(panel):
    """The tree sums f32 rates a shard at a time and merges partials on the
    host: the same rule, another order of f32 sums."""
    f = _fleet(11)
    c = references.compare(f.answer(f.tree, panel["query"]),
                           f.answer(f.fused, panel["query"]))
    assert c["malformed"] == 0 and c["absent_mismatch"] == 0
    assert c["rel_err"] <= 1e-4, c


def test_the_tree_partials_are_the_merge_rule():
    """``sum by (service, le)`` without the quantile, through the tree: each
    group's scheme is merge_base2 of its series', on both paths."""
    f = _fleet(12)
    q = "sum by (service, le) (rate(lat_bucket[5m]))"
    by_path = {}
    for name, eng in (("tree", f.tree), ("fused", f.fused)):
        res = eng.query_range(q, START_MS / 1000, OUT_T[-1] / 1000, 60)
        (g,) = res.grids
        by_path[name] = {l["service"]: (s, np.asarray(g.hist_np())[i])
                         for i, (l, s) in enumerate(zip(g.labels, g.schemes))}
    for svc, (s, h) in by_path["tree"].items():
        rows = [i for i, t in enumerate(f.data.tags) if t["service"] == svc]
        assert s == merge_base2([Base2Scheme(int(f.data.scale[i]), int(f.data.offset[i]),
                                             int(f.data.width[i]) - 2) for i in rows])
        fs, fh = by_path["fused"][svc]
        assert fs == s
        w = s.width
        np.testing.assert_allclose(fh[:, :w], h[:, :w], rtol=1e-5)


def _observed(name: str, **labels) -> int:
    want = set(labels.items())
    with REGISTRY._lock:
        return sum(m.total for (n, ls), m in REGISTRY._metrics.items()
                   if n == name and want <= set(ls))


def test_a_cold_base2_query_books_the_scheme_part():
    f = _Fleet(14, n=8)
    before = _observed("filodb_stage_part_seconds", part="scheme")
    f.answer(f.fused, PANELS[0]["query"])
    assert _observed("filodb_stage_part_seconds", part="scheme") > before


def test_a_shard_mixing_explicit_and_base2_schemes_falls_back():
    """The explicit rule and the base-2 rule do not mix: the fused path
    falls back with ``hist_scheme``, and the tree refuses the sum."""
    ms = TimeSeriesMemStore()
    ms.setup(Dataset("ds"), [0])
    ts = BASE + np.arange(60, dtype=np.int64) * INTERVAL
    counts = np.cumsum(np.ones((60, 6)), axis=0)
    shard = ms.shard("ds", 0)
    shard.ingest_series(_series(0, Base2Scheme(2, 0, 4), ts, counts))
    shard.ingest_series(SeriesBatch(
        PROM_HISTOGRAM, {METRIC_TAG: "lat", "_ws_": "w", "_ns_": "n", "instance": "x"},
        ts, {"sum": counts[:, -1], "count": counts[:, -1], "h": counts},
        bucket_les=custom_buckets([0.1, 0.2, 0.5, 1]).bounds()))
    fell = _counter("filodb_fused_fallback", reason="hist_scheme")
    with pytest.raises(QueryError):
        QueryEngine(ms, "ds").query_range(
            "histogram_quantile(0.9, sum by (le) (rate(lat_bucket[5m])))",
            (BASE + 300_000) / 1000, (BASE + 590_000) / 1000, 60)
    assert _counter("filodb_fused_fallback", reason="hist_scheme") == fell + 1


def test_a_jittered_fleet_merges_rates_the_same_on_both_paths():
    """Series on clocks of their own: the hist body's per-series rates (no
    whole-count sum: the factor differs a series), then the same merge —
    fused and tree agree, and the fused path stays one program."""
    ms = TimeSeriesMemStore()
    ms.setup(Dataset("ds"), [0, 1])
    rng = np.random.default_rng(21)
    schemes = [Base2Scheme(3, -30, 40), Base2Scheme(2, -16, 22), Base2Scheme(4, -58, 70),
               Base2Scheme(3, -25, 33), Base2Scheme(2, -14, 20), Base2Scheme(5, -110, 120)]
    for i, s in enumerate(schemes):
        ts = BASE + np.arange(120, dtype=np.int64) * INTERVAL + rng.integers(-900, 900, 120)
        c = np.cumsum(np.cumsum(rng.poisson(0.5, (120, s.n)), axis=1), axis=0)
        counts = np.concatenate([np.zeros((120, 1)), c, c[:, -1:]], axis=1)
        ms.shard("ds", i % 2).ingest_series(_series(i, s, ts, counts))
    q = "histogram_quantile(0.9, sum by (service, le) (rate(lat_bucket[5m])))"
    answers = {}
    for name, params in (("fused", None), ("tree", PlannerParams(fused_aggregate=False))):
        eng = QueryEngine(ms, "ds") if params is None else QueryEngine(ms, "ds", params)
        fell = _counter("filodb_fused_fallback", reason="hist_scheme")
        ran = _counter("filodb_fused_dispatch")
        regular = _counter("filodb_fused_dispatch", grid="regular")
        res = eng.query_range(q, START_MS / 1000, OUT_T[-1] / 1000, 60)
        assert _counter("filodb_fused_fallback", reason="hist_scheme") == fell
        assert _counter("filodb_fused_dispatch") == ran + (name == "fused")
        assert _counter("filodb_fused_dispatch", grid="regular") == regular
        answers[name] = {frozenset(l.items()): g.values_np()[i].astype(np.float64)
                         for g in res.grids for i, l in enumerate(g.labels)}
    c = references.compare(answers["tree"], answers["fused"])
    assert len(answers["fused"]) == 2 and c["absent_mismatch"] == 0
    assert c["rel_err"] <= 1e-4, c


def test_every_request_is_one_program_and_reads_nothing_back():
    """Cold and warm, a base-2 query is one fused launch and reads no staged
    block back to the host (the union rule read every block back); the
    launch counts how its group sum ran, once (a few groups: the one-hot
    product)."""
    f = _Fleet(15, n=8)
    for _ in range(2):
        d2h = _counter("filodb_stage_d2h_bytes")
        ran = _counter("filodb_fused_dispatch", body="hist_shared")
        merged = {form: _counter("filodb_hist_merge", form=form)
                  for form in ("onehot", "segment")}
        f.answer(f.fused, PANELS[2]["query"])
        assert _counter("filodb_stage_d2h_bytes") == d2h
        assert _counter("filodb_fused_dispatch", body="hist_shared") == ran + 1
        assert _counter("filodb_hist_merge", form="onehot") == merged["onehot"] + 1
        assert _counter("filodb_hist_merge", form="segment") == merged["segment"]
