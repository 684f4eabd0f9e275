"""The superblock is assembled where its rows already are (PERF.md 6, PR 29).

``ST.build_superblock`` concatenates the shards' staged blocks on the device
that holds them (``ST.assemble_rows``); the host concatenates only what the
grid classification reads, and the big mirrors wait for the first extension
(``ST.materialize_mirrors``, PR 32; ``tests/test_mirror_life.py``). What is
held here:

- the device-assembled superblock is, bit for bit and field for field, what
  the host concatenation followed by ``device_put`` gave before — against a
  plain copy of that loop kept below as the reference — over every grid
  class and the awkward row layouts (unequal padded T, an empty member, a
  last member whose padding reaches past the padded series axis);
- members made on the host after staging (a remapped bucket scheme, a
  ``le=`` slice) and a mesh placement take the host path, and the counter
  ``filodb_superblock_assembled_total{where}`` says which path ran;
- a cold fused query reads nothing back (``filodb_stage_d2h_bytes_total``);
- row offsets and counts are values of the program: selections of other
  series counts and the same padded shapes share one compile;
- the guarantee: the mirrors an extension makes are what ``extend_superblock``
  mutates — after a device-assembled build, one more acknowledged scrape
  makes the next live-edge query an ``extend`` that equals a fresh restage.

CPU backend, small shapes. Times nothing.
"""

from __future__ import annotations

import gc
import json
import urllib.parse
import urllib.request

import numpy as np
import pytest

import jax

from filodb_tpu.api.http import serve_background
from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine
from filodb_tpu.core.histograms import PROM_DEFAULT, custom_buckets
from filodb_tpu.core.records import SeriesBatch
from filodb_tpu.core.schemas import Dataset, METRIC_TAG, PROM_HISTOGRAM
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.metrics import REGISTRY
from filodb_tpu.ops import aggregations as AGG
from filodb_tpu.ops import staging as ST
from filodb_tpu.parallel.mesh import make_mesh
from filodb_tpu.testkit import counter_batch, histogram_batch

BASE = 1_600_000_000_000
INTERVAL = 10_000
BUCKETS = 5
ARRAYS = ("ts", "vals", "raw", "lens", "baseline", "ts_dev")
MIRRORS = ("h_ts", "h_vals", "h_raw", "h_lens", "h_dev")


def _counter(name: str, **labels) -> float:
    want = set(labels.items())
    with REGISTRY._lock:
        return sum(m.value for (n, ls), m in REGISTRY._metrics.items()
                   if n == name and want <= set(ls))


# -- members ------------------------------------------------------------------


def _member(kind: str, n: int, m: int, seed: int, first_ref: int,
            headroom: int = 0) -> ST.StagedBlock:
    """One shard's block as ``staged_block_for`` leaves it: staged from ``n``
    series of ``m`` samples and uploaded with its mirrors kept."""
    rng = np.random.default_rng(seed)
    nominal = BASE + INTERVAL // 2 + (1 + np.arange(m, dtype=np.int64)) * INTERVAL
    series = []
    for _ in range(n):
        ts = nominal
        if kind in ("jittered", "masked"):
            ts = nominal + np.rint(rng.uniform(-0.05, 0.05, m) * INTERVAL).astype(np.int64)
        if kind == "hist":
            vals = np.cumsum(np.cumsum(
                rng.poisson(2.0, size=(m, BUCKETS)).astype(np.float64), axis=1), axis=0)
        else:
            vals = np.cumsum(rng.uniform(0, 10, m)) + 1e9
            if kind == "counter" and m > 8:
                vals[m // 2:] -= vals[m // 2] - 3.0  # a reset: raw != corrected
        if kind == "masked" and m > 8:
            keep = np.ones(m, bool)
            keep[rng.choice(np.arange(1, m - 1), 1, replace=False)] = False
            ts, vals = ts[keep], vals[keep]
        series.append((ts, vals))
    refs = [(seed, first_ref + i) for i in range(n)]
    if kind == "hist":
        block = ST.stage_histogram_series(series, BASE, BUCKETS, refs)
    else:
        block = ST.stage_series(
            series, BASE, refs, counter_corrected=kind in ("counter", "masked"),
            time_headroom=headroom)
    return block.to_device(keep_host=True)


def _members(kind: str, layout) -> list[ST.StagedBlock]:
    out, ref = [], 0
    for seed, spec in enumerate(layout):
        n, m, *rest = spec
        out.append(_member(kind, n, m, seed, ref, *rest))
        ref += n
    return out


def _concat_as_before(blocks) -> dict:
    """The host concatenation as it was before PR 29, from the device
    arrays alone: the reference the two paths are held to."""
    real = [b for b in blocks if b.n_series > 0] or list(blocks[:1])
    T = max(b.ts.shape[1] for b in real)
    S = sum(b.n_series for b in real)
    Sp = ST.pad_series(S)
    vals0 = np.asarray(real[0].vals)
    ts = np.full((Sp, T), ST.TS_PAD, np.int32)
    vals = np.zeros((Sp, T) + vals0.shape[2:], np.float32)
    raw = (np.zeros((Sp, T), np.float32)
           if any(b.raw is not None for b in real) and vals0.ndim == 2 else None)
    lens = np.zeros(Sp, np.int32)
    baseline = np.zeros((Sp,) + vals0.shape[2:], np.float32)
    o = 0
    for b in real:
        k, t = b.n_series, b.ts.shape[1]
        ts[o:o + k, :t] = np.asarray(b.ts)[:k]
        vals[o:o + k, :t] = np.asarray(b.vals)[:k]
        if raw is not None:
            raw[o:o + k, :t] = np.asarray(b.raw if b.raw is not None else b.vals)[:k]
        lens[o:o + k] = np.asarray(b.lens)[:k]
        baseline[o:o + k] = np.asarray(b.baseline)[:k]
        o += k
    return {"ts": ts, "vals": vals, "raw": raw, "lens": lens, "baseline": baseline}


def _same_bits(a, b, what) -> None:
    assert (a is None) == (b is None), what
    if a is None:
        return
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape, (what, a.dtype, b.dtype, a.shape, b.shape)
    assert a.tobytes() == b.tobytes(), what


def _same_block(got: ST.StagedBlock, want: ST.StagedBlock) -> None:
    for f in ARRAYS + MIRRORS + ("regular_ts", "nominal_ts", "base64"):
        _same_bits(getattr(got, f, None), getattr(want, f, None), f)
    for f in ("base_ms", "n_series", "part_refs", "maxdev_ms", "placement"):
        assert getattr(got, f) == getattr(want, f), f
    assert (getattr(got, "cont", None) is None) == (getattr(want, "cont", None) is None)
    if getattr(got, "cont", None) is not None:
        for a, b in zip(got.cont, want.cont):
            _same_bits(a, b, "cont")
    assert (got.mgrid is None) == (want.mgrid is None)
    if got.mgrid is not None:
        for f in ST._MGRID_ARRAYS + ("nominal_ts",):
            _same_bits(getattr(got.mgrid, f), getattr(want.mgrid, f), f"mgrid.{f}")
        assert (got.mgrid.n_valid, got.mgrid.interval_ms, got.mgrid.maxdev_ms) == (
            want.mgrid.n_valid, want.mgrid.interval_ms, want.mgrid.maxdev_ms)


# (kind, layout of (series, samples[, time headroom]) per member, grid class)
LAYOUTS = {
    "scalar_raw_sidecar": ("counter", [(5, 40), (3, 40)], "regular"),
    "histogram": ("hist", [(5, 40), (3, 40)], "regular"),
    "jittered": ("jittered", [(5, 40), (3, 40)], "jitter"),
    "masked": ("masked", [(12, 60), (9, 60)], "holes"),
    "unequal_padded_T": ("counter", [(5, 40), (3, 40, 200)], "regular"),
    "unequal_lengths": ("gauge", [(5, 40), (3, 200)], "irregular"),
    "empty_member": ("counter", [(5, 40), (0, 40), (3, 40)], "regular"),
    "all_empty": ("gauge", [(0, 40)], "irregular"),
    # S = 32 = Sp: the second member's 32 padded rows start at row 20 and
    # the third's 8 at row 29: both reach past row 32
    "padding_passes_Sp": ("hist", [(20, 40), (9, 40), (3, 40)], "regular"),
}


def _mirror_bytes(how: str) -> float:
    return _counter("filodb_stage_mirror_bytes", site="super", how=how)


@pytest.mark.parametrize("mirrors", ["from_members", "read_back"])
@pytest.mark.parametrize("case", sorted(LAYOUTS))
def test_device_assembly_is_the_host_concatenation_bit_for_bit(case, mirrors):
    kind, layout, grid = LAYOUTS[case]
    blocks = _members(kind, layout)
    before = _counter("filodb_superblock_assembled", where="device")
    d2h = _counter("filodb_stage_d2h_bytes")
    deferred, made = _mirror_bytes("deferred"), _mirror_bytes("materialized")
    got, uploaded = ST.build_superblock(blocks)
    assert _counter("filodb_superblock_assembled", where="device") == before + 1
    assert _counter("filodb_stage_d2h_bytes") == d2h
    assert ST.grid_class(got) == grid
    for f in ARRAYS:
        a = getattr(got, f)
        assert a is None or isinstance(a, jax.Array), f
    want = ST.concat_blocks(blocks).to_device(keep_host=True)
    as_before = _concat_as_before(blocks)
    # the build wrote no mirror it did not need for the classification
    # (a masked build reads them all), and said how many bytes it spared
    waiting = ST._deferred(got)
    if case in ("scalar_raw_sidecar", "histogram", "empty_member",
                "padding_passes_Sp"):  # one grid, advertised by every member
        assert waiting == [f for f in ("ts", "vals", "raw")
                           if getattr(got, f) is not None]
    assert ("vals" in waiting) == (case != "unequal_lengths" and grid != "holes")
    spared = sum(int(getattr(got, f).nbytes) for f in waiting)
    assert _mirror_bytes("deferred") == deferred + spared
    assert _mirror_bytes("materialized") == made
    # ... and they are made when asked for: from the members' mirrors while
    # those live, else read back from the superblock's own device arrays
    if mirrors == "read_back":
        del blocks
        gc.collect()
    ST.materialize_mirrors(got)
    assert ST._deferred(got) == []
    assert _mirror_bytes("materialized") == made + spared
    assert _counter("filodb_stage_d2h_bytes") == d2h + (
        spared if mirrors == "read_back" else 0)
    ST.materialize_mirrors(got)  # once
    assert _mirror_bytes("materialized") == made + spared
    _same_block(got, want)
    for f in MIRRORS:
        m = getattr(got, f)
        assert m is None or (isinstance(m, np.ndarray) and m.flags.writeable), f
    for f, ref in as_before.items():
        _same_bits(getattr(got, f), ref, f"{f} against the loop as it was")
    # nothing but a masked sidecar crosses for a device-assembled block
    assert uploaded == ST.staged_nbytes(got) - sum(
        int(getattr(got, f).nbytes) for f in ARRAYS if getattr(got, f) is not None)
    assert (uploaded > 0) == (grid == "holes")


def test_without_mirrors_no_values_are_concatenated_on_the_host(monkeypatch):
    """Members on a shared regular grid need no host copy of ts or vals at
    all: of the mirrors the build leaves only ``h_lens``."""
    blocks = _members("hist", LAYOUTS["histogram"][1])
    asked = []
    concat_rows = ST._concat_rows
    monkeypatch.setattr(ST, "_concat_rows", lambda real, host, rows, fields, *a: (
        asked.extend(fields), concat_rows(real, host, rows, fields, *a))[1])
    got, _ = ST.build_superblock(blocks)
    assert asked == ["lens"]
    assert all(getattr(got, f, None) is None for f in MIRRORS if f != "h_lens")
    assert isinstance(got.h_lens, np.ndarray)
    for f, ref in _concat_as_before(blocks).items():
        _same_bits(getattr(got, f), ref, f)


def test_selections_of_equal_padded_shapes_share_one_compile():
    """(5, 3) and (4, 2) series pad alike: offsets and counts are values."""
    first = _members("hist", [(5, 40), (3, 40)])
    ST.build_superblock(first)
    compiles = _counter("filodb_xla_compiles", family="superblock_assemble")
    cache = ST.assemble_rows._cache_size()
    second = _members("hist", [(4, 40), (2, 40)])
    got, _ = ST.build_superblock(second)
    assert ST.assemble_rows._cache_size() == cache
    assert _counter("filodb_xla_compiles", family="superblock_assemble") == compiles
    for f, ref in _concat_as_before(second).items():
        _same_bits(getattr(got, f), ref, f)


def test_a_member_without_mirrors_is_read_back_and_booked():
    """The one place a staged array crosses back: a device-resident member
    the host holds no copy of."""
    blocks = _members("counter", [(5, 40), (3, 40)])
    bare = _member("counter", 2, 40, 9, 8)
    for f in MIRRORS + ("h_base",):
        setattr(bare, f, None)
    before = _counter("filodb_stage_d2h_bytes")
    got = ST.concat_blocks(blocks + [bare])
    crossed = sum(int(getattr(bare, f).nbytes)
                  for f in ("ts", "vals", "raw", "lens", "baseline"))
    assert _counter("filodb_stage_d2h_bytes") == before + crossed
    for f, ref in _concat_as_before(blocks + [bare]).items():
        _same_bits(getattr(got, f), ref, f)


# -- through the engine -------------------------------------------------------

N_SHARDS = 4
START = (BASE + 600_000) / 1000
END = START + 900
Q_HIST = "histogram_quantile(0.99, sum by (le) (rate(http_request_latency[5m])))"
Q_COUNTER = "sum by (job) (rate(http_requests_total[5m]))"


def _store(n_samples: int = 240) -> TimeSeriesMemStore:
    ms = TimeSeriesMemStore()
    ms.setup(Dataset("ds"), list(range(N_SHARDS)))
    ms.ingest_routed("ds", counter_batch(n_series=24, n_samples=n_samples,
                                         start_ms=BASE), spread=2)
    ms.ingest_routed("ds", histogram_batch(n_series=24, n_samples=n_samples,
                                           start_ms=BASE), spread=2)
    return ms


def _hetero_store() -> TimeSeriesMemStore:
    """Scheme A on shards 0-1, scheme B (two more bounds) on shards 2-3."""
    rng = np.random.default_rng(5)
    ms = TimeSeriesMemStore()
    ms.setup(Dataset("ds"), list(range(N_SHARDS)))
    ts = BASE + np.arange(200, dtype=np.int64) * INTERVAL
    for i in range(16):
        scheme = custom_buckets(
            [0.1, 0.5, 1, 5] if i % 4 < 2 else [0.1, 0.25, 0.5, 1, 2.5, 5])
        incr = rng.poisson(2.0, size=(200, scheme.num_buckets)).astype(np.float64)
        incr[:, -1] = incr.sum(1)
        hist = np.cumsum(np.cumsum(incr, axis=1), axis=0)
        ms.shard("ds", i % 4).ingest_series(SeriesBatch(
            PROM_HISTOGRAM,
            {METRIC_TAG: "lat_hetero", "_ws_": "w", "_ns_": "n", "instance": f"h{i}"},
            ts, {"sum": np.cumsum(rng.uniform(0, 5, size=200)),
                 "count": hist[:, -1], "h": hist},
            bucket_les=scheme.bounds()))
    return ms


def _assembled() -> dict:
    return {w: _counter("filodb_superblock_assembled", where=w)
            for w in ("device", "host")}


def _rows(res) -> dict:
    return {tuple(sorted(lbls.items())): np.asarray(vals)
            for g in res.grids for lbls, vals in zip(g.labels, g.values_np())}


def _assert_equal_answers(got, want) -> None:
    a, b = _rows(got), _rows(want)
    assert a.keys() == b.keys() and a
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])


@pytest.mark.parametrize("query", [Q_COUNTER, Q_HIST], ids=["counter", "hist"])
def test_a_cold_fused_query_assembles_on_the_device_and_reads_nothing_back(query):
    eng = QueryEngine(_store(), "ds")
    before, d2h = _assembled(), _counter("filodb_stage_d2h_bytes")
    h2d = _counter("filodb_stage_h2d_bytes", part="h2d_super")
    res = eng.query_range(query, START, END, 60)
    assert res.grids and res.query_log["path"] == "fused"
    after = _assembled()
    assert (after["device"], after["host"]) == (before["device"] + 1, before["host"])
    assert _counter("filodb_stage_d2h_bytes") == d2h
    # of the superblock only the le vector crosses
    les_bytes = 4 * PROM_DEFAULT.num_buckets if query == Q_HIST else 0
    assert _counter("filodb_stage_h2d_bytes", part="h2d_super") == h2d + les_bytes


@pytest.mark.parametrize("query", [
    "histogram_quantile(0.9, sum by (le) (rate(lat_hetero_bucket[5m])))",
    'sum(rate(lat_hetero_bucket{le="0.5"}[5m]))',
], ids=["remapped_scheme", "le_slice"])
def test_members_made_on_the_host_take_the_host_path_and_read_nothing_back(query):
    ms = _hetero_store()
    eng = QueryEngine(ms, "ds")
    start = (BASE + 400_000) / 1000
    before, d2h = _assembled(), _counter("filodb_stage_d2h_bytes")
    res = eng.query_range(query, start, start + 600, 60)
    assert res.grids and res.query_log["path"] == "fused"
    after = _assembled()
    assert (after["device"], after["host"]) == (before["device"], before["host"] + 1)
    assert _counter("filodb_stage_d2h_bytes") == d2h
    ref = QueryEngine(ms, "ds", PlannerParams(fused_aggregate=False))
    want = _rows(ref.query_range(query, start, start + 600, 60))
    got = _rows(res)
    assert got.keys() == want.keys()
    for k in got:
        np.testing.assert_allclose(got[k], want[k], rtol=2e-5, atol=1e-6,
                                   equal_nan=True)


def test_a_mesh_takes_the_host_path_and_reads_nothing_back():
    ms = _store()
    eng = QueryEngine(ms, "ds", PlannerParams(mesh=make_mesh()))
    before, d2h = _assembled(), _counter("filodb_stage_d2h_bytes")
    res = eng.query_range(Q_COUNTER, START, END, 60)
    assert res.grids and res.query_log["path"] == "fused"
    after = _assembled()
    assert (after["device"], after["host"]) == (before["device"], before["host"] + 1)
    assert _counter("filodb_stage_d2h_bytes") == d2h
    got, want = _rows(res), _rows(QueryEngine(ms, "ds").query_range(
        Q_COUNTER, START, END, 60))
    assert got.keys() == want.keys()
    for k in got:
        np.testing.assert_allclose(got[k], want[k], rtol=2e-5, atol=1e-6,
                                   equal_nan=True)


# -- the guarantee: an acknowledged write is read back by the next query ------

N_LIVE = 300
HEAD_MS = BASE + N_LIVE * INTERVAL  # first timestamp past the loaded data
LIVE_END = (HEAD_MS + 120_000) / 1000  # the range reaches past the head


def _get(port: int, query: str) -> dict:
    qs = urllib.parse.urlencode({"query": query, "start": START,
                                 "end": LIVE_END, "step": 60})
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/api/v1/query_range?{qs}") as r:
        body = json.loads(r.read())
    assert body["status"] == "success" and body["data"]["result"]
    return {tuple(sorted(s["metric"].items())): s["values"]
            for s in body["data"]["result"]}


def _post_counter_scrape(port: int, ms, t_ms: int) -> None:
    """One more sample of every ``http_requests_total`` series through
    ``POST /ingest/prom``, 1000 above its newest (no stale answer hides it)."""
    lines = ["# TYPE http_requests_total counter"]
    n = 0
    for shard in range(N_SHARDS):
        for part in ms.shard("ds", shard).partitions.values():
            if part.tags[METRIC_TAG] != "http_requests_total":
                continue
            _ts, vals = part.samples_in_range(BASE, HEAD_MS, "count")
            labels = ",".join(f'{k}="{v}"' for k, v in part.tags.items()
                              if k != METRIC_TAG)
            lines.append(f"http_requests_total{{{labels}}} {float(vals[-1]) + 1000.0!r} {t_ms}")
            n += 1
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/ingest/prom", data="\n".join(lines).encode(),
        method="POST")
    with urllib.request.urlopen(req) as r:
        ack = json.loads(r.read())
    assert ack["status"] == "success" and ack["data"]["ingested"] == n == 24


@pytest.mark.parametrize("kind", ["counter", "hist"])
def test_an_acknowledged_scrape_extends_the_device_assembled_superblock(kind):
    ms = _store(N_LIVE)
    eng = QueryEngine(ms, "ds")
    srv, port = serve_background(eng)
    try:
        query = Q_COUNTER if kind == "counter" else Q_HIST
        before = _assembled()
        _get(port, query)
        # the first build of a histogram column learns that it stages raw
        # whatever the function, and is cached where the second query looks
        stale = _get(port, query)
        after = _assembled()
        assert (after["device"], after["host"]) == (before["device"] + 1, before["host"])
        extends = _counter("filodb_superblock_maintenance", outcome="extend")
        if kind == "counter":
            _post_counter_scrape(port, ms, HEAD_MS)
        else:
            # the text exposition carries no native histogram: the scrape
            # goes in where /ingest/prom puts its batches
            ms.ingest_routed("ds", histogram_batch(
                n_series=24, n_samples=1, start_ms=HEAD_MS), spread=2)
        fresh = _get(port, query)
        assert _counter("filodb_superblock_maintenance", outcome="extend") == extends + 1
        assert _assembled() == after  # extended, not rebuilt
        assert fresh != stale
        extended = eng.query_range(query, START, LIVE_END, 60)  # a hit on it
        assert _assembled() == after
        # with both caches emptied the same query restages from the store
        ms._superblock_cache = None
        for shard in range(N_SHARDS):
            sh = ms.shard("ds", shard)
            with sh._lock:
                sh._clear_stage_cache()
        restaged = eng.query_range(query, START, LIVE_END, 60)
        assert _assembled()["device"] == after["device"] + 1
        _assert_equal_answers(extended, restaged)
    finally:
        srv.shutdown()
        srv.server_close()


# -- the program as the chip's compiler sees it -------------------------------


@pytest.fixture(scope="module")
def one_chip():
    """A described (not attached) v5e chip: the TPU compiler is installed
    where no TPU is. Only ever called from inside a test (one process at a
    time may load the TPU's library)."""
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps the compiler away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_row_bands_are_written_in_place_at_hists_slide_size(one_chip):
    """Members and superblock are both resident while it is built; anything
    more would raise the cell's peak memory. Eight members of f32[1024, 256,
    12] into f32[8192, 256, 12]: the v5e compiler's temporaries stay under a
    tenth of the 100.7 MB output."""
    import jax.numpy as jnp

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    member = (arr((1024, 256), jnp.int32), arr((1024, 256, 12), jnp.float32),
              None, arr((1024,), jnp.int32), arr((1024, 12), jnp.float32))
    values = arr((8,), jnp.int32)
    compiled = ST.assemble_rows.lower(
        (member,) * 8, values, values, None, rows=8192).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= 8192 * 256 * 12 * 4
    assert mem.temp_size_in_bytes < mem.output_size_in_bytes // 10


def _stat_sets():
    from filodb_tpu.ops import pallas_kernels as PK

    return sorted({PK.stat_set(f, c, d) for f in PK.PALLAS_FUNCS
                   for c, d in ((False, False), (True, False), (True, True))})


@pytest.mark.parametrize("stats", _stat_sets(), ids="+".join)
def test_the_pallas_kernel_of_every_statistic_set_compiles_for_the_chip(one_chip, stats):
    """Interpret mode cannot say what Mosaic lowers: each set's kernel at
    `scraped.repeat`'s shape (S131072 x T768 x J128) and at the widest block
    the kernel is selected for (MAX_T)."""
    import jax.numpy as jnp

    from filodb_tpu.ops import pallas_kernels as PK

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    for s, t in ((131072, 768), (4096, PK.MAX_T)):
        compiled = PK.window_aggregates.lower(
            arr((s, t), jnp.int32), arr((s, t), jnp.float32), arr((s, t), jnp.float32),
            arr((s,), jnp.int32), scalar, scalar, scalar,
            num_steps=PK.BJ, interpret=False, stats=stats).compile()
        assert "tpu_custom_call" in compiled.as_text()
        mem = compiled.memory_analysis()
        assert mem.output_size_in_bytes >= len(stats) * s * PK.BJ * 4
        # the lane-tile table is one pass over ts: no masked copy of it
        assert mem.temp_size_in_bytes < s * t * 4 // 8


def test_the_count_of_narrow_grid_tiles_compiles_for_the_chip(one_chip):
    """The counter's own program (`book_lane_tiles`: once a block and
    window) at the same two shapes, also without a copy of ``ts``."""
    import jax.numpy as jnp

    from filodb_tpu.ops import pallas_kernels as PK

    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    for s, t in ((131072, 768), (4096, PK.MAX_T)):
        compiled = PK._narrow_grid_tiles.lower(
            jax.ShapeDtypeStruct((s, t), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((s,), jnp.int32, sharding=one_chip),
            scalar, scalar, scalar, num_steps=PK.BJ).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < s * t * 4 // 8


# the base-2 cells' epilogue shapes: (W, G) of each grouping of expo.repeat
# and expo_delta.repeat, and the most groups the kernel takes
BASE2_EPILOGUES = ((176, 40), (120, 1), (168, 40), (112, 1), (176, 127))


def test_the_base2_epilogue_kernel_compiles_for_the_chip(one_chip):
    """Mosaic takes base2_merge_sum at the cells' shape (S4096 x J128 x
    B162) for every width and group count they launch, and the kernel reads
    the grid in place: no temporary."""
    import jax.numpy as jnp

    from filodb_tpu.ops import pallas_kernels as PK

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    for width, groups in BASE2_EPILOGUES:
        tile = PK.base2_epilogue_tile(4096, 128, 162, width, groups)
        assert tile == PK.LANES
        compiled = PK.base2_merge_sum.lower(
            arr((4096, 128, 162), jnp.float32), arr((4096,), jnp.int32),
            arr((4, 4096), jnp.int32), num_groups=groups, width=width,
            tile=tile, interpret=False).compile()
        assert "tpu_custom_call" in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("width,groups,delta", [(176, 40, False), (168, 40, True)],
                         ids=["expo", "expo_delta"])
def test_the_base2_program_writes_no_piece_to_hbm(one_chip, monkeypatch, width,
                                                  groups, delta):
    """The whole base-2 program of each cell with the kernel: the range
    product's [S, J, B] grid goes to the kernel with no copy, and no bf16
    piece of it, no [S, J, 3B] or [3S, J, W] concatenation, is an array of
    the program; its temporaries are about the grid alone (340 MB)."""
    import re

    import jax.numpy as jnp

    from filodb_tpu.ops import pallas_kernels as PK

    monkeypatch.setattr(PK, "interpret_mode", lambda: False)  # compiled for the chip

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    S, T, B, J, G = 4096, 768, 162, 128, groups
    i32 = jnp.int32
    spec = AGG.FusedSpec("hist_shared", "rate", ("hist2", "quantile", width, "pallas"),
                         G, (delta,) if delta else (False, "product"))
    compiled = AGG._fused_program_jit.lower(
        spec, (arr((S, T, B), jnp.float32),),
        tuple(arr((J,), i32) for _ in range(5)) + (jax.ShapeDtypeStruct((), i32),),
        arr((S,), i32),
        tuple(arr((S,), i32) for _ in range(3)) + tuple(arr((G + 1,), i32) for _ in range(3))
        + (arr((G, width), jnp.float32),),
        arr((2,), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"bf16\[(4096|12288),", text)
    assert not re.search(r"= f32\[(4096,128,162|162,4096,128)\]\{[^}]*\} copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.4e9
