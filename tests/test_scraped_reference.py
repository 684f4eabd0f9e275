"""The deployment ``prom-scraped-counters`` at a small size on the CPU: the
served path (``FiloServer`` over HTTP) against the plain reference
``scraped_panels`` on seeded data in which every series has its own clock,
for both bodies the foot of the kernel ladder can pick; the reference against
``counter_panels`` where the fleet has one clock; what the generator draws;
and the counter that says a launch ran off the ladder. Nothing here touches
a TPU."""

from __future__ import annotations

import copy
import json
import os
import time
import urllib.parse
import urllib.request

import numpy as np
import pytest

from benchmarks.chip import (
    counter_panels, readers, references, regular_counters, result_line,
    scraped_counters, scraped_panels,
)

ROOT = result_line.ROOT
CONFIG = result_line.chip_json("configs", "prom-scraped-counters.json")
COUNTERS = result_line.chip_json("configs", "filodb-dev-counters.json")
PANELS = result_line.chip_json("workloads", "scraped.repeat.json")["panels"]
INTERVAL, W = CONFIG["interval_ms"], 300_000
T0 = 1_700_000_000_000
N, T, STEPS, STEP = 240, 180, 24, 60_000  # 30 min of scrapes, 24 steps of 60 s
SMALL = dict(CONFIG, samples_per_series=T)
REGULAR = "regular_requests_total"  # the same fleet on one clock, beside it


def _out_t(t_last: int) -> np.ndarray:
    return t_last - (STEPS - 1 - np.arange(STEPS, dtype=np.int64)) * STEP


# -- the generator -----------------------------------------------------------


@pytest.fixture(scope="module")
def drawn():
    return scraped_counters.slots(CONFIG, 2000, np.random.default_rng(31), T0)


def test_the_generator_is_a_function_of_the_seed():
    a, b = (scraped_counters.make(SMALL, 50, np.random.default_rng(7), T0)
            for _ in range(2))
    assert a.ts.tobytes() == b.ts.tobytes() and a.vals.tobytes() == b.vals.tobytes()
    assert a.lens.tobytes() == b.lens.tobytes() and a.tags == b.tags
    c = scraped_counters.make(SMALL, 50, np.random.default_rng(8), T0)
    assert c.ts.tobytes() != a.ts.tobytes()


def test_the_values_and_tags_are_regular_counters_own(drawn):
    from filodb_tpu.core.schemas import METRIC_TAG

    same = regular_counters.make(COUNTERS, 2000, np.random.default_rng(31), T0)
    assert (drawn[1] == same.vals).all()
    assert scraped_counters.METRIC_TAG == METRIC_TAG
    assert scraped_counters.tags_of(same.name, 2000) == same.tags
    for key in ("metric", "series", "samples_per_series", "interval_ms", "shards", "spread"):
        assert CONFIG[key] == COUNTERS[key], key
    assert CONFIG["reduced"] == {} and {"phase", "late", "missed"} <= set(CONFIG["assumed"])


def test_a_phase_per_target_late_and_missed_scrapes_as_the_file_says(drawn):
    ts, _vals, keep, phase = drawn
    n, full = ts.shape
    t_last = T0 + (full - 1) * INTERVAL
    assert phase.min() >= 0 and phase.max() < INTERVAL
    hist = np.histogram(phase, bins=10, range=(0, INTERVAL))[0]
    assert hist.min() > 140 and hist.max() < 260  # 200 a bin, +- 4 sigma
    late = ts - (T0 + phase[:, None] + np.arange(full)[None, :] * INTERVAL)
    assert ((late == 0) | (late > CONFIG["late"]["tolerance_ms"])).all()
    assert late.max() <= CONFIG["late"]["cut_ms"] < INTERVAL
    assert 0.098 < (late > 0).mean() < 0.102
    tail = late[late > 0] - CONFIG["late"]["tolerance_ms"]
    assert 19.5 < tail.mean() < 21.5  # ceil of an exponential of mean 20
    # a scrape due or stamped after the newest scrape time has not happened
    future = ts > t_last
    assert not (keep & future).any() and (future[:, :-1].sum() == 0)
    assert (future[:, -1] == (phase + late[:, -1] > 0)).all()
    missed = ~keep & ~future
    assert 0.0047 < missed.mean() < 0.0053
    data = scraped_counters.ScrapedSet("m", *drawn[:3], [{}] * n)
    real = data.real()
    assert data.n_samples == int(keep.sum()) == int(real.sum()) < n * full
    assert (np.diff(data.ts, axis=1)[real[:, 1:]] > 0).all()  # strictly increasing
    assert data.ts[real].max() <= t_last and data.ts[real].min() >= T0
    assert (data.ts[~real] == scraped_counters.TS_PAD).all()
    lo, hi = T0 + 100 * INTERVAL + 17, T0 + 140 * INTERVAL + 5
    assert data.samples_in(lo, hi) == int((keep & (ts > lo) & (ts <= hi)).sum()) > 0


def test_the_reference_and_the_draws_import_nothing_of_the_program():
    for name in ("scraped_panels.py", "scraped_counters.py"):
        with open(os.path.join(ROOT, "benchmarks", "chip", name)) as f:
            text = f.read()
        if name == "scraped_counters.py":  # ``load`` is the door into the program
            head, _, text = text.partition("    def load(self, memstore, spread")
            text = head + text.partition("\ndef slots(")[2]
        assert "filodb_tpu" not in text.partition('"""\n\nfrom')[2], name
        assert "import jax" not in text


# -- the reference, where the fleet has one clock ------------------------------


@pytest.mark.parametrize("panel", PANELS, ids=lambda p: p["name"])
def test_on_one_clock_the_reference_is_counter_panels_to_the_last_bit(panel):
    """Every phase 0, nothing late, nothing missed: the shared grid."""
    one_clock = dict(CONFIG, phase={"span_ms": 1}, missed={"share": 0.0},
                     late=dict(CONFIG["late"], share=0.0))
    ours = scraped_counters.make(one_clock, 300, np.random.default_rng(5), T0)
    theirs = regular_counters.make(COUNTERS, 300, np.random.default_rng(5), T0)
    assert (ours.ts == theirs.ts[None, :]).all() and (ours.vals == theirs.vals).all()
    assert (np.diff(ours.vals, axis=1) < 0).any()  # a reset is in the draw
    full = CONFIG["samples_per_series"]
    out_t = T0 + (full - 1) * INTERVAL - (113 - np.arange(114)) * STEP
    out_t = np.concatenate([[T0 - W, T0], out_t])  # windows of 0 and of 1 sample
    got = scraped_panels.reference(ours, out_t, W, panel)
    want = counter_panels.reference(theirs, out_t, W, panel)
    assert got.keys() == want.keys()
    for key in want:
        assert got[key].tobytes() == want[key].tobytes(), key
        assert list(np.isnan(want[key][:3])) == [
            True, panel["fn"] != "avg_over_time", False]


# -- the served path against the reference -------------------------------------


def _brute(data, s, out_t, fn):
    """One series, step by step in python: the samples with t - w < ts <= t."""
    n = int(data.lens[s])
    ts, v = data.ts[s, :n].astype(float), data.vals[s, :n]
    c = v.copy()
    for i in range(1, n):
        c[i] = c[i - 1] + (v[i] - v[i - 1] if v[i] >= v[i - 1] else v[i])
    out = np.full(len(out_t), np.nan)
    for j, t in enumerate(out_t):
        idx = [i for i in range(n) if t - W < ts[i] <= t]
        if fn == "avg_over_time" and idx:
            out[j] = float(np.mean(v[idx]))
        elif fn == "irate" and len(idx) >= 2:
            a, b = idx[-2], idx[-1]
            out[j] = (c[b] - c[a]) / ((ts[b] - ts[a]) / 1e3)
        elif fn == "rate" and len(idx) >= 2:
            a, b = idx[0], idx[-1]
            d, sampled = c[b] - c[a], (ts[b] - ts[a]) / 1e3
            ds, de = (ts[a] - (t - W)) / 1e3, (t - ts[b]) / 1e3
            if d > 0 and v[a] >= 0:
                ds = min(ds, sampled * v[a] / d)
            avg = sampled / (len(idx) - 1)
            ds = avg / 2 if ds >= avg * 1.1 else ds
            de = avg / 2 if de >= avg * 1.1 else de
            out[j] = d * (sampled + ds + de) / sampled / (W / 1e3)
    return out


class Fleet:
    """A small scraped fleet ending at the wall clock's newest scrape time
    (the server evicts by wall-clock retention), with the cases planted that
    a shared grid never has, in series 0-4."""

    def __init__(self):
        self.t_last = int(time.time() * 1000) // INTERVAL * INTERVAL - INTERVAL
        self.t0 = self.t_last - (T - 1) * INTERVAL
        self.out_t = _out_t(self.t_last)
        ts, vals, keep, phase = scraped_counters.slots(
            SMALL, N, np.random.default_rng(41), self.t0)
        keep[0, 60:100] = False      # 400 s without a sample but one:
        keep[0, 80] = True           # windows of 1 sample
        keep[1, 50:120] = False      # 700 s without any: windows of 0 samples
        vals[2, 90:] -= vals[2, 89]  # a reset just after a missed scrape
        keep[2, 89] = False
        vals[3, 100:] -= vals[3, 99]  # and just before one
        keep[3, 101] = False
        # a scrape due 3 ms before a step and 8 ms late: it crosses the step
        self.step = int(self.out_t[10])
        ts[4] = ts[4] - phase[4] + (self.step - 3 - self.t0) % INTERVAL
        self.slot = (self.step - 3 - self.t0) // INTERVAL
        keep[4] = ts[4] <= self.t_last
        self.on_time = scraped_counters.ScrapedSet(
            "m", ts[4:5].copy(), vals[4:5], keep[4:5], [{}])
        ts[4, self.slot] = self.step + 5
        self.data = scraped_counters.ScrapedSet(
            CONFIG["metric"], ts, vals, keep, scraped_counters.tags_of(CONFIG["metric"], N))
        self.regular = regular_counters.make(
            dict(COUNTERS, metric=REGULAR, samples_per_series=T), N,
            np.random.default_rng(41), self.t0)


@pytest.fixture(scope="module")
def fleet():
    return Fleet()


@pytest.fixture(scope="module")
def served(fleet):
    from filodb_tpu.server import FiloServer

    srv = FiloServer({"http_port": 0, "query": {"prewarm": {"enabled": False}}})
    port = srv.start()
    try:
        assert fleet.data.load(srv.memstore, srv.spread) == fleet.data.n_samples
        assert fleet.regular.load(srv.memstore, srv.spread) == fleet.regular.n_samples
        yield port
    finally:
        srv.stop()


def _get(port: int, path: str, **params) -> str:
    url = f"http://127.0.0.1:{port}{path}"
    if params:
        url += "?" + urllib.parse.urlencode(params)
    with urllib.request.urlopen(url, timeout=120) as r:
        return r.read().decode()


def _ask(port: int, fleet, query: str) -> dict:
    body = _get(port, "/api/v1/query_range", query=query, step=STEP // 1000,
                start=int(fleet.out_t[0]) / 1000, end=int(fleet.out_t[-1]) / 1000)
    return references.parse_matrix(body, fleet.out_t)


def _launches(port: int) -> dict:
    """{(body, grid): launches so far} off /metrics, as the benchmark reads it."""
    out = {}
    for (name, labels), v in readers.parse_metrics(_get(port, "/metrics")).items():
        if name == "filodb_fused_dispatch_total":
            ls = {k: val.strip('"') for k, val in labels}
            out[ls["body"], ls["grid"]] = v
    return out


def _lane_tiles(port: int) -> dict:
    """{kind: lane tiles so far} of the Pallas body's launches, off /metrics."""
    out = {"scanned": 0.0, "resident": 0.0}
    for (name, labels), v in readers.parse_metrics(_get(port, "/metrics")).items():
        if name == "filodb_pallas_lane_tiles_total":
            out[dict(labels)["kind"].strip('"')] = v
    return out


def test_the_planted_cases_are_in_the_fleet(fleet):
    d, out_t = fleet.data, fleet.out_t
    cnt = (scraped_panels.counts_le(d.ts[:5], d.lens[:5], out_t)
           - scraped_panels.counts_le(d.ts[:5], d.lens[:5], out_t - W))
    assert (cnt[0] == 1).any() and (cnt[1] == 0).any() and cnt[2:].min() >= 2
    assert d.lens[2] == d.lens[3] == T - 1 or d.lens[2] < T  # a scrape is missing
    for s in (2, 3):
        assert (np.diff(d.vals[s, :d.lens[s]]) < 0).sum() == 1
    # the late scrape lies after its step: it belongs to the next window
    lane = int(np.searchsorted(d.ts[4, :d.lens[4]], fleet.step + 5))
    assert d.ts[4, lane] == fleet.step + 5 > fleet.step > d.ts[4, lane - 1]
    moved = scraped_panels._series_grid(
        "irate", d.ts[4:5], d.vals[4:5], d.lens[4:5], out_t, W, None)[0]
    stayed = scraped_panels._series_grid(
        "irate", fleet.on_time.ts, fleet.on_time.vals, fleet.on_time.lens, out_t, W, None)[0]
    assert moved[10] != stayed[10] and (moved[:10] == stayed[:10]).all()


@pytest.mark.parametrize("fn", ["rate", "irate", "avg_over_time"])
def test_the_reference_is_a_brute_force_per_series_and_step(fleet, fn):
    d, out_t = fleet.data, fleet.out_t
    rows = list(range(12))
    got = scraped_panels._series_grid(fn, d.ts[:12], d.vals[:12], d.lens[:12], out_t, W, None)
    for s in rows:
        want = _brute(d, s, out_t, fn)
        assert (np.isnan(got[s]) == np.isnan(want)).all(), s
        # avg_over_time takes a window's sum off prefix sums of offsets from
        # the first sample, as counter_panels does: ~1e9 after a reset
        np.testing.assert_allclose(got[s], want, err_msg=str(s),
                                   rtol=1e-7 if fn == "avg_over_time" else 1e-12)


@pytest.mark.parametrize("body", ["general", "pallas"])
def test_the_served_path_equals_the_reference_off_the_ladder(
        fleet, served, body, monkeypatch):
    """Every panel of the cell over HTTP, within the cell's limits, through
    the body named; each launch booked ``grid="irregular"``, once."""
    from filodb_tpu.ops.pallas_kernels import PALLAS_FUNCS

    monkeypatch.setenv("FILODB_PALLAS", "1" if body == "pallas" else "0")
    assert {p["fn"] for p in PANELS} <= PALLAS_FUNCS
    for panel in PANELS:
        before = _launches(served)
        got = _ask(served, fleet, panel["query"])
        want = scraped_panels.reference(fleet.data, fleet.out_t, W, panel)
        r = references.compare(got, want)
        assert r["malformed"] == 0 and r["absent_mismatch"] == 0, (panel["name"], r)
        assert r["rel_err"] <= panel["rel_err_limit"], (panel["name"], r)
        grew = {k: v - before.get(k, 0) for k, v in _launches(served).items()
                if v != before.get(k, 0)}
        assert grew == {(body, "irregular"): 1.0}, panel["name"]


def test_the_pallas_body_books_the_lane_tiles_its_steps_read(fleet, served, monkeypatch):
    """``filodb_pallas_lane_tiles_total``: the fleet's superblock is more
    than two lane tiles wide and every window of a panel touches at most
    two, so each launch books NARROW lane tiles a step as ``scanned`` and
    the row's as ``resident``; a repeated panel books the same again (off
    the memo on the block); a shared-grid query, on ``mxu``, books neither."""
    from filodb_tpu.ops import pallas_kernels as PK

    monkeypatch.setenv("FILODB_PALLAS", "1")

    def grown(query):
        before = _lane_tiles(served)
        _ask(served, fleet, query)
        after = _lane_tiles(served)
        return {k: after[k] - before[k] for k in after}

    for panel in PANELS:
        first = grown(panel["query"])
        assert 0 < first["scanned"] < first["resident"], panel["name"]
        grid_tiles, odd = divmod(first["scanned"], PK.BJ * PK.NARROW)
        lane_tiles = first["resident"] / (grid_tiles * PK.BJ)
        assert odd == 0 and lane_tiles == int(lane_tiles) > PK.NARROW, (panel["name"], first)
        assert grown(panel["query"]) == first, panel["name"]
    assert grown(f"sum(rate({REGULAR}[5m]))") == {"scanned": 0.0, "resident": 0.0}


def test_a_late_scrape_read_at_its_slot_is_outside_the_limit(fleet, served):
    """The limit sees one sample of one series moved by 8 ms over a step."""
    panel = next(p for p in PANELS if p["fn"] == "irate")
    d = fleet.data
    wrong = copy.copy(d)
    wrong.ts = d.ts.copy()
    wrong.ts[4] = fleet.on_time.ts[0]
    got = _ask(served, fleet, panel["query"])
    r = references.compare(got, scraped_panels.reference(wrong, fleet.out_t, W, panel))
    assert r["rel_err"] > panel["rel_err_limit"]


def test_the_grid_is_irregular_and_a_shared_grid_stays_on_the_ladder(fleet, served):
    from filodb_tpu.ops import staging as ST

    d = fleet.data
    S = ST.pad_series(N)
    assert ST.detect_shared_grid(d.ts, d.lens, N, T, S) == (None, None, None, 0)
    # and were no scrape ever missed, the phases alone put every sample ~5 s
    # from any shared nominal grid: the jitter class wants 2 x the widest
    # deviation under the shortest interval
    whole = np.nonzero(d.lens == T - 1)[0]
    assert len(whole) > N // 4
    _nom, _dev, maxdev = ST.nominal_midrange(d.ts[whole, :T - 1])
    assert 2 * maxdev >= 0.9 * INTERVAL
    _ask(served, fleet, PANELS[0]["query"])
    before = _launches(served)
    assert _ask(served, fleet, f"sum(rate({REGULAR}[5m]))") is not None
    grew = {k: v - before.get(k, 0) for k, v in _launches(served).items()
            if v != before.get(k, 0)}
    assert grew == {("mxu", "regular"): 1.0}
    blocks = json.loads(_get(served, "/debug/superblocks"))["data"]["entries"]
    grids = {b["grid"] for b in blocks}
    assert grids == {"irregular", "regular"}
