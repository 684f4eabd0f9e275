#!/usr/bin/env python3
"""chip_smoke.py — does the served query path start and answer correctly on
the chip?

ONE process: starts the real server object (``FiloServer(cfg).start()``,
what ``python -m filodb_tpu.cli serve`` runs) on the shipped ``config.py``
defaults (8 shards, spread 3, memory-only), bulk-loads a deployment-sized
history through the ingest API the HTTP handlers call
(``TimeSeriesMemStore.ingest_routed``), then drives it over HTTP and checks
every answer against a plain numpy f64 oracle written here. Data comes from
``--seed``; nothing is imported from tests/.

It FAILS (non-zero exit, no timing, no result line) when jax finds no TPU:
the platform is pinned to ``tpu`` before jax is imported, so a failed init
is an error and never a quiet CPU run. ``--cpu-rehearsal`` is the one
explicit switch for a tiny CPU run of the same phases (for debugging the
script itself); every line it prints says ``platform: cpu``.

The walls it prints are host walls of one HTTP request (sent -> body read),
for orientation only — this is not a benchmark. The last stdout line is one
JSON object: {"ok": true, "device": {"platform", "kind", "count"}}.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time
import urllib.error
import urllib.parse
import urllib.request

import numpy as np

INTERVAL_MS = 10_000
N_SAMPLES = 720  # 2 h at 10 s
WINDOW_MS = 300_000
STEP_S = 60
N_STEPS = 114  # query grid: the last ~1.9 h of the history, 60 s steps
# the wide irregular set: more samples per series in one selector than the
# Pallas kernel is selected for (pallas_kernels.MAX_T = 4096 padded), so the
# OTHER side of that shape-based choice — `general` on an irregular grid —
# runs on the chip too. ~14.4 h at ~10 s; queried over its last 12.5 h.
WIDE_SAMPLES = 5200
WIDE_STEP_S = 600
WIDE_STEPS = 76
RTOL = 5e-3  # max rel err is printed to tighten
# avg(avg_over_time) sums 1e9-sized values over every series: held to the
# limit of the benchmark cell counters.repeat (its avg_avg_over_time panel;
# tests/chip_benchmark/test_counters_cell.py keeps the two equal). RTOL let
# the f32 segment sum's 1.06e-3 pass for seven PRs.
WIDE_SUM_RTOL = 1.5e-6
# sharded vs single-device: the same f32 sums in another order. The 2e-5
# __graft_entry__ asserts is for its 32 series; at 131k series the two
# orders measured 1.16e-5 apart on four v5e chips, 2.24e-5 after the
# quantile interpolation's ~9x gain — the bar is ~2x what was measured.
# The sharded answers measured 2.9e-6 / 9.4e-6 from the f64 oracle.
MESH_RTOL = 5e-5
MESH_ORACLE_RTOL = 1e-4
WARM_RUNS = 3
PREWARM_WAIT_S = 300.0
# series per set: (full, rehearsal)
SIZES = {
    "main": (100_000, 384),      # regular grid, counters
    "jitter": (20_000, 96),      # +/-5 % scrape jitter
    "holes": (20_000, 96),       # jitter + 2 % dropped scrapes
    "irregular": (4_096, 64),    # per-series random intervals
    "wide": (512, 16),           # the same, x WIDE_SAMPLES samples each
    "hist": (8_000, 48),         # native histograms x 12 buckets
    "small": (256, 32),          # topk selector + the HTTP-appended scrape
}
METRICS = {
    "main": "http_requests_total",
    "jitter": "jittered_requests_total",
    "holes": "holey_requests_total",
    "irregular": "irregular_requests_total",
    "wide": "wide_irregular_requests_total",
    "hist": "http_request_latency",
    "small": "smoke_heartbeat_total",
}


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# data (all from --seed)
# ---------------------------------------------------------------------------


class ScalarSet:
    """One metric's history: padded [S, T] int64 ms timestamps (TS_SENTINEL
    past each row's length), f64 values, per-row lengths, tag dicts."""

    TS_SENTINEL = np.int64(2**62)

    def __init__(self, name, ts, vals, lens, tags):
        self.name, self.ts, self.vals, self.lens, self.tags = (
            name, ts, vals, lens, tags)

    @property
    def n_samples(self) -> int:
        return int(self.lens.sum())

    def append_scrape(self, t_ms: int, new_vals: np.ndarray) -> None:
        S = len(self.lens)
        self.ts = np.concatenate(
            [self.ts, np.full((S, 1), self.TS_SENTINEL)], axis=1)
        self.vals = np.concatenate([self.vals, np.zeros((S, 1))], axis=1)
        self.ts[np.arange(S), self.lens] = t_ms
        self.vals[np.arange(S), self.lens] = new_vals
        self.lens = self.lens + 1


def _tags(metric: str, n: int, zones: bool = True) -> list[dict]:
    from filodb_tpu.core.schemas import METRIC_TAG

    return [{METRIC_TAG: metric, "_ws_": "demo", "_ns_": "App-2",
             "instance": f"host-{i}", **({"zone": f"z{i % 8}"} if zones else {})}
            for i in range(n)]


def _counter_values(rng, n: int, T: int) -> np.ndarray:
    """Counters as every earlier round's benchmark made them (uniform
    increments on a 1e9 base), plus one reset in 1 % of the series — a
    restarted target — so the reset correction runs on the chip too."""
    vals = np.cumsum(rng.uniform(0, 10, size=(n, T)), axis=1) + 1e9
    for r in np.nonzero(rng.random(n) < 0.01)[0]:
        k = int(rng.integers(T // 8, T - T // 8))
        vals[r, k:] -= vals[r, k - 1]
    return vals


def make_scalar_set(kind: str, n: int, rng, t0: int,
                    T: int = N_SAMPLES) -> ScalarSet:
    metric = METRICS[kind]
    nominal = t0 + np.arange(T, dtype=np.int64) * INTERVAL_MS
    lens = np.full(n, T, dtype=np.int64)
    if kind in ("main", "small"):
        ts = np.broadcast_to(nominal, (n, T)).copy()
    elif kind in ("jitter", "holes"):
        # half-interval phase: no nominal slot sits on a 10 s-aligned
        # staging boundary, where +/-jitter would clip it for SOME series
        dev = np.rint(rng.uniform(-0.05, 0.05, size=(n, T)) * INTERVAL_MS)
        ts = nominal[None, :] + INTERVAL_MS // 2 + dev.astype(np.int64)
    else:  # irregular: every series scrapes at its own random intervals
        gaps = rng.integers(5_000, 15_000, size=(n, T)).astype(np.int64)
        ts = t0 + np.cumsum(gaps, axis=1)
    vals = _counter_values(rng, n, T)
    if kind == "holes":
        keep = np.ones((n, T), bool)
        n_drop = max(1, int(0.02 * T))
        for i in range(n):
            keep[i, rng.choice(np.arange(1, T - 1), n_drop, replace=False)] = False
        lens = keep.sum(1)
        order = np.argsort(~keep, axis=1, kind="stable")  # kept first, in time order
        ts = np.take_along_axis(ts, order, axis=1)
        vals = np.take_along_axis(vals, order, axis=1)
        pad = np.arange(T)[None, :] >= lens[:, None]
        ts[pad] = ScalarSet.TS_SENTINEL
        vals[pad] = 0.0
    return ScalarSet(metric, ts, vals, lens, _tags(metric, n))


def make_hist(n: int, rng, t0: int):
    """Native cumulative histograms [S, T, B]. Observations spread evenly
    over the first nine buckets with a thin tail, so the 0.99 quantile
    lands inside a finite bucket that holds ~11 % of the mass: the
    interpolation is exercised, and it amplifies f32 summation-order noise
    ~9x (a bucket with 1 % of the mass would amplify it ~100x and turn the
    sharded-vs-single comparison into a test of the data)."""
    from filodb_tpu.core.histograms import PROM_DEFAULT

    les = PROM_DEFAULT.bounds()
    B = len(les)
    lam = np.array([2.0] * 9 + [0.04] * (B - 10) + [0.01])
    ts = t0 + np.arange(N_SAMPLES, dtype=np.int64) * INTERVAL_MS
    hist = np.empty((n, N_SAMPLES, B))
    for b0 in range(0, n, 1000):
        obs = rng.poisson(lam, size=(min(1000, n - b0), N_SAMPLES, B))
        hist[b0:b0 + 1000] = np.cumsum(np.cumsum(obs, axis=2), axis=1)
    total = np.cumsum(rng.uniform(0, 5, size=(n, N_SAMPLES)), axis=1)
    return ts, hist, total, les, _tags(METRICS["hist"], n, zones=False)


def _repeat_tags(tags: list[dict], counts) -> list[dict]:
    return list(itertools.chain.from_iterable(
        itertools.repeat(t, int(c)) for t, c in zip(tags, counts)))


def load_scalar(memstore, s: ScalarSet, spread: int) -> int:
    from filodb_tpu.core.records import RecordBatch
    from filodb_tpu.core.schemas import PROM_COUNTER

    n = 0
    T = s.ts.shape[1]
    for b0 in range(0, len(s.lens), 10_000):
        sl = slice(b0, b0 + 10_000)
        live = np.arange(T)[None, :] < s.lens[sl, None]
        n += memstore.ingest_routed("prometheus", RecordBatch(
            PROM_COUNTER, s.ts[sl][live], {"count": s.vals[sl][live]},
            _repeat_tags(s.tags[sl], s.lens[sl]),
        ), spread)
    return n


def load_hist(memstore, ts, hist, total, les, tags, spread: int) -> int:
    from filodb_tpu.core.records import RecordBatch
    from filodb_tpu.core.schemas import PROM_HISTOGRAM

    n = 0
    T, B = hist.shape[1], hist.shape[2]
    for b0 in range(0, len(tags), 2_000):
        h = hist[b0:b0 + 2_000]
        k = len(h)
        n += memstore.ingest_routed("prometheus", RecordBatch(
            PROM_HISTOGRAM, np.tile(ts, k),
            {"sum": total[b0:b0 + k].ravel(), "count": h[..., -1].ravel(),
             "h": h.reshape(-1, B)},
            _repeat_tags(tags[b0:b0 + k], itertools.repeat(T)),
            bucket_les=les,
        ), spread)
    return n


# ---------------------------------------------------------------------------
# the oracle: plain numpy f64, PromQL semantics, independent of filodb_tpu
# ---------------------------------------------------------------------------


def windows(s: ScalarSet, out_t: np.ndarray, t0: int):
    """(lo, hi) [S, J]: sample i of series s is in window j = (t_j - w, t_j]
    iff lo <= i < hi. One flat searchsorted over row-offset timestamps."""
    S, T = s.ts.shape
    big = np.int64(10) ** 9
    rel = np.minimum(s.ts - t0, big - 1)  # sentinels sort last in their row
    flat = (rel + np.arange(S, dtype=np.int64)[:, None] * big).ravel()
    rows = np.arange(S, dtype=np.int64)[:, None]
    base = rows * T

    def first_after(t):  # first index with ts > t
        q = (t - t0)[None, :] + rows * big
        return np.searchsorted(flat, q.ravel(), side="right").reshape(S, -1) - base

    return first_after(out_t - WINDOW_MS), first_after(out_t)


def _take(a, idx):
    return np.take_along_axis(a, np.clip(idx, 0, a.shape[1] - 1), axis=1)


def reset_corrected(vals: np.ndarray) -> np.ndarray:
    drop = np.where(np.diff(vals, axis=1) < 0, vals[:, :-1], 0.0)
    out = vals.copy()
    out[:, 1:] += np.cumsum(drop, axis=1)
    return out


def rate_factor(tf_ms, tl_ms, cnt, out_t, zero_cap=None):
    """promql extrapolatedRate over (t - w, t] as the factor that turns a
    window's increase into its rate; tf/tl/cnt are [S, J]. ``zero_cap`` =
    (increase, first raw value) applies the counter rule that a series is
    not extrapolated back past where it would have been zero (histogram
    buckets pass None, as the engine's per-bucket rate does). The caller
    masks windows with fewer than 2 samples."""
    sampled = (tl_ms - tf_ms) / 1e3
    dur_start = (tf_ms - (out_t - WINDOW_MS)[None, :]) / 1e3
    dur_end = (out_t[None, :] - tl_ms) / 1e3
    avg = sampled / np.maximum(cnt - 1, 1)
    with np.errstate(divide="ignore", invalid="ignore"):
        if zero_cap is not None:
            delta, first_raw = zero_cap
            dur_start = np.minimum(dur_start, np.where(
                (delta > 0) & (first_raw >= 0), sampled * first_raw / delta,
                np.inf))
        dur_start = np.where(dur_start >= avg * 1.1, avg / 2, dur_start)
        dur_end = np.where(dur_end >= avg * 1.1, avg / 2, dur_end)
        return (sampled + dur_start + dur_end) / sampled / (WINDOW_MS / 1e3)


def o_rate(s: ScalarSet, out_t, t0) -> np.ndarray:
    lo, hi = windows(s, out_t, t0)
    cnt = hi - lo
    c = reset_corrected(s.vals)
    delta = _take(c, hi - 1) - _take(c, lo)
    k = rate_factor(_take(s.ts, lo), _take(s.ts, hi - 1), cnt, out_t,
                    zero_cap=(delta, _take(s.vals, lo)))
    with np.errstate(invalid="ignore"):  # 0 x inf in windows masked below
        return np.where(cnt >= 2, delta * k, np.nan)


def o_irate(s: ScalarSet, out_t, t0) -> np.ndarray:
    lo, hi = windows(s, out_t, t0)
    v1, v0 = _take(s.vals, hi - 1), _take(s.vals, hi - 2)
    dt = (_take(s.ts, hi - 1) - _take(s.ts, hi - 2)) / 1e3
    with np.errstate(divide="ignore", invalid="ignore"):
        r = np.where(v1 < v0, v1, v1 - v0) / dt
    return np.where(hi - lo >= 2, r, np.nan)


def o_avg_over_time(s: ScalarSet, out_t, t0) -> np.ndarray:
    lo, hi = windows(s, out_t, t0)
    # window sums from a mean-centred prefix sum (f64 keeps ~1e-7 rel here)
    mean = s.vals.mean()
    p = np.concatenate([np.zeros((len(s.lens), 1)),
                        np.cumsum(s.vals - mean, axis=1)], axis=1)
    cnt = hi - lo
    with np.errstate(divide="ignore", invalid="ignore"):
        r = (_take(p, hi) - _take(p, lo)) / cnt + mean
    return np.where(cnt > 0, r, np.nan)


def o_max_over_time(s: ScalarSet, out_t, t0) -> np.ndarray:
    """Shared-grid sets only (windows are the same index range for every
    series), which is all the smoke asks max_over_time of."""
    lo, hi = windows(s, out_t, t0)
    check(bool((lo == lo[0]).all() and (hi == hi[0]).all()),
          "oracle: max_over_time asked of a non-shared grid")
    out = np.full(lo.shape, np.nan)
    for j in range(lo.shape[1]):
        if hi[0, j] > lo[0, j]:
            out[:, j] = s.vals[:, lo[0, j]:hi[0, j]].max(axis=1)
    return out


def nansum0(sj):
    """PromQL sum/avg over series: NaN = absent; all-absent step = NaN."""
    has = ~np.isnan(sj)
    return np.where(has.any(0), np.where(has, sj, 0.0).sum(0), np.nan), has.sum(0)


def o_hist_quantile(q, ts, hist, les, out_t) -> np.ndarray:
    hi = np.searchsorted(ts, out_t, side="right")
    lo = np.searchsorted(ts, out_t - WINDOW_MS, side="right")
    cnt = (hi - lo)[None, :]
    T = len(ts)
    lo_c, hi_c = np.minimum(lo, T - 1), np.clip(hi - 1, 0, T - 1)
    tf = ts[lo_c][None, :].astype(np.float64)
    tl = ts[hi_c][None, :].astype(np.float64)
    k = rate_factor(tf, tl, cnt, out_t)[0]
    bsum = np.zeros((len(out_t), len(les)))
    for b0 in range(0, len(hist), 2_000):  # bound the [s, J, B] temporary
        h = hist[b0:b0 + 2_000]
        bsum += (h[:, hi_c] - h[:, lo_c]).sum(0)
    bsum *= np.where(cnt[0] >= 2, k, np.nan)[:, None]
    # promql histogram_quantile: linear interpolation inside the located
    # bucket, first bucket from 0, +Inf bucket -> highest finite bound
    total = bsum[:, -1]
    rank = q * total
    meets = bsum >= rank[:, None]
    idx = np.where(meets.any(1), np.argmax(meets, axis=1), len(les) - 1)
    rows = np.arange(len(out_t))
    c_hi = bsum[rows, idx]
    c_lo = np.where(idx > 0, bsum[rows, np.maximum(idx - 1, 0)], 0.0)
    le_lo = np.where(idx > 0, les[np.maximum(idx - 1, 0)], 0.0)
    with np.errstate(divide="ignore", invalid="ignore"):
        val = le_lo + (les[idx] - le_lo) * (rank - c_lo) / (c_hi - c_lo)
    val = np.where(idx == len(les) - 1, les[-2], val)
    return np.where(total > 0, val, np.nan)


# ---------------------------------------------------------------------------
# HTTP client + the server's own surfaces
# ---------------------------------------------------------------------------


class Client:
    def __init__(self, port: int):
        self.base = f"http://127.0.0.1:{port}"

    def _open(self, req, what: str) -> bytes:
        try:
            with urllib.request.urlopen(req, timeout=600) as r:
                return r.read()
        except urllib.error.HTTPError as e:  # the body says what went wrong
            raise SmokeFailure(
                f"{what}: HTTP {e.code}: {e.read()[:600].decode(errors='replace')}"
            ) from e

    def get(self, path: str, **params) -> bytes:
        url = self.base + path
        if params:
            url += "?" + urllib.parse.urlencode(params)
        return self._open(url, f"GET {path} {params.get('query', '')}".strip())

    def json(self, path: str, **params):
        out = json.loads(self.get(path, **params))
        check(out.get("status") != "error", f"{path}: {str(out)[:300]}")
        return out.get("data", out)

    def post(self, path: str, body: bytes):
        req = urllib.request.Request(self.base + path, data=body, method="POST")
        return json.loads(self._open(req, f"POST {path}"))

    def counters(self) -> dict:
        """{(name, frozenset(labels)): value} of /metrics (sample lines)."""
        out = {}
        for line in self.get("/metrics").decode().splitlines():
            if not line or line[0] == "#":
                continue
            head, _, val = line.rpartition(" ")
            name, _, rest = head.partition("{")
            labels = frozenset(
                tuple(kv.split("=", 1)) for kv in rest.rstrip("}").split(",") if kv
            ) if rest else frozenset()
            try:
                out[(name, labels)] = float(val)
            except ValueError:
                pass
        return out


def total(counters: dict, name: str, **labels) -> float:
    want = {(k, f'"{v}"') for k, v in labels.items()}
    return sum(v for (n, ls), v in counters.items() if n == name and want <= ls)


def matrix(data: dict, out_t: np.ndarray) -> list[tuple[dict, np.ndarray]]:
    """query_range JSON -> [(labels, [J] f64 on the step grid, NaN=absent)]."""
    check(data["resultType"] == "matrix", f"resultType {data['resultType']}")
    pos = {int(t): j for j, t in enumerate(out_t)}
    out = []
    for series in data["result"]:
        row = np.full(len(out_t), np.nan)
        for t, v in series["values"]:
            row[pos[int(round(float(t) * 1000))]] = float(v)
        out.append((series["metric"], row))
    return out


def max_rel_err(got: np.ndarray, want: np.ndarray, what: str,
                rtol: float = RTOL) -> float:
    check(got.shape == want.shape, f"{what}: shape {got.shape} != {want.shape}")
    check(bool((np.isnan(got) == np.isnan(want)).all()),
          f"{what}: absent steps differ from the oracle "
          f"(got {int(np.isnan(got).sum())} NaN, want {int(np.isnan(want).sum())})")
    m = ~np.isnan(want)
    check(bool(m.any()), f"{what}: the oracle has no samples at all")
    check(bool(np.isfinite(got[m]).all()), f"{what}: non-finite values")
    err = float(np.max(np.abs(got[m] - want[m]) / np.maximum(np.abs(want[m]), 1e-30)))
    check(err <= rtol, f"{what}: max rel err {err:.3g} > {rtol}")
    return err


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


class Smoke:
    def __init__(self, args, jax, say):
        self.args, self.jax, self.say = args, jax, say
        self.platform = jax.devices()[0].platform
        self.idx = 1 if args.cpu_rehearsal else 0
        self.rng = np.random.default_rng(args.seed)
        self.sets: dict[str, ScalarSet] = {}
        self.walls: list[tuple] = []

    # -- background quiescence ------------------------------------------------

    def prewarm_state(self) -> tuple[int, int]:
        """(eligible, done): recurrence-ring keys the server's background
        pre-warm will re-execute, and how many it has finished. The shipped
        defaults (query.prewarm) re-run a key off the serving path once it
        was seen min_count times — or once, after any recompile storm."""
        c = self.client
        ring = c.json("/debug/standing")["key_ring"]
        need = 1 if c.json("/debug/kernels", limit=0)["storms"] else 3
        eligible = sum(1 for e in ring if e["count"] >= need
                       and (e.get("desc") or {}).get("promql"))
        return eligible, int(total(c.counters(), "filodb_prewarm_total"))

    def wait_prewarm_idle(self) -> int:
        """Counting dispatches per warm query needs that background thread
        idle: wait until every eligible key has been pre-warmed. Returns
        the number done."""
        t0 = time.monotonic()
        while True:
            eligible, done = self.prewarm_state()
            if done >= eligible:
                waited = time.monotonic() - t0
                if waited > 1.0:
                    self.say(f"  (waited {waited:.1f} s for the server's "
                             f"background pre-warm: {done} keys done)")
                return done
            check(time.monotonic() - t0 < PREWARM_WAIT_S,
                  f"background pre-warm never went idle: {done}/{eligible}")
            time.sleep(0.1)

    # -- one checked query ----------------------------------------------------

    def grid(self, n_steps: int, step_s: int) -> tuple[dict, np.ndarray]:
        """(query_range params, step timestamps ms): ``n_steps`` steps of
        ``step_s`` ending at the newest bulk-loaded scrape."""
        start = self.t_end - (n_steps - 1) * step_s * 1000
        return ({"start": start / 1000, "end": self.t_end / 1000, "step": step_s},
                np.arange(start, self.t_end + 1, step_s * 1000, dtype=np.int64))

    def run_query(self, promql: str, oracle, *, fused_variant: str | None,
                  grid=None):
        """Cold once + WARM_RUNS warm; every response checked by ``oracle``
        (a function of the parsed matrix returning the max rel err). With
        ``fused_variant`` set, every warm run must be path=fused, exactly
        one kernel dispatch, zero compiles, on that kernel variant.
        ``grid`` = another (params, step timestamps) than the run's own."""
        c, label = self.client, promql
        grid_params, out_t = grid or (self.grid_params, self.out_t)
        walls, err, rec, run = [], 0.0, None, 0
        while run < 1 + WARM_RUNS:
            prewarmed = self.wait_prewarm_idle()
            before = c.counters()
            t_q = time.perf_counter()
            body = c.get("/api/v1/query_range", query=promql, **grid_params)
            walls.append(time.perf_counter() - t_q)
            after = c.counters()
            data = json.loads(body)
            check(data["status"] == "success", f"{label}: {str(data)[:300]}")
            err = max(err, oracle(matrix(data["data"], out_t)))
            rec = next(r for r in c.json("/debug/querylog", limit=8)
                       if r["promql"] == promql)
            check(rec["status"] == "ok", f"{label}: querylog status {rec['status']}")
            run += 1
            if run == 1:
                cold = rec
                continue
            if fused_variant is None:
                continue
            if self.prewarm_state()[0] > prewarmed:
                # this very request made its key eligible: the pre-warm may
                # have dispatched inside the window just counted — the
                # answer was checked, the counters are redone on a quiet run
                run -= 1
                walls.pop()
                continue
            d_disp = (total(after, "filodb_kernel_dispatch_seconds_count")
                      - total(before, "filodb_kernel_dispatch_seconds_count"))
            d_comp = (total(after, "filodb_xla_compiles_total")
                      - total(before, "filodb_xla_compiles_total"))
            d_fall = (total(after, "filodb_fused_fallback_total")
                      - total(before, "filodb_fused_fallback_total"))
            key = rec.get("executable_key") or ""
            variant = dict(kv.split("=", 1) for kv in key.split("|") if "=" in kv
                           ).get("variant")
            check(rec["path"] == "fused" and rec["fallback_reason"] is None,
                  f"{label}: warm path={rec['path']} "
                  f"fallback={rec['fallback_reason']}")
            check(d_fall == 0, f"{label}: fused fallback counter moved by {d_fall}")
            check(d_disp == 1, f"{label}: warm query issued {d_disp} kernel "
                               "dispatches, want exactly 1")
            check(d_comp == 0 and not rec["compile_miss"],
                  f"{label}: {d_comp} compiles in the warm window "
                  f"(compile_miss={rec['compile_miss']})")
            check(variant == fused_variant,
                  f"{label}: kernel variant {variant!r} (grid_class="
                  f"{rec['grid_class']}), want {fused_variant!r} — a "
                  "degrade to another kernel is a failure here")
        ph = cold["phases_ms"]
        warm = sorted(walls[1:])[len(walls[1:]) // 2]
        self.walls.append((label, walls[0], ph.get("stage", 0.0),
                           ph.get("dispatch", 0.0), bool(cold["compile_miss"]),
                           warm))
        self.say(f"  ok  {label}\n"
                 f"      max rel err {err:.3g}; cold {walls[0]:.3f} s "
                 f"(stage {ph.get('stage', 0) / 1e3:.3f} s, dispatch incl. "
                 f"compile {ph.get('dispatch', 0) / 1e3:.3f} s); warm median "
                 f"{warm * 1e3:.1f} ms; path={rec['path']} "
                 f"grid={rec['grid_class']} key={rec.get('executable_key')}")
        return rec

    # -- phases ---------------------------------------------------------------

    def start_server(self) -> None:
        from filodb_tpu import native
        from filodb_tpu.ops import compile_cache
        from filodb_tpu.server import FiloServer

        jax = self.jax
        self.cache_dir = compile_cache.cache_dir()
        self.cache_entries_before = len(os.listdir(self.cache_dir)) \
            if os.path.isdir(self.cache_dir) else 0
        # shipped defaults; only the port is ours (ephemeral, loopback)
        cfg = {"http_port": 0}
        if self.args.cpu_rehearsal:
            # the rehearsal waits on the background pre-warm once per
            # query; its shipped 5 s tick would be most of the run
            cfg["query"] = {"prewarm": {"interval_s": 0.25}}
            self.say("config CUT for rehearsal: query.prewarm.interval_s "
                     "5.0 -> 0.25")
        self.srv = FiloServer(cfg)
        port = self.srv.start()
        self.client = Client(port)
        cfg = self.srv.config
        self.say(f"server: FiloServer on :{port}, shards={cfg['shards']} "
                 f"spread={cfg['spread']} store_root={cfg['store_root']} "
                 f"query.timeout_s={cfg['query']['timeout_s']}")
        health = self.client.json("/admin/health")
        d0 = jax.devices()[0]
        check((health["platform"], health["device_kind"], health["device_count"])
              == (d0.platform, d0.device_kind, len(jax.devices())),
              f"/admin/health device {health} != jax's")
        self.say(f"server says: platform={health['platform']} "
                 f"device_kind={health['device_kind']} "
                 f"device_count={health['device_count']}")
        check(jax.config.jax_compilation_cache_dir == self.cache_dir,
              f"persistent compile cache not enabled at {self.cache_dir}")
        self.say(f"compile cache: {self.cache_dir} "
                 f"(JAX_COMPILATION_CACHE_DIR "
                 f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'}"
                 f"), {self.cache_entries_before} entries on disk at start")
        for stem, tier in native.tiers().items():
            self.say(f"native lib{stem}: {tier}")
            if not tier.startswith("native"):
                self.say(f"  NOTE: lib{stem} runs on its Python tier")

    def load(self) -> None:
        # the newest bulk-loaded scrape. Everything else comes from --seed;
        # this one input has to follow the wall clock (the server evicts by
        # wall-clock retention and serves a live edge): one interval behind
        # it, so the scrape appended over HTTP later is not in the future
        self.t_end = self.args.t_end_ms or (
            int(time.time() * 1000) // INTERVAL_MS * INTERVAL_MS - INTERVAL_MS)
        self.say(f"input: t_end_ms={self.t_end} ("
                 + ("--t-end-ms" if self.args.t_end_ms else
                    "from the wall clock; pass --t-end-ms to repeat it")
                 + f"), seed={self.args.seed}")
        self.t0 = self.t_end - (N_SAMPLES - 1) * INTERVAL_MS
        self.q_end = self.t_end
        self.grid_params, self.out_t = self.grid(N_STEPS, STEP_S)
        self.q_start = int(self.out_t[0])
        # the wide set ends ~10 min before t_end (its random gaps sum to
        # WIDE_SAMPLES x 10 s give or take a few minutes)
        self.t0_wide = self.t_end - WIDE_SAMPLES * INTERVAL_MS - 600_000
        ms, spread = self.srv.memstore, self.srv.spread
        t_load = time.perf_counter()
        series = samples = 0
        # the order fixes each set's draws from the one seeded generator
        for kind in ("main", "jitter", "holes", "irregular", "small", "hist",
                     "wide"):
            n = SIZES[kind][self.idx]
            t_s = time.perf_counter()
            if kind == "hist":
                self.hist = make_hist(n, self.rng, self.t0)
                got = load_hist(ms, *self.hist, spread)
                check(got == n * N_SAMPLES,
                      f"hist: ingested {got} of {n * N_SAMPLES}")
                what = (f"{n} native-histogram series x "
                        f"{self.hist[1].shape[2]} buckets")
            else:
                s = (make_scalar_set(kind, n, self.rng, self.t0_wide,
                                     WIDE_SAMPLES) if kind == "wide" else
                     make_scalar_set(kind, n, self.rng, self.t0))
                got = load_scalar(ms, s, spread)
                check(got == s.n_samples,
                      f"{kind}: ingested {got} of {s.n_samples}")
                self.sets[kind] = s
                what = f"{n} series"
            series += n
            samples += got
            self.say(f"loaded {METRICS[kind]}: {what}, {got} samples "
                     f"({time.perf_counter() - t_s:.1f} s)")
        self.say(f"loaded total: {series} series, {samples} samples through "
                 f"TimeSeriesMemStore.ingest_routed in "
                 f"{time.perf_counter() - t_load:.1f} s (set-up, host)")

    def queries(self) -> None:
        tpu = self.platform == "tpu"
        main, small = self.sets["main"], self.sets["small"]
        out_t, t0 = self.out_t, self.t0
        m = METRICS

        def one(want, what, rtol=RTOL):
            def chk(rows):
                check(len(rows) == 1, f"{what}: {len(rows)} result series")
                return max_rel_err(rows[0][1], want, what, rtol=rtol)
            return chk

        self.say("queries (each: cold once, warm x%d, vs the numpy f64 oracle, "
                 "rtol %g):" % (WARM_RUNS, RTOL))
        rate_main = o_rate(main, out_t, t0)
        q = f"sum(rate({m['main']}[5m]))"
        self.mesh_queries = {q: nansum0(rate_main)[0]}  # query -> oracle
        self.run_query(q, one(self.mesh_queries[q], q), fused_variant="mxu")

        q = f"sum by (zone) (rate({m['main']}[5m]))"
        zones = np.array([t["zone"] for t in main.tags])

        def by_zone(rows):
            check(sorted(r[0].get("zone") for r in rows)
                  == sorted(set(zones)), f"{q}: groups {[r[0] for r in rows]}")
            return max(max_rel_err(row, nansum0(rate_main[zones == lb["zone"]])[0],
                                   f"{q} zone={lb['zone']}") for lb, row in rows)

        self.run_query(q, by_zone, fused_variant="mxu")

        q = f"max(max_over_time({m['main']}[5m]))"
        want = np.nanmax(o_max_over_time(main, out_t, t0), axis=0)
        self.run_query(q, one(want, q), fused_variant="general")

        q = f"avg(avg_over_time({m['main']}[5m]))"
        s_, n_ = nansum0(o_avg_over_time(main, out_t, t0))
        self.run_query(q, one(s_ / n_, q, rtol=WIDE_SUM_RTOL), fused_variant="mxu")

        q = f"sum(irate({m['main']}[5m]))"
        self.run_query(q, one(nansum0(o_irate(main, out_t, t0))[0], q),
                       fused_variant="mxu")

        q = f"rate({m['main']}{{instance=\"host-7\"}}[5m])"
        self.run_query(q, one(rate_main[7], q), fused_variant=None)

        q = f"topk(10, rate({m['small']}[5m]))"
        rate_small = o_rate(small, out_t, t0)

        def topk(rows):
            got = np.stack([r[1] for r in rows])
            err = 0.0
            for j in range(len(out_t)):
                g = np.sort(got[:, j][~np.isnan(got[:, j])])[::-1]
                w = np.sort(rate_small[:, j][~np.isnan(rate_small[:, j])])[::-1][:10]
                err = max(err, max_rel_err(g, w, f"{q} step {j}"))
            return err

        self.run_query(q, topk, fused_variant=None)

        off_ladder = "pallas" if tpu else "general"
        for kind, variant in (("jitter", "jitter"), ("holes", "masked"),
                              ("irregular", off_ladder)):
            q = f"sum(rate({m[kind]}[5m]))"
            want = nansum0(o_rate(self.sets[kind], out_t, t0))[0]
            self.run_query(q, one(want, q), fused_variant=variant)
        # off the ladder irate has a finisher of its own (the last pair's
        # interval taken in int32 inside the kernel)
        q = f"sum(irate({m['irregular']}[5m]))"
        want = nansum0(o_irate(self.sets["irregular"], out_t, t0))[0]
        self.run_query(q, one(want, q), fused_variant=off_ladder)

        ts, hist, _total, les, _tags_ = self.hist
        q = (f"histogram_quantile(0.99, sum by (le) "
             f"(rate({m['hist']}_bucket[5m])))")
        self.mesh_queries[q] = o_hist_quantile(0.99, ts, hist, les, out_t)
        rec = self.run_query(q, one(self.mesh_queries[q], q),
                             fused_variant="hist_shared")
        check("hist" in rec["executable_key"], f"{q}: key {rec['executable_key']}")

        # past pallas_kernels.MAX_T the irregular grid takes `general`, on
        # the chip as on the CPU: chosen from the block's shape
        q = f"sum(rate({m['wide']}[5m]))"
        wide_grid = self.grid(WIDE_STEPS, WIDE_STEP_S)
        want = nansum0(o_rate(self.sets["wide"], wide_grid[1], self.t0_wide))[0]
        rec = self.run_query(q, one(want, q), fused_variant="general",
                             grid=wide_grid)
        check(rec["grid_class"] == "irregular",
              f"{q}: grid_class {rec['grid_class']}, want irregular")

        # one instant query: leaf path + vector render
        t_q = time.perf_counter()
        data = self.client.json("/api/v1/query", time=self.q_end / 1000,
                                query=f"{m['main']}{{instance=\"host-7\"}}")
        wall = time.perf_counter() - t_q
        check(data["resultType"] == "vector" and len(data["result"]) == 1,
              f"instant query: {str(data)[:200]}")
        got = float(data["result"][0]["value"][1])
        want = main.vals[7, -1]
        check(abs(got - want) <= 1e-6 * abs(want),
              f"instant query: {got} != newest sample {want}")
        self.say(f"  ok  /api/v1/query {m['main']}{{instance=\"host-7\"}} = {got} "
                 f"(newest sample {want:.6f}); wall {wall * 1e3:.1f} ms")

    def append_and_read_back(self) -> None:
        """An acknowledged write is read back: one more scrape for every
        series of the small metric through POST /ingest/prom, then the
        SAME live-edge query (its range reaches past the newest sample, so
        the cached superblock has to extend or restage) must contain it."""
        c, small = self.client, self.sets["small"]
        m = METRICS["small"]
        end_ms = self.q_end + 2 * STEP_S * 1000
        start_ms = end_ms - 20 * STEP_S * 1000
        out_t = np.arange(start_ms, end_ms + 1, STEP_S * 1000, dtype=np.int64)
        q = f"sum(rate({m}[5m]))"

        def ask():
            self.wait_prewarm_idle()
            data = c.json("/api/v1/query_range", query=q, start=start_ms / 1000,
                          end=end_ms / 1000, step=STEP_S)
            rows = matrix(data, out_t)
            check(len(rows) == 1, f"{q}: {len(rows)} series")
            return rows[0][1]

        stale = ask()
        max_rel_err(stale, nansum0(o_rate(small, out_t, self.t0))[0],
                    f"{q} before the append")
        t_new = self.t_end + INTERVAL_MS
        # a jump no stale answer can hide: +1000 on every series
        new_vals = small.vals[np.arange(len(small.lens)), small.lens - 1] + 1000.0
        lines = [f"# TYPE {m} counter"]
        for tags, v in zip(small.tags, new_vals):
            lbl = ",".join(f'{k}="{val}"' for k, val in tags.items()
                           if k in ("_ws_", "_ns_", "instance", "zone"))
            lines.append(f"{m}{{{lbl}}} {float(v)!r} {t_new}")
        before = c.counters()
        ack = c.post("/ingest/prom", "\n".join(lines).encode())
        check(ack["status"] == "success"
              and ack["data"]["ingested"] == len(new_vals),
              f"/ingest/prom acknowledged {ack}")
        small.append_scrape(t_new, new_vals)
        fresh = ask()
        after = c.counters()
        err = max_rel_err(fresh, nansum0(o_rate(small, out_t, self.t0))[0],
                          f"{q} after the acknowledged append")
        check(bool(np.nanmax(np.abs(fresh - stale)) > 1.0),
              "the answer did not move after the append")
        events = {dict(ls).get("outcome", "?").strip('"'): v - before.get((n, ls), 0.0)
                  for (n, ls), v in after.items()
                  if n == "filodb_superblock_maintenance_total"
                  and v != before.get((n, ls), 0.0)}
        data = c.json("/api/v1/query", time=t_new / 1000,
                      query=f"{m}{{instance=\"host-7\"}}")
        got = float(data["result"][0]["value"][1])
        check(abs(got - new_vals[7]) <= 1e-6 * abs(new_vals[7]),
              f"read-back of the appended sample: {got} != {new_vals[7]}")
        self.say(f"  ok  POST /ingest/prom acknowledged {len(new_vals)} samples; "
                 f"the next live-edge {q} contains them (max rel err {err:.3g}; "
                 f"superblock maintenance {events or 'none (rebuilt)'}); "
                 f"instant read-back {got}")

    def bytes_in_use(self) -> list[int] | None:
        """Per device, from memory_stats(); None where the backend reports
        none — which only the CPU rehearsal may."""
        stats = [d.memory_stats() for d in self.jax.devices()]
        if any(st is None for st in stats):
            check(self.args.cpu_rehearsal, "this device reports no memory_stats()")
            return None
        return [st["bytes_in_use"] for st in stats]

    def mesh_phase(self) -> None:
        jax = self.jax
        devices = jax.devices()
        if len(devices) < 2:
            self.say("mesh phase: skipped (1 device)")
            return
        from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine
        from filodb_tpu.parallel.mesh import make_mesh

        def dispatches() -> float:  # same process, same registry as /metrics
            return total(self.client.counters(),
                         "filodb_kernel_dispatch_seconds_count")

        self.wait_prewarm_idle()
        self.say(f"mesh phase: {len(devices)} devices, same memstore, "
                 "QueryEngine(PlannerParams(mesh=make_mesh(jax.devices())))")
        ms = self.srv.memstore
        in_use0 = self.bytes_in_use()
        engine = QueryEngine(ms, "prometheus",
                             PlannerParams(mesh=make_mesh(devices)))
        out_t = self.out_t
        for q, oracle in self.mesh_queries.items():
            single = matrix(self.client.json(
                "/api/v1/query_range", query=q, **self.grid_params), out_t)[0][1]
            t_c = time.perf_counter()
            engine.query_range(q, self.q_start / 1000, self.q_end / 1000, STEP_S)
            cold = time.perf_counter() - t_c
            before = dispatches()
            t_w = time.perf_counter()
            res = engine.query_range(q, self.q_start / 1000, self.q_end / 1000,
                                     STEP_S)
            got = np.asarray(res.grids[0].values_np()[0][:len(out_t)], np.float64)
            warm = time.perf_counter() - t_w
            n_disp = dispatches() - before
            check(n_disp == 1, f"mesh {q}: {n_disp} warm dispatches, want 1")
            check(bool((np.isnan(got) == np.isnan(single)).all()),
                  f"mesh {q}: absent steps differ from the single-device answer")
            ok = ~np.isnan(single)
            err = float(np.max(np.abs(got[ok] - single[ok])
                               / np.maximum(np.abs(single[ok]), 1e-30)))
            check(err <= MESH_RTOL, f"mesh {q}: max rel err vs single device "
                                    f"{err:.3g} > {MESH_RTOL}")
            err_o = max_rel_err(got, oracle, f"mesh {q} vs the oracle",
                                rtol=MESH_ORACLE_RTOL)
            self.say(f"  ok  mesh {q}: 1 dispatch warm, max rel err "
                     f"{err:.3g} vs the single-device answer, {err_o:.3g} vs "
                     f"the oracle; cold {cold:.3f} s, warm {warm * 1e3:.1f} ms")
        # where the sharded superblocks REALLY sit: /debug/superblocks reads
        # each entry's value plane off the array's own shards
        names = {str(d) for d in devices}
        sharded = [e for e in self.client.json("/debug/superblocks")["entries"]
                   if e["sharding"]]
        check(len(sharded) >= 2, f"{len(sharded)} mesh superblocks cached")
        for e in sharded:
            bands = e["vals_resident"]
            check(set(bands) == names and all(bands.values()),
                  f"superblock {e['shape']} has bands on {len(bands)} of "
                  f"{len(devices)} devices: {bands}")
            self.say(f"  superblock vals {tuple(e['shape'])}: band bytes per "
                     f"device {[bands[str(d)] for d in devices]}")
        if in_use0 is None:
            self.say("  bytes_in_use per device: not reported by this backend")
        else:
            for d, a, b in zip(devices, in_use0, self.bytes_in_use()):
                rose = b > a
                self.say(f"  device {d.id}: bytes_in_use {a} -> {b} ({b - a:+d})"
                         + ("" if rose or d != devices[0] else
                            " — fell: this device also holds the single-device "
                            "path's caches, which evicted more than its band "
                            "adds; its band is proven by the band bytes above, "
                            "not by this figure"))
                check(rose or d == devices[0],
                      f"device {d.id} holds no band of the superblocks")
        self.say(f"  ok  every device holds a band: {len(sharded)} sharded "
                 f"superblocks, each on all {len(devices)} devices")

    def report(self) -> None:
        c, jax = self.client, self.jax
        counters = c.counters()
        res = c.json("/debug/resources")
        on_device = {k: v for k, v in res["device_bytes"].items()
                     if k != "compile_cache" and v}
        self.say(f"device bytes (ledger, /debug/resources): "
                 f"{sum(on_device.values())} {on_device}")
        for d in jax.devices():
            st = d.memory_stats()
            self.say(f"device bytes (device {d.id} memory_stats): " + (
                f"bytes_in_use={st['bytes_in_use']} "
                f"peak_bytes_in_use={st.get('peak_bytes_in_use')} "
                f"bytes_limit={st.get('bytes_limit')}"
                if st else "not reported by this backend"))
        kernels = c.json("/debug/kernels")
        by_variant: dict[str, int] = {}
        for e in kernels["executables"]:
            by_variant[e["variant"]] = by_variant.get(e["variant"], 0) + e["dispatches"]
        self.say(f"/debug/kernels dispatches by variant: {by_variant}; "
                 f"recompile-storm annotations: {sorted(kernels['storms'])}")
        want = {"mxu", "jitter", "masked", "hist_shared",
                "pallas" if self.platform == "tpu" else "general"}
        check(all(by_variant.get(v, 0) >= 1 for v in want),
              f"variants never dispatched: {want - set(by_variant)}")
        fallbacks = {
            "filodb_fused_fallback_total":
                total(counters, "filodb_fused_fallback_total"),
            'filodb_batch_dispatches_total{outcome="fallback"}':
                total(counters, "filodb_batch_dispatches_total", outcome="fallback"),
            'filodb_prewarm_total{outcome="error"}':
                total(counters, "filodb_prewarm_total", outcome="error"),
        }
        self.say(f"swallowed-failure counters: {fallbacks}; pre-warm ok="
                 f"{int(total(counters, 'filodb_prewarm_total', outcome='ok'))}")
        check(not any(fallbacks.values()), f"a hidden fallback fired: {fallbacks}")
        hits = int(total(counters, "filodb_compile_cache_hits_total", tier="persistent"))
        fresh = int(total(counters, "filodb_compile_cache_misses_total", tier="persistent"))
        entries = len(os.listdir(self.cache_dir))
        self.say(f"compile cache: {self.cache_dir}: {self.cache_entries_before} -> "
                 f"{entries} entries; kernel compiles served from disk "
                 f"(persistent hits) {hits}, compiled fresh {fresh}")
        check(entries > 0, "the persistent compile cache holds no entry")
        if self.args.expect_warm_cache:
            check(fresh == 0 and hits > 0,
                  f"--expect-warm-cache: {fresh} fresh compiles, {hits} hits")
        self.say("walls — host wall of one HTTP request (sent -> body read, so "
                 "it ends after the D2H and the render), for orientation, not "
                 "a benchmark:")
        for label, cold, stage, disp, miss, warm in self.walls:
            self.say(f"  {label}: cold {cold:.3f} s (stage {stage / 1e3:.3f} s, "
                     f"dispatch{' incl. compile' if miss else ''} "
                     f"{disp / 1e3:.3f} s), warm median {warm * 1e3:.1f} ms")

    def run(self) -> None:
        self.start_server()
        try:
            self.load()
            self.queries()
            self.append_and_read_back()
            self.mesh_phase()
            self.report()
        finally:
            self.srv.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20260926)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU backend, to debug this script; "
                         "every line says platform: cpu")
    ap.add_argument("--t-end-ms", type=int, default=0,
                    help="timestamp of the newest bulk-loaded scrape (default: "
                         "the wall clock, floored to 10 s, minus 10 s; printed)")
    ap.add_argument("--expect-warm-cache", action="store_true",
                    help="also fail unless every kernel compile was served "
                         "from the persistent cache (a second run)")
    args = ap.parse_args(argv)

    want = "cpu" if args.cpu_rehearsal else "tpu"
    # pinned BEFORE jax is imported: with the platform named, a backend
    # that cannot initialize is an error, never a quiet drop to the CPU
    os.environ["JAX_PLATFORMS"] = want
    import jax
    import jaxlib

    devices = jax.devices()  # raises when the pinned platform has no device
    d0 = devices[0]
    if d0.platform != want:
        print(f"chip_smoke: jax runs on {d0.platform}, wanted {want}",
              file=sys.stderr)
        return 1

    prefix = "[platform: cpu REHEARSAL] " if args.cpu_rehearsal else ""

    def say(text: str) -> None:
        for line in text.split("\n"):
            print(prefix + line, flush=True)

    t_all = time.perf_counter()
    say(f"platform: {d0.platform}  device_kind: {d0.device_kind}  "
        f"devices: {len(devices)}  jax {jax.__version__}  "
        f"jaxlib {jaxlib.__version__}  python {sys.version.split()[0]}  "
        f"host cpus {os.cpu_count()}  seed {args.seed}")
    if args.cpu_rehearsal:
        say("sizes CUT to rehearsal: " + ", ".join(
            f"{k} {full} -> {tiny} series" for k, (full, tiny) in SIZES.items()))
    try:
        Smoke(args, jax, say).run()
    except SmokeFailure as e:
        say(f"FAILED: {e}")
        return 1
    say(f"all phases passed in {time.perf_counter() - t_all:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind, "count": len(devices)}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
