"""Generator ``expo_delta_histograms``: ``expo_histograms``' fleet of services
instrumented with the OpenTelemetry SDK's default histogram aggregation, the
Base2 Exponential Bucket Histogram (MaxSize 160, MaxScale 20), exported with
DELTA aggregation temporality (the OTel metrics SDK specification, cited from
memory: a delta aggregator is reset at each collection and starts again at
MaxScale), stored under the schema ``otel-exp-delta-histogram``. Every number
is under ``assumed`` in ``configs/otel-expo-delta-hists.json``; the fleet and
its draws are ``otel-expo-hists``' (services, instances, latencies, timeout
tail, observations a point), each drawn from the run's ``--seed``:

- each point holds only its own interval's observations, on its own scheme:
  the largest scale <= ``max_scale`` at which the interval's index range fits
  ``max_buckets``, offset its lowest index, so the scale rises and falls
  from one point to the next; an interval with no observation exports a
  zero-count point (scale ``max_scale``, no bucket), so the grid stays shared;
- no warm-up: a delta point does not depend on what came before it.

``make(config, n_series, rng, t0_ms)`` returns an ``ExpoDeltaSet``: ``ts``
[T], ``hist`` [S, T, B] f32 each point's counts on its own scheme,
cumulative over its buckets (the zero bucket, n bounds, +Inf; zeros behind),
that scheme as ``row_scale`` / ``row_offset`` / ``row_width`` [S, T] (width =
n + 2), each series' stored scheme (the join of its points',
``expo_delta_panels.series_join``) as ``scale`` / ``offset`` / ``width`` [S],
``total`` [S, T] (the sum column), ``tags``; ``buckets`` is the MEAN stored
width (the roofline counts real buckets, never padding); ``load(memstore,
spread)`` hands each record its point's scheme through ``TimeSeriesMemStore.
ingest_routed``, in bulk (``core.histograms.Base2Rows``). A point's counts
stay below a few hundred and a window's sums far below 2^24: f32 holds them
exactly.
"""

from __future__ import annotations

import math

import numpy as np

from benchmarks.chip.expo_delta_panels import series_join
from benchmarks.chip.expo_histograms import (
    CHUNK, MAX_SCALE, METRIC_TAG, _log_uniform, scheme_of,
)


class ExpoDeltaSet:
    def __init__(self, name, ts, hist, total, rows, tags, max_buckets: int):
        self.name, self.ts, self.hist, self.total = name, ts, hist, total
        self.row_scale, self.row_offset, self.row_width = rows
        sc, off, n = series_join(self.row_scale, self.row_offset,
                                 self.row_width - 2, max_buckets)
        self.scale, self.offset, self.width = sc, off, n + 2
        self.tags = tags

    @property
    def n_series(self) -> int:
        return self.hist.shape[0]

    @property
    def n_samples(self) -> int:
        return self.hist.shape[0] * self.hist.shape[1]

    @property
    def buckets(self) -> float:
        """Mean stored columns a series (its join's n bounds, zero and +Inf)."""
        return float(self.width.mean())

    def samples_in(self, lo_ms: int, hi_ms: int) -> int:
        return int(((self.ts > lo_ms) & (self.ts <= hi_ms)).sum()) * self.n_series

    def load(self, memstore, spread: int) -> int:
        """500 series a batch, each record with its point's scheme."""
        from filodb_tpu.core.histograms import Base2Rows
        from filodb_tpu.core.records import RecordBatch
        from filodb_tpu.core.schemas import OTEL_EXP_DELTA_HISTOGRAM

        S, T, B = self.hist.shape
        n = 0
        for b0 in range(0, S, 500):
            r = np.arange(b0, min(S, b0 + 500))
            h = self.hist[r]
            sc, off, nb = (a[r].ravel().astype(np.int64) for a in
                           (self.row_scale, self.row_offset, self.row_width - 2))
            count = np.take_along_axis(h, (nb + 1).reshape(len(r), T, 1), axis=2)
            n += memstore.ingest_routed("prometheus", RecordBatch(
                OTEL_EXP_DELTA_HISTOGRAM, np.tile(self.ts, len(r)),
                {"sum": self.total[r].ravel(),
                 "count": count.astype(np.float64).ravel(),
                 "h": h.reshape(-1, B).astype(np.float64)},
                [self.tags[i] for i in r for _ in range(T)],
                bucket_schemes=Base2Rows(sc, off, nb),
            ), spread)
        return n


def make(config: dict, n: int, rng, t0: int) -> ExpoDeltaSet:
    # the door ``load`` goes through, checked before the drawing: a program
    # with no bulk form of a scheme a point cannot take this set at a
    # deployment's ingest cost (one batch a point: 214 s for this fleet on a
    # TPU v5e host), and fails here, in seconds
    from filodb_tpu.core.histograms import Base2Rows  # noqa: F401

    T = int(config["samples_per_series"])
    services = int(config["services"])
    if n % services:
        raise ValueError("series must be a whole number of instances a service")
    per = n // services
    svc = np.repeat(np.arange(services), per)
    median = _log_uniform(rng, *config["median_s"], services)
    rate = _log_uniform(rng, *config["per_scrape"], services)
    tail = np.zeros(services, bool)
    tail[rng.permutation(services)[:int(config["timeout"]["services"])]] = True
    sigma = rng.uniform(*config["sigma"], n)
    t_share = float(config["timeout"]["share"])
    t_lo, t_hi = (math.log2(x) for x in config["timeout"]["range_s"])
    max_b = int(config["max_buckets"])
    B = max_b + 2
    hist = np.zeros((n, T, B), np.float32)
    total = np.empty((n, T))
    rows_of = tuple(np.empty((n, T), np.int32) for _ in range(3))
    scale, offset, width = rows_of
    j = np.arange(max_b)[None, None, :]
    for b0 in range(0, n, CHUNK):
        rows = np.arange(b0, min(n, b0 + CHUNK))
        k = svc[rows]
        cnt = rng.poisson(rate[k][:, None], size=(len(rows), T))
        flat = cnt.ravel()
        point = np.repeat(np.arange(len(flat)), flat)  # row * T + t, sorted
        who = point // T
        # log2 of each observation: lognormal, or the timeout tail
        x = (np.log(median[k])[who] + sigma[rows][who]
             * rng.standard_normal(len(who))) / math.log(2)
        is_tail = tail[k][who] & (rng.random(len(who)) < t_share)
        x[is_tail] = np.log2(rng.uniform(2 ** t_lo, 2 ** t_hi, int(is_tail.sum())))
        idx20 = np.ceil(x * 2.0 ** MAX_SCALE).astype(np.int64) - 1
        # each point's own range: a delta aggregator sees its interval only
        full = np.nonzero(flat)[0]
        lo20 = np.full(len(flat), 0, np.int64)
        hi20 = np.full(len(flat), -1, np.int64)  # no value: an empty scheme
        start = np.concatenate([[0], np.cumsum(flat)[:-1]])[full]
        lo20[full] = np.minimum.reduceat(idx20, start)
        hi20[full] = np.maximum.reduceat(idx20, start)
        sc, off, nb = scheme_of(lo20, hi20, max_b)
        col = (idx20 >> (MAX_SCALE - sc[point])) - off[point]
        c = np.bincount(point * max_b + col, minlength=len(flat) * max_b)
        cum = np.cumsum(c.reshape(len(rows), T, max_b), axis=2)
        nb2 = nb.reshape(len(rows), T)
        h = np.zeros((len(rows), T, B), np.float32)
        h[:, :, 1:max_b + 1] = np.where(j < nb2[..., None], cum, 0)
        np.put_along_axis(h, (nb2 + 1)[..., None], cnt[..., None].astype(np.float32),
                          axis=2)
        hist[rows] = h
        scale[rows] = sc.reshape(len(rows), T)
        offset[rows] = off.reshape(len(rows), T)
        width[rows] = nb2 + 2
        total[rows] = np.bincount(point, weights=np.exp2(x),
                                  minlength=len(flat)).reshape(len(rows), T)
    ts = t0 + np.arange(T, dtype=np.int64) * int(config["interval_ms"])
    metric = config["metric"]
    tags = [{METRIC_TAG: metric, "_ws_": "demo", "_ns_": "App-2",
             "service": f"svc-{svc[i]}", "instance": f"host-{i}"} for i in range(n)]
    return ExpoDeltaSet(metric, ts, hist, total, rows_of, tags, max_b)
