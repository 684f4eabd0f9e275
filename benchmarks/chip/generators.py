"""Data generators, one function per name a configuration's ``generator`` key
can give. Copied from ``chip_smoke.py`` (PR 21), where they ran on the chip.

A generator takes ``(config, n_series, rng, t0_ms)`` and returns a data set:
the f64 history the references read, ``load(memstore, spread)`` to put it
into the server through ``TimeSeriesMemStore.ingest_routed``, and
``samples_in(lo_ms, hi_ms)`` for the roofline's byte count. Everything is
drawn from ``rng`` (the run's ``--seed``); only ``t0_ms`` follows the wall
clock, because the server evicts by wall-clock retention.

A later PR adds a generator as a new module ``benchmarks/chip/<name>.py``
with a function ``make``; ``find`` looks there when the name is not here.
"""

from __future__ import annotations

import importlib
import itertools

import numpy as np


def find(name: str):
    fn = globals().get(name)
    if callable(fn) and not name.startswith("_"):
        return fn
    return importlib.import_module(f"benchmarks.chip.{name}").make


def _tags(metric: str, n: int) -> list[dict]:
    from filodb_tpu.core.schemas import METRIC_TAG

    return [{METRIC_TAG: metric, "_ws_": "demo", "_ns_": "App-2",
             "instance": f"host-{i}"} for i in range(n)]


def _repeat_tags(tags: list[dict], counts) -> list[dict]:
    return list(itertools.chain.from_iterable(
        itertools.repeat(t, int(c)) for t, c in zip(tags, counts)))


class HistSet:
    """Native cumulative histograms: shared [T] timestamps, [S, T, B] f64
    cumulative bucket counts, [S, T] sums, [B] upper bounds (last +Inf)."""

    def __init__(self, name, ts, hist, total, les, tags):
        self.name, self.ts, self.hist, self.total, self.les, self.tags = (
            name, ts, hist, total, les, tags)

    @property
    def n_series(self) -> int:
        return self.hist.shape[0]

    @property
    def buckets(self) -> int:
        return self.hist.shape[2]

    @property
    def n_samples(self) -> int:
        return self.hist.shape[0] * self.hist.shape[1]

    def samples_in(self, lo_ms: int, hi_ms: int) -> int:
        return int(((self.ts > lo_ms) & (self.ts <= hi_ms)).sum()) * self.n_series

    def load(self, memstore, spread: int) -> int:
        from filodb_tpu.core.records import RecordBatch
        from filodb_tpu.core.schemas import PROM_HISTOGRAM

        n = 0
        T, B = self.hist.shape[1], self.hist.shape[2]
        for b0 in range(0, len(self.tags), 2_000):
            h = self.hist[b0:b0 + 2_000]
            k = len(h)
            n += memstore.ingest_routed("prometheus", RecordBatch(
                PROM_HISTOGRAM, np.tile(self.ts, k),
                {"sum": self.total[b0:b0 + k].ravel(),
                 "count": h[..., -1].ravel(), "h": h.reshape(-1, B)},
                _repeat_tags(self.tags[b0:b0 + k], itertools.repeat(T)),
                bucket_les=self.les,
            ), spread)
        return n


def native_histograms(config: dict, n: int, rng, t0: int) -> HistSet:
    """Observations spread evenly over the first nine buckets with a thin
    tail, so the 0.99 quantile lands inside a finite bucket that holds
    ~11 % of the mass: the interpolation is exercised, and it amplifies f32
    summation-order noise ~9x (a bucket with 1 % of the mass would amplify
    it ~100x and turn the comparison into a test of the data)."""
    T = int(config["samples_per_series"])
    les = np.array(list(config["bucket_les"]) + [np.inf], dtype=np.float64)
    B = len(les)
    lam = np.array([2.0] * 9 + [0.04] * (B - 10) + [0.01])
    ts = t0 + np.arange(T, dtype=np.int64) * int(config["interval_ms"])
    hist = np.empty((n, T, B))
    for b0 in range(0, n, 1000):
        obs = rng.poisson(lam, size=(min(1000, n - b0), T, B))
        hist[b0:b0 + 1000] = np.cumsum(np.cumsum(obs, axis=2), axis=1)
    total = np.cumsum(rng.uniform(0, 5, size=(n, T)), axis=1)
    return HistSet(config["metric"], ts, hist, total, les,
                   _tags(config["metric"], n))
