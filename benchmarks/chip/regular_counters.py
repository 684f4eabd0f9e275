"""Generator ``regular_counters``: request counters on one shared scrape
grid. Copied from ``chip_smoke.py`` ``make_scalar_set("main")`` /
``load_scalar`` (PR 21), where it ran on the chip at this size; the draws
from ``rng`` are the same, in the same order, so a seed makes the same
values here and there.

``make(config, n_series, rng, t0_ms)`` returns a ``CounterSet``: the f64
history the references read (``ts`` [T] shared timestamps, ``vals`` [S, T]),
``load(memstore, spread)`` to put it into the server through
``TimeSeriesMemStore.ingest_routed``, and ``samples_in(lo_ms, hi_ms)`` for
the roofline's byte count. Only ``t0_ms`` follows the wall clock.
"""

from __future__ import annotations

import itertools

import numpy as np

ZONES = 8
LOAD_CHUNK = 10_000  # series per ingest_routed call


class CounterSet:
    """Cumulative counters: shared [T] int64 ms timestamps, [S, T] f64
    readings, a tag dict per series."""

    buckets = 1

    def __init__(self, name, ts, vals, tags):
        self.name, self.ts, self.vals, self.tags = name, ts, vals, tags

    @property
    def n_series(self) -> int:
        return self.vals.shape[0]

    @property
    def n_samples(self) -> int:
        return self.vals.shape[0] * self.vals.shape[1]

    def samples_in(self, lo_ms: int, hi_ms: int) -> int:
        return int(((self.ts > lo_ms) & (self.ts <= hi_ms)).sum()) * self.n_series

    def load(self, memstore, spread: int) -> int:
        from filodb_tpu.core.records import RecordBatch
        from filodb_tpu.core.schemas import PROM_COUNTER

        n, T = 0, len(self.ts)
        for b0 in range(0, self.n_series, LOAD_CHUNK):
            v = self.vals[b0:b0 + LOAD_CHUNK]
            tags = self.tags[b0:b0 + LOAD_CHUNK]
            n += memstore.ingest_routed("prometheus", RecordBatch(
                PROM_COUNTER, np.tile(self.ts, len(v)), {"count": v.ravel()},
                list(itertools.chain.from_iterable(
                    itertools.repeat(t, T) for t in tags)),
            ), spread)
        return n


def counter_values(rng, n: int, T: int) -> np.ndarray:
    """Uniform 0-10 increments on a 1e9 base, plus one reset in 1 % of the
    series (a restarted target), so the reset correction runs too."""
    vals = np.cumsum(rng.uniform(0, 10, size=(n, T)), axis=1) + 1e9
    for r in np.nonzero(rng.random(n) < 0.01)[0]:
        k = int(rng.integers(T // 8, T - T // 8))
        vals[r, k:] -= vals[r, k - 1]
    return vals


def make(config: dict, n: int, rng, t0: int) -> CounterSet:
    from filodb_tpu.core.schemas import METRIC_TAG

    T = int(config["samples_per_series"])
    ts = t0 + np.arange(T, dtype=np.int64) * int(config["interval_ms"])
    metric = config["metric"]
    tags = [{METRIC_TAG: metric, "_ws_": "demo", "_ns_": "App-2",
             "instance": f"host-{i}", "zone": f"z{i % ZONES}"} for i in range(n)]
    return CounterSet(metric, ts, counter_values(rng, n, T), tags)
