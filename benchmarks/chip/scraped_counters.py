"""Generator ``scraped_counters``: ``regular_counters``' fleet with the
timestamps a Prometheus server writes when it scrapes it
(``prometheus/prometheus`` ``scrape/target.go`` ``Target.offset``,
``scrape/scrape.go`` ``scrapeTimestampTolerance``; cited from memory of the
public source, every number ours under ``assumed`` in
``configs/prom-scraped-counters.json``):

- **a phase per target**: an integer number of ms, uniform in
  ``[0, phase.span_ms)``, fixed for the series. Scrape ``k`` of series ``s``
  is due at ``t0 + phase[s] + k * interval``;
- **on the target's grid, or late**: ``1 - late.share`` of the scrapes start
  within the timestamp tolerance and are stamped with the time they were
  due; the others are late by ``late.tolerance_ms`` plus an exponential of
  mean ``late.mean_ms`` (an exponential that has passed the tolerance is
  one again), cut at ``late.cut_ms``, and keep that time. The cut is under
  the interval, so a series' timestamps strictly increase;
- **missed**: ``missed.share`` of all scrapes, independently, left no
  sample. A counter's next reading includes what it counted meanwhile: the
  readings are cumulative;
- **nothing from the future**: ``run.py`` takes ``t0`` for the oldest and
  ``t0 + (T - 1) * interval`` for the newest scrape time and ends its ranges
  there (the server evicts by wall-clock retention). A scrape due or
  stamped after that has not happened yet and is no sample: a series with a
  phase above 0 has one scrape fewer than ``T``.

The values are ``regular_counters.counter_values``' from the SAME draws in
the same order (phases, lateness and misses are drawn after them), so a
seed makes the same readings here and in ``filodb-dev-counters``: the two
configurations differ only in when the samples were taken.

``make(config, n_series, rng, t0_ms)`` returns a ``ScrapedSet``: every
series' real samples packed to the front of its row (``ts`` [S, T] int64 ms,
``vals`` [S, T] f64, ``lens`` [S]), ``load(memstore, spread)`` through
``TimeSeriesMemStore.ingest_routed`` and ``samples_in(lo_ms, hi_ms)``, which
counts REAL samples: the roofline reads the data, whatever body runs.
"""

from __future__ import annotations

import itertools

import numpy as np

from benchmarks.chip.regular_counters import LOAD_CHUNK, ZONES, counter_values

TS_PAD = np.iinfo(np.int64).max  # behind a row's length: in no window
# the program's name for the metric's tag, as a literal: only ``load``, the
# door into the program, imports any of it (tier-1 holds the two equal)
METRIC_TAG = "_metric_"


class ScrapedSet:
    """Cumulative counters, each series on its own clock. Built from the
    scrape slots ``ts`` / ``vals`` / ``keep`` [S, T] (``keep`` False: the
    scrape left no sample); holds the kept samples packed to the front of
    each row in order, ``lens`` [S] of them. Behind ``lens`` a row's
    timestamps are ``TS_PAD`` and its last reading repeats (no false
    reset); a row with no sample at all reads 0.0 there. ``phase_ms`` [S],
    where the generator drew one, is for a reader of the data: no reference
    reads it."""

    buckets = 1

    def __init__(self, name, ts, vals, keep, tags, phase_ms=None):
        self.name, self.tags, self.phase_ms = name, tags, phase_ms
        S, T = ts.shape
        self.lens = keep.sum(axis=1)
        self.ts = np.full((S, T), TS_PAD, dtype=np.int64)
        real = self.real()  # kept samples and real lanes: both row-major
        self.ts[real] = ts[keep]
        last = np.zeros(S)
        has = self.lens > 0
        last[has] = vals[has, T - 1 - np.argmax(keep[has, ::-1], axis=1)]
        self.vals = np.repeat(last[:, None], T, axis=1)
        self.vals[real] = vals[keep]

    def real(self, rows=slice(None)) -> np.ndarray:
        """[s, T] bool: the lanes of ``rows`` that hold a sample."""
        return np.arange(self.ts.shape[1])[None, :] < self.lens[rows, None]

    @property
    def n_series(self) -> int:
        return self.vals.shape[0]

    @property
    def n_samples(self) -> int:
        return int(self.lens.sum())

    def samples_in(self, lo_ms: int, hi_ms: int) -> int:
        return int(((self.ts > lo_ms) & (self.ts <= hi_ms)).sum())

    def load(self, memstore, spread: int) -> int:
        from filodb_tpu.core.records import RecordBatch
        from filodb_tpu.core.schemas import PROM_COUNTER

        n = 0
        for b0 in range(0, self.n_series, LOAD_CHUNK):
            rows = slice(b0, b0 + LOAD_CHUNK)
            real = self.real(rows)
            n += memstore.ingest_routed("prometheus", RecordBatch(
                PROM_COUNTER, self.ts[rows][real], {"count": self.vals[rows][real]},
                list(itertools.chain.from_iterable(
                    itertools.repeat(t, int(c))
                    for t, c in zip(self.tags[rows], self.lens[rows]))),
            ), spread)
        return n


def slots(config: dict, n: int, rng, t0: int):
    """The draws: ``(ts, vals, keep, phase_ms)``, [n, T] each but the phase,
    one column a scrape slot; ``keep`` False where the scrape left no sample."""
    T = int(config["samples_per_series"])
    interval = int(config["interval_ms"])
    late, missed = config["late"], config["missed"]
    if not int(late["cut_ms"]) < interval:
        raise ValueError("late.cut_ms must lie under interval_ms: a late "
                         "scrape may not pass the next one")
    vals = counter_values(rng, n, T)  # first: regular_counters' own draws
    phase = rng.integers(0, int(config["phase"]["span_ms"]), size=n)
    is_late = rng.random((n, T)) < float(late["share"])
    by = rng.exponential(float(late["mean_ms"]), size=(n, T))
    np.ceil(by, out=by)  # whole ms; in place: [n, T] f64 is 576 MB at full size
    by += int(late["tolerance_ms"])
    np.minimum(by, int(late["cut_ms"]), out=by)
    by *= is_late
    keep = rng.random((n, T)) >= float(missed["share"])
    ts = (t0 + phase)[:, None] + np.arange(T, dtype=np.int64)[None, :] * interval
    ts += by.astype(np.int64)
    keep &= ts <= t0 + (T - 1) * interval  # not from the future
    return ts, vals, keep, phase


def tags_of(metric: str, n: int) -> list[dict]:
    return [{METRIC_TAG: metric, "_ws_": "demo", "_ns_": "App-2",
             "instance": f"host-{i}", "zone": f"z{i % ZONES}"} for i in range(n)]


def make(config: dict, n: int, rng, t0: int) -> ScrapedSet:
    ts, vals, keep, phase = slots(config, n, rng, t0)
    metric = config["metric"]
    return ScrapedSet(metric, ts, vals, keep, tags_of(metric, n), phase)
