"""Downsampling (reference core/.../downsample/: ChunkDownsampler.scala:38
dMin/dMax/dSum/dCount/dAvg/tTime ADT, ShardDownsampler.scala:40 ingest-time
emission at flush, DownsampledTimeSeriesStore query-side column rewrite
``min_over_time(m) -> m::min``; batch job: spark-jobs DownsamplerMain).

TPU-native reframing: downsampling a chunk is a vectorized period-reduce
over its sample arrays (numpy host-side at flush; the data is already
columnar). Downsampled series land in a separate dataset (e.g. ``ds_5m``)
with a gauge-like multi-column schema {min,max,sum,count,avg}; the query
planner picks the column by function (column rewrite) when serving from a
downsample dataset.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..core.records import RecordBatch, SeriesBatch
from ..core.schemas import Column, ColumnType, Schema

# the downsample schema: one row per period with all reduced columns
DS_GAUGE = Schema(
    "ds-gauge",
    [
        Column("timestamp", ColumnType.TIMESTAMP),
        Column("min", ColumnType.DOUBLE),
        Column("max", ColumnType.DOUBLE),
        Column("sum", ColumnType.DOUBLE),
        Column("count", ColumnType.DOUBLE),
        Column("avg", ColumnType.DOUBLE),
    ],
    "avg",
)

# register in the global schema registry so persisted ds chunks recover
# (recover_shard resolves schemas by name)
from ..core.schemas import SCHEMAS as _SCHEMAS

_SCHEMAS.setdefault(DS_GAUGE.name, DS_GAUGE)

# query-side column rewrite (reference DownsampledTimeSeriesShard column
# selection, doc/downsampling.md:89-96)
FUNC_TO_DS_COLUMN = {
    "min_over_time": "min",
    "max_over_time": "max",
    "sum_over_time": "sum",
    "count_over_time": "count",
    "avg_over_time": "avg",
    "last": "avg",
    "last_over_time": "avg",
}


def downsample_samples(ts: np.ndarray, vals: np.ndarray, period_ms: int):
    """Reduce one series' samples into per-period rows.

    Periods are aligned to epoch multiples of period_ms; the emitted
    timestamp is the period end (reference tTime semantics). Vectorized via
    np.add.reduceat on period boundaries.
    """
    if len(ts) == 0:
        empty = np.empty(0)
        return np.empty(0, dtype=np.int64), {k: empty for k in ("min", "max", "sum", "count", "avg")}
    period = (ts // period_ms).astype(np.int64)
    # boundaries where the period changes
    idx = np.nonzero(np.diff(period, prepend=period[0] - 1))[0]
    keep = ~np.isnan(vals)
    # reduceat needs NaN-safe values
    v0 = np.where(keep, vals, 0.0)
    sums = np.add.reduceat(v0, idx)
    counts = np.add.reduceat(keep.astype(np.float64), idx)
    mins = np.minimum.reduceat(np.where(keep, vals, np.inf), idx)
    maxs = np.maximum.reduceat(np.where(keep, vals, -np.inf), idx)
    out_ts = (period[idx] + 1) * period_ms - 1
    has = counts > 0
    avg = np.where(has, sums / np.maximum(counts, 1), np.nan)
    return out_ts[has], {
        "min": mins[has],
        "max": maxs[has],
        "sum": sums[has],
        "count": counts[has],
        "avg": avg[has],
    }


@dataclass
class ShardDownsampler:
    """Ingest-time downsampler: at flush, reduce each sealed chunk and feed
    the downsample dataset (reference ShardDownsampler emits downsample
    records during doFlushSteps)."""

    target_memstore: object
    target_dataset: str
    periods_ms: tuple[int, ...] = (300_000, 3_600_000)  # 5m, 1h

    def dataset_for(self, period_ms: int) -> str:
        return f"{self.target_dataset}_{period_ms // 60000}m"

    def _shard(self, ds: str, shard_num: int):
        from ..core.schemas import Dataset

        try:
            return self.target_memstore.shard(ds, shard_num)
        except KeyError:
            self.target_memstore.setup(Dataset(ds, schemas=[DS_GAUGE]), [shard_num])
            return self.target_memstore.shard(ds, shard_num)

    def downsample_chunks(self, shard_num: int, part, chunks) -> int:
        if part.schema.has_histogram:
            return self._downsample_histogram(shard_num, part, chunks)
        n = 0
        col = part.schema.value_column
        c0 = part.schema.column(col)
        if c0.ctype != ColumnType.DOUBLE:
            return 0
        for period in self.periods_ms:
            ts_parts, val_parts = [], []
            for c in chunks:
                ts_parts.append(c.column("timestamp"))
                val_parts.append(c.column(col).astype(np.float64))
            ts = np.concatenate(ts_parts)
            vals = np.concatenate(val_parts)
            out_ts, cols = downsample_samples(ts, vals, period)
            if len(out_ts) == 0:
                continue
            ds = self.dataset_for(period)
            sb = SeriesBatch(DS_GAUGE, dict(part.tags), out_ts, cols)
            self._shard(ds, shard_num).ingest_series(sb)
            n += len(out_ts)
        return n


def last_per_period(ts: np.ndarray, period_ms: int):
    """Indices of the last sample in each aligned period + period-end ts
    (reference hLast/dLast downsamplers for cumulative schemas)."""
    if len(ts) == 0:
        return np.empty(0, np.int64), np.empty(0, np.int64)
    period = (ts // period_ms).astype(np.int64)
    starts = np.nonzero(np.diff(period, prepend=period[0] - 1))[0]
    last_idx = np.concatenate([starts[1:] - 1, [len(ts) - 1]])
    out_ts = (period[last_idx] + 1) * period_ms - 1
    return last_idx, out_ts


def _downsample_histogram(self, shard_num: int, part, chunks) -> int:
    """Cumulative histograms downsample by taking the LAST sample of each
    period for every column (hLast/dLast — cumulative values carry the
    whole period's information); emitted into the same prom-histogram
    schema so quantile queries work unchanged on downsample datasets."""
    ts_parts = [c.column("timestamp") for c in chunks]
    if not ts_parts:
        return 0
    ts = np.concatenate(ts_parts)
    col_names = [c.name for c in part.schema.columns if c.name != "timestamp"]
    cols = {
        name: np.concatenate([c.column(name) for c in chunks]) for name in col_names
    }
    n = 0
    for period in self.periods_ms:
        last_idx, out_ts = last_per_period(ts, period)
        if len(out_ts) == 0:
            continue
        values = {name: arr[last_idx] for name, arr in cols.items()}
        sb = SeriesBatch(part.schema, dict(part.tags), out_ts, values,
                         bucket_les=part.bucket_les)
        self._shard(self.dataset_for(period), shard_num).ingest_series(sb)
        n += len(out_ts)
    return n


ShardDownsampler._downsample_histogram = _downsample_histogram


def _value_columns(schemas: dict) -> dict[str, str]:
    """{schema_name: value_column} for DOUBLE-valued schemas — the only
    schema facts the scan+reduce phase needs, shipped to workers explicitly
    so runtime-registered schemas survive the spawn boundary."""
    return {
        name: s.value_column
        for name, s in schemas.items()
        if s.value_column and s.column(s.value_column).ctype == ColumnType.DOUBLE
    }


def _downsample_shard_records(store, dataset: str, shard_num: int, periods_ms,
                              value_cols: dict[str, str]):
    """Scan one shard's persisted chunks and reduce each into downsample
    records: [(period_ms, tags, out_ts, reduced_columns)]. Pure read+compute
    — safe to run in a worker process (the Spark-executor analog)."""
    from ..core.encodings import decode

    out = []
    for header, schema_name, encs in store.read_chunks(dataset, shard_num):
        vcol = value_cols.get(schema_name)
        if vcol is None:
            continue
        cols = dict(zip(header["cols"], encs))
        if vcol not in cols:
            continue
        ts = decode(cols["timestamp"])
        vals = decode(cols[vcol]).astype(np.float64)
        for period in periods_ms:
            out_ts, reduced = downsample_samples(ts, vals, period)
            if len(out_ts):
                out.append((period, dict(header["tags"]), out_ts, reduced))
    return out


def _downsample_shard_worker(store_root: str, dataset: str, shard_num: int,
                             periods_ms, value_cols: dict[str, str]):
    """Process-pool entry: opens its own store handle (file-backed, read
    path is process-safe) and returns the reduced records."""
    from ..store.columnstore import LocalColumnStore

    return shard_num, _downsample_shard_records(
        LocalColumnStore(store_root), dataset, shard_num, tuple(periods_ms), value_cols
    )


def batch_downsample(store, memstore, dataset: str, shard_nums, target_memstore,
                     downsampler: ShardDownsampler, processes: int = 0) -> int:
    """Batch job analog of spark-jobs DownsamplerMain: scan persisted chunks
    from the column store and (re)build downsample datasets.

    ``processes`` >= 1 distributes the scan+reduce phase over a spawn-based
    process pool, one task per shard (the reference distributes Cassandra
    token ranges over Spark executors); each shard's records ingest as its
    worker finishes. Requires a LocalColumnStore (workers reopen it by root
    path); other stores fall back in-process with a warning."""
    import logging

    from ..core.schemas import SCHEMAS

    shard_nums = list(shard_nums)
    value_cols = _value_columns(SCHEMAS)
    n = 0

    def ingest(shard_num, records):
        nonlocal n
        for period, tags, out_ts, reduced in records:
            ds = downsampler.dataset_for(period)
            sb = SeriesBatch(DS_GAUGE, tags, out_ts, reduced)
            downsampler._shard(ds, shard_num).ingest_series(sb)
            n += len(out_ts)

    use_pool = processes >= 1
    if use_pool and getattr(store, "root", None) is None:
        logging.getLogger(__name__).warning(
            "batch_downsample: store has no filesystem root; --processes "
            "requested but running in-process"
        )
        use_pool = False
    if use_pool:
        import multiprocessing
        from concurrent.futures import ProcessPoolExecutor, as_completed

        # spawn, not fork: a forked child would inherit the parent's
        # initialized JAX backend, and a chip belongs to ONE process. The
        # workers never need it: this module imports no jax (they decode
        # and reduce in numpy; tests/test_chip_smoke.py pins that).
        ctx = multiprocessing.get_context("spawn")
        with ProcessPoolExecutor(max_workers=min(max(processes, 1), len(shard_nums) or 1),
                                 mp_context=ctx) as pool:
            futs = [
                pool.submit(_downsample_shard_worker, store.root, dataset, s,
                            tuple(downsampler.periods_ms), value_cols)
                for s in shard_nums
            ]
            for f in as_completed(futs):
                ingest(*f.result())
    else:
        for s in shard_nums:
            ingest(s, _downsample_shard_records(
                store, dataset, s, downsampler.periods_ms, value_cols))
    return n
