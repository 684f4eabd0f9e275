"""Ingestion records (reference L1: binaryrecord2/RecordBuilder.scala:34,
RecordContainer.scala:28).

The reference streams BinaryRecords into off-heap containers that are also
the Kafka message format. Here the unit of ingest is a columnar
``RecordBatch``: numpy arrays per column plus per-record series tags — the
natural bulk form for both the host ingest loop and eventual TPU staging.
A ``SeriesBatch`` is the grouped form (one series, many samples) that the
memstore ingest hot path consumes.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Iterable, Mapping, Sequence

import numpy as np

from .histograms import Base2Rows
from .schemas import Schema, canonical_partkey, partkey_hash, shard_for


@dataclass
class RecordBatch:
    """Columnar batch of ingestion records sharing one schema.

    values maps column name -> array of shape [N] (DOUBLE/LONG) or [N, B]
    (HISTOGRAM). ``tags[i]`` is record i's full series tag map.
    """

    schema: Schema
    timestamps: np.ndarray
    values: dict[str, np.ndarray]
    tags: Sequence[Mapping[str, str]]
    bucket_les: np.ndarray | None = None  # histogram schemas only
    # a base-2 exponential scheme per RECORD, like ``tags``: each series
    # carries its own scale and offset, which may change from one record to
    # the next. A list of core.histograms.Base2Scheme (a series' run is cut
    # where it changes), or core.histograms.Base2Rows (a scheme a point, in
    # bulk: a series' rows stay one run, each with its own scheme). When
    # set, a series' ``bucket_les`` is its scheme's (``Base2Scheme.les``),
    # and histogram columns past a record's ``width`` are padding
    bucket_schemes: Sequence | Base2Rows | None = None

    def __len__(self) -> int:
        return len(self.timestamps)

    def group_by_series(self) -> "list[SeriesBatch]":
        """Group records by partition key, preserving time order within series.

        Hot path: producers typically repeat the same tags OBJECT for every
        sample of a series AND emit each series' samples contiguously, so
        grouping walks runs of identical objects (one identity check per
        row) instead of paying per-row dict ops, and partkeys memoize by
        object identity before falling back to canonical hashing. Batches
        with fresh dicts per row or interleaved series degrade gracefully
        to per-row runs.

        Contract: a single-run (contiguous) series returns slice VIEWS of
        the batch columns — callers must not mutate either side after
        grouping (every in-repo consumer copies on ingest)."""
        groups: dict[bytes, list] = {}
        keys: dict[bytes, Mapping[str, str]] = {}
        memo: dict[int, bytes] = {}
        tags = self.tags
        n = len(tags)
        # a run of records on one scheme repeats its object, as tags do
        ids = (None if self.bucket_schemes is None
               or isinstance(self.bucket_schemes, Base2Rows)
               else np.fromiter(map(id, self.bucket_schemes), np.int64, n))
        i = 0
        while i < n:
            t = tags[i]
            j = i + 1
            while j < n and tags[j] is t:
                j += 1
            pk = memo.get(id(t))
            if pk is None:
                pk = canonical_partkey(t)
                memo[id(t)] = pk
            runs = groups.get(pk)
            if runs is None:
                groups[pk] = [(i, j)]
                keys[pk] = t
            else:
                runs.append((i, j))
            i = j
        out = []
        for pk, runs in groups.items():
            if len(runs) == 1:
                lo, hi = runs[0]
                ix = slice(lo, hi)
            elif all(hi - lo == 1 for lo, hi in runs):
                # fresh-dict-per-row producers (CSV/TCP/JSONL gateways):
                # every row is its own run — index directly, no per-row
                # arange allocations
                ix = np.asarray([lo for lo, _ in runs])
            else:
                ix = np.concatenate(
                    [np.arange(lo, hi) for lo, hi in runs]
                )
            for sub, scheme, rows in self._scheme_runs(ix, runs, ids):
                out.append(
                    SeriesBatch(
                        schema=self.schema,
                        tags=dict(keys[pk]),
                        timestamps=self.timestamps[sub],
                        values={k: v[sub] for k, v in self.values.items()},
                        bucket_les=(self.bucket_les if scheme is None
                                    else scheme.les()),
                        bucket_scheme=scheme,
                        bucket_rows=rows,
                    )
                )
        return out

    def _scheme_runs(self, ix, runs, ids) -> list:
        """A series' rows ``ix`` (its ``runs``) by base-2 scheme:
        ``[(rows, scheme, None)]`` in row order, cut where the scheme
        changes (an SDK's range grows and its scale falls from one export
        to the next), ``ix`` itself when one scheme holds (explicit schemes:
        None); ``ids`` are the schemes' object ids: only where they differ
        are two compared. From ``Base2Rows``: ``[(ix, join, rows)]``, one
        run with each row's own scheme, or ``[(ix, scheme, None)]`` where
        every row has the same."""
        sch = self.bucket_schemes
        if sch is None:
            return [(ix, None, None)]
        if isinstance(sch, Base2Rows):
            rows = sch[ix]
            one = rows.single()
            return [(ix, rows.join(), rows) if one is None else (ix, one, None)]
        rows = (np.arange(ix.start, ix.stop) if isinstance(ix, slice)
                else np.asarray(ix))
        at = np.nonzero(ids[rows[1:]] != ids[rows[:-1]])[0] + 1
        cuts = [0] + [int(j) for j in at if sch[rows[j]] != sch[rows[j - 1]]]
        if len(cuts) == 1:
            return [(ix, sch[runs[0][0]], None)]
        cuts.append(len(rows))
        if isinstance(ix, slice):  # contiguous: views, as one run gets
            return [(slice(ix.start + a, ix.start + b), sch[rows[a]], None)
                    for a, b in zip(cuts, cuts[1:])]
        return [(rows[a:b], sch[rows[a]], None) for a, b in zip(cuts, cuts[1:])]

    def shard_split(self, spread: int, num_shards: int, options=None) -> dict[int, "RecordBatch"]:
        """Partition a batch by destination shard (gateway shardingPipeline
        analog, GatewayServer.scala:335). Shard memoized per tags object.
        ``options`` (DatasetOptions) selects the shard-key columns."""
        from .schemas import DatasetOptions

        options = options or DatasetOptions()
        memo: dict[int, int] = {}

        def shard_memo(t):
            s = memo.get(id(t))
            if s is None:
                s = shard_for(t, spread, num_shards, options)
                memo[id(t)] = s
            return s

        shard_of = np.array([shard_memo(t) for t in self.tags])
        out: dict[int, RecordBatch] = {}
        for s in np.unique(shard_of):
            ix = np.nonzero(shard_of == s)[0]
            out[int(s)] = RecordBatch(
                self.schema,
                self.timestamps[ix],
                {k: v[ix] for k, v in self.values.items()},
                [self.tags[i] for i in ix],
                self.bucket_les,
                None if self.bucket_schemes is None
                else self.bucket_schemes[ix]
                if isinstance(self.bucket_schemes, Base2Rows)
                else [self.bucket_schemes[i] for i in ix],
            )
        return out


@dataclass
class SeriesBatch:
    """Samples for a single series (one partition key), time-ordered."""

    schema: Schema
    tags: Mapping[str, str]
    timestamps: np.ndarray
    values: dict[str, np.ndarray]
    bucket_les: np.ndarray | None = None
    bucket_scheme: object | None = None  # core.histograms.Base2Scheme
    # each row's own scheme (core.histograms.Base2Rows) where they differ;
    # ``bucket_scheme`` is then their join
    bucket_rows: Base2Rows | None = None

    @property
    def partkey(self) -> bytes:
        return canonical_partkey(self.tags)

    @property
    def partkey_hash(self) -> int:
        return partkey_hash(self.tags)


def gauge_batch(
    metric: str,
    samples: Iterable[tuple[Mapping[str, str], int, float]],
    schema: Schema | None = None,
) -> RecordBatch:
    """Convenience builder: (tags, ts_ms, value) triples -> RecordBatch."""
    from .schemas import GAUGE, METRIC_TAG

    schema = schema or GAUGE
    tags_list, ts, vals = [], [], []
    for tags, t, v in samples:
        full = dict(tags)
        full.setdefault(METRIC_TAG, metric)
        tags_list.append(full)
        ts.append(t)
        vals.append(v)
    col = schema.value_column
    return RecordBatch(
        schema,
        np.asarray(ts, dtype=np.int64),
        {col: np.asarray(vals, dtype=np.float64)},
        tags_list,
    )
