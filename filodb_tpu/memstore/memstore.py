"""Memstore facade: per-dataset shard map (reference L2:
memstore/TimeSeriesMemStore.scala:26 — setup:85, ingest:148, startIngestion:154).

This is also the ChunkSource the query engine reads (reference
store/ChunkSource.scala:87,161): lookup + staging of series windows.
"""

from __future__ import annotations

from typing import Mapping, Sequence

import numpy as np

from .. import native
from ..core.filters import ColumnFilter
from ..core.records import RecordBatch
from ..core.schemas import Dataset
from ..metrics import REGISTRY, span
from .shard import StoreConfig, TimeSeriesShard


class TimeSeriesMemStore:
    def __init__(self, store_config: StoreConfig | None = None):
        self._datasets: dict[str, dict[int, TimeSeriesShard]] = {}
        self._dataset_meta: dict[str, Dataset] = {}
        self._total_shards: dict[str, int] = {}
        self.store_config = store_config or StoreConfig()
        # the cold stage's native pass reads this store's chunks in place
        # (ops/staging): built (g++, once a machine) and loaded with the
        # store, so that it is set-up and never a query
        native.stage_lib()

    # -- lifecycle -----------------------------------------------------------

    def setup(self, dataset: Dataset, shard_nums: Sequence[int],
              total_shards: int | None = None) -> None:
        """``total_shards`` is the CLUSTER's shard count (the routing
        modulus); REQUIRED whenever ``shard_nums`` is a partial slice
        (multi-host), else inferred from the owned set."""
        shards = self._datasets.setdefault(dataset.name, {})
        self._dataset_meta[dataset.name] = dataset
        nums = list(shard_nums)
        self._total_shards[dataset.name] = max(
            total_shards or 0, (max(nums) + 1) if nums else 0,
            self._total_shards.get(dataset.name, 0),
        )
        for s in nums:
            if s not in shards:
                shards[s] = TimeSeriesShard(dataset.name, s, self.store_config)

    def total_shards(self, dataset: str) -> int:
        return self._total_shards[dataset]

    def shard(self, dataset: str, shard_num: int) -> TimeSeriesShard:
        return self._datasets[dataset][shard_num]

    def shards(self, dataset: str) -> list[TimeSeriesShard]:
        return list(self._datasets.get(dataset, {}).values())

    def local_shard_count(self) -> int:
        """Shards of EVERY dataset held here — they all stage onto the same
        device, so its stage-cache share is divided among them."""
        return sum(len(shards) for shards in self._datasets.values())

    def shard_nums(self, dataset: str) -> list[int]:
        return sorted(self._datasets.get(dataset, {}).keys())

    def dataset(self, name: str) -> Dataset:
        return self._dataset_meta[name]

    # -- ingest --------------------------------------------------------------

    def ingest(self, dataset: str, shard_num: int, batch: RecordBatch, offset: int = -1) -> int:
        return self.shard(dataset, shard_num).ingest(batch, offset)

    def ingest_routed(self, dataset: str, batch: RecordBatch, spread: int) -> int:
        """Route a mixed batch to owned shards by shard-key hash (gateway path;
        the dataset's options pick the shard-key columns)."""
        shards = self._datasets[dataset]
        options = self._dataset_meta[dataset].options
        n = 0
        with span("ingest:routed", dataset=dataset) as sp:
            for snum, sub in batch.shard_split(
                spread, self.total_shards(dataset), options
            ).items():
                if snum in shards:
                    n += shards[snum].ingest(sub)
        REGISTRY.histogram("filodb_ingest_seconds", dataset=dataset).observe(
            sp.seconds)
        return n

    # -- query side ----------------------------------------------------------

    def lookup(
        self, dataset: str, filters: Sequence[ColumnFilter], start_ts: int, end_ts: int,
        shard_nums: Sequence[int] | None = None, limit: int | None = None,
    ) -> list[tuple[int, np.ndarray]]:
        """(shard_num, part_ids) per shard with matches."""
        out = []
        for s in shard_nums if shard_nums is not None else self.shard_nums(dataset):
            pids = self.shard(dataset, s).lookup_partitions(filters, start_ts, end_ts, limit)
            if len(pids):
                out.append((s, pids))
        return out

    def label_values(self, dataset, filters, label, start_ts, end_ts, limit=None) -> list[str]:
        vals: set[str] = set()
        for sh in self.shards(dataset):
            vals.update(sh.label_values(filters, label, start_ts, end_ts, limit))
        out = sorted(vals)
        return out[:limit] if limit else out

    def label_names(self, dataset, filters, start_ts, end_ts) -> list[str]:
        names: set[str] = set()
        for sh in self.shards(dataset):
            names.update(sh.label_names(filters, start_ts, end_ts))
        return sorted(names)

    def series(self, dataset, filters, start_ts, end_ts, limit=None) -> list[Mapping[str, str]]:
        out: list[Mapping[str, str]] = []
        for sh in self.shards(dataset):
            out.extend(sh.partkeys(filters, start_ts, end_ts, limit))
            if limit and len(out) >= limit:
                return out[:limit]
        return out

    def metric_metadata(self, dataset: str) -> dict[str, list[dict]]:
        """Prometheus /api/v1/metadata payload derived from the live schemas:
        one entry per metric with its type (counter/gauge/histogram) taken
        from the schema of a representative series (reference: the schemas
        registry drives PrometheusModel metadata)."""
        from ..core.filters import equals
        from ..core.schemas import METRIC_TAG

        out: dict[str, list[dict]] = {}
        for sh in self.shards(dataset):
            for metric in sh.label_values([], METRIC_TAG, 0, 2**62):
                if metric in out:
                    continue
                pids = sh.lookup_partitions([equals(METRIC_TAG, metric)], 0, 2**62, limit=1)
                if not len(pids):
                    continue
                schema = sh.partition(int(pids[0])).schema
                name = schema.name
                if "histogram" in name:
                    mtype = "histogram"
                elif "counter" in name:
                    mtype = "counter"
                elif name == "untyped":
                    mtype = "unknown"
                else:
                    mtype = "gauge"
                out[metric] = [{"type": mtype, "help": "", "unit": ""}]
        return dict(sorted(out.items()))

    # -- exemplars (OpenMetrics) ---------------------------------------------

    def add_exemplars(self, dataset: str, spread: int, items) -> int:
        """Attach exemplars to their series (items: (tags, ts_ms, value,
        exemplar_labels)). Series that don't exist yet are skipped — exemplars
        ride alongside samples, they never create series."""
        from ..core.schemas import canonical_partkey, shard_for

        shards = self._datasets[dataset]
        options = self._dataset_meta[dataset].options
        num_shards = self.total_shards(dataset)
        n = 0
        for tags, ts_ms, value, ex_labels in items:
            snum = shard_for(tags, spread, num_shards, options)
            sh = shards.get(snum)
            if sh is None:
                continue
            if sh.add_exemplar(canonical_partkey(tags), ts_ms, value, ex_labels):
                n += 1
        return n

    def query_exemplars(self, dataset, filters, start_ms: int, end_ms: int) -> list[dict]:
        """Prometheus /api/v1/query_exemplars shape: per matching series, the
        exemplars within [start, end]."""
        out = []
        for sh in self.shards(dataset):
            for pid in sh.lookup_partitions(filters, start_ms, end_ms):
                part = sh.partition(int(pid))
                exs = [
                    {
                        "labels": lbls,
                        "value": f"{val:g}",
                        "timestamp": ts / 1000.0,
                    }
                    for ts, val, lbls in part.exemplars
                    if start_ms <= ts <= end_ms
                ]
                if exs:
                    out.append({"seriesLabels": dict(part.tags), "exemplars": exs})
        return out
