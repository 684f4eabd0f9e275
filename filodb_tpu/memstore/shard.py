"""One shard of the in-memory store (reference L2: memstore/TimeSeriesShard.scala:268
— ingest loop :939, partition creation :1193, flush pipeline :1273-1636,
eviction :1709-1799, label queries :1908, lookup :2097).

A shard owns: partkey -> partition map, the tag index, flush-group assignment,
and retention/eviction. The reference's ingest hot loop is a per-record Scala
loop over BinaryRecords; here ingest consumes columnar ``RecordBatch``es and
amortizes partition lookup by grouping records per series with numpy.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Mapping, Sequence

import numpy as np

from ..core.filters import ColumnFilter
from ..core.histograms import fit_base2
from ..core.records import RecordBatch, SeriesBatch
from ..core.schemas import Schema, canonical_partkey
from .index import PartKeyIndex
from .partition import DEFAULT_MAX_CHUNK_SIZE, TimeSeriesPartition

NUM_FLUSH_GROUPS = 16  # reference groups-per-shard default


@dataclass
class ShardStats:
    """reference TimeSeriesShardStats (TimeSeriesShard.scala:41-150)."""

    rows_ingested: int = 0
    rows_skipped: int = 0
    partitions_created: int = 0
    partitions_evicted: int = 0
    chunks_flushed: int = 0
    encoded_bytes: int = 0
    headroom_evictions: int = 0
    bytes_reclaimed: int = 0


@dataclass
class StoreConfig:
    """Per-dataset store tuning (reference store/IngestionConfig.scala,
    conf/timeseries-dev-source.conf:43-120)."""

    max_chunk_size: int = DEFAULT_MAX_CHUNK_SIZE
    flush_interval_ms: int = 3_600_000
    retention_ms: int = 3 * 24 * 3_600_000
    encode_on_seal: bool = False
    groups_per_shard: int = NUM_FLUSH_GROUPS
    max_partitions: int = 1_000_000
    # "python" (vectorized posting-bitmap index, the default) | "native"
    # (the C++ posting-list core, reference's tantivy analog; falls back
    # when unbuilt) | "set" (the original set-arithmetic index, retained as
    # the property-test oracle / escape hatch)
    index_backend: str = "python"
    # opt-in HBM tier for hot posting bitmaps (memstore/index_device.py):
    # all-equality selectors whose matchers are staged resolve as one tiny
    # jit intersection program. Default OFF — with it off the index never
    # touches a device and the warm fused query stays ONE kernel dispatch.
    index_device_postings: bool = False
    index_device_min_hits: int = 16
    index_device_max_bytes: int = 64 << 20
    # staging-cache byte budget per shard (HBM/working-set guard; reference
    # analog: BlockManager reclaim under memory pressure)
    stage_cache_bytes: int = 2 << 30
    # resident chunk-memory budget per shard; crossing it triggers headroom
    # eviction (reference shard-mem-size + ensureHeadroom watermarks)
    max_resident_bytes: int = 8 << 30
    # eviction drives residency down to this fraction of the budget
    evict_target_fraction: float = 0.75


class EvictablePartIdQueueSet:
    """Dedup FIFO of headroom-eviction candidates (reference
    memstore/EvictablePartIdQueueSet.scala — offer dedups; eviction consumes
    from the head). Partitions enter when a flush task is cut for them (they
    will soon have flushed chunks to reclaim) or when ODP pages chunks back
    in; they leave when tier-2 eviction reclaims them or the partition is
    removed. ``evict_for_headroom`` walks ONLY this set — partitions that
    never flushed anything (nothing reclaimable) are never touched, and no
    per-call sort of the whole partition map happens. A re-offer moves the
    entry to the BACK, so the head is the least-recently-flushed (coldest)
    partition — hot series that flush every cycle keep migrating away from
    the eviction front."""

    __slots__ = ("_q",)

    def __init__(self):
        self._q: dict[int, None] = {}  # insertion-ordered dedup set

    def offer(self, part_id: int) -> None:
        self._q.pop(part_id, None)  # move-to-back on re-offer
        self._q[part_id] = None

    def remove(self, part_id: int) -> None:
        self._q.pop(part_id, None)

    def snapshot(self) -> list[int]:
        return list(self._q)

    def __len__(self) -> int:
        return len(self._q)

    def __contains__(self, part_id: int) -> bool:
        return part_id in self._q


@dataclass
class StageEntry:
    """One staging-cache entry: an HBM-resident staged block plus a dirty
    flag set by in-range ingests since it was built. Dirty entries get
    incrementally repaired — or restaged when repair preconditions fail —
    at next use (query/exec/plans.py); ``repairing`` marks an in-flight
    repair so concurrent same-key queries restage instead of serving the
    pre-repair block. ``dirty_lo``/``dirty_hi`` accumulate the union of
    the ACCEPTED-sample intervals (absolute ms, inclusive) of the ingests
    that dirtied the entry since it was last clean; the repair declines —
    forcing a restage — when ``dirty_lo`` reaches below the staged heads
    (ops/staging._append_to_parts), guarding the append-only repair's
    monotone-ingest assumption. Reset when a repair claims the entry."""

    block: object
    nbytes: int
    dirty: bool = False
    repairing: bool = False
    dirty_lo: int | None = None
    dirty_hi: int | None = None


# how many per-version ingest effect intervals a shard retains: the proof
# window for insert-time overlap re-checks and superblock revalidation. At a
# pathological 1000 version bumps/s this still covers ~1s of history — far
# longer than a stage runs; a reader older than the window is treated
# conservatively (as if everything changed).
EFFECT_LOG_MAX = 1024


def _stage_cache_walker(shard) -> int:
    """Cold recount of the shard staging cache's true device footprint —
    the ledger drift check's ground truth (must stay byte-identical to the
    accounting at insert: both use ops/staging.staged_nbytes)."""
    from ..ops.staging import staged_nbytes

    with shard._lock:
        return sum(staged_nbytes(e.block) for e in shard.stage_cache.values())


class TimeSeriesShard:
    def __init__(self, dataset: str, shard_num: int, config: StoreConfig | None = None):
        self.dataset = dataset
        self.shard_num = shard_num
        self.config = config or StoreConfig()
        self.index = self._make_index()
        self.partitions: dict[int, TimeSeriesPartition] = {}
        self._by_partkey: dict[bytes, int] = {}
        self._next_part_id = 0
        self.stats = ShardStats()
        from .cardinality import CardinalityTracker

        self.cardinality = CardinalityTracker()
        self._lock = threading.RLock()
        self._ingested_offset = -1  # stream offset watermark (Kafka analog)
        # per-version ingest effect log: (version, lo_ms, hi_ms, full). One
        # entry per version bump, so a consumer holding an older version can
        # PROVE a staged range untouched (ingest_effects_since) instead of
        # conservatively discarding its work — the interval-aware half of
        # the staging-cache invalidation contract.
        self._effects: deque = deque(maxlen=EFFECT_LOG_MAX)
        # append listeners (standing/maintainer.py): fired outside the
        # shard lock after each ingest commits — wake signals, not truth
        self._append_listeners: list[Callable] = []
        # entries are StageEntry objects (block + bytes + dirty/repairing)
        # data version for query-side staging caches: bumped on every ingest
        # so cached HBM-resident blocks invalidate (reference analog: block
        # memory reclaim + chunk seal versioning)
        self.version = 0
        self._scheme_moves = 0  # base-2 partitions widened (take_scheme)
        self.stage_cache: dict = {}
        # device-resource ledger account (filodb_tpu/ledger.py): every
        # stage-cache insert/evict/clear debits/credits it, and the drift
        # check recounts via the walker below (weakly bound — a dead shard
        # must not be pinned by process-global accounting)
        from ..ledger import LEDGER

        self.ledger = LEDGER.register(
            self, "staged_block", _stage_cache_walker,
            name=f"{dataset}/shard-{shard_num}",
        )
        # on-demand paging source: set to the ColumnStore to transparently
        # page evicted chunks back in at query time (reference
        # OnDemandPagingShard.scala:26 + DemandPagedChunkStore)
        self.odp_store = None
        self.odp_stats_pages = 0
        # headroom-eviction candidates (reference EvictablePartIdQueueSet)
        self.evictable = EvictablePartIdQueueSet()
        # index time-lifecycle state (reference TimeSeriesShard.scala:987-993
        # updateIndexWithEndTime): part ids currently marked "ended" in the
        # index, and the latest-sample watermark seen at the previous flush —
        # a partition whose watermark is unchanged across a flush cycle has
        # stopped ingesting and gets a real end time in the index.
        self._ended: set[int] = set()
        self._flush_watermark: dict[int, int] = {}
        # evicted-partkey set (reference evictedPartKeys BloomFilter,
        # TimeSeriesShard.scala:540): partkeys whose flushed chunk data was
        # reclaimed under memory pressure. The residency check in odp_page_in
        # (earliest_ts) already routes their queries to ODP; this set is the
        # retention pass's signal for which empty shells still have pageable
        # data, and surfaces as the evicted-series stat.
        self.evicted_keys: set[bytes] = set()
        self._ingests_since_headroom_check = 0
        # cheap residency accounting: last measured value + bytes ingested
        # since, so the O(partitions) walk runs only when the estimate nears
        # the budget (reference keeps an exact counter in block memory)
        self._resident_last = 0
        self._approx_new_bytes = 0

    def _make_index(self) -> PartKeyIndex:
        idx = None
        if self.config.index_backend == "native":
            try:
                from .index_native import NativePartKeyIndex, native_index_available

                if native_index_available():
                    idx = NativePartKeyIndex()
            except Exception:
                pass
        elif self.config.index_backend == "set":
            from .index import SetBasedPartKeyIndex

            return SetBasedPartKeyIndex()
        if idx is None:
            idx = PartKeyIndex()
        if self.config.index_device_postings:
            if type(idx) is not PartKeyIndex:
                # the native backend answers all-equality selectors in C++
                # and never reaches the bitmap tier hook — attaching a tier
                # there would be a silent no-op holding a ledger account
                import logging

                logging.getLogger("filodb_tpu.memstore").warning(
                    "index_device_postings ignored: backend %r resolves "
                    "equality selectors outside the bitmap path (use "
                    "index_backend=\"python\")", self.config.index_backend,
                )
            else:
                from .index_device import DevicePostingsTier

                idx.device_tier = DevicePostingsTier(
                    idx,
                    min_hits=self.config.index_device_min_hits,
                    max_bytes=self.config.index_device_max_bytes,
                    name=f"{self.dataset}/shard-{self.shard_num}/index",
                )
        return idx

    def index_stats(self) -> dict:
        """Introspection for /debug/index + the filodb_index_* gauges (the
        set-based escape-hatch backend reports a minimal shape)."""
        if hasattr(self.index, "postings_stats"):
            return self.index.postings_stats()
        return {"num_part_keys": len(self.index), "labels": {},
                "postings_bytes": 0, "dictionary_size": 0, "device": None}

    # -- ingest ------------------------------------------------------------

    def _record_effect(self, lo, hi, full: bool) -> None:
        """Append this version bump's effect to the bounded effect log.
        ``full`` marks events that can change ANY cached block (new series,
        eviction, ODP page-in, flush/recovery — resident data moved in
        place). Caller holds the shard lock and has already bumped
        ``version``; every bump must record exactly one effect so the log's
        versions stay consecutive (ingest_effects_since relies on it to
        detect truncation)."""
        self._effects.append((self.version, lo, hi, full))

    def ingest_effects_since(self, since_version: int, lo: int, hi: int):
        """Classify what happened between ``since_version`` and the current
        version w.r.t. the absolute-ms interval [lo, hi].

        Returns None when the effect log PROVES every bump since left the
        interval untouched (disjoint-range ingest only); else a reason
        string: ``"overlap"`` (some ingest's effect interval intersects),
        ``"full_clear"`` (new series / eviction / ODP / recovery — cached
        row sets or resident data may have changed), or ``"log_truncated"``
        (the bounded log no longer reaches back that far — conservatively
        treated as changed)."""
        with self._lock:
            return self._ingest_effects_since_locked(since_version, lo, hi)

    def ingest_effects_interval_since(self, since_version: int, lo: int,
                                      hi: int):
        """Like :meth:`ingest_effects_since`, but additionally returns the
        UNION interval of the overlapping effects:
        ``(reason, eff_lo, eff_hi)`` with ``eff_lo``/``eff_hi`` None unless
        reason is ``"overlap"``. The standing-query maintainer uses the
        interval to bound which retained grid steps the appended samples
        can have touched — a live-edge append dirties only the step
        SUFFIX whose windows reach ``eff_lo``, so a delta refresh
        recomputes O(touched steps) instead of the whole grid."""
        with self._lock:
            return self._ingest_effects_interval_locked(since_version, lo, hi)

    def _ingest_effects_interval_locked(self, since_version: int, lo, hi):
        """The ONE effect-log scan (classification + overlap interval)
        behind both public forms — the staging-cache path and the
        standing-delta path must never disagree on what counts as
        covered."""
        if self.version == since_version:
            return None, None, None
        if not self._effects or self._effects[0][0] > since_version + 1:
            return "log_truncated", None, None
        eff_lo = eff_hi = None
        for v, elo, ehi, full in self._effects:
            if v <= since_version:
                continue
            if full:
                return "full_clear", None, None
            if elo <= hi and ehi >= lo:
                eff_lo = elo if eff_lo is None else min(eff_lo, elo)
                eff_hi = ehi if eff_hi is None else max(eff_hi, ehi)
        if eff_lo is None:
            return None, None, None
        return "overlap", int(eff_lo), int(eff_hi)

    # -- append notification (standing/maintainer.py wake signal) ----------

    def add_append_listener(self, cb: Callable) -> None:
        """Register ``cb(dataset, shard_num, lo_ms, hi_ms, full)`` fired
        AFTER each ingest commits (outside the shard lock — listeners must
        never run under it; a listener that re-enters shard APIs would
        deadlock otherwise). The standing-query maintainer uses this as a
        WAKE signal only: correctness derives from the effect log
        (ingest_effects_interval_since), so a lost or duplicated
        notification is harmless."""
        self._append_listeners.append(cb)

    def remove_append_listener(self, cb: Callable) -> None:
        try:
            self._append_listeners.remove(cb)
        except ValueError:
            pass

    def _notify_append(self, lo, hi, full: bool) -> None:
        for cb in list(self._append_listeners):
            try:
                cb(self.dataset, self.shard_num, lo, hi, full)
            except Exception:  # noqa: BLE001 — a sick listener must not break ingest
                pass

    def _ingest_effects_since_locked(self, since_version: int, lo, hi):
        return self._ingest_effects_interval_locked(since_version, lo, hi)[0]

    def _clear_stage_cache(self, reason: str = "invalidate") -> None:
        """Wholesale staging-cache clear, crediting the device ledger for
        every dropped entry (callers hold the shard lock). The ONE clear
        path — a bare ``stage_cache.clear()`` would leak ledger balance."""
        if self.stage_cache:
            freed = sum(e.nbytes for e in self.stage_cache.values())
            self.ledger.free(freed, reason=reason, count=len(self.stage_cache))
            self.stage_cache.clear()

    def _invalidate_stage_range(self, min_ts, max_ts, new_series: bool,
                                raw_lo=None) -> None:
        """Dirty-mark (not drop) the staging-cache entries the new samples
        can affect.

        A dashboard's historical panels must not pay a full re-stage for
        every live scrape that lands BEYOND their range: an entry staged
        for [start, end] stays valid unless (a) the ingest's EFFECT
        interval overlaps it, or (b) a NEW series appeared (it might match
        the entry's filters — conservative full clear). The effect interval
        of an append to an existing series starts at the series' PREVIOUS
        newest sample, not at the new sample: extending a gap series' span
        can pull it into a cached range it previously missed entirely, and
        the cached block's row set would no longer match a fresh lookup.

        Overlapping entries are marked DIRTY and accumulate the effect
        interval (StageEntry.dirty_lo/hi) instead of being deleted: the
        next query attempts an INCREMENTAL append repair
        (ops/staging.append_to_block — live-edge panels pay only the tail,
        reference's equivalent is serving straight from write buffers) and
        falls back to a full re-stage when repair preconditions fail.
        Eviction/ODP paths still clear wholesale (they change resident data
        in place). Every call also records the effect in the shard's effect
        log so later consumers (insert-time overlap re-check, superblock
        revalidation) can prove disjointness. Caller holds the shard
        lock."""
        if new_series or min_ts is None:
            self._record_effect(0, 0, True)
            self._clear_stage_cache()
            return
        self._record_effect(int(min_ts), int(max_ts), False)
        # entries accumulate the ACCEPTED-sample interval (not the
        # prev_end-widened one the effect log records): the widening exists
        # for the index-span-pull hazard, which the repair's part-refs
        # check covers; a widened lo would make every append to a lagging
        # series read as below-head dirt and needlessly force restages
        dlo = int(min_ts) if raw_lo is None else int(raw_lo)
        for k, entry in self.stage_cache.items():
            if k[1] <= max_ts and k[2] >= min_ts:  # k = (filters, start, end, ...)
                entry.dirty = True
                entry.dirty_lo = (dlo if entry.dirty_lo is None
                                  else min(entry.dirty_lo, dlo))
                entry.dirty_hi = (int(max_ts) if entry.dirty_hi is None
                                  else max(entry.dirty_hi, int(max_ts)))

    def _prev_end_of(self, partkey) -> int | None:
        """Newest sample ts of an existing series (None for a new one)."""
        pid = self._by_partkey.get(partkey)
        if pid is None:
            return None
        try:
            return int(self.partitions[pid].latest_ts())
        except (KeyError, ValueError):
            return None

    def ingest(self, batch: RecordBatch, offset: int = -1) -> int:
        """Ingest a columnar record batch (reference ingest:939). Returns rows
        ingested. Records are grouped by series then appended in bulk."""
        n = 0
        with self._lock:
            np0, m0 = len(self.partitions), self._scheme_moves
            min_ts = max_ts = raw_min = None
            for sb in batch.group_by_series():
                prev_end = self._prev_end_of(sb.partkey)
                n += self._ingest_series(sb)
                if len(sb.timestamps):
                    raw, hi = int(sb.timestamps.min()), int(sb.timestamps.max())
                    lo = raw if prev_end is None else min(raw, prev_end)
                    # entry-dirt floor counts ACCEPTED rows only: rows at or
                    # below prev_end are dropped by the partition's
                    # out-of-order guard and change nothing, and counting
                    # them would make one stale duplicate per scrape
                    # permanently veto the append repair
                    acc = raw if prev_end is None else max(raw, prev_end + 1)
                    raw_min = acc if raw_min is None else min(raw_min, acc)
                    min_ts = lo if min_ts is None else min(min_ts, lo)
                    max_ts = hi if max_ts is None else max(max_ts, hi)
            if offset >= 0:
                self._ingested_offset = max(self._ingested_offset, offset)
            self.version += 1
            new_series = (len(self.partitions) != np0
                          or self._scheme_moves != m0)
            self._invalidate_stage_range(min_ts, max_ts, new_series,
                                         raw_lo=raw_min)
        if n and self._append_listeners:
            self._notify_append(min_ts, max_ts, new_series or min_ts is None)
        self.stats.rows_ingested += n
        # periodic headroom check on the ingest path (reference
        # ensureFreeSpace runs inside the ingest loop). The full O(partitions)
        # walk runs only when the estimate (last measurement + bytes since)
        # could plausibly be over budget.
        self._approx_new_bytes += n * 24  # ts8 + value8 + overhead slack
        self._ingests_since_headroom_check += 1
        if self._ingests_since_headroom_check >= 64:
            self._ingests_since_headroom_check = 0
            if self._resident_last + self._approx_new_bytes > self.config.max_resident_bytes:
                self.evict_for_headroom()
        return n

    def ingest_series(self, sb: SeriesBatch) -> int:
        lo = hi = None
        full = True
        with self._lock:
            self.version += 1
            np0, m0 = len(self.partitions), self._scheme_moves
            prev_end = self._prev_end_of(sb.partkey)
            n = self._ingest_series(sb)
            if len(sb.timestamps):
                raw = int(sb.timestamps.min())
                lo = raw if prev_end is None else min(raw, prev_end)
                hi = int(sb.timestamps.max())
                # accepted-rows floor, as in ingest(): dropped out-of-order
                # rows must not veto the append repair
                acc = raw if prev_end is None else max(raw, prev_end + 1)
                full = (len(self.partitions) != np0
                        or self._scheme_moves != m0)
                self._invalidate_stage_range(lo, hi, full, raw_lo=acc)
            else:
                self._record_effect(0, 0, True)
                self._clear_stage_cache()
        if n and self._append_listeners:
            self._notify_append(lo, hi, full)
        return n

    def _ingest_series(self, sb: SeriesBatch) -> int:
        pk = sb.partkey
        pid = self._by_partkey.get(pk)
        if pid is None:
            pid = self._create_partition(
                sb.tags, sb.schema, pk, sb.bucket_les,
                start_ts=int(sb.timestamps.min()) if len(sb.timestamps) else 0,
                bucket_scheme=(None if sb.bucket_scheme is None
                               else fit_base2(sb.bucket_scheme)),
            )
        elif pid in self._ended:
            # series resumed ingesting: back to the "still ingesting" sentinel
            # (reference re-activation in getOrAddPartitionAndIngest)
            self.index.update_end_time(pid, 2**62)
            self._ended.discard(pid)
        part = self.partitions[pid]
        values, moved, merged = part.take_scheme(sb.bucket_scheme, sb.values,
                                                 sb.bucket_rows)
        if values is None:
            # an explicit scheme against a base-2 one: its counts must not
            # be stored under the partition's bounds
            from ..metrics import REGISTRY

            REGISTRY.counter("filodb_ingest_scheme_refused").inc(
                len(sb.timestamps))
            self.stats.rows_skipped += len(sb.timestamps)
            return 0
        if moved:
            # a staged block holds the old scheme's columns: the ingest is
            # a full effect, as a new series is
            from ..metrics import REGISTRY

            REGISTRY.counter("filodb_ingest_scheme_moved").inc()
            self._scheme_moves += 1
        if merged:
            from ..metrics import REGISTRY

            REGISTRY.counter("filodb_ingest_scheme_merged").inc(merged)
        # enforce time order within the run
        ts = sb.timestamps
        if len(ts) > 1 and not (np.diff(ts) >= 0).all():
            order = np.argsort(ts, kind="stable")
            ts = ts[order]
            values = {k: v[order] for k, v in values.items()}
        got = part.ingest(ts, values)
        self.stats.rows_skipped += len(ts) - got
        return got

    def _create_partition(
        self, tags: Mapping[str, str], schema: Schema, pk: bytes, bucket_les=None,
        start_ts: int = 0, end_ts: int = 2**62, bucket_scheme=None,
    ) -> int:
        """reference createNewPartition:1193 + index addPartKey + cardinality.
        ``start_ts`` is the real first-sample time (reference passes the ingest
        record's timestamp to addPartKey); ``end_ts`` defaults to the
        still-ingesting sentinel."""
        if len(self.partitions) >= self.config.max_partitions:
            raise MemoryError(f"shard {self.shard_num}: partition limit reached")
        # quota enforcement happens BEFORE any state mutates (reference
        # CardinalityTracker.modifyCount at createNewPartition)
        self.cardinality.series_created(tags)
        pid = self._next_part_id
        self._next_part_id += 1
        part = TimeSeriesPartition(
            pid,
            tags,
            schema,
            pk,
            max_chunk_size=self.config.max_chunk_size,
            encode_on_seal=self.config.encode_on_seal,
            bucket_les=bucket_les,
            bucket_scheme=bucket_scheme,
        )
        self.partitions[pid] = part
        self._by_partkey[pk] = pid
        self.index.add_partkey(pid, dict(tags), start_ts=start_ts, end_ts=end_ts)
        if end_ts < 2**62:
            self._ended.add(pid)
        self.stats.partitions_created += 1
        return pid

    # -- query lookup --------------------------------------------------------

    def lookup_partitions(
        self, filters: Sequence[ColumnFilter], start_ts: int, end_ts: int, limit: int | None = None
    ) -> np.ndarray:
        """reference lookupPartitions:2097 -> PartLookupResult."""
        return self.index.part_ids_from_filters(filters, start_ts, end_ts, limit)

    def partition(self, part_id: int) -> TimeSeriesPartition:
        return self.partitions[int(part_id)]

    def label_values(self, filters, label, start_ts, end_ts, limit=None):
        return self.index.label_values(filters, label, start_ts, end_ts, limit)

    def label_names(self, filters, start_ts, end_ts):
        return self.index.label_names(filters, start_ts, end_ts)

    def partkeys(self, filters, start_ts, end_ts, limit=None):
        return self.index.partkeys_from_filters(filters, start_ts, end_ts, limit)

    # -- flush / eviction ----------------------------------------------------

    def flush_group_of(self, part_id: int) -> int:
        """Partitions are flushed in groups round-robin (reference
        prepareFlushGroup:1273; group = partId % groups)."""
        return part_id % self.config.groups_per_shard

    def create_flush_task(self, group: int):
        """Collect sealed-but-unflushed chunks for one flush group; the store
        layer persists them and then calls mark_flushed (doFlushSteps:1462)."""
        out = []
        with self._lock:
            for pid, part in self.partitions.items():
                if pid % self.config.groups_per_shard != group:
                    continue
                part.switch_buffers()
                chunks = part.unflushed_chunks()
                if chunks:
                    out.append((part, chunks))
                    self.evictable.offer(pid)  # reclaimable once persisted
        return out

    def update_index_end_times(self) -> int:
        """Mark partitions that stopped ingesting with a real end time in the
        index (reference updateIndexWithEndTime, TimeSeriesShard.scala:987-993
        + PartKeyLuceneIndex.updatePartKeyWithEndTime:628). Called once per
        flush cycle: a partition whose latest-sample watermark is unchanged
        since the previous flush is no longer ingesting. Returns the number of
        partitions newly marked ended."""
        n = 0
        with self._lock:
            for pid, part in self.partitions.items():
                if pid in self._ended:
                    continue
                latest = part.latest_ts()
                if latest <= -(2**61):
                    continue  # never ingested
                if self._flush_watermark.get(pid) == latest:
                    self.index.update_end_time(pid, latest)
                    self._ended.add(pid)
                    n += 1
                else:
                    self._flush_watermark[pid] = latest
        return n

    def evict_for_retention(self, now_ms: int | None = None) -> int:
        """Drop chunks older than retention; remove fully-empty partitions
        (reference evictPartitions:1709)."""
        now_ms = now_ms if now_ms is not None else int(time.time() * 1000)
        cutoff = now_ms - self.config.retention_ms
        dropped = 0
        dead: list[int] = []
        with self._lock:
            for pid, part in self.partitions.items():
                dropped += part.evict_before(cutoff)
                if part.num_samples() != 0:
                    continue
                # an empty partition is removed (with its index entry) only
                # when nothing within retention could be paged back: either
                # there is no ODP store, or its last sample predates the
                # cutoff. Tier-2-evicted/live series keep their shell so the
                # index can route queries to ODP.
                if self.odp_store is None or self.index.end_time(pid) < cutoff:
                    dead.append(pid)
            for pid in dead:
                part = self.partitions.pop(pid)
                self._by_partkey.pop(part.partkey, None)
                self.index.remove([pid])
                self.cardinality.series_removed(part.tags)
                self._ended.discard(pid)
                self._flush_watermark.pop(pid, None)
                self.evicted_keys.discard(part.partkey)
                self.evictable.remove(pid)
                self.stats.partitions_evicted += 1
            if dropped or dead:
                # resident data changed in place: cached staged blocks may
                # hold evicted samples/partitions (the staging cache has no
                # version in its key — invalidation is the contract)
                self.version += 1
                self._record_effect(0, 0, True)
                self._clear_stage_cache()
        return dropped

    def add_exemplar(self, partkey: bytes, ts_ms: int, value: float, labels) -> bool:
        """Attach an exemplar to an existing series (locked: partition lookup
        and append race eviction otherwise). Returns False when the series
        does not exist — exemplars never create series."""
        with self._lock:
            pid = self._by_partkey.get(partkey)
            if pid is None:
                return False
            self.partitions[pid].add_exemplar(ts_ms, value, labels)
            return True

    def resident_bytes(self) -> int:
        """Total host-memory footprint of this shard's series data."""
        with self._lock:
            return sum(p.resident_bytes() for p in self.partitions.values())

    def evict_for_headroom(self, target_bytes: int | None = None) -> int:
        """Reclaim chunk memory until residency is under the watermark
        (reference evictForHeadroom, TimeSeriesShard.scala:1799). Two tiers,
        least-recently-flushed candidates first:

        1. drop decoded arrays of flushed chunks (encoded form stays queryable);
        2. drop flushed chunks entirely — only when an ODP store is attached,
           so queries page them back (evicted partkeys recorded in
           ``evicted_keys``, the BloomFilter analog).

        Unflushed data is never dropped. Returns bytes freed."""
        budget = self.config.max_resident_bytes
        resident = self.resident_bytes()
        self._resident_last = resident
        self._approx_new_bytes = 0
        if target_bytes is None:
            if resident <= budget:
                return 0
            target = int(budget * self.config.evict_target_fraction)
        else:
            target = target_bytes
            if resident <= target:
                return 0
        freed = 0
        with self._lock:
            # walk ONLY the evictable candidate set (dedup FIFO ~
            # least-recently-flushed), never the whole partition map
            # (reference EvictablePartIdQueueSet consumption)
            cands = [self.partitions[pid] for pid in self.evictable.snapshot()
                     if pid in self.partitions]
            for part in cands:
                if resident - freed <= target:
                    break
                freed += part.drop_decoded_flushed()
            if resident - freed > target and self.odp_store is not None:
                for part in cands:
                    if resident - freed <= target:
                        break
                    got = part.drop_flushed_chunks()
                    if got:
                        freed += got
                        self.evicted_keys.add(part.partkey)
                        # fully reclaimed: re-enters the queue at next flush
                        self.evictable.remove(part.part_id)
            if freed:
                self._resident_last = resident - freed
                self.version += 1
                self._record_effect(0, 0, True)
                self._clear_stage_cache()
                self.stats.headroom_evictions += 1
                self.stats.bytes_reclaimed += freed
        return freed

    def odp_page_in(self, part_ids, start_ms: int, end_ms: int) -> int:
        """Page persisted chunks for the given partitions back into memory
        when the query range precedes what is resident (reference
        scanPartitions ODP override, OnDemandPagingShard.scala:147).
        Returns chunks paged in."""
        if self.odp_store is None:
            return 0
        from ..core.encodings import decode
        from ..core.schemas import canonical_partkey

        need: dict[bytes, TimeSeriesPartition] = {}
        for pid in part_ids:
            part = self.partitions.get(int(pid))
            if part is not None and part.earliest_ts() > start_ms:
                need[part.partkey] = part
        if not need:
            return 0
        n = 0
        with self._lock:
            # manifest-seek read: only frames of the NEEDED partitions in the
            # queried range are touched (reference OnDemandPagingShard:147 —
            # bytes read scale with the query, not the store)
            for header, schema_name, encs in self.odp_store.read_chunks_selective(
                self.dataset, self.shard_num, list(need.keys()), start_ms, end_ms
            ):
                pk = canonical_partkey(header["tags"])
                part = need.get(pk)
                if part is None:
                    continue
                if any(c.start_ts == header["start"] for c in part.chunks):
                    continue  # already resident
                from .partition import Chunk, header_scheme

                arrays = {
                    col: decode(enc) for col, enc in zip(header["cols"], encs)
                }
                part.chunks.append(
                    Chunk(header["start"], header["end"], header["n"], arrays,
                          dict(zip(header["cols"], encs)),
                          scheme=header_scheme(header))
                )
                part.mark_flushed(header["end"])
                n += 1
            for part in need.values():
                part.chunks.sort(key=lambda c: c.start_ts)
                if n:
                    # the merge-commit downsample layout stores overlapping
                    # batch + streaming chunks side by side and relies on
                    # read-side reconciliation (store/flush); a page-in
                    # must apply it like recover_shard does, or overlapped
                    # timestamps double-count
                    from ..store.flush import _reconcile_chunks

                    _reconcile_chunks(part)
                self.evictable.offer(part.part_id)  # paged-in = re-evictable
            if n:
                self.version += 1
                self._record_effect(0, 0, True)
                self._clear_stage_cache()
                self.odp_stats_pages += n
        return n

    @property
    def num_partitions(self) -> int:
        return len(self.partitions)

    @property
    def ingested_offset(self) -> int:
        return self._ingested_offset
