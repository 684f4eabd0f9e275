"""One time series in memory (reference L2: memstore/TimeSeriesPartition.scala:64).

The reference appends rows into per-column off-heap write buffers, then
``switchBuffers`` (:232) seals them into immutable encoded BinaryVectors via
``optimize()``. Here a partition appends into growable numpy buffers and seals
fixed-max-size ``Chunk``s; sealed chunks optionally hold their codec-encoded
form (for flush/persistence and memory savings) and/or the decoded arrays (for
zero-cost query staging). Chunk metadata mirrors ChunkSetInfo (store/
ChunkSetInfo.scala:60): id = start time, numRows, endTime.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Mapping

import numpy as np

from ..core.encodings import Encoded, decode, encode_double, encode_hist, encode_int64
from ..core.schemas import Column, ColumnType, Schema

DEFAULT_MAX_CHUNK_SIZE = 400  # samples per chunk (reference store config default)
_NO_CLAMP = -(2**62)  # below every timestamp: a segment with no earlier one


class ColumnArrays(dict):
    """{column: array} of one chunk or one write buffer, with the addresses
    the native stage pass needs kept beside the arrays (native/stage.cpp
    reads them in place). ``segments[column] = (timestamp address, value
    address, rows, width, ints)`` for each [rows, width] column, when both
    arrays have the layout that pass reads: C-contiguous, int64 timestamps,
    int64 (``ints``) or float64 values. Any other histogram column has no
    entry, and a stage that meets one takes the Python tier. Made where the
    arrays are made: they are immutable (a sealed chunk) or append-only (a
    write buffer), so an address cannot go stale while this dict, which
    holds them, lives."""

    __slots__ = ("segments",)

    def __init__(self, arrays: Mapping[str, np.ndarray]):
        super().__init__(arrays)
        self.segments = {}
        ts = self.get("timestamp")
        if ts is None or ts.dtype != np.int64 or ts.ndim != 1 \
                or not ts.flags.c_contiguous:
            return
        for name, a in self.items():
            ints = a.dtype == np.int64
            if (a.ndim == 2 and len(a) == len(ts) and a.flags.c_contiguous
                    and (ints or a.dtype == np.float64)):
                self.segments[name] = (
                    ts.__array_interface__["data"][0],
                    a.__array_interface__["data"][0], len(a), a.shape[1], ints)


@dataclass
class Chunk:
    """Immutable sealed chunk: one time range of one series, all columns."""

    start_ts: int
    end_ts: int
    n: int
    # decoded columns (None if evicted to encoded-only form): set once, only
    # ever dropped, so a reader that took the dict holds the chunk's rows
    arrays: ColumnArrays | None
    # encoded columns (populated at seal when encode=True, or at flush)
    encoded: dict[str, Encoded] | None = None

    def __post_init__(self):
        if self.arrays is not None and not isinstance(self.arrays, ColumnArrays):
            self.arrays = ColumnArrays(self.arrays)

    def column(self, name: str) -> np.ndarray:
        if self.arrays is not None:
            return self.arrays[name]
        assert self.encoded is not None
        return decode(self.encoded[name])

    def ensure_encoded(self, schema: Schema) -> dict[str, Encoded]:
        if self.encoded is None:
            assert self.arrays is not None
            self.encoded = _encode_columns(schema, self.arrays)
        return self.encoded

    def drop_decoded(self, schema: Schema) -> None:
        """Keep only the compressed form (reference: post-optimize() state)."""
        self.ensure_encoded(schema)
        self.arrays = None

    @property
    def nbytes_encoded(self) -> int:
        return sum(e.nbytes for e in self.encoded.values()) if self.encoded else 0


def _encode_columns(schema: Schema, arrays: Mapping[str, np.ndarray]) -> dict[str, Encoded]:
    out = {}
    for col in schema.columns:
        if col.name not in arrays:
            continue
        a = arrays[col.name]
        if col.ctype == ColumnType.TIMESTAMP or col.ctype == ColumnType.LONG:
            out[col.name] = encode_int64(a)
        elif col.ctype == ColumnType.DOUBLE:
            out[col.name] = encode_double(a)
        elif col.ctype == ColumnType.HISTOGRAM:
            out[col.name] = encode_hist(a)
    return out


class TimeSeriesPartition:
    """Write buffers + sealed chunk list for one series."""

    __slots__ = (
        "part_id",
        "tags",
        "schema",
        "partkey",
        "chunks",
        "_buf",
        "_buf_len",
        "max_chunk_size",
        "encode_on_seal",
        "bucket_les",
        "flushed_until",
        "_hwm",
        "exemplars",
    )

    MAX_EXEMPLARS = 64  # ring-buffer cap per series (OpenMetrics exemplars)

    def __init__(
        self,
        part_id: int,
        tags: Mapping[str, str],
        schema: Schema,
        partkey: bytes,
        max_chunk_size: int = DEFAULT_MAX_CHUNK_SIZE,
        encode_on_seal: bool = False,
        bucket_les: np.ndarray | None = None,
    ):
        self.part_id = part_id
        self.tags = dict(tags)
        self.schema = schema
        self.partkey = partkey
        self.chunks: list[Chunk] = []
        self._buf: ColumnArrays | None = None
        self._buf_len = 0
        self.max_chunk_size = max_chunk_size
        self.encode_on_seal = encode_on_seal
        self.bucket_les = bucket_les
        self.flushed_until: int = -(2**62)  # flush watermark (ts)
        # ingest high-water mark: survives chunk eviction so the
        # out-of-order/duplicate guard stays intact after tier-2 reclaim
        self._hwm: int = -(2**62)
        # OpenMetrics exemplars: (ts_ms, value, labels) ring buffer
        self.exemplars: list[tuple[int, float, dict]] = []

    def add_exemplar(self, ts_ms: int, value: float, labels: dict) -> None:
        self.exemplars.append((int(ts_ms), float(value), dict(labels)))
        if len(self.exemplars) > self.MAX_EXEMPLARS:
            del self.exemplars[: len(self.exemplars) - self.MAX_EXEMPLARS]

    # -- ingest ------------------------------------------------------------

    def _alloc_buf(self, values: Mapping[str, np.ndarray]) -> None:
        cap = self.max_chunk_size
        buf: dict[str, np.ndarray] = {"timestamp": np.empty(cap, dtype=np.int64)}
        for name, arr in values.items():
            if arr.ndim == 2:
                buf[name] = np.empty((cap, arr.shape[1]), dtype=arr.dtype)
            else:
                buf[name] = np.empty(cap, dtype=arr.dtype)
        self._buf = ColumnArrays(buf)
        self._buf_len = 0

    def ingest(self, timestamps: np.ndarray, values: Mapping[str, np.ndarray]) -> int:
        """Append a time-ordered sample run; seals full chunks as it goes.
        Returns number of rows ingested (out-of-order rows are dropped, as the
        reference does — TimeSeriesPartition ingest drops rows older than the
        latest ingested timestamp)."""
        if len(timestamps) == 0:
            return 0
        last = self.latest_ts()
        if timestamps[0] <= last:
            keep = timestamps > last
            if not keep.any():
                return 0
            timestamps = timestamps[keep]
            values = {k: v[keep] for k, v in values.items()}
        n = len(timestamps)
        written = 0
        while written < n:
            if self._buf is None:
                self._alloc_buf(values)
            room = self.max_chunk_size - self._buf_len
            take = min(room, n - written)
            sl = slice(written, written + take)
            dst = slice(self._buf_len, self._buf_len + take)
            self._buf["timestamp"][dst] = timestamps[sl]
            for k, v in values.items():
                self._buf[k][dst] = v[sl]
            self._buf_len += take
            written += take
            if self._buf_len >= self.max_chunk_size:
                self.switch_buffers()
        self._hwm = max(self._hwm, int(timestamps[-1]))
        return n

    def latest_ts(self) -> int:
        # local snapshot: a concurrent seal nulls self._buf AFTER appending
        # the chunk, and readers don't hold the shard lock (the "check then
        # subscript" TOCTOU crashed queries racing ingest)
        buf, n = self._buf, self._buf_len
        if buf is not None and n:
            return max(int(buf["timestamp"][n - 1]), self._hwm)
        if self.chunks:
            return max(self.chunks[-1].end_ts, self._hwm)
        return self._hwm

    def earliest_ts(self) -> int:
        if self.chunks:
            return self.chunks[0].start_ts
        buf, n = self._buf, self._buf_len
        if buf is not None and n:
            return int(buf["timestamp"][0])
        return 2**62

    def switch_buffers(self) -> Chunk | None:
        """Seal the current write buffer into a chunk (reference
        switchBuffers:232 -> encodeAndReleaseBuffers:317)."""
        if self._buf is None or self._buf_len == 0:
            return None
        n = self._buf_len
        arrays = {k: v[:n].copy() for k, v in self._buf.items()}
        chunk = Chunk(
            start_ts=int(arrays["timestamp"][0]),
            end_ts=int(arrays["timestamp"][-1]),
            n=n,
            arrays=arrays,
        )
        if self.encode_on_seal:
            chunk.ensure_encoded(self.schema)
        self.chunks.append(chunk)
        self._buf = None
        self._buf_len = 0
        return chunk

    # -- read --------------------------------------------------------------

    def num_samples(self) -> int:
        return sum(c.n for c in self.chunks) + self._buf_len

    def chunks_in_range(self, t0: int, t1: int) -> list[Chunk]:
        return [c for c in self.chunks if c.end_ts >= t0 and c.start_ts <= t1]

    def samples_in_range(self, t0: int, t1: int, col: str) -> tuple[np.ndarray, np.ndarray]:
        """All samples with t0 <= ts <= t1 for one column, including the open
        write buffer. Returns (ts[int64], vals)."""
        ts_parts: list[np.ndarray] = []
        val_parts: list[np.ndarray] = []
        # snapshot order matters: queries read without the shard lock while
        # ingest can seal the buffer into a chunk mid-call (switch_buffers
        # appends the chunk, THEN nulls self._buf, THEN zeroes _buf_len).
        # Reading (len, buf, chunks) in that order — each exactly once; the
        # old re-read of self._buf crashed with a NoneType subscript —
        # covers every interleaving: a seal completing before the buf read
        # leaves buf=None and the chunk list (read after) holds the sealed
        # rows; a seal completing after it leaves the pre-seal buf ref
        # valid, and the sealed_end clamp below drops any buffer rows a
        # seen chunk already covers (per-series timestamps are monotone
        # across seal points), so sealed rows are neither lost nor counted
        # twice. A stale len against a freshly re-allocated buf fails the
        # ts[-1] >= t0 gate (trailing zeros) and skips the buffer — the
        # same slightly-stale-but-consistent view as querying a moment
        # earlier.
        n = self._buf_len
        buf = self._buf
        chunk_list = list(self.chunks)  # real copy: no mid-iteration appends
        sealed_end = chunk_list[-1].end_ts if chunk_list else -(2**62)
        for c in chunk_list:
            if c.end_ts < t0 or c.start_ts > t1:
                continue
            ts = c.column("timestamp")
            lo, hi = np.searchsorted(ts, [t0, t1 + 1])
            if hi > lo:
                ts_parts.append(ts[lo:hi])
                val_parts.append(c.column(col)[lo:hi])
        if buf is not None and n:
            ts = buf["timestamp"][:n]
            if ts[-1] >= t0 and ts[0] <= t1:
                lo, hi = np.searchsorted(ts, [max(t0, sealed_end + 1), t1 + 1])
                if hi > lo:
                    ts_parts.append(ts[lo:hi].copy())
                    val_parts.append(buf[col][lo:hi].copy())
        if not ts_parts:
            ncol = self._hist_width(col)
            empty_v = np.empty((0, ncol)) if ncol else np.empty(0)
            return np.empty(0, dtype=np.int64), empty_v
        return np.concatenate(ts_parts), np.concatenate(val_parts)

    def segments_in_range(self, t0: int, t1: int, col: str) -> list[tuple]:
        """``samples_in_range`` without touching an array: the snapshot it
        takes, in its order, as ``(arrays, buffer length, lower clamp)`` per
        sealed chunk that overlaps [t0, t1] and for the write buffer. The
        caller (ops/staging's native histogram pass) searches, slices and
        copies in one call over all series, and holds every ``arrays``
        until that call has returned. A sealed chunk is read whole (length
        None); the buffer comes with its snapshotted length and is gated
        on its first and last timestamp by the reader, as above. Rows
        before the clamp belong to an earlier segment (``sealed_end + 1``
        for the buffer: a seal that races the read counts no row twice and
        loses none). An encoded-only chunk is decoded here, as
        ``Chunk.column`` does it."""
        n = self._buf_len
        buf = self._buf
        chunk_list = list(self.chunks)  # real copy: no mid-iteration appends
        out = []
        for c in chunk_list:
            if c.end_ts < t0 or c.start_ts > t1:
                continue
            arrays = c.arrays
            if arrays is None:
                arrays = ColumnArrays(
                    {"timestamp": c.column("timestamp"), col: c.column(col)})
            out.append((arrays, None, _NO_CLAMP))
        if buf is not None and n:
            out.append((buf, n, chunk_list[-1].end_ts + 1 if chunk_list
                        else _NO_CLAMP))
        return out

    def tail_samples(self, t0: int, t1: int, col: str) -> tuple[np.ndarray, np.ndarray]:
        """Lean ``samples_in_range`` for the live-edge append window
        (ops/staging._append_to_parts calls this once per partition per
        repair, so per-call overhead is the whole cost at 100k series).
        When every requested sample lives in the open write buffer it
        returns VIEWS — no chunk scan, no copies, no concatenate. The
        views are only stable until the next ingest into this partition:
        callers must consume (stack/copy) them before releasing whatever
        ordering guarantees they hold; appends land at rows >= the
        snapshotted length so the returned slice itself is never
        rewritten. Falls back to samples_in_range whenever any chunk
        reaches into [t0, t1] or the seal race is in play."""
        n = self._buf_len
        buf = self._buf
        chunks = self.chunks
        sealed_end = chunks[-1].end_ts if chunks else -(2**62)
        if buf is None or not n or sealed_end >= t0:
            return self.samples_in_range(t0, t1, col)
        ts = buf["timestamp"][:n]
        if ts[-1] < t0 or ts[0] > t1:
            ncol = self._hist_width(col)
            empty_v = np.empty((0, ncol)) if ncol else np.empty(0)
            return np.empty(0, dtype=np.int64), empty_v
        lo, hi = np.searchsorted(ts, [t0, t1 + 1])
        return ts[lo:hi], buf[col][lo:hi]

    def _hist_width(self, col: str) -> int | None:
        try:
            c = self.schema.column(col)
        except KeyError:
            return None
        if c.ctype == ColumnType.HISTOGRAM and self.bucket_les is not None:
            return len(self.bucket_les)
        return None

    # -- flush / eviction ---------------------------------------------------

    def unflushed_chunks(self) -> list[Chunk]:
        return [c for c in self.chunks if c.start_ts > self.flushed_until]

    def mark_flushed(self, until_ts: int) -> None:
        self.flushed_until = max(self.flushed_until, until_ts)

    def resident_bytes(self) -> int:
        """Host-memory footprint of this series: open write buffer + decoded
        chunk arrays + encoded forms (reference: per-TSP write buffers +
        block-memory chunk bytes)."""
        n = 0
        buf = self._buf
        if buf is not None:
            n += sum(a.nbytes for a in buf.values())
        for c in self.chunks:
            if c.arrays is not None:
                n += sum(a.nbytes for a in c.arrays.values())
            n += c.nbytes_encoded
        return n

    def drop_decoded_flushed(self) -> int:
        """Tier-1 reclaim: keep only the encoded form of flushed chunks
        (reference: optimized BinaryVectors stay, decoded staging is
        rebuildable). Returns bytes freed."""
        freed = 0
        for c in self.chunks:
            if c.end_ts <= self.flushed_until and c.arrays is not None:
                decoded = sum(a.nbytes for a in c.arrays.values())
                had_enc = c.nbytes_encoded
                c.drop_decoded(self.schema)
                freed += decoded - (c.nbytes_encoded - had_enc)
        return freed

    def drop_flushed_chunks(self) -> int:
        """Tier-2 reclaim: remove flushed chunks from memory entirely — ODP
        pages them back from the column store on demand (reference
        evictPartitions + DemandPagedChunkStore). Returns bytes freed."""
        freed = 0
        keep = []
        for c in self.chunks:
            if c.end_ts <= self.flushed_until:
                if c.arrays is not None:
                    freed += sum(a.nbytes for a in c.arrays.values())
                freed += c.nbytes_encoded
            else:
                keep.append(c)
        self.chunks = keep
        return freed

    def evict_before(self, cutoff_ts: int) -> int:
        """Drop whole chunks ending before cutoff; returns samples dropped."""
        dropped = 0
        keep = []
        for c in self.chunks:
            if c.end_ts < cutoff_ts:
                dropped += c.n
            else:
                keep.append(c)
        self.chunks = keep
        return dropped
