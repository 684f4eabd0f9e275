"""Staging: memstore chunk windows -> fixed-shape device blocks.

This is the TPU-native replacement for the reference's per-series iterator
read path (ChunkedWindowIterator, PeriodicSamplesMapper.scala:256): instead of
cursoring over encoded off-heap vectors per window, we gather ALL samples for
ALL selected series in [start - lookback, end] into one padded
``[series, time]`` block, push it to HBM once, and let jit kernels compute
every output step for every series at once.

Shape discipline (SURVEY.md §7 "ragged data vs static shapes" — the #1 risk):
- NaN samples (Prometheus staleness markers) are dropped host-side; validity
  on device is purely "index < length", so kernels never branch on NaN inputs.
- Timestamps become int32 ms offsets from ``base_ms`` (exact for ranges up to
  ~24 days; queries longer than that split at the planner like the
  reference's LongTimeRangePlanner).
- Cumulative counters are reset-corrected HOST-SIDE in f64 (the prefix-sum
  form of the reference's CorrectingDoubleVectorReader carry), then staged
  minus a per-series baseline: staged values are small monotone increments, so
  f32 keeps full precision even on 1e15-magnitude raw counters, and the device
  needs no correction pass at all. A corrected-value difference across a reset
  equals the post-reset raw reading — exactly Prometheus' reset semantics —
  so rate/irate need no reset branches on device. Raw-minus-baseline offsets
  ride along only for Prometheus' zero-crossing extrapolation cap.
- S and T pad up to bucketed sizes so the jit cache stays small.
"""

from __future__ import annotations

import functools
import threading
import time
import weakref
from collections import OrderedDict
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import native
from ..core.schemas import ColumnType
from ..metrics import REGISTRY, record_kernel_dispatch, span

# S pads to the next bucket; T pads to a multiple of 128 (TPU lane width)
_S_BUCKETS = (8, 32, 128, 512, 1024, 2048, 4096, 8192, 16384, 32768, 65536, 131072)


def pad_series(s: int) -> int:
    for b in _S_BUCKETS:
        if s <= b:
            return b
    return ((s + 8191) // 8192) * 8192


def pad_time(t: int) -> int:
    return max(128, ((t + 127) // 128) * 128)


TS_PAD = np.int32(2**31 - 1)  # padded slots sort after every real timestamp

# Widest selector span a staged block can represent exactly: ts offsets are
# int32 ms from base_ms (the selector start), so anything wider wraps
# negative and searchsorted over the no-longer-sorted vector silently
# empties late windows. Consumers that window over staged offsets
# (the fused superblock paths) must refuse wider selections up front;
# ~24.8 days — long-range reads beyond it are the rollup tier's job.
MAX_STAGE_SPAN_MS = 2**31 - 2


def series_put(mesh):
    """``jax.device_put`` closure for a block placement: single-device when
    ``mesh`` is None, else series-axis row sharding
    (``PartitionSpec(axis)`` — trailing dims replicate implicitly, so ONE
    spec covers [S], [S, T] and [S, T, B] arrays alike)."""
    if mesh is None:
        return jax.device_put
    from jax.sharding import NamedSharding, PartitionSpec

    sharding = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
    return lambda a: jax.device_put(a, sharding)


def _put_may_alias(placement) -> bool:
    """Whether ``device_put`` onto a block placement (a mesh, or None for
    the default device) may hand back the numpy memory it was given: the CPU
    backend's zero-copy. A TPU's memory is its own."""
    devices = (placement.devices.flat if placement is not None
               else jax.devices()[:1])
    return any(d.platform == "cpu" for d in devices)


def replicated_put(mesh):
    """``jax.device_put`` closure committing an array REPLICATED across the
    mesh (window matrices, group-id-free [J] vectors): placed once at build
    so warm dispatches pay no per-call broadcast transfer."""
    if mesh is None:
        return jax.device_put
    from jax.sharding import NamedSharding, PartitionSpec

    sharding = NamedSharding(mesh, PartitionSpec())
    return lambda a: jax.device_put(a, sharding)


def mesh_spec_str(mesh) -> str | None:
    """Human-readable sharding descriptor for introspection endpoints
    (/debug/superblocks) — the EXACT spec series_put applies: leading dim
    sharded, trailing dims implicitly replicated whatever the rank."""
    if mesh is None:
        return None
    axis = mesh.axis_names[0]
    return f"PartitionSpec('{axis}') x {mesh.devices.size} devices"


def mesh_device_bytes(mesh, nbytes: int) -> dict | None:
    """Even per-device byte attribution of a series-sharded block (the row
    arrays dominate and split evenly across the mesh)."""
    if mesh is None:
        return None
    devs = list(mesh.devices.flat)
    per = nbytes // len(devs)
    out = {str(d): per for d in devs}
    # remainder lands on the first device so the totals stay exact
    out[str(devs[0])] += nbytes - per * len(devs)
    return out

# masked (missing-scrape) grid detection: tolerate up to this fraction of
# holes before dropping to the general gather path
MAX_HOLE_FRAC = 0.05


# the [S, T'] arrays of a MaskedGrid: what its to_device pins in HBM
_MGRID_ARRAYS = ("valid", "vals", "dev", "raw", "ffv", "ffd", "bfv", "bfd",
                 "ff2v", "ff2d", "bfraw", "cc")


@dataclass
class MaskedGrid:
    """Slot-aligned sidecar for near-regular data with MISSED scrapes.

    The packed block arrays stay canonical (the general kernels and every
    other consumer read those); this sidecar maps each sample to its nominal
    slot and carries per-slot validity plus host-precomputed forward/backward
    fills, so the masked jitter kernel (ops/mxu_jitter.jitter_masked_kernel)
    can evaluate first/last/rate with shared-index fetches instead of
    per-series scans. All [S, T] f32; holes carry 0. Fill semantics:

    - ffv/ffd: value / time-offset of the LAST valid slot <= t
      (ffd = R[t'] - R[t] + dev[s, t'], small by construction)
    - bfv/bfd: value / time-offset of the FIRST valid slot >= t
    - ff2v/ff2d: value / time-offset of the SECOND-TO-LAST valid slot <= t
    - bfraw: backward fill of raw values (counter extrapolation cap only)

    Window-semantics contract: reference PeriodicSamplesMapper.scala:256 —
    the same windows the reference's iterators produce over data with gaps.
    """

    nominal_ts: np.ndarray  # [T] int32 ms offsets of the slot grid
    n_valid: int  # real slot count (grid width; <= T)
    interval_ms: float  # refined nominal interval (grid = t0 + k*interval)
    maxdev_ms: int
    valid: np.ndarray  # [S, T] f32 1.0 = real sample
    vals: np.ndarray  # [S, T] f32 transformed values, 0 at holes
    dev: np.ndarray  # [S, T] f32 ts deviation from nominal, 0 at holes
    raw: np.ndarray | None  # [S, T] f32 raw values (counters), 0 at holes
    ffv: np.ndarray
    ffd: np.ndarray
    bfv: np.ndarray
    bfd: np.ndarray
    ff2v: np.ndarray
    ff2d: np.ndarray
    bfraw: np.ndarray | None
    # cumulative valid count (prefix sum): per-series window counts become
    # two shared-index fetches instead of a [S,T]x[T,J] matmul
    cc: np.ndarray | None = None

    def to_device(self, put=None):
        """``put`` overrides the placement of every [S, T'] array (a
        series-sharded superblock passes its row-band sharding so the
        masked fused program spans the mesh without a gather)."""
        if put is None:
            put = jax.device_put
        for f in _MGRID_ARRAYS:
            a = getattr(self, f)
            if a is not None:
                setattr(self, f, put(a))
        return self


def _snap_slots(cleaned) -> tuple[float, float, list] | None:
    """Estimate a shared nominal grid for series with missed scrapes.

    Returns (interval_ms, t0_ms, [per-series slot indices]) or None when the
    data isn't near-regular-with-holes. Holes make per-series sample counts
    differ, so the equal-count detection above can't see these blocks."""
    if not cleaned or any(len(ts) < 2 for ts, _ in cleaned):
        return None
    ref = max((ts for ts, _ in cleaned), key=len)
    d = np.diff(ref)
    if not len(d) or (d <= 0).any():
        return None
    est = float(np.median(d))
    if est <= 0:
        return None
    k = np.rint(d / est)
    if (k < 1).any():
        return None
    # least-squares interval refinement over the reference series
    interval = float(d.sum()) / float(k.sum())
    if interval <= 0:
        return None
    t0 = float(ref[0])
    ks = []
    for ts, _ in cleaned:
        ki = np.rint((ts.astype(np.float64) - t0) / interval).astype(np.int64)
        if len(ki) > 1 and (np.diff(ki) < 1).any():
            return None  # two samples snapped to one slot: not this grid
        ks.append(ki)
    return interval, t0, ks


def masked_fills(valid, m_vals, m_dev, m_raw, R):
    """Host-precomputed forward/backward fills over slot-aligned masked
    arrays (the MaskedGrid fill semantics); R is the full-length int64
    nominal offset vector. Returns (ffv, ffd, bfv, bfd, ff2v, ff2d, bfraw).

    Slots with NO valid neighbor in the fill direction carry value 0 and a
    SIGNED time sentinel (-3e38 forward, +3e38 backward) instead of 0: the
    kernels never select such slots, and the sentinel keeps the fill-time
    invariant the masked kernel's lean gather mode relies on — at a VALID
    slot t, ffd[t] == bfd[t] == dev[t] (|.| <= maxdev), while at a hole
    ffd is <= -(interval - maxdev) and bfd >= interval - maxdev — so
    window-boundary membership and slot validity are decidable from the
    time fills alone, without fetching the validity plane."""
    T = valid.shape[1]
    V = valid > 0
    tind = np.arange(T)

    def gather(a, idx):
        return np.take_along_axis(a, np.clip(idx, 0, T - 1), axis=1)

    ffi = np.maximum.accumulate(np.where(V, tind[None, :], -1), axis=1)
    rev = np.maximum.accumulate(np.where(V[:, ::-1], tind[None, :], -1), axis=1)
    bfi = np.where(rev[:, ::-1] >= 0, T - 1 - rev[:, ::-1], T)
    ff2i = np.where(ffi >= 1, gather(ffi, ffi - 1), -1)
    Rf = R.astype(np.float64)

    def fill(vsrc, idx, t_sentinel):
        ok = (idx >= 0) & (idx < T)
        v = np.where(ok, gather(vsrc, idx), 0.0).astype(np.float32)
        dd = np.where(
            ok,
            (Rf[np.clip(idx, 0, T - 1)] - Rf[tind[None, :]])
            + gather(m_dev, idx),
            t_sentinel,
        ).astype(np.float32)
        return v, dd

    ffv, ffd = fill(m_vals, ffi, -3e38)
    bfv, bfd = fill(m_vals, bfi, 3e38)
    ff2v, ff2d = fill(m_vals, ff2i, -3e38)
    bfraw = fill(m_raw, bfi, 3e38)[0] if m_raw is not None else None
    return ffv, ffd, bfv, bfd, ff2v, ff2d, bfraw


def _build_masked_grid(cleaned, base_ms, out_vals, out_raw, lens,
                       T: int, S: int, grid=None) -> MaskedGrid | None:
    """Slot-align already-transformed packed values onto a shared nominal
    grid with validity holes; returns None when the bound or hole-fraction
    checks fail. ``grid`` forces a (interval_ms, t0_abs_ms) pair — the
    harmonize path uses it to put every shard on ONE common grid (slot 0 at
    t0; per-block widths may differ, validity masks absorb the difference).
    """
    if grid is None:
        snap = _snap_slots(cleaned)
        if snap is None:
            return None
        interval, t0, ks = snap
        kmin = min(int(k[0]) for k in ks)
    else:
        interval, t0 = grid
        ks = []
        for ts, _ in cleaned:
            ki = np.rint((ts.astype(np.float64) - t0) / interval).astype(np.int64)
            if (ki < 0).any() or (len(ki) > 1 and (np.diff(ki) < 1).any()):
                return None
            ks.append(ki)
        kmin = 0
    kmax = max(int(k[-1]) for k in ks)
    width = kmax - kmin + 1
    # holes stretch the slot span beyond the packed sample width, so the
    # sidecar sizes itself by SLOT count (may exceed the packed block's T —
    # the masked kernel touches only sidecar arrays)
    T = max(T, pad_time(width))
    total = sum(len(k) for k in ks)
    if grid is None and total < len(ks) * width * (1.0 - MAX_HOLE_FRAC):
        return None
    # nominal slot times as exact ints; deviations measured against them
    nom_abs = np.rint(t0 + (kmin + np.arange(T, dtype=np.float64)) * interval
                      ).astype(np.int64)
    md = 0
    valid = np.zeros((S, T), dtype=np.float32)
    m_vals = np.zeros((S, T), dtype=np.float32)
    m_dev = np.zeros((S, T), dtype=np.float32)
    m_raw = np.zeros((S, T), dtype=np.float32) if out_raw is not None else None
    for i, ((ts, _), ki) in enumerate(zip(cleaned, ks)):
        slots = (ki - kmin).astype(np.int64)
        dv = ts - nom_abs[slots]
        md = max(md, int(np.abs(dv).max()))
        valid[i, slots] = 1.0
        m_vals[i, slots] = out_vals[i, : lens[i]]
        m_dev[i, slots] = dv.astype(np.float32)
        if m_raw is not None:
            m_raw[i, slots] = out_raw[i, : lens[i]]
    if 2 * md >= interval:
        return None  # same safety bound as the aligned jitter path
    R = (nom_abs - base_ms).astype(np.int64)
    if R.max() > 2**31 - 2 or R.min() < -(2**31):
        return None
    ffv, ffd, bfv, bfd, ff2v, ff2d, bfraw = masked_fills(
        valid, m_vals, m_dev, m_raw, R
    )
    nominal = np.full(T, TS_PAD, dtype=np.int32)
    nominal[:width] = R[:width].astype(np.int32)
    return MaskedGrid(
        nominal_ts=nominal, n_valid=width, interval_ms=float(interval),
        maxdev_ms=md, valid=valid, vals=m_vals, dev=m_dev, raw=m_raw,
        ffv=ffv, ffd=ffd, bfv=bfv, bfd=bfd, ff2v=ff2v, ff2d=ff2d,
        bfraw=bfraw, cc=np.cumsum(valid, axis=1, dtype=np.float64
                                  ).astype(np.float32),
    )


def harmonize_masked(blocks) -> bool:
    """Rewrite per-shard masked (missing-scrape) grids onto ONE common
    nominal grid so the mesh kernel can share a single window structure
    (parallel/exec.py). Per-shard staging snapped each block to its own
    anchor; the common grid takes the earliest anchor and the mean interval,
    and every block's sidecar is rebuilt against it from the packed arrays.
    Per-block widths may differ — validity masks make shorter blocks exact.
    Returns False (blocks untouched) when grids can't be reconciled."""
    real = [b for b in blocks if b.n_series > 0]
    if not real:
        return False
    if len({b.base_ms for b in real}) != 1:
        return False
    base = real[0].base_ms
    ints, anchors = [], []
    for b in real:
        # grid evidence per block: a masked grid, OR a (possibly trivially)
        # regular/near-regular grid — e.g. a single-series shard stages as
        # "regular" even when it has holes, but still snaps onto the common
        # grid below
        if b.mgrid is not None:
            src = np.asarray(b.mgrid.nominal_ts)[: b.mgrid.n_valid]
        elif b.regular_ts is not None or b.nominal_ts is not None:
            m = int(np.asarray(b.lens)[0])
            grid = b.regular_ts if b.regular_ts is not None else b.nominal_ts
            src = np.asarray(grid)[:m]
        else:
            return False
        src = src.astype(np.int64)
        if len(src) < 2:
            return False
        d = np.diff(src)
        if (d <= 0).any():
            return False
        est = float(np.median(d))
        k = np.rint(d / est)
        if est <= 0 or (k < 1).any():
            return False
        ints.append(float(d.sum()) / float(k.sum()))
        anchors.append(int(src[0]))
    interval = float(np.mean(ints))
    if interval <= 0 or max(
        abs(x - interval) for x in ints
    ) > 0.01 * interval:
        return False
    t0_abs = float(min(anchors) + base)
    rebuilt = []
    for b in real:
        n = b.n_series
        ts_np = np.asarray(b.ts)
        lens = np.asarray(b.lens)
        cleaned = [
            (ts_np[i, : lens[i]].astype(np.int64) + base, None)
            for i in range(n)
        ]
        mg = _build_masked_grid(
            cleaned, base, np.asarray(b.vals),
            np.asarray(b.raw) if b.raw is not None else None,
            lens, b.ts.shape[1], b.vals.shape[0], grid=(interval, t0_abs),
        )
        if mg is None:
            return False
        rebuilt.append(mg)
    md = max(mg.maxdev_ms for mg in rebuilt)
    if 2 * md >= interval:
        return False
    width = max(mg.n_valid for mg in rebuilt)
    if any(width > mg.valid.shape[1] for mg in rebuilt):
        return False  # a block can't advertise slots its sidecar can't hold
    for b, mg in zip(real, rebuilt):
        # unify the advertised grid: same width everywhere (validity masks
        # cover slots a block has no samples for), same maxdev bound
        T = len(mg.nominal_ts)
        R = np.rint(
            (t0_abs - base) + np.arange(T, dtype=np.float64) * interval
        ).astype(np.int64)
        nominal = np.full(T, TS_PAD, dtype=np.int32)
        nominal[:width] = R[:width].astype(np.int32)
        mg.nominal_ts = nominal
        mg.n_valid = width
        mg.maxdev_ms = md
        b.mgrid = mg
        if hasattr(b, "_mwm_cache"):
            del b._mwm_cache
    return True


@dataclass
class StagedBlock:
    """One staged window block: everything a range kernel needs."""

    ts: np.ndarray  # [S, T] int32 ms offsets from base_ms; TS_PAD in padding
    vals: np.ndarray  # [S, T] f32; counters: reset-corrected minus baseline
    lens: np.ndarray  # [S] int32 valid sample count per series
    base_ms: int  # absolute ms of offset 0
    baseline: np.ndarray  # [S] f32 per-series value offset (counters; else 0)
    n_series: int  # real series count (<= S)
    part_refs: list  # (shard_num, part_id) per real series row
    raw: np.ndarray | None = None  # [S, T] f32 raw values (counters only)
    # device mesh this block's series axis is partitioned over
    # (NamedSharding, PartitionSpec(axis, None)); None = single-device.
    # Set by to_device(mesh=...); consumers (group_ids_memo, the sharded
    # fused kernels, append repairs) read it to co-place their arrays.
    placement: "object | None" = None
    # regular-grid fast path: every real series shares ONE timestamp vector
    # and one length — window matrices become series-independent and the
    # range kernel becomes a batched matmul on the MXU (see kernels.py)
    regular_ts: np.ndarray | None = None  # [T] int32 shared offsets, or None
    # near-regular (jittered) fast path: every series has the same sample
    # COUNT and each sample sits within half a scrape interval of a shared
    # nominal grid. Window membership then deviates from the nominal-grid
    # answer by at most one sample per window boundary, which mxu_jitter.py
    # resolves per-series with one-hot-matmul gathers — keeping real-world
    # jittered scrapes on the MXU path (reference semantics contract:
    # PeriodicSamplesMapper.scala:256 window iterators over arbitrary ts)
    nominal_ts: np.ndarray | None = None  # [T] int32 shared nominal offsets
    ts_dev: np.ndarray | None = None  # [S, T] f32 per-sample deviation (ms)
    maxdev_ms: int = 0  # bound on |ts - nominal|; < half min nominal interval
    # missing-scrape fast path: near-regular grid with HOLES (a dropped
    # scrape breaks the equal-count detection above). Slot-aligned masked
    # sidecar; packed arrays above stay canonical. See MaskedGrid.
    mgrid: "MaskedGrid | None" = None

    @property
    def shape(self):
        return self.ts.shape

    def to_device(self, keep_host: bool = False,
                  mesh=None) -> "StagedBlock":
        """Pin the block's arrays in HBM (the north-star 'decoded chunk
        windows staged to HBM'); returns self for chaining. ``keep_host``
        retains mutable host mirrors so cached blocks can be incrementally
        APPENDED to when live samples arrive (append_to_block) instead of
        fully restaged. A mirror costs nothing where ``device_put`` cannot
        alias host memory (:func:`_put_may_alias`: any backend but the
        CPU's): the numpy arrays the device arrays are about to replace ARE
        the mirrors, untouched (``aliased``). On the CPU backend they are
        explicit copies (``copied``), since the upload may be the same
        memory and a repair writes the mirrors while older device arrays
        are still read. ``self.mirrored`` says which, in bytes, for the
        caller to book (:func:`book_mirrors`). The first repair waits for
        the upload before it writes (:func:`_append_to_parts`).

        ``mesh`` partitions the SERIES axis across a device mesh
        (``NamedSharding``, ``PartitionSpec(axis)`` on the leading dim of
        every [S, ...] array) so one shard_map program spans all devices —
        the padded S must be mesh-divisible (concat_blocks
        ``series_multiple``). The mesh is recorded as ``self.placement``."""
        if mesh is not None:
            self.placement = mesh
        if keep_host:
            alias = not _put_may_alias(self.placement)
            self.mirrored = kept = {"aliased": 0, "copied": 0}

            def mirror(a):
                if a is None:
                    return None
                own = alias and isinstance(a, np.ndarray) and a.flags.writeable
                kept["aliased" if own else "copied"] += int(a.nbytes)
                return a if own else np.array(a, copy=True)

            self.h_ts = mirror(self.ts)
            self.h_vals = mirror(self.vals)
            self.h_lens = mirror(self.lens)
            self.h_raw = mirror(self.raw)
            self.h_dev = mirror(self.ts_dev)
            # no repair writes a baseline: the array itself is its mirror
            self.h_base = self.baseline
        put = series_put(self.placement)
        self.ts = put(self.ts)
        self.vals = put(self.vals)
        self.lens = put(self.lens)
        self.baseline = put(self.baseline)
        if self.raw is not None:
            self.raw = put(self.raw)
        if self.ts_dev is not None:
            self.ts_dev = put(self.ts_dev)
        if self.mgrid is not None:
            self.mgrid.to_device(put if self.placement is not None else None)
        return self


def detect_shared_grid(out_ts: np.ndarray, lens: np.ndarray, n: int,
                       T: int, S: int):
    """Shared-grid classification over packed [S, T] timestamp rows — the
    ONE rule used by per-shard staging (stage_series /
    stage_histogram_series) AND superblock concatenation (concat_blocks), so
    a cross-shard superblock keeps the same fast-path eligibility its member
    blocks had. Returns ``(regular, nominal, ts_dev, maxdev)``:

    - regular [T] when every real series shares one exact timestamp vector;
    - else nominal [T] + ts_dev [S, T] + maxdev when every series has the
      same sample count and each sample lies within half the minimum
      nominal interval of the per-slot midrange grid (the mxu_jitter bound:
      at most ONE uncertain slot per window boundary);
    - (None, None, None, 0) otherwise (caller may still try the masked
      missing-scrape grid)."""
    if n <= 0 or not (lens[:n] == lens[0]).all() or lens[0] == 0:
        return None, None, None, 0
    if not (out_ts[:n] != out_ts[0]).any():
        # a copy: ``out_ts`` may become a mirror that a repair writes in
        # place, and the grid of the block it was staged for must not move
        return out_ts[0].copy(), None, None, 0
    if lens[0] < 2:
        return None, None, None, 0
    m = int(lens[0])
    real = out_ts[:n, :m].astype(np.int64)
    nom, dev, md = nominal_midrange(real)
    min_int = int(np.diff(nom).min()) if m >= 2 else 0
    if min_int > 0 and 2 * md < min_int:
        nominal = np.full(T, TS_PAD, dtype=np.int32)
        nominal[:m] = nom.astype(np.int32)
        ts_dev = np.zeros((S, T), dtype=np.float32)
        ts_dev[:n, :m] = dev.astype(np.float32)
        return None, nominal, ts_dev, md
    return None, None, None, 0


def grid_class(block) -> str:
    """Classification of a staged (super)block's time grid — the fused
    kernel-variant ladder (ops/aggregations) and the /debug/superblocks
    introspection both key on it: ``regular`` (exact shared grid, MXU
    window matmuls) > ``jitter`` (near-regular, certain-matmul + boundary
    corrections) > ``holes`` (near-regular with missed scrapes, masked
    sidecar) > ``irregular`` (general / Pallas gather-scan)."""
    if block.regular_ts is not None:
        return "regular"
    if block.nominal_ts is not None:
        return "jitter"
    if getattr(block, "mgrid", None) is not None:
        return "holes"
    return "irregular"


def nominal_midrange(real: np.ndarray):
    """Shared nominal-grid estimator for near-regular data: per-column
    midrange (minimax-optimal for the max deviation) over [n, m] actual
    timestamps. Returns (nominal int64 [m], deviations int64 [n, m],
    maxdev int). The ONE definition used by staging detection and the
    live-edge append repair — the 2*maxdev < min-interval safety bound must
    be checked against the same estimator everywhere."""
    nom = (real.min(axis=0) + real.max(axis=0)) // 2
    dev = real - nom[None, :]
    return nom, dev, int(np.abs(dev).max())


def counter_correct(vals: np.ndarray) -> np.ndarray:
    """f64 prefix-sum reset correction: add the prior raw value at each drop
    (Prometheus semantics; reference CorrectingDoubleVectorReader:308)."""
    v = vals.astype(np.float64)
    if len(v) < 2:
        return v
    drops = np.where(v[1:] < v[:-1], v[:-1], 0.0)
    corr = np.concatenate([[0.0], np.cumsum(drops)])
    return v + corr


def stage_series(
    series: list[tuple[np.ndarray, np.ndarray]],
    base_ms: int,
    part_refs: list | None = None,
    subtract_baseline: bool = False,
    counter_corrected: bool = False,
    diff_encode: bool = False,
    dtype=np.float32,
    time_headroom: int = 0,
) -> StagedBlock:
    """Build a StagedBlock from per-series (ts_ms int64, values f64) pairs.

    Drops NaN samples (staleness). Pads S and T to bucketed shapes;
    ``time_headroom`` extra columns let live-edge append repairs
    (append_to_block) absorb many scrapes before the padded width forces a
    full re-stage.
    With ``counter_corrected``, values are reset-corrected in f64 first and
    raw offsets are staged alongside (see module docstring).
    With ``diff_encode``, slot i carries the f64-exact adjacent difference
    v[i]-v[i-1] (slot 0 = 0): changes/resets/idelta are pure functions of the
    diff sequence, and no single f32 shift of the *values* can preserve both
    tiny adjacent changes and a 1e9-magnitude counter-reset cliff.
    """
    n = len(series)
    cleaned: list[tuple[np.ndarray, np.ndarray]] = []
    maxlen = 1
    for ts, vals in series:
        keep = ~np.isnan(vals)
        if not keep.all():
            ts, vals = ts[keep], vals[keep]
        cleaned.append((ts, vals))
        maxlen = max(maxlen, len(ts))
    S = pad_series(max(n, 1))
    T = pad_time(maxlen + max(time_headroom, 0))
    out_ts = np.full((S, T), TS_PAD, dtype=np.int32)
    out_vals = np.zeros((S, T), dtype=dtype)
    out_raw = np.zeros((S, T), dtype=dtype) if counter_corrected else None
    lens = np.zeros(S, dtype=np.int32)
    baseline = np.zeros(S, dtype=dtype)
    # f64 continuation state per series (last raw value, last corrected
    # value) so cached counter blocks can be incrementally appended to with
    # EXACT correction continuation (append_to_block); base64 keeps the
    # UNROUNDED per-series baseline — the f32 baseline array rounds to
    # +-64 at 1e9 magnitudes, which would shift every appended value
    cont_raw = np.zeros(S, dtype=np.float64)
    cont_corr = np.zeros(S, dtype=np.float64)
    base64 = np.zeros(S, dtype=np.float64)
    for i, (ts, vals) in enumerate(cleaned):
        m = len(ts)
        lens[i] = m
        if m == 0:
            continue
        out_ts[i, :m] = (ts - base_ms).astype(np.int32)
        if counter_corrected:
            b = np.float64(vals[0])
            baseline[i] = b
            base64[i] = b
            corrected = counter_correct(vals)
            cont_raw[i] = vals[-1]
            cont_corr[i] = corrected[-1]
            out_vals[i, :m] = (corrected - b).astype(dtype)
            # raw rides along unshifted: it only feeds the zero-crossing
            # extrapolation cap, which engages only for raw values near zero —
            # exactly where plain f32 is exact (large raws disable the cap)
            out_raw[i, :m] = vals.astype(dtype)
        elif diff_encode:
            v64 = vals.astype(np.float64)
            out_vals[i, 1:m] = np.diff(v64).astype(dtype)
        elif subtract_baseline:
            b = np.float64(vals[0])
            baseline[i] = b
            base64[i] = b
            out_vals[i, :m] = (vals.astype(np.float64) - b).astype(dtype)
        else:
            out_vals[i, :m] = vals.astype(dtype)
    mgrid = None
    regular, nominal, ts_dev, maxdev = detect_shared_grid(
        out_ts, lens, n, T, S
    )
    if n > 1 and regular is None and nominal is None:
        # unequal counts (or equal counts on misaligned slots): try the
        # missing-scrape masked grid before resigning to the general path
        mgrid = _build_masked_grid(
            cleaned[:n], base_ms, out_vals, out_raw, lens, T, S
        )
    block = StagedBlock(
        out_ts, out_vals, lens, base_ms, baseline, n, part_refs or [],
        raw=out_raw, regular_ts=regular, nominal_ts=nominal, ts_dev=ts_dev,
        maxdev_ms=maxdev, mgrid=mgrid,
    )
    if counter_corrected or subtract_baseline:
        block.base64 = base64
    if counter_corrected:
        block.cont = (cont_raw, cont_corr)
    return block


def append_to_block(shard, block: StagedBlock, part_ids, column: str,
                    end_ms: int, mode: str,
                    dirty_lo: int | None = None) -> "StagedBlock | None":
    """Incrementally append samples that arrived AFTER ``block`` was staged
    (the live-edge dashboard path: every scrape lands just past the staged
    head, and a full re-stage per scrape is the single biggest query cost
    under ingest — the reference serves this straight from write buffers).
    ``dirty_lo`` is the entry's accumulated effect-interval floor
    (StageEntry.dirty_lo): the repair is declined when the dirt provably
    reaches below the staged heads. Thin shard-level wrapper around
    :func:`_append_to_parts`; the cross-shard superblock variant is
    :func:`extend_superblock`."""
    refs = [(shard.shard_num, int(p)) for p in part_ids]
    if refs != list(block.part_refs):
        return None
    parts = [shard.partition(int(p)) for p in part_ids]
    return _append_to_parts(parts, block, column, end_ms, mode,
                            dirty_lo=dirty_lo)


def extend_superblock(memstore, dataset: str, block: StagedBlock,
                      column: str, end_ms: int, mode: str,
                      les=None) -> "StagedBlock | None":
    """``append_to_block`` lifted to the cross-shard superblock (the
    delta-summation move: maintain the device-resident aggregate input
    incrementally on append instead of invalidate-and-restage). Resolves
    every ``part_refs`` row back to its live partition across member shards
    and appends through the same uniform-batch repair core, so the warm
    single-dispatch query stays ONE dispatch under live ingest. The caller
    (plans.FusedAggregateExec) is responsible for proving the ROW SET is
    unchanged (fresh per-shard lookups + the shards' effect logs) before
    calling. ``les`` must be the entry's bucket bounds for [ΣS, T, B]
    histogram superblocks — extension declines when any member partition's
    scheme no longer matches (appended raw rows would land on the wrong
    bounds). Returns None when any precondition fails (caller restages)."""
    parts = []
    try:
        for sn, pid in block.part_refs:
            parts.append(memstore.shard(dataset, sn).partitions[int(pid)])
    except KeyError:
        return None
    if les is not None:
        from ..core.histograms import same_scheme

        for p in parts:
            if p.bucket_les is None or not same_scheme(p.bucket_les, les):
                return None
    return _append_to_parts(parts, block, column, end_ms, mode)


def _append_to_parts(parts, block: StagedBlock, column: str,
                     end_ms: int, mode: str,
                     dirty_lo: int | None = None) -> "StagedBlock | None":
    """Uniform-batch incremental append core shared by the per-shard repair
    path (append_to_block) and the cross-shard superblock extension
    (extend_superblock). ``parts`` are the live partitions in the block's
    ``part_refs`` order — callers have already verified the selection is
    unchanged.

    Mutates the big [n, T] HOST mirrors in place but only at columns >= the
    old head; the small per-series state (h_lens, cont) is copy-on-write,
    so a reader holding the OLD block — an in-flight concat_blocks as much
    as a device-array consumer — keeps a consistent head-m view. The first
    write is where a mirror is paid for (:func:`_writable_mirrors`): every
    check below reads shapes and ``h_lens`` only, so a repair that declines
    costs a deferred mirror nothing. Returns a
    NEW
    StagedBlock carrying the refreshed device arrays and extended shared
    grid — the caller swaps it into the cache entry atomically, so a
    concurrent query sees either the whole old block or the whole new one,
    never a torn mix. Returns None whenever a precondition fails and the
    caller restages from scratch:

    - mode must be raw/shifted/corrected (diff continuation needs state the
      block doesn't carry) and the block host-mirrored (``h_lens``: a
      superblock assembled on the device has its big mirrors deferred, not
      absent), on a REGULAR or
      NEAR-REGULAR (jittered) shared grid — the common live cases;
      masked/irregular blocks restage. Scalar [S, T] blocks support all
      three modes; histogram [S, T, B] blocks (raw cumulative bucket
      counts) support raw on a regular grid;
    - every series must gain the SAME COUNT of new samples — identical
      timestamps on a regular grid, or near-nominal ones (the jitter bound
      re-checked over the extended grid) on a jittered grid — and the
      padded T must still fit.
    """
    if mode not in ("raw", "shifted", "corrected"):
        return None
    if mode == "corrected" and getattr(block, "cont", None) is None:
        return None
    if mode in ("corrected", "shifted") and getattr(block, "base64", None) is None:
        return None  # exact f64 baselines required (f32 rounds +-64 at 1e9)
    jittered = block.regular_ts is None and block.nominal_ts is not None
    if getattr(block, "h_lens", None) is None:
        return None
    if block.regular_ts is None and not jittered:
        return None
    if jittered and getattr(block, "h_dev", None) is None:
        return None
    if block.n_series == 0:
        return None
    is_hist = block.vals.ndim == 3
    if is_hist and (mode != "raw" or jittered):
        return None
    if not is_hist and block.vals.ndim != 2:
        return None
    n = block.n_series
    lens = block.h_lens
    m = int(lens[0])
    if m == 0 or not (lens[:n] == m).all():
        return None
    base = block.base_ms
    grid = np.asarray(block.nominal_ts if jittered else block.regular_ts)
    last_nom = int(grid[m - 1]) + base
    # jittered: each series' head sits at last_nom + its own deviation, so
    # the read starts PER SERIES — an in-order sample landing in another
    # series' (head, last_nom+maxdev] gap must not be silently skipped
    # (it shows up as a non-uniform batch and forces the restage fallback)
    if jittered:
        dev_last = block.h_dev[:n, m - 1].astype(np.int64)
        read_from = [last_nom + int(d) + 1 for d in dev_last]
    else:
        read_from = [last_nom + 1] * n
    # accumulated-dirt floor guard: the append-only repair can only be
    # correct when every dirtying sample sits at or past the staged heads.
    # Today that is guaranteed structurally (partitions drop out-of-order
    # rows and uniform lens pin every member's store head to its staged
    # head), so this cannot fire — it exists to turn a future relaxation
    # of either invariant (e.g. accepting backfill) into a safe restage
    # instead of a silently incomplete block.
    if dirty_lo is not None and dirty_lo < min(read_from) - 1:
        return None
    # gather the per-series tails with NO per-series validation — at 100k
    # series the python-level per-call overhead IS the cost of the repair,
    # so uniformity/NaN/grid checks run vectorized over the stacked [n, k]
    # batch below, with a per-series pass only when the batch is odd
    # (diverging counts, staleness NaNs, histogram shape drift)
    read = getattr(parts[0], "tail_samples", None)
    if read is None:  # test doubles without the lean path
        per = [p.samples_in_range(read_from[i], end_ms, column)
               for i, p in enumerate(parts)]
    else:
        per = [p.tail_samples(read_from[i], end_ms, column)
               for i, p in enumerate(parts)]
    per_ts = [ts for ts, _ in per]
    per_vals = [v for _, v in per]
    V0 = TS0 = None
    k = len(per_ts[0])
    uniform = all(len(ts) == k for ts in per_ts)
    if uniform and k > 0:
        V0 = np.stack(per_vals)
        if V0.ndim != (3 if is_hist else 2):
            uniform = False
            V0 = None
        elif is_hist and V0.shape[2] != block.vals.shape[2]:
            return None  # bucket scheme width changed: restage
        elif not is_hist and np.isnan(V0).any():
            uniform = False  # staleness markers: per-series filtering
            V0 = None
        else:
            TS0 = np.stack(per_ts)
            if not jittered and (TS0 != TS0[0]).any():
                return None  # regular grid would not stay shared
    if not uniform:
        # odd batch: the original per-series discipline (filter staleness
        # NaNs, then require uniform counts + a shared grid)
        new_ts = None
        per_vals = []
        per_ts = []
        for ts, vals in per:
            if getattr(vals, "ndim", 1) != (2 if is_hist else 1):
                return None
            if is_hist:
                if vals.shape[1] != block.vals.shape[2]:
                    return None  # bucket scheme width changed: restage
            else:
                keep = ~np.isnan(vals)
                if not keep.all():
                    ts, vals = ts[keep], vals[keep]
            if new_ts is None:
                new_ts = ts
            elif len(ts) != len(new_ts):
                return None  # appended counts diverge
            elif not jittered and (ts != new_ts).any():
                return None  # regular grid would not stay shared
            per_vals.append(vals)
            per_ts.append(ts)
        k = 0 if new_ts is None else len(new_ts)
    if k == 0:
        return block  # nothing new in this block's range: still clean
    new_ts = per_ts[0]
    T = block.ts.shape[1]
    if m + k > T:
        return None  # padded width exhausted: restage with a bigger T
    if jittered:
        TS = (TS0 if TS0 is not None else np.stack(per_ts)).astype(np.int64)
        if (np.diff(TS, axis=1) <= 0).any():
            return None
        nom_new, dev_new, md_new = nominal_midrange(TS)
        md = max(md_new, int(block.maxdev_ms))
        ext = np.concatenate([grid[:m].astype(np.int64) + base, nom_new])
        d = np.diff(ext)
        if (d <= 0).any() or 2 * md >= int(d.min()):
            return None  # jitter bound fails on the extended grid
        off = (nom_new - base)
        OFF = (TS - base).astype(np.int64)
        if OFF.max() >= 2**31 - 1:
            return None
    else:
        off = (new_ts - base).astype(np.int64)
        if off.max() >= 2**31 - 1 or off.min() <= int(grid[m - 1]):
            return None
    off32 = off.astype(np.int32)
    # vectorized across series: uniform appended counts make the whole
    # repair a handful of [n, k] array ops, not n small python loops
    V = (V0 if V0 is not None else np.stack(per_vals)).astype(np.float64)
    # [n, k] ([n, k, B] hist)
    _writable_mirrors(block)
    if jittered:
        block.h_ts[:n, m : m + k] = (OFF).astype(np.int32)
        block.h_dev[:n, m : m + k] = dev_new.astype(np.float32)
    else:
        block.h_ts[:n, m : m + k] = off32[None, :]
    if mode == "raw":
        block.h_vals[:n, m : m + k] = V.astype(block.h_vals.dtype)
    elif mode == "shifted":
        b = block.base64[:n]
        block.h_vals[:n, m : m + k] = (V - b[:, None]).astype(block.h_vals.dtype)
    new_cont = None
    if mode == "corrected":
        # corrected: exact f64 continuation from the stored state. The
        # continuation arrays are COPY-ON-WRITE (like lens below): the old
        # block object must stay frozen at head m, or a concurrent
        # concat_blocks would snapshot cont at m+k against values at m and
        # a later superblock extension would mis-correct the re-read tail
        # as ~1e9 counter resets
        cont_raw, cont_corr = block.cont
        prev = np.concatenate([cont_raw[:n, None], V[:, :-1]], axis=1)
        drops = np.where(V < prev, prev, 0.0)
        corr = cont_corr[:n, None] + np.cumsum(V - prev + drops, axis=1)
        b = block.base64[:n]
        block.h_vals[:n, m : m + k] = (corr - b[:, None]).astype(block.h_vals.dtype)
        block.h_raw[:n, m : m + k] = V.astype(block.h_raw.dtype)
        new_cont = (cont_raw.copy(), cont_corr.copy())
        new_cont[0][:n] = V[:, -1]
        new_cont[1][:n] = corr[:, -1]
    # lens is copy-on-write: the big [n, T] mirrors may be shared with
    # readers of the OLD block (concat_blocks mid-superblock-build) — the
    # in-place column writes above land only at >= m, invisible under the
    # old lens, so the old block stays a consistent head-m view as long as
    # ITS lens never advances
    new_lens = lens.copy()
    new_lens[:n] = m + k
    ext_grid = grid.copy()
    ext_grid[m : m + k] = off32
    # fresh block object: in-flight readers keep the old (immutable device
    # arrays + old grid) view; window-matrix caches start empty against the
    # extended grid. device_put gets COPIES — on the CPU backend it can
    # alias numpy memory, and the next repair mutates these same mirrors.
    # A series-sharded block (mesh superblock) re-uploads with the SAME
    # placement: extension never changes S, so the row bands still divide.
    put = series_put(block.placement)
    nb = StagedBlock(
        put(block.h_ts.copy()), put(block.h_vals.copy()),
        put(new_lens.copy()), base, block.baseline, n,
        list(block.part_refs),
        raw=(put(block.h_raw.copy())
             if block.h_raw is not None else None),
        regular_ts=None if jittered else ext_grid,
        nominal_ts=ext_grid if jittered else None,
        ts_dev=(put(block.h_dev.copy()) if jittered else None),
        maxdev_ms=(md if jittered else 0),
        placement=block.placement,
    )
    nb.h_ts = block.h_ts
    nb.h_vals = block.h_vals
    nb.h_lens = new_lens
    nb.h_raw = block.h_raw
    nb.h_dev = getattr(block, "h_dev", None)
    nb.h_base = getattr(block, "h_base", None)
    if new_cont is not None:
        nb.cont = new_cont
    elif getattr(block, "cont", None) is not None:
        nb.cont = block.cont
    if getattr(block, "base64", None) is not None:
        nb.base64 = block.base64
    if "_gid_cache" in block.__dict__:
        # label grouping is a pure function of the (unchanged) series set:
        # carrying the memo keeps an extended superblock's warm query free
        # of the O(S) regroup AND the group-id device re-upload
        nb._gid_cache = dict(block._gid_cache)
    return nb


def harmonize_nominal(blocks) -> bool:
    """Rewrite per-shard near-regular blocks onto ONE common nominal grid so
    a mesh kernel can share a single certain/uncertain window structure
    across shards (parallel/exec.py). Each shard staged independently and
    estimated its own nominal grid; the common grid is the midrange of the
    per-block grids, deviations are recomputed exactly from the int
    timestamps, and the safety bound (2*maxdev < min interval) is re-checked
    against the common grid. Returns False (blocks untouched) when the
    blocks can't be harmonized."""
    real = [b for b in blocks if b.n_series > 0]
    if not real:
        return False
    noms = []
    m = None
    for b in real:
        lens = np.asarray(b.lens)
        if not (lens[: b.n_series] == lens[0]).all() or lens[0] == 0:
            return False
        if m is None:
            m = int(lens[0])
        elif int(lens[0]) != m:
            return False
        if b.regular_ts is not None:
            noms.append(np.asarray(b.regular_ts)[:m].astype(np.int64))
        elif b.nominal_ts is not None:
            noms.append(np.asarray(b.nominal_ts)[:m].astype(np.int64))
        else:
            return False
    if len({b.base_ms for b in real}) != 1:
        return False
    nom_mat = np.stack(noms)
    common = (nom_mat.min(axis=0) + nom_mat.max(axis=0)) // 2
    if m >= 2:
        min_int = int(np.diff(common).min())
    else:
        return False
    devs, md = [], 0
    for b in real:
        ts = np.asarray(b.ts)[: b.n_series, :m].astype(np.int64)
        d = ts - common[None, :]
        md = max(md, int(np.abs(d).max()))
        devs.append(d)
    if min_int <= 0 or 2 * md >= min_int:
        return False
    for b, d in zip(real, devs):
        T = b.ts.shape[1]
        S = b.vals.shape[0]
        nominal = np.full(T, TS_PAD, dtype=np.int32)
        nominal[:m] = common.astype(np.int32)
        ts_dev = np.zeros((S, T), dtype=np.float32)
        ts_dev[: b.n_series, :m] = d.astype(np.float32)
        b.nominal_ts = nominal
        b.ts_dev = ts_dev
        b.maxdev_ms = md
        b.regular_ts = b.regular_ts if md == 0 else None
        if hasattr(b, "_jwm_cache"):
            del b._jwm_cache
    return True


def stage_histogram_series(
    series: list[tuple[np.ndarray, np.ndarray]],
    base_ms: int,
    n_buckets: int,
    part_refs: list | None = None,
    subtract_baseline: bool = False,
    dtype=np.float32,
):
    """Like stage_series but values are [T, B] bucket-count rows.

    Returns (StagedBlock with vals [S, T, B], baseline [S, B]).
    """
    n = len(series)
    maxlen = 1
    for ts, _ in series:
        maxlen = max(maxlen, len(ts))
    S = pad_series(max(n, 1))
    T = pad_time(maxlen)
    out_ts = np.full((S, T), TS_PAD, dtype=np.int32)
    out_vals = np.zeros((S, T, n_buckets), dtype=dtype)
    lens = np.zeros(S, dtype=np.int32)
    baseline = np.zeros((S, n_buckets), dtype=dtype)
    for i, (ts, vals) in enumerate(series):
        m = len(ts)
        lens[i] = m
        if m == 0:
            continue
        out_ts[i, :m] = (ts - base_ms).astype(np.int32)
        if subtract_baseline:
            b = vals[0].astype(np.float64)
            baseline[i] = b.astype(dtype)
            out_vals[i, :m] = (vals.astype(np.float64) - b).astype(dtype)
        else:
            out_vals[i, :m] = vals.astype(dtype)
    # shared-grid detection, same rule as scalar staging: regular grids get
    # the series-independent [J] window boundaries (ops/hist_kernels shared
    # variant), NEAR-regular (jittered scrape) grids get the certain-range
    # boundaries + per-series one-slot corrections (jitter variant) instead
    # of the O(S*J*T) per-series compare
    regular, nominal, ts_dev, maxdev = detect_shared_grid(
        out_ts, lens, n, T, S
    )
    return StagedBlock(out_ts, out_vals, lens, base_ms, baseline, n,
                       part_refs or [], regular_ts=regular,
                       nominal_ts=nominal, ts_dev=ts_dev, maxdev_ms=maxdev)


def _slot_align(shard, part_ids, column, series, start_ms: int, end_ms: int):
    """Repair ragged staging of near-regular grids at the read-range edges.

    A sample whose jittered timestamp falls just outside [start_ms, end_ms]
    is excluded for SOME series, so per-series sample counts differ by 1-2
    and the near-regular detection (and with it the MXU jitter path) fails.
    Re-read with a one-interval margin, map every sample to its nominal slot,
    and trim all series to the common slot range that can contribute to any
    window. Dropped edge slots provably can't: a slot with nominal time
    g <= start - maxdev has true ts <= start for every series (windows need
    ts > bound >= start - window... bound >= start_ms here because start_ms
    is the staged lower bound = earliest window start), and one with
    g > end + maxdev has ts > end >= every window end.

    Returns the slot-aligned series list, or None when the data isn't
    near-regular (caller keeps the original packed staging)."""
    lens = [len(t) for t, _ in series]
    if not lens or min(lens) < 2 or max(lens) - min(lens) > 2:
        return None
    ref = series[int(np.argmax(lens))][0]
    diffs = np.diff(ref)
    # endpoint-based estimate: per-sample jitter contributes only
    # O(maxdev / n) error, where a median of jittered diffs drifts by
    # O(n * median_error) across the span
    interval = float(ref[-1] - ref[0]) / (len(ref) - 1)
    if interval <= 0 or (np.abs(diffs - interval) > 0.45 * interval).any():
        return None
    anchor = float(ref[0])
    margin = int(round(interval))
    per = []
    md = 0.0
    for pid in part_ids:
        ts, v = shard.partition(int(pid)).samples_in_range(
            start_ms - margin, end_ms + margin, column
        )
        if v.ndim == 2 or len(ts) < 2:
            return None
        keep = ~np.isnan(v)
        if not keep.all():
            return None  # staleness holes: packed staging handles them
        k = np.rint((ts.astype(np.float64) - anchor) / interval).astype(np.int64)
        if (np.diff(k) != 1).any():
            return None  # missed scrapes: not slot-contiguous
        md = max(md, float(np.abs(ts - (anchor + k * interval)).max()))
        per.append((k, ts, v))
    if 2.0 * md >= 0.9 * interval:
        return None
    # slots that could contribute to any window of the staged range
    k_need_lo = int(np.ceil((start_ms - md - anchor) / interval - 1e-9))
    while anchor + k_need_lo * interval <= start_ms - md:
        k_need_lo += 1
    k_need_hi = int(np.floor((end_ms + md - anchor) / interval + 1e-9))
    while anchor + k_need_hi * interval > end_ms + md:
        k_need_hi -= 1
    # clamp the needed range to slots where data EXISTS at all: a live-edge
    # query's end (beyond every series' newest sample) must not make the
    # repair demand future slots of nobody (and symmetrically at the low
    # edge before retention)
    k_need_lo = max(k_need_lo, min(k[0] for k, _, _ in per))
    k_need_hi = min(k_need_hi, max(k[-1] for k, _, _ in per))
    k_lo = max(k[0] for k, _, _ in per)
    k_hi = min(k[-1] for k, _, _ in per)
    if k_lo > k_need_lo or k_hi < k_need_hi or k_need_hi < k_need_lo:
        return None  # a needed slot is genuinely missing for some series
    out = []
    width = k_need_hi - k_need_lo + 1
    for k, ts, v in per:
        o = k_need_lo - int(k[0])
        out.append((ts[o : o + width], v[o : o + width]))
    return out


# Shares of EACH device's memory the two device-resident caches may pin
# there. Their byte ceilings (SuperblockCache.max_bytes 8 GiB, StoreConfig
# .stage_cache_bytes 2 GiB x shards) were set without a chip and together
# exceed a 16 GB v5e: at 100k series x 8 shards the shipped defaults ran
# HBM out after ~8 distinct query keys (CHANGES.md PR 21). The rest of the
# device is for what the caches do not count: the block being built while
# the old ones are still pinned, window structures, kernel temporaries
# (2.5 GB over the ledger at the peak of that run). The two figures come
# from that ONE deployment size on a v5e — they bound the duplication,
# they do not repair it (ROADMAP S2 / D4). What is duplicated is residency
# only: every row is on the device twice, in its shard's block and in the
# superblock assemble_rows copied it into (and a key the pre-warm restages
# holds both again), but it crosses to the device once, as part of its
# shard's block, and is never read back.
SUPERBLOCK_DEVICE_SHARE = 0.35
STAGE_CACHE_DEVICE_SHARE = 0.20


@functools.lru_cache(maxsize=1)
def _device_bytes_limits() -> dict:
    """``memory_stats()["bytes_limit"]`` of every visible device, keyed by
    ``str(device)`` (the key ``mesh_device_bytes`` uses); a device whose
    backend reports none (the CPU backend) is absent."""
    out = {}
    for d in jax.devices():
        stats = d.memory_stats()
        if stats and stats.get("bytes_limit"):
            out[str(d)] = int(stats["bytes_limit"])
    return out


@functools.lru_cache(maxsize=1)
def default_device_key() -> str:
    """``str()`` of the device an un-sharded ``jax.device_put`` lands on."""
    return str(jax.devices()[0])


def device_cache_budget(share: float, ceiling: int,
                        device: str | None = None) -> int:
    """Bytes a device-resident cache may pin on ``device`` (default: where
    un-sharded blocks land): ``share`` of that device's memory, never above
    the configured ``ceiling`` — the ceiling alone where the device reports
    no limit."""
    limit = _device_bytes_limits().get(device or default_device_key())
    return ceiling if limit is None else min(ceiling, int(limit * share))


def staged_nbytes(block: StagedBlock) -> int:
    """True device-byte footprint of a staged block: every array a
    ``to_device`` pins in HBM. Histogram blocks carry [S, T, B] vals and
    [S, B] baselines — the B axis multiplies the footprint ~20-60x over a
    scalar block of the same selection, and cache eviction budgets
    (stage_cache_bytes, SuperblockCache.max_bytes) must see that. Reads
    ``.nbytes`` directly so device arrays are never fetched to host."""
    total = 0
    for arr in (block.ts, block.vals, block.raw, block.baseline, block.lens,
                block.ts_dev):
        if arr is not None:
            total += int(arr.nbytes)
    return total + _mgrid_nbytes(block.mgrid)


def _mgrid_nbytes(mgrid) -> int:
    if mgrid is None:
        return 0
    return sum(
        int(arr.nbytes)
        for arr in (getattr(mgrid, f) for f in _MGRID_ARRAYS)
        if arr is not None)


# a staged block's arrays and the host mirrors ``to_device(keep_host=True)``
# and the append repair leave beside them
_MIRRORS = {"ts": "h_ts", "vals": "h_vals", "lens": "h_lens", "raw": "h_raw",
            "ts_dev": "h_dev", "baseline": "h_base"}


def read_back(blocks, *fields) -> list[list]:
    """Per block, the host copy of each of its arrays named in ``fields``
    (``ts``, ``vals``, ``lens``, ``raw``, ``ts_dev``, ``baseline``), as the
    ``readback`` part of ``stage``. What the host still holds is not fetched:
    an array that is still numpy is returned as it is, and one uploaded with
    ``to_device(keep_host=True)`` (every block out of the stage cache) gives
    its mirror — rows below ``lens`` are the device's bits; a repair that has
    since moved on may have written columns beyond them. Only an array with
    neither is converted, and that is the ONE place a staged array crosses
    back: a D2H copy the first time (jax keeps the host copy on the array
    afterwards) that waits for the upload it reads, its bytes counted in
    ``filodb_stage_d2h_bytes_total``. An absent array gives ``None``."""
    out, nbytes = [], 0
    with span("stage:readback", part="readback"):
        for b in blocks:
            row = []
            for f in fields:
                a = getattr(b, f)
                if isinstance(a, jax.Array):
                    mirror = getattr(b, _MIRRORS[f], None)
                    if isinstance(mirror, np.ndarray):
                        a = mirror
                    else:
                        # _npy_value is where jax caches a fetched host copy;
                        # a jax without it would count every conversion,
                        # never too few
                        if getattr(a, "_npy_value", None) is None:
                            nbytes += int(a.nbytes)
                        a = np.asarray(a)
                row.append(a)
            out.append(row)
    # booked even when nothing crossed: /metrics then says 0, not nothing
    REGISTRY.counter("filodb_stage_d2h_bytes").inc(nbytes)
    return out


def staged_samples(blocks) -> int:
    """Samples the blocks hold: the sum of their ``lens``."""
    return sum(int(lens.sum()) for (lens,) in read_back(blocks, "lens"))


def _members(blocks) -> list:
    """The blocks a superblock is made of: those with series, or the first
    one when none has (an empty-but-shaped block: mesh rows can be empty)."""
    return [b for b in blocks if b.n_series > 0] or list(blocks[:1])


def _padded_rows(real, series_multiple: int = 1) -> int:
    rows = pad_series(sum(b.n_series for b in real))
    if series_multiple > 1:
        rows = -(-rows // series_multiple) * series_multiple
    return rows


def _shared_regular(real, T: int):
    """The [T] regular grid every member advertises identically, else None
    (narrower padded members keep the shared grid)."""
    reg = real[0].regular_ts
    if reg is None or not all(
        b.regular_ts is not None
        and len(b.regular_ts) == len(reg)
        and not (np.asarray(b.regular_ts) != np.asarray(reg)).any()
        for b in real[1:]
    ):
        return None
    regular = np.asarray(reg)
    if len(regular) < T:
        ext = np.full(T, TS_PAD, np.int32)
        ext[: len(regular)] = regular
        regular = ext
    return regular


def concat_blocks(blocks, force_raw: bool = False,
                  series_multiple: int = 1, full: bool = True) -> StagedBlock:
    """Row-concatenate staged blocks into one padded superblock EXACTLY, on
    the host — corrected values, raw sidecars, baselines and part refs carry
    over with no restaging and no semantic drift. All blocks must share
    base_ms. Members' arrays are taken where the host has them (their
    mirrors, :func:`read_back`): concatenating blocks out of the stage cache
    reads nothing back from the device.

    Histogram blocks ([S, T, B] vals, [S, B] baselines) concatenate the same
    way into a ``[ΣS, T, B]`` superblock; all blocks must already share one
    bucket scheme (callers unify heterogeneous ``le`` schemes first via
    core.histograms.remap_buckets — see plans._build_superblock).

    The shared regular grid survives only when every non-empty block
    advertises the identical ``regular_ts`` (same padded length, same
    offsets) — that keeps the MXU window-matrix path available for the
    single-dispatch fused aggregate; otherwise the superblock runs the
    general kernels. ``force_raw`` always materializes the raw sidecar
    (filling from vals where a block has none) for consumers that index it
    unconditionally (the mesh stacking path); histogram blocks never carry
    one. ``series_multiple`` rounds the padded series axis up to a multiple
    (a device-mesh size): series-axis sharding needs equal per-device row
    bands, and the trash-group/padded-row masking already makes the extra
    rows inert.

    ``full=False`` is for a caller that assembles the arrays on the device
    (:func:`build_superblock`) and defers the mirrors: only what the grid
    classification reads is concatenated — ``lens`` always, ``ts`` unless
    the members share a regular grid, ``vals`` / ``raw`` for a masked build
    — and the other arrays of the result are ``None``."""
    real = _members(blocks)
    assert real and len({b.base_ms for b in real}) == 1
    T = max(b.ts.shape[1] for b in real)
    S = sum(b.n_series for b in real)
    Sp = _padded_rows(real, series_multiple)
    is_hist = any(b.vals.ndim == 3 for b in real)
    buckets: tuple = ()
    if is_hist:
        assert len({b.vals.shape[2] for b in real}) == 1, (
            "histogram blocks must share one bucket scheme before concat"
        )
        buckets = (real[0].vals.shape[2],)
    any_raw = (force_raw or any(b.raw is not None for b in real)) and not is_hist
    host: list[dict] = [{} for _ in real]  # per member, what read_back gave
    rows: dict = {}  # field -> the members' rows of it, concatenated

    def need(*fields):
        todo = [f for f in fields if f not in rows]
        if todo:
            rows.update(
                zip(todo, _concat_rows(real, host, Sp, todo, T, buckets)))

    big = ("vals", "raw") if any_raw else ("vals",)
    need("lens", *(("ts", "baseline") + big if full else ()))
    lens = rows["lens"]
    part_refs: list = []
    for b in real:
        part_refs.extend(b.part_refs)
    regular = _shared_regular(real, T)
    # grid classification does NOT stop at "not exactly regular": re-detect
    # the near-regular (jittered scrape) and masked (missing-scrape) grids
    # over the CONCATENATED rows, so a cross-shard superblock keeps the
    # jitter-tolerant fused kernels available instead of silently dropping
    # to the multi-pass general path (the jitter5pct 1.70x / jitter+holes
    # 4.85x gap). Per-shard blocks estimated their nominal grids
    # independently; the midrange over the full row set re-derives one
    # common grid with the same 2*maxdev < min-interval safety bound, and
    # the masked build snaps every row onto one slot grid with validity
    # holes. Truly irregular data fails both checks and stays general.
    nominal = ts_dev = None
    maxdev = 0
    mgrid = None
    if regular is None and S > 0:
        need("ts")
        ts = rows["ts"]
        _reg2, nominal, ts_dev, maxdev = detect_shared_grid(
            ts, lens, S, T, Sp
        )
        if _reg2 is not None:
            # members' advertised grids differed (padded widths) but the
            # real rows agree exactly ([T]-wide: row 0 of the concatenated
            # timestamp array)
            regular = _reg2
        elif nominal is None and not is_hist and S > 1 and int(
            lens[:S].min()
        ) >= 2:
            need(*big)
            base = real[0].base_ms
            cleaned = [
                (ts[i, : lens[i]].astype(np.int64) + base, None)
                for i in range(S)
            ]
            mgrid = _build_masked_grid(cleaned, base, rows["vals"],
                                       rows.get("raw"), lens, T, Sp)
    out = StagedBlock(rows.get("ts"), rows.get("vals"), lens,
                      real[0].base_ms, rows.get("baseline"), S,
                      part_refs, raw=rows.get("raw"), regular_ts=regular,
                      nominal_ts=nominal, ts_dev=ts_dev, maxdev_ms=maxdev,
                      mgrid=mgrid)
    if not is_hist:
        # f64 continuation state rides along (snapshot — the member blocks'
        # own state keeps evolving under per-shard repairs) so the
        # superblock can itself be incrementally extended on live-edge
        # ingest (extend_superblock) with exact counter correction
        if all(getattr(b, "base64", None) is not None for b in real):
            base64 = np.zeros(Sp, np.float64)
            o = 0
            for b in real:
                base64[o : o + b.n_series] = np.asarray(b.base64)[: b.n_series]
                o += b.n_series
            out.base64 = base64
        if all(getattr(b, "cont", None) is not None for b in real):
            cont_raw = np.zeros(Sp, np.float64)
            cont_corr = np.zeros(Sp, np.float64)
            o = 0
            for b in real:
                k = b.n_series
                cont_raw[o : o + k] = np.asarray(b.cont[0])[:k]
                cont_corr[o : o + k] = np.asarray(b.cont[1])[:k]
                o += k
            out.cont = (cont_raw, cont_corr)
    return out


def _concat_rows(real, host, rows: int, fields, T: int,
                 buckets: tuple) -> list:
    """Per field, a fresh ``[rows, ...]`` host array holding every member's
    real series at its row band and padding (``TS_PAD`` timestamps, else 0)
    everywhere else. ``host`` holds, per member, the arrays taken on the host
    so far (:func:`read_back`), and gains what is missing: no array is
    taken twice. ``raw`` takes a member's ``vals`` where it has no sidecar.
    A member's columns are copied up to its longest series only: beyond it a
    staged block holds padding, and its mirror may hold what a later repair
    wrote (:func:`_append_to_parts`), which is not this block's. The arrays
    are written once and belong to the caller: they become the superblock's
    arrays on the host path, and on the device path the mirrors it has from
    the start (the others wait: :func:`materialize_mirrors`)."""
    want = dict.fromkeys(tuple(fields) + ("lens",) + (
        ("vals",) if "raw" in fields else ()))
    missing = [f for f in want if f not in host[0]]
    if missing:
        for have, got in zip(host, read_back(real, *missing)):
            have.update(zip(missing, got))
    return _write_bands([(b.n_series, have) for b, have in zip(real, host)],
                        rows, fields, T, buckets)


def _write_bands(bands, rows: int, fields, T: int, buckets: tuple) -> list:
    """:func:`_concat_rows`' one write: ``bands`` are, per member, its series
    count and its host arrays by field (``lens`` among them)."""
    shapes = {"ts": (rows, T), "vals": (rows, T) + buckets, "raw": (rows, T),
              "lens": (rows,), "baseline": (rows,) + buckets}
    out = [np.full(shapes[f], TS_PAD, np.int32) if f == "ts"
           else np.zeros(shapes[f], np.int32 if f == "lens" else np.float32)
           for f in fields]
    o = 0
    for k, have in bands:
        w = int(have["lens"][:k].max()) if k else 0
        for f, dst in zip(fields, out):
            src = have[f] if have[f] is not None else have["vals"]
            if f in ("lens", "baseline"):
                dst[o : o + k] = src[:k]
            else:
                dst[o : o + k, :w] = src[:k, :w]
        o += k
    return out


def _place_rows(out, member, offset, count):
    """``out`` with ``member``'s first ``count`` rows written at row
    ``offset`` and every other row as it was. ``offset`` and ``count`` are
    values, so one program serves every selection of these padded shapes.
    The window written is the member's whole padded height; where that
    would reach past ``out``'s last row (the last members' padding may) it
    is moved up, as ``dynamic_update_slice`` would move it, and the member's
    rows are moved down within it by as much; rows of the window that are
    not the member's are written back as read."""
    n = member.shape[0]
    tail = (0,) * (member.ndim - 1)
    start = jnp.clip(offset, 0, out.shape[0] - n)
    shift = offset - start
    row = lax.broadcasted_iota(jnp.int32, (n,) + (1,) * (member.ndim - 1), 0)
    mine = (row >= shift) & (row < shift + count)
    window = lax.dynamic_slice(out, (start,) + tail, member.shape)
    moved = jnp.roll(member.astype(out.dtype), shift, axis=0)
    return lax.dynamic_update_slice(
        out, jnp.where(mine, moved, window), (start,) + tail)


@functools.partial(jax.jit, static_argnames=("rows",))
def assemble_rows(members, offsets, counts, jitter, *, rows: int):
    """The device side of :func:`concat_blocks`: one program that writes
    every member's ``(ts, vals, raw, lens, baseline)`` (``raw`` None
    throughout for blocks without a sidecar) into its row band of fresh
    ``[rows, ...]`` arrays, narrower members padded to the common T, all
    else padding. ``jitter`` is None, or ``(nominal [T], n, m)`` of a
    near-regular superblock: the per-sample deviations of its ``n`` series'
    first ``m`` samples from the nominal grid come back as a sixth array
    (``detect_shared_grid``'s ``ts_dev``). Compiled per padded shapes and
    member count only."""
    T = max(m[0].shape[1] for m in members)
    buckets = members[0][1].shape[2:]
    ts = jnp.full((rows, T), TS_PAD, jnp.int32)
    vals = jnp.zeros((rows, T) + buckets, jnp.float32)
    raw = None if members[0][2] is None else jnp.zeros((rows, T), jnp.float32)
    lens = jnp.zeros((rows,), jnp.int32)
    baseline = jnp.zeros((rows,) + buckets, jnp.float32)
    for i, (m_ts, m_vals, m_raw, m_lens, m_base) in enumerate(members):
        at = (offsets[i], counts[i])
        ts = _place_rows(ts, m_ts, *at)
        vals = _place_rows(vals, m_vals, *at)
        if raw is not None:
            raw = _place_rows(raw, m_raw, *at)
        lens = _place_rows(lens, m_lens, *at)
        baseline = _place_rows(baseline, m_base, *at)
    ts_dev = None
    if jitter is not None:
        nominal, n, m = jitter
        real = ((lax.broadcasted_iota(jnp.int32, (rows, 1), 0) < n)
                & (lax.broadcasted_iota(jnp.int32, (1, T), 1) < m))
        ts_dev = jnp.where(
            real, (ts - nominal[None, :]).astype(jnp.float32), 0.0)
    return ts, vals, raw, lens, baseline, ts_dev


def _one_device(real) -> bool:
    """Whether every array of every member is committed to one and the same
    single device — what :func:`assemble_rows` can read in place."""
    devices = set()
    for b in real:
        for a in (b.ts, b.vals, b.lens, b.baseline, b.raw):
            if a is None:
                continue
            if not isinstance(a, jax.Array):
                return False
            devices |= a.devices()
    return len(devices) == 1


def build_superblock(blocks, mesh=None) -> tuple[StagedBlock, int]:
    """The fused aggregate's superblock, resident where its kernels run:
    ``(block, bytes uploaded for it)``. One algorithm — row-concatenate, pad,
    classify the grid (:func:`concat_blocks`) — whose copy of the big arrays
    runs where the bytes already are:

    - every member on ONE device and no mesh (blocks straight out of the
      stage cache): :func:`assemble_rows` builds the device arrays from the
      members' device arrays, bit for bit what the host concatenation and a
      ``device_put`` give. The host concatenates only what the grid
      classification reads (``lens`` always), and that is all the mirror the
      block has: the big ones are ``deferred`` until an extension first
      writes them (:func:`materialize_mirrors`), which a historical panel
      never does. Nothing but a masked sidecar is uploaded;
    - otherwise (a member made on the host after staging — a remapped bucket
      scheme, a ``le=`` slice — or a mesh placement): concatenate on the
      host and upload the whole; what was concatenated is the mirror
      (:meth:`StagedBlock.to_device`).

    Books ``stage:concat`` (the host's part) and ``stage:h2d_super`` (the
    device's), one ``filodb_superblock_assembled_total{where}``, and what
    became of the mirrors (:func:`book_mirrors`, ``site="super"``)."""
    real = _members(blocks)
    multiple = mesh.devices.size if mesh is not None else 1
    rows = _padded_rows(real, multiple)
    on_device = (mesh is None and _one_device(real)
                 and max(b.ts.shape[0] for b in real) <= rows)
    with span("stage:concat", part="concat"):
        out = concat_blocks(blocks, series_multiple=multiple,
                            full=not on_device)
    with span("stage:h2d_super", part="h2d_super"):
        if on_device:
            uploaded = _assemble_on_device(out, real, rows)
        else:
            out.to_device(keep_host=True, mesh=mesh)
            uploaded = staged_nbytes(out)
    book_mirrors("super", out.mirrored)
    REGISTRY.counter("filodb_superblock_assembled",
                     where="device" if on_device else "host").inc()
    return out, uploaded


# the big arrays a device-assembled superblock may lack a mirror of
_DEFERRED = ("ts", "vals", "raw")


def _assemble_on_device(out: StagedBlock, real, rows: int) -> int:
    """Give the host-concatenated ``out`` its device arrays, assembled from
    the members'. What the host concatenated is a mirror already; for the
    rest ``out`` remembers, weakly, the members' mirrors it can be copied
    from while they live. Returns the bytes uploaded (the masked sidecar's;
    the program's arguments are not counted, as for any dispatch)."""
    any_raw = any(b.raw is not None for b in real) and real[0].vals.ndim == 2
    members = tuple(
        (b.ts, b.vals,
         (b.raw if b.raw is not None else b.vals) if any_raw else None,
         b.lens, b.baseline)
        for b in real)
    offsets = np.cumsum([0] + [b.n_series for b in real[:-1]]).astype(np.int32)
    counts = np.asarray([b.n_series for b in real], np.int32)
    jitter = None
    if out.nominal_ts is not None:
        m = int(out.lens[0])
        jitter = (out.nominal_ts, np.int32(out.n_series), np.int32(m))
    t0 = time.perf_counter()
    before = assemble_rows._cache_size()
    ts, vals, raw, lens, baseline, ts_dev = assemble_rows(
        members, offsets, counts, jitter, rows=rows)
    record_kernel_dispatch(
        "superblock_assemble", time.perf_counter() - t0,
        compiled=assemble_rows._cache_size() > before,
        key={"variant": "hist" if vals.ndim == 3 else "scalar",
             "shapes": "x".join(
                 ["S%d" % rows] + ["%s%d" % p for p in zip("TB", vals.shape[1:])]),
             "batch": len(real)},
    )
    out.h_ts, out.h_vals, out.h_lens = out.ts, out.vals, out.lens
    out.h_raw, out.h_dev, out.h_base = out.raw, out.ts_dev, out.baseline
    out.ts, out.vals, out.raw, out.lens = ts, vals, raw, lens
    out.baseline, out.ts_dev = baseline, ts_dev
    out.mirror_sources = [
        (b.n_series, {f: weakref.ref(a) for f in _DEFERRED
                      if (a := getattr(b, _MIRRORS[f], None)) is not None})
        for b in real]
    out.mirrored = {"deferred": sum(
        int(getattr(out, f).nbytes) for f in _deferred(out))}
    if out.mgrid is not None:
        out.mgrid.to_device()
    return _mgrid_nbytes(out.mgrid)


def _deferred(block: StagedBlock) -> list[str]:
    return [f for f in _DEFERRED if getattr(block, f) is not None
            and getattr(block, _MIRRORS[f], None) is None]


def materialize_mirrors(block: StagedBlock) -> None:
    """Make the mirrors a device-assembled superblock deferred, when an
    extension first writes them; a block that has them is left as it is.
    From the members' mirrors while every one of them is still alive (the
    shards' stage caches hold them; repairs since have written only past the
    columns copied here): the host copy ``concat`` did not do at build, and
    booked there. Else from the superblock's own device arrays
    (:func:`read_back`: a D2H copy, booked under ``readback`` and in
    ``filodb_stage_d2h_bytes_total``). Either way ``materialized``, once per
    cache entry: the extended block carries them on."""
    todo = _deferred(block)
    if not todo:
        return
    bands = _member_bands(block)
    if bands:
        with span("stage:concat", part="concat"):
            made = _write_bands(bands, block.ts.shape[0], todo,
                                block.ts.shape[1], tuple(block.vals.shape[2:]))
    else:
        # np.asarray of a device array is jax's own cached, read-only copy
        made = [np.array(a, copy=True) for a in read_back([block], *todo)[0]]
    for f, a in zip(todo, made):
        setattr(block, _MIRRORS[f], a)
    book_mirrors("super", {"materialized": sum(int(a.nbytes) for a in made)})


def _member_bands(block: StagedBlock) -> list | None:
    """:func:`_write_bands`' input from the members' mirrors a
    device-assembled ``block`` remembers, cut to the heads it was built at
    (its own ``h_lens``); None when any of them has gone."""
    bands, o = [], 0
    for k, refs in block.__dict__.pop("mirror_sources", ()):
        have = {f: ref() for f, ref in refs.items()}
        if not {"ts", "vals"} <= have.keys() or any(
                a is None for a in have.values()):
            return None
        have.setdefault("raw", None)  # no sidecar: the member's vals serve
        bands.append((k, dict(have, lens=block.h_lens[o : o + k])))
        o += k
    return bands


def _writable_mirrors(block: StagedBlock) -> None:
    """Before a repair's first in-place write: the mirrors exist, and the
    upload that may still be reading the same host memory (an ``aliased``
    mirror, :meth:`StagedBlock.to_device`) has finished. By the time a
    scrape arrives the device arrays are long ready, so the wait is free."""
    materialize_mirrors(block)
    jax.block_until_ready((block.ts, block.vals, block.raw, block.ts_dev))


def book_mirrors(site: str, kept: dict) -> None:
    """``filodb_stage_mirror_bytes_total{site, how}``: what became of the
    host mirrors of a shard's staged block (``site="shard"``) or of a
    superblock (``"super"``), in bytes by ``how`` — ``aliased`` | ``copied``
    at an upload, ``deferred`` at a device assembly, ``materialized`` at a
    deferred mirror's first extension."""
    for how, nbytes in kept.items():
        if nbytes:
            REGISTRY.counter("filodb_stage_mirror_bytes",
                             site=site, how=how).inc(nbytes)


def _superblock_cache_walker(cache) -> int:
    """Cold recount of the superblock cache's true device footprint (drift
    ground truth; must match the staged_nbytes accounting put() receives)."""
    with cache._lock:
        values = [v[1] for v in cache._d.values()]
    total = 0
    for v in values:
        block = getattr(v, "block", None)
        if block is not None:
            total += staged_nbytes(block)
    return total


def _superblock_device_walker(cache) -> dict:
    """Per-device byte balances of SHARDED cached superblocks (metadata-only
    split recorded at put time) — the filodb_device_bytes{kind,device}
    breakdown; single-device entries carry no device dimension."""
    with cache._lock:
        metas = list(cache._meta.values())
    out: dict[str, int] = {}
    for m in metas:
        db = m.get("device_bytes")
        if db:
            for dev, b in db.items():
                out[dev] = out.get(dev, 0) + int(b)
    return out


class SuperblockCache:
    """Shard-version-keyed cache of device-resident cross-shard superblocks
    (the staging layer of the single-dispatch fused aggregate).

    Entries are keyed by the query's staging identity (selector filters,
    range, column, stage mode, shard set); each stores the vector of member
    shard versions it was built from, so ANY ingest on ANY member shard
    invalidates the entry at its next lookup — the rebuild then re-reads the
    per-shard blocks, which repair incrementally through the shard staging
    cache (append_to_block) instead of restaging from chunks. LRU on hit,
    bounded by entry count and bytes."""

    def __init__(self, max_entries: int = 8, max_bytes: int = 8 << 30):
        from ..ledger import LEDGER
        from ..singleflight import KeyedSingleFlight

        self.max_entries = max_entries
        # the knob: a ceiling on all entries together. Each device holds at
        # most its own share besides (_fits): an un-sharded entry is charged
        # whole to the default device, a sharded one band by band
        self.max_bytes = max_bytes
        self._d: OrderedDict = OrderedDict()
        # per-key introspection sidecar for /debug/superblocks: created
        # time, hit count, last maintenance outcome (the PR-6 taxonomy)
        self._meta: dict = {}
        # pinned keys -> owner set (standing queries): pinned entries are
        # SKIPPED by put()'s eviction loop, so an ad-hoc eviction storm
        # cannot churn a standing query's entry out from under its delta
        # refresh (which would silently degrade every refresh to
        # rebuild+suffix). Pins are identity, not storage — a key may be
        # pinned before its entry is built, and unpinning never drops data.
        self._pins: dict = {}
        self._lock = threading.Lock()
        self._flight = KeyedSingleFlight(
            max_keys=4 * max_entries, alive=lambda k: k in self._d
        )
        # device-ledger account (filodb_tpu/ledger.py): every put/evict/drop
        # debits/credits; the walker recounts live entries for drift checks.
        # The device walker splits sharded entries' balances per device for
        # the filodb_device_bytes{kind,device} gauges.
        self.ledger = LEDGER.register(
            self, "superblock", _superblock_cache_walker,
            name="superblock-cache",
            device_walker=_superblock_device_walker,
        )

    def build_lock(self, key) -> threading.Lock:
        """Per-key single-flight for builders (the shared
        filodb_tpu/singleflight utility): concurrent identical cold queries
        serialize on this lock so only one concatenates + uploads the
        superblock; the rest hit its freshly-put entry. Locks for keys no
        longer cached are pruned opportunistically (a racer holding a pruned
        lock merely degrades to a duplicate build)."""
        return self._flight.lock(key)

    def get(self, key, versions: tuple):
        with self._lock:
            hit = self._d.get(key)
            if hit is None or hit[0] != versions:
                # version-stale entries are RETAINED (not dropped): the
                # interval-aware refresh path (peek/revalidate + the
                # superblock extension in plans.FusedAggregateExec) can
                # prove them still valid or extend them in place, which is
                # the whole point of surviving ingest that doesn't touch
                # their range. LRU + the byte budget bound them; put()
                # replaces in place on rebuild.
                return None
            self._d.move_to_end(key)
            meta = self._meta.get(key)
            if meta is not None:
                meta["hits"] += 1
            return hit[1]

    def peek(self, key):
        """The stored ``(versions, value, nbytes)`` triple regardless of
        staleness (None when absent) — input to the interval-aware
        revalidate/extend decision."""
        with self._lock:
            return self._d.get(key)

    def revalidate(self, key, old_versions: tuple, new_versions: tuple) -> bool:
        """CAS the stored version vector: the caller proved (via the member
        shards' effect logs) that every bump between the two vectors was
        disjoint from the entry's staged range. Fails — returns False —
        when a racer replaced or dropped the entry in the meantime."""
        with self._lock:
            hit = self._d.get(key)
            if hit is None or hit[0] != old_versions:
                return False
            self._d[key] = (new_versions, hit[1], hit[2])
            self._d.move_to_end(key)
            return True

    def drop(self, key) -> None:
        """Remove an entry outright — required when an in-place extension
        mutated its host mirrors but could not be committed (the mirrors
        are now ahead of the entry's device arrays, so it must never be
        served or extended again)."""
        with self._lock:
            gone = self._d.pop(key, None)
            self._meta.pop(key, None)
            if gone is not None:
                self.ledger.free(gone[2], reason="drop")
            self._publish_pinned_locked()

    def note(self, key, outcome: str) -> None:
        """Record the last maintenance outcome for an entry (the
        ``filodb_superblock_maintenance_total`` taxonomy, surfaced per
        entry at /debug/superblocks)."""
        with self._lock:
            meta = self._meta.get(key)
            if meta is not None:
                meta["last_outcome"] = outcome

    def pin(self, key, owner) -> None:
        """Pin ``key`` against eviction on behalf of ``owner`` (a standing
        query id). Pinning a not-yet-built key is allowed — the pin takes
        effect when put() stores it."""
        with self._lock:
            self._pins.setdefault(key, set()).add(owner)
            self._publish_pinned_locked()

    def unpin(self, key, owner) -> None:
        with self._lock:
            owners = self._pins.get(key)
            if owners is not None:
                owners.discard(owner)
                if not owners:
                    self._pins.pop(key, None)
            self._publish_pinned_locked()

    def unpin_owner(self, owner) -> None:
        """Release every pin held by ``owner`` (standing-query
        unregister)."""
        with self._lock:
            for key in [k for k, o in self._pins.items() if owner in o]:
                self._pins[key].discard(owner)
                if not self._pins[key]:
                    self._pins.pop(key, None)
            self._publish_pinned_locked()

    def pinned_bytes(self) -> int:
        with self._lock:
            return self._pinned_bytes_locked()

    def _pinned_bytes_locked(self) -> int:
        return sum(v[2] for k, v in self._d.items() if k in self._pins)

    def _publish_pinned_locked(self) -> None:
        from ..metrics import REGISTRY

        REGISTRY.gauge("filodb_superblock_pinned_bytes").set(
            float(self._pinned_bytes_locked())
        )

    @staticmethod
    def _charge(value, nbytes: int) -> dict:
        """Bytes an entry pins on each device it is placed on: the even
        band split of a sharded block, else all of it on the default
        device."""
        mesh = getattr(getattr(value, "block", None), "placement", None)
        return mesh_device_bytes(mesh, nbytes) or {default_device_key(): nbytes}

    def device_budget(self, device: str | None = None) -> int:
        """Bytes this cache may pin on ``device`` (default: where
        un-sharded entries land)."""
        return device_cache_budget(SUPERBLOCK_DEVICE_SHARE, self.max_bytes,
                                   device)

    def _fits(self, used: int, used_dev: dict, nbytes: int, charge: dict) -> bool:
        return used + nbytes <= self.max_bytes and all(
            used_dev.get(d, 0) + b <= self.device_budget(d)
            for d, b in charge.items()
        )

    def put(self, key, versions: tuple, value, nbytes: int) -> None:
        charge = self._charge(value, nbytes)
        if not self._fits(0, {}, nbytes, charge):
            return  # never pin more device memory than the whole budget
        with self._lock:
            replaced = self._d.pop(key, None)
            if replaced is not None:
                self.ledger.free(replaced[2], reason="replace")
            used = sum(e[2] for e in self._d.values())
            used_dev: dict = {}
            for e in self._d.values():
                for d, b in self._charge(e[1], e[2]).items():
                    used_dev[d] = used_dev.get(d, 0) + b
            while self._d and (
                len(self._d) >= self.max_entries
                or not self._fits(used, used_dev, nbytes, charge)
            ):
                # evict in LRU order but never a pinned entry; when only
                # pinned entries remain, tolerate running over budget (the
                # standing set is deliberately small and bounded by its own
                # registration cap)
                ek = next((k for k in self._d if k not in self._pins), None)
                if ek is None:
                    break
                ev = self._d.pop(ek)
                self._meta.pop(ek, None)
                used -= ev[2]
                for d, b in self._charge(ev[1], ev[2]).items():
                    used_dev[d] -= b
                self.ledger.free(ev[2], reason="evict")
            self._d[key] = (versions, value, nbytes)
            self.ledger.alloc(nbytes)
            self._publish_pinned_locked()
            prev = self._meta.get(key)
            # sharded entries record their placement at put time (metadata
            # only — never touches device values): the sharding spec and
            # even per-device byte split feed /debug/superblocks and the
            # filodb_device_bytes{kind,device} gauges
            mesh = getattr(getattr(value, "block", None), "placement", None)
            self._meta[key] = {
                "created": time.time(),
                "hits": prev["hits"] if prev else 0,
                "last_outcome": prev["last_outcome"] if prev else None,
                "sharding": mesh_spec_str(mesh),
                "device_bytes": mesh_device_bytes(mesh, nbytes),
            }

    def snapshot(self) -> list[dict]:
        """Introspection view for /debug/superblocks: one dict per cached
        entry (key rendered, true device bytes, age, hits, last maintenance
        outcome, and the entry's scan accounting when it carries any)."""
        now = time.time()
        with self._lock:
            items = [(k, v, dict(self._meta.get(k) or {}),
                      k in self._pins)
                     for k, v in self._d.items()]
        out = []
        for key, (versions, value, nbytes), meta, pinned in items:
            entry = {
                "key": repr(key),
                "bytes": int(nbytes),
                "age_s": round(now - meta.get("created", now), 3),
                "hits": int(meta.get("hits", 0)),
                "last_outcome": meta.get("last_outcome"),
                "versions": list(versions),
                "sharding": meta.get("sharding"),
                "device_bytes": meta.get("device_bytes"),
                "pinned": bool(pinned),
            }
            block = getattr(value, "block", None)
            if block is not None:
                entry["series"] = int(getattr(value, "series", 0)
                                      or block.n_series)
                # .shape is metadata on both jax and numpy arrays — never
                # np.asarray here, that would pull the device block to host
                entry["shape"] = list(block.vals.shape)
                entry["is_hist"] = bool(getattr(value, "is_hist", False))
                entry["stage_mode"] = getattr(value, "stage_mode", None)
                entry["grid"] = grid_class(block)
                # where the value plane REALLY sits, off the array's own
                # shards (metadata, no transfer) — device_bytes above is
                # put()'s even split of the whole entry
                shards = getattr(block.vals, "addressable_shards", None)
                entry["vals_resident"] = None if shards is None else {
                    str(sh.device): int(sh.data.nbytes) for sh in shards}
            out.append(entry)
        return out

    def __len__(self) -> int:
        with self._lock:
            return len(self._d)


def _segment_table(shard, part_ids, column: str, t0: int, t1: int):
    """One row a chunk segment of the selection (``native.STAGE_SEG_COLS``),
    in block-row then time order, for ``native/stage.cpp``: per series only
    the partition's snapshot and the overlap test on each chunk's bounds; no
    array is read. Returns ``(table, bucket width, held)``, where ``held``
    keeps every array the table names alive (a chunk evicted or a buffer
    sealed meanwhile must not free memory the call reads), or None for a
    selection the pass does not take: arrays it cannot read in place
    (``ColumnArrays.segments`` has no entry for them), or a bucket width
    that differs between series."""
    tab, held = [], []
    width = 0
    for row, pid in enumerate(part_ids):
        for arrays, buf_len, clamp in shard.partition(int(pid)) \
                .segments_in_range(t0, t1, column):
            seg = arrays.segments.get(column)
            if seg is None:
                return None
            ts_addr, v_addr, rows, v_width, ints = seg
            if v_width != width:
                if width:
                    return None
                width = v_width
            flags = native.STAGE_INT_VALUES if ints else 0
            if buf_len is not None:  # the write buffer, up to its snapshot
                rows = min(buf_len, rows)
                flags |= native.STAGE_GATED
            tab.append((row, ts_addr, v_addr, rows, clamp, flags, 0, 0))
            held.append(arrays)
    if not tab:
        return None
    return np.array(tab, dtype=np.int64), width, held


def _stage_histograms(shard, part_ids, column: str, start_ms: int,
                      end_ms: int, mode: str, dtype):
    """The block ``stage_histogram_series`` makes of a histogram selection,
    bit for bit, in one pass over the shard's chunks: a table of segments
    (``stage:gather``) and one native call that searches, casts and pads
    (``stage:assemble``), instead of a ``(ts, vals)`` pair a series and a
    second loop over them. None where the pass does not apply (a scalar
    column, no library, arrays it does not read in place, nothing in
    range): the caller's Python tier stages those."""
    L = native.stage_lib()
    if L is None or np.dtype(dtype) != np.float32:
        return None
    try:
        ctype = shard.partition(int(part_ids[0])).schema.column(column).ctype
    except KeyError:
        return None
    if ctype != ColumnType.HISTOGRAM:
        return None
    with span("stage:gather", part="gather"):
        made = _segment_table(shard, part_ids, column, start_ms, end_ms)
    if made is None:
        return None
    table, n_buckets, held = made
    n = len(part_ids)
    S = pad_series(n)
    with span("stage:assemble", part="assemble"):
        lens, longest = native.stage_measure(L, table, start_ms, end_ms, S)
        if longest == 0:
            return None  # the Python tier knows an empty selection's width
        T = pad_time(longest)
        out_ts, out_vals, baseline = native.stage_fill(
            L, table, lens, T, n_buckets, start_ms,
            mode in ("corrected", "shifted"))
        del held  # the call has returned
        regular, nominal, ts_dev, maxdev = detect_shared_grid(
            out_ts, lens, n, T, S)
        refs = [(shard.shard_num, int(pid)) for pid in part_ids]
        return StagedBlock(out_ts, out_vals, lens, start_ms, baseline, n,
                           refs, regular_ts=regular, nominal_ts=nominal,
                           ts_dev=ts_dev, maxdev_ms=maxdev)


def stage_from_shard(
    shard,
    part_ids,
    column: str,
    start_ms: int,
    end_ms: int,
    is_counter: bool = False,
    dtype=np.float32,
    mode: str | None = None,
) -> StagedBlock:
    """Gather [start_ms, end_ms] samples for part_ids from a shard and stage.

    ``mode`` selects the counter staging strategy (function-driven — the
    reference applies counter correction only inside rate-family
    RangeFunctions, never at the read path):

    - ``"corrected"`` — reset-corrected minus baseline (rate/increase/irate)
    - ``"shifted"``   — raw minus per-series baseline, NO reset correction:
      exact f32 for shift-invariant functions (delta/deriv/stddev...) even on
      1e15-magnitude counters
    - ``"diff"``      — f64-exact adjacent differences (changes/resets/idelta)
    - ``"raw"``       — plain raw values (value-returning functions: a plain
      selector, last/min/max/sum_over_time, quantile...)

    When mode is None, is_counter=True maps to "corrected" (legacy callers
    that only ever stage for rate-family kernels).
    """
    if mode is None:
        mode = "corrected" if is_counter else "raw"
    if len(part_ids):
        block = _stage_histograms(shard, part_ids, column, start_ms, end_ms,
                                  mode, dtype)
        REGISTRY.counter(
            "filodb_stage_gather_series",
            how="python" if block is None else "native").inc(len(part_ids))
        if block is not None:
            return block
    series = []
    refs = []
    hist_width = None
    with span("stage:gather", part="gather"):
        for pid in part_ids:
            part = shard.partition(int(pid))
            ts, vals = part.samples_in_range(start_ms, end_ms, column)
            if vals.ndim == 2:
                hist_width = vals.shape[1]
            series.append((ts, vals))
            refs.append((shard.shard_num, int(pid)))
    if hist_width is not None:
        with span("stage:assemble", part="assemble"):
            return stage_histogram_series(
                series, start_ms, hist_width, refs,
                subtract_baseline=mode in ("corrected", "shifted"),
                dtype=dtype,
            )

    newest = max((int(ts[-1]) for ts, _ in series if len(ts)), default=None)

    def _stage(sr):
        # modest time headroom on small-to-medium LIVE-EDGE blocks (range
        # reaches past the newest sample): append repairs then absorb many
        # scrapes before the padded width forces a full re-stage. Purely
        # historical ranges never repair, so they never pay the wider T.
        live_edge = newest is not None and end_ms >= newest
        headroom = 256 if (live_edge and len(sr) <= 8192) else 0
        with span("stage:assemble", part="assemble"):
            return stage_series(
                sr, start_ms, refs,
                counter_corrected=mode == "corrected",
                subtract_baseline=mode == "shifted",
                diff_encode=mode == "diff",
                dtype=dtype,
                time_headroom=headroom,
            )

    block = _stage(series)
    if (
        block.regular_ts is None and block.nominal_ts is None
        and block.n_series > 1
    ):
        with span("stage:gather", part="gather"):
            aligned = _slot_align(shard, part_ids, column, series, start_ms,
                                  end_ms)
        if aligned is not None:
            block = _stage(aligned)
    return block


# kernel-observatory registration (obs/kernels.py; linted by
# tools/check_metrics.py — every jit wrapper here must register)
def _register_kernel_observatory() -> None:
    from ..obs.kernels import KERNELS

    KERNELS.register_jits("ops.staging", assemble_rows=assemble_rows)


_register_kernel_observatory()
