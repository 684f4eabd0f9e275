"""Histogram bucket schemes (reference L0 filodb.memory format/vectors/
Histogram.scala:609-899 — Geometric, Custom, Base2Exponential schemes;
quantile/fraction math at :64-130 moves to ops/kernels.py on device).

A histogram sample is a vector of cumulative bucket counts aligned to a
bucket scheme; the top bucket is +Inf. Native histograms are first-class:
chunks store ``[T, B]`` count arrays (ideal TPU layout), and
histogram_quantile runs as a vectorized kernel over ``[S, T, B]`` blocks.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class BucketScheme:
    """Bucket upper bounds (``le`` values), last = +inf."""

    les: tuple[float, ...]

    @property
    def num_buckets(self) -> int:
        return len(self.les)

    def bounds(self) -> np.ndarray:
        return np.asarray(self.les, dtype=np.float64)


def custom_buckets(les) -> BucketScheme:
    les = tuple(float(x) for x in les)
    if les[-1] != np.inf:
        les = les + (np.inf,)
    return BucketScheme(les)


def geometric_buckets(first: float, multiplier: float, num: int) -> BucketScheme:
    """reference GeometricBuckets (Histogram.scala:609)."""
    les = tuple(first * multiplier**i for i in range(num)) + (np.inf,)
    return BucketScheme(les)


@functools.lru_cache(maxsize=1 << 16)  # immutable: one a (scale, offset, n)
def base2_exp_buckets(scale: int, start_index: int, num: int) -> BucketScheme:
    """OTel base-2 exponential scheme (reference Base2ExpHistogramBuckets,
    Histogram.scala:684): bucket i upper bound = 2^((start+i+1) * 2^-scale),
    with a zero bucket first."""
    base = 2.0 ** (2.0**-scale)
    les = (0.0,) + tuple(base ** (start_index + i + 1) for i in range(num)) + (np.inf,)
    return BucketScheme(les)


# -- base-2 exponential schemes as a first-class value ------------------------
#
# OTel's ExponentialHistogram (and Prometheus' native histograms) give each
# series a scale and an offset. Bucket i covers (b^(offset+i), b^(offset+i+1)]
# with b = 2^(2^-scale); the zero bucket is [0, 0]. Counts are stored
# cumulatively on ``Base2Scheme.les()`` = (0, b^(offset+1), ..., b^(offset+n),
# +Inf): column 0 is the zero bucket, column n + 1 the total. Sums follow the
# data model's rule: every histogram is downscaled to the smallest scale
# present (index >> d: exact for cumulative counts, the coarse bound K is the
# fine bound K * 2^d) and the index ranges are joined. The fused program
# (ops/aggregations._base2_epilogue) and the reference tree (plans
# _partial_aggregate / _merge_partials) both apply it through the helpers
# below, the host one in f64 and the device one in f32 on the same indices.
# The store applies the same rule to one series over time: a partition's
# scheme is the join of every scheme its batches brought (an SDK's range
# grows and its scale falls as new extremes arrive), fit to 160 buckets;
# each chunk keeps the scheme it was written on, and a read puts it on the
# partition's (memstore/partition.py). A point finer than the join (a delta
# exporter's interval that saw a narrow range) is merged down onto it at
# ingest, exactly: whole counts add.

BASE2_MAX_BUCKETS = 160  # the OTel SDK's default MaxSize
BASE2_WIDTH = BASE2_MAX_BUCKETS + 2  # + the zero column and the +Inf column


@dataclass(frozen=True)
class Base2Scheme:
    """``(scale, offset, n)``: n buckets from index ``offset`` at ``scale``."""

    scale: int
    offset: int
    n: int

    @property
    def width(self) -> int:
        """Columns of its cumulative counts: zero bucket, n bounds, +Inf."""
        return self.n + 2

    def les(self) -> np.ndarray:
        return np.asarray(
            base2_exp_buckets(self.scale, self.offset, self.n).les, np.float64)


def merge_base2(schemes) -> Base2Scheme:
    """The scheme a sum of histograms on ``schemes`` lands on: the smallest
    scale, and the join of every index range downscaled to it."""
    schemes = list(schemes)
    scale = min(s.scale for s in schemes)
    lo = hi = None
    for s in schemes:
        if s.n <= 0:
            continue
        d = s.scale - scale
        a, b = s.offset >> d, (s.offset + s.n - 1) >> d
        lo = a if lo is None else min(lo, a)
        hi = b if hi is None else max(hi, b)
    if lo is None:
        return Base2Scheme(scale, 0, 0)
    return Base2Scheme(scale, lo, hi - lo + 1)


def fit_base2(s: Base2Scheme, max_buckets: int = BASE2_MAX_BUCKETS) -> Base2Scheme:
    """``s`` downscaled until its range fits ``max_buckets``, as the SDK's
    aggregator downscales when a new value would not fit."""
    lo, hi = s.offset, s.offset + s.n - 1
    scale = s.scale
    while hi - lo + 1 > max_buckets:
        scale, lo, hi = scale - 1, lo >> 1, hi >> 1
    return s if scale == s.scale else Base2Scheme(scale, lo, hi - lo + 1)


def base2_covers(dst: Base2Scheme, src: Base2Scheme) -> bool:
    """True when counts on ``src`` can be put on ``dst`` exactly and lose no
    bucket: ``dst`` is no finer, and its range holds ``src``'s."""
    return dst.scale <= src.scale and merge_base2([dst, src]) == dst


def base2_index(src: Base2Scheme, dst: Base2Scheme, width: int | None = None) -> np.ndarray:
    """For each of ``width`` (default ``dst.width``) columns of ``dst``, the
    column of ``src``'s cumulative counts that holds the same count: the zero
    column for the zero bucket, ``clip(K * 2^d - src.offset, 0, src.n)`` for
    the bound b_dst^K (below src's first bucket: its zero count; above its
    last: its total), and src's +Inf column from dst's +Inf on (the padding
    a wider array carries). ``dst.scale`` must not exceed ``src.scale``."""
    return Base2Rows([src.scale], [src.offset], [src.n]).index(dst, width)[0]


def base2_remap(arr: np.ndarray, src: Base2Scheme, dst: Base2Scheme,
                width: int | None = None) -> np.ndarray:
    """``arr[..., >= src.width]`` cumulative counts on ``src`` ->
    ``[..., width or dst.width]`` on ``dst`` (columns past ``dst.n + 1``
    repeat the total)."""
    return np.asarray(arr)[..., base2_index(src, dst, width)]


class Base2Rows:
    """A base-2 scheme a record, in bulk: ``scale``, ``offset``, ``n`` [N]
    int64. ``RecordBatch.bucket_schemes``' form for a stream that sends a
    scheme a point — an OTel exporter under DELTA temporality resets its
    aggregator at each collection, so each point sits at the finest scale
    its own interval fits and the scale rises and falls from point to
    point. Indexing with rows (a slice or an index array) gives theirs."""

    __slots__ = ("scale", "offset", "n")

    def __init__(self, scale, offset, n):
        self.scale, self.offset, self.n = (
            np.asarray(a, np.int64) for a in (scale, offset, n))

    def __len__(self) -> int:
        return len(self.scale)

    def __getitem__(self, rows) -> "Base2Rows":
        return Base2Rows(self.scale[rows], self.offset[rows], self.n[rows])

    def single(self) -> Base2Scheme | None:
        """The one scheme every row is on, else None."""
        first = (self.scale[0], self.offset[0], self.n[0])
        if all((a == a[0]).all() for a in (self.scale, self.offset, self.n)):
            return Base2Scheme(*(int(x) for x in first))
        return None

    def join(self) -> Base2Scheme:
        """``fit_base2(merge_base2(rows))``: the scheme that holds every row."""
        scale = int(self.scale.min())
        has = self.n > 0
        if not has.any():
            return Base2Scheme(scale, 0, 0)
        d = self.scale[has] - scale
        lo = int((self.offset[has] >> d).min())
        hi = int(((self.offset[has] + self.n[has] - 1) >> d).max())
        return fit_base2(Base2Scheme(scale, lo, hi - lo + 1))

    def index(self, dst: Base2Scheme, width: int | None = None) -> np.ndarray:
        """[N, width or dst.width] ``base2_index`` of each row onto ``dst``,
        which holds every row: take it along the columns of the rows'
        counts."""
        d = self.scale - dst.scale
        if (d < 0).any():
            raise ValueError(f"cannot upscale a row onto {dst}")
        k = np.arange(dst.width if width is None else width, dtype=np.int64)[None, :]
        fine = (dst.offset + k) * np.left_shift(1, d)[:, None]
        idx = np.clip(fine - self.offset[:, None], 0, self.n[:, None])
        idx[:, 0] = 0
        return np.where(k > dst.n, (self.n + 1)[:, None], idx)


def base2_les_rows(schemes, width: int) -> np.ndarray:
    """``[R, width]`` f64 bounds of ``schemes``, a row each: 0, b^(offset+1)
    .. b^(offset+n), then +Inf from the row's own +Inf column on."""
    out = np.full((len(schemes), width), np.inf)
    for r, s in enumerate(schemes):
        out[r, : s.n + 1] = s.les()[:-1]
    return out


def base2_family(schemes) -> bool:
    """True when every entry is a Base2Scheme that fits the staged width."""
    return all(isinstance(s, Base2Scheme) and 0 <= s.n <= BASE2_MAX_BUCKETS
               for s in schemes)


# The reference's default Prometheus-style scheme used by test fixtures
PROM_DEFAULT = custom_buckets(
    [0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10]
)


# -- bucket-scheme unification (heterogeneous schemes across shards) --------
#
# The reference resizes histograms onto a common scheme before HistSum
# (Histogram.scala HistogramWithBuckets add/convert); here the same
# unification runs host-side on [.., B]-shaped cumulative count arrays so
# BOTH aggregation paths — the fused superblock concat and the reference
# partial-merge — share one definition and stay numerically identical.

_LE_TOL = 1e-10  # same bound-match tolerance as histogram_bucket selection


def same_scheme(a, b) -> bool:
    """True when two ``le`` bound vectors describe the same bucket scheme:
    equal length, every bound within _LE_TOL (equal +Inf top buckets
    match). The ONE equality rule for every fused/reference unification
    site — keep them on this helper so the tolerance can't drift apart."""
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    if len(a) != len(b):
        return False
    with np.errstate(invalid="ignore"):
        diff = np.abs(a - b)  # inf - inf -> nan: equal infinite tops match
    return not (diff > _LE_TOL).any()


def union_les(les_list) -> np.ndarray:
    """Union bucket scheme of several ``le`` bound vectors: sorted unique
    finite bounds (within _LE_TOL) plus the +Inf top bucket every scheme
    carries."""
    bounds: list[float] = []
    for les in les_list:
        for x in np.asarray(les, dtype=np.float64):
            if np.isinf(x):
                continue
            if not any(abs(x - b) < _LE_TOL for b in bounds):
                bounds.append(float(x))
    return np.asarray(sorted(bounds) + [np.inf], dtype=np.float64)


def bucket_mapping(src_les, dst_les) -> np.ndarray:
    """For each dst bound, the index of the matching src bound, or the
    largest src bound strictly below it (-1 when none). Cumulative counts
    at a bound a scheme doesn't carry take the count of the nearest LOWER
    bound it does (0 below the first): the exact lower-bound completion of
    a cumulative distribution, and monotone by construction."""
    src = np.asarray(src_les, dtype=np.float64)
    out = np.empty(len(dst_les), dtype=np.int64)
    for i, x in enumerate(np.asarray(dst_les, dtype=np.float64)):
        hit = np.nonzero(
            np.isclose(src, x, rtol=0.0, atol=_LE_TOL)
            | (np.isinf(src) & np.isinf([x] * len(src)))
        )[0]
        if len(hit):
            out[i] = hit[0]
        else:
            below = np.nonzero(src < x - _LE_TOL)[0]
            out[i] = below[-1] if len(below) else -1
    return out


def unify_schemes(arrays, les_list):
    """Remap several [..., B_i]-shaped cumulative-count arrays onto the
    union of their bucket schemes (union_les + remap_buckets — the ONE
    unification rule, shared by the fused superblock concat and both
    reference partial-merge sites). Returns (arrays', union, changed);
    arrays already on the union scheme pass through as the SAME objects,
    and changed=False means every one did."""
    les64 = [np.asarray(l, dtype=np.float64) for l in les_list]
    union = union_les(les64)
    out = [remap_buckets(a, l, union) for a, l in zip(arrays, les64)]
    changed = any(o is not a for o, a in zip(out, arrays))
    return out, union, changed


def remap_buckets(arr: np.ndarray, src_les, dst_les) -> np.ndarray:
    """Remap an [..., B_src] cumulative-count array onto ``dst_les``:
    matching bounds copy through, missing bounds take the nearest lower
    bound's count (0 when below the scheme's first bound). Exact identity
    when the schemes already agree."""
    src = np.asarray(src_les, dtype=np.float64)
    dst = np.asarray(dst_les, dtype=np.float64)
    if len(src) == len(dst) and np.allclose(
        src[:-1], dst[:-1], rtol=0.0, atol=_LE_TOL
    ):
        return arr
    m = bucket_mapping(src, dst)
    a = np.asarray(arr)
    out = np.zeros(a.shape[:-1] + (len(dst),), dtype=a.dtype)
    have = m >= 0
    out[..., have] = a[..., m[have]]
    return out
