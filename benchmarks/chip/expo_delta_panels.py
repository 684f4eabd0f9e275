"""Reference ``expo_delta_panels``: ``histogram_quantile(q, sum by (<by>, le)
(rate(m_bucket[w])))`` over base-2 exponential histograms exported under
DELTA temporality, a scheme a point (``expo_delta_histograms``), in plain
numpy f64:

- each series on ONE scheme, the join of the schemes of every point it sent:
  the smallest scale, the joined index range, downscaled until it fits 160
  buckets (``series_join``; the store's rule, as ``expo_panels`` follows
  it); each point's whole counts put on it (``expo_panels.on_scheme``: the
  count at a coarse bound is the count at the fine bound it covers, exact
  for counts cumulative over the buckets);
- ``rate(h[w])`` at step t: per bucket, the sum of the points with timestamp
  in (t - w, t], divided by w in seconds. No extrapolation: a delta point
  holds its own interval, so the window's sum is its increase (the
  program's delta rule, ``ops/hist_kernels``, and FiloDB's rate over delta
  columns, cited from memory); absent where the window holds no point;
- each group's scheme (the smallest scale among its series, their joined
  range), the sum and the linear-interpolation quantile: ``expo_panels``'.

Departures, as the configuration's ``guarantees`` state them: Prometheus
keeps no delta temporality (its OTLP receiver takes cumulative series) and
interpolates a native bucket exponentially; this system sums delta points
over the window with no extrapolation and interpolates linearly, and so
does this reference. Nothing here imports the program or takes anything it
has made. ``quantize`` is the control's hook (``control.py``), applied where
a lower precision would round the program's whole counts: each series'
running sum of its points at its scheme's columns, whose differences are
the window sums.

``reference(data, out_t, window_ms, panel, quantize=None)`` returns
``{frozenset(label items): [J] f64 row, NaN = absent}``.
"""

from __future__ import annotations

import numpy as np

from benchmarks.chip.expo_panels import bounds, group_schemes, on_scheme

CHUNK = 100  # series at a time: bounds the [s, T, B] temporaries
MAX_BUCKETS = 160


def series_join(scale, offset, n, max_buckets: int = MAX_BUCKETS):
    """[S] (scale, offset, n) of each series' stored scheme from its points'
    [S, T] schemes: the smallest scale, the join of the index ranges
    downscaled to it (a point with no bucket adds no range), downscaled
    again while it holds more than ``max_buckets``."""
    scale, offset, n = (np.asarray(a, np.int64) for a in (scale, offset, n))
    s = scale.min(axis=1)
    d = scale - s[:, None]
    has = n > 0
    big = np.iinfo(np.int64).max
    lo = np.where(has, offset >> d, big).min(axis=1)
    hi = np.where(has, (offset + n - 1) >> d, -big).max(axis=1)
    empty = ~has.any(axis=1)
    lo, hi = np.where(empty, 0, lo), np.where(empty, -1, hi)
    while True:
        wide = hi - lo + 1 > max_buckets
        if not wide.any():
            return s, lo, hi - lo + 1
        s, lo, hi = s - wide, lo >> wide, hi >> wide


def window_sums(data, out_t, window_ms, quantize=None) -> np.ndarray:
    """[S, J, B] f64: each series' sum of the points in each window, at its
    stored scheme's columns (every point put on it first)."""
    scale = np.asarray(data.scale, np.int64)
    offset = np.asarray(data.offset, np.int64)
    n = np.asarray(data.width, np.int64) - 2
    hi = np.searchsorted(data.ts, out_t, side="right")
    lo = np.searchsorted(data.ts, out_t - window_ms, side="right")
    out = np.empty((data.n_series, len(out_t), data.hist.shape[2]))
    for b0 in range(0, data.n_series, CHUNK):
        rows = slice(b0, b0 + CHUNK)
        h = on_scheme(data.hist[rows].astype(np.float64),
                      data.row_scale[rows].astype(np.int64),
                      data.row_offset[rows].astype(np.int64),
                      data.row_width[rows].astype(np.int64) - 2,
                      scale[rows], offset[rows], n[rows])
        run = np.concatenate([np.zeros_like(h[:, :1]), np.cumsum(h, axis=1)], axis=1)
        if quantize is not None:
            run = quantize(run)
        out[rows] = run[:, hi] - run[:, lo]
    return out


def reference(data, out_t, window_ms, panel, quantize=None):
    q = float(panel["q"])
    by = [k for k in panel.get("by", ()) if k != "le"]
    keys = [frozenset((k, t[k]) for k in by) for t in data.tags]
    groups = {k: g for g, k in enumerate(dict.fromkeys(keys))}
    gids = np.array([groups[k] for k in keys])
    G = len(groups)
    scale = np.asarray(data.scale, np.int64)
    offset = np.asarray(data.offset, np.int64)
    n = np.asarray(data.width, np.int64) - 2
    s_g, o_g, k_g = group_schemes(scale, offset, n, gids, G)
    W = int(k_g.max()) + 2
    if quantize is None:  # every panel of a range reads the same sums
        key = (out_t.tobytes(), int(window_ms))
        memo = data.__dict__.setdefault("_window_sums", {})
        if key not in memo:
            memo.clear()
            memo[key] = window_sums(data, out_t, window_ms)
        win = memo[key]
    else:
        win = window_sums(data, out_t, window_ms, quantize)
    J = len(out_t)
    bsum = np.zeros((G, J, W))
    k = np.arange(W)[None, :]
    for b0 in range(0, data.n_series, CHUNK):
        rows = slice(b0, b0 + CHUNK)
        g = gids[rows]
        d = scale[rows] - s_g[g]
        fine = (o_g[g][:, None] + k) * (2 ** d)[:, None]
        idx = np.clip(fine - offset[rows][:, None], 0, n[rows][:, None])
        idx = np.where(k == 0, 0, np.where(k > k_g[g][:, None],
                                           n[rows][:, None] + 1, idx))
        on_group = np.take_along_axis(win[rows], idx[:, None, :], axis=2)
        for gi in np.unique(g):
            bsum[gi] += on_group[g == gi].sum(0)
    bsum /= window_ms / 1e3
    hi = np.searchsorted(data.ts, out_t, side="right")
    lo = np.searchsorted(data.ts, out_t - window_ms, side="right")
    bsum[:, hi - lo < 1] = np.nan
    les = bounds(s_g, o_g, k_g, W)
    out = {}
    steps = np.arange(J)
    for key, gi in groups.items():
        b = bsum[gi]
        total = b[:, -1]
        rank = q * total
        meets = b >= rank[:, None]
        idx = np.where(meets.any(1), np.argmax(meets, axis=1), W - 1)
        c_hi = b[steps, idx]
        c_lo = np.where(idx > 0, b[steps, np.maximum(idx - 1, 0)], 0.0)
        le_hi = les[gi, idx]
        le_lo = np.where(idx > 0, les[gi, np.maximum(idx - 1, 0)], 0.0)
        with np.errstate(divide="ignore", invalid="ignore"):
            val = le_lo + (le_hi - le_lo) * (rank - c_lo) / (c_hi - c_lo)
        val = np.where(np.isinf(le_hi), le_lo, val)
        out[key] = np.where(total > 0, val, np.nan)
    return out
