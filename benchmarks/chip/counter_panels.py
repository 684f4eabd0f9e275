"""Reference ``counter_panels``: the counter deployment's dashboard panels in
plain numpy f64, PromQL semantics. Copied from ``chip_smoke.py`` ``o_rate`` /
``o_irate`` / ``o_avg_over_time`` / ``nansum0`` (PR 21), cut to the shared
scrape grid of ``regular_counters``; nothing here imports the program or
takes anything it has made.

``reference(data, out_t, window_ms, panel, quantize=None)`` answers
``<agg> [by (<by>)] (<fn>(metric[w]))`` for ``panel["fn"]`` in ``rate``,
``irate``, ``avg_over_time`` and ``panel["agg"]`` in ``sum``, ``avg``, and
returns ``{frozenset(label items): [J] f64 row, NaN = absent}``.

``quantize`` is the control's hook (``control.py``), applied where the
program stages its values: each series' readings as offsets from its first
sample (reset-corrected for the rate family, as the program stages them;
raw for ``avg_over_time``). The first sample itself and all arithmetic
after the staging stay f64, so the control reads the least that staging in
fewer bits could cost.
"""

from __future__ import annotations

import numpy as np

CHUNK = 10_000  # series at a time: bounds the [s, T] and [s, J] temporaries


def reset_corrected(vals: np.ndarray) -> np.ndarray:
    drop = np.where(np.diff(vals, axis=1) < 0, vals[:, :-1], 0.0)
    out = vals.copy()
    out[:, 1:] += np.cumsum(drop, axis=1)
    return out


def rate_factor(tf_ms, tl_ms, cnt, out_t, window_ms, delta, first_raw):
    """promql extrapolatedRate over (t - w, t] as the factor that turns a
    window's increase into its rate, with the counter rule that a series is
    not extrapolated back past where it would have been zero. tf/tl/cnt are
    [J]; delta and first_raw [S, J]. The caller masks windows with fewer
    than 2 samples."""
    sampled = ((tl_ms - tf_ms) / 1e3)[None, :]
    dur_start = ((tf_ms - (out_t - window_ms)) / 1e3)[None, :]
    dur_end = ((out_t - tl_ms) / 1e3)[None, :]
    avg = sampled / np.maximum(cnt - 1, 1)[None, :]
    with np.errstate(divide="ignore", invalid="ignore"):
        dur_start = np.minimum(dur_start, np.where(
            (delta > 0) & (first_raw >= 0), sampled * first_raw / delta, np.inf))
        dur_start = np.where(dur_start >= avg * 1.1, avg / 2, dur_start)
        dur_end = np.where(dur_end >= avg * 1.1, avg / 2, dur_end)
        return (sampled + dur_start + dur_end) / sampled / (window_ms / 1e3)


def _series_grid(fn, vals, ts, out_t, window_ms, quantize):
    """[s, J] values of ``fn`` for one chunk of series, NaN = absent."""
    T = len(ts)
    hi = np.searchsorted(ts, out_t, side="right")
    lo = np.searchsorted(ts, out_t - window_ms, side="right")
    cnt = hi - lo
    lo_c, last, prev = (np.clip(i, 0, T - 1) for i in (lo, hi - 1, hi - 2))
    q = quantize if quantize is not None else (lambda x: x)
    if fn == "avg_over_time":
        off = q(vals - vals[:, :1])
        p = np.concatenate([np.zeros((len(vals), 1)), np.cumsum(off, axis=1)], axis=1)
        with np.errstate(divide="ignore", invalid="ignore"):
            r = (p[:, hi] - p[:, lo]) / cnt[None, :] + vals[:, :1]
        return np.where(cnt[None, :] > 0, r, np.nan)
    c = reset_corrected(vals)
    off = q(c - c[:, :1])
    if fn == "irate":
        dt = (ts[last] - ts[prev]) / 1e3
        with np.errstate(divide="ignore", invalid="ignore"):
            r = (off[:, last] - off[:, prev]) / dt[None, :]
        return np.where(cnt[None, :] >= 2, r, np.nan)
    if fn == "rate":
        delta = off[:, last] - off[:, lo_c]
        k = rate_factor(ts[lo_c].astype(np.float64), ts[last].astype(np.float64),
                        cnt, out_t, window_ms, delta, vals[:, lo_c])
        with np.errstate(invalid="ignore"):  # 0 x inf in windows masked below
            return np.where(cnt[None, :] >= 2, delta * k, np.nan)
    raise ValueError(f"counter_panels: unknown fn {fn!r}")


def reference(data, out_t, window_ms, panel, quantize=None):
    by = list(panel.get("by", ()))
    keys = [frozenset((k, t[k]) for k in by) for t in data.tags]
    groups = {k: g for g, k in enumerate(dict.fromkeys(keys))}
    gids = np.array([groups[k] for k in keys])
    total = np.zeros((len(groups), len(out_t)))
    count = np.zeros((len(groups), len(out_t)))
    for b0 in range(0, data.n_series, CHUNK):
        sj = _series_grid(panel["fn"], data.vals[b0:b0 + CHUNK], data.ts, out_t,
                          window_ms, quantize)
        has = ~np.isnan(sj)
        np.add.at(total, gids[b0:b0 + CHUNK], np.where(has, sj, 0.0))
        np.add.at(count, gids[b0:b0 + CHUNK], has)
    if panel["agg"] == "avg":
        with np.errstate(divide="ignore", invalid="ignore"):
            total = total / count
    elif panel["agg"] != "sum":
        raise ValueError(f"counter_panels: unknown agg {panel['agg']!r}")
    # PromQL sum/avg over series: NaN = absent; all-absent step = NaN
    return {k: np.where(count[g] > 0, total[g], np.nan) for k, g in groups.items()}
