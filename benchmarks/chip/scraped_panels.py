"""Reference ``scraped_panels``: the counter dashboard's panels over a fleet
in which every series has its own clock (``scraped_counters``), in plain
numpy f64, PromQL semantics written out per series on its OWN timestamps:
window membership ``(t - w, t]``, the window's count, first, last and
previous sample, ``extrapolatedRate`` with the counter's rule at zero,
reset correction over the samples that exist, ``irate`` from the last two
samples of the window, ``avg_over_time`` over the samples actually in it,
``sum`` / ``avg`` over the series present (NaN = absent). A missed scrape is
no sample and a late one keeps its own time. Nothing here imports the
program or takes anything it has made.

``reference(data, out_t, window_ms, panel, quantize=None)`` answers
``<agg> [by (<by>)] (<fn>(metric[w]))`` for ``panel["fn"]`` in ``rate``,
``irate``, ``avg_over_time`` and ``panel["agg"]`` in ``sum``, ``avg``, and
returns ``{frozenset(label items): [J] f64 row, NaN = absent}``. ``data`` is
a ``ScrapedSet``: ``ts`` / ``vals`` [S, T] with each row's ``lens`` real
samples at its front. The series go through in blocks, so 100 000 x 720
samples x 114 steps fit the host.

Each step of the arithmetic is ``counter_panels``' in the same order, with
[s, J] window edges where that one has [J]: on a fleet whose every phase is
0, with nothing late and nothing missed, the two agree to the last bit
(tier-1 holds them to it). ``quantize`` is the control's hook
(``control.py``), applied as there: to each series' readings as offsets from
its first sample (reset-corrected for the rate family, as the program stages
them; raw for ``avg_over_time``); all else stays f64.
"""

from __future__ import annotations

import numpy as np

from benchmarks.chip.counter_panels import reset_corrected

CHUNK = 5_000   # series at a time: bounds the [s, T] and [s, J] temporaries
ROW = 1 << 42   # rows apart in the one sorted key: far above any ms offset


def counts_le(ts, lens, edges):
    """[s, J]: each row's real samples at or before each edge. The rows'
    increasing timestamps become ONE sorted key (row number in the high
    bits, a lane behind ``lens`` last in its row), so one ``searchsorted``
    answers every (row, edge)."""
    s, T = ts.shape
    real = np.arange(T)[None, :] < lens[:, None]
    t_min = min(int(ts[real].min()) if real.any() else 0, int(edges.min()))
    rows = np.arange(s, dtype=np.int64)[:, None] * ROW
    key = np.where(real, ts - t_min, ROW - 1) + rows
    want = (edges - t_min)[None, :] + rows
    return (np.searchsorted(key.ravel(), want.ravel(), side="right")
            .reshape(s, -1) - np.arange(s)[:, None] * T)


def _series_grid(fn, ts, vals, lens, out_t, window_ms, quantize):
    """[s, J] values of ``fn`` for one block of series, NaN = absent."""
    T = ts.shape[1]
    hi = counts_le(ts, lens, out_t)
    lo = counts_le(ts, lens, out_t - window_ms)  # at or before t - w: outside
    cnt = hi - lo
    lo_c, last, prev = (np.clip(i, 0, T - 1) for i in (lo, hi - 1, hi - 2))
    q = quantize if quantize is not None else (lambda x: x)
    at = lambda a, i: np.take_along_axis(a, i, axis=1)  # noqa: E731
    if fn == "avg_over_time":
        off = q(vals - vals[:, :1])
        p = np.concatenate([np.zeros((len(vals), 1)), np.cumsum(off, axis=1)], axis=1)
        with np.errstate(divide="ignore", invalid="ignore"):
            r = (at(p, hi) - at(p, lo)) / cnt + vals[:, :1]
        return np.where(cnt > 0, r, np.nan)
    c = reset_corrected(vals)
    off = q(c - c[:, :1])
    if fn == "irate":
        dt = (at(ts, last) - at(ts, prev)) / 1e3
        with np.errstate(divide="ignore", invalid="ignore"):
            r = (at(off, last) - at(off, prev)) / dt
        return np.where(cnt >= 2, r, np.nan)
    if fn == "rate":
        # promql extrapolatedRate over (t - w, t]: the window's increase,
        # stretched to the window's ends where the samples come near them,
        # a counter never further back than where it would have read zero
        delta = at(off, last) - at(off, lo_c)
        first_raw = at(vals, lo_c)
        tf, tl = at(ts, lo_c).astype(np.float64), at(ts, last).astype(np.float64)
        sampled = (tl - tf) / 1e3
        dur_start = (tf - (out_t - window_ms)[None, :]) / 1e3
        dur_end = (out_t[None, :] - tl) / 1e3
        avg = sampled / np.maximum(cnt - 1, 1)
        with np.errstate(divide="ignore", invalid="ignore"):
            dur_start = np.minimum(dur_start, np.where(
                (delta > 0) & (first_raw >= 0), sampled * first_raw / delta, np.inf))
            dur_start = np.where(dur_start >= avg * 1.1, avg / 2, dur_start)
            dur_end = np.where(dur_end >= avg * 1.1, avg / 2, dur_end)
            k = (sampled + dur_start + dur_end) / sampled / (window_ms / 1e3)
            return np.where(cnt >= 2, delta * k, np.nan)
    raise ValueError(f"scraped_panels: unknown fn {fn!r}")


def reference(data, out_t, window_ms, panel, quantize=None):
    by = list(panel.get("by", ()))
    keys = [frozenset((k, t[k]) for k in by) for t in data.tags]
    groups = {k: g for g, k in enumerate(dict.fromkeys(keys))}
    gids = np.array([groups[k] for k in keys])
    out_t = np.asarray(out_t, dtype=np.int64)
    total = np.zeros((len(groups), len(out_t)))
    count = np.zeros((len(groups), len(out_t)))
    for b0 in range(0, data.n_series, CHUNK):
        rows = slice(b0, b0 + CHUNK)
        sj = _series_grid(panel["fn"], data.ts[rows], data.vals[rows],
                          data.lens[rows], out_t, window_ms, quantize)
        has = ~np.isnan(sj)
        np.add.at(total, gids[rows], np.where(has, sj, 0.0))
        np.add.at(count, gids[rows], has)
    if panel["agg"] == "avg":
        with np.errstate(divide="ignore", invalid="ignore"):
            total = total / count
    elif panel["agg"] != "sum":
        raise ValueError(f"scraped_panels: unknown agg {panel['agg']!r}")
    # PromQL sum/avg over series: NaN = absent; all-absent step = NaN
    return {k: np.where(count[g] > 0, total[g], np.nan) for k, g in groups.items()}
