"""The plain references (numpy f64, PromQL semantics) and the comparison that
decides ``correct``. Copied from ``chip_smoke.py`` (PR 21); nothing here
imports the program or takes anything it has made.

A reference is found by the name a workload's panel gives
(``"reference": "hist_quantile_sum_rate"``) and is called as
``fn(data, out_t, window_ms, panel, quantize)``; it returns
``{frozenset(label items): [J] f64 row, NaN = absent}``. A later PR adds one
as a new module ``benchmarks/chip/<name>.py`` with a function ``reference``.

``quantize`` is the control's hook (``control.py``): None for the reference
itself; otherwise a function that rounds an f64 array to a lower precision.
It is applied where the program stages its values — histogram buckets as
offsets from each series' first sample, which is what a later PR would be
tempted to store in fewer bits — and the rest of the arithmetic stays f64,
so the control reads the least that such a change could cost.
"""

from __future__ import annotations

import importlib

import numpy as np


def find(name: str):
    fn = globals().get(name)
    if callable(fn) and not name.startswith("_"):
        return fn
    return importlib.import_module(f"benchmarks.chip.{name}").reference


def rate_factor(tf_ms, tl_ms, cnt, out_t, window_ms):
    """promql extrapolatedRate over (t - w, t] as the factor that turns a
    window's increase into its rate; tf/tl/cnt are [S, J]. Histogram buckets
    are not held back where they would have been zero, as the engine's
    per-bucket rate is not. The caller masks windows with fewer than 2
    samples."""
    sampled = (tl_ms - tf_ms) / 1e3
    dur_start = (tf_ms - (out_t - window_ms)[None, :]) / 1e3
    dur_end = (out_t[None, :] - tl_ms) / 1e3
    avg = sampled / np.maximum(cnt - 1, 1)
    with np.errstate(divide="ignore", invalid="ignore"):
        dur_start = np.where(dur_start >= avg * 1.1, avg / 2, dur_start)
        dur_end = np.where(dur_end >= avg * 1.1, avg / 2, dur_end)
        return (sampled + dur_start + dur_end) / sampled / (window_ms / 1e3)


_ALL = frozenset()


def hist_quantile_sum_rate(data, out_t, window_ms, panel, quantize=None):
    """``histogram_quantile(q, sum by (le) (rate(m_bucket[w])))``."""
    q, ts, les = float(panel["q"]), data.ts, data.les
    hi = np.searchsorted(ts, out_t, side="right")
    lo = np.searchsorted(ts, out_t - window_ms, side="right")
    cnt = (hi - lo)[None, :]
    T = len(ts)
    lo_c, hi_c = np.minimum(lo, T - 1), np.clip(hi - 1, 0, T - 1)
    tf = ts[lo_c][None, :].astype(np.float64)
    tl = ts[hi_c][None, :].astype(np.float64)
    k = rate_factor(tf, tl, cnt, out_t, window_ms)[0]
    bsum = np.zeros((len(out_t), len(les)))
    for b0 in range(0, len(data.hist), 2_000):  # bound the [s, J, B] temporary
        h = data.hist[b0:b0 + 2_000]
        if quantize is not None:
            h = quantize(h - h[:, :1])
        bsum += (h[:, hi_c] - h[:, lo_c]).sum(0)
    bsum *= np.where(cnt[0] >= 2, k, np.nan)[:, None]
    # promql histogram_quantile: linear interpolation inside the located
    # bucket, first bucket from 0, +Inf bucket -> highest finite bound
    total = bsum[:, -1]
    rank = q * total
    meets = bsum >= rank[:, None]
    idx = np.where(meets.any(1), np.argmax(meets, axis=1), len(les) - 1)
    rows = np.arange(len(out_t))
    c_hi = bsum[rows, idx]
    c_lo = np.where(idx > 0, bsum[rows, np.maximum(idx - 1, 0)], 0.0)
    le_lo = np.where(idx > 0, les[np.maximum(idx - 1, 0)], 0.0)
    with np.errstate(divide="ignore", invalid="ignore"):
        val = le_lo + (les[idx] - le_lo) * (rank - c_lo) / (c_hi - c_lo)
    val = np.where(idx == len(les) - 1, les[-2], val)
    return {_ALL: np.where(total > 0, val, np.nan)}


# -- the comparison ----------------------------------------------------------


def parse_matrix(body: str, out_t: np.ndarray) -> dict | None:
    """A query_range body -> {frozenset(label items): [J] f64, NaN = absent};
    None when the body is not a successful matrix on the step grid."""
    import json

    try:
        doc = json.loads(body)
        if doc["status"] != "success" or doc["data"]["resultType"] != "matrix":
            return None
        pos = {int(t): j for j, t in enumerate(out_t)}
        out = {}
        for series in doc["data"]["result"]:
            row = np.full(len(out_t), np.nan)
            for t, v in series["values"]:
                row[pos[int(round(float(t) * 1000))]] = float(v)
            # PromQL: a label with an empty value is the label absent
            labels = {k: v for k, v in series["metric"].items()
                      if k != "__name__" and v != ""}
            out[frozenset(labels.items())] = row
        return out
    except (KeyError, ValueError, TypeError):
        return None


def compare(got: dict | None, want: dict) -> dict:
    """One answer against its reference: ``malformed`` (not a matrix on the
    grid, or another set of series), ``absent_mismatch`` (steps present on
    one side only) and ``rel_err`` (the widest |got - want| / |want| over
    the steps both have). An answer that is malformed has no rel_err."""
    if got is None or set(got) != set(want):
        return {"malformed": 1, "absent_mismatch": 0, "rel_err": None}
    absent, err = 0, 0.0
    for key, w in want.items():
        g = got[key]
        absent += int((np.isnan(g) != np.isnan(w)).sum())
        m = ~np.isnan(w) & ~np.isnan(g)
        if m.any():
            e = np.abs(g[m] - w[m]) / np.maximum(np.abs(w[m]), 1e-30)
            err = max(err, float(e.max()) if np.isfinite(e).all() else np.inf)
    return {"malformed": 0, "absent_mismatch": absent, "rel_err": err}


class Tally:
    """The numbers a run compares, over all its answers: the counts of
    ``malformed`` and ``absent_mismatch`` and each panel's widest
    ``rel_err``, each beside its limit."""

    def __init__(self, panels: list[dict]):
        self.panels = panels
        self.worst = {p["name"]: 0.0 for p in panels}
        self.malformed = self.absent = 0

    def add(self, panel: dict, r: dict | None) -> bool:
        """Count one answer's comparison (None: no answer, e.g. a status
        other than 200); True when it is right by the panel's limit."""
        if r is None:
            r = {"malformed": 1, "absent_mismatch": 0, "rel_err": None}
        self.malformed += r["malformed"]
        self.absent += r["absent_mismatch"]
        if r["rel_err"] is None:
            return False
        self.worst[panel["name"]] = max(self.worst[panel["name"]], r["rel_err"])
        return not r["absent_mismatch"] and r["rel_err"] <= panel["rel_err_limit"]

    def compared(self) -> dict:
        out = {"malformed": {"value": self.malformed, "limit": 0},
               "absent_mismatch": {"value": self.absent, "limit": 0}}
        for p in self.panels:
            out[f"rel_err.{p['name']}"] = {"value": self.worst[p["name"]],
                                           "limit": p["rel_err_limit"]}
        return out
