"""TPU range-function kernels (reference L4 hot path re-designed for XLA).

The reference evaluates PromQL range functions per series per output step with
iterator state machines (rangefn/RangeFunction.scala:84, RateFunctions.scala:230,
AggrOverTimeFunctions.scala) plus Rust SIMD for inner sums
(simd_vectors.rs:174). Here ONE jit kernel computes the whole ``[S, J]``
output grid (S series x J output steps) from a staged ``[S, T]`` block:

- Window boundaries resolve by compare-and-reduce contractions
  (``#{ts <= t_j}``) which XLA fuses — no per-window iterators, no dynamic
  shapes, no data-dependent control flow.
- sum/count family reads prefix sums at the boundary indices (the parallel
  form of the reference's chunked running aggregates).
- Counter reset correction happens HOST-SIDE in f64 at staging
  (staging.counter_correct — the prefix-scan form of
  CounterChunkedRangeFunction's per-chunk carry); staged counter values are
  already corrected, so the device needs no correction pass.
- rate/increase/delta implement Prometheus extrapolation semantics
  (promql extrapolatedRate), which the reference's ChunkedRateFunctionBase
  also follows.
- Functions needing per-window sample *sets* (quantile_over_time, mad) sort
  masked windows in step blocks via lax.map to bound memory.

Everything is shape-static: S, T, J are padded-bucketed by staging, so the
jit cache stays tiny across queries.

Empty windows yield NaN; the serialization layer treats NaN as "no sample"
(Prometheus absence). Inputs are NaN-free by staging contract.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .staging import StagedBlock

_NAN = jnp.nan


@dataclass(frozen=True)
class RangeParams:
    """Output grid + window spec. start/step/window ride as dynamic args;
    num_steps is static (padded to 64s by the caller via pad_steps)."""

    start_ms: int  # absolute ms of first output step
    step_ms: int
    num_steps: int
    window_ms: int


def pad_steps(j: int) -> int:
    return max(64, ((j + 63) // 64) * 64)


# ---------------------------------------------------------------------------
# shared window machinery (all [S, J] index math)
# ---------------------------------------------------------------------------


def _bounds(ts, lens, out_t, window):
    """hi/lo sample-count indices per (series, step).

    Window j = (out_t[j] - window, out_t[j]]. Returns (lo, hi): sample i is in
    the window iff lo <= i < hi. Padding slots carry TS_PAD and never match.
    """
    T = ts.shape[1]
    valid = jnp.arange(T, dtype=jnp.int32)[None, :] < lens[:, None]
    le = (ts[:, None, :] <= out_t[None, :, None]) & valid[:, None, :]
    hi = le.sum(-1, dtype=jnp.int32)
    lo_bound = out_t - window
    le2 = (ts[:, None, :] <= lo_bound[None, :, None]) & valid[:, None, :]
    lo = le2.sum(-1, dtype=jnp.int32)
    return lo, hi


def _gather(arr, idx):
    """arr [S, T], idx [S, J] -> [S, J] (idx clipped; caller masks validity)."""
    T = arr.shape[1]
    return jnp.take_along_axis(arr, jnp.clip(idx, 0, T - 1), axis=1)


def _prefix(vals):
    """[S, T] -> [S, T+1] exclusive prefix sum in f32."""
    cs = jnp.cumsum(vals, axis=1)
    return jnp.concatenate([jnp.zeros_like(cs[:, :1]), cs], axis=1)


def _window_mask(ts, lens, out_t, window):
    T = ts.shape[1]
    valid = jnp.arange(T, dtype=jnp.int32)[None, :] < lens[:, None]
    in_win = (
        (ts[:, None, :] <= out_t[None, :, None])
        & (ts[:, None, :] > (out_t - window)[None, :, None])
        & valid[:, None, :]
    )
    return in_win  # [S, J, T] — consumers must fuse-reduce, never materialize


def _extrapolated(delta, t_first, t_last, count, v_first_raw, out_t, window, is_counter, as_rate):
    """Prometheus extrapolatedRate: extrapolate the in-window delta to the
    window edges, capped at 1.1x the average sample spacing (and at the
    zero-crossing for counters)."""
    f32 = delta.dtype
    w_s = window.astype(f32) * 1e-3
    range_start = (out_t - window)[None, :].astype(f32) * 1e-3
    range_end = out_t[None, :].astype(f32) * 1e-3
    tf = t_first.astype(f32) * 1e-3
    tl = t_last.astype(f32) * 1e-3
    sampled = tl - tf
    cnt = count.astype(f32)
    dur_start = tf - range_start
    dur_end = range_end - tl
    avg_dur = sampled / jnp.maximum(cnt - 1.0, 1.0)
    if is_counter:
        dur_zero = jnp.where(delta > 0, sampled * (v_first_raw / jnp.maximum(delta, 1e-30)), jnp.inf)
        dur_start = jnp.minimum(dur_start, jnp.where(v_first_raw >= 0, dur_zero, jnp.inf))
    thresh = avg_dur * 1.1
    dur_start = jnp.where(dur_start >= thresh, avg_dur / 2.0, dur_start)
    dur_end = jnp.where(dur_end >= thresh, avg_dur / 2.0, dur_end)
    factor = (sampled + dur_start + dur_end) / jnp.maximum(sampled, 1e-30)
    result = delta * factor
    if as_rate:
        result = result / w_s
    return jnp.where(count >= 2, result, _NAN)


# ---------------------------------------------------------------------------
# the kernel: one jit per (func, S, T, J)
# ---------------------------------------------------------------------------



@functools.partial(
    jax.jit, static_argnames=("func", "num_steps", "is_counter", "is_delta")
)
@jax.named_scope("range_fn")
def range_kernel(
    func: str,
    ts,  # [S, T] i32
    vals,  # [S, T] f32 (counters: reset-corrected minus baseline by staging)
    lens,  # [S] i32
    baseline,  # [S] f32
    raw,  # [S, T] f32 raw-minus-baseline (== vals for non-counters)
    start_off,  # scalar i32: first output step (offset ms)
    step_ms,  # scalar i32
    window,  # scalar i32
    num_steps: int,
    is_counter: bool = False,
    is_delta: bool = False,
    arg0=0.0,  # function scalar arg (quantile q, holt sf, predict horizon s)
    arg1=0.0,  # second scalar arg (holt tf)
):
    """Compute [S, num_steps] results for one range function."""
    S, T = ts.shape
    out_t = start_off + jnp.arange(num_steps, dtype=jnp.int32) * step_ms
    lo, hi = _bounds(ts, lens, out_t, window)
    count = (hi - lo).astype(jnp.float32)
    has = count > 0

    def prefix_sum_of(x):
        p = _prefix(x)  # [S, T+1] exclusive; sum over [lo, hi) = p[hi]-p[lo]
        return _gather(p, hi) - _gather(p, lo)

    # boundary samples
    t_first = _gather(ts, lo)
    t_last = _gather(ts, hi - 1)
    v_last = _gather(vals, hi - 1)
    v_first = _gather(vals, lo)

    if func in ("sum_over_time", "avg_over_time"):
        # masked in-window reduce, NOT a prefix-sum difference: prefix sums
        # accumulate the full history, so p[hi]-p[lo] catastrophically cancels
        # in f32 for large-magnitude (e.g. raw counter) values; summing only
        # in-window samples keeps the error relative to the window sum (XLA
        # fuses the mask into the reduction — nothing [S,J,T] materializes)
        m = _window_mask(ts, lens, out_t, window)
        s = jnp.where(m, vals[:, None, :], 0.0).sum(-1)
        if func == "avg_over_time":
            s = s / count
        return jnp.where(has, s, _NAN)
    if func == "count_over_time":
        return jnp.where(has, count, _NAN)
    if func in ("last", "last_over_time"):
        return jnp.where(has, v_last, _NAN)
    if func == "first_over_time":
        return jnp.where(has, v_first, _NAN)
    if func == "timestamp":
        # returns ms offsets; host adds base_ms and converts to seconds (f64)
        return jnp.where(has, t_last.astype(jnp.float32), _NAN)
    if func == "present_over_time":
        return jnp.where(has, 1.0, _NAN)
    if func == "absent_over_time":
        # 1.0 where NO sample; presenter turns it into an absent-vector
        return jnp.where(has, _NAN, 1.0)
    if func in ("min_over_time", "max_over_time"):
        m = _window_mask(ts, lens, out_t, window)
        big = jnp.float32(np.inf if func == "min_over_time" else -np.inf)
        w = jnp.where(m, vals[:, None, :], big)
        r = w.min(-1) if func == "min_over_time" else w.max(-1)
        return jnp.where(has, r, _NAN)
    if func in ("stddev_over_time", "stdvar_over_time", "z_score"):
        s = prefix_sum_of(vals)
        mean = s / jnp.maximum(count, 1.0)
        m = _window_mask(ts, lens, out_t, window)
        dev = jnp.where(m, (vals[:, None, :] - mean[:, :, None]) ** 2, 0.0)
        var = dev.sum(-1) / jnp.maximum(count, 1.0)
        if func == "stdvar_over_time":
            return jnp.where(has, var, _NAN)
        sd = jnp.sqrt(var)
        if func == "z_score":
            return jnp.where(has, (v_last - mean) / jnp.maximum(sd, 1e-30), _NAN)
        return jnp.where(has, sd, _NAN)
    if func in ("changes", "resets"):
        # MUST see raw (uncorrected) value movement: corrected counter vals
        # are monotone, so resets() over them would always be 0 and changes()
        # would miss every reset. Counter blocks stage f64-exact adjacent
        # diffs (staging mode "diff" — f32 values can't preserve tiny changes
        # next to 1e9 reset cliffs); gauges compare raw values directly.
        if is_counter and not is_delta:
            flag = (vals != 0) if func == "changes" else (vals < 0)
        else:
            prev = jnp.concatenate([raw[:, :1], raw[:, :-1]], axis=1)
            flag = (raw != prev) if func == "changes" else (raw < prev)
        idx = jnp.arange(T, dtype=jnp.int32)[None, None, :]
        pair_in = (idx > lo[:, :, None]) & (idx < hi[:, :, None])
        n = (pair_in & flag[:, None, :]).sum(-1).astype(jnp.float32)
        return jnp.where(has, n, _NAN)
    if func in ("deriv", "predict_linear"):
        # least-squares slope over (t - out_t) seconds, per window
        m = _window_mask(ts, lens, out_t, window)
        tc = (ts[:, None, :] - out_t[None, :, None]).astype(jnp.float32) * 1e-3
        tc = jnp.where(m, tc, 0.0)
        vm = jnp.where(m, vals[:, None, :], 0.0)
        st = tc.sum(-1)
        sv = vm.sum(-1)
        stt = (tc * tc).sum(-1)
        stv = (tc * vm).sum(-1)
        n = count
        denom = n * stt - st * st
        slope = (n * stv - st * sv) / jnp.where(jnp.abs(denom) < 1e-30, 1.0, denom)
        intercept = (sv - slope * st) / jnp.maximum(n, 1.0)
        ok = (count >= 2) & (jnp.abs(denom) >= 1e-30)
        if func == "deriv":
            return jnp.where(ok, slope, _NAN)
        return jnp.where(ok, intercept + slope * arg0, _NAN)
    if func == "double_exponential_smoothing":
        return _holt_winters(ts, vals, lens, out_t, window, lo, hi, arg0, arg1)

    # counter family ------------------------------------------------------
    if func in ("rate", "increase", "delta"):
        if is_delta:
            # delta-temporality counters: each sample IS the increase
            s = prefix_sum_of(vals)
            if func == "rate":
                r = s / (window.astype(jnp.float32) * 1e-3)
            else:
                r = s
            return jnp.where(has, r, _NAN)
        # vals are already reset-corrected by staging for counters, so the
        # plain in-window difference IS the corrected increase
        dlt = v_last - v_first
        v_first_raw = _gather(raw, lo)  # only read when is_counter (zero cap)
        use_counter = is_counter and func != "delta"
        return _extrapolated(
            dlt, t_first, t_last, count, v_first_raw, out_t, window,
            is_counter=use_counter, as_rate=(func == "rate"),
        )
    if func in ("irate", "idelta"):
        ok = (hi - lo) >= 2
        if func == "idelta" and is_counter and not is_delta:
            # counter idelta reads the staged f64-exact diff of the last pair
            return jnp.where(ok, _gather(vals, hi - 1), _NAN)
        t_prev = _gather(ts, hi - 2)
        v_prev = _gather(vals, hi - 2)
        dt_s = (t_last - t_prev).astype(jnp.float32) * 1e-3
        # irate on counters: corrected-value difference across a reset equals
        # the post-reset raw reading — Prometheus reset semantics, no branch
        dv = v_last - v_prev
        r = dv / jnp.maximum(dt_s, 1e-30) if func == "irate" else dv
        return jnp.where(ok, r, _NAN)

    raise ValueError(f"unknown range function {func}")


def _holt_winters(ts, vals, lens, out_t, window, lo, hi, sf, tf):
    """Holt's double exponential smoothing per window (reference
    RangeFunction.scala holt-winters). Sequential in samples: lax.scan over T
    carrying (level, trend) per (series, step)."""
    S, T = vals.shape
    J = out_t.shape[0]
    idx = jnp.arange(T, dtype=jnp.int32)

    def body(carry, t_i):
        # promql holtWinters recurrence: level0 = x0; the 2nd sample sets
        # trend = x1 - x0 and leaves level = x1; then the standard update.
        level, trend, n_seen = carry
        in_win = (t_i >= lo) & (t_i < hi)  # [S, J]
        x = vals[:, t_i][:, None]  # [S, 1]
        new_level = sf * x + (1 - sf) * (level + trend)
        new_trend = tf * (new_level - level) + (1 - tf) * trend
        lvl = jnp.where(
            in_win,
            jnp.where(n_seen == 0, x, jnp.where(n_seen == 1, x, new_level)),
            level,
        )
        trd = jnp.where(
            in_win,
            jnp.where(
                n_seen == 0,
                jnp.zeros_like(trend),
                jnp.where(n_seen == 1, x - level, new_trend),
            ),
            trend,
        )
        n2 = jnp.where(in_win, n_seen + 1, n_seen)
        return (lvl, trd, n2), None

    init = (
        jnp.zeros((S, J), vals.dtype),
        jnp.zeros((S, J), vals.dtype),
        jnp.zeros((S, J), jnp.int32),
    )
    (level, trend, n_seen), _ = jax.lax.scan(body, init, idx)
    return jnp.where(n_seen >= 2, level, _NAN)


# quantile / mad: need per-window sorts — run in step blocks to bound memory
@functools.partial(jax.jit, static_argnames=("func", "num_steps", "block"))
def sorted_window_kernel(
    func: str, ts, vals, lens, start_off, step_ms, window, num_steps: int,
    q=0.5, arg1=0.0, block: int = 16
):
    S, T = ts.shape
    out_t_all = start_off + jnp.arange(num_steps, dtype=jnp.int32) * step_ms

    def one_block(out_t):
        lo, hi = _bounds(ts, lens, out_t, window)
        count = (hi - lo).astype(jnp.float32)
        m = _window_mask(ts, lens, out_t, window)
        w = jnp.where(m, vals[:, None, :], jnp.inf)
        sw = jnp.sort(w, axis=-1)

        def interp_at(sorted_w, rank):
            lo_i = jnp.floor(rank).astype(jnp.int32)
            hi_i = jnp.ceil(rank).astype(jnp.int32)
            frac = rank - lo_i.astype(jnp.float32)
            v_lo = jnp.take_along_axis(sorted_w, lo_i[..., None], axis=-1)[..., 0]
            v_hi = jnp.take_along_axis(sorted_w, hi_i[..., None], axis=-1)[..., 0]
            return v_lo + (v_hi - v_lo) * frac

        def mad_of(cnt):
            med_rank = 0.5 * jnp.maximum(cnt - 1.0, 0.0)
            med = interp_at(sw, med_rank)
            dev = jnp.where(m, jnp.abs(vals[:, None, :] - med[:, :, None]), jnp.inf)
            sd = jnp.sort(dev, axis=-1)
            return med, interp_at(sd, med_rank)

        if func == "quantile_over_time":
            rank = jnp.clip(q, 0.0, 1.0) * jnp.maximum(count - 1.0, 0.0)
            r = interp_at(sw, rank)
        elif func == "median_absolute_deviation_over_time":
            _, r = mad_of(count)
        elif func == "last_over_time_is_mad_outlier":
            # (tolerance=q, bounds=arg1): emit the last value iff it lies
            # outside median +/- tolerance*MAD per the bounds mode
            # (reference LastOverTimeIsMadOutlierFunction,
            # AggrOverTimeFunctions.scala:488)
            med, mad = mad_of(count)
            tmax = jnp.where(m, ts[:, None, :], -(2**31) + 1).max(-1)
            lastv = jnp.where(m & (ts[:, None, :] == tmax[:, :, None]), vals[:, None, :], 0.0).sum(-1)
            lower = med - q * mad
            upper = med + q * mad
            is_out = ((lastv < lower) & (arg1 <= 1)) | ((lastv > upper) & (arg1 >= 1))
            r = jnp.where(is_out, lastv, _NAN)
        else:
            raise ValueError(func)
        return jnp.where(count > 0, r, _NAN)

    blocks = out_t_all.reshape(num_steps // block, block)
    out = jax.lax.map(one_block, blocks)  # [nb, S, block]
    return jnp.moveaxis(out, 0, 1).reshape(S, num_steps)


SORTED_FUNCS = {
    "quantile_over_time",
    "median_absolute_deviation_over_time",
    "last_over_time_is_mad_outlier",
}


# ---------------------------------------------------------------------------
# host-facing entry
# ---------------------------------------------------------------------------


def _host_timestamp(block: StagedBlock, params: RangeParams) -> np.ndarray:
    """timestamp() computed host-side from the int32 ts array in f64.

    The device grid is f32, which represents integer ms offsets exactly only
    up to 2^24 (~4.6h); Prometheus returns exact sample timestamps, so this
    function never goes through the f32 kernel path. Returns absolute
    seconds [S, J_pad] f64 (NaN = no sample in window)."""
    j_pad = pad_steps(params.num_steps)
    out_t = (
        np.int64(params.start_ms - block.base_ms)
        + np.arange(j_pad, dtype=np.int64) * params.step_ms
    )
    lens_np = np.asarray(block.lens)
    S = np.asarray(block.ts).shape[0]
    out = np.full((S, j_pad), np.nan)

    def row_for(ts1: np.ndarray) -> np.ndarray:
        hi = np.searchsorted(ts1, out_t, side="right")
        lo = np.searchsorted(ts1, out_t - params.window_ms, side="right")
        has = hi > lo
        t_last = ts1[np.minimum(hi - 1, len(ts1) - 1)]
        return np.where(has, (t_last + block.base_ms) / 1e3, np.nan)

    if block.regular_ts is not None and block.n_series > 0:
        ts1 = np.asarray(block.regular_ts)[: int(lens_np[0])].astype(np.int64)
        out[: block.n_series] = row_for(ts1)[None, :]
        return out
    # irregular grids: one batched searchsorted over all series via per-row
    # offsets (rows are sorted and TS_PAD sorts after every real offset)
    n = block.n_series
    if n == 0:
        return out
    ts_np = np.asarray(block.ts)[:n].astype(np.int64)
    T = ts_np.shape[1]
    lens_n = lens_np[:n].astype(np.int64)
    stride = np.int64(1) << 33  # > any int32 ms offset incl. TS_PAD
    row_off = (np.arange(n, dtype=np.int64) * stride)[:, None]
    flat = (ts_np + row_off).ravel()
    hi = np.searchsorted(flat, (out_t[None, :] + row_off).ravel(), side="right")
    lo = np.searchsorted(
        flat, ((out_t - params.window_ms)[None, :] + row_off).ravel(), side="right"
    )
    hi = np.minimum(hi.reshape(n, -1) - np.arange(n)[:, None] * T, lens_n[:, None])
    lo = np.minimum(lo.reshape(n, -1) - np.arange(n)[:, None] * T, lens_n[:, None])
    has = hi > lo
    t_last = np.take_along_axis(ts_np, np.maximum(hi - 1, 0), axis=1)
    out[:n] = np.where(has, (t_last + block.base_ms) / 1e3, np.nan)
    return out


def _jit_cache_size() -> int:
    """Combined compile-cache size of the kernels run_range_function can
    dispatch to — a growth across one dispatch means a compile happened
    (the hit/miss signal of filodb_compile_cache_{hits,misses}_total and
    filodb_xla_compiles_total; SURVEY §7 calls
    recompilation the #1 risk, so hits/misses must be observable in
    production).

    Best-effort attribution under concurrency: a sibling thread's compile
    during this dispatch is counted as this dispatch's miss, and two racing
    first-dispatches of one shape may both count. Misses are therefore an
    UPPER bound — but a miss can only register while some cache genuinely
    grew, so the steady-state signal (misses must go to zero) is exact."""
    total = range_kernel._cache_size() + sorted_window_kernel._cache_size()
    try:
        from .mxu_jitter import jitter_masked_kernel, jitter_range_kernel
        from .mxu_kernels import mxu_minmax, mxu_range_kernel

        total += mxu_range_kernel._cache_size() + mxu_minmax._cache_size()
        total += jitter_range_kernel._cache_size() + jitter_masked_kernel._cache_size()
    except Exception:  # noqa: BLE001 — accounting must never break dispatch
        pass
    return total


def run_range_function(
    func: str,
    block: StagedBlock,
    params: RangeParams,
    is_counter: bool = False,
    is_delta: bool = False,
    args: tuple = (),
):
    """Dispatch one range function over a staged block (instrumented entry
    point: per-kernel dispatch latency + JIT cache hit/miss). Returns a
    device array [S, J_padded]; caller slices [:n_series, :num_steps]."""
    import time as _time

    from ..metrics import record_kernel_dispatch

    t0 = _time.perf_counter()
    before = _jit_cache_size()
    out, variant = _dispatch_range_function(
        func, block, params, is_counter=is_counter, is_delta=is_delta, args=args
    )
    s_, t_ = np.shape(block.ts)
    record_kernel_dispatch(
        func, _time.perf_counter() - t0, compiled=_jit_cache_size() > before,
        key={"variant": variant,
             "shapes": f"S{s_}xT{t_}xJ{pad_steps(params.num_steps)}"},
    )
    return out


def _dispatch_range_function(
    func: str,
    block: StagedBlock,
    params: RangeParams,
    is_counter: bool = False,
    is_delta: bool = False,
    args: tuple = (),
):
    """Returns ``(grid, variant)``: the variant is the ladder rung that
    actually served the dispatch — the observatory's executable-key
    ``variant`` dimension, reported by the rung that ran rather than
    re-derived (the jitter/masked fast paths can decline at runtime)."""
    from .mxu_kernels import MXU_FUNCS, run_mxu_range_function

    if func == "timestamp":
        return _host_timestamp(block, params), "host"
    if (
        block.regular_ts is not None
        and func in MXU_FUNCS
        and not (is_delta and func in ("irate", "idelta"))
    ):
        # shared-scrape-grid fast path: window reduction as MXU matmuls
        return run_mxu_range_function(
            func, block, params, is_counter=is_counter, is_delta=is_delta, args=args
        ), "mxu"
    if (
        block.nominal_ts is not None
        and not (is_delta and func in ("irate", "idelta"))
        and not args
    ):
        from .mxu_jitter import JITTER_FUNCS, run_jitter_range_function

        if func in JITTER_FUNCS:
            # near-regular (jittered scrape) fast path: certain-membership
            # matmul + per-series boundary corrections (mxu_jitter.py)
            res = run_jitter_range_function(
                func, block, params, is_counter=is_counter, is_delta=is_delta
            )
            if res is not None:
                return res, "jitter"
    if (
        block.mgrid is not None
        and not (is_delta and func in ("irate", "idelta"))
        and not args
    ):
        from .mxu_jitter import JITTER_FUNCS, run_masked_jitter_range_function

        if func in JITTER_FUNCS:
            # missing-scrape fast path: validity masks on the nominal grid
            # (a dropped scrape must not cost the 40x general-path penalty)
            res = run_masked_jitter_range_function(
                func, block, params, is_counter=is_counter, is_delta=is_delta
            )
            if res is not None:
                return res, "masked"
    from .pallas_kernels import (
        PALLAS_FUNCS,
        pallas_enabled,
        run_pallas_range_function,
    )

    if (func in PALLAS_FUNCS and not args
            and pallas_enabled(block.ts.shape[1])):
        # the ONE FILODB_PALLAS policy (pallas_kernels.pallas_enabled),
        # shared with the fused dispatch ladder: the one-pass VMEM kernel,
        # compiled on real hardware (interpreted on CPU only when forced)
        return run_pallas_range_function(
            func, block, params, is_counter=is_counter, is_delta=is_delta,
        ), "pallas"
    j_pad = pad_steps(params.num_steps)
    start_off = np.int32(params.start_ms - block.base_ms)
    if func in SORTED_FUNCS:
        return sorted_window_kernel(
            func,
            block.ts,
            block.vals,
            block.lens,
            start_off,
            np.int32(params.step_ms),
            np.int32(params.window_ms),
            j_pad,
            q=np.float32(args[0]) if args else np.float32(0.5),
            arg1=np.float32(args[1]) if len(args) > 1 else np.float32(0.0),
        ), "sorted"
    a0 = np.float32(args[0]) if len(args) > 0 else np.float32(0.0)
    a1 = np.float32(args[1]) if len(args) > 1 else np.float32(0.0)
    return range_kernel(
        func,
        block.ts,
        block.vals,
        block.lens,
        block.baseline,
        block.raw if block.raw is not None else block.vals,
        start_off,
        np.int32(params.step_ms),
        np.int32(params.window_ms),
        j_pad,
        is_counter=is_counter,
        is_delta=is_delta,
        arg0=a0,
        arg1=a1,
    ), "general"


# kernel-observatory registration (obs/kernels.py; linted by
# tools/check_metrics.py — every jit wrapper here must register)
def _register_kernel_observatory() -> None:
    from ..obs.kernels import KERNELS

    KERNELS.register_jits(
        "ops.kernels",
        range_kernel=range_kernel,
        sorted_window_kernel=sorted_window_kernel,
    )


_register_kernel_observatory()
