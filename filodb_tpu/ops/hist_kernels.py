"""Native-histogram kernels (reference L0/L4: format/vectors/Histogram.scala
quantile math :64-130, HistogramQuantileMapper, RateFunctions hist rate :367).

Native histograms stage as ``[S, T, B]`` cumulative bucket-count blocks —
already the ideal TPU layout. Per-bucket rate/increase/sum reuse the same
boundary-index machinery as scalar kernels (broadcast over B);
histogram_quantile is a vectorized interpolation over the bucket axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .kernels import RangeParams, _bounds, pad_steps
from .staging import StagedBlock


def hist_window_form(func: str, is_delta: bool) -> str:
    """How a hist range body reads a window: ``"sums"``, every sample in it
    (sum_over_time, and rate / increase of a delta column, whose points
    each hold only their own interval), or ``"edges"``, the samples at its
    edges (a cumulative column's rate family, last). The one test every
    body makes; each base-2 launch books it (filodb_hist_window_total)."""
    if func == "sum_over_time" or (is_delta and func in ("rate", "increase")):
        return "sums"
    return "edges"


def _gather3(arr, idx):
    """arr [S, T, B], idx [S, J] -> [S, J, B]."""
    T = arr.shape[1]
    return jnp.take_along_axis(arr, jnp.clip(idx, 0, T - 1)[:, :, None], axis=1)


@functools.partial(jax.jit, static_argnames=("func", "num_steps", "is_delta"))
@jax.named_scope("range_fn")
def hist_range_kernel(
    func: str,
    ts,  # [S, T] i32
    vals,  # [S, T, B] f32 bucket counts (cumulative; baseline-subtracted)
    lens,  # [S] i32
    start_off,
    step_ms,
    window,
    num_steps: int,
    is_delta: bool = False,
):
    """[S, num_steps, B] per-bucket results for hist rate/increase/last/sum."""
    out_t = start_off + jnp.arange(num_steps, dtype=jnp.int32) * step_ms
    lo, hi = _bounds(ts, lens, out_t, window)
    count = (hi - lo).astype(jnp.float32)[:, :, None]
    has = count > 0
    if func in ("last", "last_over_time"):
        return jnp.where(has, _gather3(vals, hi - 1), jnp.nan)
    if hist_window_form(func, is_delta) == "sums":
        cs = jnp.cumsum(vals, axis=1)
        cs = jnp.concatenate([jnp.zeros_like(cs[:, :1]), cs], axis=1)
        s = _gather3(cs, hi) - _gather3(cs, lo)
        if func == "rate":
            s = s / (window.astype(jnp.float32) * 1e-3)
        return jnp.where(has, s, jnp.nan)
    if func in ("rate", "increase", "delta"):
        # cumulative histograms: per-bucket extrapolated increase, same
        # Prometheus window-edge extrapolation as scalars (no zero cap —
        # bucket counts are far from zero-crossing concerns; reference hist
        # rate RateFunctions.scala:367 likewise extrapolates per bucket)
        t_first = jnp.take_along_axis(ts, jnp.clip(lo, 0, ts.shape[1] - 1), axis=1)
        t_last = jnp.take_along_axis(ts, jnp.clip(hi - 1, 0, ts.shape[1] - 1), axis=1)
        v_first = _gather3(vals, lo)
        v_last = _gather3(vals, hi - 1)
        dlt = v_last - v_first  # [S, J, B]
        f32 = vals.dtype
        tf = t_first.astype(f32) * 1e-3
        tl = t_last.astype(f32) * 1e-3
        sampled = tl - tf
        cnt = (hi - lo).astype(f32)
        range_start = (out_t - window)[None, :].astype(f32) * 1e-3
        range_end = out_t[None, :].astype(f32) * 1e-3
        factor = _rate_factor(sampled, tf - range_start, range_end - tl, cnt)
        res = dlt * factor[:, :, None]
        if func == "rate":
            res = res / (window.astype(f32) * 1e-3)
        return jnp.where((cnt >= 2)[:, :, None], res, jnp.nan)
    raise ValueError(f"unknown histogram range function {func}")


def _rate_factor(sampled, dur_start, dur_end, cnt):
    """PromQL's ``extrapolatedRate`` factor, the one copy every hist body
    uses: a window's increase times it is the increase over the whole
    window (an edge gap up to 1.1 average intervals is extrapolated over,
    a longer one by half an interval). Seconds in, any shape."""
    avg_dur = sampled / jnp.maximum(cnt - 1.0, 1.0)
    thresh = avg_dur * 1.1
    dur_start = jnp.where(dur_start >= thresh, avg_dur / 2.0, dur_start)
    dur_end = jnp.where(dur_end >= thresh, avg_dur / 2.0, dur_end)
    return (sampled + dur_start + dur_end) / jnp.maximum(sampled, 1e-30)


@functools.partial(jax.jit, static_argnames=("even",))
@jax.named_scope("epilogue")
def histogram_quantile(q, buckets, les, even: bool = False):
    """Prometheus histogram_quantile over bucket-count/rate grids.

    buckets [..., B] cumulative counts per le; les [B] upper bounds with
    les[-1] = +inf. Linear interpolation within the located bucket; lower
    bound of the first bucket is 0 when its le > 0 (promql semantics, and
    reference Histogram.scala:64-130 quantile()). ``even`` assumes samples
    spread evenly over count+1 positions (reference evenDistribution,
    Histogram.scala:96).
    """
    B = buckets.shape[-1]
    total = buckets[..., -1]
    ok = (total > 0) & jnp.isfinite(total)
    rank = jnp.clip(q, 0.0, 1.0) * total
    # first bucket index with count >= rank
    meets = buckets >= rank[..., None]
    idx = jnp.argmax(meets, axis=-1)
    idx = jnp.where(meets.any(-1), idx, B - 1)
    c_hi = jnp.take_along_axis(buckets, idx[..., None], axis=-1)[..., 0]
    c_lo = jnp.where(idx > 0, jnp.take_along_axis(buckets, jnp.maximum(idx - 1, 0)[..., None], axis=-1)[..., 0], 0.0)
    le_hi = les[idx]
    le_lo = jnp.where(idx > 0, les[jnp.maximum(idx - 1, 0)], jnp.where(les[0] > 0, 0.0, -jnp.inf))
    # top (+inf) bucket: return the highest finite bound (promql behavior)
    highest_finite = jnp.where(B >= 2, les[B - 2], les[0])
    in_top = idx == B - 1
    denom = (c_hi - c_lo + 1.0) if even else (c_hi - c_lo)
    frac = (rank - c_lo) / jnp.maximum(denom, 1e-30)
    val = le_lo + (le_hi - le_lo) * frac
    # q<=0 -> lower bound of first bucket; q>=1 -> highest bound
    val = jnp.where(in_top, highest_finite, val)
    val = jnp.where(jnp.isneginf(le_lo), le_hi, val)  # le[0] <= 0 edge
    out = jnp.where(ok, val, jnp.nan)
    out = jnp.where(q < 0, -jnp.inf, out)
    out = jnp.where(q > 1, jnp.inf, out)
    return out


def quantile_parts(q: float) -> np.ndarray:
    """[2] f32, high part and low part, whose sum is ``q`` to f64's last
    bits: the two halves ``histogram_quantile_rows`` takes."""
    hi = np.float32(q)
    return np.array([hi, np.float32(float(q) - float(hi))], np.float32)


def _split12(x):
    """``(hi, lo)``, hi = x with its low 12 mantissa bits cleared (bit ops,
    not Veltkamp's ``4097 * x`` trick, which a fused multiply-add breaks):
    products of two such halves are exact in f32."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    hi = jax.lax.bitcast_convert_type(bits & jnp.int32(~0xFFF), jnp.float32)
    return hi, x - hi


def _less_rank(c, parts):
    """``c - rank`` to ~2^-35 of the rank: ``parts`` are the rank's exact
    partial products, largest first. ``c - parts[0]`` is exact where c is
    near the rank (Sterbenz), and each product is exact, so no rounding of
    a product — fused into a multiply-add or not — can move the result."""
    out = c - parts[0]
    for x in parts[1:]:
        out = out - x
    return out


def histogram_quantile_rows(q, q_lo, buckets, les):
    """``histogram_quantile`` with a bound vector per row: ``buckets``
    [R, J, W] cumulative counts (columns past a row's +Inf repeat its
    total), ``les`` [R, W] (+Inf from each row's own +Inf column on). The
    same rule as ``histogram_quantile``: linear interpolation inside the
    first bucket whose count reaches the rank, from 0 below the first
    bound; the +Inf bucket answers the highest finite bound.

    The rank ``q * total`` is never rounded to one f32: ``q`` + ``q_lo`` is
    the f64 quantile, and q and the total are split in 12-bit halves whose
    products are exact, so the bucket search and ``rank - c_lo`` are exact
    to the last bit of the counts. Where the rank falls by a bucket that
    holds one observation of a hundred thousand, an f32 rank alone would
    move the answer across that bucket."""
    W = buckets.shape[-1]
    total = buckets[..., -1]
    ok = (total > 0) & jnp.isfinite(total)
    qh, ql = _split12(jnp.clip(q, 0.0, 1.0))
    th, tl = _split12(total)
    qr = jnp.where((q >= 0) & (q <= 1), q_lo, 0.0)
    parts = (qh * th, qh * tl, ql * th, ql * tl, qr * total)
    meets = _less_rank(buckets, tuple(x[..., None] for x in parts)) >= 0
    idx = jnp.where(meets.any(-1), jnp.argmax(meets, axis=-1), W - 1)
    below = jnp.maximum(idx - 1, 0)
    c_hi = jnp.take_along_axis(buckets, idx[..., None], axis=-1)[..., 0]
    c_lo = jnp.where(
        idx > 0, jnp.take_along_axis(buckets, below[..., None], axis=-1)[..., 0],
        0.0)
    row_les = jnp.broadcast_to(les[:, None, :], buckets.shape)
    le_hi = jnp.take_along_axis(row_les, idx[..., None], axis=-1)[..., 0]
    le_lo = jnp.where(
        idx > 0, jnp.take_along_axis(row_les, below[..., None], axis=-1)[..., 0],
        0.0)
    frac = -_less_rank(c_lo, parts) / jnp.maximum(c_hi - c_lo, 1e-30)
    val = le_lo + (le_hi - le_lo) * frac
    val = jnp.where(jnp.isinf(le_hi), le_lo, val)
    out = jnp.where(ok, val, jnp.nan)
    out = jnp.where(q < 0, -jnp.inf, out)
    return jnp.where(q > 1, jnp.inf, out)


@jax.jit
def histogram_fraction(lower, upper, buckets, les):
    """promql histogram_fraction(lower, upper, h): fraction of observations in
    [lower, upper] (reference Histogram.scala fraction math)."""

    def cum_at(x):
        # interpolated cumulative count at value x
        B = buckets.shape[-1]
        xb = jnp.searchsorted(les, x)  # first le >= x
        xb = jnp.clip(xb, 0, B - 1)
        c_hi = jnp.take_along_axis(buckets, jnp.broadcast_to(xb, buckets.shape[:-1])[..., None], axis=-1)[..., 0]
        c_lo = jnp.where(
            xb > 0,
            jnp.take_along_axis(buckets, jnp.broadcast_to(jnp.maximum(xb - 1, 0), buckets.shape[:-1])[..., None], axis=-1)[..., 0],
            0.0,
        )
        le_hi = les[xb]
        le_lo = jnp.where(xb > 0, les[jnp.maximum(xb - 1, 0)], jnp.where(les[0] > 0, 0.0, -jnp.inf))
        w = jnp.where(jnp.isfinite(le_hi - le_lo), (x - le_lo) / jnp.maximum(le_hi - le_lo, 1e-30), 1.0)
        w = jnp.clip(w, 0.0, 1.0)
        return c_lo + (c_hi - c_lo) * w

    total = buckets[..., -1]
    frac = (cum_at(upper) - cum_at(lower)) / jnp.maximum(total, 1e-30)
    return jnp.where(total > 0, jnp.clip(frac, 0.0, 1.0), jnp.nan)


# histogram range functions the fused single-dispatch path supports (the
# hist_range_kernel dispatch set; "last" is the plain-selector read)
FUSED_HIST_FUNCS = frozenset({
    "rate", "increase", "delta", "sum_over_time", "last", "last_over_time",
})


@jax.named_scope("range_fn")
def _hist_range_shared(func, vals, lo, hi, t_first, t_last, out_t, window,
                       is_delta: bool):
    """Shared-regular-grid form of hist_range_kernel: every series shares
    ONE timestamp vector, so window boundaries are series-INDEPENDENT [J]
    vectors precomputed host-side (np.searchsorted) — no O(S*J*T) compare.
    Same math as hist_range_kernel over identical indices, so results are
    bit-identical to the general path on shared grids. Padded series rows
    get garbage values (count is series-independent); the fused epilogue's
    trash-group contract discards them."""
    f32 = vals.dtype
    T = vals.shape[1]
    cnt = (hi - lo).astype(f32)  # [J]
    has = (cnt > 0)[None, :, None]

    def gidx(idx):  # [S, J, B] gather at shared [J] sample indices
        return jnp.take(vals, jnp.clip(idx, 0, T - 1), axis=1)

    if func in ("last", "last_over_time"):
        return jnp.where(has, gidx(hi - 1), jnp.nan)
    if hist_window_form(func, is_delta) == "sums":
        # each window's sum as ONE product with its [J, T] 0/1 membership,
        # at HIGHEST: every bit of an f32 count kept (whole counts below
        # 2^24 sum exactly). A running sum over T costs the chip ~15x the
        # bytes, and its TPU lowering names its ops out of this scope.
        t = jnp.arange(T, dtype=lo.dtype)[None, :]
        member = ((t >= lo[:, None]) & (t < hi[:, None])).astype(f32)
        s = jnp.einsum("jt,stb->sjb", member, vals,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=f32)
        if func == "rate":
            s = s / (window.astype(f32) * 1e-3)
        return jnp.where(has, s, jnp.nan)
    if func in ("rate", "increase", "delta"):
        v_first = gidx(lo)
        v_last = gidx(hi - 1)
        dlt = v_last - v_first  # [S, J, B]
        factor = _shared_rate_factor(t_first, t_last, cnt, out_t, window, f32)
        res = dlt * factor[None, :, None]
        if func == "rate":
            res = res / (window.astype(f32) * 1e-3)
        return jnp.where((cnt >= 2)[None, :, None], res, jnp.nan)
    raise ValueError(f"unknown histogram range function {func}")


def _shared_rate_factor(t_first, t_last, cnt, out_t, window, f32):
    """[J] ``_rate_factor`` on a shared grid: one factor a step for every
    series (``_hist_range_shared`` and the base-2 body)."""
    tf = t_first.astype(f32) * 1e-3
    tl = t_last.astype(f32) * 1e-3
    sampled = tl - tf
    range_start = (out_t - window).astype(f32) * 1e-3
    range_end = out_t.astype(f32) * 1e-3
    return _rate_factor(sampled, tf - range_start, range_end - tl, cnt)


@jax.named_scope("range_fn")
def _hist_base2_shared(func, vals, lo, hi, t_first, t_last, out_t, window,
                       is_delta: bool):
    """The shared-grid hist body for base-2 exponential histograms: the rate
    family as ``(whole counts [S, J, B], factor [J])``, so the epilogue sums
    whole counts exactly and applies the factor once, after the sum —
    sum(rate) == rate of the summed counts, because the factor is the same
    for every series on a shared grid. A cumulative column's counts are
    each window's increase a bucket and its factor the extrapolation
    factor; a delta column's (and sum_over_time's) are each window's sum
    (below 2^24 a bucket: exact in f32, one product) and its factor 1 /
    window for ``rate``, else 1. Any other function: ``(_hist_range_shared's
    grid, None)``."""
    f32 = vals.dtype
    if hist_window_form(func, is_delta) == "sums":
        sums = _hist_range_shared("sum_over_time", vals, lo, hi, t_first,
                                  t_last, out_t, window, is_delta)
        factor = jnp.ones(lo.shape, f32)
        if func == "rate":
            factor = factor / (window.astype(f32) * 1e-3)
        return sums, factor
    if is_delta or func not in ("rate", "increase", "delta"):
        return _hist_range_shared(func, vals, lo, hi, t_first, t_last, out_t,
                                  window, is_delta), None
    T = vals.shape[1]
    cnt = (hi - lo).astype(f32)
    dlt = (jnp.take(vals, jnp.clip(hi - 1, 0, T - 1), axis=1)
           - jnp.take(vals, jnp.clip(lo, 0, T - 1), axis=1))
    factor = _shared_rate_factor(t_first, t_last, cnt, out_t, window, f32)
    if func == "rate":
        factor = factor / (window.astype(f32) * 1e-3)
    return jnp.where((cnt >= 2)[None, :, None], dlt, jnp.nan), factor


@jax.named_scope("range_fn")
def _hist_range_jitter(func, vals, dev, hwa, window, is_delta: bool):
    """Near-regular (jittered) grid form of hist_range_kernel: the SHARED
    certain-range boundary vectors [J] (clo/chi from the nominal grid,
    mxu_jitter.JitterWindowMatrices) replace the O(S*J*T) per-series
    boundary compare, and the <=1 uncertain slot per window boundary is
    resolved per series from the staged deviations — a handful of [S, J, B]
    gathers at shared slot indices. Window membership is EXACT (the same
    certain/uncertain decomposition as the scalar jitter kernel;
    PeriodicSamplesMapper.scala:256 contract), so results match the general
    kernel on the same data. ``hwa`` is the flat structure tuple
    (aggregations-side _hist_jwm_args order)."""
    (clo, chi, idx, count0, c0pos, has_klo, has_khi, F0_rel, L0_rel,
     Klo_rel, Khi_rel, blo_rel, ehi_rel) = hwa
    f32 = vals.dtype
    T = vals.shape[1]
    nan = jnp.nan

    def tk(x, i):  # x [S, T(, B)], shared [J] indices -> [S, J(, B)]
        return jnp.take(x, jnp.clip(i, 0, T - 1), axis=1)

    dKlo, dKhi = tk(dev, idx[3]), tk(dev, idx[4])
    in_lo = has_klo[None, :] & (dKlo > blo_rel[None, :])
    in_hi = has_khi[None, :] & (dKhi <= ehi_rel[None, :])
    cnt = count0[None, :] + in_lo + in_hi  # [S, J]
    has3 = (cnt > 0)[:, :, None]
    il3, ih3 = in_lo[:, :, None], in_hi[:, :, None]
    c0 = c0pos[None, :]
    c03 = c0pos[None, :, None]

    def w3(m1, a, m2, b_, c):
        return jnp.where(m1, a, jnp.where(m2, b_, c))

    if func in ("last", "last_over_time"):
        vL0, vKlo, vKhi = tk(vals, idx[1]), tk(vals, idx[3]), tk(vals, idx[4])
        return jnp.where(has3, w3(ih3, vKhi, c03, vL0, vKlo), nan)
    if hist_window_form(func, is_delta) == "sums":
        cs = jnp.cumsum(vals, axis=1)
        cs = jnp.concatenate([jnp.zeros_like(cs[:, :1]), cs], axis=1)
        s = (jnp.take(cs, jnp.clip(chi, 0, T), axis=1)
             - jnp.take(cs, jnp.clip(clo, 0, T), axis=1))
        vKlo, vKhi = tk(vals, idx[3]), tk(vals, idx[4])
        s = s + jnp.where(il3, vKlo, 0.0) + jnp.where(ih3, vKhi, 0.0)
        if func == "rate":
            s = s / (window.astype(f32) * 1e-3)
        return jnp.where(has3, s, nan)
    if func in ("rate", "increase", "delta"):
        vF0, vL0 = tk(vals, idx[0]), tk(vals, idx[1])
        vKlo, vKhi = tk(vals, idx[3]), tk(vals, idx[4])
        dF0, dL0 = tk(dev, idx[0]), tk(dev, idx[1])
        v_first = w3(il3, vKlo, c03, vF0, vKhi)
        v_last = w3(ih3, vKhi, c03, vL0, vKlo)
        # boundary times RELATIVE to each window's start (f32 ms — same
        # precision contract as the scalar jitter kernel)
        tf_rel = w3(in_lo, Klo_rel[None, :] + dKlo, c0,
                    F0_rel[None, :] + dF0, Khi_rel[None, :] + dKhi)
        tl_rel = w3(in_hi, Khi_rel[None, :] + dKhi, c0,
                    L0_rel[None, :] + dL0, Klo_rel[None, :] + dKlo)
        dlt = v_last - v_first  # [S, J, B]
        factor = _rate_factor((tl_rel - tf_rel) * 1e-3, tf_rel * 1e-3,
                              (window.astype(f32) - tl_rel) * 1e-3, cnt)
        res = dlt * factor[:, :, None]
        if func == "rate":
            res = res / (window.astype(f32) * 1e-3)
        return jnp.where((cnt >= 2)[:, :, None], res, nan)
    raise ValueError(f"unknown histogram range function {func}")


def run_hist_range_function(
    func: str, block: StagedBlock, params: RangeParams, is_delta: bool = False
):
    j_pad = pad_steps(params.num_steps)
    start_off = np.int32(params.start_ms - block.base_ms)
    return hist_range_kernel(
        func,
        block.ts,
        block.vals,
        block.lens,
        start_off,
        np.int32(params.step_ms),
        np.int32(params.window_ms),
        j_pad,
        is_delta=is_delta,
    )


# kernel-observatory registration (obs/kernels.py; linted by
# tools/check_metrics.py — every jit wrapper here must register). The fused
# hist programs are compositions of ops/aggregations._fused_program_jit.
def _register_kernel_observatory() -> None:
    from ..obs.kernels import KERNELS

    KERNELS.register_jits(
        "ops.hist_kernels",
        hist_range_kernel=hist_range_kernel,
        histogram_quantile=histogram_quantile,
        histogram_fraction=histogram_fraction,
    )


_register_kernel_observatory()
