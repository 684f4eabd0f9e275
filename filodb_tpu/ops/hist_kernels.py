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
    if func == "sum_over_time" or (is_delta and func in ("rate", "increase")):
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
        dur_start = tf - range_start
        dur_end = range_end - tl
        avg_dur = sampled / jnp.maximum(cnt - 1.0, 1.0)
        thresh = avg_dur * 1.1
        dur_start = jnp.where(dur_start >= thresh, avg_dur / 2.0, dur_start)
        dur_end = jnp.where(dur_end >= thresh, avg_dur / 2.0, dur_end)
        factor = (sampled + dur_start + dur_end) / jnp.maximum(sampled, 1e-30)
        res = dlt * factor[:, :, None]
        if func == "rate":
            res = res / (window.astype(f32) * 1e-3)
        return jnp.where((cnt >= 2)[:, :, None], res, jnp.nan)
    raise ValueError(f"unknown histogram range function {func}")


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
    if func == "sum_over_time" or (is_delta and func in ("rate", "increase")):
        cs = jnp.cumsum(vals, axis=1)
        cs = jnp.concatenate([jnp.zeros_like(cs[:, :1]), cs], axis=1)
        s = (jnp.take(cs, jnp.clip(hi, 0, T), axis=1)
             - jnp.take(cs, jnp.clip(lo, 0, T), axis=1))
        if func == "rate":
            s = s / (window.astype(f32) * 1e-3)
        return jnp.where(has, s, jnp.nan)
    if func in ("rate", "increase", "delta"):
        v_first = gidx(lo)
        v_last = gidx(hi - 1)
        dlt = v_last - v_first  # [S, J, B]
        tf = t_first.astype(f32) * 1e-3  # [J]
        tl = t_last.astype(f32) * 1e-3
        sampled = tl - tf
        range_start = (out_t - window).astype(f32) * 1e-3
        range_end = out_t.astype(f32) * 1e-3
        dur_start = tf - range_start
        dur_end = range_end - tl
        avg_dur = sampled / jnp.maximum(cnt - 1.0, 1.0)
        thresh = avg_dur * 1.1
        dur_start = jnp.where(dur_start >= thresh, avg_dur / 2.0, dur_start)
        dur_end = jnp.where(dur_end >= thresh, avg_dur / 2.0, dur_end)
        factor = (sampled + dur_start + dur_end) / jnp.maximum(sampled, 1e-30)
        res = dlt * factor[None, :, None]
        if func == "rate":
            res = res / (window.astype(f32) * 1e-3)
        return jnp.where((cnt >= 2)[None, :, None], res, jnp.nan)
    raise ValueError(f"unknown histogram range function {func}")


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
    if func == "sum_over_time" or (is_delta and func in ("rate", "increase")):
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
        sampled = (tl_rel - tf_rel) * 1e-3
        dur_start = tf_rel * 1e-3
        dur_end = (window.astype(f32) - tl_rel) * 1e-3
        avg_dur = sampled / jnp.maximum(cnt - 1.0, 1.0)
        thresh = avg_dur * 1.1
        ds = jnp.where(dur_start >= thresh, avg_dur / 2.0, dur_start)
        de = jnp.where(dur_end >= thresh, avg_dur / 2.0, dur_end)
        factor = (sampled + ds + de) / jnp.maximum(sampled, 1e-30)
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
