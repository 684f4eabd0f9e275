"""Cross-series aggregation kernels (reference L4: query/exec/aggregator/ —
RowAggregator SPI with Sum/Min/Max/Count/Avg/Stddev/Stdvar/TopK/Quantile/
CountValues/Group over RangeVectors, AggrOverRangeVectors.scala mapReduce).

The reference map-reduces per-series rows through per-aggregator state
machines; here ``sum by (labels)`` is a masked segment-reduce over the
``[S, J]`` result grid — one jit call for all steps and all groups — and
cross-shard merging becomes a psum over the mesh (parallel/).

NaN = absence everywhere: a NaN sample doesn't contribute, and a group with
no members at a step yields NaN.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..metrics import (
    REGISTRY, current_span, record_fused_fallback, record_kernel_dispatch,
)
from ..singleflight import memo_on
from .hist_kernels import (
    _hist_base2_shared,
    _hist_range_jitter,
    _hist_range_shared,
    base2_select_rule,
    bf16_pieces,
    hist_range_kernel,
    hist_window_form,
    histogram_quantile,
    histogram_quantile_rows,
    quantile_parts,
)
from .kernels import pad_steps, range_kernel
from .mxu_jitter import (
    jitter_masked_kernel,
    jitter_masked_minmax,
    jitter_minmax,
    jitter_range_kernel,
    jitter_window_matrices,
    masked_window_matrices,
)
from .mxu_kernels import fetch_strategy, mxu_range_kernel, window_matrices
from .staging import grid_class, replicated_put, series_put

SIMPLE_AGG_OPS = ("sum", "count", "avg", "min", "max", "stddev", "stdvar", "group")


def segment_aggregate(op: str, values, group_ids, num_groups: int):
    """values [S, J] (NaN = absent), group_ids [S] int32 -> [G, J].

    Instrumented entry point: per-op dispatch latency + JIT cache hit/miss
    (metrics.record_kernel_dispatch) around the jitted kernel."""
    t0 = time.perf_counter()
    before = _segment_aggregate_jit._cache_size()
    out = _segment_aggregate_jit(op, values, group_ids, num_groups)
    s_, j_ = np.shape(values)
    record_kernel_dispatch(
        f"segment_{op}", time.perf_counter() - t0,
        compiled=_segment_aggregate_jit._cache_size() > before,
        key={"variant": "general", "epilogue": f"agg:{op}",
             "shapes": f"S{s_}xJ{j_}xG{num_groups}"},
    )
    return out


# -- the wide sum -------------------------------------------------------------
#
# jax.ops.segment_sum adds a group's rows one after another in f32. Where the
# rows are the samples themselves (a value-returning range function over
# 100 000 counters that all read about 1e9), the running sum soon has an ulp
# far above the low bits every addend shares, each add rounds the same way,
# and the error grows with the rows: 1.06e-3 of the answer at 100 000 series
# on a v5e (PERF.md 6, PR 28). The wide sum splits each value on a grid the
# whole step shares into WIDE_PIECES signed integers of WIDE_PIECE_BITS bits
# and a small f32 remainder. Sums of such integers are exact in int32
# whatever the order (and the same on any mesh or batch), so only the
# remainder and ONE final rounding are left of f32.

# range functions that return sample values, or window sums of them
WIDE_SUM_FUNCS = frozenset({
    "last", "last_over_time", "first_over_time", "avg_over_time",
    "sum_over_time", "min_over_time", "max_over_time",
})
WIDE_PIECE_BITS = 7  # |piece| <= 64: an int8, and 2**24 rows fit an int32
WIDE_PIECES = 4      # 27 bits below the step's largest value; the rest is f32
# at most this many groups (trash group included) sum as a one-hot matmul on
# the MXU; more would make the [G, S] one-hot the larger operand
WIDE_ONEHOT_MAX_GROUPS = 128


def reduce_form(func: str, epilogue: tuple, num_groups: int) -> str:
    """``"wide"`` or ``"plain"`` for one fused dispatch. Wide: a sum / avg
    over series of a value-returning range function, whatever the number of
    groups (the digits need it); and, on a device with an MXU, every other
    sum / avg of up to WIDE_ONEHOT_MAX_GROUPS groups, because there the wide
    sum's int8 one-hot matmul is also the faster reduce (0.94 ms against
    the segment_sum's 2.38 over 131072 rows on a v5e: PERF.md 6, PR 28).
    min / max / count / topk / quantile accumulate nothing: plain."""
    if epilogue[:1] != ("agg",) or epilogue[1] not in ("sum", "avg"):
        return "plain"
    if func in WIDE_SUM_FUNCS:
        return "wide"
    few = num_groups + 1 <= WIDE_ONEHOT_MAX_GROUPS  # the trash group rides along
    return "wide" if few and _has_mxu() else "plain"


def _has_mxu() -> bool:
    return jax.default_backend() == "tpu"


def _with_reduce_form(func: str, epilogue: tuple, num_groups: int) -> tuple:
    """The epilogue statics with the reduction's form made explicit:
    ``("agg", op)`` -> ``("agg", op, "wide")`` where reduce_form says so. The
    form is part of the executable's identity (a static jit argument and
    the kernel observatory's ``epilogue`` key, ``agg:avg:wide``). Every
    fused dispatch counts its form: filodb_group_reduce_total{form}."""
    form = reduce_form(func, epilogue, num_groups)
    REGISTRY.counter("filodb_group_reduce", form=form).inc()
    return epilogue + ("wide",) if form == "wide" else epilogue


@jax.named_scope("wide_sum")
def _wide_aggregate(op: str, v0, valid, gids, num_groups: int,
                    axis: str | None = None):
    """[G, J] ``sum`` or ``avg`` per group of ``v0`` [S, J] (absent = 0,
    flagged by ``valid``; a group with no member at a step yields NaN), the
    sum as exact as one f32 rounding. With ``axis`` the rows are one
    device's band of a series-sharded grid and the integer sums combine by
    psum, still exactly."""
    f32, i8 = jnp.float32, jnp.int8
    J = v0.shape[1]
    m = jnp.max(jnp.abs(v0), axis=0)  # [J]: the step's largest magnitude
    if axis is not None:
        m = jax.lax.pmax(m, axis)
    # a step that holds an infinity has no digits to keep: it goes through
    # the flags below, and its finite part through the remainder alone
    step_ok = jnp.isfinite(m)
    _, e = jnp.frexp(jnp.where(step_ok, m, 0.0))  # |v| < 2**e
    # (every scale below stays a normal f32)
    e = jnp.clip(e + 1, WIDE_PIECE_BITS * WIDE_PIECES - 126, 127)
    step_ok = step_ok[None, :]
    r = jnp.where(step_ok, v0, 0.0)
    lanes, scales = [], []
    for k in range(1, WIDE_PIECES + 1):
        up = jnp.ldexp(f32(1.0), WIDE_PIECE_BITS * k - e)
        dn = jnp.ldexp(f32(1.0), e - WIDE_PIECE_BITS * k)
        p = jnp.round(r * up[None, :])  # |p| <= 64, every product exact
        r = r - p * dn[None, :]
        lanes.append(p.astype(i8))
        scales.append(dn)
    r = jnp.where(step_ok, r, jnp.where(jnp.isfinite(v0), v0, 0.0))
    lanes += [valid.astype(i8), (v0 == jnp.inf).astype(i8),
              (v0 == -jnp.inf).astype(i8)]
    ints = jnp.concatenate(lanes, axis=1)  # [S, 7J] int8
    if num_groups <= WIDE_ONEHOT_MAX_GROUPS:
        member = gids[None, :] == jnp.arange(num_groups, dtype=gids.dtype)[:, None]
        isum = jax.lax.dot(member.astype(i8), ints,
                           preferred_element_type=jnp.int32)
        rsum = jax.lax.dot(member.astype(f32), r,
                           precision=jax.lax.Precision.HIGHEST)
    else:
        isum = jax.ops.segment_sum(ints.astype(jnp.int32), gids, num_groups)
        rsum = jax.ops.segment_sum(r, gids, num_groups)
    if axis is not None:
        isum, rsum = jax.lax.psum(isum, axis), jax.lax.psum(rsum, axis)
    total = rsum
    for k in reversed(range(WIDE_PIECES)):  # smallest first: one rounding counts
        total = total + isum[:, k * J:(k + 1) * J].astype(f32) * scales[k][None, :]
    count, pinf, ninf = (isum[:, k * J:(k + 1) * J]
                         for k in range(WIDE_PIECES, WIDE_PIECES + 3))
    total = jnp.where(pinf > 0, jnp.where(ninf > 0, jnp.nan, jnp.inf),
                      jnp.where(ninf > 0, -jnp.inf, total))
    if op == "avg":
        total = total / jnp.maximum(count, 1).astype(f32)
    return jnp.where(count > 0, total, jnp.nan)


@functools.partial(jax.jit, static_argnames=("op", "num_groups", "wide"))
@jax.named_scope("group_reduce")
def _segment_aggregate_jit(op: str, values, group_ids, num_groups: int,
                           wide: bool = False):
    valid = ~jnp.isnan(values)
    v0 = jnp.where(valid, values, 0.0)
    if wide and op in ("sum", "avg"):
        return _wide_aggregate(op, v0, valid, group_ids, num_groups)
    count = jax.ops.segment_sum(valid.astype(values.dtype), group_ids, num_groups)
    has = count > 0
    if op == "count":
        return jnp.where(has, count, jnp.nan)
    if op == "group":
        return jnp.where(has, 1.0, jnp.nan)
    if op in ("sum", "avg", "stddev", "stdvar"):
        s = jax.ops.segment_sum(v0, group_ids, num_groups)
        if op == "sum":
            return jnp.where(has, s, jnp.nan)
        mean = s / jnp.maximum(count, 1.0)
        if op == "avg":
            return jnp.where(has, mean, jnp.nan)
        dev = jnp.where(valid, (values - mean[group_ids]) ** 2, 0.0)
        var = jax.ops.segment_sum(dev, group_ids, num_groups) / jnp.maximum(count, 1.0)
        return jnp.where(has, var if op == "stdvar" else jnp.sqrt(var), jnp.nan)
    if op in ("min", "max"):
        big = jnp.inf if op == "min" else -jnp.inf
        vm = jnp.where(valid, values, big)
        r = (
            jax.ops.segment_min(vm, group_ids, num_groups)
            if op == "min"
            else jax.ops.segment_max(vm, group_ids, num_groups)
        )
        return jnp.where(has, r, jnp.nan)
    raise ValueError(f"unknown aggregation {op}")


@jax.named_scope("group_reduce")
def _segment_psum_axis(op: str, grid, gids, num_groups: int, axis: str,
                       wide: bool = False):
    """Local segment-reduce + collective combine over a mesh axis: the
    device-local half of ``segment_aggregate`` followed by psum/pmin/pmax,
    so a series-sharded [S_local, J] grid reduces to the REPLICATED [G, J]
    partials inside one program. Semantics mirror _segment_aggregate_jit
    exactly (NaN = absence; a group with no members anywhere yields NaN;
    ``wide`` sums exactly, see _wide_aggregate).
    The ONE definition shared by the sharded fused path and the parallel/
    mesh engines (parallel.mesh._segment_psum delegates here)."""
    valid = ~jnp.isnan(grid)
    v0 = jnp.where(valid, grid, 0.0)
    if wide and op in ("sum", "avg"):
        return _wide_aggregate(op, v0, valid, gids, num_groups, axis)
    psum = jax.lax.psum
    c = psum(
        jax.ops.segment_sum(valid.astype(jnp.float32), gids, num_groups), axis
    )
    if op in ("sum", "avg", "count"):
        s = psum(jax.ops.segment_sum(v0, gids, num_groups), axis)
        if op == "sum":
            return jnp.where(c > 0, s, jnp.nan)
        if op == "count":
            return jnp.where(c > 0, c, jnp.nan)
        return jnp.where(c > 0, s / jnp.maximum(c, 1.0), jnp.nan)
    if op in ("min", "max"):
        big = jnp.inf if op == "min" else -jnp.inf
        vm = jnp.where(valid, grid, big)
        if op == "min":
            r = jax.lax.pmin(jax.ops.segment_min(vm, gids, num_groups), axis)
        else:
            r = jax.lax.pmax(jax.ops.segment_max(vm, gids, num_groups), axis)
        return jnp.where(c > 0, r, jnp.nan)
    raise ValueError(f"unsupported sharded aggregation {op}")


# ---------------------------------------------------------------------------
# fused range-function -> segment-aggregate (single-dispatch cross-shard path)
# ---------------------------------------------------------------------------

# range functions the fused MXU variant handles directly (the subset of
# mxu_kernels.MXU_FUNCS that needs no extra lazily-built window structures)
FUSED_MXU_FUNCS = {
    "sum_over_time", "count_over_time", "avg_over_time", "last",
    "last_over_time", "first_over_time", "present_over_time",
    "stddev_over_time", "stdvar_over_time", "z_score",
    "rate", "increase", "delta", "idelta", "irate",
}

# range functions the fused JITTER/MASKED variants handle: the mxu_jitter
# set plus min/max_over_time, which ride dedicated fused minmax programs
# (tile hierarchy + edge one-hots, built lazily via wm.ensure_minmax) —
# jittered/holey grids stay ONE fast fused dispatch for them too
FUSED_JITTER_FUNCS = FUSED_MXU_FUNCS | {"min_over_time", "max_over_time"}


def _grid_variant(block, func: str, is_delta: bool):
    """Kernel-variant ladder for one fused dispatch, decided from the
    (super)block's grid classification (staging.grid_class) and the
    function: ``mxu`` (exact shared grid, window matmuls) > ``jitter``
    (near-regular: certain-membership matmul + per-series boundary
    corrections, ops/mxu_jitter) > ``masked`` (near-regular with missed
    scrapes: validity-masked sidecar) > ``general``. The ONE selection
    (through _fused_body) of the single-query dispatch and the cross-query
    batcher — a batched lane MUST compute through the same variant its
    unbatched execution would, or batched-vs-sequential parity breaks.

    Returns ``(variant, degrade_reason)``: ``degrade_reason`` is a
    fused-fallback taxonomy entry (``grid_jitter``/``grid_holes``) set only
    when a jittered/holey grid is truly unsupported by its fast variant
    (function outside FUSED_JITTER_FUNCS) and the dispatch degrades to the
    multi-pass general kernel — still ONE fused dispatch, just slower."""
    if not (is_delta and func in ("irate", "idelta")):
        if block.regular_ts is not None:
            if func in FUSED_MXU_FUNCS:
                return "mxu", None
        elif block.nominal_ts is not None:
            if func in FUSED_JITTER_FUNCS:
                return "jitter", None
            return "general", "grid_jitter"
        elif getattr(block, "mgrid", None) is not None:
            if func in FUSED_JITTER_FUNCS:
                return "masked", None
            return "general", "grid_holes"
    return "general", None


def _pallas_variant(block, func: str, mesh) -> bool:
    """Whether a general-path dispatch should promote to the fused Pallas
    gather-scan backend: single-device, a truly IRREGULAR grid (regular /
    near-regular / masked grids have cheaper structured variants), a
    function the Pallas finisher models, and the shared FILODB_PALLAS
    policy (pallas_kernels.pallas_enabled — the same predicate the legacy
    range-function dispatch applies, so the two paths can't drift)."""
    if mesh is not None:
        return False
    if (block.regular_ts is not None or block.nominal_ts is not None
            or getattr(block, "mgrid", None) is not None):
        return False
    from .pallas_kernels import PALLAS_FUNCS, pallas_enabled

    return func in PALLAS_FUNCS and pallas_enabled(block.ts.shape[1])


def _jwm_args(wm) -> tuple:
    """The jitter window structure as ONE flat tuple in
    jitter_range_kernel's positional order (a pytree jit argument — one
    signature for the plain/sharded/batched fused jitter programs)."""
    return (wm.d_W0, wm.d_SEL, wm.d_idx, wm.d_count0, wm.d_c0pos,
            wm.d_c0ge2, wm.d_has_klo, wm.d_has_khi, wm.d_F0_rel,
            wm.d_L0_rel, wm.d_L2_rel, wm.d_Klo_rel, wm.d_Khi_rel,
            wm.d_blo_rel, wm.d_ehi_rel)


def _mwm_args(wm) -> tuple:
    """Masked-grid window structure tuple (jitter_masked_kernel order)."""
    return (wm.d_W0, wm.d_SEL, wm.d_idx, wm.d_c0pos, wm.d_has_klo,
            wm.d_has_khi, wm.d_F0_rel, wm.d_L0_rel, wm.d_Klo_rel,
            wm.d_Khi_rel, wm.d_blo_rel, wm.d_ehi_rel)


def _jmm_args(wm) -> tuple:
    """The minmax window structure as ONE flat tuple in jitter_minmax's
    positional order (requires wm.ensure_minmax() first — the tile/edge
    structures build lazily)."""
    return (wm.d_SEL, wm.d_idx, wm.d_tile_mask, wm.d_edge_onehot,
            wm.d_edge_valid, wm.d_edge_idx, wm.d_count0, wm.d_has_klo,
            wm.d_has_khi, wm.d_blo_rel, wm.d_ehi_rel)


def _mmm_args(wm) -> tuple:
    """Masked-grid minmax structure tuple (jitter_masked_minmax order:
    the grid-level c0pos replaces the per-window certain count)."""
    return (wm.d_SEL, wm.d_idx, wm.d_tile_mask, wm.d_edge_onehot,
            wm.d_edge_valid, wm.d_edge_idx, wm.d_c0pos, wm.d_has_klo,
            wm.d_has_khi, wm.d_blo_rel, wm.d_ehi_rel)


def _mgrid_args(g) -> tuple:
    """A block's masked sidecar arrays as ONE flat tuple in
    jitter_masked_kernel's positional order (vals..bfraw)."""
    raw = g.raw if g.raw is not None else g.vals
    bfraw = g.bfraw if g.bfraw is not None else g.bfv
    return (g.vals, g.dev, raw, g.valid, g.cc, g.ffv, g.ffd, g.bfv, g.bfd,
            g.ff2v, g.ff2d, bfraw)


def _hist_jwm_args(wm) -> tuple:
    """Jitter window structure in hist_kernels._hist_range_jitter's order:
    shared certain-range boundaries + the uncertain-slot selections."""
    return (wm.d_clo, wm.d_chi, wm.d_idx, wm.d_count0, wm.d_c0pos,
            wm.d_has_klo, wm.d_has_khi, wm.d_F0_rel, wm.d_L0_rel,
            wm.d_Klo_rel, wm.d_Khi_rel, wm.d_blo_rel, wm.d_ehi_rel)


def _hist_shared_windows(block, start_off: int, step_ms: int, j_pad: int,
                         window_ms: int, mesh):
    """Host-precomputed [J] searchsorted window-boundary vectors for a
    shared-regular-grid histogram (super)block, memoized device-resident on
    the block (the O(S*J*T) per-series boundary compare never runs for
    scraped histograms), then the window itself: the hist_shared body's
    window operands in _hist_range_shared's order."""
    key = (start_off, int(step_ms), j_pad, int(window_ms), mesh is not None)

    def build_windows():
        m = int(np.asarray(block.lens)[0])
        tsv = np.asarray(block.regular_ts)[:m].astype(np.int64)
        out_t = start_off + np.arange(j_pad, dtype=np.int64) * int(step_ms)
        hi = np.searchsorted(tsv, out_t, side="right").astype(np.int32)
        lo = np.searchsorted(
            tsv, out_t - int(window_ms), side="right"
        ).astype(np.int32)
        t_first = tsv[np.minimum(lo, m - 1)].astype(np.int32)
        t_last = tsv[np.minimum(hi - 1, m - 1)].astype(np.int32)
        put = replicated_put(mesh)
        return (put(lo), put(hi), put(t_first), put(t_last),
                put(out_t.astype(np.int32)))

    return memo_on(block, "_hist_win_cache", key, build_windows) + (
        np.int32(window_ms),)


@jax.named_scope("epilogue")
def _apply_epilogue(sj, epilogue: tuple, gids, n_real, qv, num_groups: int):
    """Device-side epilogue over the [S, J] range grid, INSIDE the same
    compiled program as the range kernel. ``epilogue`` is a static tuple:

      ("agg", op)          -> [G, J] segment aggregate; ("agg", op, "wide")
                              sums exactly (_with_reduce_form decides)
      ("topk", k, bottom)  -> ([k, J] values, [k, J] i32 series indices):
                              per-step top/bottom-k across series, the
                              compact form of ``topk_mask`` — only O(k*J)
                              crosses to the host, never [S, J]
      ("quantile",)        -> [G, J] per-(group, step) quantile at ``qv``
                              (``segment_quantile`` inside the jit boundary)

    ``gids`` follows the trash-group contract (padded rows -> group
    ``num_groups``); ``n_real`` additionally masks padded rows for the
    non-segmented epilogues (count/present-style functions yield REAL
    values on padded rows in the MXU kernel variant, which a top-k would
    otherwise happily select)."""
    kind = epilogue[0]
    if kind == "agg":
        return _segment_aggregate_jit(
            epilogue[1], sj, gids, num_groups + 1, wide="wide" in epilogue[2:],
        )[:num_groups]
    S, J = sj.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (S, J), 0)
    sj = jnp.where(rows < n_real, sj, jnp.nan)
    if kind == "topk":
        _, k, bottom = epilogue
        v = jnp.where(jnp.isnan(sj), jnp.inf if bottom else -jnp.inf, sj)
        vt = v.T if not bottom else -v.T  # [J, S], larger = better
        top_vals, top_idx = jax.lax.top_k(vt, min(k, S))  # [J, kk]
        vals = jnp.where(
            jnp.isfinite(top_vals),
            top_vals if not bottom else -top_vals,
            jnp.nan,
        )
        return vals.T, top_idx.T.astype(jnp.int32)  # [kk, J] each
    if kind == "quantile":
        return segment_quantile(sj, gids, num_groups + 1, qv)[:num_groups]
    raise ValueError(f"unknown fused epilogue {epilogue}")


@jax.named_scope("epilogue")
def _sharded_epilogue(sj, epilogue: tuple, gids_l, n_real, qv,
                      num_groups: int, axis: str):
    """Device-local half of _apply_epilogue inside a shard_map body, with
    the cross-device combine fused into the SAME program:

      ("agg", op)          -> local segment reduce + psum/pmin/pmax -> [G, J]
      ("topk", k, bottom)  -> local top-k winners (values + GLOBAL series
                              indices), all_gather'd and re-reduced to the
                              global [k, J] winner set — O(D*k*J) on the
                              interconnect, never the [ΣS, J] grid
      ("quantile",)        -> exact quantile needs the full value multiset
                              per group: all_gather the [S_l, J] rows (the
                              one epilogue that moves O(ΣS*J) over ICI,
                              still inside the single program) and sort

    Padded-row handling matches the single-device contract: trash-group
    gids for segment reduces; GLOBAL row index vs ``n_real`` for the
    non-segmented epilogues (a device's local rows map to global rows
    ``axis_index * S_local + i``)."""
    kind = epilogue[0]
    if kind == "agg":
        return _segment_psum_axis(
            epilogue[1], sj, gids_l, num_groups + 1, axis,
            wide="wide" in epilogue[2:],
        )[:num_groups]
    S_l, J = sj.shape
    d = jax.lax.axis_index(axis)
    rows = jax.lax.broadcasted_iota(jnp.int32, (S_l, J), 0) + d * S_l
    sj = jnp.where(rows < n_real, sj, jnp.nan)
    if kind == "topk":
        _, k, bottom = epilogue
        v = jnp.where(jnp.isnan(sj), jnp.inf if bottom else -jnp.inf, sj)
        vt = v.T if not bottom else -v.T  # [J, S_l], larger = better
        kk = min(k, S_l)
        lv, li = jax.lax.top_k(vt, kk)  # [J, kk] local winners
        gi = li.astype(jnp.int32) + d * S_l  # global series indices
        av = jax.lax.all_gather(lv, axis)  # [D, J, kk]
        ai = jax.lax.all_gather(gi, axis)
        D = av.shape[0]
        av = jnp.transpose(av, (1, 0, 2)).reshape(J, D * kk)
        ai = jnp.transpose(ai, (1, 0, 2)).reshape(J, D * kk)
        k2 = min(k, D * kk)  # == single-device min(k, S_pad)
        fv, fi = jax.lax.top_k(av, k2)  # [J, k2] global winners
        gidx = jnp.take_along_axis(ai, fi, axis=1)
        vals = jnp.where(
            jnp.isfinite(fv), fv if not bottom else -fv, jnp.nan
        )
        return vals.T, gidx.T.astype(jnp.int32)  # [k2, J] each
    if kind == "quantile":
        full = jax.lax.all_gather(sj, axis).reshape(-1, J)  # [ΣS, J]
        full_g = jax.lax.all_gather(gids_l, axis).reshape(-1)
        return segment_quantile(full, full_g, num_groups + 1, qv)[:num_groups]
    raise ValueError(f"unknown fused epilogue {epilogue}")


def _sharded_out_specs(epilogue: tuple):
    return (P(), P()) if epilogue[0] == "topk" else P()


def _hist_epilogue(sjb, epilogue: tuple, gids, les, qv, num_groups: int):
    """The hist family's epilogue over the [S, J, B] range grid: per-bucket
    segment-sum, then (``epilogue == ("hist", "quantile")``) the device-side
    histogram_quantile interpolation — only the [G, J, B] group partials, or
    just the [G, J] quantile grid, exist as program outputs. ``gids``
    follows the trash-group contract (padded rows -> group ``num_groups``);
    per-bucket summation is the flattened [S, J*B] form of the same segment
    reduce the reference partial-merge path runs, so the two paths agree
    bit-for-bit on identical schemes."""
    S, J, B = sjb.shape
    gjb = _segment_aggregate_jit(
        "sum", sjb.reshape(S, J * B), gids, num_groups + 1
    )[:num_groups].reshape(num_groups, J, B)
    if epilogue[1] == "quantile":
        return histogram_quantile(qv, gjb, les)
    return gjb


def _hist_sharded_combine(sjb, epilogue: tuple, gids_l, les, qv,
                          num_groups: int, axis: str):
    """Device-local half of _hist_epilogue inside a shard_map body: local
    per-bucket segment-sum + psum over the mesh axis, then the (optional)
    histogram_quantile interpolation on the REPLICATED [G, J, B] partials —
    the whole hist pipeline stays one multi-device program. NaN-absence
    semantics match _segment_aggregate_jit's "sum" (a group with no members
    anywhere is NaN), via psum'd validity counts."""
    S, J, B = sjb.shape
    with jax.named_scope("group_reduce"):
        flat = sjb.reshape(S, J * B)
        valid = ~jnp.isnan(flat)
        s = jax.ops.segment_sum(
            jnp.where(valid, flat, 0.0), gids_l, num_groups + 1
        )
        c = jax.ops.segment_sum(
            valid.astype(flat.dtype), gids_l, num_groups + 1)
        s = jax.lax.psum(s, axis)
        c = jax.lax.psum(c, axis)
        gjb = jnp.where(c > 0, s, jnp.nan)[:num_groups].reshape(
            num_groups, J, B
        )
    if epilogue[1] == "quantile":
        return histogram_quantile(qv, gjb, les)
    return gjb


# -- base-2 exponential histograms: a scale and an offset a series ----------
#
# A block of such series stages as ONE [S, T, B] block (B =
# core.histograms.BASE2_WIDTH, each row at its own width n + 2, zeros behind)
# with [S] int32 sidecars scale / offset / n. The range body is the hist
# family's own, at each series' own scheme: on a shared grid hist_shared's
# ``base2_grid`` (each window's whole-count increase a bucket and the [J]
# rate factor every series shares), elsewhere the per-series rates. The
# epilogue ("hist2", kind, W) then merges every series onto its group's
# smallest scale and sums by group, both as exact contractions on the MXU:
# each value cut into three bf16 pieces, the merge a product with a [B, W]
# 0/1 selection a series (one 1 a column), the sum a product with the
# [G, S] 0/1 membership (segment_sum past WIDE_ONEHOT_MAX_GROUPS groups),
# both accumulated in f32 — and, for the quantile, interpolates on each
# group's own bounds. Explicit-bucket blocks keep ("hist", ...) and
# hist_shared's own grid: their program does not change.


@functools.partial(jax.jit, static_argnames=("num_groups",))
@jax.named_scope("hist_rescale")
def _base2_group_scheme(scale, offset, n, gids, num_groups: int):
    """Each group's ``(scale_g, offset_g, K_g)`` ([G + 1] int32, the trash
    group last) — the smallest scale among its series and the join of their
    index ranges downscaled to it (core.histograms.merge_base2) — and how
    many real series sit above their group's scale."""
    G1 = num_groups + 1
    s_g = jax.ops.segment_min(scale, gids, G1)
    d = scale - s_g[gids]
    has = n > 0
    big = jnp.int32(1 << 30)
    lo = jnp.where(has, jnp.right_shift(offset, d), big)
    hi = jnp.where(has, jnp.right_shift(offset + n - 1, d), -big)
    o_g = jax.ops.segment_min(lo, gids, G1)
    top = jax.ops.segment_max(hi, gids, G1)
    empty = o_g >= big
    k_g = jnp.where(empty, 0, top - o_g + 1)
    rescaled = jnp.sum(((d > 0) & (gids < num_groups)).astype(jnp.int32))
    return s_g, jnp.where(empty, 0, o_g), k_g, rescaled


def pad8(n: int) -> int:
    """A bucket width up to a multiple of 8 (fewer executable shapes)."""
    return max(8, -(-n // 8) * 8)


def base2_group_plan(block, gids_dev, num_groups: int, scheme_dev, key):
    """``(device (scale_g, offset_g, K_g, bounds), W, group schemes,
    rescaled)`` of one grouping of a base-2 block: the group reduction runs
    on the device once a (block, grouping) and is memoised on the block as
    the group ids are; W (the static output width, the widest group's K + 2
    to a multiple of 8), the groups' schemes and one count come back, and
    each group's [W] bounds go up once, computed in f64 and rounded once
    (an f32 exp2 on the device is ~2.5e-6 off; my chip runs, PR 40)."""
    from ..core.histograms import Base2Scheme, base2_les_rows

    def build():
        _tag_memo_miss()
        s_g, o_g, k_g, rescaled = _base2_group_scheme(
            *scheme_dev, gids_dev, num_groups)
        s_h, o_h, k_h = (np.asarray(a)[:num_groups] for a in (s_g, o_g, k_g))
        width = pad8(int(k_h.max(initial=0)) + 2)
        schemes = [Base2Scheme(int(a), int(b), int(c))
                   for a, b, c in zip(s_h, o_h, k_h)]
        les = jnp.asarray(base2_les_rows(schemes, width).astype(np.float32))
        return (s_g, o_g, k_g, les), width, schemes, int(rescaled)

    return memo_on(block, "_base2_plan", key, build)


# a block whose every value is a whole number below this in magnitude reads
# a cumulative window's increase as one +-1 product, bit for bit the gathers
EDGE_PRODUCT_BOUND = float(1 << 23)


@jax.jit
def _whole_below_bound(vals):
    """True where every value of ``vals`` is a whole number below
    EDGE_PRODUCT_BOUND in magnitude (so none is a NaN or an Inf)."""
    return jnp.all((jnp.abs(vals) < EDGE_PRODUCT_BOUND)
                   & (vals == jnp.round(vals)))


def hist_edge_form(block, func: str, is_delta: bool) -> str | None:
    """How a base-2 launch reads a cumulative window's increase (the rate
    family of a cumulative column; None for any other launch): ``"product"``,
    one +-1 product on the MXU (hist_kernels._hist_base2_shared), where
    the shared-grid body runs and every value of the block is a whole
    number below EDGE_PRODUCT_BOUND — one device reduction a block,
    memoised on it (a superblock that moves is a new block); ``"gather"``,
    the samples at each window's edges, elsewhere. A static of the launch;
    every such launch counts its form: filodb_hist_edges_total."""
    if is_delta or func not in ("rate", "increase", "delta"):
        return None
    if _fused_body(True, block, func, is_delta, None)[0] != "hist_shared":
        return "gather"
    return memo_on(block, "_edge_form", "form", lambda: (
        "product" if bool(_whole_below_bound(block.vals)) else "gather"))


def hist_merge_form(num_groups: int) -> str:
    """How a base-2 launch sums its groups: ``"onehot"``, a product with
    the [G, S] 0/1 membership on the MXU, up to WIDE_ONEHOT_MAX_GROUPS
    groups (the trash group counted, the scalar wide sum's rule); past it
    the one-hot is the larger operand, and ``"segment"`` keeps the
    segment_sum. Every launch counts its form: filodb_hist_merge_total."""
    few = num_groups + 1 <= WIDE_ONEHOT_MAX_GROUPS
    return "onehot" if few else "segment"


def hist_epilogue_form(block, num_groups: int, j_pad: int, width: int) -> str:
    """How a base-2 launch merges onto each group's scheme and sums by
    group: ``"pallas"``, ONE kernel whose intermediates stay in VMEM
    (pallas_kernels.base2_merge_sum), where the one Pallas policy selects
    kernels on this platform (pallas_kernels.pallas_platform), the sum is
    ``onehot`` (hist_merge_form) and the kernel's VMEM plan fits the static
    (J, B, W, G) (pallas_kernels.base2_epilogue_tile); ``"xla"``, the two
    products of _base2_rescale and _base2_group_sum, elsewhere. A static of
    the launch; every base-2 launch counts its form:
    filodb_hist_epilogue_total."""
    from .pallas_kernels import base2_epilogue_tile, pallas_platform

    S, _T, B = block.vals.shape
    if (hist_merge_form(num_groups) == "onehot" and pallas_platform()
            and base2_epilogue_tile(S, j_pad, B, width, num_groups) is not None):
        return "pallas"
    return "xla"


def _base2_select_scalars(gids, shared):
    """[4, S] int32: each series' d (its scale above its group's), base
    (offset_g * 2^d - offset), K_g and n, the operands of
    hist_kernels.base2_select_rule, the one rule of both forms of the merge
    (_base2_rescale, pallas_kernels.base2_merge_sum)."""
    scale, offset, n, s_g, o_g, k_g = shared[:6]
    d = scale - s_g[gids]
    return jnp.stack([d, jnp.left_shift(o_g[gids], d) - offset, k_g[gids], n])


@jax.named_scope("hist_rescale")
def _base2_rescale(sjb, gids, shared, width: int):
    """[S, J, B] values at each series' own scheme -> [S, J, W] on its
    group's (hist_kernels.base2_select_rule picks each column's bucket).
    One product on the MXU:
    each value's three bf16 pieces side by side on the contracted axis, the
    0/1 selection (one 1 a column) repeated under each, so a column sums
    the pieces of ONE value — exact in f32 in any order. ``sjb`` holds no
    NaN (a 0 times a NaN is a NaN)."""
    B = sjb.shape[2]
    d, base, k_g, n = _base2_select_scalars(gids, shared)[:, :, None]
    idx = base2_select_rule(jnp.arange(width, dtype=jnp.int32)[None, :],
                            d, base, k_g, n)  # [S, W]
    pick = ((jnp.arange(3 * B, dtype=jnp.int32) % B)[None, :, None]
            == idx[:, None, :]).astype(jnp.bfloat16)  # [S, 3B, W]
    pieces = jnp.concatenate(bf16_pieces(sjb), axis=2)  # [S, J, 3B]
    return jax.lax.dot_general(pieces, pick, (((2,), (1,)), ((0,), (0,))),
                               preferred_element_type=jnp.float32)


@jax.named_scope("group_reduce")
def _base2_group_sum(sjw, ok, gids, num_groups: int):
    """[G, J, W] sums of ``sjw`` [S, J, W] by group, NaN where no member has
    a sample (``ok`` [S, J]). ``onehot``: the membership repeated under
    each value's three bf16 pieces, stacked on the series axis; for whole
    counts every partial sum is a whole number below 2^24 (PERF.md 2), so
    the product is the segment_sum to the bit. No reshape around either
    product: one costs the compiler a transposed copy of the operand."""
    S, J, W = sjw.shape
    if hist_merge_form(num_groups) == "segment":
        return _segment_aggregate_jit(
            "sum", jnp.where(ok[:, :, None], sjw, jnp.nan).reshape(S, J * W),
            gids, num_groups + 1,
        )[:num_groups].reshape(num_groups, J, W)
    groups = jnp.arange(num_groups, dtype=gids.dtype)[:, None]  # no trash row
    member3 = (jnp.concatenate([gids] * 3)[None, :] == groups
               ).astype(jnp.bfloat16)  # [G, 3S]
    total = jax.lax.dot_general(
        member3, jnp.concatenate(bf16_pieces(sjw), axis=0),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    count = jax.lax.dot((gids[None, :] == groups).astype(jnp.bfloat16),
                        ok.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    return jnp.where(count[:, :, None] > 0, total, jnp.nan)


def _base2_epilogue(grid, epilogue: tuple, gids, shared, qv, num_groups: int):
    """The ("hist2", kind, W[, form]) epilogue: rescale, per-column group
    sum, then the quantile on each group's own bounds — or the [G, J, W]
    partials. The merge and the sum run as ``form`` (hist_epilogue_form)
    says: ``"pallas"``, one kernel that keeps every intermediate in VMEM
    (pallas_kernels.base2_merge_sum); ``"xla"`` (the default), the two
    products below.
    ``grid`` is ``hist_shared``'s ``base2_grid`` (whole counts — a
    cumulative column's window increases or a delta column's window sums —
    and a [J] factor): the sum is exact and the factor, common to every
    series, comes after it (the quantile never needs it: it is the same for
    every column); or a rate grid with no factor (any other body). ``qv``
    is the quantile as two f32, high and low part.

    A sample is a whole row of buckets: every body writes a missing one as
    NaN across its row (tests/test_base2_contract.py), so the zero bucket,
    which every scheme has, says whether it is there. Before the products
    the padded rows and every value that is not finite become 0, and
    absence is rebuilt from that count after them."""
    _, kind, width = epilogue[:3]
    sjb, factor = grid if isinstance(grid, tuple) else (grid, None)
    if epilogue[3:] == ("pallas",):
        from .pallas_kernels import (
            base2_epilogue_tile, base2_merge_sum, interpret_mode,
        )

        S, J, B = sjb.shape
        gjw = base2_merge_sum(
            sjb, gids, _base2_select_scalars(gids, shared), num_groups, width,
            base2_epilogue_tile(S, J, B, width, num_groups), interpret_mode())
    else:
        ok = ~jnp.isnan(sjb[:, :, 0]) & (gids < num_groups)[:, None]
        sjb = jnp.where(ok[:, :, None] & jnp.isfinite(sjb), sjb, 0.0)
        gjw = _base2_group_sum(_base2_rescale(sjb, gids, shared, width), ok,
                               gids, num_groups)
    if kind != "quantile":
        return gjw if factor is None else gjw * factor[None, :, None]
    with jax.named_scope("epilogue"):
        return histogram_quantile_rows(qv[0], qv[1], gjw, shared[6])


# ---------------------------------------------------------------------------
# the fused program family: range body x placement x lanes
# ---------------------------------------------------------------------------
#
# Every fused query is the same three steps — a range body over the block's
# row arrays, then an epilogue, optionally under shard_map, optionally
# unrolled over lanes. Each part is written ONCE: the bodies in FUSED_BODIES,
# the epilogue pairs above, the placement and the lane plan in
# _fused_program_jit. A sharded or batched program is therefore the same
# text as its single-device, single-query form by construction, which is
# what the parities asserted in tests/test_fused_programs.py,
# test_fused_mesh.py and test_scheduler.py rest on.


class FusedBody(NamedTuple):
    """One range body. ``rows(block)`` are the operands whose leading axis
    is the series axis (a mesh shards them there; their PartitionSpec
    follows from their rank). ``windows(block, start_off, step_ms, j_pad,
    window_ms, mesh)`` builds — memoized on the block — what depends on the
    window alone and is replicated, or returns None where the window fails
    the grid's safety bound (the dispatch then degrades to the general body
    and counts ``degrade``). ``statics(block, j_pad, is_counter, is_delta)``
    are the trailing static arguments of ``grid(func, rows, windows,
    *statics)``, the pure function to the [S, J] / [S, J, B] grid."""
    variant: str  # the executable key's vocabulary (_exec_key_parts)
    rows: Callable
    windows: Callable
    statics: Callable
    grid: Callable
    hist: bool = False
    mesh: bool = True   # has a series-sharded form
    lanes: bool = True  # has a cross-query batched form
    degrade: str | None = None
    # the grid under the base-2 epilogue ("hist2"), where it differs: the
    # same operands, whole-count increments and a [J] factor out
    base2_grid: Callable | None = None


def _raw(block):
    return block.raw if block.raw is not None else block.vals


def _fetch_statics(block, j_pad, is_counter, is_delta):
    return (is_counter, is_delta, fetch_strategy())


def _int_windows(block, start_off, step_ms, j_pad, window_ms, mesh):
    return (np.int32(start_off), np.int32(step_ms), np.int32(window_ms))


def _mxu_windows(block, start_off, step_ms, j_pad, window_ms, mesh):
    # window_matrices reads block.placement: a sharded block's set is
    # committed mesh-replicated at build, so no per-dispatch broadcast
    wm = window_matrices(block, start_off, step_ms, j_pad, window_ms)
    return (wm.dW, wm.dF, wm.dL, wm.dL2, wm.d_count, wm.d_tf, wm.d_tl,
            wm.d_tl2, wm.d_out_t, np.float32(window_ms), wm.d_idx)


def _near_regular_windows(build, take, tail, minmax: bool = False):
    """Window hook of the jitter / masked bodies: ``take``'s flat tuple of
    the memoized window structure, then ``tail(block, window_ms)``."""
    def windows(block, start_off, step_ms, j_pad, window_ms, mesh):
        wm = build(block, start_off, step_ms, j_pad, window_ms)
        if not wm.ok:  # window not wider than the deviation band
            return None
        if minmax:
            # the tile/edge structures build lazily on the memoized window
            # structure (only min/max_over_time read them)
            wm.ensure_minmax()
        return take(wm) + tail(block, window_ms)
    return windows


def _general_grid(func, rows, win, num_steps, is_counter, is_delta):
    return range_kernel(func, *rows, *win, num_steps, is_counter=is_counter,
                        is_delta=is_delta)


def _mxu_grid(func, rows, win, is_counter, is_delta, fetch):
    return mxu_range_kernel(func, *rows, *win[:-1], idx=win[-1],
                            is_counter=is_counter, is_delta=is_delta,
                            fetch=fetch)


def _jitter_grid(func, rows, win, is_counter, is_delta, fetch):
    return jitter_range_kernel(func, *rows, *win, is_counter=is_counter,
                               is_delta=is_delta, fetch=fetch)


def _masked_grid(func, rows, win, is_counter, is_delta, fetch):
    # the trailing ``maxdev`` enables the kernel's lean gather plan
    return jitter_masked_kernel(func, *rows, *win[:-1], is_counter=is_counter,
                                is_delta=is_delta, fetch=fetch,
                                maxdev=win[-1])


def _jitter_minmax_grid(func, rows, win, n_valid, fetch):
    # ``n_valid`` masks the TIME axis, unchanged by series sharding
    return jitter_minmax(*rows, *win, n_valid=n_valid,
                         is_min=(func == "min_over_time"), fetch=fetch)


def _masked_minmax_grid(func, rows, win, fetch):
    return jitter_masked_minmax(*rows, *win,
                                is_min=(func == "min_over_time"), fetch=fetch)


def _pallas_grid(func, rows, win, j_pad, is_counter, is_delta, interpret):
    """The one-pass Pallas window-stats kernel (VMEM-tiled gather-scan) and
    its finisher. The Pallas grid pads S/J up to its tile sizes; slice back
    to the block's own padding before the epilogue so the trash-group/gids
    contract is unchanged."""
    from .pallas_kernels import finish, stat_set, window_aggregates

    agg = window_aggregates(*rows, *win, j_pad, interpret=interpret,
                            stats=stat_set(func, is_counter, is_delta))
    sj = finish(func, agg, *win, is_counter=is_counter, is_delta=is_delta)
    return sj[: rows[1].shape[0], :j_pad]


def _pallas_statics(block, j_pad, is_counter, is_delta):
    from .pallas_kernels import interpret_mode  # True on the CPU backend only

    return (j_pad, is_counter, is_delta, interpret_mode())


def _hist_general_grid(func, rows, win, num_steps, is_delta):
    return hist_range_kernel(func, *rows, *win, num_steps, is_delta=is_delta)


def _hist_shared_grid(func, rows, win, is_delta):
    return _hist_range_shared(func, *rows, *win, is_delta)


def _hist_base2_grid(func, rows, win, is_delta, edges="gather"):
    return _hist_base2_shared(func, *rows, *win, is_delta, edges)


def _hist_jitter_grid(func, rows, win, is_delta):
    return _hist_range_jitter(func, *rows, win[:-1], win[-1], is_delta)


# pallas has no sharded or batched form (irregular mesh grids run the
# sharded general body); min/max on jitter/masked grids and a jittered hist
# grid have no batched form: such a query still runs ONE fused dispatch, it
# just doesn't coalesce with other lanes.
FUSED_BODIES = {
    "general": FusedBody(
        "general",
        lambda b: (b.ts, b.vals, b.lens, b.baseline, _raw(b)),
        _int_windows, lambda b, j, c, d: (j, c, d), _general_grid),
    "mxu": FusedBody(
        "mxu", lambda b: (b.vals, _raw(b), b.baseline),
        _mxu_windows, _fetch_statics, _mxu_grid),
    "jitter": FusedBody(
        "jitter", lambda b: (b.vals, b.ts_dev, _raw(b)),
        _near_regular_windows(jitter_window_matrices, _jwm_args,
                              lambda b, w: (np.float32(w),)),
        _fetch_statics, _jitter_grid, degrade="grid_jitter"),
    "masked": FusedBody(
        "masked", lambda b: _mgrid_args(b.mgrid),
        _near_regular_windows(
            masked_window_matrices, _mwm_args,
            lambda b, w: (np.float32(w), np.float32(b.mgrid.maxdev_ms))),
        _fetch_statics, _masked_grid, degrade="grid_holes"),
    "jitter_minmax": FusedBody(
        "jitter", lambda b: (b.vals, b.ts_dev),
        _near_regular_windows(jitter_window_matrices, _jmm_args,
                              lambda b, w: (), minmax=True),
        lambda b, j, c, d: (int(np.asarray(b.lens)[0]), fetch_strategy()),
        _jitter_minmax_grid, lanes=False, degrade="grid_jitter"),
    "masked_minmax": FusedBody(
        "masked",
        lambda b: (b.mgrid.vals, b.mgrid.dev, b.mgrid.valid, b.mgrid.cc),
        _near_regular_windows(masked_window_matrices, _mmm_args,
                              lambda b, w: (), minmax=True),
        lambda b, j, c, d: (fetch_strategy(),),
        _masked_minmax_grid, lanes=False, degrade="grid_holes"),
    "pallas": FusedBody(
        "pallas", lambda b: (b.ts, b.vals, _raw(b), b.lens),
        _int_windows, _pallas_statics, _pallas_grid, mesh=False, lanes=False),
    "hist_general": FusedBody(
        "hist_general", lambda b: (b.ts, b.vals, b.lens),
        _int_windows, lambda b, j, c, d: (j, d), _hist_general_grid,
        hist=True),
    "hist_shared": FusedBody(
        "hist_shared", lambda b: (b.vals,),
        _hist_shared_windows, lambda b, j, c, d: (d,), _hist_shared_grid,
        hist=True, base2_grid=_hist_base2_grid),
    "hist_jitter": FusedBody(
        "hist_jitter", lambda b: (b.vals, b.ts_dev),
        _near_regular_windows(jitter_window_matrices, _hist_jwm_args,
                              lambda b, w: (np.int32(w),)),
        lambda b, j, c, d: (d,), _hist_jitter_grid,
        hist=True, lanes=False, degrade="grid_jitter"),
}


def _fused_body(hist: bool, block, func: str, is_delta: bool, mesh):
    """``(body, degrade_reason)`` of one fused dispatch, before its window
    is known: the _grid_variant ladder (mxu > jitter > masked > pallas >
    general) with min/max_over_time on its dedicated tile-hierarchy bodies,
    or the hist rule (shared grid > near-regular > per-series). The ONE
    selection the single-query dispatch, the cross-query batcher and
    batch_variant_supported share."""
    if hist:
        if block.regular_ts is not None:
            return "hist_shared", None
        return ("hist_jitter" if block.nominal_ts is not None
                else "hist_general"), None
    variant, reason = _grid_variant(block, func, is_delta)
    if variant in ("jitter", "masked"):
        if func in ("min_over_time", "max_over_time"):
            variant += "_minmax"
    elif variant == "general" and reason is None and _pallas_variant(
        block, func, mesh
    ):
        variant = "pallas"
    return variant, reason


class FusedSpec(NamedTuple):
    """The static half of one fused program: what selects the executable."""
    body: str
    func: str
    epilogue: tuple
    num_groups: int
    statics: tuple
    mesh: Any = None             # a 1-D device mesh: one shard_map frame
    u_map: tuple | None = None   # lane -> unique window: the batched form


# The batched form UNROLLS over lanes (static lane count + static
# lane->unique-window map) instead of vmapping: each lane's subgraph is the
# EXACT single-query computation — bit-equality is structural, not a
# property of vmap batching rules — while XLA CSEs the work lanes share
# (the unique-window range grids, and the NaN-validity masks lanes with the
# same grid recompute). vmap was measured 3-10x slower here: its
# segment-reduce batching rules materialize per-lane [S, J] operand copies.


@functools.partial(jax.jit, static_argnums=0)
def _fused_program_jit(spec: FusedSpec, rows, windows, gids, shared, qv):
    """range body -> epilogue as ONE compiled program: only the [G, J] group
    partials (or [k, J] top-k rows, or the hist family's [G, J, B]) ever
    exist as program outputs — no [S, J] grid reaches the host, and no
    second dispatch happens. ``shared`` is ``n_real`` (scalar family) or
    ``les`` (hist family).

    With ``spec.mesh`` the same two calls run inside one shard_map frame:
    the row operands and gids are each device's row band, the replicated
    window operands ride the closure (committed mesh-replicated at build),
    and the epilogue combines across the mesh INSIDE the program — one
    dispatch spans every device, and only replicated outputs exist.

    With ``spec.u_map`` every window operand carries a leading
    unique-window axis and ``gids`` / ``qv`` a leading lane axis: the body
    evaluates ONCE per unique window, the epilogue once per lane, and the
    outputs stack. The unbatched program is not one lane of the batched:
    its outputs stay unstacked."""
    body = FUSED_BODIES[spec.body]
    mesh, u_map = spec.mesh, spec.u_map
    if mesh is not None and not body.mesh:
        raise NotImplementedError(f"the {spec.body} body has no sharded form")
    if u_map is not None and not body.lanes:
        raise NotImplementedError(f"the {spec.body} body has no batched form")
    local, combine = ((_hist_epilogue, _hist_sharded_combine) if body.hist
                      else (_apply_epilogue, _sharded_epilogue))
    grid = body.grid
    if spec.epilogue[0] == "hist2":
        if mesh is not None or u_map is not None:
            raise NotImplementedError("base-2 histograms run one device, one lane")
        local, grid = _base2_epilogue, body.base2_grid or body.grid

    def run(rows, gids, finish):
        if u_map is None:
            return finish(
                grid(spec.func, rows, windows, *spec.statics), gids, qv
            )
        grids = [
            grid(spec.func, rows, tuple(a[u] for a in windows),
                 *spec.statics)
            for u in range(max(u_map) + 1)
        ]
        outs = [finish(grids[u_map[i]], gids[i], qv[i])
                for i in range(len(u_map))]
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *outs)

    if mesh is None:
        return run(rows, gids, lambda grid, g, q: local(
            grid, spec.epilogue, g, shared, q, spec.num_groups))
    axis = mesh.axis_names[0]

    def band(rows_l, gids_l):
        return run(rows_l, gids_l, lambda grid, g, q: combine(
            grid, spec.epilogue, g, shared, q, spec.num_groups, axis))

    return jax.shard_map(
        band, mesh=mesh,
        in_specs=(tuple(P(axis, *(None,) * (r.ndim - 1)) for r in rows),
                  P(axis) if u_map is None else P(None, axis)),
        out_specs=_sharded_out_specs(spec.epilogue),
        check_vma=False,
    )(rows, gids)


def batch_variant_supported(block, func: str, kind: str, is_delta: bool,
                            mesh) -> bool:
    """Whether this dispatch's body has a batched form. The scheduler
    consults this BEFORE grouping (FusedAggregateExec._dispatch_fused): a
    structurally-unbatchable request runs unbatched immediately instead of
    paying the batch window and a guaranteed-to-raise launch (which would
    also mint ``outcome="fallback"`` dispatches operators are told to
    investigate). The raises inside _fused_dispatch remain as the defensive
    backstop, and for window-dependent cases (a merged window failing the
    jitter safety bound)."""
    if kind == "hist2":
        return False  # base-2 histograms have no batched form
    body, _reason = _fused_body(kind == "hist", block, func, is_delta, mesh)
    return FUSED_BODIES[body].lanes


def _exec_key_parts(variant: str, epilogue, block, j_pad: int,
                    num_groups: int, mesh=None, batch: str | None = None):
    """Executable-key parts for the kernel observatory (obs/kernels.py
    KEY_DIMS): the static signature that selects the XLA executable —
    kernel variant, epilogue statics, PADDED device shapes, mesh width and
    batched-lane composition. Metadata reads only (shape tuples), shared
    by every fused dispatch site so the key vocabulary has ONE builder."""
    shape = tuple(np.shape(block.vals))
    dims = f"S{shape[0]}xT{shape[1] if len(shape) > 1 else 1}"
    if len(shape) > 2:
        dims += f"xB{shape[2]}"
    ep = (":".join(str(x) for x in epilogue) if isinstance(epilogue, tuple)
          else str(epilogue))
    return {
        "variant": variant,
        "epilogue": ep or None,
        "shapes": f"{dims}xJ{j_pad}xG{num_groups}",
        "mesh": mesh.devices.size if mesh is not None else None,
        "batch": batch,
    }


# ---------------------------------------------------------------------------
# cross-query batched dispatch (query/scheduler.py): ONE kernel launch for Q
# concurrent fused queries sharing a (super)block + grid/epilogue signature
# ---------------------------------------------------------------------------
#
# The batched programs run the SAME per-query computation the single-query
# jits run, restructured for cross-query sharing (the Storyboard move —
# PAPERS.md): the expensive range kernel evaluates ONCE per UNIQUE
# (start, step, window) among the lanes — sj_u [U, S, J] — and each lane's
# epilogue (its own group-by vector, its own q) gathers its grid by index.
# Q dashboard panels differing only in group-by pay ONE range computation;
# panels differing in window pay one each, inside one launch. Per-lane math
# is identical to the single-query program, so lane i of the batched output
# is bit-equal to the unbatched dispatch of query i (asserted in
# tests/test_scheduler.py).
#
# ``num_groups`` is the MAX across lanes: a lane with G_i < G_max routes its
# padded rows to its own trash group G_i, whose output row the caller
# discards by slicing [:G_i] — segment reduces are independent per segment,
# so the extra empty segments change nothing.
#
# Lane and unique-window counts pad to powers of two (repeating lane/window
# 0) so fluctuating live group sizes reuse a handful of executables instead
# of recompiling per width; the stacked device inputs are memoized on the
# block per (sorted) batch composition, so a recurring dashboard round pays
# ZERO host->device copies after its first occurrence.


def _pow2(n: int, lo: int = 1) -> int:
    q = max(lo, 1)
    while q < n:
        q *= 2
    return q


def _pad_lanes(lanes) -> list:
    """Pad the lane list to the next power of two (min 2) by repeating
    lane 0; callers index only their real lane, so pad outputs are simply
    never read."""
    lanes = list(lanes)
    lanes.extend(lanes[0] for _ in range(_pow2(len(lanes), 2) - len(lanes)))
    return lanes


def _unique_windows(lanes, base_ms: int):
    """(u_idx per lane, pow2-padded unique (start_off, step, window) list)."""
    uniq: dict[tuple, int] = {}
    u_idx = []
    for l in lanes:
        k = (int(l[2].start_ms - base_ms), int(l[2].step_ms),
             int(l[2].window_ms))
        u_idx.append(uniq.setdefault(k, len(uniq)))
    ukeys = list(uniq)
    ukeys.extend(ukeys[0] for _ in range(_pow2(len(ukeys)) - len(ukeys)))
    return u_idx, ukeys


_BATCH_STACK_MEMO_MAX = 64


def _batched_stacks(block, lanes, j_pad: int, body_name: str, mesh):
    """Device-resident stacked batch inputs, memoized on the block per
    (sorted) batch composition: the group-id stack [Q_pad, S] and, field by
    field, the stack over the unique windows of what the body's window hook
    returns for one. A recurring dashboard round — the steady state the
    batcher exists for — pays ZERO host->device copies after its first
    occurrence. qv is NOT part of the memo (built per call): quantile
    sweeps must reuse the same stacks.

    The memo key embeds the body (the grid metadata half of the cache
    identity: a jittered block's stacks can never serve a regular-grid
    program shape or vice versa) and id(gids_dev) per lane; those arrays
    are themselves memoized on the block (group_ids_memo / zero_gids), so
    ids are stable for the block's lifetime and the key can never alias
    across bodies."""
    sig = tuple(
        (int(l[2].start_ms - block.base_ms), int(l[2].step_ms),
         int(l[2].window_ms), id(l[0]))
        for l in lanes
    )
    key = (body_name, j_pad, mesh is not None, sig)
    cache = block.__dict__.get("_batch_stacks")
    if cache is not None and len(cache) > _BATCH_STACK_MEMO_MAX:
        cache.clear()  # bounded: stacks rebuild in one call

    def build():
        padded = _pad_lanes(lanes)
        _u_idx, ukeys = _unique_windows(padded, block.base_ms)
        hook = FUSED_BODIES[body_name].windows
        wins = [hook(block, so, sm, j_pad, w, mesh) for so, sm, w in ukeys]
        if any(w is None for w in wins):
            # a merged window not wider than the deviation band: the
            # per-lane dispatch degrades to the general kernel, which the
            # batched program shape here does not model — raise so the
            # scheduler falls back to per-lane unbatched execution
            raise RuntimeError(
                f"{body_name} window bound fails for a batched window"
            )
        return jnp.stack([l[0] for l in padded]), tuple(
            jnp.asarray(np.asarray(col)) if isinstance(col[0], np.generic)
            else jnp.stack(col)
            for col in zip(*wins)
        )

    return memo_on(block, "_batch_stacks", key, build)


def _fused_dispatch(func: str, epilogue: tuple, block, num_groups: int,
                    is_counter: bool, is_delta: bool, name: str, mesh=None,
                    *, gids=None, qv=None, params=None, lanes=None,
                    j_pad=None, les=None, edges=None):
    """The ONE host-side dispatch of every fused entry point: body
    selection (_fused_body), the degrade-and-count rules, the reduction's
    form, the window operands, then one launch of _fused_program_jit with
    one latency observation and one JIT hit/miss account.

    Unbatched (``gids``, ``qv``, ``params``): a jitter / masked window
    failing its safety bound degrades to the general body — the dispatch
    STAYS one fused program, counted grid_jitter / grid_holes. Batched
    (``lanes`` of ``(gids_padded_dev, qv, params)``, ``j_pad``): selection
    matches the unbatched dispatch exactly, so a lane computes through the
    same body its unbatched execution would; combinations the batched form
    does not model — and a merged window failing the safety bound — RAISE,
    which the scheduler turns into per-lane unbatched execution (batching
    is an optimization, never a correctness risk).

    With ``mesh`` (a 1-D device mesh matching the block's series-sharded
    placement) the same program dispatches ONCE across every device.
    ``edges`` (hist_edge_form) is a base-2 launch's last static of the
    shared-grid body's ``base2_grid``."""
    hist = epilogue[0] in ("hist", "hist2")
    body_name, reason = _fused_body(hist, block, func, is_delta, mesh)
    body = FUSED_BODIES[body_name]
    if lanes is not None and not body.lanes:
        # defensive backstop — the scheduler consults the same predicate
        # (batch_variant_supported) before grouping, so this fires only for
        # requests that bypassed it
        raise RuntimeError(
            f"batched programs do not model the {body_name} body here: "
            "per-lane dispatch"
        )
    if not hist:
        epilogue = _with_reduce_form(func, epilogue, num_groups)
    # window structures build (memoized per block) BEFORE the timed span,
    # for every body alike — the dispatch-latency observation must compare
    # kernel cost across grid classes, not host-side build placement
    u_map = batch = None
    if lanes is not None:
        gids, windows = _batched_stacks(block, lanes, j_pad, body_name, mesh)
        padded = _pad_lanes(lanes)
        u_idx, ukeys = _unique_windows(padded, block.base_ms)
        u_map = tuple(u_idx)
        qv = jnp.asarray(np.asarray([l[1] for l in padded], np.float32))
        batch = f"Q{len(padded)}xU{len(ukeys)}"
    else:
        j_pad = pad_steps(params.num_steps)
        window = (int(params.start_ms - block.base_ms), params.step_ms,
                  j_pad, params.window_ms, mesh)
        windows = body.windows(block, *window)
        if windows is None:
            reason = body.degrade
            body_name = "hist_general" if hist else "general"
            body = FUSED_BODIES[body_name]
            windows = body.windows(block, *window)
    if reason is not None:
        # degraded-kernel taxonomy: the dispatch STAYS one fused program
        # (the general kernel), it just lost the jitter-tolerant fast
        # variant — reserved for truly unsupported shapes (doc/perf.md).
        # Batched lanes degrade exactly like their unbatched executions
        # would, counted once per launch
        record_fused_fallback(reason)
    # the body that runs (after any degradation) on the grid class it met:
    # a dispatch that fell off the ladder (mxu > jitter > masked) says so
    REGISTRY.counter("filodb_fused_dispatch", body=body_name,
                     grid=grid_class(block)).inc()
    if body_name == "pallas":  # never batched: ``params`` is this launch's
        from .pallas_kernels import book_lane_tiles

        book_lane_tiles(block, params.start_ms - block.base_ms,
                        params.step_ms, params.window_ms, j_pad)
    statics = body.statics(block, j_pad, is_counter, is_delta)
    if edges is not None and body.base2_grid is not None:
        statics += (edges,)
    t0 = time.perf_counter()
    spec = FusedSpec(body_name, func, epilogue, num_groups, statics, mesh,
                     u_map)
    before = _fused_program_jit._cache_size()
    out = _fused_program_jit(
        spec, body.rows(block), windows, gids,
        les if hist else np.int32(block.n_series), qv,
    )
    record_kernel_dispatch(
        ("batch_" if lanes is not None else "")
        + ("mesh_" if mesh is not None else "") + name,
        time.perf_counter() - t0,
        compiled=_fused_program_jit._cache_size() > before,
        key=_exec_key_parts(body.variant, epilogue, block, j_pad, num_groups,
                            mesh, batch),
    )
    return out


def fused_range_aggregate(func: str, op: str, block, gids_padded,
                          num_groups: int, params, is_counter: bool = False,
                          is_delta: bool = False, mesh=None):
    """One device dispatch for ``op by (...) (func(selector[w]))`` over a
    staged (super)block: returns the [G, J_pad] group partials on device.

    ``gids_padded`` is [S_padded] int32 with padded rows assigned the trash
    group ``num_groups``. Regular shared grids ride the MXU window-matrix
    kernel (matrices cached device-resident on the block); everything else
    runs the general compare-and-reduce kernel. With ``mesh`` (the block's
    series-sharded placement) the body runs under shard_map with a
    psum-combined [G, J] — ONE dispatch across the whole mesh. Instrumented
    like every other kernel entry (per-dispatch latency + JIT hit/miss)."""
    return _fused_dispatch(
        func, ("agg", op), block, num_groups, is_counter, is_delta,
        f"fused_{op}_{func}", mesh, gids=gids_padded, qv=np.float32(0.0),
        params=params,
    )


def _tag_memo_miss() -> None:
    """A group-id memo is being built: say so on the span that asked (the
    exec nodes' ``fused:groups``, opened with ``memo="hit"``)."""
    sp = current_span()
    if sp is not None and "memo" in sp.tags:
        sp.tags["memo"] = "miss"


def zero_gids(block):
    """All-zeros trash-group vector for epilogues that need no label
    grouping (global topk/bottomk): unused by the epilogue math but part of
    the shared jit signature. Memoized device-resident per block (co-placed
    with a sharded block's series axis); also handed to the cross-query
    batcher so identical-lane dedup keys on ONE object per block."""
    s_pad = np.asarray(block.lens).shape[0]

    def build():
        _tag_memo_miss()
        return series_put(getattr(block, "placement", None))(
            np.zeros(s_pad, dtype=np.int32)
        )

    return memo_on(block, "_zero_gids", s_pad, build)


def fused_topk(func: str, block, k: int, bottom: bool, params,
               is_counter: bool = False, is_delta: bool = False, mesh=None):
    """One device dispatch for global ``topk(k, func(selector[w]))``:
    returns ([k, J_pad] values, [k, J_pad] i32 series indices) on device —
    the compact per-step winner set, O(k*J) on the wire instead of the
    [S, J] grid AggregatePresentExec gathers. Needs no label grouping at
    all (global top-k), so the O(S) group pass is skipped too. With
    ``mesh`` the per-device winner state combines across devices inside
    the same program (all_gather of [k, J] candidates + re-reduce)."""
    return _fused_dispatch(
        func, ("topk", int(k), bool(bottom)), block, 1, is_counter, is_delta,
        f"fused_{'bottomk' if bottom else 'topk'}_{func}", mesh,
        gids=zero_gids(block), qv=np.float32(0.0), params=params,
    )


def fused_quantile(func: str, block, gids_padded, num_groups: int, q: float,
                   params, is_counter: bool = False, is_delta: bool = False,
                   mesh=None):
    """One device dispatch for ``quantile(q, func(selector[w])) by (...)``:
    range kernel -> segment_quantile inside one compiled program; only the
    [G, J_pad] quantile grid reaches the host. ``q`` rides as a dynamic
    argument so dashboards sweeping quantiles share one executable. With
    ``mesh`` the exact per-group multiset is all_gather'd across devices
    inside the same program before the sort (see _sharded_epilogue)."""
    return _fused_dispatch(
        func, ("quantile",), block, num_groups, is_counter, is_delta,
        f"fused_quantile_{func}", mesh, gids=gids_padded, qv=np.float32(q),
        params=params,
    )


def fused_hist_range_aggregate(func: str, block, gids_padded,
                               num_groups: int, params, les,
                               q: float | None = None,
                               is_delta: bool = False, mesh=None):
    """One device dispatch for ``sum by (...) (hist_fn(selector[w]))`` over
    a 3-D histogram (super)block — optionally with the device-side
    ``histogram_quantile`` interpolation epilogue fused into the same
    program (q != None). Returns [G, J_pad, B] group bucket partials, or
    [G, J_pad] quantiles. ``les`` is the (unified) [B] bound vector.

    Shared regular grids (the overwhelmingly common scraped-histogram case)
    use the shared-window body: [J] boundary vectors precomputed host-side
    and memoized device-resident on the block, skipping the O(S*J*T)
    per-series boundary compare entirely. Near-regular (jittered scrape)
    grids ride the shared-boundary jitter body; a grid failing the window
    safety bound degrades to the general per-series kernel (still one
    dispatch), counted grid_jitter.

    With ``mesh`` (the block's [ΣS, T, B] series-sharded placement) the
    hist range_fn -> per-bucket segment-sum -> psum -> (quantile) body
    runs under shard_map — one dispatch across the mesh, with the quantile
    interpolation evaluated on the replicated [G, J, B] partials inside
    the same program."""
    return _fused_dispatch(
        func, ("hist", "quantile" if q is not None else "sum"), block,
        num_groups, False, is_delta,
        f"fused_hist_{'quantile_' if q is not None else ''}sum_{func}", mesh,
        gids=gids_padded, qv=np.float32(q if q is not None else 0.0),
        params=params, les=les,
    )


def fused_base2_hist_aggregate(func: str, block, gids_padded,
                               num_groups: int, params, plan, scheme_dev,
                               q: float | None = None,
                               is_delta: bool = False):
    """``fused_hist_range_aggregate`` for a block of base-2 exponential
    histograms (``plan`` from ``base2_group_plan``; ``scheme_dev`` the [S]
    int32 scale / offset / n sidecars): ONE program, the hist range body
    then the ("hist2", ...) epilogue. Returns [G, J_pad] quantiles, or
    [G, J_pad, W] partials on the plan's group schemes. Books
    ``filodb_hist_rescale_series_total``: series merged onto a coarser
    scale, and series already at their group's; one
    ``filodb_hist_merge_total{form}`` (hist_merge_form), one
    ``filodb_hist_window_total{form}`` (hist_kernels.hist_window_form),
    one ``filodb_hist_epilogue_total{form}`` (hist_epilogue_form) and,
    for a cumulative column's rate family, one
    ``filodb_hist_edges_total{form}`` (hist_edge_form)."""
    group_dev, width, _schemes, rescaled = plan
    REGISTRY.counter("filodb_hist_rescale_series", how="rescaled").inc(rescaled)
    REGISTRY.counter("filodb_hist_rescale_series", how="native").inc(
        block.n_series - rescaled)
    REGISTRY.counter("filodb_hist_merge", form=hist_merge_form(num_groups)).inc()
    form = hist_epilogue_form(block, num_groups, pad_steps(params.num_steps), width)
    REGISTRY.counter("filodb_hist_epilogue", form=form).inc()
    REGISTRY.counter("filodb_hist_window", form=hist_window_form(func, is_delta)).inc()
    edges = hist_edge_form(block, func, is_delta)
    if edges is not None:
        REGISTRY.counter("filodb_hist_edges", form=edges).inc()
    kind = "quantile" if q is not None else "sum"
    return _fused_dispatch(
        func, ("hist2", kind, width, form), block, num_groups, False, is_delta,
        f"fused_hist_{'quantile_' if q is not None else ''}sum_{func}", None,
        gids=gids_padded, qv=quantile_parts(q if q is not None else 0.0),
        params=params, les=tuple(scheme_dev) + tuple(group_dev), edges=edges,
    )



def fused_batched_scalar(func: str, epilogue: tuple, block, lanes,
                         num_groups: int, j_pad: int, is_counter: bool,
                         is_delta: bool, mesh=None):
    """ONE device dispatch serving Q concurrent scalar fused queries over
    the SAME (super)block. ``lanes`` is a sequence of
    ``(gids_padded_dev, qv, params)`` triples — the per-query dynamics;
    everything else (func, epilogue statics, body, j_pad) is uniform across
    the group by construction of the coalescing key (query/scheduler.py).
    Returns the stacked [Q_pad, ...] outputs; callers take lane i's
    ``[:G_i]`` rows (or its [k, J] winner pair). Raises where the batched
    form does not model the dispatch (see _fused_dispatch)."""
    kind = epilogue[1] if epilogue[0] == "agg" else epilogue[0]
    return _fused_dispatch(
        func, epilogue, block, num_groups, is_counter, is_delta,
        f"fused_{kind}_{func}", mesh, lanes=lanes, j_pad=j_pad,
    )


def fused_batched_hist(func: str, block, lanes, num_groups: int, j_pad: int,
                       les, quantile: bool, is_delta: bool, mesh=None):
    """Batched twin of fused_hist_range_aggregate: ONE dispatch returns the
    stacked [Q_pad, G, J, B] bucket partials (or [Q_pad, G, J] interpolated
    quantiles) for Q concurrent hist queries over one 3-D superblock.
    Shared regular grids evaluate the hist range grid once per unique
    window ([U, S, J, B]) with the per-lane [J] boundary vectors stacked
    from the _hist_shared_windows memo; per-lane q rides the dynamic qv
    axis so dashboards sweeping quantiles share one program AND one range
    grid."""
    return _fused_dispatch(
        func, ("hist", "quantile" if quantile else "sum"), block, num_groups,
        False, is_delta,
        f"fused_hist_{'quantile_' if quantile else ''}sum_{func}", mesh,
        lanes=lanes, j_pad=j_pad, les=les,
    )


# ---------------------------------------------------------------------------
# standing-query delta maintenance (filodb_tpu/standing/): retained [G, J]
# partials + suffix-only re-dispatch + bitwise splice
# ---------------------------------------------------------------------------
#
# A standing query's [G, J] output grid decomposes PER STEP: every fused
# epilogue computes step j from the samples inside window j alone, so steps
# are independent panes (the delta-summation move, PAPERS.md, with pane ==
# output step and bitwise-exact combination). On a live-edge append the
# appended columns can only touch the step SUFFIX whose windows reach the
# append interval — the delta refresh re-dispatches ONLY those steps
# through the SAME fused program ladder (same superblock object, same
# kernel variant, same per-step math) and splices the retained prefix back
# in. Two facts make the splice bit-exact rather than merely close, both
# pinned by tests/test_standing.py across regular/jitter/holes grids:
#
# - a suffix-grid dispatch over the SAME staged superblock produces
#   bit-identical per-step values to the full-grid dispatch (each step's
#   window reduce runs over the identical [S, T] operand rows; the output
#   grid start/count only select which independent reduces run);
# - steps whose windows closed before an in-place extension are bit-stable
#   across it (appended columns land masked-out of closed windows, and
#   extension never rewrites resident columns — PR 6's consistency model).
#
# True sample-level partial combination (old_sum + appended_sum) was
# rejected: float addition does not re-associate, so combined open-window
# partials could never be bit-equal to a full re-evaluation — and bit
# parity with the normal query path is the property the whole fused engine
# asserts everywhere else (batched lanes, sharded twins).

# epilogues whose [G, J] output splices per step: exactly the ("agg", op)
# segment reduces. topk ([k, J] winner rows whose label reconstruction is
# per-refresh), quantile and fused histogram_quantile keep full re-dispatch
# (fallback taxonomy: standing_nondecomposable).
STANDING_DELTA_OPS = frozenset(SIMPLE_AGG_OPS)


def standing_delta_eligible(op: str, params=(),
                            hist_quantile=None) -> bool:
    """Whether a fused aggregate's epilogue supports standing delta
    maintenance (per-step retained-partial splicing). Ineligible shapes
    demote cleanly to full re-dispatch, counted
    ``filodb_fused_fallback_total{reason="standing_nondecomposable"}``."""
    return (op in STANDING_DELTA_OPS and not params
            and hist_quantile is None)


def shift_partials(retained: np.ndarray, shift: int,
                   num_steps: int) -> np.ndarray:
    """Slide retained [G, J] partials left by ``shift`` whole steps onto a
    ``num_steps``-wide grid (the dashboard window advancing): steps falling
    off the front drop, steps not yet computed arrive as NaN (absence) for
    the delta dispatch to fill."""
    G = retained.shape[0]
    out = np.full((G, num_steps), np.nan, dtype=retained.dtype)
    if shift < retained.shape[1]:
        keep = retained[:, shift:]
        n = min(keep.shape[1], num_steps)
        out[:, :n] = keep[:, :n]
    return out


def splice_partials(retained: np.ndarray, fresh: np.ndarray,
                    k0: int) -> np.ndarray:
    """Combine a delta dispatch's [G, J-k0] suffix partials into the
    retained [G, J] grid in place at step ``k0``. The ONE combination rule
    of the standing delta path — callers must have verified the group axis
    matches (same group_ids_memo labels); a mismatch means the block was
    restaged with a different row set and the refresh must reset instead."""
    if fresh.shape[0] != retained.shape[0]:
        raise ValueError(
            f"standing splice group mismatch: retained G={retained.shape[0]} "
            f"vs fresh G={fresh.shape[0]}"
        )
    n = retained.shape[1] - k0
    retained[:, k0:] = fresh[:, :n]
    return retained


def group_ids_memo(block, series_labels, by, without,
                   strip_metric: bool = False):
    """``group_ids_for`` memoized on the (super)block object: repeated
    dashboard queries over an unchanged block skip the O(S) python
    regrouping, the label stripping that feeds it, AND the group-id device
    upload. Sound because a staged block's series set is immutable for its
    lifetime — the superblock cache hands out a NEW block whenever any
    member shard's version moves. Keyed by (by, without, strip).

    Returns ``(gids_padded_dev, num_groups, group_labels)`` where
    gids_padded_dev is a device-resident [S_padded] int32 with padded rows
    routed to the trash group ``num_groups`` (the fused_range_aggregate
    contract). Misses build through the shared keyed single-flight
    (filodb_tpu/singleflight.memo_on): concurrent same-key queries must not
    each pay the O(S) regroup + device upload, nor clobber the memo dict."""
    key = (
        tuple(by) if by else None,
        tuple(without) if without else None,
        bool(strip_metric),
    )

    def build():
        _tag_memo_miss()
        labels = series_labels
        if strip_metric:
            from ..core.schemas import METRIC_TAG

            labels = [
                {k: v for k, v in l.items()
                 if k not in (METRIC_TAG, "__name__")}
                for l in labels
            ]
        gids, group_labels = group_ids_for(
            labels, list(by) if by else None,
            list(without) if without else None,
        )
        G = len(group_labels)
        s_pad = np.asarray(block.lens).shape[0]
        gids_padded = np.full(s_pad, G, dtype=np.int32)
        gids_padded[: len(gids)] = gids
        # co-placed with the block: a series-sharded superblock's gids
        # shard the same axis so the fused program needs no resharding
        put = series_put(getattr(block, "placement", None))
        return (put(gids_padded), G, group_labels)

    return memo_on(block, "_gid_cache", key, build)


@functools.partial(jax.jit, static_argnames=("k", "bottom"))
def topk_mask(values, k: int, bottom: bool = False):
    """values [S, J] -> [S, J] keeping only per-step top-k (rest NaN).

    Prometheus topk: at each step, the k highest series survive with their own
    labels (reference TopBottomKRowAggregator with its k-heap per step).
    Ties broken by series index for determinism.
    """
    S, J = values.shape
    v = jnp.where(jnp.isnan(values), -jnp.inf if not bottom else jnp.inf, values)
    vt = v.T if not bottom else -v.T  # [J, S], larger = better
    kk = min(k, S)
    top_vals, top_idx = jax.lax.top_k(vt, kk)  # [J, kk]
    sel = jnp.zeros((J, S), dtype=bool)
    sel = sel.at[jnp.arange(J)[:, None], top_idx].set(True)
    keep = sel.T & jnp.isfinite(v)
    return jnp.where(keep, values, jnp.nan)


@functools.partial(jax.jit, static_argnames=("num_groups",))
def segment_quantile(values, group_ids, num_groups: int, q):
    """Per (group, step) quantile across series: [S, J] -> [G, J].

    Sorts within groups by composite key (group asc, value asc); absent
    values sort to the group's end. (reference QuantileRowAggregator uses
    t-digest sketches; exact sort is affordable on device.)
    """
    S, J = values.shape
    valid = ~jnp.isnan(values)
    count = jax.ops.segment_sum(valid.astype(jnp.float32), group_ids, num_groups)  # [G,J]
    # sort per step by (group, value) — put NaN/absent at +inf within group.
    # lexsort as two stable argsorts (least-significant key first)
    v = jnp.where(valid, values, jnp.inf)
    gcol = jnp.broadcast_to(group_ids[:, None], (S, J))
    ord1 = jnp.argsort(v, axis=0, stable=True)
    g1 = jnp.take_along_axis(gcol, ord1, axis=0)
    ord2 = jnp.argsort(g1, axis=0, stable=True)
    order = jnp.take_along_axis(ord1, ord2, axis=0)  # [S, J]
    sorted_v = jnp.take_along_axis(v, order, axis=0)
    # start offset of each group in the sorted column = cumulative counts of
    # all series (valid or not) in earlier groups — series count per group is
    # step-independent
    sizes = jax.ops.segment_sum(jnp.ones_like(group_ids, dtype=jnp.int32), group_ids, num_groups)
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(sizes)[:-1]])  # [G]
    rank = jnp.clip(q, 0.0, 1.0) * jnp.maximum(count - 1.0, 0.0)  # [G, J]
    lo_i = starts[:, None] + jnp.floor(rank).astype(jnp.int32)
    hi_i = starts[:, None] + jnp.ceil(rank).astype(jnp.int32)
    frac = rank - jnp.floor(rank)
    v_lo = jnp.take_along_axis(sorted_v, jnp.clip(lo_i, 0, S - 1), axis=0)
    v_hi = jnp.take_along_axis(sorted_v, jnp.clip(hi_i, 0, S - 1), axis=0)
    out = v_lo + (v_hi - v_lo) * frac
    return jnp.where(count > 0, out, jnp.nan)


def count_values(values: np.ndarray, decimals: int = 10) -> dict[str, np.ndarray]:
    """Host-side count_values: value-string -> [J] counts (reference
    CountValuesRowAggregator; inherently dynamic-cardinality, stays on host)."""
    vals = np.asarray(values)
    out: dict[str, np.ndarray] = {}
    J = vals.shape[1]
    for j in range(J):
        col = vals[:, j]
        col = col[~np.isnan(col)]
        for x in col:
            key = f"{x:.{decimals}g}".rstrip("0").rstrip(".") if "." in f"{x:.{decimals}g}" else f"{x:.{decimals}g}"
            arr = out.setdefault(key, np.full(J, np.nan))
            arr[j] = (0.0 if np.isnan(arr[j]) else arr[j]) + 1.0
    return out


def group_ids_for(series_labels: list[dict], by: list[str] | None, without: list[str] | None):
    """Host-side grouping: label subset -> contiguous group ids + group labels.

    by=None, without=None -> one global group (classic `sum(...)`).
    """
    keys = []
    for lbls in series_labels:
        if by is not None:
            key = tuple((k, lbls.get(k, "")) for k in sorted(by))
        elif without:
            drop = set(without) | {"_metric_", "__name__"}
            key = tuple(sorted((k, v) for k, v in lbls.items() if k not in drop))
        else:
            key = ()
        keys.append(key)
    uniq: dict[tuple, int] = {}
    gids = np.empty(len(keys), dtype=np.int32)
    group_labels: list[dict] = []
    for i, k in enumerate(keys):
        if k not in uniq:
            uniq[k] = len(uniq)
            group_labels.append(dict(k))
        gids[i] = uniq[k]
    return gids, group_labels


# -- kernel observatory registration (obs/kernels.py) -----------------------
# every jit wrapper in this module registers with the executable registry so
# the observatory can report live in-process cache sizes per wrapper and
# tools/check_metrics.py can lint that no jit entry point dispatches outside
# the observatory (a new kernel added without registration fails the lint).
# The whole fused family is ONE wrapper: its cache holds every composition.
def _register_kernel_observatory() -> None:
    from ..obs.kernels import KERNELS

    KERNELS.register_jits(
        "ops.aggregations",
        _segment_aggregate_jit=_segment_aggregate_jit,
        _fused_program_jit=_fused_program_jit,
        _base2_group_scheme=_base2_group_scheme,
        _whole_below_bound=_whole_below_bound,
        topk_mask=topk_mask,
        segment_quantile=segment_quantile,
    )


_register_kernel_observatory()
