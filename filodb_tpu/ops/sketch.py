"""Log-linear quantile sketches for distributed quantile pushdown
(reference: QuantileRowAggregator ships t-digest sketches between nodes,
query/exec/aggregator/RowAggregator; design informed by the Circllhist
log-linear histogram paper surfaced in PAPERS.md).

A sketch is a fixed ``[B]`` histogram over log-spaced bins: sign x octave x
SUB sub-bins per octave, plus a zero bin. Sketches are mergeable by
addition (psum across mesh shards, += across clusters); quantiles read off
the merged sketch with log-linear interpolation. Worst-case relative error
is 2^(1/SUB)-1 (~2.2% at SUB=32), the classic log-linear trade.

Device side is all elementwise + segment_sum — no sorts, no gathers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


SUB = 32  # sub-bins per octave
E_MIN = -24  # 2^-24 ~ 6e-8: smaller magnitudes collapse to the zero bin
E_MAX = 40  # 2^40 ~ 1e12
OCTAVES = E_MAX - E_MIN
HALF = OCTAVES * SUB  # bins per sign
B = 2 * HALF + 1  # [negative bins | zero | positive bins]
ZERO_BIN = HALF


def _bin_of(values):
    """values [*] -> bin ids [*] (NaN -> -1, excluded by caller)."""
    mag = jnp.abs(values)
    log = jnp.log2(jnp.maximum(mag, 1e-300))
    pos = jnp.clip(((log - E_MIN) * SUB).astype(jnp.int32), 0, HALF - 1)
    tiny = mag < 2.0**E_MIN
    bin_pos = jnp.where(tiny, 0, pos + 1)  # offset from zero bin
    b = jnp.where(values >= 0, ZERO_BIN + bin_pos, ZERO_BIN - bin_pos)
    b = jnp.where(tiny, ZERO_BIN, b)
    return jnp.where(jnp.isnan(values), -1, b)


def bin_centers() -> np.ndarray:
    """Representative value per bin (log-linear midpoint)."""
    idx = np.arange(HALF)
    mags = 2.0 ** (E_MIN + (idx + 0.5) / SUB)
    return np.concatenate([-mags[::-1], [0.0], mags])


def bin_of_np(values: np.ndarray) -> np.ndarray:
    """Host (numpy) mirror of :func:`_bin_of` — the rollup maintainer bins
    raw samples into per-period sketches on the ingest path without a
    device round trip. NaN -> -1 (caller excludes)."""
    values = np.asarray(values, dtype=np.float64)
    mag = np.abs(np.nan_to_num(values, nan=1.0))
    with np.errstate(divide="ignore"):
        log = np.log2(np.maximum(mag, 1e-300))
    pos = np.clip(((log - E_MIN) * SUB).astype(np.int64), 0, HALF - 1)
    tiny = mag < 2.0**E_MIN
    bin_pos = np.where(tiny, 0, pos + 1)
    b = np.where(values >= 0, ZERO_BIN + bin_pos, ZERO_BIN - bin_pos)
    b = np.where(tiny, ZERO_BIN, b)
    return np.where(np.isnan(values), -1, b).astype(np.int64)


@functools.partial(jax.jit, static_argnames=("num_groups",))
def build_sketch(values, gids, num_groups: int):
    """values [S, J] (NaN absent) -> sketch counts [G, J, B] (f32)."""
    S, J = values.shape
    bins = _bin_of(values)  # [S, J]
    valid = bins >= 0
    # accumulate counts without one-hot blowup: scan over sub-blocks of B
    BLK = 64

    def block(counts, b0):
        ids = b0 + jnp.arange(BLK)[None, None, :]  # [1, 1, BLK]
        m = (bins[:, :, None] == ids) & valid[:, :, None]  # [S, J, BLK]
        part = jax.ops.segment_sum(m.astype(jnp.float32), gids, num_groups)
        # blocks cover disjoint bin ranges: plain write, no accumulate
        return jax.lax.dynamic_update_slice(counts, part, (0, 0, b0)), None

    n_blocks = -(-B // BLK)
    init = jnp.zeros((num_groups, J, n_blocks * BLK), jnp.float32)
    starts = jnp.arange(n_blocks) * BLK
    out, _ = jax.lax.scan(block, init, starts)
    return out[:, :, :B]


def sketch_quantile(counts: np.ndarray, q: float) -> np.ndarray:
    """Merged sketch [G, J, B] -> quantile values [G, J] (host, tiny)."""
    counts = np.asarray(counts, dtype=np.float64)
    total = counts.sum(-1)
    cum = np.cumsum(counts, axis=-1)
    # rank >= 1 sample: q=0 must read the first POPULATED bin (the min),
    # not the empty bottom of the bin axis
    rank = np.maximum(np.clip(q, 0.0, 1.0) * total, np.minimum(total, 1.0))
    # first bin with cum >= rank
    idx = (cum < rank[..., None]).sum(-1)
    idx = np.minimum(idx, B - 1)
    centers = bin_centers()
    out = centers[idx]
    return np.where(total > 0, out, np.nan)


@functools.partial(
    jax.jit, static_argnames=("mesh", "func", "num_steps", "num_groups", "is_counter", "is_delta")
)
def distributed_sketch_quantile(
    mesh,
    func: str,
    ts, vals, lens, baseline, raw, gids,
    start_off, step_ms, window,
    num_steps: int,
    num_groups: int,
    is_counter: bool = False,
    is_delta: bool = False,
):
    """Per-shard range function -> per-shard sketch -> psum merge: the
    mesh-distributed form of quantile(q, range_fn(...)). Returns merged
    sketch [G, J, B]; the (tiny) quantile read-off happens on host."""
    from jax.sharding import PartitionSpec as P

    from . import kernels as K

    def local(ts_l, vals_l, lens_l, base_l, raw_l, gids_l):
        grid = K.range_kernel(
            func, ts_l, vals_l, lens_l, base_l, raw_l,
            start_off, step_ms, window, num_steps,
            is_counter=is_counter, is_delta=is_delta,
        )
        sk = build_sketch(grid, gids_l, num_groups)
        return jax.lax.psum(sk, "shard")

    shard = P("shard")
    row = P("shard", None)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(row, row, shard, shard, row, shard),
        out_specs=P(),
        check_vma=False,
    )(ts, vals, lens, baseline, raw, gids)


# ---------------------------------------------------------------------------
# Rollup-tier kernels (doc/perf.md "Sketch rollup tier"): long-range queries
# read per-period summary blocks maintained by downsample/rollup.py instead
# of raw samples. A rollup block stores, per series per period, a COMPACTED
# sketch (the [lo, hi] slice of the full bin axis actually populated — the
# read-off is exact-equivalent because bins stay sorted by value) plus
# min/max/sum/count/corrected-last moments. Serving merges periods (cumsum
# gather) or series (segment_sum / psum) on device; only [S, J] / [G, J]
# grids reach the host.
# ---------------------------------------------------------------------------


def _sketch_readoff(w, centers, q):
    """Windowed sketch counts [..., Bc] -> quantile values [...]: cumulative
    rank scan + log-linear bin-center read-off (the device form of
    sketch_quantile)."""
    total = w.sum(-1)
    cum = jnp.cumsum(w, -1)
    # rank >= 1 sample: q=0 reads the first POPULATED bin (see
    # sketch_quantile, the host twin)
    rank = jnp.maximum(jnp.clip(q, 0.0, 1.0) * total,
                       jnp.minimum(total, 1.0))
    idx = jnp.minimum((cum < rank[..., None]).sum(-1), w.shape[-1] - 1)
    return jnp.where(total > 0, centers[idx], jnp.nan)


@functools.partial(jax.jit, static_argnames=("win_p",))
def rollup_sketch_quantile(counts, centers, starts, q, win_p: int):
    """Per-series quantile_over_time from a rollup sketch block.

    counts [S, P, Bc] per-series-per-period bin counts; centers [Bc]
    compacted bin centers (ascending); starts [J] first period index of
    each output step's window; win_p periods per window. Returns [S, J].
    O(S*P*Bc) summary reads — never O(raw samples)."""
    cs = jnp.cumsum(counts.astype(jnp.float32), axis=1)
    cs = jnp.pad(cs, ((0, 0), (1, 0), (0, 0)))
    w = cs[:, starts + win_p, :] - cs[:, starts, :]  # [S, J, Bc]
    return _sketch_readoff(w, centers, q)


def _windowed(x, init, op, win_p: int, step_p: int):
    """[S, Pw] -> [S, J] sliding reduce over the period axis."""
    return jax.lax.reduce_window(
        x, init, op, window_dimensions=(1, win_p),
        window_strides=(1, step_p), padding="VALID",
    )


def _moment_vals(func: str, mn, mx, sm, cnt, clast, win_p: int, step_p: int,
                 window_s: float):
    """Per-series per-step values [S, J] of a moment-servable range function
    evaluated from rollup moments. All inputs are [S, Pw+1] with ONE lead
    period at index 0 (counter diffs need the pre-window corrected last);
    window j covers local periods [1 + j*step_p, 1 + j*step_p + win_p)."""
    cntw = _windowed(cnt[:, 1:], 0.0, jax.lax.add, win_p, step_p)
    present = cntw > 0
    if func in ("rate", "increase"):
        j = jnp.arange((cnt.shape[1] - 1 - win_p) // step_p + 1) * step_p
        inc = clast[:, j + win_p] - clast[:, j]
        out = inc / window_s if func == "rate" else inc
    elif func == "min_over_time":
        out = _windowed(mn[:, 1:], jnp.inf, jax.lax.min, win_p, step_p)
    elif func == "max_over_time":
        out = _windowed(mx[:, 1:], -jnp.inf, jax.lax.max, win_p, step_p)
    elif func == "sum_over_time":
        out = _windowed(sm[:, 1:], 0.0, jax.lax.add, win_p, step_p)
    elif func == "count_over_time":
        out = cntw
    elif func == "avg_over_time":
        sw = _windowed(sm[:, 1:], 0.0, jax.lax.add, win_p, step_p)
        out = sw / jnp.maximum(cntw, 1.0)
    else:
        raise ValueError(f"not a moment-servable function: {func}")
    return jnp.where(present, out, jnp.nan)


@functools.partial(
    jax.jit, static_argnames=("func", "win_p", "step_p")
)
def rollup_moment_range(func: str, mn, mx, sm, cnt, clast,
                        win_p: int, step_p: int, window_s: float):
    """Per-series range function from rollup moments -> [S, J]."""
    return _moment_vals(func, mn, mx, sm, cnt, clast, win_p, step_p, window_s)


@functools.partial(
    jax.jit, static_argnames=("func", "op", "num_groups", "win_p", "step_p")
)
def rollup_moment_aggregate(func: str, op: str, mn, mx, sm, cnt, clast, gids,
                            num_groups: int, win_p: int, step_p: int,
                            window_s: float):
    """``op by (...) (func(selector[w]))`` from rollup moments: per-series
    values then one masked segment reduce -> [G, J]."""
    vals = _moment_vals(func, mn, mx, sm, cnt, clast, win_p, step_p, window_s)
    valid = jnp.isfinite(vals)
    nvalid = jax.ops.segment_sum(valid.astype(jnp.float32), gids, num_groups)
    if op == "sum":
        out = jax.ops.segment_sum(jnp.where(valid, vals, 0.0), gids, num_groups)
    elif op == "count":
        out = nvalid
    elif op == "avg":
        tot = jax.ops.segment_sum(jnp.where(valid, vals, 0.0), gids, num_groups)
        out = tot / jnp.maximum(nvalid, 1.0)
    elif op == "min":
        out = jax.ops.segment_min(
            jnp.where(valid, vals, jnp.inf), gids, num_groups
        )
    elif op == "max":
        out = jax.ops.segment_max(
            jnp.where(valid, vals, -jnp.inf), gids, num_groups
        )
    else:
        raise ValueError(f"not a moment-servable aggregate: {op}")
    return jnp.where(nvalid > 0, out, jnp.nan)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "func", "num_groups", "win_p", "step_p"),
)
def rollup_agg_sketch_quantile(func: str, mn, mx, sm, cnt, clast, gids, q,
                               num_groups: int, win_p: int, step_p: int,
                               window_s: float, mesh=None):
    """``quantile(q, func(selector[w]))`` from rollup moments via the
    merge-sketches -> epilogue program: per-series values sketch by group
    (build_sketch), sketches MERGE BY ADDITION — psum across the mesh's
    shard axis under shard_map when ``mesh`` is set, exactly the
    fused_hist_range_aggregate pattern — and the quantile reads off the
    merged sketch on device. Only [G, J] reaches the host."""
    centers = jnp.asarray(bin_centers(), jnp.float32)

    def local(mn_l, mx_l, sm_l, cnt_l, clast_l, gids_l):
        vals = _moment_vals(
            func, mn_l, mx_l, sm_l, cnt_l, clast_l, win_p, step_p, window_s
        )
        sk = build_sketch(vals, gids_l, num_groups)  # [G, J, B]
        if mesh is not None:
            sk = jax.lax.psum(sk, "shard")
        return sk

    if mesh is None:
        merged = local(mn, mx, sm, cnt, clast, gids)
    else:
        from jax.sharding import PartitionSpec as P

        row = P("shard", None)
        merged = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(row, row, row, row, row, P("shard")),
            out_specs=P(),
            check_vma=False,
        )(mn, mx, sm, cnt, clast, gids)
    return _sketch_readoff(merged, centers, q)


# kernel-observatory registration (obs/kernels.py; linted by
# tools/check_metrics.py — every jit wrapper here must register)
def _register_kernel_observatory() -> None:
    from ..obs.kernels import KERNELS

    KERNELS.register_jits(
        "ops.sketch",
        build_sketch=build_sketch,
        distributed_sketch_quantile=distributed_sketch_quantile,
        rollup_sketch_quantile=rollup_sketch_quantile,
        rollup_moment_range=rollup_moment_range,
        rollup_moment_aggregate=rollup_moment_aggregate,
        rollup_agg_sketch_quantile=rollup_agg_sketch_quantile,
    )


_register_kernel_observatory()
