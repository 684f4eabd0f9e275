"""Pallas TPU kernel: fused window aggregation for irregular series.

The general (non-shared-grid) path in kernels.py makes several passes over
the staged ``[S, T]`` block (bounds, prefix sums, boundary gathers). This
Pallas kernel computes the per-window statistics of ONE range function —
its ``stat_set`` out of count, sum, min, max, first/last timestamp,
first/last value, first raw value and the last pair's interval and
difference — in ONE pass with the block resident in VMEM, tiled ``(BS
series x BJ steps)`` over a grid that reuses the series block across step
tiles (the block index map keeps ts/vals constant along the step axis, so
Pallas skips the re-fetch DMA). A step costs a mask and two operations a
statistic over every vreg it reads, and then a trip through the cross-lane
unit for each reduce in its chain, so the kernel is built for the
statistics its function's finisher reads and no other (the set is static,
chosen while tracing: ``FUNC_STATS``, ``stat_set``), and a step reads only
the lane tiles its window can touch: a ``[5m]`` window at a 10 s scrape
holds 30 samples of a row's 768 lanes. ``_lane_tile_steer`` makes, inside
the same jitted program, a table of the smallest and largest valid
timestamp of each (BS series x LANES lanes) tile and from it, for every
(series tile, step), the first of the NARROW adjacent lane tiles that hold
the step's window; the verdicts reach the kernel as scalars in SMEM. A tile
of the grid whose BJ steps all fit scans ``[BS, NARROW * LANES]`` slices at a
dynamic, 128-aligned lane offset; one with a step that does not (a ``[1h]``
window, 1 s and 60 s series in one tile, lanes out of time order) scans
whole rows, as every block one or two lane tiles wide does with no table at
all. The test holds for any block: only the speed rests on the data.

A small jit finisher then derives the range function from these statistics
(Prometheus extrapolation for rate/increase/delta; irate/idelta from the
last two samples of the window, their interval taken in int32 inside the
kernel). Compiled by Mosaic on a TPU; interpret mode exists for the CPU
backend only (``interpret_mode`` — the one place that decides, from the
platform jax reports).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .hist_kernels import base2_select_rule, bf16_pieces
from .staging import StagedBlock

BS = 64   # series per tile (second-to-last block dim: multiple of 8)
BJ = 128  # steps per tile (last block dim: hardware requires a multiple of 128)
LANES = 128  # samples per lane tile: the unit a step's scan leaves out
NARROW = 2  # lane tiles the narrow scan reads: a window's samples astride one boundary
NEG = -3.0e38  # python literals: jnp scalars would be captured consts
POS = 3.0e38


# Every statistic the kernel can write. ``dt_last`` /
# ``dv_last`` are the last sample's distance from the one before it inside
# the window (irate / idelta), taken in the kernel: dt in int32, because the
# f32 ``t_first`` / ``t_last`` round to 2 ms past 2^24 ms of block offset.
STATS = ("count", "sum", "min", "max", "t_first", "t_last",
         "v_first", "v_last", "raw_first", "dt_last", "dv_last")

_EXTRAPOLATED = ("count", "t_first", "t_last", "v_first", "v_last")

# function -> the statistics its finisher reads: the kernel is built for
# exactly this set (stat_set: the rate family's reads depend on the schema)
FUNC_STATS = {
    "sum_over_time": ("count", "sum"),
    "count_over_time": ("count",),
    "avg_over_time": ("count", "sum"),
    "min_over_time": ("count", "min"),
    "max_over_time": ("count", "max"),
    "last": ("count", "v_last"),
    "last_over_time": ("count", "v_last"),
    "first_over_time": ("count", "v_first"),
    "present_over_time": ("count",),
    "absent_over_time": ("count",),
    "rate": _EXTRAPOLATED,
    "increase": _EXTRAPOLATED,
    "delta": _EXTRAPOLATED,
    "irate": ("count", "dt_last", "dv_last"),
    "idelta": ("count", "dv_last"),
}

PALLAS_FUNCS = set(FUNC_STATS)


def stat_set(func: str, is_counter: bool = False, is_delta: bool = False) -> tuple:
    """The window statistics ``finish(func, ...)`` reads."""
    if is_delta and func in ("rate", "increase"):
        return ("count", "sum")  # each sample IS the increase
    if func == "idelta" and is_counter and not is_delta:
        return ("count", "v_last")  # the staged diff of the last pair
    stats = FUNC_STATS[func]
    if is_counter and func in ("rate", "increase"):
        stats += ("raw_first",)  # the zero cap of the extrapolation
    return stats


def _step_stats(want, ts, vals, raw, valid, t_j, window):
    """The window statistics of ONE step over the lanes given: ``[BS, W]``
    operands (the whole row, or the slice of it the window can touch) ->
    ``[BS]`` each."""
    IMAX = jnp.int32(2**31 - 1)
    IMIN = jnp.int32(-(2**31) + 1)
    m = (ts <= t_j) & (ts > t_j - window) & valid
    new = {"count": m.astype(jnp.float32).sum(axis=1)}
    if "sum" in want:
        new["sum"] = jnp.where(m, vals, 0.0).sum(axis=1)
    if "min" in want:
        new["min"] = jnp.where(m, vals, POS).min(axis=1)
    if "max" in want:
        new["max"] = jnp.where(m, vals, NEG).max(axis=1)
    # boundary selection in exact int32 time (f32 would round >2^24 ms)
    if want & {"t_first", "v_first", "raw_first"}:
        tmin = jnp.where(m, ts, IMAX).min(axis=1)
        first_m = m & (ts == tmin[:, None])
        new["t_first"] = tmin.astype(jnp.float32)
        new["v_first"] = jnp.where(first_m, vals, 0.0).sum(axis=1)
        if raw is not None:
            new["raw_first"] = jnp.where(first_m, raw, 0.0).sum(axis=1)
    if want & {"t_last", "v_last", "dt_last", "dv_last"}:
        tmax = jnp.where(m, ts, IMIN).max(axis=1)
        last_m = m & (ts == tmax[:, None])
        new["t_last"] = tmax.astype(jnp.float32)
        new["v_last"] = jnp.where(last_m, vals, 0.0).sum(axis=1)
    if want & {"dt_last", "dv_last"}:
        # the sample before the last one in the window; a window of
        # fewer than two leaves trash the finisher masks (count < 2)
        before = m & (ts < tmax[:, None])
        tprev = jnp.where(before, ts, IMIN).max(axis=1)
        prev_m = before & (ts == tprev[:, None])
        new["dt_last"] = (tmax - tprev).astype(jnp.float32)
        new["dv_last"] = new["v_last"] - jnp.where(prev_m, vals, 0.0).sum(axis=1)
    return new


def _window_agg_kernel(stats, narrow, params_ref, *refs):
    """One (BS series x BJ steps) tile: the statistics named in ``stats``
    (static: a Python-level choice while tracing, no runtime branch), one
    output ref each, after the ``raw`` operand where ``raw_first`` is read.
    With ``narrow`` (static) the tile reads ``steer_ref``'s verdict first:
    each step scans NARROW lane tiles from the one it names, or every step
    the whole row."""
    want = frozenset(stats)
    refs = list(refs)
    steer_ref = refs.pop(0) if narrow else None
    ts_ref, vals_ref = refs.pop(0), refs.pop(0)
    raw_ref = refs.pop(0) if "raw_first" in want else None
    lens_ref, *out_refs = refs
    start = params_ref[0]
    step = params_ref[1]
    window = params_ref[2]
    j0 = pl.program_id(1) * BJ
    lens = lens_ref[:]  # [BS, 1]
    rows, T = ts_ref.shape
    # column one-hot accumulation: per step jj compute [BS] stats and add
    # stat ⊗ onehot(jj) into [BS, BJ] carries — vector-only ops (no dynamic
    # stores), so Mosaic lowers it; a BJ=128 static unroll would explode
    # compile time and a (BS, <128) output block is rejected by hardware
    col = jax.lax.broadcasted_iota(jnp.int32, (1, BJ), 1)

    def steps(read):
        """The BJ steps, each over the lanes ``read(jj)`` gives it: (ts,
        vals, raw, valid). What does not depend on ``jj`` is made before
        the loop: a ``[BS, 1]`` -> lanes broadcast is a trip through the
        cross-lane unit, as long as a step's whole reduce."""
        def body(jj, accs):
            t_j = start + (j0 + jj) * step
            new = _step_stats(want, *read(jj), t_j, window)
            hot = col == jj  # [1, BJ] bool
            # select, don't multiply: NaN stats (stale markers, parsed 'NaN'
            # samples) must stay confined to their own step (NaN * 0 == NaN).
            # An entry of ``new`` that ``stats`` does not name is a [BS] cast
            # or subtraction at most, and dead code.
            return tuple(a + jnp.where(hot, new[k][:, None], 0.0) for a, k in zip(accs, stats))

        zero = jnp.zeros((rows, BJ), jnp.float32)
        return jax.lax.fori_loop(0, BJ, body, (zero,) * len(stats))

    def whole_row():
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, T), 1)
        row = (ts_ref[:], vals_ref[:], None if raw_ref is None else raw_ref[:], lane < lens)
        return steps(lambda jj: row)

    def lane_tiles():
        width = NARROW * LANES
        # lane0 + lane < lens  <=>  lane0 < room
        room = lens - jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)

        def read(jj):
            lane0 = pl.multiple_of(steer_ref[0, jj] * LANES, LANES)
            at = (slice(None), pl.ds(lane0, width))
            return (ts_ref[at], vals_ref[at],
                    None if raw_ref is None else raw_ref[at], room > lane0)

        return steps(read)

    if narrow:
        accs = jax.lax.cond(steer_ref[0, 0] >= 0, lane_tiles, whole_row)
    else:
        accs = whole_row()
    for ref, acc in zip(out_refs, accs):
        ref[:] = acc


def _pad_bj(num_steps: int) -> int:
    return ((num_steps + BJ - 1) // BJ) * BJ


def _narrow_scan(T: int) -> bool:
    """Whether a block of padded width ``T`` has lane tiles to leave out."""
    return T % LANES == 0 and T > NARROW * LANES


def _pad_series(lens, ts, *rows):
    """``lens`` and the ``[S, T]`` rows padded to whole BS tiles with empty
    series: ``(lens, [ts, *rows])``."""
    short = -ts.shape[0] % BS
    if not short:
        return lens, [ts, *rows]
    pad = ((0, short), (0, 0))
    return jnp.pad(lens, ((0, short),)), [
        jnp.pad(ts, pad, constant_values=2**31 - 1), *(jnp.pad(r, pad) for r in rows)]


def _lane_tile_steer(ts, lens, start_off, step_ms, window_ms, J: int):
    """``[S_pad/BS, J]`` int32, for each series tile and step the FIRST of
    the NARROW lane tiles the step's window can touch; -1 through a whole
    (series tile, BJ steps) tile of the kernel's grid where any step of it
    can touch more than NARROW of them (that tile reads whole rows).

    Lane tile ``k`` can hold a sample of step ``j``'s window iff ``tmax[k] >
    t_j - window`` and ``tmin[k] <= t_j``, the smallest and largest
    timestamp among the tile's valid lanes (``lane < lens``) over the BS
    series: true of ANY block — unsorted lanes, ragged lens, unlike scrape
    intervals — so only the speed rests on what the data looks like."""
    T, G, K = ts.shape[1], ts.shape[0] // BS, ts.shape[1] // LANES
    # over the BS series first (one pass over ts, rows onto rows: no
    # relayout), then over a tile's lanes in what is left, [G, T]
    ts = ts.reshape(G, BS, T)
    valid = jnp.arange(T, dtype=jnp.int32) < lens.reshape(G, BS, 1)
    tmin = jnp.where(valid, ts, 2**31 - 1).min(axis=1)
    tmax = jnp.where(valid, ts, -(2**31) + 1).max(axis=1)
    tmin = tmin.reshape(G, K, LANES).min(axis=2)
    tmax = tmax.reshape(G, K, LANES).max(axis=2)
    t_j = (start_off + jnp.arange(J, dtype=jnp.int32) * step_ms)[None, :, None]
    need = (tmax[:, None, :] > t_j - window_ms) & (tmin[:, None, :] <= t_j)  # [G, J, K]
    k = jnp.arange(K, dtype=jnp.int32)
    k_lo = jnp.where(need, k, K).min(axis=2)
    k_hi = jnp.where(need, k, -1).max(axis=2)  # no tile needed: k_hi < k_lo
    fits = (k_hi - k_lo < NARROW).reshape(G, J // BJ, BJ).all(axis=2)
    return jnp.where(jnp.repeat(fits, BJ, axis=1), jnp.minimum(k_lo, K - NARROW), -1)


@functools.partial(jax.jit, static_argnames=("num_steps", "interpret", "stats"))
@jax.named_scope("range_fn")
def window_aggregates(ts, vals, raw, lens, start_off, step_ms, window_ms,
                      num_steps: int, interpret: bool, stats: tuple):
    """[S, T] staged block -> dict of the [S, num_steps] per-window
    statistics named in ``stats`` (a function's ``stat_set``)."""
    J = _pad_bj(num_steps)
    lens, rows = _pad_series(lens.astype(jnp.int32), ts, vals,
                             *([raw] if "raw_first" in stats else []))
    S_pad, T = rows[0].shape
    from jax.experimental.pallas import tpu as pltpu

    params = jnp.stack([start_off, step_ms, window_ms]).astype(jnp.int32)
    narrow = _narrow_scan(T)
    grid = (S_pad // BS, J // BJ)
    # index maps receive the scalar-prefetch ref as a trailing arg
    row_spec = pl.BlockSpec((BS, T), lambda i, j, *_: (i, 0))
    out_spec = pl.BlockSpec((BS, BJ), lambda i, j, *_: (i, j))
    out_shape = [jax.ShapeDtypeStruct((S_pad, J), jnp.float32)] * len(stats)
    steer, steer_spec = [], []
    if narrow:
        # a tile's BJ verdicts reach the kernel as scalars: an SMEM block
        # (Mosaic wants its second-to-last dimension whole: [G, 1, J])
        steer = [_lane_tile_steer(rows[0], lens, *params, J)[:, None, :]]
        steer_spec = [pl.BlockSpec((None, 1, BJ), lambda i, j, *_: (i, 0, j),
                                   memory_space=pltpu.SMEM)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # params land in SMEM before the pipeline
        grid=grid,
        in_specs=steer_spec + [row_spec] * len(rows)
        + [pl.BlockSpec((BS, 1), lambda i, j, *_: (i, 0))],
        out_specs=[out_spec] * len(stats),
    )
    outs = pl.pallas_call(
        functools.partial(_window_agg_kernel, stats, narrow),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(params, *steer, *rows, lens[:, None])
    return dict(zip(stats, outs))


def interpret_mode() -> bool:
    """Whether the Pallas kernel runs interpreted: on the CPU backend only
    (tier-1). On an accelerator it is always compiled — a kernel Mosaic
    refuses is an error to repair, never a quiet interpreter run."""
    return jax.devices()[0].platform == "cpu"


@functools.partial(jax.jit, static_argnames=("num_steps",))
def _narrow_grid_tiles(ts, lens, start_off, step_ms, window_ms, num_steps: int):
    """How many (series tile, BJ steps) tiles of ``window_aggregates``'
    grid scan NARROW lane tiles a step: its own table, its own test."""
    lens, (ts,) = _pad_series(lens.astype(jnp.int32), ts)
    steer = _lane_tile_steer(ts, lens, start_off, step_ms, window_ms, _pad_bj(num_steps))
    return (steer[:, ::BJ] >= 0).sum()


def book_lane_tiles(block: StagedBlock, start_off, step_ms, window_ms,
                    num_steps: int) -> None:
    """Book one launch of the kernel over ``block``:
    ``filodb_pallas_lane_tiles_total{kind="scanned"}`` the lane tiles its
    steps read, ``{kind="resident"}`` the lane tiles of the rows they had
    before them (a step that scans its whole row reads them all), so scanned
    / resident says how far the narrow scan engaged. The count is a device
    reduction of the steering table; the first launch of a (block, window)
    waits for it once, a repeated panel reads the memo."""
    from ..metrics import REGISTRY
    from ..singleflight import memo_on

    S, T = block.ts.shape
    J, K = _pad_bj(num_steps), -(-T // LANES)
    tiles = -(-S // BS) * (J // BJ)  # the kernel's grid
    window = (int(start_off), int(step_ms), int(window_ms))

    def narrow_tiles() -> int:
        if not _narrow_scan(T):
            return 0
        return int(_narrow_grid_tiles(block.ts, block.lens, *map(np.int32, window), J))

    narrow = memo_on(block, "_lane_tile_memo", (*window, J), narrow_tiles)
    REGISTRY.counter("filodb_pallas_lane_tiles", kind="scanned").inc(
        BJ * (narrow * NARROW + (tiles - narrow) * K))
    REGISTRY.counter("filodb_pallas_lane_tiles", kind="resident").inc(BJ * tiles * K)


# Widest staged block (padded samples per series) the kernel is selected
# for. Its row tiles are (BS, T) with the whole T resident in VMEM. Measured
# on ONE generation, a TPU v5e ("TPU v5 lite", jax 0.9.0): compiles and runs
# at T=1024/2048/4096, Mosaic runs out of VMEM at T=6144 — not bisected in
# between, so 4096 is the widest width SEEN to work, not the limit (chip
# sweep, CHANGES.md PR 21). Wider irregular blocks — a >11 h selector at a
# 10 s scrape — take the general kernel, chosen here from the shape, never by
# catching the compile error; chip_smoke.py runs one set on each side.
MAX_T = 4096


def pallas_platform() -> bool:
    """The ONE Pallas policy's platform half: FILODB_PALLAS "0" disables
    every kernel outright; "auto" (default) selects them on real
    accelerators only; "1" forces them everywhere — interpret mode on CPU,
    which is for tests. pallas_enabled (the window kernel) and
    aggregations.hist_epilogue_form (base2_merge_sum) add what their
    kernel's shapes need."""
    import os

    mode = os.environ.get("FILODB_PALLAS", "auto")
    if mode == "0":
        return False
    return jax.devices()[0].platform not in ("cpu",) or mode == "1"


def pallas_enabled(t_pad: int) -> bool:
    """The window kernel's selection for a block of padded width ``t_pad``,
    shared by the legacy range-function dispatch
    (kernels._dispatch_range_function) and the fused variant ladder
    (aggregations._pallas_variant): never past MAX_T, then pallas_platform."""
    return t_pad <= MAX_T and pallas_platform()


# -- the base-2 epilogue's merge and group sum, resident in VMEM -------------
#
# aggregations._base2_epilogue's XLA form cuts the [S, J, B] whole-count grid
# into three bf16 pieces, concatenates them in HBM ([S, J, 3B]), takes the
# batched selection product to [S, J, W], cuts THAT into pieces stacked on
# the series axis ([3S, J, W]) and takes the membership product: ~3 GB of
# temporaries a program against a 0.34 GB input (PERF.md 5). base2_merge_sum
# reads each (step tile x series tile) slab of the grid once and keeps the
# rest in VMEM. It reads the grid as [B, S, J], the layout the shared-grid
# body's range product writes on a TPU (a bitcast, no copy), takes each
# series' [B, TJ] out of the slab with one sublane-strided load, makes its
# [W, B] 0/1 selection by an iota compare (hist_kernels.base2_select_rule,
# from five scalars a series in SMEM), multiplies its three bf16 pieces on
# the MXU, and adds the [W, TJ] result in f32 into its group's rows of a
# [G, W, TJ] sum resident across the series axis. The sample count rides the
# row past W.

B2_SERIES = 16  # series a grid step: two sublane tiles of the [B, S, J] grid
B2_VMEM_LIMIT = 64 << 20  # the scoped VMEM the kernel asks of Mosaic (v5e: 128 MiB)
B2_VMEM_PLAN = 40 << 20  # what its buffers and one series' temporaries may take


def _round(n: int, m: int) -> int:
    return -(-n // m) * m


def base2_epilogue_tile(S: int, J: int, B: int, W: int, num_groups: int):
    """Steps a grid tile of ``base2_merge_sum`` over an [S, J, B] grid to W
    columns and ``num_groups`` groups: LANES (Mosaic's strided load reads
    rows of exactly 128 lanes); None where J or S is not a whole number of
    tiles or the VMEM plan — the slab and the resident sums
    double-buffered, one series' pieces, selection and products — passes
    B2_VMEM_PLAN (the XLA form runs). Reckoned from the static shapes
    alone."""
    wp = _round(W + 1, 8)
    need = (2 * B * B2_SERIES * LANES * 4 + 2 * num_groups * wp * LANES * 4
            + _round(B, 8) * LANES * (2 * 4 + 3 * 2)
            + wp * _round(B, LANES) * (4 + 2) + 4 * wp * LANES * 4)
    if J % LANES or S % B2_SERIES or need > B2_VMEM_PLAN:
        return None
    return LANES


def _base2_merge_kernel(num_groups: int, width: int, tab_ref, x_ref, out_ref):
    """One (TJ steps x B2_SERIES series) tile. ``x_ref`` [B, B2_SERIES, TJ];
    ``tab_ref`` [5, B2_SERIES] a series' group, d, base, K_g and n;
    ``out_ref`` [G, Wp, TJ], the same block along the series axis (the last
    of the grid): zeroed at the first tile, each real series' merged
    [W, TJ] added at its group's rows and its count at row ``width``, NaN
    where a group's count is 0 after the last. A series of n + 2 <= 128
    buckets reads and multiplies its first 128 alone: its selection has no
    1 past them, so the rest adds nothing."""
    i = pl.program_id(1)
    B, rows, tj = x_ref.shape
    flat = x_ref.reshape(B * rows, tj)

    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.float32)

    w = jax.lax.broadcasted_iota(jnp.int32, (width, 1), 0)

    def series(s, carry):
        g = tab_ref[0, s]
        n = tab_ref[4, s]

        def add(buckets: int):
            x = flat[pl.ds(s, buckets, stride=rows), :]  # [buckets, TJ]: this series
            ok = ~jnp.isnan(x[0:1, :])  # a sample is a whole row of buckets
            x = jnp.where(ok & jnp.isfinite(x), x, 0.0)
            idx = base2_select_rule(w, tab_ref[1, s], tab_ref[2, s], tab_ref[3, s], n)
            pick = (jax.lax.broadcasted_iota(jnp.int32, (width, buckets), 1) == idx
                    ).astype(jnp.bfloat16)
            hi, mid, lo = (jnp.dot(pick, p, preferred_element_type=jnp.float32)
                           for p in bf16_pieces(x))
            out_ref[g, :width] += hi + mid + lo
            out_ref[g, width:width + 1] += ok.astype(jnp.float32)

        real = g < num_groups  # padded and trash-group rows add nothing
        if B <= LANES:
            pl.when(real)(lambda: add(B))
        else:
            pl.when(real & (n + 2 <= LANES))(lambda: add(LANES))
            pl.when(real & (n + 2 > LANES))(lambda: add(B))
        return carry

    jax.lax.fori_loop(0, rows, series, 0)

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        acc = out_ref[...]
        out_ref[...] = jnp.where(acc[:, width:width + 1, :] > 0, acc, jnp.nan)


@functools.partial(jax.jit, static_argnames=("num_groups", "width", "tile",
                                             "interpret"))
@jax.named_scope("group_reduce")
def base2_merge_sum(sjb, gids, select, num_groups: int, width: int, tile: int,
                    interpret: bool):
    """[G, J, W] sums by group of the [S, J, B] grid ``sjb`` merged onto
    each group's scheme, NaN where no member has a sample: column w of
    series s reads its bucket base2_select_rule(w, *select[:, s]) (the
    [4, S] int32 d, base, K_g, n of aggregations._base2_select_scalars);
    rows of ``gids`` >= num_groups are padding. The grid's non-finite
    values and a row whose zero bucket is NaN read 0, as in the XLA form,
    and every value is cut into bf16_pieces under a 0/1 selection: each
    column of a product is ONE value exactly, and on whole counts whose
    partial sums stay below 2^24 the f32 sum over series is exact in any
    order — bit for bit the XLA form. ``tile`` is base2_epilogue_tile's."""
    from jax.experimental.pallas import tpu as pltpu

    S, J, B = sjb.shape
    wp = _round(width + 1, 8)
    tab = jnp.concatenate([gids.astype(jnp.int32)[None], select])  # [5, S]
    tab = tab.reshape(5, S // B2_SERIES, B2_SERIES).transpose(1, 0, 2)
    out = pl.pallas_call(
        functools.partial(_base2_merge_kernel, num_groups, width),
        grid=(J // tile, S // B2_SERIES),
        in_specs=[
            pl.BlockSpec((None, 5, B2_SERIES), lambda j, i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((B, B2_SERIES, tile), lambda j, i: (0, i, j)),
        ],
        out_specs=pl.BlockSpec((num_groups, wp, tile), lambda j, i: (0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((num_groups, wp, J), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=B2_VMEM_LIMIT),
        interpret=interpret,
    )(tab, jnp.transpose(sjb, (2, 0, 1)))
    return jnp.transpose(out[:, :width], (0, 2, 1))


@functools.partial(jax.jit, static_argnames=("func", "is_counter", "is_delta"))
@jax.named_scope("range_fn")
def finish(func: str, agg: dict, start_off, step_ms, window_ms,
           is_counter: bool = False, is_delta: bool = False):
    """Derive a range function from its ``stat_set`` of window statistics
    (``agg`` need hold no other key)."""
    cnt = agg["count"]
    has = cnt > 0
    nan = jnp.nan
    if func == "sum_over_time" or (is_delta and func in ("rate", "increase")):
        r = agg["sum"]
        if func == "rate":
            r = r / (window_ms.astype(jnp.float32) * 1e-3)
        return jnp.where(has, r, nan)
    if func == "count_over_time":
        return jnp.where(has, cnt, nan)
    if func == "avg_over_time":
        return jnp.where(has, agg["sum"] / jnp.maximum(cnt, 1.0), nan)
    if func == "min_over_time":
        return jnp.where(has, agg["min"], nan)
    if func == "max_over_time":
        return jnp.where(has, agg["max"], nan)
    if func in ("last", "last_over_time"):
        return jnp.where(has, agg["v_last"], nan)
    if func == "first_over_time":
        return jnp.where(has, agg["v_first"], nan)
    if func == "present_over_time":
        return jnp.where(has, 1.0, nan)
    if func == "absent_over_time":
        return jnp.where(has, nan, 1.0)
    if func in ("rate", "increase", "delta"):
        J = cnt.shape[1]
        out_t = (start_off + jnp.arange(J, dtype=jnp.int32) * step_ms).astype(jnp.float32)
        f32 = jnp.float32
        w_s = window_ms.astype(f32) * 1e-3
        tf = agg["t_first"] * 1e-3
        tl = agg["t_last"] * 1e-3
        dlt = agg["v_last"] - agg["v_first"]
        sampled = tl - tf
        dur_start = tf - (out_t - window_ms.astype(f32))[None, :] * 1e-3
        dur_end = out_t[None, :] * 1e-3 - tl
        avg_dur = sampled / jnp.maximum(cnt - 1.0, 1.0)
        thresh = avg_dur * 1.1
        if is_counter and func != "delta":
            dur_zero = jnp.where(dlt > 0, sampled * (agg["raw_first"] / jnp.maximum(dlt, 1e-30)), jnp.inf)
            dur_start = jnp.minimum(dur_start, jnp.where(agg["raw_first"] >= 0, dur_zero, jnp.inf))
        dur_start = jnp.where(dur_start >= thresh, avg_dur / 2.0, dur_start)
        dur_end = jnp.where(dur_end >= thresh, avg_dur / 2.0, dur_end)
        factor = (sampled + dur_start + dur_end) / jnp.maximum(sampled, 1e-30)
        res = dlt * factor
        if func == "rate":
            res = res / w_s
        return jnp.where(cnt >= 2, res, nan)
    if func in ("irate", "idelta"):
        # kernels.range_kernel's irate / idelta line for line: corrected
        # values make the difference across a reset the post-reset reading
        if func == "irate":
            r = agg["dv_last"] / jnp.maximum(agg["dt_last"] * 1e-3, 1e-30)
        elif is_counter and not is_delta:
            r = agg["v_last"]  # the staged f64-exact diff of the last pair
        else:
            r = agg["dv_last"]
        return jnp.where(cnt >= 2, r, nan)
    raise ValueError(f"pallas path does not support {func}")


def run_pallas_range_function(func: str, block: StagedBlock, params,
                              is_counter=False, is_delta=False):
    from .kernels import pad_steps

    J = pad_steps(params.num_steps)
    start_off = np.int32(params.start_ms - block.base_ms)
    raw = block.raw if block.raw is not None else block.vals
    book_lane_tiles(block, start_off, params.step_ms, params.window_ms, J)
    agg = window_aggregates(
        block.ts, block.vals, raw, block.lens,
        start_off, np.int32(params.step_ms), np.int32(params.window_ms), J,
        interpret=interpret_mode(), stats=stat_set(func, is_counter, is_delta),
    )
    return finish(func, agg, start_off, np.int32(params.step_ms), np.int32(params.window_ms),
                  is_counter=is_counter, is_delta=is_delta)


# kernel-observatory registration (obs/kernels.py; linted by
# tools/check_metrics.py — every jit wrapper here must register)
def _register_kernel_observatory() -> None:
    from ..obs.kernels import KERNELS

    KERNELS.register_jits(
        "ops.pallas_kernels",
        window_aggregates=window_aggregates,
        finish=finish,
        _narrow_grid_tiles=_narrow_grid_tiles,
        base2_merge_sum=base2_merge_sum,
    )


_register_kernel_observatory()
