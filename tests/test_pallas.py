"""Pallas fused window-aggregate kernel vs the general kernel (interpret
mode on CPU; the same kernel compiles for TPU with interpret=False)."""

import dataclasses

import numpy as np
import pytest

from filodb_tpu.ops import kernels as K
from filodb_tpu.ops import pallas_kernels as PK
from filodb_tpu.ops.staging import stage_series

BASE = 1_600_000_000_000
RATE_FAMILY = ["rate", "increase", "delta", "irate", "idelta"]
SCHEMAS = {"gauge": (False, False), "counter": (True, False), "delta": (True, True)}


def stage(series, func, counter, base=BASE):
    # what the planner stages a counter column as for ``func``
    # (query/exec/plans._stage_mode_for_function): idelta reads f64-exact
    # adjacent diffs, the rest of the family reset-corrected values
    diff = counter and func == "idelta"
    return stage_series(series, base, counter_corrected=counter and not diff,
                        diff_encode=diff)


def make_block(n_series=5, n=200, seed=0, counter=False, func=None, offset=0):
    rng = np.random.default_rng(seed)
    series = []
    for i in range(n_series):
        ts = BASE + offset + np.cumsum(rng.integers(5000, 15000, n)).astype(np.int64)
        if counter:
            vals = np.cumsum(rng.uniform(0, 10, n)) + 1e9
            k = n // 2  # a reset inside the queried windows
            vals[k:] -= vals[k] - 3.0
        else:
            vals = 50 + 20 * rng.standard_normal(n)
        series.append((ts, vals))
    return stage(series, func, counter)


def both(func, block, params, counter=False):
    n = block.n_series
    got = np.asarray(
        PK.run_pallas_range_function(func, block, params, is_counter=counter)
    )[:n, :params.num_steps]
    want = np.asarray(
        K.run_range_function(func, block, params, is_counter=counter)
    )[:n, :params.num_steps]
    return got, want


def assert_close(got, want, msg, rtol=2e-4, atol=1e-4):
    np.testing.assert_array_equal(np.isnan(got), np.isnan(want), err_msg=msg)
    m = ~np.isnan(want)
    np.testing.assert_allclose(got[m], want[m], rtol=rtol, atol=atol, err_msg=msg)


def compare(func, counter=False, seed=0):
    block = make_block(seed=seed, counter=counter, func=func)
    params = K.RangeParams(BASE + 400_000, 60_000, 20, 300_000)
    got, want = both(func, block, params, counter)
    assert_close(got, want, func)


@pytest.mark.parametrize("func", sorted(PK.PALLAS_FUNCS - {"rate", "increase", "delta"}))
def test_pallas_matches_general_gauge(func):
    compare(func, counter=False, seed=3)


@pytest.mark.parametrize("func", RATE_FAMILY)
def test_pallas_matches_general_counter(func):
    compare(func, counter=True, seed=4)


@pytest.mark.parametrize("counter", [False, True], ids=["gauge", "counter"])
@pytest.mark.parametrize("func", ["irate", "idelta"])
def test_the_last_pair_needs_two_samples_in_the_window(func, counter):
    """Windows of two, one and no sample (the last two NaN), in a series
    that the BS tiling pads."""
    at = np.array([1, 2, 3, 10, 20, 21, 40], dtype=np.int64) * 1_000
    vals = np.array([5.0, 7.0, 8.5, 2.0, 4.0, 9.0, 9.5]) + (1e6 if counter else 0)
    block = stage([(BASE + at, vals), (BASE + at[:3] + 500, vals[:3] * 2)], func, counter)
    params = K.RangeParams(BASE + 1_000, 1_000, 45, 2_500)
    got, want = both(func, block, params, counter)
    steps = (np.arange(45) + 1) * 1_000
    n_in = ((at[None, :] <= steps[:, None]) & (at[None, :] > steps[:, None] - 2_500)).sum(1)
    assert set(n_in) == {0, 1, 2, 3}
    np.testing.assert_array_equal(np.isnan(got[0]), n_in < 2)
    assert_close(got, want, func, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("func", ["irate", "idelta"])
def test_a_nan_sample_of_the_last_pair_stays_in_its_steps(func):
    """A NaN the stage did not drop (an appended stale marker) reaches
    only the steps whose last pair holds it: select, not multiply."""
    block = make_block(n_series=3, n=60, seed=11)
    vals = np.array(block.vals)
    vals[1, 30] = np.nan
    block = dataclasses.replace(block, vals=vals)
    params = K.RangeParams(BASE + 100_000, 5_000, 100, 60_000)
    got, want = both(func, block, params)
    hit = np.isnan(want[1]) & ~np.isnan(np.roll(want[1], 1))
    assert hit.any() and not np.isnan(want[1]).all()
    assert_close(got, want, func)


def test_irate_interval_is_exact_past_2_to_the_24_ms_of_offset():
    """Block offsets above 2^24 ms (4.66 h) round to 2 ms in f32: the
    kernel takes ``t_last - t_prev`` in int32 and converts the difference."""
    block = make_block(n_series=4, n=200, seed=5, counter=True, func="irate",
                       offset=3 * 2**24)
    assert int(np.asarray(block.ts)[0, 0]) > 2**24
    params = K.RangeParams(BASE + 3 * 2**24 + 400_000, 60_000, 20, 300_000)
    agg = PK.window_aggregates(
        block.ts, block.vals, block.raw, block.lens,
        np.int32(params.start_ms - BASE), np.int32(60_000), np.int32(300_000), PK.BJ,
        interpret=True, stats=PK.stat_set("irate", True))
    ts = np.asarray(block.ts).astype(np.int64)
    dt = np.asarray(agg["dt_last"])[:4, :20]
    for s in range(4):
        t = ts[s, : int(block.lens[s])]
        for j in range(20):
            hi = np.searchsorted(t, params.start_ms - BASE + j * 60_000, side="right")
            assert dt[s, j] == t[hi - 1] - t[hi - 2], (s, j)
    got, want = both("irate", block, params, counter=True)
    assert_close(got, want, "irate", rtol=1e-6, atol=0)


def test_the_table_covers_every_function():
    assert set(PK.FUNC_STATS) == PK.PALLAS_FUNCS >= {"irate", "idelta", "rate"}
    for func in PK.PALLAS_FUNCS:
        for c, d in SCHEMAS.values():
            assert set(PK.stat_set(func, c, d)) <= set(PK.STATS)


@pytest.mark.parametrize("schema", sorted(SCHEMAS))
@pytest.mark.parametrize("func", sorted(PK.PALLAS_FUNCS))
def test_finish_reads_exactly_its_set(func, schema):
    """``finish`` runs on a dict holding ONLY the function's set, and every
    key of the set is one it reads (without it: KeyError)."""
    c, d = SCHEMAS[schema]
    stats = PK.stat_set(func, c, d)
    agg = {k: np.full((8, PK.BJ), 2.0, np.float32) for k in stats}
    win = (np.int32(0), np.int32(1_000), np.int32(5_000))
    out = PK.finish(func, agg, *win, is_counter=c, is_delta=d)
    assert out.shape == (8, PK.BJ)
    for k in stats:
        with pytest.raises(KeyError):
            PK.finish(func, {x: v for x, v in agg.items() if x != k}, *win,
                      is_counter=c, is_delta=d)


@pytest.mark.parametrize("stats", sorted(
    {PK.stat_set(f, c, d) for f in PK.PALLAS_FUNCS for c, d in SCHEMAS.values()}),
    ids="+".join)
def test_a_set_computes_what_the_whole_kernel_computes(stats):
    """Each statistic's reduction is its own: built for a subset, the
    kernel gives the bits it gives when built for all of them."""
    block = make_block(n_series=3, n=120, seed=9, counter=True)
    args = (block.ts, block.vals, block.raw, block.lens,
            np.int32(400_000), np.int32(60_000), np.int32(300_000), PK.BJ)
    whole = PK.window_aggregates(*args, interpret=True, stats=PK.STATS)
    part = PK.window_aggregates(*args, interpret=True, stats=stats)
    assert set(part) == set(stats)
    for k in stats:
        np.testing.assert_array_equal(np.asarray(part[k]), np.asarray(whole[k]), err_msg=k)


def test_padding_of_series_dimension():
    # 5 series pads to 8 internally; BS=64 tiling pads to 64 — outputs for
    # real rows must be unaffected
    block = make_block(n_series=3, n=100, seed=7)
    params = K.RangeParams(BASE + 400_000, 60_000, 7, 300_000)
    got = np.asarray(PK.run_pallas_range_function("sum_over_time", block, params))[:3, :7]
    want = np.asarray(K.run_range_function("sum_over_time", block, params))[:3, :7]
    np.testing.assert_allclose(got, want, rtol=1e-5, equal_nan=True)


def test_nan_sample_confined_to_its_window():
    """Review regression: one NaN sample must not poison the whole step tile
    (the one-hot accumulation must select, not multiply)."""
    import numpy as np

    from filodb_tpu.ops import kernels as K
    from filodb_tpu.ops import pallas_kernels as PK
    from filodb_tpu.ops import staging as ST

    base = 1_600_000_000_000
    ts = base + np.arange(5, dtype=np.int64) * 1_000
    vals = np.array([1.0, 2.0, np.nan, 4.0, 5.0])
    block = ST.stage_series([(ts, vals)], base)
    params = K.RangeParams(base + 1_000, 1_000, PK.BJ, 1_000)
    out = np.asarray(PK.run_pallas_range_function("sum_over_time", block, params))[0, :5]
    # windows: step k covers (t_k-1s, t_k] = exactly sample k+1
    expect = [2.0, np.nan, 4.0, 5.0]
    np.testing.assert_allclose(out[:4], expect, equal_nan=True)


def test_selection_stops_at_the_widest_block_the_chip_holds(monkeypatch):
    """Past MAX_T (measured on a v5e: VMEM runs out at T=6144) the kernel
    is not selected — from the block's shape, whatever the switch says —
    so a wide irregular selector takes the general kernel instead of a
    compile error."""
    from filodb_tpu.ops import aggregations as AGG

    monkeypatch.setenv("FILODB_PALLAS", "1")
    assert PK.pallas_enabled(PK.MAX_T)
    assert not PK.pallas_enabled(PK.MAX_T + 128)

    class Irregular:  # what _pallas_variant reads of a staged block
        regular_ts = nominal_ts = mgrid = None

        def __init__(self, t):
            self.ts = np.zeros((8, t), np.int32)

    assert AGG._pallas_variant(Irregular(PK.MAX_T), "rate", None)
    assert not AGG._pallas_variant(Irregular(PK.MAX_T + 128), "rate", None)


# -- the narrow scan: only the lane tiles a window can touch ------------------

LANE, WIDE_T = PK.LANES, 6 * PK.LANES  # scraped.repeat's six lane tiles
STAT_SETS = sorted({PK.stat_set(f, c, d) for f in PK.PALLAS_FUNCS for c, d in SCHEMAS.values()})


def _rows(series, T, junk=None):
    """``[(ts, vals)]`` packed to the front of ``[S, T]`` rows the way the
    stage packs them (``TS_PAD`` after ``lens``, or ``junk`` timestamps
    there: what only ``lens`` keeps out of a window)."""
    S = len(series)
    ts = np.full((S, T), 2**31 - 1, np.int32)
    vals = np.zeros((S, T), np.float32)
    lens = np.array([len(t) for t, _ in series], np.int32)
    for s, (t, v) in enumerate(series):
        ts[s, :len(t)], vals[s, :len(t)] = t, v
        if junk is not None:
            ts[s, len(t):] = junk.integers(0, 7_000_000, T - len(t))
            vals[s, len(t):] = 1e6
    return ts, vals, lens


def _scrapes(rng, n, interval=10_000, start=0):
    t = start + rng.integers(0, interval) + np.arange(n) * interval
    return t + np.where(rng.random(n) < 0.1, rng.integers(2, 1000, n), 0), 50 + 20 * rng.standard_normal(n)


def _case(name):
    """(ts, vals, lens, (start, step, window), num_steps) of a block made
    to break a scan that reads two lane tiles a step."""
    rng = np.random.default_rng(sum(map(ord, name)))
    win, steps = (600_000, 60_000, 300_000), 110
    if name == "astride":
        # 70 series (a second, padded series tile) on a 10 s scrape, each
        # missing its own few scrapes: at a lane-tile boundary a window lies
        # astride it in some rows and wholly on one side in others
        series = []
        for _ in range(70):
            t, v = _scrapes(rng, 700)
            keep = rng.random(700) >= rng.choice([0.0, 0.005, 0.05])
            series.append((t[keep], v[keep]))
        return *_rows(series, WIDE_T), win, steps
    if name == "mixed":
        # tile 0 mixes 1 s and 60 s series (a window touches five lane tiles
        # of one, one of the other: whole rows), tile 1 is 10 s throughout
        series = [_scrapes(rng, 700, 1_000 if s % 2 else 60_000) for s in range(PK.BS)]
        series += [_scrapes(rng, 700) for _ in range(PK.BS)]
        return *_rows(series, WIDE_T), (300_000, 5_000, 300_000), steps
    if name == "hour":
        return *_rows([_scrapes(rng, 700) for _ in range(9)], WIDE_T), (3_600_000, 30_000, 3_600_000), steps
    if name == "ragged":
        # lens from 0 (an empty series) to 640: the sixth lane tile is empty
        # in every row, and what lies past lens has times INSIDE the windows
        lens = [0, 1, 2, 127, 128, 129, 640] + list(rng.integers(3, 640, 20))
        return *_rows([_scrapes(rng, n) for n in lens], WIDE_T, junk=rng), win, steps
    if name == "unsorted":
        # lanes NOT in time order: tile 0's rows shuffled inside each lane
        # tile (the tiles' time ranges stay apart: the narrow scan holds),
        # tile 1's rows across the whole series (every tile spans all times)
        series = []
        for s in range(2 * PK.BS):
            t, v = _scrapes(rng, 640)
            if s < PK.BS:
                order = np.concatenate([k * LANE + rng.permutation(LANE) for k in range(5)])
            else:
                order = rng.permutation(640)
            series.append((t[order], v[order]))
        return *_rows(series, WIDE_T), win, steps
    if name == "one_tile":
        return *_rows([_scrapes(rng, 120) for _ in range(5)], LANE), (100_000, 10_000, 300_000), steps
    if name == "max_t":
        series = [_scrapes(rng, int(n)) for n in rng.integers(3_900, PK.MAX_T, 6)]
        return *_rows(series, PK.MAX_T), (20_000_000, 120_000, 300_000), steps
    if name == "edges":
        # two step tiles, from 50 min before the first sample to 90 after
        # the last; the series begin up to 20 min apart
        series = [_scrapes(rng, 600, start=int(rng.integers(0, 1_200_000))) for _ in range(12)]
        return *_rows(series, WIDE_T), (-3_000_000, 60_000, 300_000), 2 * PK.BJ
    raise KeyError(name)


CASES = ["astride", "mixed", "hour", "ragged", "unsorted", "one_tile", "max_t", "edges"]
# what the steering table must say of each: some grid tile narrow, some whole
STEERED = {"astride": (True, False), "mixed": (True, True), "hour": (False, True),
           "ragged": (True, False), "unsorted": (True, True), "max_t": (True, False),
           "edges": (True, False)}


def _steer(ts, lens, win, num_steps):
    import jax.numpy as jnp

    lens_p, (ts_p,) = PK._pad_series(jnp.asarray(lens), jnp.asarray(ts))
    return np.asarray(PK._lane_tile_steer(ts_p, lens_p, *map(np.int32, win), PK._pad_bj(num_steps)))


_whole_rows = {}


def whole_row_scan(name, monkeypatch):
    """Every statistic of a case by the parent's body: every step reads
    its whole row (the kernel traced with the narrow scan switched off)."""
    if name not in _whole_rows:
        ts, vals, lens, win, num_steps = _case(name)
        with monkeypatch.context() as m:
            m.setattr(PK, "_narrow_scan", lambda T: False)
            _whole_rows[name] = PK.window_aggregates.__wrapped__(
                ts, vals, vals * 3, lens, *map(np.int32, win), num_steps,
                interpret=True, stats=PK.STATS)
    return _whole_rows[name]


@pytest.mark.parametrize("name", CASES)
@pytest.mark.parametrize("stats", STAT_SETS, ids="+".join)
def test_the_narrow_scan_gives_the_bits_of_the_whole_row(name, stats, monkeypatch):
    """The same kernel, steered by the block's own lane-tile table, against
    the scan of all ``T`` lanes: every statistic set, bit for bit."""
    want = whole_row_scan(name, monkeypatch)
    ts, vals, lens, win, num_steps = _case(name)
    got = PK.window_aggregates(ts, vals, vals * 3, lens, *map(np.int32, win), num_steps,
                               interpret=True, stats=stats)
    assert set(got) == set(stats)
    for k in stats:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]), err_msg=f"{name} {k}")


@pytest.mark.parametrize("name", sorted(STEERED))
def test_a_window_lies_inside_the_lane_tiles_its_step_is_steered_to(name):
    """The table's verdict against a brute force over the samples: where a
    step is steered to lane tile k, every valid sample of its window, in all
    BS series, lies in lanes [128 k, 128 (k + NARROW)); a grid tile is either
    steered in all its BJ steps or in none; and the case is on the side of
    the choice it was made for."""
    ts, _vals, lens, (start, step, window), num_steps = _case(name)
    steer = _steer(ts, lens, (start, step, window), num_steps)
    S, T = ts.shape
    assert steer.shape == (-(-S // PK.BS), PK._pad_bj(num_steps))
    by_tile = steer.reshape(steer.shape[0], -1, PK.BJ)
    assert ((by_tile >= 0).all(axis=2) | (by_tile == -1).all(axis=2)).all()
    assert steer.max() <= T // LANE - PK.NARROW
    assert ((steer >= 0).any(), (steer < 0).any()) == STEERED[name]
    lane = np.arange(T)
    for g in range(steer.shape[0]):
        rows = slice(g * PK.BS, min((g + 1) * PK.BS, S))
        real = lane[None, :] < lens[rows, None]
        for j in np.nonzero(steer[g] >= 0)[0]:
            t_j = start + j * step
            inside = real & (ts[rows] <= t_j) & (ts[rows] > t_j - window)
            lanes = np.nonzero(inside.any(axis=0))[0]
            lo = steer[g, j] * LANE
            assert lanes.size == 0 or (lo <= lanes.min() and lanes.max() < lo + PK.NARROW * LANE), (g, j)


def test_a_block_of_one_or_two_lane_tiles_is_the_parents_kernel():
    """Nothing to leave out: no table, no steering operand, no branch."""
    import functools

    import jax

    assert not PK._narrow_scan(LANE) and not PK._narrow_scan(2 * LANE)
    assert PK._narrow_scan(3 * LANE) and PK._narrow_scan(PK.MAX_T)

    def operands(name):
        ts, vals, lens, win, num_steps = _case(name)
        jaxpr = jax.make_jaxpr(functools.partial(
            PK.window_aggregates.__wrapped__, num_steps=num_steps, interpret=True,
            stats=("count",)))(ts, vals, vals, lens, *map(np.int32, win))
        (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        return len(call.invars), len(jaxpr.eqns)

    (few, eqns), (more, _) = operands("one_tile"), operands("astride")
    assert (few, more) == (4, 5)  # params, [steer,] ts, vals, lens
    assert eqns < 12  # the operands' casts and the call: no pass over ts


@pytest.mark.parametrize("func", ["rate", "irate", "avg_over_time", "max_over_time", "last"])
def test_the_narrow_scan_matches_the_general_kernel(func):
    """Through the finisher, on a block six lane tiles wide whose every
    grid tile is steered."""
    rng = np.random.default_rng(17)
    series = []
    for _ in range(7):
        t, v = _scrapes(rng, 700)
        series.append((BASE + t, np.cumsum(np.abs(v)) + 1e9 if func in RATE_FAMILY else v))
    counter = func in RATE_FAMILY
    block = stage(series, func, counter)
    assert block.ts.shape[1] == WIDE_T
    params = K.RangeParams(BASE + 600_000, 60_000, 100, 300_000)
    assert (_steer(np.asarray(block.ts), np.asarray(block.lens), (600_000, 60_000, 300_000), 100) >= 0).all()
    got, want = both(func, block, params, counter)
    assert_close(got, want, func)


def test_a_launch_books_the_lane_tiles_its_steps_read(monkeypatch):
    """``scanned`` and ``resident`` from the table the kernel is steered
    by: NARROW of six lane tiles a step where a grid tile is steered, all
    six where it is not, equal on a block two lane tiles wide; a repeated
    window reads the memo on the block."""
    from filodb_tpu.metrics import REGISTRY

    def booked(block, params):
        before = {k: REGISTRY.counter("filodb_pallas_lane_tiles", kind=k).value
                  for k in ("scanned", "resident")}
        PK.run_pallas_range_function("avg_over_time", block, params)
        return {k: REGISTRY.counter("filodb_pallas_lane_tiles", kind=k).value - v
                for k, v in before.items()}

    rng = np.random.default_rng(23)
    wide = stage([(BASE + t, v) for t, v in (_scrapes(rng, 700) for _ in range(70))], "avg_over_time", False)
    S = wide.ts.shape[0]
    grid_tiles = -(-S // PK.BS)
    assert grid_tiles >= 2 and wide.ts.shape[1] == WIDE_T
    five_min = K.RangeParams(BASE + 600_000, 60_000, 100, 300_000)
    got = booked(wide, five_min)
    assert got == {"scanned": grid_tiles * PK.BJ * PK.NARROW, "resident": grid_tiles * PK.BJ * 6}
    with monkeypatch.context() as m:  # the memo: the count is not made again
        m.setattr(PK, "_narrow_grid_tiles", None)
        assert booked(wide, five_min) == got
    hour = booked(wide, K.RangeParams(BASE + 3_600_000, 60_000, 50, 3_600_000))
    real_tiles = -(-wide.n_series // PK.BS)  # padded series tiles are empty: steered
    assert hour["resident"] == grid_tiles * PK.BJ * 6
    assert hour["scanned"] == PK.BJ * (real_tiles * 6 + (grid_tiles - real_tiles) * PK.NARROW)
    small = booked(make_block(), K.RangeParams(BASE + 400_000, 60_000, 20, 300_000))
    assert small["scanned"] == small["resident"] == PK.BJ * 2
