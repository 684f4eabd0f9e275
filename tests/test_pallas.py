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
