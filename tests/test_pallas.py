"""Pallas fused window-aggregate kernel vs the general kernel (interpret
mode on CPU; the same kernel compiles for TPU with interpret=False)."""

import numpy as np
import pytest

from filodb_tpu.ops import kernels as K
from filodb_tpu.ops import pallas_kernels as PK
from filodb_tpu.ops.staging import stage_series

BASE = 1_600_000_000_000


def make_block(n_series=5, n=200, seed=0, counter=False):
    rng = np.random.default_rng(seed)
    series = []
    for i in range(n_series):
        ts = BASE + np.cumsum(rng.integers(5000, 15000, n)).astype(np.int64)
        if counter:
            vals = np.cumsum(rng.uniform(0, 10, n)) + 1e9
            k = n // 2
            vals[k:] -= vals[k] - 3.0
        else:
            vals = 50 + 20 * rng.standard_normal(n)
        series.append((ts, vals))
    return stage_series(series, BASE, counter_corrected=counter)


def compare(func, counter=False, seed=0):
    block = make_block(seed=seed, counter=counter)
    params = K.RangeParams(BASE + 400_000, 60_000, 20, 300_000)
    got = np.asarray(
        PK.run_pallas_range_function(func, block, params, is_counter=counter)
    )[:5, :20]
    want = np.asarray(
        K.run_range_function(func, block, params, is_counter=counter)
    )[:5, :20]
    np.testing.assert_array_equal(np.isnan(got), np.isnan(want), err_msg=func)
    m = ~np.isnan(want)
    np.testing.assert_allclose(got[m], want[m], rtol=2e-4, atol=1e-4, err_msg=func)


@pytest.mark.parametrize("func", sorted(PK.PALLAS_FUNCS - {"rate", "increase", "delta"}))
def test_pallas_matches_general_gauge(func):
    compare(func, counter=False, seed=3)


@pytest.mark.parametrize("func", ["rate", "increase", "delta"])
def test_pallas_matches_general_counter(func):
    compare(func, counter=True, seed=4)


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
