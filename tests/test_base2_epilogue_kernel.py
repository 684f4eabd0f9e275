"""The base-2 epilogue's merge and group sum as one Pallas kernel
(``ops/pallas_kernels.base2_merge_sum``).

Where the one Pallas policy selects kernels, the sum is ``onehot`` and the
kernel's VMEM plan fits, a base-2 launch merges every series onto its
group's scheme and sums by group in ONE kernel whose intermediates stay in
VMEM (``aggregations.hist_epilogue_form``); elsewhere the XLA form's two
products run. The kernel is the XLA form bit for bit: at 1, 40 and 127
groups, W of 112 and 176, series 0-3 scales above their group's, padded and
trash-group rows, an all-NaN row, +-Inf values, both kinds, and through a
launch of either window form. Every base-2 launch books its form once
(``filodb_hist_epilogue_total``). CPU backend, the kernel in interpret mode
(``FILODB_PALLAS=1``), small shapes. Times nothing.
"""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import expo_delta_histograms, expo_histograms
from filodb_tpu.core.histograms import BASE2_WIDTH, Base2Scheme, base2_les_rows
from filodb_tpu.core.schemas import Dataset
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.metrics import REGISTRY
from filodb_tpu.ops import aggregations as AGG
from filodb_tpu.ops import pallas_kernels as PK
from filodb_tpu.ops import staging as ST
from filodb_tpu.ops.hist_kernels import quantile_parts
from filodb_tpu.ops.kernels import RangeParams, pad_steps
from filodb_tpu.query.exec.plans import _base2_sidecars

J = 128  # the kernel's step tile: a launch of 65-128 steps
S = 176  # eleven series tiles
N_REAL = 168


def _counter(name: str, **labels) -> float:
    want = set(labels.items())
    with REGISTRY._lock:
        return sum(m.value for (n, ls), m in REGISTRY._metrics.items()
                   if n == name and want <= set(ls))


def _bits(x) -> np.ndarray:
    return np.asarray(x).view(np.uint32)


# -- the epilogue's operands, made by hand ----------------------------------------


def _grid(G: int, W: int, seed: int = 3, J: int = J):
    """One [S, J, B] grid of whole counts and its epilogue operands: a
    group's series at 0-3 scales above its smallest (scale 2 + i // G mod 4,
    one more in odd groups), negative offsets, one empty scheme and one of
    as many buckets as W holds (160 at W 176), two on either side of 128
    buckets (n + 2 = 128, 129); step 0 under two samples (NaN for every series), missing
    samples, one real series NaN at every step, +-Inf planted in real rows
    (one in a zero bucket), padded rows of garbage in the trash group."""
    rng = np.random.default_rng(seed)
    gids = np.full(S, G, np.int32)
    gids[:N_REAL] = np.arange(N_REAL) % G
    scale = np.zeros(S, np.int32)
    offset = np.zeros(S, np.int32)
    n = np.zeros(S, np.int32)
    real = np.arange(N_REAL)
    scale[:N_REAL] = 2 + (real // G) % 4 + gids[:N_REAL] % 2
    offset[:N_REAL] = (-7 << scale[:N_REAL]) + rng.integers(-3, 4, N_REAL)
    widest = min(W - 12, 160)
    n[:N_REAL] = rng.integers(1, widest + 1, N_REAL)
    n[1], n[2] = widest, 0
    n[3:5] = min(126, widest), min(127, widest)  # 128 buckets and the first past them
    sjb = np.zeros((S, J, BASE2_WIDTH), np.float32)
    for r in range(N_REAL):
        c = np.cumsum(rng.poisson(rng.uniform(0.2, 3.0), (J, n[r])), axis=1)
        top = c[:, -1:] if n[r] else np.zeros((J, 1))
        sjb[r, :, : n[r] + 2] = np.concatenate([np.zeros((J, 1)), c, top], axis=1)
    sjb[:, 0] = np.nan  # a step under two samples
    sjb[:N_REAL:5, J // 2] = np.nan  # missing samples
    sjb[7] = np.nan  # a series with no sample at all
    sjb[11, 3, 0] = np.inf  # in a zero bucket: the sample is there, the value 0
    sjb[13, 5, 9] = -np.inf
    sjb[17, 9, 40] = np.inf
    garbage = np.array([np.nan, np.inf, -np.inf, 3e38], np.float32)
    sjb[N_REAL:] = garbage[rng.integers(0, 4, sjb[N_REAL:].shape)]
    # the contract both forms rest on: a column's sums stay below 2^24
    assert np.abs(np.where(np.isfinite(sjb[:N_REAL]), sjb[:N_REAL], 0)).sum(axis=0).max() < 2 ** 24
    s_g, o_g, k_g, _ = AGG._base2_group_scheme(
        jnp.asarray(scale), jnp.asarray(offset), jnp.asarray(n), jnp.asarray(gids), G)
    assert set(np.asarray(scale - np.asarray(s_g)[gids])[:N_REAL]) >= {0, 1} | ({2, 3} if G < 127 else set())
    assert AGG.pad8(int(np.asarray(k_g)[:G].max()) + 2) <= W  # the launch's own width fits
    return jnp.asarray(sjb), jnp.asarray(gids), (
        jnp.asarray(scale), jnp.asarray(offset), jnp.asarray(n), s_g, o_g, k_g)


def _shared(shared6, G: int, W: int):
    schemes = [Base2Scheme(int(a), int(b), int(c)) for a, b, c in
               zip(*(np.asarray(x)[:G] for x in shared6[3:6]))]
    return shared6 + (jnp.asarray(base2_les_rows(schemes, W).astype(np.float32)),)


def _xla_partials(sjb, gids, shared, G: int, W: int):
    ok = ~jnp.isnan(sjb[:, :, 0]) & (gids < G)[:, None]
    clean = jnp.where(ok[:, :, None] & jnp.isfinite(sjb), sjb, 0.0)
    return AGG._base2_group_sum(AGG._base2_rescale(clean, gids, shared, W), ok, gids, G)


def _epilogue(route: str, form: str, sjb, gids, shared, G: int, W: int):
    factor = jnp.linspace(0.001, 0.01, J, dtype=jnp.float32)
    epilogue = ("hist2", route, W) + (("pallas",) if form == "pallas" else ())
    run = jax.jit(AGG._base2_epilogue, static_argnums=(1, 5))
    return run((sjb, factor), epilogue, gids, shared,
               jnp.asarray(quantile_parts(0.9)), G)


@pytest.mark.parametrize("route", ["kernel", "sum", "quantile"])
@pytest.mark.parametrize("W", [112, 176])
@pytest.mark.parametrize("G", [1, 40, 127])
def test_the_kernel_is_the_xla_form_bit_for_bit(G, W, route):
    """``kernel``: base2_merge_sum's [G, J, W] against
    _base2_group_sum(_base2_rescale(...)); ``sum`` / ``quantile``: the whole
    _base2_epilogue in its two forms."""
    sjb, gids, shared6 = _grid(G, W)
    shared = _shared(shared6, G, W)
    assert AGG.hist_merge_form(G) == "onehot"
    if route == "kernel":
        got = PK.base2_merge_sum(
            sjb, gids, AGG._base2_select_scalars(gids, shared), G, W,
            PK.base2_epilogue_tile(S, J, BASE2_WIDTH, W, G), True)
        want = _xla_partials(sjb, gids, shared, G, W)
    else:
        got = _epilogue(route, "pallas", sjb, gids, shared, G, W)
        want = _epilogue(route, "xla", sjb, gids, shared, G, W)
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape == ((G, J, W) if route != "quantile" else (G, J))
    assert np.isnan(want).any() and np.isfinite(want).any()
    assert np.isnan(want[:, 0]).all()  # the step no series has
    assert np.array_equal(_bits(got), _bits(want))


def test_the_kernel_walks_step_tiles_bit_for_bit():
    """256 steps: two step tiles, each with its own sum resident across the
    series axis."""
    G, W, steps = 40, 176, 256
    sjb, gids, shared6 = _grid(G, W, J=steps)
    shared = _shared(shared6, G, W)
    tile = PK.base2_epilogue_tile(S, steps, BASE2_WIDTH, W, G)
    assert steps // tile == 2
    got = PK.base2_merge_sum(sjb, gids, AGG._base2_select_scalars(gids, shared),
                             G, W, tile, True)
    want = _xla_partials(sjb, gids, shared, G, W)
    assert np.isfinite(np.asarray(want)[:, steps - 1]).any()
    assert np.array_equal(_bits(got), _bits(want))


def test_a_plan_that_does_not_fit_and_the_segment_form_take_xla(monkeypatch):
    """The plan reckons from the static (S, J, B, W, G) alone; 128 groups and
    more sum by segment_sum, which the kernel does not model."""
    monkeypatch.setenv("FILODB_PALLAS", "1")
    B = BASE2_WIDTH

    def form(S, J, W, G):
        block = types.SimpleNamespace(vals=jax.ShapeDtypeStruct((S, 768, B), jnp.float32))
        return AGG.hist_epilogue_form(block, G, J, W)

    assert PK.base2_epilogue_tile(4096, 128, B, 176, 127) == PK.LANES
    assert form(4096, 128, 176, 40) == form(4096, 256, 120, 1) == "pallas"
    assert PK.base2_epilogue_tile(4096, 128, B, 2048, 127) is None  # [G, W, 128] x 2 past the plan
    assert form(4096, 128, 2048, 127) == "xla"
    assert form(4096, 64, 176, 40) == "xla"  # a step tile is 128 lanes
    assert form(4088, 128, 176, 40) == "xla"  # a series tile is B2_SERIES rows
    assert AGG.hist_merge_form(127) == "onehot" and AGG.hist_merge_form(128) == "segment"
    assert PK.base2_epilogue_tile(4096, 128, B, 176, 128) == PK.LANES
    assert form(4096, 128, 176, 128) == "xla"
    monkeypatch.setenv("FILODB_PALLAS", "0")
    assert form(4096, 128, 176, 40) == "xla"
    monkeypatch.delenv("FILODB_PALLAS")
    assert form(4096, 128, 176, 40) == "xla"  # the CPU default


# -- a launch of each window form ----------------------------------------------------

BASE = 1_600_000_000_000
INTERVAL = 10_000
WINDOW = 300_000
CONFIG = {
    "samples_per_series": 120, "warmup_scrapes": 30, "services": 4,
    "median_s": [0.002, 0.5], "sigma": [0.2, 1.5], "per_scrape": [5, 200],
    "timeout": {"services": 1, "share": 0.01, "range_s": [1, 30]},
    "max_buckets": 160, "interval_ms": INTERVAL, "metric": "lat",
}
STEPS = 70  # J = 128: 10 s steps from 400 s to 1 090 s of a 1 190 s fleet
PARAMS = RangeParams(BASE + 400_000, INTERVAL, STEPS, WINDOW)


def _block(make, seed: int):
    data = make(dict(CONFIG), 16, np.random.default_rng(seed), BASE)
    ms = TimeSeriesMemStore()
    ms.setup(Dataset("prometheus"), [0])
    assert data.load(ms, 0) == data.n_samples
    shard = ms.shard("prometheus", 0)
    block = ST.stage_from_shard(shard, np.array(sorted(shard.partitions)), "h",
                                int(data.ts[0]), int(data.ts[-1]), mode="raw")
    assert block.schemes is not None and block.regular_ts is not None
    return block


@pytest.fixture(scope="module")
def blocks():
    return {"product": _block(expo_histograms.make, 7),
            "sums": _block(expo_delta_histograms.make, 12)}


def _launch(block, G: int, delta: bool, q):
    """One fused base-2 launch over ``block``, its series dealt round the G
    groups."""
    s_pad = np.asarray(block.lens).shape[0]
    gids = jnp.asarray(np.where(np.arange(s_pad) < block.n_series,
                                np.arange(s_pad) % G, G).astype(np.int32))
    scheme_dev = tuple(jnp.asarray(a) for a in _base2_sidecars(block.schemes, s_pad))
    plan = AGG.base2_group_plan(block, gids, G, scheme_dev, ("by", G))
    return np.asarray(AGG.fused_base2_hist_aggregate(
        "rate", block, gids, G, PARAMS, plan, scheme_dev, q=q, is_delta=delta))


def _booked():
    return {(name, form): _counter(name, form=form)
            for name, forms in (("filodb_hist_epilogue", ("pallas", "xla")),
                                ("filodb_hist_merge", ("onehot", "segment")))
            for form in forms}


def _moved(before) -> dict:
    return {k: v - before[k] for k, v in _booked().items() if v != before[k]}


@pytest.mark.parametrize("q", [0.9, None], ids=["quantile", "partials"])
@pytest.mark.parametrize("window", ["product", "sums"])
def test_a_launch_is_the_xla_form_bit_for_bit(blocks, monkeypatch, window, q):
    """A cumulative column's rate (the +-1 edge product) and a delta
    column's (the window sums): forced, the launch runs the kernel and
    books it once; on the CPU's default the XLA form, booked once; the
    merge form books as it did."""
    block = blocks[window]
    assert pad_steps(STEPS) == J and np.asarray(block.lens).shape[0] % PK.B2_SERIES == 0
    if window == "product":
        assert AGG.hist_edge_form(block, "rate", False) == "product"
    answers = {}
    for form, env in (("pallas", "1"), ("xla", None)):
        if env is None:
            monkeypatch.delenv("FILODB_PALLAS", raising=False)
        else:
            monkeypatch.setenv("FILODB_PALLAS", env)
        before = _booked()
        answers[form] = _launch(block, 3, window == "sums", q)
        assert _moved(before) == {("filodb_hist_epilogue", form): 1,
                                  ("filodb_hist_merge", "onehot"): 1}
    assert np.isfinite(answers["xla"]).any()
    assert np.array_equal(_bits(answers["pallas"]), _bits(answers["xla"]))


def test_past_the_one_hot_a_forced_launch_books_xla(blocks, monkeypatch):
    """128 groups sum by segment_sum: the kernel does not engage."""
    monkeypatch.setenv("FILODB_PALLAS", "1")
    before = _booked()
    got = _launch(blocks["product"], 128, False, 0.9)
    assert np.isfinite(got).any()
    assert _moved(before) == {("filodb_hist_epilogue", "xla"): 1,
                              ("filodb_hist_merge", "segment"): 1}
