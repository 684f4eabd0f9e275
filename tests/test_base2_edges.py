"""A cumulative column's window increase in the base-2 body
(``ops/hist_kernels._hist_base2_shared``, ``edges="product"``).

Each window's increase is ONE +-1 product of the block with a [J, T] edge
matrix on the MXU, where every value of the block is a whole number below
2^23 (``ops/aggregations.hist_edge_form``, a device reduction memoised on
the block); elsewhere the two gathers along T stay. The product equals the
gathers bit for bit (that form is kept here, as the reference); a block
with a NaN, an Inf or a count at the bound takes the gathers and answers as
before; the lowered program reads the [S, T, B] parameter through no
gather; the delta body's window sum lowers to the text it had; every
cumulative launch books its form. CPU backend, small shapes. Times nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import expo_delta_histograms, expo_histograms
from filodb_tpu.coordinator.planner import QueryEngine
from filodb_tpu.core.schemas import Dataset
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.metrics import REGISTRY
from filodb_tpu.ops import aggregations as AGG
from filodb_tpu.ops import staging as ST
from filodb_tpu.ops.hist_kernels import _hist_base2_shared
from filodb_tpu.ops.kernels import RangeParams, pad_steps
from filodb_tpu.query.exec.plans import _base2_sidecars

BASE = 1_600_000_000_000
INTERVAL = 10_000
WINDOW = 300_000
BOUND = 1 << 23
CONFIG = {
    "samples_per_series": 120, "warmup_scrapes": 30, "services": 4,
    "median_s": [0.002, 0.5], "sigma": [0.2, 1.5], "per_scrape": [5, 200],
    "timeout": {"services": 1, "share": 0.01, "range_s": [1, 30]},
    "max_buckets": 160, "interval_ms": INTERVAL, "metric": "lat",
}
QUERY = "histogram_quantile(0.9, sum by (service, le) ({}(lat_bucket[5m])))"
START_MS = BASE + 400_000
STEPS = 11


def _counter(name: str, **labels) -> float:
    want = set(labels.items())
    with REGISTRY._lock:
        return sum(m.value for (n, ls), m in REGISTRY._metrics.items()
                   if n == name and want <= set(ls))


def _gather_form(vals, lo, hi):
    """Each window's increase as the two gathers along T."""
    T = vals.shape[1]
    return (jnp.take(vals, jnp.clip(hi - 1, 0, T - 1), axis=1)
            - jnp.take(vals, jnp.clip(lo, 0, T - 1), axis=1))


# -- the product against the gathers, on the body's own operands --------------

def _operands(case: str, seed: int):
    """[S, T, B] cumulative whole counts on ragged widths (zeros behind),
    and [J] windows of 0, 1 and >= 2 samples, one ending at T - 1, one past
    the data (clipped), one at the start. ``near_the_bound``: counts within
    a few hundred of 2^23, of either sign."""
    rng = np.random.default_rng(seed)
    S, T, B = 24, 96, 40
    vals = np.zeros((S, T, B), np.float32)
    for s in range(S):
        n = int(rng.integers(0, B - 1))
        inc = rng.poisson(rng.uniform(0.5, 40.0), (T, n))
        c = np.cumsum(np.cumsum(inc, axis=0), axis=1)
        top = c[:, -1:] if n else np.zeros((T, 1))
        vals[s, :, :n + 2] = np.concatenate([np.zeros((T, 1)), c, top], axis=1)
    if case == "near_the_bound":
        sign = np.where(rng.random((S, 1, B)) < 0.5, -1.0, 1.0)
        vals = (sign * (BOUND - 1 - np.minimum(vals, 500))).astype(np.float32)
        vals[:, :, -1] = 0.0  # a column of zeros behind
    lo = rng.integers(0, T, 32)
    hi = np.minimum(lo + rng.integers(0, 30, 32), T)
    lo[:4], hi[:4] = [5, 7, 0, T - 3], [5, 8, 0, T]   # 0, 1, none at t=0, the end
    lo[4:6], hi[4:6] = [T, T + 3], [T, T + 9]        # past the data: clipped
    assert abs(vals).max() < BOUND and (vals == np.round(vals)).all()
    return jnp.asarray(vals), jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32)


def _body(func, vals, lo, hi, edges):
    J = lo.shape[0]
    out_t = jnp.arange(J, dtype=jnp.int32) * 60_000 + 400_000
    t_first = lo * INTERVAL
    t_last = (hi - 1) * INTERVAL
    return jax.jit(_hist_base2_shared, static_argnums=(0, 8, 9))(
        func, vals, lo, hi, t_first, t_last, out_t, jnp.int32(WINDOW), False,
        edges)


def _bits(x):
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("case", ["counts", "near_the_bound"])
@pytest.mark.parametrize("func", ["rate", "increase", "delta"])
def test_the_product_is_the_gathers_bit_for_bit(func, case, seed):
    vals, lo, hi = _operands(case, seed)
    got, factor = _body(func, vals, lo, hi, "product")
    want, factor2 = _body(func, vals, lo, hi, "gather")
    assert np.array_equal(_bits(got), _bits(want))
    assert np.array_equal(_bits(factor), _bits(factor2))
    cnt = np.asarray(hi - lo)
    ref = np.asarray(_gather_form(vals, lo, hi))
    many = cnt >= 2
    assert np.array_equal(_bits(np.asarray(got)[:, many]), _bits(ref[:, many]))
    assert np.isnan(np.asarray(got)[:, ~many]).all() and (~many).sum() >= 4


@pytest.mark.parametrize("plant", [np.nan, np.inf, -np.inf, BOUND, -BOUND, 0.5])
def test_the_test_of_a_block(plant):
    vals, _lo, _hi = _operands("counts", 3)
    assert bool(AGG._whole_below_bound(vals))
    bad = vals.at[5, 40, 3].set(plant)
    assert not bool(AGG._whole_below_bound(bad))
    assert bool(AGG._whole_below_bound(vals.at[5, 40, 3].set(BOUND - 1)))


# -- the launch: a staged block, planted samples, the counter ---------------------

def _store(make, n: int = 16, seed: int = 7):
    data = make(dict(CONFIG), n, np.random.default_rng(seed), BASE)
    ms = TimeSeriesMemStore()
    ms.setup(Dataset("prometheus"), [0])
    assert data.load(ms, 0) == data.n_samples
    return data, ms


@pytest.fixture(scope="module")
def cumulative():
    return _store(expo_histograms.make)


def _staged(ms, data):
    shard = ms.shard("prometheus", 0)
    pids = np.array(sorted(shard.partitions))
    block = ST.stage_from_shard(shard, pids, "h", int(data.ts[0]), int(data.ts[-1]),
                                mode="raw")
    assert block.schemes is not None and block.regular_ts is not None
    return block


def _params():
    return RangeParams(START_MS, 60_000, STEPS, WINDOW)


def _fleet_group(block):
    """``(gids, scheme sidecars, plan)`` of one group, the fleet's."""
    s_pad = np.asarray(block.lens).shape[0]
    gids = jnp.asarray(np.where(np.arange(s_pad) < block.n_series, 0, 1).astype(np.int32))
    scheme_dev = tuple(jnp.asarray(a) for a in _base2_sidecars(block.schemes, s_pad))
    return gids, scheme_dev, AGG.base2_group_plan(block, gids, 1, scheme_dev, ("fleet",))


def _launch(block, func="rate", q=0.9):
    """One fused base-2 launch over ``block``."""
    gids, scheme_dev, plan = _fleet_group(block)
    return np.asarray(AGG.fused_base2_hist_aggregate(
        func, block, gids, 1, _params(), plan, scheme_dev, q=q))


def _interior(block):
    """A (series, sample) that no window of the launch reads as an edge."""
    tsv = np.asarray(block.regular_ts)[:int(np.asarray(block.lens)[0])]
    out_t = START_MS - block.base_ms + np.arange(pad_steps(STEPS)) * 60_000
    hi = np.searchsorted(tsv, out_t, side="right")
    lo = np.searchsorted(tsv, out_t - WINDOW, side="right")
    edges = set(np.clip(lo, 0, len(tsv) - 1)) | set(np.clip(hi - 1, 0, len(tsv) - 1))
    t = next(t for t in range(len(tsv) // 2, len(tsv)) if t not in edges)
    return 2, t


@pytest.mark.parametrize("func", ["rate", "increase"])
def test_a_whole_count_block_takes_the_product(cumulative, func):
    data, ms = cumulative
    block = _staged(ms, data)
    assert AGG.hist_edge_form(block, func, False) == "product"
    assert AGG.hist_edge_form(block, func, True) is None
    assert AGG.hist_edge_form(block, "last", False) is None
    got = _launch(block, func)
    want = _launch(_forced(ms, data, "gather"), func)
    assert np.isfinite(got).any()
    assert np.array_equal(_bits(got), _bits(want))


def _forced(ms, data, form):
    """A fresh block whose memoised edge form is ``form``."""
    block = _staged(ms, data)
    block.__dict__["_edge_form"] = {"form": form}
    return block


@pytest.mark.parametrize("plant", [np.nan, np.inf, float(BOUND)],
                         ids=["nan", "inf", "at_the_bound"])
def test_a_planted_block_takes_the_gathers_and_answers_as_before(cumulative, plant):
    data, ms = cumulative
    clean = _staged(ms, data)
    want = _launch(clean)
    assert clean.__dict__["_edge_form"]["form"] == "product"
    block = _staged(ms, data)
    r, t = _interior(block)
    vals = np.array(block.vals)
    vals[r, t, 1] = plant
    block.vals = vals
    before = {f: _counter("filodb_hist_edges", form=f) for f in ("product", "gather")}
    got = _launch(block)
    assert _counter("filodb_hist_edges", form="gather") == before["gather"] + 1
    assert _counter("filodb_hist_edges", form="product") == before["product"]
    assert np.array_equal(_bits(got), _bits(want))
    if not np.isfinite(plant):  # the product would carry it into every window
        body = AGG.FUSED_BODIES["hist_shared"]
        wins = body.windows(block, START_MS - block.base_ms, 60_000,
                            pad_steps(STEPS), WINDOW, None)
        prod, _f = body.base2_grid("rate", body.rows(block), wins, False, "product")
        gath, _f = body.base2_grid("rate", body.rows(block), wins, False, "gather")
        assert np.isnan(np.asarray(prod)[r, :, 1]).all()  # every window
        assert np.isfinite(np.asarray(gath)[r, :, 1]).any()


# -- the lowered programs --------------------------------------------------------

def _param_gathers(text: str, shape: tuple) -> list:
    """Lines that gather from the f32 parameter of ``shape``: a gather op,
    or a call of the private ``_take`` function that holds one."""
    ty = "tensor<" + "x".join(map(str, shape)) + "xf32>"
    return [l for l in text.splitlines()
            if ("gather" in l or "@_take" in l) and ty in l.split("->")[0]]


@pytest.mark.parametrize("form,dots", [("product", 1), ("gather", 0)])
def test_the_rate_body_reads_the_block_through_one_product(cumulative, form, dots):
    data, ms = cumulative
    block = _staged(ms, data)
    body = AGG.FUSED_BODIES["hist_shared"]
    wins = body.windows(block, START_MS - block.base_ms, 60_000, pad_steps(STEPS),
                        WINDOW, None)
    text = jax.jit(lambda rows, w: body.base2_grid("rate", rows, w, False, form)).lower(
        body.rows(block), wins).as_text()
    found = [l for l in text.splitlines() if "dot_general" in l]
    assert len(found) == dots and all("HIGHEST" in l for l in found), found
    reads = _param_gathers(text, block.vals.shape)
    assert len(reads) >= 2 if form == "gather" else reads == [], reads


def test_the_launched_program_holds_no_gather_of_the_block(cumulative):
    data, ms = cumulative
    block = _staged(ms, data)
    gids, scheme_dev, (group_dev, width, _s, _r) = _fleet_group(block)
    body = AGG.FUSED_BODIES["hist_shared"]
    j_pad = pad_steps(STEPS)
    wins = body.windows(block, START_MS - block.base_ms, 60_000, j_pad, WINDOW, None)
    texts = {}
    for form in ("product", "gather"):
        spec = AGG.FusedSpec("hist_shared", "rate", ("hist2", "quantile", width), 1,
                             (False, form))
        texts[form] = AGG._fused_program_jit.lower(
            spec, body.rows(block), wins, gids, scheme_dev + tuple(group_dev),
            jnp.asarray(np.array([0.9, 0.0], np.float32))).as_text()
    assert _param_gathers(texts["product"], block.vals.shape) == []
    assert len(_param_gathers(texts["gather"], block.vals.shape)) >= 2
    highest = [l for l in texts["product"].splitlines()
               if "dot_general" in l and "HIGHEST" in l]
    assert len(highest) == 1, highest


def _parent_delta_rate(rows, win):
    """The delta ``rate`` body as it read before the edge product: the
    window sum, one HIGHEST product with the 0/1 membership, 1 / window."""
    (vals,), (lo, hi, _tf, _tl, _out_t, window) = rows, win
    f32 = vals.dtype
    T = vals.shape[1]
    cnt = (hi - lo).astype(f32)
    has = (cnt > 0)[None, :, None]
    t = jnp.arange(T, dtype=lo.dtype)[None, :]
    member = ((t >= lo[:, None]) & (t < hi[:, None])).astype(f32)
    s = jnp.einsum("jt,stb->sjb", member, vals,
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=f32)
    sums = jnp.where(has, s, jnp.nan)
    factor = jnp.ones(lo.shape, f32)
    return sums, factor / (window.astype(f32) * 1e-3)


def test_the_delta_window_sum_lowers_as_before():
    data, ms = _store(expo_delta_histograms.make, n=8, seed=11)
    block = _staged(ms, data)
    body = AGG.FUSED_BODIES["hist_shared"]
    wins = body.windows(block, START_MS - block.base_ms, 60_000, pad_steps(STEPS),
                        WINDOW, None)
    text = jax.jit(lambda rows, w: body.base2_grid("rate", rows, w, True)).lower(
        body.rows(block), wins).as_text()
    want = jax.jit(lambda rows, w: _parent_delta_rate(rows, w)).lower(
        body.rows(block), wins).as_text()
    assert text == want
    assert AGG.hist_edge_form(block, "rate", True) is None


# -- the counter, through the engine ------------------------------------------------

def _query(ms, func):
    eng = QueryEngine(ms, "prometheus")
    return eng.query_range(QUERY.format(func), START_MS / 1000,
                           (START_MS + (STEPS - 1) * 60_000) / 1000, 60)


def test_a_cumulative_launch_books_the_product_once(cumulative):
    _data, ms = cumulative
    before = {f: _counter("filodb_hist_edges", form=f) for f in ("product", "gather")}
    window = _counter("filodb_hist_window", form="edges")
    ran = _counter("filodb_fused_dispatch", body="hist_shared")
    res = _query(ms, "rate")
    assert np.isfinite(res.grids[0].values_np()).any()
    assert _counter("filodb_fused_dispatch", body="hist_shared") == ran + 1
    assert _counter("filodb_hist_edges", form="product") == before["product"] + 1
    assert _counter("filodb_hist_edges", form="gather") == before["gather"]
    assert _counter("filodb_hist_window", form="edges") == window + 1


def test_a_delta_launch_books_no_edge_form():
    _data, ms = _store(expo_delta_histograms.make, n=8, seed=12)
    before = {f: _counter("filodb_hist_edges", form=f) for f in ("product", "gather")}
    window = {f: _counter("filodb_hist_window", form=f) for f in ("edges", "sums")}
    res = _query(ms, "rate")
    assert np.isfinite(res.grids[0].values_np()).any()
    for f in ("product", "gather"):
        assert _counter("filodb_hist_edges", form=f) == before[f]
    assert _counter("filodb_hist_window", form="sums") == window["sums"] + 1
    assert _counter("filodb_hist_window", form="edges") == window["edges"]

