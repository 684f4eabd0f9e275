"""The base-2 epilogue's contract (``ops/aggregations._base2_epilogue``).

The merge onto each group's scale and the group sum run as 0/1 products on
the MXU. On whole counts they equal the gather along the bucket axis and the
per-column segment_sum they replaced, bit for bit (that form is kept here,
as the reference, and nowhere in the program); every product in the
lowered epilogue is exact on a TPU, whose DEFAULT precision would round an
f32 operand to bf16; and a missing sample is NaN across its whole row of
buckets in every range body, which the epilogue's count rests on. CPU
backend, small shapes. Times nothing.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from filodb_tpu.core.histograms import BASE2_WIDTH, Base2Scheme, base2_les_rows
from filodb_tpu.core.records import SeriesBatch
from filodb_tpu.core.schemas import METRIC_TAG, PROM_HISTOGRAM, Dataset
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.ops import aggregations as AGG
from filodb_tpu.ops import staging as ST
from filodb_tpu.ops.hist_kernels import histogram_quantile_rows, quantile_parts


def _gather_form(grid, epilogue, gids, shared, qv, num_groups):
    """The epilogue as a gather along the bucket axis (one column index a
    series and output column), then the per-column segment_sum."""
    _, kind, width = epilogue
    sjb, factor = grid
    S, J = sjb.shape[:2]
    scale, offset, n, s_g, o_g, k_g = shared[:6]
    d = scale - s_g[gids]
    k = jnp.arange(width, dtype=jnp.int32)[None, :]
    fine = (o_g[gids][:, None] + k) * jnp.left_shift(jnp.int32(1), d)[:, None]
    idx = jnp.clip(fine - offset[:, None], 0, n[:, None])
    idx = jnp.where(k == 0, 0,
                    jnp.where(k > k_g[gids][:, None], n[:, None] + 1, idx))
    sjw = jnp.take_along_axis(sjb, idx[:, None, :], axis=2)
    gjw = AGG._segment_aggregate_jit(
        "sum", sjw.reshape(S, J * width), gids, num_groups + 1
    )[:num_groups].reshape(num_groups, J, width)
    if kind != "quantile":
        return gjw * factor[None, :, None]
    return histogram_quantile_rows(qv[0], qv[1], gjw, shared[6])


# name: (groups, real series, padded rows, steps, largest count factor)
CASES = {
    "two_groups": (2, 40, 48, 8, 1),
    "forty_one_groups": (41, 120, 128, 8, 1),
    "counts_above_2_16": (2, 24, 32, 8, 1 << 9),
    "past_the_one_hot": (129, 300, 304, 4, 1),
}


def _inputs(case: str, seed: int = 5):
    """One block's epilogue operands: schemes at scales 2-6 with negative
    offsets (the first group holds one series at each, d = 0..4), the last
    group empty where there are more than two, step 0 under two samples
    (NaN for every series), a missing sample in a series of three, padded
    rows of NaN, +-Inf and huge garbage, whole counts."""
    G, n_real, s_pad, J, big = CASES[case]
    rng = np.random.default_rng(seed)
    scale = np.zeros(s_pad, np.int32)
    offset = np.zeros(s_pad, np.int32)
    n = np.zeros(s_pad, np.int32)
    scale[:n_real] = np.r_[2:7, rng.integers(2, 7, n_real - 5)]
    offset[:n_real] = (rng.integers(-9, -5, n_real) << scale[:n_real]) \
        + rng.integers(-3, 4, n_real)
    n[:n_real] = rng.integers(0, 161, n_real)
    n[1] = 160
    gids = np.full(s_pad, G, np.int32)
    used = G - 1 if G > 2 else G
    gids[:n_real] = np.r_[[0] * 5, np.arange(n_real - 5) % used].astype(np.int32)
    sjb = np.zeros((s_pad, J, BASE2_WIDTH), np.float32)
    for r in range(n_real):
        inc = rng.poisson(rng.uniform(0.5, 6.0), (J, n[r]))
        if big > 1 and r % 3:  # every bit of a count above 2^16 set at random
            inc = inc * big + rng.integers(0, big, inc.shape)
        c = np.cumsum(inc, axis=1)
        top = c[:, -1:] if n[r] else np.zeros((J, 1))
        sjb[r, :, : n[r] + 2] = np.concatenate([np.zeros((J, 1)), c, top], axis=1)
    sjb[:, 0] = np.nan  # a step under two samples
    sjb[:n_real:3, J // 2] = np.nan  # missing samples
    garbage = np.array([np.nan, np.inf, -np.inf, 3e38], np.float32)
    sjb[n_real:] = garbage[rng.integers(0, 4, sjb[n_real:].shape)]
    # the contract the products rest on: a column's sums stay below 2^24
    real = np.where(np.isfinite(sjb[:n_real]), sjb[:n_real], 0)
    assert np.abs(real).sum(axis=0).max() < 2 ** 24
    s_g, o_g, k_g, _ = AGG._base2_group_scheme(
        jnp.asarray(scale), jnp.asarray(offset), jnp.asarray(n),
        jnp.asarray(gids), G)
    width = AGG.pad8(int(np.asarray(k_g)[:G].max()) + 2)
    schemes = [Base2Scheme(int(a), int(b), int(c)) for a, b, c in
               zip(*(np.asarray(x)[:G] for x in (s_g, o_g, k_g)))]
    les = jnp.asarray(base2_les_rows(schemes, width).astype(np.float32))
    shared = (jnp.asarray(scale), jnp.asarray(offset), jnp.asarray(n),
              s_g, o_g, k_g, les)
    factor = jnp.asarray(rng.uniform(0.001, 0.01, J).astype(np.float32))
    return (jnp.asarray(sjb), factor), width, jnp.asarray(gids), shared, G


def _run(fn, grid, kind, width, gids, shared, G, q=0.9):
    jitted = jax.jit(fn, static_argnums=(1, 5))
    return np.asarray(jitted(grid, ("hist2", kind, width), gids, shared,
                             jnp.asarray(quantile_parts(q)), G))


@pytest.mark.parametrize("kind", ["sum", "quantile"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_products_are_the_gather_and_the_segment_sum(case, kind):
    grid, width, gids, shared, G = _inputs(case)
    got = _run(AGG._base2_epilogue, grid, kind, width, gids, shared, G)
    want = _run(_gather_form, grid, kind, width, gids, shared, G)
    assert got.shape == want.shape
    assert np.isnan(want).any() and np.isfinite(want).any()
    if G > 2:  # the empty group reads NaN at every step and column
        assert np.isnan(got[G - 1]).all()
    assert np.array_equal(got.view(np.uint32), want.view(np.uint32))


@pytest.mark.parametrize("case,form", [("two_groups", "onehot"),
                                       ("forty_one_groups", "onehot"),
                                       ("past_the_one_hot", "segment")])
def test_every_product_in_the_epilogue_is_exact(case, form):
    """Each dot_general takes bf16 pieces and accumulates in f32 (or asks
    for HIGHEST): DEFAULT on an f32 operand would keep 8 bits of a count
    on the chip, while the CPU computes it in f32 and would not show it."""
    grid, width, gids, shared, G = _inputs(case)
    assert AGG.hist_merge_form(G) == form
    text = jax.jit(AGG._base2_epilogue, static_argnums=(1, 5)).lower(
        grid, ("hist2", "quantile", width), gids, shared,
        jnp.asarray(quantile_parts(0.5)), G).as_text()
    dots = [l for l in text.splitlines() if "dot_general" in l]
    assert len(dots) == (3 if form == "onehot" else 1), dots
    for line in dots:
        types = re.search(r":\s*\((tensor<[^>]*>),\s*(tensor<[^>]*>)\)\s*->\s*(tensor<[^>]*>)",
                          line)
        assert types, line
        lhs, rhs, out = types.groups()
        exact = (lhs.endswith("xbf16>") and rhs.endswith("xbf16>")
                 and out.endswith("xf32>"))
        assert exact or "HIGHEST" in line, line


def _block(holes: bool):
    """A staged base-2 block whose series each miss a third of their
    scrapes (no shared grid: the per-series body runs), or one on a
    shared grid (``hist_shared``'s whole-count body)."""
    base, step = 1_600_000_000_000, 10_000
    ms = TimeSeriesMemStore()
    ms.setup(Dataset("ds"), [0])
    rng = np.random.default_rng(8)
    schemes = [Base2Scheme(3, -30, 40), Base2Scheme(2, -16, 22), Base2Scheme(5, -110, 120),
               Base2Scheme(4, -58, 70), Base2Scheme(6, -200, 150)]
    for i, s in enumerate(schemes):
        ts = base + np.arange(120, dtype=np.int64) * step
        if holes:
            ts = ts[np.sort(rng.choice(120, 80, replace=False))]
        c = np.cumsum(np.cumsum(rng.poisson(0.5, (len(ts), s.n)), axis=1), axis=0)
        counts = np.concatenate([np.zeros((len(ts), 1)), c, c[:, -1:]], axis=1)
        ms.shard("ds", 0).ingest_series(SeriesBatch(
            PROM_HISTOGRAM, {METRIC_TAG: "lat", "_ws_": "w", "_ns_": "n", "i": str(i)},
            ts, {"sum": counts[:, -1] * 0.01, "count": counts[:, -1], "h": counts},
            bucket_scheme=s))
    shard = ms.shard("ds", 0)
    block = ST.stage_from_shard(shard, np.array(sorted(shard.partitions)), "h",
                                base, base + 119 * step, mode="corrected")
    return block, base, len(schemes)


@pytest.mark.parametrize("func", ["rate", "increase", "last"])
@pytest.mark.parametrize("holes", [True, False], ids=["missing_samples", "shared_grid"])
def test_a_missing_sample_is_a_whole_row_of_buckets(holes, func):
    """The epilogue counts a sample from its zero bucket: on a block with
    missing samples, and on a shared grid, the body writes NaN across a
    (series, step)'s whole row of buckets, or nowhere in it."""
    block, base, n_real = _block(holes)
    assert (block.regular_ts is None) == holes and block.schemes is not None
    name, _ = AGG._fused_body(True, block, func, False, None)
    assert name == ("hist_general" if holes else "hist_shared")
    body = AGG.FUSED_BODIES[name]
    j_pad = 32  # the last steps lie past the data
    wins = body.windows(block, base + 300_000 - block.base_ms, 30_000, j_pad,
                        30_000, None)
    grid = (body.base2_grid or body.grid)(
        func, body.rows(block), wins, *body.statics(block, j_pad, False, False))
    grid = np.asarray(grid[0] if isinstance(grid, tuple) else grid)
    gaps = np.isnan(grid[:n_real])
    assert gaps.any() and not gaps.all(), name
    assert (gaps == gaps[:, :, :1]).all(), name
