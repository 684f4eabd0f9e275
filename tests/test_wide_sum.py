"""The wide sum (ops/aggregations._wide_aggregate): a fused ``sum`` / ``avg``
over series of a value-returning range function holds the limit of the cell
``counters.repeat`` on counters that read about 1e9, at a size where the
plain f32 segment sum does not; the sum is exact whatever the order, the
mesh or the number of groups; everything else keeps the plain form.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip.regular_counters import counter_values
from filodb_tpu.coordinator.planner import QueryEngine
from filodb_tpu.core.records import RecordBatch
from filodb_tpu.core.schemas import METRIC_TAG, PROM_COUNTER, Dataset
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.metrics import REGISTRY
from filodb_tpu.ops import aggregations as AGG

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmarks", "chip", "workloads",
                       "counters.repeat.json")) as _f:
    LIMIT = next(p["rel_err_limit"] for p in json.load(_f)["panels"]
                 if p["name"] == "avg_avg_over_time")

BASE = 1_600_000_000_000
S, T, INTERVAL = 768, 120, 10_000
STEPS, STEP_MS, WINDOW_MS = 12, 60_000, 300_000
START_MS = BASE + 400_000
LATE = 50  # in the "late" set every 16th series has no sample before this one


def _history(kind: str):
    vals = counter_values(np.random.default_rng(28), S, T)
    ts = BASE + np.arange(T, dtype=np.int64) * INTERVAL
    live = np.ones((S, T), bool)
    if kind == "late":
        live[::16, :LATE] = False
    return ts, vals, live


@pytest.fixture(scope="module")
def engine():
    ms = TimeSeriesMemStore()
    ms.setup(Dataset("ds"), list(range(8)))
    for kind in ("regular", "late"):
        ts, vals, live = _history(kind)
        tags = [{METRIC_TAG: f"{kind}_requests_total", "_ws_": "demo",
                 "_ns_": "App-2", "instance": f"host-{i}", "zone": f"z{i % 8}"}
                for i in range(S)]
        rows = np.nonzero(live)
        ms.ingest_routed("ds", RecordBatch(
            PROM_COUNTER, ts[rows[1]], {"count": vals[live]},
            [tags[i] for i in rows[0]]), spread=3)
    return QueryEngine(ms, "ds")


def _reference(kind: str, fn: str, op: str, by_zone: bool):
    """{zone or None: [STEPS] f64}, NaN = absent: numpy f64, window by window."""
    ts, vals, live = _history(kind)
    out_t = START_MS + np.arange(STEPS) * STEP_MS
    sj = np.full((S, STEPS), np.nan)
    for j, t in enumerate(out_t):
        w = (ts > t - WINDOW_MS) & (ts <= t)
        for s in range(S):
            v = vals[s, w & live[s]]
            if len(v):
                sj[s, j] = {"avg_over_time": v.mean(), "sum_over_time": v.sum(),
                            "last_over_time": v[-1]}[fn]
    out = {}
    for z in (range(8) if by_zone else [None]):
        rows = sj[z::8] if by_zone else sj
        has = ~np.isnan(rows)
        total = np.where(has, rows, 0.0).sum(0)
        if op == "avg":
            with np.errstate(invalid="ignore", divide="ignore"):
                total = total / has.sum(0)
        out[None if z is None else f"z{z}"] = np.where(has.any(0), total, np.nan)
    return out


def _ask(engine, kind, fn, op, by_zone):
    q = (f"{op} by (zone) " if by_zone else f"{op}") + \
        f"({fn}({kind}_requests_total[5m]))"
    res = engine.query_range(q, START_MS / 1000,
                             (START_MS + (STEPS - 1) * STEP_MS) / 1000, 60)
    return {lbl.get("zone"): np.asarray(row[:STEPS], np.float64)
            for g in res.grids for lbl, row in zip(g.labels, g.values_np())}


def _worst(got: dict, want: dict) -> float:
    assert got.keys() == want.keys()
    worst = 0.0
    for k, w in want.items():
        assert (np.isnan(got[k]) == np.isnan(w)).all(), k
        m = ~np.isnan(w)
        worst = max(worst, float(np.max(np.abs(got[k][m] - w[m]) / np.abs(w[m]))))
    return worst


@pytest.mark.parametrize("by_zone", [False, True], ids=["one_group", "eight_groups"])
@pytest.mark.parametrize("op", ["sum", "avg"])
@pytest.mark.parametrize("fn", ["avg_over_time", "last_over_time", "sum_over_time"])
@pytest.mark.parametrize("kind", ["regular", "late"])
def test_sum_over_series_of_a_value_function_holds_the_cells_limit(
        engine, kind, fn, op, by_zone):
    wide = REGISTRY.counter("filodb_group_reduce", form="wide")
    n0 = wide.value
    got = _ask(engine, kind, fn, op, by_zone)
    assert wide.value == n0 + 1  # one dispatch, and it took the wide form
    want = _reference(kind, fn, op, by_zone)
    if kind == "late":  # a series absent at some steps, present at others
        assert np.isnan(_reference(kind, fn, "sum", False)[None]).sum() == 0
    assert _worst(got, want) <= LIMIT


def test_the_plain_form_does_not_hold_it_at_this_size(engine, monkeypatch):
    """What the parent commit computed: the f32 segment sum, row after row."""
    monkeypatch.setattr(AGG, "reduce_form", lambda func, epilogue, groups: "plain")
    got = _ask(engine, "regular", "avg_over_time", "avg", False)
    assert _worst(got, _reference("regular", "avg_over_time", "avg", False)) > LIMIT


@pytest.mark.parametrize("q,form", [
    ("sum(rate(regular_requests_total[5m]))", "plain"),
    ("sum(irate(regular_requests_total[5m]))", "plain"),
    ("max(avg_over_time(regular_requests_total[5m]))", "plain"),
    ("count(last_over_time(regular_requests_total[5m]))", "plain"),
    ("quantile(0.5, avg_over_time(regular_requests_total[5m]))", "plain"),
    ("sum(regular_requests_total)", "wide"),
    ("avg by (zone) (max_over_time(regular_requests_total[5m]))", "wide"),
])
def test_each_dispatch_counts_the_form_it_took(engine, q, form):
    forms = {f: REGISTRY.counter("filodb_group_reduce", form=f)
             for f in ("wide", "plain")}
    before = {f: c.value for f, c in forms.items()}
    engine.query_range(q, START_MS / 1000, (START_MS + 5 * STEP_MS) / 1000, 60)
    after = {f: c.value for f, c in forms.items()}
    other = "plain" if form == "wide" else "wide"
    assert after[form] == before[form] + 1 and after[other] == before[other]


# -- the reduction alone -------------------------------------------------------


def _f64_sums(v, gids, G):
    v64 = np.where(np.isnan(v), 0.0, v.astype(np.float64))
    return np.stack([v64[gids == g].sum(0) for g in range(G)])


@pytest.mark.parametrize("G", [1, 8, 128, 129, 400])
def test_one_hot_and_segment_paths_sum_the_same(G, monkeypatch):
    rng = np.random.default_rng(G)
    v = (1e9 + rng.uniform(0, 4000, (2048, 1)) + rng.uniform(0, 100, (2048, 24))
         ).astype(np.float32)
    v[rng.random(v.shape) < 0.05] = np.nan
    gids = (np.arange(2048) % G).astype(np.int32)
    want = _f64_sums(v, gids, G)
    got = np.asarray(AGG._segment_aggregate_jit("sum", v, gids, G + 1, wide=True))[:G]
    assert np.max(np.abs(got - want) / want) < 1.2e-7  # one f32 rounding
    monkeypatch.setattr(AGG, "WIDE_ONEHOT_MAX_GROUPS", 0 if G <= 128 else 1024)
    other = AGG._segment_aggregate_jit.__wrapped__("sum", v, gids, G + 1, wide=True)
    assert np.asarray(other)[:G].tobytes() == got.tobytes()


def test_small_groups_beside_large_ones_infinities_and_absence():
    inf, nan = np.inf, np.nan
    v = np.array([[1.0, inf, inf, 1e-3, nan, 3e-39],
                  [1e9, 1.0, -inf, 3e-3, nan, 1.5],
                  [0.25, 2.0, 1.0, nan, nan, 2e-39],
                  [3e9, 4.0, 5.0, nan, nan, 0.5]], np.float32)
    gids = np.array([0, 1, 0, 1], np.int32)
    for op in ("sum", "avg"):
        wide = np.asarray(AGG._segment_aggregate_jit(op, v, gids, 3, wide=True))
        plain = np.asarray(AGG._segment_aggregate_jit(op, v, gids, 3))
        np.testing.assert_array_equal(wide, plain)
    assert wide[0, 0] == 0.625 and np.isnan(wide[:, 4]).all() and np.isnan(wide[2]).all()
    both = np.array([[inf], [-inf]], np.float32)
    assert np.isnan(np.asarray(AGG._segment_aggregate_jit(
        "sum", both, np.zeros(2, np.int32), 2, wide=True))[0, 0])


def test_the_sharded_wide_sum_is_the_single_device_one():
    from jax.sharding import Mesh, PartitionSpec as P

    devs = jax.devices()[:8]
    if len(devs) < 2:
        pytest.skip("one device")
    mesh = Mesh(np.array(devs), ("shard",))
    rng = np.random.default_rng(9)
    rows = 64 * len(devs)
    v = (1e9 + rng.uniform(0, 5000, (rows, 16))).astype(np.float32)
    v[rng.random(v.shape) < 0.1] = np.nan
    gids = (np.arange(rows) % 5).astype(np.int32)
    for op in ("sum", "avg"):
        sharded = jax.jit(jax.shard_map(
            lambda g, i: AGG._segment_psum_axis(op, g, i, 5, "shard", wide=True),
            mesh=mesh, in_specs=(P("shard"), P("shard")), out_specs=P(),
            check_vma=False))(v, gids)
        one = AGG._segment_aggregate_jit(op, v, gids, 5, wide=True)
        assert np.asarray(sharded).tobytes() == np.asarray(one).tobytes()


def test_reduce_form_is_decided_by_function_op_groups_and_device(monkeypatch):
    few, many = 8, AGG.WIDE_ONEHOT_MAX_GROUPS
    for mxu in (False, True):
        monkeypatch.setattr(AGG, "_has_mxu", lambda mxu=mxu: mxu)
        for fn in AGG.WIDE_SUM_FUNCS:  # the digits need it: whatever the groups
            for groups in (few, many):
                assert AGG.reduce_form(fn, ("agg", "sum"), groups) == "wide"
                assert AGG.reduce_form(fn, ("agg", "avg"), groups) == "wide"
                for op in ("min", "max", "count"):
                    assert AGG.reduce_form(fn, ("agg", op), groups) == "plain"
            assert AGG.reduce_form(fn, ("topk", 3, False), 1) == "plain"
            assert AGG.reduce_form(fn, ("quantile",), few) == "plain"
        for fn in ("rate", "irate", "increase", "delta", "stddev_over_time",
                   "count_over_time", "changes"):
            # nothing large to accumulate: wide only where it is the faster reduce
            assert AGG.reduce_form(fn, ("agg", "sum"), few) == ("wide" if mxu else "plain")
            assert AGG.reduce_form(fn, ("agg", "sum"), many) == "plain"
            assert AGG.reduce_form(fn, ("agg", "max"), few) == "plain"
    assert AGG.reduce_form("rate", ("agg", "sum"), many - 1) == "wide"
    assert AGG._with_reduce_form("last", ("agg", "sum"), 1) == ("agg", "sum", "wide")
    assert AGG._with_reduce_form("rate", ("agg", "sum"), many) == ("agg", "sum")
    assert jnp.int8(64) == 2 ** (AGG.WIDE_PIECE_BITS - 1)


@pytest.mark.parametrize("q", [
    "sum(rate(regular_requests_total[5m]))",
    "sum by (zone) (rate(late_requests_total[5m]))",
    "avg(irate(regular_requests_total[5m]))",
])
def test_on_an_mxu_the_few_group_sums_of_rates_take_the_wide_form_too(
        engine, monkeypatch, q):
    """What a TPU dispatches (the choice is by backend; the arithmetic runs
    anywhere): the same answer as the plain form's, to f32 rounding."""
    args = (q, START_MS / 1000, (START_MS + (STEPS - 1) * STEP_MS) / 1000, 60)
    rows = lambda res: {tuple(sorted(l.items())): np.asarray(v[:STEPS], np.float64)
                        for g in res.grids for l, v in zip(g.labels, g.values_np())}
    plain = rows(engine.query_range(*args))
    wide = REGISTRY.counter("filodb_group_reduce", form="wide")
    n0 = wide.value
    monkeypatch.setattr(AGG, "_has_mxu", lambda: True)
    got = rows(engine.query_range(*args))
    assert wide.value == n0 + 1 and got.keys() == plain.keys()
    for k, w in plain.items():
        assert (np.isnan(got[k]) == np.isnan(w)).all()
        np.testing.assert_allclose(got[k], w, rtol=2e-5)
