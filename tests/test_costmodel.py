"""Cost-model scheduling (query/costmodel.py; doc/perf.md "Cost-model
scheduling").

The scheduling plane prices work in device-seconds: the predictor joins
querylog fingerprints to realized kernel time (EWMA per fingerprint +
family, flat prior for the truly cold), admission drains per-tenant
buckets by the prediction (Retry-After = the bucket's actual drain time —
shed, wait the advertised seconds, admit, by construction), and the
dispatch scheduler widens its batch window under predicted queue cost,
collapses it when idle, and pre-warms recurrence-ring executables off the
serving path.

Rides the scheduler marker (make test-scheduler). All bucket/window tests
use an injected clock — deterministic by construction. The min/max fused
minmax tests assert BIT-equality (min/max are exact reduces: no
accumulation-order ulps) and a zero grid_jitter/grid_holes fallback delta.
"""

from __future__ import annotations

import threading
import time

import jax
import numpy as np
import pytest

from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine
from filodb_tpu.core.records import SeriesBatch
from filodb_tpu.core.schemas import (
    Dataset,
    METRIC_TAG,
    PROM_COUNTER,
    shard_for,
)
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.obs.kernels import KERNELS
from filodb_tpu.query.costmodel import CostModel, family_of
from filodb_tpu.query.scheduler import (
    AdmissionController,
    AdmissionRejected,
    DispatchScheduler,
)
from filodb_tpu.testkit import counter_batch, kernel_dispatch_total

pytestmark = pytest.mark.scheduler

BASE = 1_600_000_000_000
INTERVAL = 10_000
N_SHARDS = 8
N_SAMPLES = 240
START = (BASE + 600_000) / 1000
END = START + 900
STEP = 60


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


def _ingest_gauges(ms, metric, n_series, jitter=0.05, hole_frac=0.0,
                   seed=5):
    """Near-regular (jitter) or holey (masked) gauge fixtures — the grid
    classes whose min/max used to degrade to the general kernel."""
    rng = np.random.default_rng(seed)
    # half-interval phase shift keeps the jittered fixture out of the
    # "holes" classification (see tests/test_fused_jitter.py)
    nominal = (BASE + INTERVAL // 2
               + (1 + np.arange(N_SAMPLES, dtype=np.int64)) * INTERVAL)
    for i in range(n_series):
        tags = {METRIC_TAG: metric, "_ws_": "w", "_ns_": "n",
                "instance": f"h{i}", "job": f"j{i % 4}"}
        shard = shard_for(tags, spread=3, num_shards=N_SHARDS)
        dev = np.rint(
            rng.uniform(-jitter, jitter, N_SAMPLES) * INTERVAL
        ).astype(np.int64)
        ts = nominal + dev
        vals = 50 + 20 * rng.standard_normal(N_SAMPLES)
        keep = np.ones(N_SAMPLES, bool)
        if hole_frac > 0:
            drop = rng.choice(np.arange(1, N_SAMPLES - 1),
                              max(1, int(hole_frac * N_SAMPLES)),
                              replace=False)
            keep[drop] = False
        ms.shard("ds", shard).ingest_series(
            SeriesBatch(PROM_COUNTER, tags, ts[keep], {"count": vals[keep]})
        )


@pytest.fixture(scope="module")
def store():
    ms = TimeSeriesMemStore()
    ms.setup(Dataset("ds"), list(range(N_SHARDS)))
    ms.ingest_routed(
        "ds", counter_batch(n_series=48, n_samples=N_SAMPLES, start_ms=BASE),
        spread=3,
    )
    _ingest_gauges(ms, "gauge_jit", 24, jitter=0.05, seed=5)
    _ingest_gauges(ms, "gauge_holes", 24, jitter=0.05, hole_frac=0.01,
                   seed=9)
    return ms


def _rows(res):
    out = {}
    for g in res.grids:
        for lbls, vals in zip(g.labels, g.values_np()):
            out[tuple(sorted(lbls.items()))] = np.asarray(vals)
    return out


def _fallback_count(reason: str) -> int:
    from filodb_tpu.metrics import REGISTRY

    for line in REGISTRY.expose().splitlines():
        if line.startswith(
            f'filodb_fused_fallback_total{{reason="{reason}"}}'
        ):
            return int(float(line.rsplit(" ", 1)[1]))
    return 0


def _record(fp, promql, predicted, realized, steps=16, series=48,
            status="ok"):
    """A synthetic completed querylog record in the shape
    QueryLog.publish emits (the predictor's only input)."""
    return {
        "fingerprint": fp, "promql": promql, "status": status,
        "predicted_cost_s": predicted, "realized_cost_s": realized,
        "grid": {"steps": steps}, "stats": {"series_scanned": series},
    }


# ---------------------------------------------------------------------------
# the predictor
# ---------------------------------------------------------------------------


class TestFamilyOf:
    def test_range_functions_and_instant(self):
        assert family_of("sum by (job) (rate(http[5m]))") == "rate"
        assert family_of("min(min_over_time(g[3m]))") == "min_over_time"
        assert family_of("quantile_over_time(0.9, g[30m])") == (
            "quantile_over_time")
        assert family_of("sum(up)") == "instant"
        assert family_of("") == "instant"


class TestPredictor:
    def test_cold_prior_then_convergence(self):
        """The acceptance loop: cold -> flat prior; after N observations
        of realized cost the fingerprint EWMA prices within 2x."""
        cm = CostModel(prior_cost_s=0.05)
        fp, q = "f" * 16, "sum(rate(http_requests_total[5m]))"
        cost, src = cm.predict(fp, steps=16, family=family_of(q))
        assert (cost, src) == (0.05, "prior")
        realized = 0.4  # 8x the prior: convergence must actually move
        for _ in range(8):
            pred, _src = cm.predict(fp, steps=16, family=family_of(q))
            cm.observe(_record(fp, q, pred, realized))
        pred, src = cm.predict(fp, steps=16, family=family_of(q))
        assert src == "fingerprint"
        assert max(pred / realized, realized / pred) < 2.0
        assert cm.error_ratio(fp) is not None
        assert cm.error_ratio(fp) < 2.0

    def test_cold_fingerprint_priced_by_family_prior(self):
        """A never-seen fingerprint with family evidence is priced at the
        family unit cost x its own grid work x the conservative cold
        multiplier — and scales with the work, so a 10x-larger grid of
        the same family predicts 10x the cost."""
        cm = CostModel(prior_cost_s=0.05, cold_multiplier=2.0)
        q = "sum(rate(http_requests_total[5m]))"
        for i in range(4):
            cm.observe(_record(f"warm{i}", q, None, 0.2, steps=16,
                               series=48))
        small, src = cm.predict("cold-a", steps=16, series=48,
                                family="rate")
        assert src == "family"
        big, _ = cm.predict("cold-b", steps=160, series=48, family="rate")
        assert big == pytest.approx(10 * small, rel=1e-6)
        # cold multiplier: over-pricing an unknown is the cheap mistake
        assert small == pytest.approx(2.0 * 0.2, rel=1e-6)
        # no family evidence either -> the flat prior
        cost, src = cm.predict("cold-c", family="quantile_over_time")
        assert (cost, src) == (0.05, "prior")

    def test_observe_skips_shed_and_unrealized(self):
        cm = CostModel()
        cm.observe(_record("s" * 16, "sum(rate(m[5m]))", 0.05, 0.2,
                           status="shed"))
        cm.observe(_record("u" * 16, "sum(rate(m[5m]))", 0.05, None))
        snap = cm.snapshot()
        assert snap["observed"] == 0
        assert snap["fingerprints"] == []

    def test_snapshot_surfaces_predictions_and_errors(self):
        """GET /debug/costmodel payload: per-fingerprint prediction vs
        realized, family priors, evidence-tier counts."""
        cm = CostModel()
        fp, q = "a" * 16, "max(max_over_time(g[5m]))"
        pred, _ = cm.predict(fp, family=family_of(q))
        cm.observe(_record(fp, q, pred, 0.1))
        snap = cm.snapshot()
        assert snap["observed"] == 1
        assert snap["prediction_sources"]["prior"] == 1
        (e,) = snap["fingerprints"]
        assert e["fingerprint"] == fp
        assert e["last_realized_s"] == pytest.approx(0.1)
        assert e["last_error_ratio"] == pytest.approx(2.0)
        assert snap["families"]["max_over_time"]["n"] == 1


# ---------------------------------------------------------------------------
# device-second admission
# ---------------------------------------------------------------------------


class TestDeviceSecondAdmission:
    def test_legacy_query_quota_converts_unchanged(self):
        """A legacy ``{"rate": 1, "burst": 2}`` (queries) quota converted
        to device-seconds via the prior admits exactly the same pattern:
        2-query burst, then one query/second — unit conversion alone
        changes no admission decision."""
        clk = FakeClock()
        ctl = AdmissionController({"demo/app": {"rate": 1.0, "burst": 2}},
                                  clock=clk, prior_cost_s=0.05)
        with ctl.admit("demo", "app"):
            pass
        with ctl.admit("demo", "app"):
            pass
        with pytest.raises(AdmissionRejected) as ei:
            ctl.admit("demo", "app")
        assert ei.value.outcome == "shed_rate"
        # one prior-priced query refills in exactly 1/rate seconds
        assert ei.value.retry_after_s == pytest.approx(1.0)
        snap = ctl.snapshot()
        assert snap["unit"] == "device_seconds"
        assert snap["prior_cost_s"] == pytest.approx(0.05)

    def test_legacy_quota_floors_cheap_queries_at_one(self):
        """A legacy query-count quota charges at least one prior-priced
        query even when the model prices the query far cheaper — "2
        queries/s" configured by the operator keeps meaning 2, not
        thousands of model-priced cheap ones."""
        clk = FakeClock()
        ctl = AdmissionController({"demo/app": {"rate": 1.0, "burst": 2}},
                                  clock=clk, prior_cost_s=0.05)
        with ctl.admit("demo", "app", cost_s=1e-4):
            pass
        with ctl.admit("demo", "app", cost_s=1e-4):
            pass
        with pytest.raises(AdmissionRejected) as ei:
            ctl.admit("demo", "app", cost_s=1e-4)
        assert ei.value.outcome == "shed_rate"

    def test_cheap_tenant_flows_while_monster_sheds(self):
        """The tentpole fairness contract: 100 cheap queries fit the
        cheap tenant's device-second budget while one monster query
        drains (and then sheds) its own tenant's bucket — expensive
        queries drain proportionally, they don't count as '1'."""
        clk = FakeClock()
        ctl = AdmissionController(
            {"demo/cheap": {"rate_device_s": 0.5, "burst_device_s": 1.0},
             "demo/monster": {"rate_device_s": 0.5, "burst_device_s": 1.0}},
            clock=clk,
        )
        for _ in range(100):
            with ctl.admit("demo", "cheap", cost_s=0.002):
                pass
            clk.t += 0.01  # 0.2 dev-s/s arrival rate < 0.5 refill
        # the monster's first admit is the full-bucket clamp (a query
        # pricier than the burst admits after a full drain, not never)...
        with ctl.admit("demo", "monster", cost_s=30.0):
            pass
        # ...and leaves the bucket empty: the next one sheds
        with pytest.raises(AdmissionRejected) as ei:
            ctl.admit("demo", "monster", cost_s=30.0)
        assert ei.value.outcome == "shed_rate"
        assert ei.value.predicted_cost_s == pytest.approx(30.0)
        # the cheap tenant's own bucket is untouched by the monster
        with ctl.admit("demo", "cheap", cost_s=0.002):
            pass

    def test_expensive_queries_drain_proportionally(self):
        clk = FakeClock()
        ctl = AdmissionController(
            {"*": {"rate_device_s": 1.0, "burst_device_s": 1.0}},
            clock=clk,
        )
        for _ in range(4):  # 4 x 0.25 dev-s empties the 1.0 dev-s burst
            with ctl.admit("t", "a", cost_s=0.25):
                pass
        with pytest.raises(AdmissionRejected) as ei:
            ctl.admit("t", "a", cost_s=0.1)
        # Retry-After is THIS query's drain time (0.1 dev-s at 1/s), not
        # a flat per-query constant
        assert ei.value.retry_after_s == pytest.approx(0.1)

    def test_shed_plus_advertised_wait_admits(self):
        """Regression (the 429 contract): a shed tenant that waits
        exactly the advertised Retry-After is admitted — the hint is the
        bucket's computed drain time, not a guess."""
        clk = FakeClock()
        ctl = AdmissionController(
            {"*": {"rate_device_s": 0.25, "burst_device_s": 0.5}},
            clock=clk,
        )
        with ctl.admit("t", "a", cost_s=0.5):
            pass
        for cost in (0.5, 0.125, 0.04):
            with pytest.raises(AdmissionRejected) as ei:
                ctl.admit("t", "a", cost_s=cost)
            assert ei.value.outcome == "shed_rate"
            assert 0 < ei.value.retry_after_s <= 60
            clk.t += ei.value.retry_after_s
            with ctl.admit("t", "a", cost_s=cost):
                pass  # waiting the advertised seconds admits
            # leave the bucket empty again for the next round
            drain = ctl._states["t/a"].bucket
            drain._tokens = 0.0


# ---------------------------------------------------------------------------
# adaptive batch window
# ---------------------------------------------------------------------------


class TestAdaptiveWindow:
    def test_widens_under_load_and_collapses_idle(self):
        clk = FakeClock()
        s = DispatchScheduler(window_ms=2, window_cap_ms=50,
                              load_ref_cost_s=0.25, clock=clk)
        assert s.enabled and s.adaptive
        assert s.window_s == 0.0  # idle pipe: a lone query never waits
        s._note_load(0.05)  # a fifth of the reference cost
        assert s.window_s == pytest.approx(0.05 * 0.05 / 0.25)
        s._note_load(1.0)  # well past the reference: clamp at the cap
        assert s.window_s == pytest.approx(0.050)
        clk.t += 30.0  # ~15 decay constants with no arrivals
        assert s.window_s < 0.001

    def test_without_cap_window_is_constant(self):
        clk = FakeClock()
        s = DispatchScheduler(window_ms=5, clock=clk)
        assert s.enabled and not s.adaptive
        s._note_load(100.0)
        assert s.window_s == pytest.approx(0.005)
        assert DispatchScheduler(window_ms=0, clock=clk).enabled is False

    def test_load_decays_between_arrivals(self):
        clk = FakeClock()
        s = DispatchScheduler(window_ms=2, window_cap_ms=40,
                              load_ref_cost_s=1.0, clock=clk)
        s._note_load(1.0)
        w_full = s.window_s
        clk.t += s._load_tau_s  # one decay constant
        assert s.window_s == pytest.approx(w_full * np.exp(-1.0), rel=1e-6)


# ---------------------------------------------------------------------------
# executable pre-warm
# ---------------------------------------------------------------------------


class TestPrewarm:
    DESC = {"promql": "sum(rate(m[5m]))", "step_ms": 60_000,
            "span_ms": 900_000, "end_lag_ms": 0}

    def test_ring_keys_warm_once_past_the_bar(self):
        s = DispatchScheduler(window_ms=0, prewarm_min_count=3)
        warmed = []
        s.register_prewarmer(lambda desc: warmed.append(desc["promql"]))
        s.key_ring.observe("k1", self.DESC)
        assert s.prewarm_tick(storms={}) == []  # 1 observation < bar
        s.key_ring.observe("k1", self.DESC)
        s.key_ring.observe("k1", self.DESC)
        assert s.prewarm_tick(storms={}) == ["k1"]
        assert warmed == ["sum(rate(m[5m]))"]
        # once-only: a warmed key never re-runs
        assert s.prewarm_tick(storms={}) == []
        assert s.stats["prewarmed"] == 1

    def test_recompile_storm_lowers_the_bar(self):
        s = DispatchScheduler(window_ms=0, prewarm_min_count=3)
        s.register_prewarmer(lambda desc: None)
        s.key_ring.observe("k2", self.DESC)
        assert s.prewarm_tick(storms={}) == []
        # a live storm annotation: every cold executable is about to be
        # hot — one observation suffices
        assert s.prewarm_tick(storms={"fused_agg": {"n": 6}}) == ["k2"]

    def test_prewarm_errors_are_advisory(self):
        def boom(desc):
            raise RuntimeError("trace failed")

        s = DispatchScheduler(window_ms=0, prewarm_min_count=1)
        s.register_prewarmer(boom)
        s.key_ring.observe("k3", self.DESC)
        assert s.prewarm_tick(storms={}) == []  # error -> not "warmed"
        assert s.stats["prewarmed"] == 0
        # the failing key is memoed anyway: no retry storm
        assert s.prewarm_tick(storms={}) == []

    def test_prewarmed_key_first_real_dispatch_compiles_nothing(self, store):
        """The acceptance contract: seed the recurrence ring with a
        not-yet-compiled query shape, run one prewarm tick, then issue
        the query for real — the serving dispatch must record ZERO new
        compiles (the tick paid trace+compile off the serving path)."""
        sched = DispatchScheduler(window_ms=5, prewarm_min_count=3)
        engine = QueryEngine(store, "ds", PlannerParams(
            batch_window_ms=5, dispatch_scheduler=sched))
        # "not yet compiled" is made true, not hoped for: step counts pad to
        # 64, so whether this shape was new depended on which files ran in
        # the process before (after tests/test_engine.py it was not)
        jax.clear_caches()
        end_s = START + 840
        q = "sum by (job) (rate(http_requests_total[6m]))"
        desc = {"promql": q, "step_ms": 60_000, "span_ms": 840_000,
                "end_lag_ms": (time.time() - end_s) * 1000}
        key = ("prewarm-proof", q)
        for _ in range(3):
            sched.key_ring.observe(key, desc)
        before = KERNELS.totals()["compiles"]
        assert sched.prewarm_tick(storms={}) == [key]
        warmed = KERNELS.totals()["compiles"]
        assert warmed > before, "the tick itself must trace+compile"
        engine.query_range(q, START, end_s, STEP)
        assert KERNELS.totals()["compiles"] == warmed, (
            "first real dispatch after prewarm must record zero compiles"
        )


# ---------------------------------------------------------------------------
# min/max_over_time on jittered/holey grids: fused, bit-equal, no fallback
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def minmax_engines(store):
    fused = QueryEngine(store, "ds")
    ref = QueryEngine(store, "ds", PlannerParams(fused_aggregate=False))
    return fused, ref


MINMAX_QUERIES = [
    "min(min_over_time({m}[5m]))",
    "max(max_over_time({m}[5m]))",
    "min by (job) (min_over_time({m}[3m]))",
    "max by (job) (max_over_time({m}[5m]))",
]


@pytest.mark.parametrize("metric", ["gauge_jit", "gauge_holes"])
@pytest.mark.parametrize("q_tpl", MINMAX_QUERIES)
def test_minmax_fused_bit_equal_no_fallback(minmax_engines, metric, q_tpl):
    """min/max_over_time on jittered and holey grids rides the fused
    minmax programs: BIT-equal to the reference tree (min/max are exact
    reduces under min/max epilogues — no accumulation-order ulps) with
    the grid_jitter/grid_holes degrade reasons NOT firing."""
    fused, ref = minmax_engines
    q = q_tpl.format(m=metric)
    before = (_fallback_count("grid_jitter"), _fallback_count("grid_holes"))
    a = _rows(fused.query_range(q, START, END, STEP))
    b = _rows(ref.query_range(q, START, END, STEP))
    assert (_fallback_count("grid_jitter"),
            _fallback_count("grid_holes")) == before, q
    assert a.keys() == b.keys(), q
    for k in a:
        assert np.array_equal(a[k], b[k], equal_nan=True), (q, k)


@pytest.mark.parametrize("metric", ["gauge_jit", "gauge_holes"])
def test_minmax_warm_single_dispatch_with_cost_model_active(store, metric):
    """The warm canonical query stays exactly ONE fused dispatch with the
    whole cost-model plane active (admission pricing + adaptive window +
    recurrence ring all in the loop)."""
    ctl = AdmissionController(
        {"*": {"rate_device_s": 100.0, "burst_device_s": 100.0}})
    sched = DispatchScheduler(window_ms=5, window_cap_ms=50)
    engine = QueryEngine(store, "ds", PlannerParams(
        admission=ctl, batch_window_ms=5, dispatch_scheduler=sched))
    q = f"min(min_over_time({metric}[5m]))"
    engine.query_range(q, START, END, STEP)  # stage + compile warm
    before = kernel_dispatch_total()
    engine.query_range(q, START, END, STEP)
    assert kernel_dispatch_total() - before == 1, (
        f"warm {q} must stay ONE fused dispatch with the cost model on"
    )


def test_engine_stamps_costs_on_querylog(store):
    """End-to-end: a served query's cost record carries the admission
    prediction AND the realized device time, and the global model folds
    the observation in (fingerprint goes warm)."""
    from filodb_tpu.obs.querylog import promql_fingerprint
    from filodb_tpu.query.costmodel import COST_MODEL

    engine = QueryEngine(store, "ds")
    q = "max by (job) (max_over_time(gauge_jit[4m]))"
    res = engine.query_range(q, START, END, STEP)
    rec = res.query_log
    assert rec is not None
    assert rec["predicted_cost_s"] is not None and rec["predicted_cost_s"] > 0
    assert rec["realized_cost_s"] is not None and rec["realized_cost_s"] > 0
    fp = promql_fingerprint("ds", q, int(STEP * 1000),
                            int((END - START) * 1000))
    assert rec["fingerprint"] == fp
    # the observation landed: the model now prices this fingerprint from
    # its own evidence tier
    cost, src = COST_MODEL.predict(fp, family=family_of(q))
    assert src == "fingerprint"
    assert cost > 0


# ---------------------------------------------------------------------------
# HTTP surfaces
# ---------------------------------------------------------------------------


def test_costmodel_http_surfaces():
    """GET /debug/costmodel, the querylog cost fields on
    /api/v1/query_profile, and the error-ratio histogram on the
    self-scrape."""
    import json
    import urllib.parse
    import urllib.request

    from filodb_tpu.server import FiloServer

    srv = FiloServer({"dataset": "prometheus", "shards": 2})
    port = srv.start(port=0)
    host = f"http://127.0.0.1:{port}"
    try:
        srv.memstore.ingest_routed(
            "prometheus",
            counter_batch(n_series=12, n_samples=N_SAMPLES, start_ms=BASE),
            spread=1,
        )
        q = urllib.parse.quote("sum(rate(http_requests_total[5m]))")
        url = (f"{host}/api/v1/query_range?query={q}"
               f"&start={START}&end={END}&step={STEP}")
        for _ in range(2):
            with urllib.request.urlopen(url) as r:
                assert json.loads(r.read())["status"] == "success"
        with urllib.request.urlopen(f"{host}/debug/costmodel") as r:
            snap = json.loads(r.read())["data"]
        assert snap["observed"] >= 1
        assert snap["fingerprints"], "served queries must appear"
        assert any(e["last_realized_s"] for e in snap["fingerprints"])
        with urllib.request.urlopen(f"{host}/debug/querylog") as r:
            records = json.loads(r.read())["data"]
        rec = next(r for r in records
                   if r.get("predicted_cost_s") is not None)
        assert rec["realized_cost_s"] is not None
        with urllib.request.urlopen(
            f"{host}/api/v1/query_profile?id={rec['id']}"
        ) as r:
            prof = json.loads(r.read())["data"]
        assert prof["predicted_cost_s"] == rec["predicted_cost_s"]
        assert prof["realized_cost_s"] == rec["realized_cost_s"]
        with urllib.request.urlopen(f"{host}/metrics") as r:
            scrape = r.read().decode()
        assert "filodb_costmodel_error_ratio" in scrape
    finally:
        srv.stop()
