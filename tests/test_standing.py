"""Standing-query engine (doc/operations.md "Standing queries & recording
rules"): delta-maintained dashboards with push fan-out and recording rules.

The load-bearing property: a standing query's delta-maintained ``[G, J]``
partials are BIT-EQUAL to a full re-evaluation of the same grid over the
same (aligned) superblock — across regular, jittered and holey scrape
grids, across live-edge appends riding the in-place superblock extension
path, across forced restages, and under
concurrent ingest. Plus the serving contract: a warm refresh with provably
disjoint ingest performs ZERO kernel dispatches, a live-edge refresh
dispatches exactly ONCE for only the touched step suffix, one refresh
materialization serves N concurrent SSE subscribers, promotion/demotion is
hysteretic over the scheduler's retained recurrence ring, and recording
rules write real queryable series back.
"""

import json
import threading
import time

import numpy as np
import pytest

from filodb_tpu.coordinator.planner import QueryEngine
from filodb_tpu.core.records import SeriesBatch
from filodb_tpu.core.schemas import (
    METRIC_TAG, PROM_COUNTER, Dataset, shard_for,
)
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.standing import StandingEngine, SubscriptionHub, SubscriptionLimit
from filodb_tpu.testkit import counter_batch, kernel_dispatch_total

pytestmark = pytest.mark.standing

BASE = 1_600_000_000_000
INTERVAL = 10_000
N_SHARDS = 4
STEP_MS = 15_000
SPAN_MS = 1_200_000


def _series_data(metric, n_series, total, jitter=0.0, hole_frac=0.0, seed=7):
    """Full per-series (tags, ts, vals) counter arrays: callers ingest a
    prefix by time, then append later slices — values stay monotone so
    appends continue each series exactly like live scrapes."""
    rng = np.random.default_rng(seed)
    # half-interval phase shift, as in test_fused_jitter: keeps the grid
    # class deterministic against 5m-aligned staging boundaries
    nominal = (BASE + INTERVAL // 2
               + (1 + np.arange(total, dtype=np.int64)) * INTERVAL)
    out = []
    for i in range(n_series):
        tags = {METRIC_TAG: metric, "_ws_": "w", "_ns_": "n",
                "instance": f"h{i}", "job": f"j{i % 4}"}
        dev = (np.rint(rng.uniform(-jitter, jitter, total) * INTERVAL)
               .astype(np.int64) if jitter > 0 else 0)
        ts = nominal + dev
        vals = np.cumsum(rng.uniform(0, 10, total)) + 1e9
        keep = np.ones(total, bool)
        if hole_frac > 0:
            drop = rng.choice(np.arange(1, total - 1),
                              max(1, int(hole_frac * total)), replace=False)
            keep[drop] = False
        out.append((tags, ts[keep], vals[keep]))
    return out


def _ingest_window(ms, dataset, data, lo_ms, hi_ms):
    """Ingest every sample with lo_ms <= ts < hi_ms (one live batch)."""
    n = 0
    for tags, ts, vals in data:
        m = (ts >= lo_ms) & (ts < hi_ms)
        if not m.any():
            continue
        shard = shard_for(tags, spread=3, num_shards=N_SHARDS)
        n += ms.shard(dataset, shard).ingest_series(
            SeriesBatch(PROM_COUNTER, tags, ts[m], {"count": vals[m]})
        )
    return n


def _fresh(metric="rq", n_series=24, total=260, jitter=0.0, hole_frac=0.0,
           seed=7, prefix=200):
    """(memstore, engine, data, edge_ms): prefix samples ingested, the rest
    held back for live appends."""
    ms = TimeSeriesMemStore()
    ms.setup(Dataset("ds"), list(range(N_SHARDS)))
    data = _series_data(metric, n_series, total, jitter, hole_frac, seed)
    edge = BASE + prefix * INTERVAL
    _ingest_window(ms, "ds", data, 0, edge)
    return ms, QueryEngine(ms, "ds"), data, edge


def _standing(engine, edge_ms, **cfg):
    cfg = {"default_span_ms": SPAN_MS, **cfg}
    return StandingEngine(engine, cfg, clock=lambda: (edge_ms + 5_000) / 1e3)


# -- registration & modes ----------------------------------------------------


def test_register_modes_and_unregister():
    _ms, eng, _data, edge = _fresh()
    se = _standing(eng, edge)
    sq = se.register("sum by (job) (rate(rq[5m]))", STEP_MS)
    assert sq.mode == "delta" and sq.mode_reason is None
    top = se.register("topk(3, rate(rq[5m]))", STEP_MS)
    assert top.mode == "full"
    assert top.mode_reason == "standing_nondecomposable"
    qt = se.register("quantile(0.9, rate(rq[5m]))", STEP_MS)
    assert qt.mode == "full"
    assert se.registry.get(sq.qid) is sq
    assert len(se.registry.list()) == 3
    se.unregister(sq.qid)
    assert se.registry.get(sq.qid) is None
    with pytest.raises(Exception):
        se.register("not a promql ((", STEP_MS)


def test_registry_bounded():
    _ms, eng, _data, edge = _fresh()
    se = _standing(eng, edge, max_standing=2)
    se.register("sum(rate(rq[5m]))", STEP_MS)
    se.register("avg(rate(rq[5m]))", STEP_MS)
    with pytest.raises(ValueError):
        se.register("count(rate(rq[5m]))", STEP_MS)


# -- delta maintenance: bit-equality property --------------------------------


GRIDS = {
    "regular": dict(jitter=0.0, hole_frac=0.0),
    "jitter": dict(jitter=0.05, hole_frac=0.0),
    "holes": dict(jitter=0.05, hole_frac=0.01),
}

QUERIES = [
    "sum by (instance) (rate(rq[5m]))",
    "avg by (job) (increase(rq[5m]))",
    "count(sum_over_time(rq[2m]))",
]


@pytest.mark.parametrize("grid", list(GRIDS))
@pytest.mark.parametrize("q", QUERIES)
def test_delta_biteq_vs_full_reevaluation(grid, q):
    """THE acceptance property: across live-edge append rounds, the delta
    path's spliced partials are bit-equal to a forced full re-evaluation
    of the same grid (same aligned superblock), for every grid class."""
    ms, eng, data, edge = _fresh(seed=11, **GRIDS[grid])
    se = _standing(eng, edge)
    sq = se.register(q, STEP_MS)
    twin = se.register(q, STEP_MS)
    se.refresh(sq)
    for rnd in range(3):
        lo, hi = edge + rnd * 50_000, edge + (rnd + 1) * 50_000
        assert _ingest_window(ms, "ds", data, lo, hi) > 0
        se.clock = lambda e=hi: (e + 5_000) / 1e3
        se.refresh(sq)
        se.refresh(twin, force_full=True)
        assert sq.grid_start_ms == twin.grid_start_ms
        assert sq.labels == twin.labels
        assert sq.retained.tobytes() == twin.retained.tobytes(), (
            f"{grid} {q} round {rnd}: delta partials diverge from full "
            f"re-evaluation"
        )
    assert sq.stats["delta"] >= 1, "the delta path never ran"
    assert sq.stats["steps_retained"] > 0


def test_delta_refresh_is_suffix_only_single_dispatch():
    """A live-edge append refresh re-dispatches exactly ONCE, computing
    only the touched step suffix — no full re-dispatch (the acceptance
    criterion's 'runs the delta path')."""
    ms, eng, data, edge = _fresh()
    se = _standing(eng, edge)
    sq = se.register("sum by (instance) (rate(rq[5m]))", STEP_MS)
    se.refresh(sq)
    # priming round: if the aligned staging range happens to roll right
    # here (it rolls once per align_ms of wall time), pay the reset now
    _ingest_window(ms, "ds", data, edge, edge + 30_000)
    se.clock = lambda: (edge + 35_000) / 1e3
    se.refresh(sq)
    J = sq.num_steps()
    computed0 = sq.stats["steps_computed"]
    _ingest_window(ms, "ds", data, edge + 30_000, edge + 60_000)
    se.clock = lambda: (edge + 65_000) / 1e3
    before = kernel_dispatch_total()
    se.refresh(sq)
    assert kernel_dispatch_total() - before == 1, (
        "delta refresh must be exactly ONE kernel dispatch"
    )
    delta_steps = sq.stats["steps_computed"] - computed0
    assert 0 < delta_steps < J / 2, (
        f"delta refresh computed {delta_steps} of {J} steps — not a suffix"
    )
    assert sq.stats["delta"] >= 1


def test_disjoint_ingest_serves_retained_zero_dispatch():
    """Nothing new in range → the refresh serves retained partials with
    ZERO kernel dispatches, and — since the content is byte-identical —
    skips the render/publish too (no redundant fan-out per wake)."""
    _ms, eng, _data, edge = _fresh()
    se = _standing(eng, edge)
    sq = se.register("sum by (instance) (rate(rq[5m]))", STEP_MS)
    first = se.refresh(sq)
    assert first is not None
    before = kernel_dispatch_total()
    renders0 = sq.stats["renders"]
    payload = se.refresh(sq)
    assert payload is None  # unchanged content: nothing re-rendered/pushed
    assert sq.last_payload == first  # subscribers' snapshot frame intact
    assert kernel_dispatch_total() - before == 0
    assert sq.stats["retained"] == 1
    assert sq.stats["renders"] == renders0


def test_concurrent_extension_soak():
    """Refreshes racing live ingest: no errors, every refresh serves a
    well-formed grid, and the quiesced final state is bit-equal to a full
    re-evaluation."""
    ms, eng, data, edge = _fresh(total=300, prefix=200)
    se = _standing(eng, edge)
    q = "sum by (job) (rate(rq[5m]))"
    sq = se.register(q, STEP_MS)
    twin = se.register(q, STEP_MS)
    se.refresh(sq)
    stop = threading.Event()
    state = {"hi": edge}

    def ingester():
        hi = edge
        while not stop.is_set() and hi < edge + 90_000:
            _ingest_window(ms, "ds", data, hi, hi + 10_000)
            hi += 10_000
            state["hi"] = hi
            time.sleep(0.01)

    t = threading.Thread(target=ingester)
    t.start()
    try:
        for _ in range(12):
            se.clock = lambda e=state["hi"]: (e + 5_000) / 1e3
            se.refresh(sq)
            assert sq.last_error is None, sq.last_error
            assert sq.retained.shape[1] == sq.num_steps()
            time.sleep(0.005)
    finally:
        stop.set()
        t.join()
    se.clock = lambda e=state["hi"]: (e + 5_000) / 1e3
    se.refresh(sq)
    se.refresh(twin, force_full=True)
    assert sq.labels == twin.labels
    assert sq.retained.tobytes() == twin.retained.tobytes()
    assert sq.stats["errors"] == 0


def test_new_series_resets_cleanly():
    """A NEW series appearing (full-clear effect) resets the retained
    state instead of splicing a mismatched group axis."""
    ms, eng, data, edge = _fresh(n_series=12)
    se = _standing(eng, edge)
    sq = se.register("sum by (instance) (rate(rq[5m]))", STEP_MS)
    se.refresh(sq)
    g0 = len(sq.labels)
    extra = _series_data("rq", 16, 260, seed=99)[12:]  # 4 unseen series
    _ingest_window(ms, "ds", extra, 0, edge + 40_000)
    se.clock = lambda: (edge + 45_000) / 1e3
    se.refresh(sq)
    assert sq.stats["reset"] >= 2  # first refresh + the new-series reset
    assert len(sq.labels) > g0
    twin = se.register("sum by (instance) (rate(rq[5m]))", STEP_MS)
    se.refresh(twin, force_full=True)
    assert sq.retained.tobytes() == twin.retained.tobytes()


# -- nondecomposable demotion ------------------------------------------------


def _fallback_count(reason):
    from filodb_tpu.metrics import REGISTRY

    return REGISTRY.counter("filodb_fused_fallback", reason=reason).value


def test_nondecomposable_full_refresh_counted():
    """topk standing queries demote cleanly: refreshes run the full
    re-dispatch, counted in the fused-fallback taxonomy, and still serve
    correct pushed payloads."""
    _ms, eng, _data, edge = _fresh()
    se = _standing(eng, edge)
    sq = se.register("topk(3, rate(rq[5m]))", STEP_MS)
    before = _fallback_count("standing_nondecomposable")
    payload = se.refresh(sq)
    assert payload is not None
    assert _fallback_count("standing_nondecomposable") == before + 1
    body = json.loads(payload)
    assert body["resultType"] == "matrix"
    assert body["result"], "topk standing refresh returned no rows"
    assert sq.stats["full"] == 1 and sq.stats["delta"] == 0


# -- promotion / demotion over the scheduler's recurrence ring ---------------


def test_key_ring_retained_across_batch_close():
    """The satellite fix: per-key recurrence survives batch-group close —
    repeated queries accumulate in the scheduler's ring instead of
    vanishing with each closed window."""
    _ms, eng, _data, edge = _fresh()
    se = _standing(eng, edge)  # injects a scheduler with the ring
    q = "sum by (instance) (rate(rq[5m]))"
    for _ in range(4):
        eng.query_range(q, (edge - SPAN_MS) / 1e3, edge / 1e3, STEP_MS / 1e3)
    ring = se.scheduler.key_ring
    assert len(ring) >= 1
    entries = ring.entries()
    (key, e) = next((k, v) for k, v in entries
                    if (v.get("desc") or {}).get("promql") == q)
    assert e["count"] == 4
    assert e["desc"]["dataset"] == "ds"
    assert e["desc"]["step_ms"] == STEP_MS
    snap = se.scheduler.snapshot()
    assert snap["standing_keys"] >= 1


def test_observe_key_without_trace_root():
    """Direct exec.execute (no engine trace root → no promql) must still
    observe safely: the fallback key normalizes by/without to hashable
    tuples instead of crashing the dispatch path."""
    from filodb_tpu.query.promql import query_range_to_logical_plan

    _ms, eng, _data, edge = _fresh()
    se = _standing(eng, edge)
    plan = query_range_to_logical_plan(
        "sum by (job) (rate(rq[5m]))", (edge - SPAN_MS) / 1e3, edge / 1e3, 15
    )
    ex = eng.planner.materialize(plan)
    res = ex.execute(eng.context())
    assert res.grids
    assert len(se.scheduler.key_ring) >= 1
    # promql-less keys never promote (nothing to re-register from)
    assert se.promote_tick() == 0


def test_key_ring_bounded():
    from filodb_tpu.query.scheduler import KeyStatsRing

    ring = KeyStatsRing(max_entries=8)
    for i in range(50):
        ring.observe(("k", i))
    assert len(ring) == 8
    # LRU: the most recently observed keys survive
    kept = {k for k, _ in ring.entries()}
    assert ("k", 49) in kept and ("k", 0) not in kept


def test_promotion_hysteresis():
    """A bursting live-edge key promotes; demotion needs long idle AND no
    subscribers; nondecomposable keys are remembered, never flapped on."""
    ms = TimeSeriesMemStore()
    ms.setup(Dataset("ds"), list(range(N_SHARDS)))
    now_ms = int(time.time() * 1000)
    ms.ingest_routed(
        "ds", counter_batch(n_series=12, n_samples=120,
                            start_ms=now_ms - 1_200_000), spread=3,
    )
    eng = QueryEngine(ms, "ds")
    se = StandingEngine(eng, {
        "promote_min_count": 3, "promote_window_s": 300.0,
        "demote_idle_s": 600.0, "default_span_ms": 600_000,
    })
    q = "sum by (instance) (rate(http_requests_total[5m]))"

    def dashboard(promql):
        # three polls of one panel, the end a step later each time: an end
        # that stands still is a fixed range and never promotes
        # (tests/test_standing_promotion.py)
        for i in (2, 1, 0):
            end_ms = now_ms - i * 15_000
            eng.query_range(promql, (end_ms - 600_000) / 1e3, end_ms / 1e3, 15)

    dashboard(q)
    assert se.promote_tick() == 1
    sqs = se.registry.list()
    assert len(sqs) == 1 and sqs[0].source == "promoted"
    assert sqs[0].promql == q and sqs[0].mode == "delta"
    assert se.promote_tick() == 0  # already registered: no re-promotion
    # nondecomposable keys are declined and remembered
    qt = "topk(2, rate(http_requests_total[5m]))"
    dashboard(qt)
    assert se.promote_tick() == 0
    reasons = {d["reason"] for d in se.registry.snapshot()["demoted"]}
    assert "standing_nondecomposable" in reasons
    # demotion: not before the idle bound...
    assert se.demote_tick(time.time() + 60) == 0
    # ...not while a subscriber holds the query...
    sub = se.hub.subscribe(sqs[0].qid)
    assert se.demote_tick(time.time() + 10_000) == 0
    se.hub.unsubscribe(sub)
    # ...then idle + unsubscribed demotes, and the key is remembered
    assert se.demote_tick(time.time() + 10_000) == 1
    assert not se.registry.list()
    assert se.registry.demoted_reason(sqs[0].key) == "idle"
    # hysteresis: the demoted key does not immediately re-promote
    assert se.promote_tick() == 0


def test_historical_scan_never_promotes():
    _ms, eng, _data, edge = _fresh()  # data far in the past vs wall clock
    se = _standing(eng, edge, promote_min_count=2)
    q = "sum(rate(rq[5m]))"
    for _ in range(3):
        eng.query_range(q, (edge - SPAN_MS) / 1e3, edge / 1e3, 15)
    assert se.promote_tick() == 0  # end lags wall clock by years


# -- shard effect intervals (the classification feed) ------------------------


def test_ingest_effects_interval_since():
    from filodb_tpu.memstore.shard import TimeSeriesShard

    sh = TimeSeriesShard("ds", 0)
    data = _series_data("m", 2, 40)
    for tags, ts, vals in data:
        sh.ingest_series(SeriesBatch(PROM_COUNTER, tags, ts[:20],
                                     {"count": vals[:20]}))
    v0 = sh.version
    assert sh.ingest_effects_interval_since(v0, 0, 2**62) == (None, None, None)
    tags, ts, vals = data[0]
    sh.ingest_series(SeriesBatch(PROM_COUNTER, tags, ts[20:25],
                                 {"count": vals[20:25]}))
    reason, lo, hi = sh.ingest_effects_interval_since(v0, 0, 2**62)
    assert reason == "overlap"
    assert lo <= int(ts[20]) and hi == int(ts[24])
    # disjoint probe range: proves untouched
    assert sh.ingest_effects_interval_since(
        v0, 0, int(ts[19]) - 600_000
    ) == (None, None, None)
    # a NEW series is a full clear
    v1 = sh.version
    sh.ingest_series(SeriesBatch(
        PROM_COUNTER, {METRIC_TAG: "m", "instance": "new"},
        ts[:5] + 1, {"count": vals[:5]},
    ))
    assert sh.ingest_effects_interval_since(v1, 0, 2**62)[0] == "full_clear"


def test_append_listener_fires_outside_lock():
    from filodb_tpu.memstore.shard import TimeSeriesShard

    sh = TimeSeriesShard("ds", 0)
    seen = []

    def cb(dataset, shard, lo, hi, full):
        # re-entering shard APIs must not deadlock (fired outside the lock)
        sh.ingest_effects_since(0, 0, 1)
        seen.append((dataset, shard, lo, hi, full))

    sh.add_append_listener(cb)
    tags, ts, vals = _series_data("m", 1, 10)[0]
    sh.ingest_series(SeriesBatch(PROM_COUNTER, tags, ts, {"count": vals}))
    assert len(seen) == 1
    assert seen[0][0] == "ds" and seen[0][4] is True  # new series = full
    sh.remove_append_listener(cb)
    sh.ingest_series(SeriesBatch(PROM_COUNTER, tags, ts + 200_000,
                                 {"count": vals + 1}))
    assert len(seen) == 1


# -- subscription hub --------------------------------------------------------


def test_hub_limit_and_newest_wins():
    hub = SubscriptionHub(max_subscribers=2, queue_depth=2)
    a = hub.subscribe("q1")
    _b = hub.subscribe("q1")
    with pytest.raises(SubscriptionLimit):
        hub.subscribe("q1")
    for i in range(4):
        hub.publish("q1", b"payload-%d" % i)
    # bounded queue keeps the NEWEST frames
    got = [a.get(timeout=1), a.get(timeout=1)]
    assert got == [b"payload-2", b"payload-3"]
    hub.close("q1")
    assert hub.total() == 0


# -- push fan-out over live SSE ----------------------------------------------


def _sse_events(resp, n, timeout_s=15.0):
    """Read n SSE data events from an open response."""
    out = []
    deadline = time.time() + timeout_s
    buf = b""
    while len(out) < n and time.time() < deadline:
        line = resp.fp.readline()
        if not line:
            break
        line = line.rstrip(b"\r\n")
        if line.startswith(b"data: "):
            buf += line[6:]
        elif not line and buf:
            out.append(json.loads(buf))
            buf = b""
    return out


def test_sse_fanout_one_materialization():
    """N >= 8 concurrent SSE subscribers each receive the SAME refresh
    payload from ONE materialization (renders == refreshes, not
    refreshes x N); past max_subscribers the subscription sheds 429."""
    import http.client

    from filodb_tpu.api.http import serve_background

    ms, eng, data, edge = _fresh()
    se = _standing(eng, edge, max_subscribers=8)
    sq = se.register("sum by (job) (rate(rq[5m]))", STEP_MS)
    se.refresh(sq)
    srv, port = serve_background(eng, standing=se)
    conns = []
    try:
        for _ in range(8):
            c = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
            c.request("GET", f"/api/v1/standing/subscribe?id={sq.qid}")
            r = c.getresponse()
            assert r.status == 200
            assert r.getheader("Content-Type") == "text/event-stream"
            conns.append((c, r))
        # the 9th subscriber sheds with the overload contract
        c9 = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        c9.request("GET", f"/api/v1/standing/subscribe?id={sq.qid}")
        r9 = c9.getresponse()
        assert r9.status == 429
        assert r9.getheader("Retry-After")
        c9.close()
        # one refresh -> one render -> every subscriber gets the same frame
        renders0 = sq.stats["renders"]
        _ingest_window(ms, "ds", data, edge, edge + 20_000)
        se.clock = lambda: (edge + 25_000) / 1e3
        se.refresh(sq)
        assert sq.stats["renders"] == renders0 + 1
        frames = []
        for _c, r in conns:
            evs = _sse_events(r, 2)  # initial snapshot + the refresh
            assert len(evs) == 2
            frames.append(evs[1])
        assert all(f == frames[0] for f in frames)
        assert frames[0]["seq"] == sq.seq
        assert frames[0]["result"]
    finally:
        for c, _r in conns:
            c.close()
        srv.shutdown()


def test_standing_http_api_and_debug():
    import urllib.request

    from filodb_tpu.api.http import serve_background

    _ms, eng, _data, edge = _fresh()
    se = _standing(eng, edge)
    srv, port = serve_background(eng, standing=se)
    url = f"http://127.0.0.1:{port}"
    try:
        req = urllib.request.Request(
            f"{url}/api/v1/standing/register",
            data=json.dumps({"query": "sum(rate(rq[5m]))",
                             "step": "15s", "range": "20m"}).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            out = json.loads(r.read())
        assert out["status"] == "success"
        qid = out["data"]["id"]
        assert out["data"]["mode"] == "delta"
        with urllib.request.urlopen(f"{url}/api/v1/standing", timeout=30) as r:
            lst = json.loads(r.read())["data"]
        assert lst["count"] == 1
        with urllib.request.urlopen(f"{url}/debug/standing", timeout=30) as r:
            dbg = json.loads(r.read())["data"]
        assert dbg["count"] == 1 and "key_ring" in dbg
        req = urllib.request.Request(
            f"{url}/api/v1/standing/unregister",
            data=json.dumps({"id": qid}).encode(), method="POST",
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            assert json.loads(r.read())["status"] == "success"
        with urllib.request.urlopen(f"{url}/api/v1/standing", timeout=30) as r:
            assert json.loads(r.read())["data"]["count"] == 0
    finally:
        srv.shutdown()


# -- recording rules ---------------------------------------------------------


def test_recording_rule_writes_back_series():
    """A recording rule's refresh writes its newest closed steps back as a
    real series, queryable through the standard path, and the rule lists
    at /api/v1/rules."""
    ms, eng, data, edge = _fresh()
    se = _standing(eng, edge)
    sq = se.register(
        "sum by (job) (rate(rq[5m]))", STEP_MS, span_ms=4 * STEP_MS,
        source="rule", rule_name="job_rq_rate5m", eval_interval_s=15.0,
    )
    se.refresh(sq)
    end1 = sq.grid_end_ms
    # the written sample equals the rule's own newest partial
    res = eng.query_range("job_rq_rate5m", end1 / 1e3, end1 / 1e3, 15)
    rows = {tuple(sorted(g_lbl.items())): v
            for g in res.grids
            for g_lbl, v in zip(g.labels, g.values_np())}
    assert rows, "rule wrote no series"
    mine = {tuple(sorted({**dict(l), METRIC_TAG: "job_rq_rate5m"}.items())):
            sq.retained[i, -1] for i, l in enumerate(sq.labels)}
    for k, v in rows.items():
        assert k in mine
        assert np.float32(v[-1]) == np.float32(mine[k])
    # a later eval appends the NEW closed steps only (no rewrite storm)
    _ingest_window(ms, "ds", data, edge, edge + 30_000)
    se.clock = lambda: (edge + 35_000) / 1e3
    se.refresh(sq)
    assert sq.last_rule_write_ms == sq.grid_end_ms > end1
    payload = se.rules_payload()
    assert payload["groups"][0]["rules"][0]["name"] == "job_rq_rate5m"
    assert payload["groups"][0]["rules"][0]["type"] == "recording"


# -- lifecycle: append-wake loop ---------------------------------------------


def test_append_wake_refreshes_via_loop():
    """start() subscribes to shard appends: a live ingest wakes the loop
    and the registered query refreshes without anyone polling."""
    ms, eng, data, edge = _fresh()
    se = _standing(eng, edge, refresh_debounce_ms=0, tick_s=0.05)
    sq = se.register("sum(rate(rq[5m]))", STEP_MS)
    se.refresh(sq)
    seq0 = sq.seq
    se.start()
    try:
        _ingest_window(ms, "ds", data, edge, edge + 20_000)
        deadline = time.time() + 10
        while sq.seq == seq0 and time.time() < deadline:
            time.sleep(0.02)
        assert sq.seq > seq0, "append never woke the maintainer loop"
    finally:
        se.stop()


# -- resource attribution ----------------------------------------------------


def test_ledger_and_tenant_attribution():
    from filodb_tpu.ledger import LEDGER

    _ms, eng, _data, edge = _fresh()
    se = _standing(eng, edge)
    sq = se.register(
        'sum by (instance) (rate(rq{_ws_="w",_ns_="n"}[5m]))', STEP_MS
    )
    se.refresh(sq)
    assert sq.ws == "w" and sq.ns == "n"
    verify = LEDGER.verify()
    kind = verify["kinds"].get("standing_state")
    assert kind is not None
    assert kind["ledger"] == kind["actual"] > 0
    assert kind["drift"] == 0
    se.unregister(sq.qid)
    verify = LEDGER.verify()
    # this registry's account drained (other tests' registries may live)
    acct = [a for a in verify["accounts"]
            if a["kind"] == "standing_state" and a["actual"] == 0]
    assert acct
    assert all(a["drift"] == 0 if "drift" in a else True for a in acct)
    # a refresh racing the unregister bails instead of re-growing state
    # the ledger already credited back (the drift hazard)
    assert se.refresh(sq) is None
    assert sq.retained is None
    this = [a for a in verify["accounts"]
            if a["kind"] == "standing_state" and a["actual"] == 0]
    assert all(a["bytes"] == a["actual"] for a in this)
