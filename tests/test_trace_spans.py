"""The span primitive (doc/observability.md "Spans, parts and the device
trace"): ``metrics.span`` books host walls (``phase=``), names the parts of
``stage`` (``part=``), and holds a ``jax.profiler.TraceAnnotation`` so the
span tree lands on a profiler trace's clock.

Contracts pinned here:

- (a) a cold fused query books every part of ``stage`` that applies, the
  querylog record carries ``stage_parts_ms``, and sum(parts) <= stage <=
  sum(parts) + slack; an unknown part raises as an unknown phase does;
- (b) a coalesced follower books its wait, the leader books nothing;
- (c) the handler's clock covers the engine's, ``transfer`` and ``render``;
  ``transfer_ready`` <= ``transfer`` and ``render_write`` <= ``render``;
- (d) a ``jax.profiler`` trace taken around served queries holds the
  program's spans as host events with trace ids, nested as the span tree;
- (e) the two fused programs the cells and the north star run carry the
  three stage scopes, and a scope changes metadata only;
- (f) every counter a benchmark metric file names is a documented family,
  and the sample each per-layer metric of ISSUE 26 would read is on
  ``/metrics`` under the name and label the benchmark's own parser finds;
- (g) ISSUE 38, every second has an owner: phase ``group`` around the group
  ids and ``queue`` around the pool's two hops leave ``other`` a residual;
  a pre-warm, a routed ingest and a server's start have clocks of their
  own, and a pre-warm books nothing where the window's metrics read.
"""

from __future__ import annotations

import glob
import hashlib
import importlib.util
import json
import os
import re
import threading
import time
import urllib.parse
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from filodb_tpu import metrics, native
from filodb_tpu.api.http import serve_background
from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine
from filodb_tpu.coordinator.scheduler import SingleFlight
from filodb_tpu.core.schemas import Dataset
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.memstore.shard import StoreConfig
from filodb_tpu.metrics import REGISTRY, STAGE_PARTS, span
from filodb_tpu.obs.querylog import QUERY_LOG, PhaseRecorder
from filodb_tpu.ops import aggregations as AGG
from filodb_tpu.ops import hist_kernels as HK
from filodb_tpu.testkit import counter_batch, histogram_batch

pytestmark = pytest.mark.observability

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASE = 1_600_000_000_000
N_SAMPLES = 240
START_S = (BASE + 600_000) / 1000
END_S = (BASE + 1_800_000) / 1000
QUERIES = {
    "hist": ("histogram_quantile(0.99, sum by (le) "
             "(rate(http_request_latency[5m])))"),
    "counter": "sum by (job) (rate(http_requests_total[5m]))",
}


def _hist_sum(name: str, **labels) -> tuple[float, int]:
    """(sum, count) over every series of a histogram family that carries
    ``labels`` (whatever else it is labelled by)."""
    want = set(labels.items())
    total, count = 0.0, 0
    with REGISTRY._lock:
        for (n, ls), m in REGISTRY._metrics.items():
            if n == name and want <= set(ls):
                total += m.sum
                count += m.total
    return total, count


def _engine(**params):
    ms = TimeSeriesMemStore(StoreConfig())
    ms.setup(Dataset("ds"), list(range(4)))
    ms.ingest_routed("ds", counter_batch(n_series=24, n_samples=N_SAMPLES,
                                         start_ms=BASE), spread=2)
    ms.ingest_routed("ds", histogram_batch(n_series=24, n_samples=N_SAMPLES,
                                           start_ms=BASE), spread=2)
    return QueryEngine(ms, "ds", PlannerParams(**params))


def _get(port: int, query: str, **extra) -> dict:
    qs = urllib.parse.urlencode({"query": query, "start": START_S,
                                 "end": END_S, "step": 60, **extra})
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/api/v1/query_range?{qs}") as r:
        return json.loads(r.read())


# -- (a) the parts of stage ---------------------------------------------------


@pytest.fixture(scope="module")
def cold():
    """One cold query of each kind through the engine; per kind the growth
    of every part's histogram and the querylog record."""
    eng = _engine()
    out = {}
    for kind, q in QUERIES.items():
        before = {p: _hist_sum("filodb_stage_part_seconds", part=p)
                  for p in STAGE_PARTS}
        res = eng.query_range(q, START_S, END_S, 60)
        assert res.grids, kind
        after = {p: _hist_sum("filodb_stage_part_seconds", part=p)
                 for p in STAGE_PARTS}
        out[kind] = {
            "parts": {p: (after[p][0] - before[p][0],
                          after[p][1] - before[p][1]) for p in STAGE_PARTS},
            "record": QUERY_LOG.get(res.query_log["id"]),
        }
    return out


@pytest.mark.parametrize("part", STAGE_PARTS)
@pytest.mark.parametrize("kind", sorted(QUERIES))
def test_cold_query_books_every_part_of_stage(cold, kind, part):
    _seconds, count = cold[kind]["parts"][part]
    assert count == 1, (kind, part)  # observed once per execution
    assert part in cold[kind]["record"]["stage_parts_ms"]


@pytest.mark.parametrize("kind", sorted(QUERIES))
def test_parts_sum_to_at_most_stage(cold, kind):
    rec = cold[kind]["record"]
    assert rec["path"] == "fused"
    stage = rec["phases_ms"]["stage"]
    parts = sum(rec["stage_parts_ms"].values())
    assert set(rec["stage_parts_ms"]) <= set(STAGE_PARTS)
    # the slack is the bookkeeping between the parts (cache inserts under
    # the shard lock, version checks): at 24 series it is a large share of
    # a stage of a few ms, so half the stage or 5 ms, whichever is more
    assert parts <= stage + 0.01  # rounding of the record's 3 decimals
    assert stage <= parts + max(0.5 * stage, 5.0), (stage, parts)
    hist = sum(s for s, _n in cold[kind]["parts"].values())
    assert hist * 1e3 == pytest.approx(parts, abs=0.02)


def test_warm_query_books_no_part():
    eng = _engine()
    q = QUERIES["counter"]
    eng.query_range(q, START_S, END_S, 60)
    res = eng.query_range(q, START_S, END_S, 60)  # superblock hit
    assert res.query_log["stage_parts_ms"] == {}


@pytest.mark.parametrize("how", ["span", "recorder"])
def test_unknown_part_raises_as_an_unknown_phase_does(how):
    with pytest.raises(ValueError, match="unknown stage part"):
        if how == "span":
            with span("stage:reticulate", part="reticulate"):
                pass
        else:
            PhaseRecorder().add_part("reticulate", 0.1)


def test_a_part_books_only_under_the_stage_phase_and_exclusively():
    rec = PhaseRecorder()
    with metrics.activate_phases(rec):
        with span("outside", part="gather"):  # no stage phase around it
            pass
        assert rec.parts_snapshot() == {}
        with span("fused:stage", phase="stage"):
            with span("stage:concat", part="concat"):
                time.sleep(0.004)
                with span("stage:readback", part="readback"):
                    time.sleep(0.004)
    parts, stage = rec.parts_snapshot(), rec.snapshot()["stage"]
    assert parts["readback"] >= 0.004 and parts["concat"] >= 0.004
    assert parts["concat"] + parts["readback"] <= stage  # concat excludes it


def test_span_ids_are_sixteen_hex_and_distinct():
    ids = {metrics.new_span_id() for _ in range(2000)}
    assert len(ids) == 2000
    assert all(len(i) == 16 and int(i, 16) >= 0 for i in ids)


def test_no_span_id_reads_as_a_number(monkeypatch):
    """The profiler parses an annotation's stats: an id of decimal digits
    came back as an int and ``..e..`` as a float, and the by-name checks of
    the trace below failed on the one run in 400 that drew such an id for
    the follower (five cases at once: the names a follower has events of)."""
    draws = iter([0x4152310936287155, 0x12345e6789012345, 0x00000000000000e5,
                  0xabcdef0123456789])
    monkeypatch.setattr(metrics.random, "getrandbits", lambda _n: next(draws))
    assert metrics.new_trace_id() == "abcdef0123456789"
    monkeypatch.undo()
    for i in (metrics.new_span_id() for _ in range(20000)):
        with pytest.raises(ValueError):
            float(i)


def test_check_spans_lints_part_literals():
    spec = importlib.util.spec_from_file_location(
        "check_spans", os.path.join(ROOT, "tools", "check_spans.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    import ast

    tree = ast.parse('with span("x", part="gather"): pass\n'
                     'with span("y", part="reticulate"): pass\n'
                     'rec.add_part("concat", 1.0)\n')
    assert sorted(lit for lit, _ in mod._part_literals(tree)) == [
        "concat", "gather", "reticulate"]
    assert mod._canonical("STAGE_PARTS") == set(STAGE_PARTS)
    assert mod.main() == 0


# -- (b) the followers' wait --------------------------------------------------


def _one_flight() -> dict:
    """A leader and one follower on one ``SingleFlight`` key."""
    sf = SingleFlight()
    coalesced = REGISTRY.counter("filodb_queries_coalesced")
    n0 = coalesced.value
    out = {}

    def leader_fn():
        deadline = time.monotonic() + 10
        while coalesced.value == n0 and time.monotonic() < deadline:
            time.sleep(0.001)  # until the follower is waiting on us
        time.sleep(0.01)
        return "answer"

    def run(who):
        out[who] = sf.run("k", leader_fn, timeout_s=10)

    a = threading.Thread(target=run, args=("leader",))
    a.start()
    while "k" not in sf._flights:
        time.sleep(0.001)
    b = threading.Thread(target=run, args=("follower",))
    b.start()
    a.join()
    b.join()
    return out


def test_follower_books_its_wait_and_the_leader_books_nothing():
    before = _hist_sum("filodb_query_wait_seconds", kind="coalesced")
    assert _one_flight() == {"leader": "answer", "follower": "answer"}
    after = _hist_sum("filodb_query_wait_seconds", kind="coalesced")
    assert after[1] - before[1] == 1  # the follower alone
    assert after[0] - before[0] >= 0.01


# -- (c) the handler's clock --------------------------------------------------


@pytest.fixture(scope="module")
def served():
    eng = _engine()
    srv, port = serve_background(eng)
    yield eng, port
    srv.shutdown()
    srv.server_close()


def _edge_clocks() -> dict:
    return {
        "http": _hist_sum("filodb_http_request_seconds", route="query_range"),
        "engine": _hist_sum("filodb_query_latency_seconds", dataset="ds"),
        "transfer": _hist_sum("filodb_query_phase_seconds", phase="transfer",
                              dataset="ds"),
        "render": _hist_sum("filodb_query_phase_seconds", phase="render",
                            dataset="ds"),
        "ready": _hist_sum("filodb_transfer_ready_seconds"),
        "write": _hist_sum("filodb_render_write_seconds"),
    }


@pytest.mark.parametrize("kind", sorted(QUERIES))
def test_handler_clock_covers_engine_transfer_and_render(served, kind):
    _eng, port = served
    before = _edge_clocks()
    body = _get(port, QUERIES[kind])
    assert body["status"] == "success" and body["data"]["result"]
    # the handler observes after the client has its body: wait for it
    deadline = time.monotonic() + 5
    while (_edge_clocks()["http"][1] == before["http"][1]
           and time.monotonic() < deadline):
        time.sleep(0.005)
    after = _edge_clocks()
    d = {k: after[k][0] - before[k][0] for k in after}
    n = {k: after[k][1] - before[k][1] for k in after}
    assert n == dict.fromkeys(n, 1), n  # each clock once, per caller
    assert d["http"] >= d["engine"] + d["transfer"] + d["render"]
    assert 0 < d["ready"] <= d["transfer"]
    assert 0 < d["write"] <= d["render"]


# -- (d) the spans on a profiler trace ----------------------------------------


def _tree_edges(node: dict):
    for c in node.get("children", []):
        yield node["name"], c["name"]
        yield from _tree_edges(c)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A profiler trace around two concurrent identical cold queries, the
    second held to be a follower of the first, and the leader's span tree."""
    eng = _engine()
    srv, port = serve_background(eng)
    coalesced = REGISTRY.counter("filodb_queries_coalesced")
    n0 = coalesced.value
    inner = eng._query_range_uncoalesced

    def held(*a, **kw):
        deadline = time.monotonic() + 10
        while coalesced.value == n0 and time.monotonic() < deadline:
            time.sleep(0.001)
        return inner(*a, **kw)

    eng._query_range_uncoalesced = held
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    log_dir = str(tmp_path_factory.mktemp("trace"))
    bodies = []
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        threads = [threading.Thread(target=lambda: bodies.append(
            _get(port, QUERIES["hist"], trace="true"))) for _ in range(2)]
        threads[0].start()
        while not eng._single_flight._flights:
            time.sleep(0.001)
        threads[1].start()
        for t in threads:
            t.join()
        time.sleep(0.05)  # the handlers' last spans close after the bodies
    finally:
        jax.profiler.stop_trace()
        srv.shutdown()
        srv.server_close()
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    assert len(files) == 1
    events = []  # (name, start, end, trace_id)
    for plane in jax.profiler.ProfileData.from_file(files[0]).planes:
        for line in plane.lines:
            for e in line.events:
                tid = dict(e.stats).get("trace_id")
                if tid is not None:
                    events.append((e.name, e.start_ns,
                                   e.start_ns + e.duration_ns, tid))
    assert coalesced.value == n0 + 1
    return events, bodies[0]["data"]["trace"]


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
@pytest.mark.parametrize("name", [
    "http:query_range", "engine:query_range", "coalesce:wait", "fused:stage",
    "stage:gather", "stage:h2d_super", "transfer:ready", "render:write"])
def test_trace_holds_the_programs_spans_by_name(traced, name):
    events, _tree = traced
    mine = [e for e in events if e[0] == name]
    assert mine, sorted({e[0] for e in events})
    assert all(len(e[3]) == 16 for e in mine)


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_trace_events_nest_as_the_span_tree_does(traced):
    events, tree = traced
    leader = tree["trace_id"]
    by = {}
    for n, a, b, tid in events:
        by.setdefault((n, tid), []).append((a, b))

    def n_inside(child, parent, tid):
        return sum(any(pa <= ca and cb <= pb for pa, pb in by[(parent, tid)])
                   for ca, cb in by[(child, tid)])

    def inside(child, parent, tid):
        return n_inside(child, parent, tid) == len(by[(child, tid)])

    # the query's own tree (what ?trace=true returns): as many events of
    # the child's name lie inside events of the parent's name as the tree
    # has such edges (names repeat: a read-back happens under several
    # parts). The root is opened by hand in the engine, not an annotation.
    edges = [e for e in _tree_edges(tree) if e[0] != "query"]
    assert ("fused:stage", "stage:gather") in edges
    for parent, child in set(edges):
        assert n_inside(child, parent, leader) >= edges.count(
            (parent, child)), (parent, child)
    # around it, the caller's spans share the request's trace id
    for parent, child in [("http:query_range", "engine:query_range"),
                          ("engine:query_range", "FusedAggregateExec"),
                          ("http:query_range", "transfer"),
                          ("transfer", "transfer:ready"),
                          ("render", "render:write")]:
        assert inside(child, parent, leader), (parent, child)
    # the follower: its own id, waiting inside its engine span
    followers = {tid for (n, tid) in by if n == "coalesce:wait"}
    assert len(followers) == 1 and leader not in followers
    (f,) = followers
    assert inside("coalesce:wait", "engine:query_range", f)
    assert inside("engine:query_range", "http:query_range", f)
    assert ("fused:stage", f) not in by  # it staged nothing


# -- (e) names on the device --------------------------------------------------

# sha256 of the lowered StableHLO (no locations: scopes live in them alone;
# the module named ``program``, since the one entry point replaced a wrapper
# per program) of each program on the inputs below, taken at the commit
# before any scope was added and held across the move from hand-written
# wrappers to the composed family, with this jax: equal text in is equal
# code out.
PARENT_STABLEHLO = {
    "0.9.0": {
        "hist_shared": "0bdc321e72463eee2cd2a2fe5a7a0e1a74f2f65957edb3a1c5c47150716dfc17",
        "mxu": "eb565297d6b96aa6e684267ae6663f1b63e3a5bd5f7fcbf30487130068c7dea0",
    },
}


def _hist_shared_args():
    rng = np.random.default_rng(5)
    S, T, B, J = 8, 32, 4, 8
    vals = np.cumsum(np.cumsum(rng.poisson(2.0, (S, T, B)), axis=2),
                     axis=1).astype(np.float32)
    ts = np.arange(T, dtype=np.int32) * 10_000
    out_t = (np.arange(J, dtype=np.int32) + 1) * 30_000 + 40_000
    window = np.int32(60_000)
    lo = np.searchsorted(ts, out_t - window, side="right").astype(np.int32)
    hi = np.searchsorted(ts, out_t, side="right").astype(np.int32)
    t_first, t_last = ts[np.minimum(lo, T - 1)], ts[np.maximum(hi - 1, 0)]
    gids = (np.arange(S) % 2).astype(np.int32)
    les = np.array([0.1, 1.0, 10.0, np.inf], np.float32)
    return (AGG.FusedSpec("hist_shared", "rate", ("hist", "quantile"), 2,
                          (False,)),
            (vals,), (lo, hi, t_first, t_last, out_t, window), gids, les,
            np.float32(0.9))


def _mxu_call(monkeypatch, func="rate", op="sum"):
    """(jit, args) of the ``_fused_program_jit`` dispatch (the mxu body)
    that ``<op>(<func>())`` over a regular grid makes."""
    from filodb_tpu.ops import staging as ST
    from filodb_tpu.ops.kernels import RangeParams

    rng = np.random.default_rng(6)
    ts = BASE + np.arange(64, dtype=np.int64) * 10_000
    series = [(ts, np.cumsum(rng.uniform(0, 10, 64))) for _ in range(8)]
    block = ST.stage_series(series, BASE, [(0, i) for i in range(8)],
                            counter_corrected=func == "rate")
    seen = {}
    real = AGG._fused_program_jit

    class Recorder:
        _cache_size = staticmethod(real._cache_size)

        def __call__(self, *args):
            seen["args"] = args
            return real(*args)

    monkeypatch.setattr(AGG, "_fused_program_jit", Recorder())
    gids = (np.arange(block.ts.shape[0]) % 2).astype(np.int32)
    AGG.fused_range_aggregate(
        func, op, block, jnp.asarray(gids), 2,
        RangeParams(BASE + 300_000, 60_000, 5, 300_000), is_counter=True)
    return real, seen["args"]


def _trace_join():
    spec = importlib.util.spec_from_file_location(
        "trace_join", os.path.join(ROOT, "tools", "trace_join.py"))
    tj = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tj)
    return tj


@pytest.fixture
def program(request, monkeypatch):
    if request.param == "hist_shared":
        return request.param, AGG._fused_program_jit, _hist_shared_args()
    return (request.param, *_mxu_call(monkeypatch))


PROGRAMS = pytest.mark.parametrize("program", ["hist_shared", "mxu"],
                                   indirect=True)


@PROGRAMS
@pytest.mark.parametrize("scope", ["range_fn", "group_reduce", "epilogue"])
def test_fused_programs_carry_the_three_stage_scopes(program, scope):
    _name, fn, args = program
    # op names: ``jit(<program>)/jit(<kernel>)/<scope>/<op>`` once inlined
    assert f'"{scope}/' in fn.lower(*args).as_text(debug_info=True) or \
        f"/{scope}/" in fn.lower(*args).as_text(debug_info=True)


@PROGRAMS
def test_a_scope_changes_metadata_only(program):
    name, fn, args = program
    lowered = fn.lower(*args)
    for scope in ("range_fn", "group_reduce", "epilogue"):
        assert scope not in lowered.as_text()  # locations only
    want = PARENT_STABLEHLO.get(jax.__version__)
    if want is not None:  # another jax lowers to other text: nothing to hold
        text = re.sub(r"^module @\S+", "module @program", lowered.as_text(),
                      count=1)
        assert hashlib.sha256(text.encode()).hexdigest() == want[name]


def test_the_wide_sum_has_a_scope_of_its_own_inside_group_reduce(monkeypatch):
    """``avg(avg_over_time())`` takes the wide form: its reduce is
    ``…/group_reduce/wide_sum/…`` on the trace, cut out by trace_join; the
    plain ``sum(rate())`` program holds no such name."""
    fn, plain_args = _mxu_call(monkeypatch)
    # (as a scope: a frame of an inner jit's cached trace may name a file or
    # a function of whoever traced it first, ``test_wide_sum.py`` among them)
    assert "/wide_sum/" not in fn.lower(*plain_args).as_text(debug_info=True)
    monkeypatch.undo()
    fn, wide_args = _mxu_call(monkeypatch, "avg_over_time", "avg")
    assert wide_args[0].body == "mxu"
    assert wide_args[0].epilogue == ("agg", "avg", "wide")
    assert "group_reduce/wide_sum/" in fn.lower(*wide_args).as_text(debug_info=True)
    assert _trace_join().scope_of({
        "tf_op": "jit(f)/epilogue/jit(g)/group_reduce/wide_sum/dot_general"}
    ) == "wide_sum"


def test_scoped_program_is_bit_equal_to_its_unscoped_twin():
    """The hist program again from the same bodies with every scope taken
    off (``__wrapped__`` under the jit and under the scope decorator)."""
    args = _hist_shared_args()
    spec, (vals,), (lo, hi, t_first, t_last, out_t, window), gids, les, qv = args
    func, num_groups, (is_delta,) = spec.func, spec.num_groups, spec.statics
    range_fn = HK._hist_range_shared.__wrapped__
    reduce_fn = AGG._segment_aggregate_jit.__wrapped__.__wrapped__
    quantile_fn = HK.histogram_quantile.__wrapped__.__wrapped__

    @jax.jit
    def twin(vals, lo, hi, t_first, t_last, out_t, window, gids, les, qv):
        sjb = range_fn(func, vals, lo, hi, t_first, t_last, out_t, window,
                       is_delta)
        S, J, B = sjb.shape
        gjb = reduce_fn("sum", sjb.reshape(S, J * B), gids, num_groups + 1
                        )[:num_groups].reshape(num_groups, J, B)
        return quantile_fn(qv, gjb, les)

    lowered = twin.lower(vals, lo, hi, t_first, t_last, out_t, window, gids,
                         les, qv).as_text(debug_info=True)
    # as a scope, not as a frame's function name (``_hist_epilogue``): the
    # inner jits' cached traces keep the frames of whoever traced them first
    for scope in ("range_fn", "group_reduce", "epilogue"):
        assert f'"{scope}/' not in lowered and f"/{scope}/" not in lowered
    got = np.asarray(AGG._fused_program_jit(*args))
    want = np.asarray(twin(vals, lo, hi, t_first, t_last, out_t, window,
                           gids, les, qv))
    assert np.isfinite(got).any()
    assert got.tobytes() == want.tobytes()


def _pb(num: int, value) -> bytes:
    """One protobuf field: an int as a varint, bytes length-delimited."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    return varint(num << 3 | 2) + varint(len(value)) + value


def test_trace_join_reads_the_op_name_from_the_events_metadata(tmp_path):
    """On a v5e the op-name (``tf_op``) is a stat of the event's METADATA,
    which jax's ProfileData does not hand out: the tool reads it from the
    file's bytes. One device plane: stat names 7 = tf_op, 9 = hlo_category,
    300 = a referenced value; one op with a string and a ref stat."""
    tj = _trace_join()

    def stat_name(i, name):
        return _pb(5, _pb(1, i) + _pb(2, _pb(1, i) + _pb(2, name)))

    op = "%copy.6 = f32[8192,768,12] copy(%vals.1)"
    event = _pb(1, 5) + _pb(2, op.encode()) + _pb(5, _pb(1, 7) + _pb(
        5, b"jit(f)/jit(_take)/range_fn/gather")) + _pb(5, _pb(1, 9) + _pb(7, 300))
    plane = (_pb(2, b"/device:TPU:0") + stat_name(7, b"tf_op")
             + stat_name(9, b"hlo_category") + stat_name(300, b"data formatting")
             + _pb(4, _pb(1, 5) + _pb(2, event)))
    host = _pb(2, b"/host:CPU") + _pb(4, _pb(1, 1) + _pb(2, _pb(2, b"x") + _pb(
        5, _pb(1, 7) + _pb(5, b"not a device op"))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb(1, plane) + _pb(1, host))
    got = tj.metadata_strings(str(path), "/device:TPU:")
    assert got == {op: {"tf_op": "jit(f)/jit(_take)/range_fn/gather",
                        "hlo_category": "data formatting"}}
    assert tj.scope_of(got[op]) == "range_fn"
    assert tj.scope_of({"tf_op": "jit(f)/epilogue/jit(g)/group_reduce/add"}
                       ) == "group_reduce"  # the innermost stage
    assert tj.scope_of({"tf_op": "vals:"}) == "(no stage)"


# -- (f) the benchmark's metric files -----------------------------------------

METRIC_FILES = sorted(glob.glob(os.path.join(
    ROOT, "benchmarks", "chip", "layer_metrics", "*.json")))
_SUFFIXES = ("_total", "_sum", "_count", "_bucket")


def _counter_of(path: str):
    with open(path) as f:
        return json.load(f)["source"].get("counter")


@pytest.mark.parametrize("path", [p for p in METRIC_FILES if _counter_of(p)],
                         ids=lambda p: os.path.basename(p)[:-5])
def test_every_counter_a_metric_file_names_is_a_family(path):
    """A typo reads 0 for ever: ``readers.total`` sums what it finds."""
    counter = _counter_of(path)
    family = next((counter[: -len(s)] for s in _SUFFIXES
                   if counter.endswith(s)), counter)
    assert family in metrics.HELP_TEXTS, counter


# What a ``benchmark`` PR's metric files will name (PERF.md 7): the sample on
# /metrics and the one label ``readers.counter_per_request`` selects it by.
PLANNED_READS = {
    **{f"stage_{p}_ms": ("filodb_stage_part_seconds_sum", {"part": p})
       for p in STAGE_PARTS},
    "stage_h2d_mb": ("filodb_stage_h2d_bytes_total", {}),
    # PR 32: a mirror's life (aliased|copied at a shard's block, deferred at a
    # device-assembled superblock, materialized at its first extension)
    "stage_mirror_mb": ("filodb_stage_mirror_bytes_total", {"site": "super"}),
    # PR 35: series a cold stage took through the native pass, of all it staged
    "stage_native_pct": ("filodb_stage_gather_series_total",
                         {"how": "native" if native.stage_lib() else "python"}),
    "superblocks_assembled": ("filodb_superblock_assembled_total", {"where": "device"}),
    # PR 36: the body a fused launch ran and the grid class it met; read per
    # grid by scraped_off_ladder_pct (grid="irregular"), here on a shared grid
    "fused_on_ladder_pct": ("filodb_fused_dispatch_total", {"grid": "regular"}),
    "coalesce_wait_ms": ("filodb_query_wait_seconds_sum", {"kind": "coalesced"}),
    "handler_ms": ("filodb_http_request_seconds_sum", {"route": "query_range"}),
    "device_ready_ms": ("filodb_transfer_ready_seconds_sum", {}),
    "render_write_ms": ("filodb_render_write_seconds_sum", {}),
    # PR 38: the files are there (layer_metrics/); what one served query books
    "group_ms": ("filodb_query_phase_seconds_sum", {"phase": "group"}),
    "other_ms": ("filodb_query_phase_seconds_sum", {"phase": "other"}),
    "queue_wait_ms": ("filodb_query_wait_seconds_sum", {"kind": "queued"}),
    "handback_ms": ("filodb_query_wait_seconds_sum", {"kind": "handback"}),
    "setup_stage_s": ("filodb_query_phase_seconds_sum", {"phase": "stage"}),
    "setup_query_s": ("filodb_http_request_seconds_sum", {"route": "query_range"}),
}


@pytest.fixture(scope="module")
def scraped(cold, served):
    from benchmarks.chip import readers

    _eng, port = served
    _one_flight()
    _get(port, QUERIES["hist"])
    _pooled_query()  # the pool's two hops: the served engine runs inline
    deadline = time.monotonic() + 5  # the handler observes after the body
    while True:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics") as r:
            counters = readers.parse_metrics(r.read().decode())
        sample, labels = PLANNED_READS["handler_ms"]
        if (readers.total(counters, sample, **labels) > 0
                or time.monotonic() > deadline):
            return counters
        time.sleep(0.005)


@pytest.mark.parametrize("metric", sorted(PLANNED_READS))
def test_the_benchmarks_parser_finds_what_a_planned_metric_reads(scraped, metric):
    from benchmarks.chip import readers

    sample, labels = PLANNED_READS[metric]
    assert readers.total(scraped, sample, **labels) > 0, (sample, labels)


# -- (g) group, queue, and the clocks of set-up -------------------------------

BIG_SERIES = 2048


@pytest.fixture(scope="module")
def big():
    """A cold fused query over BIG_SERIES counters and its repeat: per query
    the caller's wall, the querylog record and the span tree."""
    ms = TimeSeriesMemStore(StoreConfig())
    ms.setup(Dataset("ds"), list(range(4)))
    ms.ingest_routed("ds", counter_batch(n_series=BIG_SERIES,
                                         n_samples=N_SAMPLES, start_ms=BASE),
                     spread=2)
    eng = QueryEngine(ms, "ds", PlannerParams())
    out = {}
    for which in ("cold", "repeat"):
        t0 = time.perf_counter()
        res = eng.query_range(QUERIES["counter"], START_S, END_S, 60)
        out[which] = {"wall_ms": 1e3 * (time.perf_counter() - t0),
                      "record": QUERY_LOG.get(res.query_log["id"]),
                      "tree": res.trace.to_dict()}
    return out


def _spans_named(node: dict, name: str) -> list:
    found = [node] if node["name"] == name else []
    for c in node.get("children", []):
        found += _spans_named(c, name)
    return found


@pytest.mark.parametrize("which", ["cold", "repeat"])
def test_phases_sum_to_the_wall(big, which):
    rec, wall = big[which]["record"], big[which]["wall_ms"]
    assert rec["path"] == "fused"
    booked = sum(rec["phases_ms"].values())
    assert booked == pytest.approx(rec["duration_ms"], abs=0.02)
    assert booked <= wall
    if which == "cold":  # a second of staging: the engine's own edges are < 1 %
        assert booked >= 0.99 * wall, (booked, wall)


def test_group_is_booked_on_a_memo_miss_and_next_to_nothing_on_a_hit(big):
    cold, repeat = (big[w]["record"]["phases_ms"] for w in ("cold", "repeat"))
    assert cold["group"] > 0.2  # BIG_SERIES label sets regrouped, in Python
    assert repeat["group"] < 0.1 * cold["group"]
    for which, memo in (("cold", "miss"), ("repeat", "hit")):
        (sp,) = _spans_named(big[which]["tree"], "fused:groups")
        assert sp["tags"] == {"memo": memo, "series": BIG_SERIES}
        assert sp["duration_ms"] == pytest.approx(
            big[which]["record"]["phases_ms"]["group"], abs=0.02)


def test_other_is_a_residual_again(big):
    rec = big["cold"]["record"]
    assert rec["phases_ms"].get("other", 0.0) <= 0.05 * rec["duration_ms"]


def _wait_clocks() -> dict:
    return {
        "queued": _hist_sum("filodb_query_wait_seconds", kind="queued"),
        "handback": _hist_sum("filodb_query_wait_seconds", kind="handback"),
        "engine": _hist_sum("filodb_query_latency_seconds", dataset="ds"),
    }


def _pooled_query():
    """One query through the bounded pool: (result, growth of the clocks)."""
    from filodb_tpu.coordinator.scheduler import QueryScheduler

    sched = QueryScheduler(parallelism=2)
    try:
        eng = _engine(scheduler=sched)
        before = _wait_clocks()
        res = eng.query_range(QUERIES["counter"], START_S, END_S, 60)
        after = _wait_clocks()
    finally:
        sched.shutdown()
    return res, {k: (after[k][0] - before[k][0], after[k][1] - before[k][1])
                 for k in after}


def test_a_pooled_query_books_its_two_hops_once_each():
    res, grew = _pooled_query()
    assert res.grids
    assert grew["queued"][1] == grew["handback"][1] == grew["engine"][1] == 1
    queued, handback, engine = (grew[k][0] for k in ("queued", "handback", "engine"))
    assert queued >= 0 and handback >= 0
    assert queued + handback <= engine  # inside engine:query_range's wall
    rec = QUERY_LOG.get(res.query_log["id"])
    assert rec["phases_ms"]["queue"] == pytest.approx(
        1e3 * (queued + handback), abs=0.01)
    assert sum(rec["phases_ms"].values()) == pytest.approx(
        rec["duration_ms"], abs=0.02)


def test_an_inline_query_books_no_hop(big):
    assert "queue" not in big["cold"]["record"]["phases_ms"]


def test_a_prewarm_has_clocks_of_its_own_and_leaks_into_no_query_metric():
    from filodb_tpu.query.scheduler import DispatchScheduler

    sched = DispatchScheduler(window_ms=0, prewarm_min_count=1)
    _engine(dispatch_scheduler=sched)  # registers its _prewarm_key
    desc = {"promql": QUERIES["counter"], "step_ms": 60_000,
            "span_ms": int((END_S - START_S) * 1000),
            "end_lag_ms": (time.time() - END_S) * 1000}
    sched.key_ring.observe(("prewarm-clock", desc["promql"]), desc)

    def clocks():
        return {
            "prewarm": _hist_sum("filodb_prewarm_seconds"),
            "stage": _hist_sum("filodb_prewarm_phase_seconds", phase="stage"),
            "group": _hist_sum("filodb_prewarm_phase_seconds", phase="group"),
            "query_phases": _hist_sum("filodb_query_phase_seconds"),
            "stage_parts": _hist_sum("filodb_stage_part_seconds"),
            "ok": REGISTRY.counter("filodb_prewarm", outcome="ok").value,
        }

    before = clocks()
    assert len(sched.prewarm_tick(storms={})) == 1
    after = clocks()
    assert after["ok"] == before["ok"] + 1
    for clock in ("prewarm", "stage", "group"):  # one observation a key
        assert after[clock][1] == before[clock][1] + 1, clock
        assert after[clock][0] > before[clock][0], clock
    assert (after["stage"][0] - before["stage"][0]
            <= after["prewarm"][0] - before["prewarm"][0])
    # nothing where the window's metrics read (PERF.md 7 (b))
    assert after["query_phases"] == before["query_phases"]
    assert after["stage_parts"] == before["stage_parts"]


def test_a_failed_prewarm_is_clocked_and_counted_as_an_error():
    from filodb_tpu.query.scheduler import DispatchScheduler

    def boom(_desc):
        raise RuntimeError("trace failed")

    sched = DispatchScheduler(window_ms=0, prewarm_min_count=1)
    sched.register_prewarmer(boom)
    sched.key_ring.observe("k", {"promql": "up", "step_ms": 1, "span_ms": 1})
    errors = REGISTRY.counter("filodb_prewarm", outcome="error")
    n0, clock0 = errors.value, _hist_sum("filodb_prewarm_seconds")
    assert sched.prewarm_tick(storms={}) == []
    assert errors.value == n0 + 1
    assert _hist_sum("filodb_prewarm_seconds")[1] == clock0[1] + 1


def test_ingest_routed_books_one_observation_a_call():
    ms = TimeSeriesMemStore(StoreConfig())
    ms.setup(Dataset("ds"), list(range(4)))
    before = _hist_sum("filodb_ingest_seconds", dataset="ds")
    for call in range(3):
        assert ms.ingest_routed("ds", counter_batch(
            n_series=8, n_samples=20, start_ms=BASE + call * 200_000),
            spread=2) == 160
    after = _hist_sum("filodb_ingest_seconds", dataset="ds")
    assert after[1] - before[1] == 3
    assert after[0] > before[0]


STARTUP_STAGES = ("import", "backend", "store", "listen")


@pytest.fixture(scope="module")
def started():
    """/metrics of a FiloServer that has started, and the clock around it."""
    from benchmarks.chip import readers
    from filodb_tpu.server import FiloServer, process_start_time

    t0 = time.time()
    srv = FiloServer({"shards": 2, "http_port": 0})
    port = srv.start()
    wall = time.time() - t0
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics") as r:
            text = r.read().decode()
    finally:
        srv.stop()
    return readers.parse_metrics(text), t0, wall, process_start_time()


@pytest.mark.parametrize("stage", STARTUP_STAGES)
def test_startup_seconds_has_its_four_stages(started, stage):
    counters, _t0, _wall, _born = started
    got = {dict(ls)["stage"].strip('"'): v for (n, ls), v in counters.items()
           if n == "filodb_startup_seconds"}
    assert sorted(got) == sorted(STARTUP_STAGES)
    assert got[stage] >= 0


def test_startup_stages_add_up_to_the_start_and_the_process_start_is_exact(started):
    from benchmarks.chip import readers

    counters, t0, wall, born = started
    # /metrics prints a gauge in full where six digits would lose it
    assert readers.total(counters, "process_start_time_seconds") == born
    assert born <= t0
    stages = {s: readers.total(counters, "filodb_startup_seconds", stage=s)
              for s in STARTUP_STAGES}
    assert stages["import"] == pytest.approx(t0 - born, abs=0.5)
    inside = stages["backend"] + stages["store"] + stages["listen"]
    assert inside <= wall + 1e-3
    assert inside >= 0.9 * wall - 0.05  # but for the caller's own gap


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_the_new_spans_are_host_events_of_a_profiler_trace(tmp_path):
    """``ingest:routed``, ``sched:run``, ``fused:groups`` and ``prewarm:key``
    on a trace's clock; the pool's hop is the gap between ``sched:run``'s
    start and the worker's plan span."""
    from filodb_tpu.coordinator.scheduler import QueryScheduler
    from filodb_tpu.query.scheduler import DispatchScheduler

    pool = QueryScheduler(parallelism=2)
    sched = DispatchScheduler(window_ms=0, prewarm_min_count=1)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng = _engine(scheduler=pool, dispatch_scheduler=sched)
        eng.query_range(QUERIES["counter"], START_S, END_S, 60)
        # the served query put its key (and its lag behind now) in the ring
        assert len(sched.prewarm_tick(storms={})) == 1
    finally:
        jax.profiler.stop_trace()
        pool.shutdown()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    spans = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if dict(e.stats).get("trace_id") is not None:
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    for name in ("ingest:routed", "sched:run", "fused:groups", "prewarm:key"):
        assert name in spans, sorted(spans)
    (run,) = spans["sched:run"]
    plan = min(spans["FusedAggregateExec"])  # the served one: the first
    assert run[0] <= plan[0] and plan[1] <= run[1]
    warm = spans["prewarm:key"][0]
    assert any(warm[0] <= a and b <= warm[1] for a, b in spans["fused:groups"])
