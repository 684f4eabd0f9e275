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
  ``/metrics`` under the name and label the benchmark's own parser finds.
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
}


@pytest.fixture(scope="module")
def scraped(cold, served):
    from benchmarks.chip import readers

    _eng, port = served
    _one_flight()
    _get(port, QUERIES["hist"])
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
