"""Chaos tests: deterministic fault injection over the scatter-gather path
(query/faults.py retry/breaker/partial-results + testkit.FaultInjector).

Everything here is seeded and clock-injected — no sleeps against real
failure windows, no flaky timing: the same schedule always produces the
same outcomes, so these run inside tier-1.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import urllib.parse
import urllib.request

import numpy as np
import pytest

from filodb_tpu.coordinator.cluster import ShardManager, ShardStatus
from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine
from filodb_tpu.core.schemas import Dataset
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.query.exec.plans import DistConcatExec, ExecPlan, QueryContext
from filodb_tpu.query.exec.transformers import QueryDeadlineExceeded
from filodb_tpu.query.faults import (
    BreakerRegistry,
    CircuitOpenError,
    RetryPolicy,
    dispatch_child,
)
from filodb_tpu.query.rangevector import QueryResult
from filodb_tpu.testkit import FaultInjector, FaultRule, InjectedFault, counter_batch

pytestmark = pytest.mark.chaos

START = 1_600_000_000_000
Q = "sum(rate(http_requests_total[5m]))"
S, E = START / 1000 + 400, START / 1000 + 900


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


class FlakyRemoteExec(ExecPlan):
    """Minimal remote leaf: fails its first ``fail_times`` executions (or
    always), then returns an empty result."""

    is_remote = True

    def __init__(self, endpoint: str, fail_times: int | None = None,
                 always_fail: bool = False):
        super().__init__()
        self.endpoint = endpoint
        self.fail_times = fail_times
        self.always_fail = always_fail
        self.calls = 0

    def args_str(self) -> str:
        return f"endpoint={self.endpoint}"

    def do_execute(self, ctx):
        n = self.calls
        self.calls += 1
        if self.always_fail or (self.fail_times is not None and n < self.fail_times):
            raise InjectedFault(f"flaky {self.endpoint} call {n}")
        return QueryResult()


class SilentRemoteExec(ExecPlan):
    """Remote leaf whose transport never returns on the ``silent`` endpoint:
    ``execute`` blocks on an Event nobody sets, as a gRPC stream wedged on a
    connection its server was closing does (timeout and cancel() ignored).
    On any other endpoint it answers one raw series."""

    is_remote = True

    def __init__(self, endpoint: str, silent: str, sibling_endpoints=()):
        super().__init__()
        self.endpoint = endpoint
        self.silent = silent
        self.sibling_endpoints = tuple(sibling_endpoints)

    def with_endpoint(self, endpoint: str) -> "SilentRemoteExec":
        return SilentRemoteExec(endpoint, self.silent)

    def args_str(self) -> str:
        return f"endpoint={self.endpoint}"

    def do_execute(self, ctx):
        if self.endpoint == self.silent:
            threading.Event().wait()
        res = QueryResult()
        res.raw = [({"job": "replicated"}, np.arange(4), np.arange(4) / 3.0)]
        return res


def silent_gather(silent: str, siblings=(), deadline_s: float = 1.0, **ctx_kw):
    """Gather over a replicated leg pinned to ``grpc://a:1`` and a healthy
    remote one; returns (context, merge node)."""
    ctx = make_ctx(deadline_s=deadline_s, **ctx_kw)
    plan = DistConcatExec([
        SilentRemoteExec("grpc://a:1", silent, sibling_endpoints=siblings),
        FlakyRemoteExec("grpc://healthy:1"),
    ])
    return ctx, plan


def make_engine(dispatcher=None, **params):
    ms = TimeSeriesMemStore()
    ms.setup(Dataset("prometheus"), range(4))
    ms.ingest_routed(
        "prometheus",
        counter_batch(n_series=16, n_samples=60, start_ms=START),
        spread=2,
    )
    eng = QueryEngine(
        ms, "prometheus",
        PlannerParams(spread=2, num_shards=4, dispatcher=dispatcher, **params),
    )
    return ms, eng


def make_ctx(deadline_s: float = 60.0, **kw) -> QueryContext:
    ctx = QueryContext(None, "ds", deadline_s=deadline_s)
    for k, v in kw.items():
        setattr(ctx, k, v)
    return ctx


# ---------------------------------------------------------------------------
# partial results
# ---------------------------------------------------------------------------


class TestPartialResults:
    def test_aggregation_merges_survivors_and_names_lost_shard(self):
        ms0, full_eng = make_engine()
        victim = next(sh.shard_num for sh in ms0.shards("prometheus")
                      if sh.num_partitions)
        inj = FaultInjector([FaultRule(target=f"shard={victim} ")], seed=1)
        _, eng = make_engine(dispatcher=inj)
        full = full_eng.query_range(Q, S, E, 60)
        res = eng.query_range(Q, S, E, 60, allow_partial_results=True)
        assert res.partial is True
        assert len(res.warnings) == 1
        w = res.warnings[0]
        assert w["shard"] == victim and w["plan"] == "SelectRawPartitionsExec"
        assert "InjectedFault" in w["error"]
        # survivors merged: same grid shape, strictly less mass than full
        got, want = res.grids[0].values_np(), full.grids[0].values_np()
        assert got.shape == want.shape
        assert 0 < np.nansum(got) < np.nansum(want)

    def test_without_flag_single_wrapped_error(self):
        ms0, _ = make_engine()
        victim = next(sh.shard_num for sh in ms0.shards("prometheus")
                      if sh.num_partitions)
        inj = FaultInjector([FaultRule(target=f"shard={victim} ")], seed=1)
        _, eng = make_engine(dispatcher=inj)
        with pytest.raises(InjectedFault, match=r"child SelectRawPartitionsExec"):
            eng.query_range(Q, S, E, 60)

    def test_all_children_lost_still_raises(self):
        inj = FaultInjector([FaultRule(target="SelectRawPartitionsExec")], seed=1)
        _, eng = make_engine(dispatcher=inj)
        with pytest.raises(InjectedFault):
            eng.query_range(Q, S, E, 60, allow_partial_results=True)

    def test_latency_injection_still_correct(self):
        """A straggler shard (latency spike, no failure) changes nothing in
        the result — the gather absorbs it."""
        slept = []
        inj = FaultInjector(
            [FaultRule(target="shard=", kind="latency", latency_s=0.01, count=2)],
            seed=3, sleep=slept.append,
        )
        _, eng = make_engine(dispatcher=inj)
        _, full_eng = make_engine()
        res = eng.query_range(Q, S, E, 60, allow_partial_results=True)
        full = full_eng.query_range(Q, S, E, 60)
        assert not res.partial and not res.warnings
        np.testing.assert_allclose(
            res.grids[0].values_np(), full.grids[0].values_np(), rtol=1e-6
        )
        assert slept == [0.01, 0.01]

    def test_deterministic_across_runs(self):
        """Same seed + schedule => byte-identical warnings on every run."""
        outs = []
        for _ in range(2):
            inj = FaultInjector([FaultRule(target="shard=1 ")], seed=42)
            _, eng = make_engine(dispatcher=inj)
            res = eng.query_range(Q, S, E, 60, allow_partial_results=True)
            outs.append((json.dumps(res.warnings, sort_keys=True),
                         np.nansum(res.grids[0].values_np())))
        assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# retries
# ---------------------------------------------------------------------------


class TestRetries:
    def test_transient_failure_recovers_with_backoff(self):
        sleeps: list[float] = []
        policy = RetryPolicy(max_attempts=4, base_backoff_s=0.01, seed=7,
                             sleep=sleeps.append)
        child = FlakyRemoteExec("grpc://p:1", fail_times=2)
        ctx = make_ctx(retry_policy=policy, breakers=BreakerRegistry())
        res = dispatch_child(child, ctx)
        assert isinstance(res, QueryResult)
        assert child.calls == 3  # 2 failures + the success
        assert len(sleeps) == 2
        assert sleeps[1] > sleeps[0]  # exponential growth survives jitter

    def test_jitter_is_deterministic_with_seed(self):
        runs = []
        for _ in range(2):
            sleeps: list[float] = []
            policy = RetryPolicy(max_attempts=4, base_backoff_s=0.01, seed=7,
                                 sleep=sleeps.append)
            ctx = make_ctx(retry_policy=policy, breakers=BreakerRegistry())
            dispatch_child(FlakyRemoteExec("grpc://p:1", fail_times=2), ctx)
            runs.append(tuple(sleeps))
        assert runs[0] == runs[1]

    def test_exhausted_attempts_raise_last_error(self):
        sleeps: list[float] = []
        policy = RetryPolicy(max_attempts=3, base_backoff_s=0.01, seed=0,
                             sleep=sleeps.append)
        child = FlakyRemoteExec("grpc://p:1", always_fail=True)
        ctx = make_ctx(retry_policy=policy, breakers=BreakerRegistry())
        with pytest.raises(InjectedFault):
            dispatch_child(child, ctx)
        assert child.calls == 3 and len(sleeps) == 2

    def test_backoff_never_outlives_deadline(self):
        """A backoff that would sleep past the deadline is not taken."""
        sleeps: list[float] = []
        policy = RetryPolicy(max_attempts=50, base_backoff_s=10.0, jitter=0.0,
                             seed=0, sleep=sleeps.append)
        child = FlakyRemoteExec("grpc://p:1", always_fail=True)
        ctx = make_ctx(deadline_s=0.5, retry_policy=policy,
                       breakers=BreakerRegistry())
        with pytest.raises(InjectedFault):
            dispatch_child(child, ctx)
        assert child.calls == 1  # no retry: 10s backoff >= 0.5s budget
        assert sleeps == []

    def test_grpc_unavailable_retries_at_dispatch_layer(self):
        """A real dead gRPC endpoint: the transport (retries disabled for
        plan-scatter children) surfaces UNAVAILABLE marked retryable, and
        the dispatch-layer policy — the one config tunes — retries it."""
        from filodb_tpu.api.grpc_exec import GrpcPlanRemoteExec
        from filodb_tpu.query import logical as L
        from filodb_tpu.query.proto_plan import RemoteExecError

        sleeps: list[float] = []
        policy = RetryPolicy(max_attempts=2, base_backoff_s=0.01, seed=1,
                             sleep=sleeps.append)
        ctx = make_ctx(deadline_s=30.0, retry_policy=policy,
                       breakers=BreakerRegistry())
        child = GrpcPlanRemoteExec("grpc://127.0.0.1:9", L.LabelNames((), 1, 2))
        with pytest.raises(RemoteExecError, match="UNAVAILABLE"):
            dispatch_child(child, ctx)
        assert len(sleeps) == 1  # the dispatch layer retried once

    def test_retry_sequence_bounded_by_deadline_wallclock(self):
        """Real-sleep variant: many fast failures + small backoffs still end
        within the query deadline."""
        deadline = 0.3
        policy = RetryPolicy(max_attempts=1000, base_backoff_s=0.02,
                             max_backoff_s=0.02, jitter=0.0, seed=0)
        child = FlakyRemoteExec("grpc://p:1", always_fail=True)
        # breaker sized to never open: retries, not the breaker, must stop
        ctx = make_ctx(deadline_s=deadline, retry_policy=policy,
                       breakers=BreakerRegistry(min_calls=10_000))
        t0 = time.monotonic()
        with pytest.raises((InjectedFault, QueryDeadlineExceeded)):
            dispatch_child(child, ctx)
        elapsed = time.monotonic() - t0
        assert child.calls > 1  # it did retry
        assert elapsed <= deadline + 0.1


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------


class TestCircuitBreaker:
    def _ctx(self, clock, **breaker_kw):
        kw = dict(window=8, failure_rate=0.5, min_calls=4, cooldown_s=10.0)
        kw.update(breaker_kw)
        breakers = BreakerRegistry(clock=clock, **kw)
        policy = RetryPolicy(max_attempts=1, seed=0, sleep=lambda s: None)
        return make_ctx(retry_policy=policy, breakers=breakers), breakers

    def test_opens_at_threshold_and_fails_fast(self):
        clock = FakeClock()
        ctx, breakers = self._ctx(clock)
        child = FlakyRemoteExec("grpc://flappy:1", always_fail=True)
        for _ in range(4):
            with pytest.raises(InjectedFault):
                dispatch_child(child, ctx)
        br = breakers.breaker_for("grpc://flappy:1")
        assert br.state() == "open"
        with pytest.raises(CircuitOpenError, match="grpc://flappy:1"):
            dispatch_child(child, ctx)
        assert child.calls == 4  # open breaker never dispatched

    def test_recloses_after_cooldown_probe(self):
        clock = FakeClock()
        ctx, breakers = self._ctx(clock)
        child = FlakyRemoteExec("grpc://flappy:1", always_fail=True)
        for _ in range(4):
            with pytest.raises(InjectedFault):
                dispatch_child(child, ctx)
        br = breakers.breaker_for("grpc://flappy:1")
        assert br.state() == "open"
        clock.advance(10.0)
        assert br.state() == "half_open"
        child.always_fail = False  # endpoint recovered
        dispatch_child(child, ctx)  # the probe
        assert br.state() == "closed"
        dispatch_child(child, ctx)  # and traffic flows again
        assert child.calls == 6

    def test_failed_probe_reopens(self):
        clock = FakeClock()
        ctx, breakers = self._ctx(clock)
        child = FlakyRemoteExec("grpc://flappy:1", always_fail=True)
        for _ in range(4):
            with pytest.raises(InjectedFault):
                dispatch_child(child, ctx)
        clock.advance(10.0)
        with pytest.raises(InjectedFault):
            dispatch_child(child, ctx)  # probe fails
        br = breakers.breaker_for("grpc://flappy:1")
        assert br.state() == "open"
        # fresh cooldown: still open halfway through
        clock.advance(5.0)
        assert br.state() == "open"
        clock.advance(5.0)
        assert br.state() == "half_open"

    def test_flapping_endpoint_converges_via_injector(self):
        """End-to-end convergence: a flapping endpoint (4 bad, 4 good, ...)
        opens its breaker within the threshold, then re-closes after cooldown
        once the probe lands in a healthy phase."""
        clock = FakeClock()
        ctx, breakers = self._ctx(clock)
        inj = FaultInjector(
            [FaultRule(target="grpc://flap:7", kind="flap", period=4)], seed=9,
        )
        ctx.dispatcher = inj
        child = FlakyRemoteExec("grpc://flap:7")  # healthy unless injected
        for _ in range(4):  # failing phase -> breaker opens at min_calls
            with pytest.raises(InjectedFault):
                dispatch_child(child, ctx)
        br = breakers.breaker_for("grpc://flap:7")
        assert br.state() == "open"
        with pytest.raises(CircuitOpenError):
            dispatch_child(child, ctx)
        clock.advance(10.0)
        dispatch_child(child, ctx)  # probe: injector now in healthy phase
        assert br.state() == "closed"
        for _ in range(3):
            dispatch_child(child, ctx)  # healthy phase continues

    def test_typed_error_probe_does_not_wedge_half_open(self):
        """Regression: a query-shaped error (peer answered) during the
        half-open probe must release the probe slot — not leave the breaker
        half-open with zero capacity forever."""
        from filodb_tpu.query.exec.transformers import QueryError

        class TypedErrorExec(FlakyRemoteExec):
            typed = False

            def do_execute(self, ctx):
                self.calls += 1
                if self.typed:
                    raise QueryError("bad query per the peer")
                raise InjectedFault("transport down")

        clock = FakeClock()
        ctx, breakers = self._ctx(clock)
        child = TypedErrorExec("grpc://wedge:1")
        for _ in range(4):
            with pytest.raises(InjectedFault):
                dispatch_child(child, ctx)
        br = breakers.breaker_for("grpc://wedge:1")
        clock.advance(10.0)
        assert br.state() == "half_open"
        child.typed = True  # probe gets a typed answer, not a transport fail
        with pytest.raises(QueryError):
            dispatch_child(child, ctx)
        assert br.state() == "half_open"  # no transition either way...
        with pytest.raises(QueryError):
            dispatch_child(child, ctx)  # ...but the slot was released
        child.typed = False
        child.always_fail = False
        child.fail_times = 0

        class HealthyExec(FlakyRemoteExec):
            pass

        healthy = HealthyExec("grpc://wedge:1")
        dispatch_child(healthy, ctx)  # successful probe closes it
        assert br.state() == "closed"

    def test_breaker_metrics_exposed(self):
        from filodb_tpu.metrics import REGISTRY

        clock = FakeClock()
        ctx, _ = self._ctx(clock)
        child = FlakyRemoteExec("grpc://metrics-probe:1", always_fail=True)
        for _ in range(4):
            with pytest.raises(InjectedFault):
                dispatch_child(child, ctx)
        text = REGISTRY.expose()
        assert ('filodb_breaker_transitions_total{endpoint="grpc://metrics-probe:1",'
                'frm="closed",to="open"}') in text
        assert 'filodb_breaker_state{endpoint="grpc://metrics-probe:1"} 1' in text


# ---------------------------------------------------------------------------
# cross-transport partial results
# ---------------------------------------------------------------------------


class TestPartialOverGrpc:
    def test_warnings_cross_the_wire(self):
        from filodb_tpu.api.grpc_exec import exec_promql, serve_grpc
        from filodb_tpu.query.proto_plan import RemoteExecError

        ms0, _ = make_engine()
        victim = next(sh.shard_num for sh in ms0.shards("prometheus")
                      if sh.num_partitions)
        inj = FaultInjector([FaultRule(target=f"shard={victim} ")], seed=5)
        _, eng = make_engine(dispatcher=inj)
        server, port = serve_grpc(eng, port=0, host="127.0.0.1")
        ep = f"grpc://127.0.0.1:{port}"
        try:
            res = exec_promql(ep, Q, int(S * 1000), int(E * 1000), 60_000,
                              allow_partial=True)
            assert res.partial is True
            assert res.warnings and res.warnings[0]["shard"] == victim
            assert res.grids and res.grids[0].n_series == 1
            # without the flag the same query is an in-band error
            with pytest.raises(RemoteExecError, match="InjectedFault"):
                exec_promql(ep, Q, int(S * 1000), int(E * 1000), 60_000)
        finally:
            server.stop(grace=0)

    def test_explicit_strict_overrides_peer_partial_default(self):
        """allow_partial is tri-state on the wire: absent -> the peer's
        configured default applies; explicit False -> strict even on a peer
        whose default is partial=True."""
        from filodb_tpu.api.grpc_exec import exec_promql, serve_grpc
        from filodb_tpu.query.proto_plan import RemoteExecError

        ms0, _ = make_engine()
        victim = next(sh.shard_num for sh in ms0.shards("prometheus")
                      if sh.num_partitions)
        inj = FaultInjector([FaultRule(target=f"shard={victim} ")], seed=5)
        _, eng = make_engine(dispatcher=inj, allow_partial_results=True)
        server, port = serve_grpc(eng, port=0, host="127.0.0.1")
        ep = f"grpc://127.0.0.1:{port}"
        try:
            # absent flag: peer's default (partial) applies
            res = exec_promql(ep, Q, int(S * 1000), int(E * 1000), 60_000)
            assert res.partial is True and res.warnings
            # explicit strict: overrides the peer's partial default
            with pytest.raises(RemoteExecError, match="InjectedFault"):
                exec_promql(ep, Q, int(S * 1000), int(E * 1000), 60_000,
                            allow_partial=False)
        finally:
            server.stop(grace=0)


class TestPartialOverFlight:
    def test_warnings_ride_schema_metadata(self):
        pytest.importorskip("pyarrow.flight")
        from filodb_tpu.api.arrow_edge import HAVE_FLIGHT

        if not HAVE_FLIGHT:
            pytest.skip("pyarrow.flight unavailable")
        from filodb_tpu.api.arrow_edge import FlightQueryClient, FlightQueryServer

        ms0, _ = make_engine()
        victim = next(sh.shard_num for sh in ms0.shards("prometheus")
                      if sh.num_partitions)
        inj = FaultInjector([FaultRule(target=f"shard={victim} ")], seed=5)
        # Flight tickets carry no per-request flag: the engine default governs
        _, eng = make_engine(dispatcher=inj, allow_partial_results=True)
        server = FlightQueryServer(eng)
        try:
            ep = f"grpc://127.0.0.1:{server.port}"
            res = FlightQueryClient.query_range(ep, Q, S, E, 60)
            assert res.partial is True
            assert res.warnings and res.warnings[0]["shard"] == victim
            assert res.grids
        finally:
            server.shutdown()


class TestQueryDeadline:
    def test_deadline_exceeded_never_degrades_to_partial(self):
        """A query-deadline breach is a query-level condition: even with
        allow_partial_results the query fails instead of returning a 'partial'
        200 missing the shards that never got to run."""
        ms0, _ = make_engine()
        victim = next(sh.shard_num for sh in ms0.shards("prometheus")
                      if sh.num_partitions)

        class DeadlineBurner:
            """Dispatcher: the first child succeeds, then the budget is
            spent — remaining children all hit the deadline. Pre-fix, the
            one survivor made this a 'partial' success."""

            def dispatch(self, child, ctx):
                out = child.execute(ctx)
                ctx._start_time -= ctx.deadline_s + 1  # burn the budget
                return out

        _, eng = make_engine(dispatcher=DeadlineBurner(), deadline_s=30)
        with pytest.raises(QueryDeadlineExceeded):
            eng.query_range(Q, S, E, 60, allow_partial_results=True)

    @pytest.mark.parametrize("allow_partial", [False, True])
    def test_silent_leg_without_sibling_ends_at_the_deadline(self, allow_partial):
        """The one who waits bounds the wait: a remote leg whose transport
        never returns costs the query its deadline and no more, is named,
        and with the budget spent is an error even where a partial result
        would be allowed."""
        ctx, plan = silent_gather(
            "grpc://a:1", breakers=BreakerRegistry(min_calls=1),
            allow_partial_results=allow_partial)
        t0 = time.monotonic()
        with pytest.raises(QueryDeadlineExceeded,
                           match=r"child SilentRemoteExec\(endpoint=grpc://a:1\)"):
            plan.execute(ctx)
        assert time.monotonic() - t0 < 1.0 + 2.0
        assert not ctx.warnings
        assert ctx.breakers.states() == {"grpc://a:1": "open",
                                         "grpc://healthy:1": "closed"}

    def test_silent_replica_costs_its_share_then_the_sibling_answers(self):
        from filodb_tpu.metrics import REGISTRY

        def failovers():
            return sum(v for k, v in REGISTRY.counter_samples(
                "filodb_replica_failovers").items()
                if "endpoint_failure" in k and "grpc://a:1" in k)

        healthy_ctx, healthy = silent_gather(None, siblings=("grpc://b:1",))
        want = healthy.execute(healthy_ctx).raw
        ctx, plan = silent_gather("grpc://a:1", siblings=("grpc://b:1",),
                                  deadline_s=2.0,
                                  breakers=BreakerRegistry(min_calls=1))
        fo0 = failovers()
        t0 = time.monotonic()
        got = plan.execute(ctx).raw
        elapsed = time.monotonic() - t0
        # one share of two: half the deadline, and the sibling had the rest
        assert 1.0 <= elapsed < 2.0
        assert len(got) == len(want) == 1
        for (gl, gt, gv), (wl, wt, wv) in zip(got, want):
            assert gl == wl
            assert gt.tobytes() == wt.tobytes() and gv.tobytes() == wv.tobytes()
        assert failovers() == fo0 + 1
        assert ctx.breakers.states()["grpc://a:1"] == "open"
        assert ctx.breakers.states()["grpc://b:1"] == "closed"
        assert ctx.obs["endpoints"] == ["grpc://healthy:1", "grpc://b:1"]

    def test_process_that_left_a_silent_leg_behind_still_exits(self):
        """The gather's pool workers come back once the attempt is bounded,
        and the thread left on the silent call is a daemon: the interpreter
        exits by itself after main returns."""
        code = (
            "import sys\n"
            "sys.path.insert(0, 'tests')\n"
            "import test_chaos as t\n"
            "ctx, plan = t.silent_gather('grpc://a:1')\n"
            "try:\n"
            "    plan.execute(ctx)\n"
            "except t.QueryDeadlineExceeded:\n"
            "    print('MAIN_RETURNS', flush=True)\n"
        )
        tests_dir = os.path.dirname(os.path.abspath(__file__))
        proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                              text=True, timeout=60,
                              cwd=os.path.dirname(tests_dir))
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "MAIN_RETURNS" in proc.stdout


class TestPartialOverHttp:
    def test_warnings_and_partial_in_json(self):
        from filodb_tpu.api.http import serve_background

        ms0, _ = make_engine()
        victim = next(sh.shard_num for sh in ms0.shards("prometheus")
                      if sh.num_partitions)
        inj = FaultInjector([FaultRule(target=f"shard={victim} ")], seed=5)
        _, eng = make_engine(dispatcher=inj)
        srv, port = serve_background(eng, port=0)
        try:
            url = (
                f"http://127.0.0.1:{port}/api/v1/query_range?query="
                f"{urllib.parse.quote(Q)}&start={S}&end={E}&step=60"
                "&allow_partial_results=true"
            )
            with urllib.request.urlopen(url, timeout=30) as r:
                payload = json.loads(r.read())
            assert payload["status"] == "success"
            assert payload["partial"] is True
            assert payload["warnings"][0]["shard"] == victim
            assert payload["data"]["result"]
            # metrics exposition counts the partial answer
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30
            ) as r:
                text = r.read().decode()
            assert "filodb_partial_results_total" in text
        finally:
            srv.shutdown()


# ---------------------------------------------------------------------------
# trace annotations (metrics.py spans x fault machinery)
# ---------------------------------------------------------------------------


class TestTraceAnnotations:
    def test_retries_annotate_dispatching_span(self):
        from filodb_tpu.metrics import span

        sleeps: list[float] = []
        policy = RetryPolicy(max_attempts=4, base_backoff_s=0.01, seed=7,
                             sleep=sleeps.append)
        child = FlakyRemoteExec("grpc://p:1", fail_times=2)
        ctx = make_ctx(retry_policy=policy, breakers=BreakerRegistry())
        with span("gather") as s:
            dispatch_child(child, ctx)
        assert s.tags["retries"]["grpc://p:1"] == 2
        # each ATTEMPT produced its own child span (3 = 2 failures + success)
        assert [c.name for c in s.children] == ["FlakyRemoteExec"] * 3

    def test_open_breaker_annotates_span(self):
        from filodb_tpu.metrics import span

        clock = FakeClock()
        breakers = BreakerRegistry(clock=clock, window=8, failure_rate=0.5,
                                   min_calls=4, cooldown_s=10.0)
        policy = RetryPolicy(max_attempts=1, seed=0, sleep=lambda s: None)
        ctx = make_ctx(retry_policy=policy, breakers=breakers)
        child = FlakyRemoteExec("grpc://annot:1", always_fail=True)
        for _ in range(4):
            with pytest.raises(InjectedFault):
                dispatch_child(child, ctx)
        with span("gather") as s:
            with pytest.raises(CircuitOpenError):
                dispatch_child(child, ctx)
        assert s.tags["breaker_open"] == ["grpc://annot:1"]
        # half-open probing is annotated as breaker state encountered
        clock.advance(10.0)
        child.always_fail = False
        with span("gather2") as s2:
            dispatch_child(child, ctx)
        assert s2.tags["breaker_state"]["grpc://annot:1"] == "half_open"

    def test_partial_drops_annotate_merge_node_span(self):
        """Chaos-injected partials appear as lost_children annotations on
        the merge node's span in the query's trace tree."""
        from filodb_tpu.metrics import trace_to_dict

        ms0, _ = make_engine()
        victim = next(sh.shard_num for sh in ms0.shards("prometheus")
                      if sh.num_partitions)
        inj = FaultInjector([FaultRule(target=f"shard={victim} ")], seed=1)
        _, eng = make_engine(dispatcher=inj)
        res = eng.query_range(Q, S, E, 60, allow_partial_results=True)
        assert res.partial is True

        def walk(d):
            yield d
            for c in d.get("children", ()):
                yield from walk(c)

        tree = trace_to_dict(res.trace)
        annotated = [
            sp for sp in walk(tree)
            if "lost_children" in sp.get("tags", {})
        ]
        assert len(annotated) == 1
        lost = annotated[0]["tags"]["lost_children"]
        assert lost == res.warnings
        assert lost[0]["shard"] == victim


# ---------------------------------------------------------------------------
# shard reassignment convergence
# ---------------------------------------------------------------------------


class TestReassignmentSettles:
    def test_repeated_ingestion_errors_settle_down_not_bounce(self):
        clock = FakeClock()
        mgr = ShardManager(4, shards_per_node=4, reassignment_damper_s=3600,
                           clock=clock)
        mgr.node_joined("a")
        mgr.node_joined("b")
        events = []
        mgr.mapper.subscribe(events.append)
        for _ in range(6):
            mgr.ingestion_error(0)
            clock.advance(1.0)
        # converged: DOWN, not oscillating between nodes
        assert mgr.mapper.status_of(0) == ShardStatus.DOWN
        assigns = [e for e in events
                   if e.shard == 0 and e.status == ShardStatus.ASSIGNED]
        assert len(assigns) == 1  # exactly one reassignment before the damper
        # damper expiry: the shard is recoverable again
        clock.advance(3600.0)
        assert mgr.ingestion_error(0) is True
        assert mgr.mapper.status_of(0) == ShardStatus.ASSIGNED
