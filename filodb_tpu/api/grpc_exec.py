"""gRPC cross-cluster exec: server + client exec nodes (reference analog:
grpc/.../query_service.proto service RemoteExec:1126-1134 and its
GrpcCommonUtils/PromQLGrpcServer — exec, execStreaming, executePlan).

Service stubs are hand-written over grpc generic handlers (grpc_tools is
not in the image); messages are protoc-generated (query_exec_pb2). Two
methods, both server-streaming (the reference's non-streaming `exec` is
subsumed — a unary result is a one-grid stream):

- ``Exec``        PromQL string + grid params -> StreamFrame stream
- ``ExecutePlan`` serialized LogicalPlan      -> StreamFrame stream

Cross-host semantics mirror the HTTP scatter path exactly: ``local_only``
pins the peer to its own shard slice (the X-FiloDB-Local twin), bearer
tokens ride call metadata, and errors travel in-band as the final frame so
clients re-raise typed QueryErrors.
"""

from __future__ import annotations

import logging
import os
import threading
from concurrent import futures

import grpc

from ..query.proto_plan import (
    PlanDecodeError,
    RemoteExecError,
    error_frame,
    frames_to_result,
    plan_to_proto,
    proto_to_plan,
    result_to_frames,
)
from . import query_exec_pb2 as pb

log = logging.getLogger("filodb_tpu.grpc")

SERVICE = "filodb_tpu.exec.RemoteExec"
_EXEC = f"/{SERVICE}/Exec"
_EXECUTE_PLAN = f"/{SERVICE}/ExecutePlan"


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------


class _RemoteExecServicer:
    def __init__(self, engine, local_engine=None, auth_token: str | None = None):
        self.engine = engine
        self.local_engine = local_engine
        self.auth_token = auth_token
        # shard-subset engines (replica routing): a caller may pin the call
        # to a subset of this node's shards via x-filodb-shards metadata —
        # engines are built lazily per distinct subset and cached
        self._subset_engines: dict = {}
        self._subset_lock = threading.Lock()

    # -- helpers ----------------------------------------------------------

    def _authorize(self, context) -> bool:
        if not self.auth_token:
            return True
        import hmac

        got = ""
        for k, v in context.invocation_metadata():
            if k == "authorization":
                got = v
        # constant-time compare, same as the HTTP edge (api/http.py)
        if hmac.compare_digest(got, f"Bearer {self.auth_token}"):
            return True
        context.abort(grpc.StatusCode.UNAUTHENTICATED, "bad or missing bearer token")
        return False  # unreached

    def _engine_for(self, params: "pb.QueryParams", context=None):
        base = self.engine
        is_local = False
        if params.local_only and self.local_engine is not None:
            base = self.local_engine
            is_local = True
        subset = self._shard_subset(context) if context is not None else None
        if subset is None:
            return base
        return self._subset_engine(base, is_local, subset)

    def _subset_engine(self, base, is_local: bool, subset: tuple):
        """Engine pinned to a subset of this node's shards (replica routing:
        the origin asks for exactly the shards this replica serves for it).
        Cached per distinct subset; peer fan-out and replica routing are
        stripped so the subset engine only reads local state."""
        key = (is_local, subset)
        with self._subset_lock:
            eng = self._subset_engines.get(key)
            if eng is not None:
                return eng
            import dataclasses

            from ..coordinator.planner import QueryEngine

            owned = set(base.memstore.shard_nums(base.dataset))
            shards = [s for s in subset if s in owned]
            params = dataclasses.replace(
                base.planner.params, peer_endpoints=(), replica_router=None,
            )
            eng = QueryEngine(base.memstore, base.dataset, params=params,
                              shard_nums=shards)
            self._subset_engines[key] = eng
            return eng

    @staticmethod
    def _shard_subset(context) -> tuple | None:
        """Sorted shard subset from x-filodb-shards metadata, or None."""
        for k, v in context.invocation_metadata():
            if k == SHARDS_MD_KEY:
                try:
                    return tuple(sorted(int(x) for x in v.split(",") if x))
                except ValueError:
                    return None
        return None

    @staticmethod
    def _allow_partial(context) -> bool | None:
        """Tri-state like the HTTP edge: absent metadata means the engine's
        configured default, not False."""
        for k, v in context.invocation_metadata():
            if k == ALLOW_PARTIAL_MD_KEY:
                return v == "1"
        return None

    @staticmethod
    def _stats_ext(context) -> bool:
        """Origin advertises StatsExt support via metadata; absent = older
        origin that would fail on the unknown frame type, so don't send."""
        for k, v in context.invocation_metadata():
            if k == STATS_EXT_MD_KEY:
                return v == "1"
        return False

    @staticmethod
    def _trace_parent(context) -> tuple[str | None, str | None]:
        """(trace_id, parent_span_id) from call metadata: the origin's span
        identity, so this peer's span tree joins the origin's trace and its
        slow-query entries share the origin's trace id."""
        trace_id = parent = None
        for k, v in context.invocation_metadata():
            if k == TRACE_ID_MD_KEY:
                trace_id = v
            elif k == PARENT_SPAN_MD_KEY:
                parent = v
        return trace_id, parent

    def _stream(self, run, context=None, stats_ext: bool = False):
        """Run ``run()`` -> QueryResult and stream frames; errors go in-band
        as the final frame (clients re-raise typed)."""
        import json as _json

        from ..coordinator.scheduler import QueryRejected
        from ..query.exec.transformers import QueryDeadlineExceeded, QueryError
        from ..query.promql import PromQLError
        from ..query.scheduler import AdmissionRejected

        try:
            res = run()
        except AdmissionRejected as e:
            # admission shed: typed in-band frame (clients re-raise the
            # local AdmissionRejected) + the HTTP Retry-After's gRPC
            # equivalent riding trailing call metadata
            if context is not None:
                context.set_trailing_metadata(
                    ((RETRY_AFTER_MD_KEY, f"{e.retry_after_s:.3f}"),)
                )
            yield error_frame("AdmissionRejected", _json.dumps(e.warning()))
            return
        except QueryRejected as e:
            yield error_frame("QueryRejected", str(e))
            return
        except QueryDeadlineExceeded as e:
            yield error_frame("DeadlineExceeded", str(e))
            return
        except PlanDecodeError as e:
            yield error_frame("PlanDecodeError", str(e))
            return
        except (QueryError, PromQLError) as e:
            yield error_frame("QueryError", str(e))
            return
        except Exception as e:  # noqa: BLE001
            log.exception("remote exec failed")
            yield error_frame("Internal", f"{type(e).__name__}: {e}")
            return
        # result-plane accounting parity with the HTTP edge: the gRPC leg
        # is already columnar (proto frames wrap the raw f32 grid bytes) —
        # time the frame encode and count wire bytes under format=grpc
        import time as _time

        from ..metrics import REGISTRY

        t_r = _time.perf_counter()
        nbytes = 0
        for frame in result_to_frames(res, stats_ext=stats_ext):
            nbytes += frame.ByteSize()
            yield frame
        REGISTRY.histogram("filodb_render_seconds", format="grpc").observe(
            _time.perf_counter() - t_r)
        REGISTRY.counter("filodb_response_bytes", format="grpc").inc(nbytes)

    # -- methods ----------------------------------------------------------

    def Exec(self, request: "pb.ExecRequest", context):
        self._authorize(context)
        eng = self._engine_for(request.params, context)
        p = request.params
        allow_partial = self._allow_partial(context)
        trace_id, parent_span = self._trace_parent(context)

        def run():
            if request.instant:
                return eng.query_instant(request.promql, p.end_ms / 1000.0,
                                         allow_partial_results=allow_partial,
                                         trace_id=trace_id,
                                         parent_span_id=parent_span)
            return eng.query_range(
                request.promql, p.start_ms / 1000.0, p.end_ms / 1000.0,
                (p.step_ms or 1000) / 1000.0,
                allow_partial_results=allow_partial,
                trace_id=trace_id, parent_span_id=parent_span,
            )

        yield from self._stream(run, context=context,
                                stats_ext=self._stats_ext(context))

    def ExecutePlan(self, request: "pb.ExecutePlanRequest", context):
        self._authorize(context)
        eng = self._engine_for(request.params, context)
        p = request.params
        allow_partial = self._allow_partial(context)
        trace_id, parent_span = self._trace_parent(context)

        def run():
            plan = proto_to_plan(request.plan)
            return eng.execute_plan(plan, deadline_s=p.deadline_s,
                                    max_series=p.max_series,
                                    allow_partial_results=allow_partial,
                                    trace_id=trace_id,
                                    parent_span_id=parent_span)

        yield from self._stream(run, context=context,
                                stats_ext=self._stats_ext(context))


def serve_grpc(engine, port: int = 0, auth_token: str | None = None,
               local_engine=None, max_workers: int = 8,
               host: str = "127.0.0.1"):
    """Start the RemoteExec gRPC server; returns (server, bound_port)."""
    servicer = _RemoteExecServicer(engine, local_engine, auth_token)
    handlers = {
        "Exec": grpc.unary_stream_rpc_method_handler(
            servicer.Exec,
            request_deserializer=pb.ExecRequest.FromString,
            response_serializer=pb.StreamFrame.SerializeToString,
        ),
        "ExecutePlan": grpc.unary_stream_rpc_method_handler(
            servicer.ExecutePlan,
            request_deserializer=pb.ExecutePlanRequest.FromString,
            response_serializer=pb.StreamFrame.SerializeToString,
        ),
    }
    server = grpc.server(
        futures.ThreadPoolExecutor(max_workers=max_workers,
                                   thread_name_prefix="filodb-grpc"),
        options=[("grpc.so_reuseport", 0)],
    )
    server.add_generic_rpc_handlers(
        (grpc.method_handlers_generic_handler(SERVICE, handlers),)
    )
    bound = server.add_insecure_port(f"{host}:{port}")
    if bound == 0:
        raise OSError(f"cannot bind gRPC port {port}")
    server.start()
    return server, bound


# ---------------------------------------------------------------------------
# client
# ---------------------------------------------------------------------------

_channels: dict[str, grpc.Channel] = {}
_channels_lock = threading.Lock()


def grpc_target(endpoint: str) -> str:
    """'grpc://host:port' or 'host:port' -> grpc channel target."""
    return endpoint[len("grpc://"):] if endpoint.startswith("grpc://") else endpoint


def _channel(endpoint: str) -> grpc.Channel:
    target = grpc_target(endpoint)
    with _channels_lock:
        ch = _channels.get(target)
        if ch is None:
            ch = grpc.insecure_channel(
                target,
                options=[
                    ("grpc.max_receive_message_length", 64 * 1024 * 1024),
                    ("grpc.max_send_message_length", 64 * 1024 * 1024),
                    # no retry filter: exactly one layer retries (below), and
                    # grpc-core 51's transparent retry of a stream the peer's
                    # GOAWAY refused (a server stopping) wedges the call when
                    # the re-attempt cannot connect — final status set, the
                    # pending recv_message never completed, so the response
                    # iterator outlives its timeout and ignores cancel().
                    # Without the filter that call ends UNAVAILABLE at once.
                    ("grpc.enable_retries", 0),
                ],
            )
            _channels[target] = ch
        return ch


# the flag rides call metadata (no proto change): peers answering a
# partial-tolerant origin degrade gracefully instead of failing the RPC
ALLOW_PARTIAL_MD_KEY = "x-filodb-allow-partial"

# trace propagation rides call metadata too: the origin's trace id and the
# dispatching span's id, so the peer's spans join the origin's trace (its
# tree returns in-band as a TraceTree frame and gets stitched)
TRACE_ID_MD_KEY = "x-filodb-trace-id"
PARENT_SPAN_MD_KEY = "x-filodb-parent-span"

# origin capability flag: "1" = the caller's frames_to_result understands
# the in-band StatsExt frame (kernel_ns + cache events); peers never send
# the frame unsolicited so older origins keep working mid-rolling-deploy
STATS_EXT_MD_KEY = "x-filodb-stats-ext"

# replica routing: the origin pins the call to a subset of the peer's
# shards (comma-joined ints) — the peer serves exactly those shards so a
# scatter leg re-routed to a sibling replica reads the same slice
SHARDS_MD_KEY = "x-filodb-shards"

# admission-control shed: the peer's Retry-After (seconds) rides trailing
# call metadata — the gRPC equivalent of the HTTP 429 Retry-After header
# (the typed rejection itself travels in-band as an AdmissionRejected frame)
RETRY_AFTER_MD_KEY = "x-filodb-retry-after"

# transient codes; DEADLINE_EXCEEDED is excluded — the budget is already
# burnt. Retry ownership: plan-scatter children (GrpcPlanRemoteExec) pass
# retries=0 and mark the error retryable so the dispatch layer
# (query/faults.py) owns the retry loop — breaker-aware, jittered, budgeted
# by the query deadline, tunable via config query.retry.*. Direct client
# helpers (exec_promql / remote_metadata) keep one transport-level retry
# instead; either way exactly ONE layer retries.
_RETRYABLE_CODES = (grpc.StatusCode.UNAVAILABLE, grpc.StatusCode.RESOURCE_EXHAUSTED)

# codes that are NOT peer-health evidence and must not open the endpoint's
# breaker: auth/arg/config problems are real answers from a live peer, and
# DEADLINE_EXCEEDED reflects the ORIGIN's (possibly nearly-spent) budget —
# a healthy peer given a 50ms window says nothing about the peer
_NOT_PEER_HEALTH_CODES = (
    grpc.StatusCode.UNAUTHENTICATED,
    grpc.StatusCode.PERMISSION_DENIED,
    grpc.StatusCode.INVALID_ARGUMENT,
    grpc.StatusCode.UNIMPLEMENTED,
    grpc.StatusCode.FAILED_PRECONDITION,
    grpc.StatusCode.NOT_FOUND,
    grpc.StatusCode.OUT_OF_RANGE,
    grpc.StatusCode.DEADLINE_EXCEEDED,
)


def _metadata(auth_token: str | None, allow_partial: bool | None = None,
              trace: tuple[str, str] | None = None, shards=None):
    """``allow_partial`` is tri-state: None omits the key (peer uses its own
    default); True/False send "1"/"0" so an origin's explicit choice —
    including strict mode — overrides the peer's configured default.
    ``trace`` is (trace_id, parent_span_id) of the dispatching span.
    ``shards`` pins the peer to a shard subset (replica routing)."""
    md = []
    if auth_token:
        md.append(("authorization", f"Bearer {auth_token}"))
    if allow_partial is not None:
        md.append((ALLOW_PARTIAL_MD_KEY, "1" if allow_partial else "0"))
    if trace is not None:
        md.append((TRACE_ID_MD_KEY, trace[0]))
        md.append((PARENT_SPAN_MD_KEY, trace[1]))
    if shards:
        md.append((SHARDS_MD_KEY, ",".join(str(int(s)) for s in shards)))
    # this client understands the StatsExt frame (proto_plan.STATS_EXT);
    # peers only send it when the origin advertises so
    md.append((STATS_EXT_MD_KEY, "1"))
    return tuple(md) or None


def _call_stream(endpoint: str, method: str, request, serializer, auth_token,
                 timeout_s: float | None, retries: int = 1,
                 allow_partial: bool | None = None,
                 trace: tuple[str, str] | None = None, shards=None):
    """unary_stream call with bounded UNAVAILABLE retries (mirrors the HTTP
    transport's retry discipline in planners.fetch_json). ``timeout_s`` is a
    TOTAL budget: retries and their per-attempt RPC deadlines all fit inside
    it. That a hung peer cannot stall past the caller's query deadline is
    kept one level up, by faults.call_with_retries, which bounds its own wait
    for this call; the one wedge seen here is cured in _channel's options."""
    import time as _t

    ch = _channel(endpoint)
    call = ch.unary_stream(
        method,
        request_serializer=serializer,
        response_deserializer=pb.StreamFrame.FromString,
    )
    deadline = None if timeout_s is None else _t.monotonic() + timeout_s
    md = _metadata(auth_token, allow_partial, trace, shards)
    attempt = 0
    while True:
        per_attempt = (
            None if deadline is None else max(deadline - _t.monotonic(), 0.001)
        )
        try:
            return frames_to_result(call(request, timeout=per_attempt, metadata=md))
        except grpc.RpcError as e:
            code = e.code() if hasattr(e, "code") else None
            backoff = 0.2 * (attempt + 1)
            if (
                code in _RETRYABLE_CODES
                and attempt < retries
                and (deadline is None or _t.monotonic() + backoff < deadline)
            ):
                attempt += 1
                _t.sleep(backoff)
                continue
            err = RemoteExecError(
                str(code), e.details() if hasattr(e, "details") else str(e)
            )
            # only when NO transport retry happened: the dispatch layer may
            # retry a transient code it knows was tried exactly once
            err.retryable = retries == 0 and code in _RETRYABLE_CODES
            err.endpoint_failure = code not in _NOT_PEER_HEALTH_CODES
            raise err from e


def exec_promql(endpoint: str, promql: str, start_ms: int, end_ms: int, step_ms: int,
                auth_token: str | None = None, local_only: bool = False,
                instant: bool = False, timeout_s: float | None = None,
                allow_partial: bool | None = None):
    req = pb.ExecRequest(
        promql=promql, instant=instant,
        params=pb.QueryParams(start_ms=start_ms, end_ms=end_ms, step_ms=step_ms,
                              local_only=local_only),
    )
    return _call_stream(endpoint, _EXEC, req, pb.ExecRequest.SerializeToString,
                        auth_token, timeout_s, allow_partial=allow_partial)


def exec_plan_remote(endpoint: str, logical_plan, auth_token: str | None = None,
                     local_only: bool = False, deadline_s: float = 0.0,
                     max_series: int = 0, timeout_s: float | None = None,
                     allow_partial: bool | None = None, transport_retries: int = 1,
                     trace: tuple[str, str] | None = None, shard_subset=None):
    req = pb.ExecutePlanRequest(
        plan=plan_to_proto(logical_plan),
        params=pb.QueryParams(local_only=local_only, deadline_s=deadline_s,
                              max_series=max_series),
    )
    return _call_stream(endpoint, _EXECUTE_PLAN, req,
                        pb.ExecutePlanRequest.SerializeToString, auth_token,
                        timeout_s, retries=transport_retries,
                        allow_partial=allow_partial, trace=trace,
                        shards=shard_subset)


from ..query.exec.plans import ExecPlan  # noqa: E402  (no cycle: query/ never imports api/)


class GrpcPlanRemoteExec(ExecPlan):
    """ExecPlan leaf executing a serialized LogicalPlan subtree on a peer
    over gRPC (reference executePlan handler of service RemoteExec)."""

    is_remote = True

    def __init__(self, endpoint: str, logical_plan, auth_token: str | None = None,
                 local_only: bool = False, timeout_s: float | None = None,
                 shard_subset=None, sibling_endpoints=()):
        super().__init__()
        self.endpoint = endpoint
        self.logical_plan = logical_plan
        # same env fallback as PromQlRemoteExec so token-protected federation
        # works over either transport
        self.auth_token = auth_token or os.environ.get("FILODB_REMOTE_TOKEN")
        self.local_only = local_only
        self.timeout_s = timeout_s
        # replica routing: pin the peer to exactly these shards, with the
        # sibling replicas the dispatch layer may fail over to
        self.shard_subset = tuple(shard_subset) if shard_subset else None
        self.sibling_endpoints = tuple(sibling_endpoints)

    def with_endpoint(self, endpoint: str) -> "GrpcPlanRemoteExec":
        """Clone for replica failover: same plan/subset/token on a sibling
        endpoint (the failover layer manages the candidate list)."""
        clone = GrpcPlanRemoteExec(
            endpoint, self.logical_plan, auth_token=self.auth_token,
            local_only=self.local_only, timeout_s=self.timeout_s,
            shard_subset=self.shard_subset,
        )
        clone.transformers = list(self.transformers)
        return clone

    def push_aggregate(self, wrapped_logical) -> None:
        """Aggregate pushdown rewrite: ship ``sum by(...)`` of the leaf
        instead of raw series (planner._push_peer_aggregate)."""
        self.logical_plan = wrapped_logical

    def args_str(self) -> str:
        s = f"endpoint={self.endpoint} plan={type(self.logical_plan).__name__}"
        if self.shard_subset:
            s += " shards=" + ",".join(str(x) for x in self.shard_subset)
        return s

    def do_execute(self, ctx):
        from ..metrics import current_span

        # budget with the REMAINING deadline, not the full deadline_s: by
        # the time this child dispatches (or re-dispatches on retry), part
        # of the query budget is already spent, and both the per-RPC timeout
        # and the peer's own deadline must fit in what's left
        remaining = ctx.remaining_deadline_s()
        # the active span here is this exec node's (ExecPlan.execute): its
        # identity rides call metadata so the peer's spans join our trace
        sp = current_span()
        return exec_plan_remote(
            self.endpoint, self.logical_plan, auth_token=self.auth_token,
            local_only=self.local_only, deadline_s=remaining,
            max_series=ctx.max_series,
            timeout_s=min(self.timeout_s, remaining) if self.timeout_s else remaining,
            allow_partial=getattr(ctx, "allow_partial_results", False),
            # the dispatch layer (faults.call_with_retries) owns this
            # child's retries: transient errors come back marked retryable
            transport_retries=0,
            trace=(sp.trace_id, sp.span_id) if sp is not None else None,
            shard_subset=self.shard_subset,
        )


def remote_metadata(endpoint: str, plan, auth_token: str | None = None,
                    timeout_s: float | None = 60.0):
    """Metadata scatter over gRPC: execute a metadata LogicalPlan on the
    peer (locally pinned) and return its ``metadata`` payload."""
    res = exec_plan_remote(endpoint, plan, auth_token=auth_token,
                           local_only=True, timeout_s=timeout_s)
    return res.metadata or []
