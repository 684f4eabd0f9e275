"""Prometheus-compatible HTTP API (reference L6:
http/.../PrometheusApiRoute.scala:43-130 — query_range:49, query:68,
labels:85, label-values:105; AdminRoutes health).

Stdlib ThreadingHTTPServer: the API edge is not the hot path (queries run on
device); zero extra dependencies.

Endpoints:
  GET/POST /api/v1/query_range?query&start&end&step
  GET/POST /api/v1/query?query&time
  GET      /api/v1/labels
  GET      /api/v1/label/<name>/values
  GET      /api/v1/series?match[]=...
  GET      /api/v1/metadata (types from live schemas), /api/v1/status/buildinfo
  GET      /api/v1/query_exemplars (OpenMetrics exemplars ingested via /ingest/prom)
  GET      /api/v1/rules  (recording + alerting rule groups, Prometheus
           shape; ?type=alert|record, ?state=inactive|pending|firing)
  GET      /api/v1/alerts (active alerts from the alerting plane,
           obs/alerting.py; ?state= filter)
  POST     /api/v1/rules/record, /api/v1/rules/alert (runtime rules)
  GET      /admin/health
  POST     /ingest  (JSON lines of {metric, tags, ts_ms, value} — test/dev
           ingest transport; production path is the gateway)
"""

from __future__ import annotations

import json
import re
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import jax
import numpy as np

from ..coordinator.planner import QueryEngine
from ..core.filters import ColumnFilter
from ..metrics import REGISTRY, span
from ..query.exec.transformers import QueryError
from ..query.promql import PromQLError, Parser as PromParser
from ..query.proto_plan import RemoteExecError
from . import promjson as J


def _parse_time(s: str, default: float | None = None) -> float:
    if s is None:
        if default is None:
            raise ValueError("missing time parameter")
        return default
    try:
        return float(s)
    except ValueError:
        # RFC3339
        import datetime as dt

        return dt.datetime.fromisoformat(s.replace("Z", "+00:00")).timestamp()


def _parse_step(s: str) -> float:
    if s is None:
        return 15.0
    try:
        return float(s)
    except ValueError:
        from ..query.promql import parse_duration_ms

        return parse_duration_ms(s) / 1000.0


def _matchers_from(expr: str) -> list[ColumnFilter]:
    """Parse a series matcher like {job="x"} or metric{a="b"}."""
    node = PromParser(expr).selector()
    from ..core.schemas import METRIC_TAG

    filters = list(node.matchers)
    if node.metric:
        filters.append(ColumnFilter(METRIC_TAG, "=", node.metric))
    return [
        ColumnFilter(METRIC_TAG, f.op, f.value) if f.column == "__name__" else f
        for f in filters
    ]


class PromApiHandler(BaseHTTPRequestHandler):
    engine: QueryEngine = None  # set by server factory
    # optional zero-arg flush hook (FiloServer.flush_now) behind POST
    # /admin/flush (reference AdminRoutes; ops + crash-recovery tests)
    flush_hook = None
    members_hook = None
    join_hook = None
    # engine answering from this process's shards only (no peer scatter);
    # selected by the X-FiloDB-Local header peers set — the multi-host
    # anti-recursion guard. None = same as engine. TRUST BOUNDARY: any
    # caller presenting the header (after passing bearer auth, when
    # configured) gets the shard-local view on the unbounded local engine —
    # multi-host deployments should set http_auth_token so only peers (who
    # share the token) can reach it, and keep the port off the public edge.
    local_engine: QueryEngine = None
    # additional per-dataset engines reachable via ?dataset=<name> — the
    # `_system` self-telemetry dataset rides this so the server's own
    # metrics are queryable through the standard (fused) query API
    dataset_engines: dict = {}
    # standing-query engine (filodb_tpu/standing/): registration +
    # recording-rules APIs, SSE push subscriptions, /debug/standing.
    # None = endpoints 404 (engine disabled or embedded without one).
    standing = None
    # second StandingEngine bound to the _system engine: maintains the
    # query observatory's SLO burn-rate recording rules (obs/slo.py);
    # its rules merge into /api/v1/rules. None = no SLO maintainer.
    standing_system = None
    # RollupManager (downsample/rollup.py): the sketch-rollup summary
    # tier's admin surface, /debug/rollups. None = endpoint 404s.
    rollups = None
    # AlertingEngine (obs/alerting.py): alerting rule groups + active
    # alerts; serves /api/v1/alerts and merges its groups into
    # /api/v1/rules. None = alerts list empty, no alerting groups.
    alerting = None
    auth_token: str | None = None  # optional bearer auth (server factory)
    # zero-arg profiler report hook; wired by the server ONLY when the
    # profiler config block enables it (/debug/profile gate)
    profiler_hook = None
    # zero-arg cluster snapshot hook (ShardManager.snapshot or
    # ReplicationPlane.snapshot): shard -> replica table with statuses, lag
    # watermarks, damper state, recent reassignments (/debug/cluster).
    # None = endpoint 404s (single-node deployment without a shard plane).
    cluster_hook = None
    protocol_version = "HTTP/1.1"
    GZIP_MIN_BYTES = 1024
    STREAM_MIN_SAMPLES = 200_000  # above this, query_range streams chunked
    # series rows per device->host block on the streaming path (the
    # D2H/encode overlap granularity; 0 = pull whole grids upfront).
    # Config key result_plane.stream_block_rows.
    STREAM_BLOCK_ROWS = 512
    # peer columnar edge: honor "Accept: application/vnd.filodb.arrow.v1"
    # on query_range with Arrow IPC bodies (config result_plane.peer_exchange
    # = json disables, forcing decimal JSON on every hop)
    ARROW_EDGE = True

    def _engine_for_request(self, params: dict | None = None) -> QueryEngine:
        if self.local_engine is not None and self.headers.get("X-FiloDB-Local"):
            return self.local_engine
        if params is not None:
            # handlers pass their parsed params so a POSTed form body's
            # dataset= routes too (the body is consumable only once)
            ds = (params.get("dataset") or [None])[0]
        else:
            qs = urllib.parse.parse_qs(urllib.parse.urlparse(self.path).query)
            ds = (qs.get("dataset") or [None])[0]
        if ds:
            eng = (self.dataset_engines or {}).get(ds)
            if eng is not None:
                return eng
            if ds != getattr(self.engine, "dataset", None):
                # a typo must be a 400, never silently the default dataset
                raise ValueError(f"unknown dataset {ds!r}")
        return self.engine

    # -- plumbing ---------------------------------------------------------

    def log_message(self, fmt, *args):  # quiet by default
        pass

    @staticmethod
    def _observe_render(fmt: str, render_s: float, nbytes: int,
                        stalls: int = 0) -> None:
        """Result-plane encode accounting: filodb_render_seconds{format},
        filodb_response_bytes_total{format}, and (streaming only)
        filodb_render_stream_stalls_total — encoder waits on a D2H block
        the double-buffer failed to hide."""
        REGISTRY.histogram("filodb_render_seconds", format=fmt).observe(render_s)
        REGISTRY.counter("filodb_response_bytes", format=fmt).inc(nbytes)
        if stalls:
            REGISTRY.counter("filodb_render_stream_stalls").inc(stalls)

    @staticmethod
    def _observe_write(render_span) -> None:
        """``filodb_render_write_seconds``: the socket write(s) inside one
        query's ``render`` span — what is left of ``render`` is encoding."""
        for c in render_span.children:
            if c.name == "render:write":
                REGISTRY.histogram("filodb_render_write_seconds").observe(
                    c.seconds)

    @staticmethod
    def _pull_grids(res) -> float:
        """Pull every result grid to the host HERE, timed, instead of
        implicitly inside the encoder: the ``transfer`` phase, returned in
        seconds. First the wait for the device to finish the program
        (``transfer:ready``, ``filodb_transfer_ready_seconds``), then the
        copy back. Not an added sync: the conversion blocked on the same
        event one call later."""
        with span("transfer") as sp:
            with span("transfer:ready") as ready:
                jax.block_until_ready([(g.values, g.hist) for g in res.grids])
            with span("transfer:copy"):
                for g in res.grids:
                    g.values = np.asarray(g.values)
                    if g.hist is not None:
                        g.hist = np.asarray(g.hist)
        REGISTRY.histogram("filodb_transfer_ready_seconds").observe(
            ready.seconds)
        return sp.seconds

    def _peer_accepts_arrow(self) -> bool:
        """Version negotiation for the node-to-node columnar hop: only a
        peer that explicitly lists the Arrow media type in Accept gets IPC
        frames; everyone else (browsers, Grafana, older FiloDB builds) gets
        JSON. Requires pyarrow locally — an arrow-less install quietly
        answers JSON, which the requesting peer equally accepts."""
        if not self.ARROW_EDGE:
            return False
        accept = self.headers.get("Accept") or ""
        if "application/vnd.filodb.arrow" not in accept:
            return False
        try:
            from . import arrow_edge  # noqa: F401 (pyarrow gate)
        except Exception:
            return False
        return True

    @staticmethod
    def _count_response(code: int) -> None:
        """Per-status response accounting — the availability-SLO feed
        (obs/slo.py): ``filodb_http_responses_total{code,class}``. Class
        ``shed`` (429 admission sheds) is deliberate load management and
        is excluded from BOTH sides of the availability ratio; ``5xx`` is
        the error budget's numerator."""
        klass = ("shed" if code == 429 else "5xx" if code >= 500
                 else "4xx" if code >= 400 else "2xx")
        REGISTRY.counter("filodb_http_responses", code=str(code),
                         **{"class": klass}).inc()

    def _send(self, code: int, payload: dict, headers: dict | None = None):
        """Returns the UNCOMPRESSED body byte count — the query
        observatory records it as the result size, which must measure the
        query, not the client's Accept-Encoding."""
        return self._send_body(code, json.dumps(payload).encode(), headers)

    def _send_body(self, code: int, body: bytes, headers: dict | None = None,
                   content_type: str = "application/json"):
        """Pre-encoded-body twin of _send (same gzip/accounting contract) —
        the buffered matrix path sends stream_matrix's joined chunks through
        here so buffered and streamed bodies are byte-identical."""
        raw_len = len(body)
        self._count_response(code)
        # transparent gzip for big results (remote execs request it)
        gzipped = (
            len(body) >= self.GZIP_MIN_BYTES
            and "gzip" in (self.headers.get("Accept-Encoding") or "")
        )
        if gzipped:
            import gzip

            body = gzip.compress(body, compresslevel=1)
        # status line, headers and body onto the socket: the part of a
        # query's ``render`` that is not encoding (_observe_write)
        with span("render:write"):
            self.send_response(code)
            self.send_header("Content-Type", content_type)
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            if gzipped:
                self.send_header("Content-Encoding", "gzip")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        return raw_len

    def _send_chunked(self, code: int, chunks):
        """Stream an iterable of byte chunks with chunked transfer encoding
        (HTTP/1.1 keep-alive safe); memory stays bounded by one chunk.
        Returns total bytes streamed.

        A producer error after the 200 status line cannot become a real
        error response any more — without care the client would see a
        truncated 200 that json-parses as nothing. Instead the stream ends
        with a newline-delimited error envelope (valid JSON on its own
        line — machine-detectable by any client that notices the body
        doesn't parse) and a CLEAN chunked terminator, and the abort is
        counted under filodb_http_responses_total{class="stream_abort"}
        (the availability SLO's 5xx-equivalent for streamed bodies). A
        transport error (client gone) just stops the stream."""
        self._count_response(code)
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()
        total = 0
        try:
            for chunk in chunks:
                if chunk:
                    self.wfile.write(f"{len(chunk):X}\r\n".encode() + chunk + b"\r\n")
                    total += len(chunk)
        except (BrokenPipeError, ConnectionResetError):
            raise  # client is gone; nothing to mark
        except Exception as e:  # noqa: BLE001 — producer died mid-stream
            marker = (b'\n{"status":"error","errorType":"stream_aborted",'
                      + b'"error":' + json.dumps(f"{type(e).__name__}: {e}").encode()
                      + b"}\n")
            self.wfile.write(f"{len(marker):X}\r\n".encode() + marker + b"\r\n")
            total += len(marker)
            REGISTRY.counter("filodb_http_responses", code=str(code),
                             **{"class": "stream_abort"}).inc()
        self.wfile.write(b"0\r\n\r\n")
        return total

    def _read_body(self) -> str:
        length = int(self.headers.get("Content-Length") or 0)
        return self.rfile.read(length).decode() if length else ""

    def _params(self) -> dict:
        parsed = urllib.parse.urlparse(self.path)
        qs = urllib.parse.parse_qs(parsed.query)
        if self.command == "POST":
            body = self._read_body()
            ctype = self.headers.get("Content-Type", "")
            # urllib clients default to the form content-type even for raw
            # payloads; only parse as a form when it actually looks like one
            if "urlencoded" in ctype and "=" in body and "\n" not in body:
                for k, v in urllib.parse.parse_qs(body).items():
                    qs.setdefault(k, v)
            elif body:
                qs["__body__"] = [body]
        return {k: v for k, v in qs.items()}

    def _q(self, params, name, default=None):
        v = params.get(name)
        return v[0] if v else default

    def _allow_partial(self, params) -> bool | None:
        """Tri-state: None = engine default, else the request's choice."""
        v = self._q(params, "allow_partial_results")
        if v is None:
            return None
        return v.lower() in ("1", "true", "yes")

    def _trace_requested(self, params) -> bool:
        """``?trace=true`` / ``?explain=analyze``: return the annotated span
        tree (per-node durations, QueryStats, retries/breaker/partial
        annotations) alongside the result."""
        v = self._q(params, "trace")
        if v is not None and v.lower() in ("1", "true", "yes"):
            return True
        return (self._q(params, "explain") or "").lower() == "analyze"

    def _trace_parent(self) -> tuple[str | None, str | None]:
        """Upstream trace linkage headers (a scattering origin's span
        identity) — this node's spans join that trace."""
        from ..metrics import TraceContext

        return (
            self.headers.get(TraceContext.TRACE_ID_HEADER),
            self.headers.get(TraceContext.PARENT_SPAN_HEADER),
        )

    # -- routing ----------------------------------------------------------

    def do_GET(self):
        self._route()

    def do_POST(self):
        self._route()

    # the routes whose whole handler wall is clocked, entry to return:
    # ``http:<route>`` spans, filodb_http_request_seconds{route}
    TIMED_ROUTES = {"/api/v1/query_range": "query_range",
                    "/api/v1/query": "query"}

    def _route(self):
        path = urllib.parse.urlparse(self.path).path
        route = self.TIMED_ROUTES.get(path)
        if route is None:
            return self._dispatch(path)
        with span(f"http:{route}") as sp:
            self._dispatch(path)
        REGISTRY.histogram(
            "filodb_http_request_seconds", route=route).observe(sp.seconds)

    def _dispatch(self, path: str):
        if self.auth_token and path != "/admin/health":
            import hmac

            got = self.headers.get("Authorization") or ""
            if not hmac.compare_digest(got, f"Bearer {self.auth_token}"):
                # drain the body first: this handler speaks HTTP/1.1
                # keep-alive, and leftover body bytes would desync the
                # connection's next request
                length = int(self.headers.get("Content-Length") or 0)
                while length > 0:
                    chunk = self.rfile.read(min(length, 65536))
                    if not chunk:
                        break
                    length -= len(chunk)
                return self._send(401, J.error("unauthorized", "missing or bad bearer token"))
        try:
            if path == "/api/v1/query_range":
                return self._query_range()
            if path == "/api/v1/query":
                return self._query()
            if path == "/api/v1/labels":
                return self._labels()
            m = re.fullmatch(r"/api/v1/label/([^/]+)/values", path)
            if m:
                return self._label_values(m.group(1))
            if path == "/api/v1/series":
                return self._series()
            if path == "/api/v1/metadata":
                return self._send(
                    200,
                    J.success(self.engine.memstore.metric_metadata(self.engine.dataset)),
                )
            if path == "/api/v1/status/buildinfo":
                from .. import __version__

                return self._send(200, J.success({"version": __version__, "application": "filodb-tpu"}))
            if path == "/admin/health":
                return self._send(200, {
                    "status": "healthy",
                    "shards": len(self.engine.memstore.shards(self.engine.dataset)),
                    **device_facts(),
                })
            if path == "/__members":
                # cluster membership contract (reference akka-bootstrapper's
                # /__members endpoint; coordinator/bootstrap.py). POST with
                # {"url": ...} announces the caller (one-RTT join): we learn
                # them, they get the member list back.
                if self.members_hook is None:
                    return self._send(404, J.error("not_found", "no bootstrapper attached"))
                if self.command == "POST":
                    try:
                        body = json.loads(self._read_body() or b"{}")
                    except ValueError:
                        return self._send(400, J.error("bad_data", "invalid JSON body"))
                    url = body.get("url")
                    if url and self.join_hook is not None:
                        self.join_hook(str(url), node_id=body.get("id"))
                return self._send(200, J.success(self.members_hook()))
            if path == "/admin/flush" and self.command == "POST":
                if self.flush_hook is None:
                    return self._send(404, J.error("not_found", "no flusher attached"))
                self._read_body()  # drain: keep-alive connections desync otherwise
                res = self.flush_hook()
                return self._send(200, J.success({
                    "chunks_written": res.chunks_written,
                    "partkeys_written": res.partkeys_written,
                }))
            if path == "/metrics":
                return self._metrics()
            if path == "/debug/slow_queries":
                from ..metrics import SLOW_QUERY_LOG

                return self._send(200, J.success(SLOW_QUERY_LOG.entries()))
            if path == "/debug/querylog":
                return self._querylog()
            if path == "/api/v1/query_profile":
                return self._query_profile()
            if path == "/debug/resources":
                return self._resources()
            if path == "/debug/scheduler":
                return self._scheduler()
            if path == "/debug/cluster":
                return self._cluster()
            if path == "/debug/kernels":
                return self._kernels()
            if path == "/debug/costmodel":
                return self._costmodel()
            if path == "/debug/superblocks":
                return self._superblocks()
            if path == "/debug/index":
                return self._index_debug()
            if path == "/debug/profile":
                return self._profile()
            if path == "/api/v1/cardinality":
                return self._cardinality()
            if path == "/ingest":
                return self._ingest()
            if path == "/ingest/prom":
                return self._ingest_prom()
            if path == "/ingest/influx":
                return self._ingest_influx()
            if path == "/api/v1/write":
                return self._remote_write()
            if path == "/api/v1/read":
                return self._remote_read()
            if path == "/api/v1/query_exemplars":
                return self._query_exemplars()
            if path == "/api/v1/standing/register" and self.command == "POST":
                return self._standing_register()
            if path == "/api/v1/standing/unregister" and self.command == "POST":
                return self._standing_unregister()
            if path == "/api/v1/standing/subscribe":
                return self._standing_subscribe()
            if path == "/api/v1/standing":
                if self.standing is None:
                    return self._send(404, J.error("not_found", "standing engine disabled"))
                return self._send(200, J.success(self.standing.registry.snapshot()))
            if path == "/api/v1/rules/record" and self.command == "POST":
                return self._rules_record()
            if path == "/api/v1/rules/alert" and self.command == "POST":
                return self._rules_alert()
            if path == "/debug/standing":
                if self.standing is None:
                    return self._send(404, J.error("not_found", "standing engine disabled"))
                return self._send(200, J.success(self.standing.snapshot()))
            if path == "/debug/rollups":
                if self.rollups is None:
                    return self._send(404, J.error("not_found", "rollup tier disabled"))
                return self._send(200, J.success(self.rollups.snapshot()))
            if path == "/api/v1/rules":
                return self._rules()
            if path == "/api/v1/alerts":
                return self._alerts()
            if path == "/api/v1/status/flags" or path == "/api/v1/status/config":
                return self._send(200, J.success({}))
            self._send(404, J.error("not_found", f"unknown path {path}"))
        except (PromQLError, QueryError, ValueError, RemoteExecError) as e:
            import math

            from ..coordinator.planners import RemoteFetchError
            from ..coordinator.scheduler import QueryRejected
            from ..query.exec.transformers import QueryDeadlineExceeded
            from ..query.faults import CircuitOpenError
            from ..query.scheduler import AdmissionRejected

            if isinstance(e, AdmissionRejected):
                # admission control shed: 429 + Retry-After (the overload
                # contract, distinct from 503 pool saturation — the client
                # should back off for a KNOWN interval, not fail over) plus
                # the structured warning in the error envelope
                payload = J.error("throttled", str(e))
                payload["warnings"] = [e.warning()]
                self._send(429, payload, headers={
                    "Retry-After": str(max(
                        1, math.ceil(e.retry_after_s)
                    )),
                })
            elif isinstance(e, (QueryRejected, CircuitOpenError, RemoteFetchError,
                                RemoteExecError)):
                # overload / open breaker / peer transport outage (either
                # transport): availability conditions, not bad queries
                # (Prometheus: 503)
                self._send(503, J.error("unavailable", str(e)))
            elif isinstance(e, QueryDeadlineExceeded):
                self._send(503, J.error("timeout", str(e)))
            else:
                self._send(400, J.error("bad_data", str(e)))
        except Exception as e:  # noqa: BLE001 — the API edge must not die
            self._send(500, J.error("internal", f"{type(e).__name__}: {e}"))

    # -- endpoints --------------------------------------------------------

    def _query_range(self):
        p = self._params()
        query = self._q(p, "query")
        if not query:
            return self._send(400, J.error("bad_data", "missing query"))
        start = _parse_time(self._q(p, "start"))
        end = _parse_time(self._q(p, "end"))
        step = _parse_step(self._q(p, "step"))
        if step <= 0:
            return self._send(
                400, J.error("bad_data", "zero or negative query resolution step")
            )
        if end < start:
            return self._send(400, J.error("bad_data", "end timestamp before start"))
        trace_on = self._trace_requested(p)
        trace_id, parent_span = self._trace_parent()
        engine = self._engine_for_request(p)
        res = None
        served_standing = False
        if (self.standing is not None and engine is self.engine
                and not trace_on):
            # a registered standing query already holds this result's
            # matrix as retained partials — splice + render instead of
            # re-executing (ROADMAP leftover: only SSE subscribers rode
            # them before). Trace requests bypass: the retained state has
            # no span tree to annotate.
            res = self.standing.serve_range(query, start, end, step)
            served_standing = res is not None
        if res is None:
            res = engine.query_range(
                query, start, end, step,
                allow_partial_results=self._allow_partial(p),
                trace_id=trace_id, parent_span_id=parent_span,
            )
        from ..metrics import trace_to_dict
        from ..obs.querylog import QUERY_LOG

        # the query-observatory record this execution published (None for
        # remote-child legs); the edge folds in its serving phases below
        record = getattr(res, "query_log", None)
        trace = trace_to_dict(res.trace) if trace_on and res.trace is not None else None
        warnings = res.warnings or None
        render_format = "json-" + J.active_render_format()
        if res.result_type == "scalar":
            # range query over a scalar: render as matrix of the scalar
            sc = res.scalar
            data = {
                "resultType": "matrix",
                "result": [
                    {
                        "metric": {},
                        "values": [
                            [t / 1000.0, J._fmt(v)]
                            for t, v in zip(
                                sc.start_ms + np.arange(sc.num_steps) * sc.step_ms, sc.values
                            )
                        ],
                    }
                ]
                if sc is not None
                else [],
            }
            if trace is not None:
                data["trace"] = trace
            with span("render") as sp_r:
                nbytes = self._send(200, J.success(data, warnings=warnings,
                                                   partial=res.partial))
            self._observe_write(sp_r)
            if record is not None:
                QUERY_LOG.finish_serving(record, 0.0, sp_r.seconds,
                                         body_bytes=nbytes, code=200,
                                         render_format=render_format)
            return
        stats = {
            "seriesScanned": res.stats.series_scanned,
            "samplesScanned": res.stats.samples_scanned,
            "cpuNanos": res.stats.cpu_ns,
            "bytesStaged": res.stats.bytes_staged,
            # resource attribution (doc/observability.md): device dispatch
            # seconds and staging/superblock cache events for THIS query
            "kernelSeconds": round(res.stats.kernel_ns / 1e9, 9),
            "cacheHits": res.stats.cache_hits,
            "cacheMisses": res.stats.cache_misses,
            "cacheExtends": res.stats.cache_extends,
        }
        if served_standing:
            stats["servedFrom"] = "standing"
        # peer edge: a FiloDB peer advertises Arrow via Accept and gets the
        # grids as columnar IPC frames — floats cross bit-exact, no decimal
        # render here and no parse there. Browsers/old peers never send the
        # media type and fall through to JSON: the user edge renders decimal
        # JSON exactly once, at the outermost hop.
        if self._peer_accepts_arrow():
            from . import arrow_edge as AE

            transfer_s = self._pull_grids(res)
            with span("render") as sp_r:
                body = AE.result_to_ipc(res, trace=trace)
                nbytes = self._send_body(200, body,
                                         content_type=AE.ARROW_CONTENT_TYPE)
            render_s = sp_r.seconds
            self._observe_write(sp_r)
            self._observe_render("arrow", render_s, nbytes)
            if record is not None:
                QUERY_LOG.finish_serving(record, transfer_s, render_s,
                                         body_bytes=nbytes, code=200,
                                         render_format="arrow")
            return
        # large results stream chunked: memory stays bounded instead of
        # holding matrix + full JSON string (reference executeStreaming,
        # ExecPlan.scala:146); small ones keep the gzip-capable buffered
        # path — built from the SAME stream_matrix fragments, so streamed
        # and buffered bodies are byte-identical
        n_samples = sum(g.n_series * g.num_steps for g in res.grids)
        if res.raw is not None:
            n_samples += sum(len(t) for _, t, _ in res.raw)
        if n_samples >= self.STREAM_MIN_SAMPLES:
            # streaming path: grid values stay on device; stream_matrix
            # pulls them in STREAM_BLOCK_ROWS-series blocks through a
            # double-buffered prefetch thread, so the first body bytes
            # leave before the full D2H completes and transfer overlaps
            # encode. render phase = send wall minus the encoder's waits
            # on unfetched blocks (those waits ARE the transfer phase
            # leaking through the overlap — counted as stream stalls).
            phases: dict = {}
            with span("render") as sp_r:
                nbytes = self._send_chunked(
                    200, J.stream_matrix(
                        res, stats, warnings=warnings, trace=trace,
                        partial=res.partial,
                        block_rows=self.STREAM_BLOCK_ROWS or None,
                        phases=phases)
                )
            transfer_s = phases.get("transfer", 0.0)
            render_s = max(sp_r.seconds - phases.get("stall_s", 0.0), 0.0)
            self._observe_render(render_format, render_s, nbytes,
                                 stalls=phases.get("stalls", 0))
            if record is not None:
                QUERY_LOG.finish_serving(record, transfer_s, render_s,
                                         body_bytes=nbytes, code=200,
                                         render_format=render_format)
            return
        # buffered path: the transfer vs render decomposition the
        # result-plane phase plane needs (_pull_grids)
        transfer_s = self._pull_grids(res)
        with span("render") as sp_r:
            body = b"".join(J.stream_matrix(res, stats, warnings=warnings,
                                            trace=trace, partial=res.partial))
            nbytes = self._send_body(200, body)
        render_s = sp_r.seconds
        self._observe_write(sp_r)
        self._observe_render(render_format, render_s, nbytes)
        if record is not None:
            QUERY_LOG.finish_serving(record, transfer_s, render_s,
                                     body_bytes=nbytes, code=200,
                                     render_format=render_format)
        return

    def _query(self):
        p = self._params()
        query = self._q(p, "query")
        if not query:
            return self._send(400, J.error("bad_data", "missing query"))
        t = _parse_time(self._q(p, "time"), default=time.time())
        trace_on = self._trace_requested(p)
        trace_id, parent_span = self._trace_parent()
        res = self._engine_for_request(p).query_instant(
            query, t, allow_partial_results=self._allow_partial(p),
            trace_id=trace_id, parent_span_id=parent_span,
        )
        from ..obs.querylog import QUERY_LOG

        record = getattr(res, "query_log", None)
        transfer_s = self._pull_grids(res)
        warnings = res.warnings or None
        with span("render") as sp_r:
            if res.result_type == "scalar":
                data = J.render_scalar(res, t)
            elif res.raw is not None:
                data = J.render_matrix(res)
            else:
                data = J.render_vector(res, t)
            if trace_on and res.trace is not None:
                from ..metrics import trace_to_dict

                data["trace"] = trace_to_dict(res.trace)
            nbytes = self._send(200, J.success(data, warnings=warnings,
                                               partial=res.partial))
        self._observe_write(sp_r)
        if record is not None:
            QUERY_LOG.finish_serving(record, transfer_s, sp_r.seconds,
                                     body_bytes=nbytes, code=200)
        return

    def _labels(self):
        p = self._params()
        start = _parse_time(self._q(p, "start"), 0.0)
        end = _parse_time(self._q(p, "end"), time.time() + 1e9)
        limit = self._q(p, "limit")
        match = p.get("match[]", [])
        filters = _matchers_from(match[0]) if match else []
        names = self._engine_for_request(p).label_names(
            filters, int(start * 1000), int(end * 1000)
        )
        names = ["__name__" if n == "_metric_" else n for n in names]
        if limit:
            names = names[: int(limit)]
        return self._send(200, J.success(names))

    def _label_values(self, label: str):
        p = self._params()
        if label == "__name__":
            label = "_metric_"
        start = _parse_time(self._q(p, "start"), 0.0)
        end = _parse_time(self._q(p, "end"), time.time() + 1e9)
        match = p.get("match[]", [])
        limit = self._q(p, "limit")
        filters = _matchers_from(match[0]) if match else []
        vals = self._engine_for_request(p).label_values(
            filters, label, int(start * 1000), int(end * 1000),
            limit=int(limit) if limit else None,
        )
        return self._send(200, J.success(vals))

    def _series(self):
        p = self._params()
        start = _parse_time(self._q(p, "start"), 0.0)
        end = _parse_time(self._q(p, "end"), time.time() + 1e9)
        out = []
        for expr in p.get("match[]", []):
            filters = _matchers_from(expr)
            for tags in self._engine_for_request(p).series(
                filters, int(start * 1000), int(end * 1000), limit=10000
            ):
                out.append(J._labels_out(dict(tags)))
        return self._send(200, J.success(out))

    def _metrics(self):
        """Prometheus exposition of internal metrics. Per-shard stats are a
        scrape-time collector registered by make_server (reference
        TimeSeriesShardStats gauges + Kamon reporters) — one exposition
        path, with proper label escaping, for everything. Content-type
        negotiation: an Accept header naming application/openmetrics-text
        gets the OpenMetrics 1.0 rendering (HELP/TYPE metadata, trace-id
        exemplars on latency buckets, # EOF terminator)."""
        openmetrics = "application/openmetrics-text" in (
            self.headers.get("Accept") or ""
        )
        body = REGISTRY.expose(openmetrics=openmetrics).encode()
        ctype = (
            "application/openmetrics-text; version=1.0.0; charset=utf-8"
            if openmetrics else "text/plain; version=0.0.4"
        )
        self.send_response(200)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _kernels(self):
        """Kernel & compile observatory (doc/observability.md "Kernel &
        compile observatory"): the per-executable table — compiles,
        dispatches, device p50/p99, executable bytes, compile-cache
        provenance — plus recompile-storm annotations (each naming the
        unstable key dimension) and registered-wrapper cache sizes.
        ``?limit=`` caps the executable table."""
        from ..obs.kernels import KERNELS

        p = self._params()
        limit = self._q(p, "limit")
        return self._send(
            200, J.success(KERNELS.snapshot(int(limit) if limit else None))
        )

    def _costmodel(self):
        """Work cost model (doc/perf.md "Cost-model scheduling"): the
        per-fingerprint predicted vs realized device-second table (EWMA
        cost, unit cost, last error ratio), per-family priors, and the
        prediction-source mix (fingerprint / family / prior). ``?limit=``
        caps the fingerprint table (newest first)."""
        from ..query.costmodel import COST_MODEL

        p = self._params()
        limit = self._q(p, "limit")
        return self._send(
            200,
            J.success(COST_MODEL.snapshot(int(limit) if limit else 64)),
        )

    def _resources(self):
        """Resource-ledger introspection: per-kind device bytes, the
        ledger-vs-cold-walk drift check, and per-tenant query-resource
        totals (doc/observability.md "Resource accounting")."""
        from ..ledger import LEDGER
        from ..metering import tenant_query_snapshot

        verify = LEDGER.verify()
        return self._send(200, J.success({
            "device_bytes": LEDGER.balances(),
            "kinds": verify["kinds"],
            "accounts": verify["accounts"],
            "tenants": tenant_query_snapshot(),
        }))

    def _scheduler(self):
        """Query-dispatch-scheduler introspection (doc/observability.md):
        the micro-batcher's queue depth / open batch windows / cumulative
        batching outcomes, and the admission controller's per-tenant token
        balances, in-flight counts and shed totals — alongside
        /debug/resources like the rest of the debug surface."""
        params = self.engine.planner.params
        sched = getattr(params, "dispatch_scheduler", None)
        adm = getattr(params, "admission", None)
        return self._send(200, J.success({
            "batch": sched.snapshot() if sched is not None else None,
            "admission": adm.snapshot() if adm is not None else None,
        }))

    def _cluster(self):
        """Replicated-shard-plane introspection (doc/operations.md): the
        shard -> replica table (per-replica status + lag watermark), node
        liveness, damper state and the recent-reassignment ring — how an
        operator confirms a failover routed and a rebalance cut over."""
        if self.cluster_hook is None:
            return self._send(404, J.error("no cluster plane configured"))
        return self._send(200, J.success(self.cluster_hook()))

    def _superblocks(self):
        """Superblock-cache introspection: one entry per cached superblock
        (key, true device bytes, age, hits, last maintenance outcome from
        the filodb_superblock_maintenance_total taxonomy; mesh-sharded
        entries additionally carry their sharding spec + per-device byte
        split, rolled up in device_bytes)."""
        cache = getattr(self.engine.memstore, "_superblock_cache", None)
        entries = cache.snapshot() if cache is not None else []
        device_bytes: dict = {}
        for e in entries:
            for dev, b in (e.get("device_bytes") or {}).items():
                device_bytes[dev] = device_bytes.get(dev, 0) + int(b)
        return self._send(200, J.success({
            "entries": entries,
            "count": len(entries),
            "bytes": sum(e["bytes"] for e in entries),
            # per-device roll-up over SHARDED entries (mesh path); also
            # published as filodb_device_bytes{kind="superblock",device}
            "device_bytes": device_bytes,
            # THIS cache's ledger balance (the kind-wide filodb_device_bytes
            # gauge sums every live cache in the process)
            "ledger_bytes": cache.ledger.bytes if cache is not None else 0,
        }))

    def _index_debug(self):
        """Part-key index introspection (doc/perf.md "Vectorized part-key
        index"): per-label cardinality + postings footprint per shard, the
        rolled-up label dictionary."""
        from ..memstore.cardinality import label_top_values

        p = self._params()
        drill_label = self._q(p, "label")
        ds = self.engine.dataset
        shards = []
        labels_rollup: dict[str, dict] = {}
        drill: dict[str, int] = {}
        total_bytes = 0
        for sh in self.engine.memstore.shards(ds):
            st = sh.index_stats()
            if drill_label:
                for rec in label_top_values(sh.index, drill_label, k=50):
                    drill[rec["value"]] = (
                        drill.get(rec["value"], 0) + rec["series"]
                    )
            for k, rec in st.get("labels", {}).items():
                slot = labels_rollup.setdefault(
                    k, {"values": 0, "postings_bytes": 0}
                )
                slot["values"] += rec["values"]
                slot["postings_bytes"] += rec["postings_bytes"]
            total_bytes += st.get("postings_bytes", 0)
            shards.append({
                "shard": sh.shard_num,
                "part_keys": st.get("num_part_keys", 0),
                "postings_bytes": st.get("postings_bytes", 0),
                "dictionary_size": st.get("dictionary_size", 0),
                "lookups": st.get("lookups", 0),
            })
        return self._send(200, J.success({
            "dataset": ds,
            "shards": shards,
            # per-label cardinality summed over shards (a label's true
            # cross-shard value cardinality is <= this sum; exact dedup
            # would require merging dictionaries)
            "labels": dict(sorted(
                labels_rollup.items(),
                key=lambda kv: -kv[1]["postings_bytes"],
            )),
            "postings_bytes": total_bytes,
            # ?label= drill-down: top values of that label by series count
            "label_values": (sorted(
                ({"value": v, "series": n} for v, n in drill.items()),
                key=lambda r: (-r["series"], r["value"]),
            )[:50] if drill_label else None),
        }))

    def _profile(self):
        """Sampling-profiler report (config-gated: the server wires
        profiler_hook only when filodb.profiler is enabled)."""
        if self.profiler_hook is None:
            return self._send(404, J.error("not_found", "profiler not enabled"))
        body = str(self.profiler_hook()).encode()
        self.send_response(200)
        self.send_header("Content-Type", "text/plain")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _cardinality(self):
        """Per-shard-key-prefix cardinality scan (reference TsCardinalities
        metadata plan + /api/v1/metering endpoints)."""
        p = self._params()
        prefix = [x for x in (self._q(p, "prefix", "") or "").split(",") if x]
        depth = int(self._q(p, "depth", str(len(prefix) + 1)))
        out = self._engine_for_request(p).ts_cardinalities(prefix, depth)
        return self._send(200, J.success(out))

    def _querylog(self):
        """Query-observatory ring (doc/observability.md "Query
        observatory"): exemplar-level per-query cost records, newest
        first; ``?limit=`` caps the page, ``?fingerprint=`` keeps only one
        normalized query shape, ``?path=`` one execution path (e.g.
        ``standing:delta`` — the alerting plane's evaluations filter out
        this way). Filters apply BEFORE the limit, so a page of a rare
        fingerprint/path is still a full page."""
        from ..obs.querylog import QUERY_LOG

        p = self._params()
        limit = self._q(p, "limit")
        fingerprint = self._q(p, "fingerprint")
        path_f = self._q(p, "path")
        entries = QUERY_LOG.entries(None)
        if fingerprint:
            entries = [e for e in entries
                       if e.get("fingerprint") == fingerprint]
        if path_f:
            entries = [e for e in entries if e.get("path") == path_f]
        if limit:
            entries = entries[: int(limit)]
        return self._send(200, J.success(entries))

    def _query_profile(self):
        """One query's full cost record by id (= its trace id) — the
        target of slow-query-log ``profile`` links and OpenMetrics
        exemplars."""
        from ..obs.querylog import QUERY_LOG

        p = self._params()
        qid = self._q(p, "id")
        if not qid:
            return self._send(400, J.error("bad_data", "missing id"))
        e = QUERY_LOG.get(str(qid))
        if e is None:
            return self._send(
                404, J.error("not_found", f"no query-log record {qid!r}")
            )
        return self._send(200, J.success(e))

    def _query_exemplars(self):
        """Prometheus /api/v1/query_exemplars: exemplars of the series a
        selector matches, within [start, end]."""
        from ..query.logical import leaf_raw_series
        from ..query.promql import query_to_logical_plan

        p = self._params()
        query = self._q(p, "query")
        if not query:
            return self._send(400, J.error("bad_data", "missing query"))
        start = _parse_time(self._q(p, "start") or "0")
        end = _parse_time(self._q(p, "end") or str(2**31))
        plan = query_to_logical_plan(query, end)
        leaves = leaf_raw_series(plan)
        out = []
        for leaf in leaves:
            out.extend(
                self.engine.memstore.query_exemplars(
                    self.engine.dataset, leaf.filters, int(start * 1000), int(end * 1000)
                )
            )
        return self._send(200, J.success(out))

    # -- standing queries / recording rules (filodb_tpu/standing/) ---------

    def _json_body(self, params) -> dict:
        """POSTed JSON body (handlers pass their parsed params — the body
        is consumable only once and _params() stashes it)."""
        body = self._q(params, "__body__") or ""
        if not body:
            return {}
        try:
            out = json.loads(body)
        except ValueError as e:
            raise ValueError(f"invalid JSON body: {e}") from None
        if not isinstance(out, dict):
            raise ValueError("JSON body must be an object")
        return out

    def _standing_register(self):
        """Register a standing query: ``{"query", "step", "range"?}`` (step
        and range in seconds or PromQL durations). Returns its id, mode
        (delta|full) and grid shape."""
        if self.standing is None:
            return self._send(404, J.error("not_found", "standing engine disabled"))
        p = self._params()
        body = self._json_body(p)
        query = body.get("query") or self._q(p, "query")
        if not query:
            return self._send(400, J.error("bad_data", "missing query"))
        step_ms = int(_parse_step(str(body.get("step") or
                                      self._q(p, "step") or 15)) * 1000)
        rng = body.get("range") or self._q(p, "range")
        span_ms = int(_parse_step(str(rng)) * 1000) if rng else None
        sq = self.standing.register(query, step_ms, span_ms=span_ms)
        return self._send(200, J.success(sq.snapshot()))

    def _standing_unregister(self):
        if self.standing is None:
            return self._send(404, J.error("not_found", "standing engine disabled"))
        p = self._params()
        qid = self._json_body(p).get("id") or self._q(p, "id")
        if not qid:
            return self._send(400, J.error("bad_data", "missing id"))
        sq = self.standing.unregister(str(qid))
        if sq is None:
            return self._send(404, J.error("not_found", f"no standing query {qid}"))
        return self._send(200, J.success({"unregistered": qid}))

    def _rules_record(self):
        """Register a recording rule: ``{"name", "expr", "interval",
        "range"?}`` — a standing query whose newest closed steps write back
        into the memstore as the series ``name{group labels}``."""
        if self.standing is None:
            return self._send(404, J.error("not_found", "standing engine disabled"))
        p = self._params()
        body = self._json_body(p)
        name = body.get("name") or self._q(p, "name")
        expr = body.get("expr") or self._q(p, "expr")
        if not name or not expr:
            return self._send(400, J.error("bad_data", "missing name or expr"))
        if not re.fullmatch(r"[a-zA-Z_:][a-zA-Z0-9_:]*", str(name)):
            return self._send(400, J.error("bad_data", f"invalid rule name {name!r}"))
        interval_s = _parse_step(str(body.get("interval") or
                                     self._q(p, "interval") or 15))
        step_ms = int(interval_s * 1000)
        rng = body.get("range") or self._q(p, "range")
        span_ms = int(_parse_step(str(rng)) * 1000) if rng else 4 * step_ms
        sq = self.standing.register(
            str(expr), step_ms, span_ms=span_ms, source="rule",
            rule_name=str(name), eval_interval_s=float(interval_s),
        )
        return self._send(200, J.success(sq.snapshot()))

    # -- alerting plane (obs/alerting.py) ----------------------------------

    def _rules_alert(self):
        """Register an alerting rule at runtime: the rule-file spec as
        JSON (``{"alert", "expr", "for"?, "keep_firing_for"?, "labels"?,
        "annotations"?}``) plus optional ``"group"`` (default ``api``) and
        ``"interval"``."""
        if self.alerting is None:
            return self._send(
                404, J.error("not_found", "alerting plane disabled")
            )
        from ..obs.alerting import RuleFileError

        p = self._params()
        body = self._json_body(p)
        group = str(body.pop("group", "") or "api")
        interval = body.pop("interval", None)
        interval_s = (_parse_step(str(interval))
                      if interval is not None else None)
        try:
            rule = self.alerting.add_rule(body, group=group,
                                          interval_s=interval_s)
        except RuleFileError as e:
            return self._send(400, J.error("bad_data", str(e)))
        return self._send(200, J.success({
            "group": group,
            "name": rule.name,
            "query": rule.expr,
            "duration": rule.for_s,
            "keepFiringFor": rule.keep_firing_for_s,
            "type": "alerting",
        }))

    def _rules(self):
        """Prometheus ``GET /api/v1/rules``: the standing engines'
        runtime-registered recording rules (synthetic ``standing`` group)
        plus the alerting plane's loaded groups — top-level ``groups``,
        rule ``type`` recording|alerting, camelCase eval fields.
        ``?type=alert|record`` and ``?state=`` filter rules (a state
        filter keeps only alerting rules — recording rules have no
        state); groups a filter empties are dropped."""
        from ..obs.alerting import ALERT_STATES

        p = self._params()
        rtype = self._q(p, "type")
        state = self._q(p, "state")
        if rtype and rtype not in ("alert", "record"):
            return self._send(400, J.error(
                "bad_data", "type must be alert|record"
            ))
        if state and state not in ALERT_STATES:
            return self._send(400, J.error(
                "bad_data",
                f"state must be one of {'|'.join(ALERT_STATES)}",
            ))
        groups: list = []
        # names the alerting plane owns: its file/API-registered recording
        # rules also live in the standing registry, so the synthetic
        # `standing` group must not double-list them
        owned = (self.alerting.rule_names()
                 if self.alerting is not None else set())
        for eng in (self.standing, self.standing_system):
            if eng is not None:
                for g in eng.rules_payload()["groups"]:
                    g["rules"] = [r for r in g["rules"]
                                  if r["name"] not in owned]
                    groups.append(g)
        if self.alerting is not None:
            groups.extend(self.alerting.rules_payload()["groups"])
        want = {"alert": "alerting", "record": "recording"}.get(rtype)
        out = []
        for g in groups:
            rules = g["rules"]
            if want:
                rules = [r for r in rules if r["type"] == want]
            if state:
                rules = [r for r in rules if r.get("state") == state]
            if not rules:
                continue
            out.append({**g, "rules": rules})
        return self._send(200, J.success({"groups": out}))

    def _alerts(self):
        """Prometheus ``GET /api/v1/alerts``: active (pending|firing)
        alerts with expanded annotations; ``?state=`` filters."""
        from ..obs.alerting import ALERT_STATES

        p = self._params()
        state = self._q(p, "state")
        if state and state not in ALERT_STATES:
            return self._send(400, J.error(
                "bad_data",
                f"state must be one of {'|'.join(ALERT_STATES)}",
            ))
        if self.alerting is None:
            return self._send(200, J.success({"alerts": []}))
        return self._send(200, J.success(
            self.alerting.alerts_payload(state)
        ))

    def _standing_subscribe(self):
        """SSE push stream for one standing query: the initial frame is
        the current materialization, then every refresh's payload — the
        SAME rendered bytes every subscriber receives (one materialization,
        N sockets). Subscriber counts are bounded per query
        (``standing.max_subscribers`` → 429 + Retry-After past it)."""
        from ..standing.hub import CLOSED, SubscriptionLimit

        if self.standing is None:
            return self._send(404, J.error("not_found", "standing engine disabled"))
        p = self._params()
        qid = self._q(p, "id")
        sq = self.standing.get(str(qid)) if qid else None
        if sq is None:
            return self._send(404, J.error("not_found", f"no standing query {qid}"))
        try:
            sub = self.standing.hub.subscribe(sq.qid)
        except SubscriptionLimit as e:
            return self._send(429, J.error("throttled", str(e)),
                              headers={"Retry-After": "5"})
        if self.standing.get(sq.qid) is None:
            # unregister raced between get() and subscribe(): hub.close
            # already ran, so this fresh subscription would never receive
            # a frame (and would resurrect a dead hub entry)
            self.standing.hub.unsubscribe(sub)
            return self._send(404, J.error("not_found",
                                           f"no standing query {qid}"))
        import queue as _queue

        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Cache-Control", "no-cache")
        self.send_header("Connection", "close")
        self.close_connection = True
        self.end_headers()
        try:
            first = sq.last_payload
            if first:
                self.wfile.write(b"data: " + first + b"\n\n")
                self.wfile.flush()
            while not sub.closed:
                try:
                    item = sub.get(timeout=15.0)
                except _queue.Empty:
                    self.wfile.write(b": keep-alive\n\n")
                    self.wfile.flush()
                    continue
                if item is CLOSED:
                    break
                self.wfile.write(b"data: " + item + b"\n\n")
                self.wfile.flush()
        except (BrokenPipeError, ConnectionError, OSError):
            pass  # client went away — the normal end of an SSE stream
        finally:
            self.standing.hub.unsubscribe(sub)

    def _ingest_prom(self):
        """Prometheus text exposition ingest (push-gateway style; counters
        route to the prom-counter schema via # TYPE comments)."""
        import time as _time

        from ..gateway.parsers import prom_text_to_batches_and_exemplars

        length = int(self.headers.get("Content-Length") or 0)
        text = self.rfile.read(length).decode() if length else ""
        n = 0
        now_ms = int(_time.time() * 1000)
        batches, exs = prom_text_to_batches_and_exemplars(text, now_ms)
        for batch in batches:
            n += self.engine.memstore.ingest_routed(self.engine.dataset, batch, spread=3)
        # OpenMetrics exemplars ride alongside their samples
        if exs:
            self.engine.memstore.add_exemplars(self.engine.dataset, 3, exs)
        return self._send(200, J.success({"ingested": n}))

    def _ingest_influx(self):
        """Influx line protocol over HTTP (the TCP gateway's HTTP twin)."""
        import time as _time

        from ..gateway.parsers import influx_to_batch

        length = int(self.headers.get("Content-Length") or 0)
        text = self.rfile.read(length).decode() if length else ""
        batch = influx_to_batch(text, int(_time.time() * 1000))
        n = self.engine.memstore.ingest_routed(self.engine.dataset, batch, spread=3)
        return self._send(200, J.success({"ingested": n}))

    def _remote_write(self):
        """Prometheus remote write receiver (snappy+protobuf)."""
        from .remote_storage import parse_write_request

        # binary body: bypass _params (which decodes as text)
        length = int(self.headers.get("Content-Length") or 0)
        raw = self.rfile.read(length) if length else b""
        n = 0
        for batch in parse_write_request(raw):
            n += self.engine.memstore.ingest_routed(self.engine.dataset, batch, spread=3)
        self._count_response(204)
        self.send_response(204)
        self.send_header("Content-Length", "0")
        self.end_headers()

    def _remote_read(self):
        from .remote_storage import handle_read_request

        length = int(self.headers.get("Content-Length") or 0)
        raw = self.rfile.read(length) if length else b""
        out = handle_read_request(raw, self.engine.memstore, self.engine.dataset)
        self.send_response(200)
        self.send_header("Content-Type", "application/x-protobuf")
        self.send_header("Content-Encoding", "snappy")
        self.send_header("Content-Length", str(len(out)))
        self.end_headers()
        self.wfile.write(out)

    def _ingest(self):
        from ..core.records import gauge_batch

        p = self._params()
        body = self._q(p, "__body__", "")
        n = 0
        samples = []
        for line in body.splitlines():
            if not line.strip():
                continue
            rec = json.loads(line)
            samples.append((rec.get("tags", {}), int(rec["ts_ms"]), float(rec["value"])))
            n += 1
        if samples:
            by_metric: dict[str, list] = {}
            for tags, ts, v in samples:
                by_metric.setdefault(tags.get("__name__", tags.get("_metric_", "unknown")), []).append((tags, ts, v))
            for metric, recs in by_metric.items():
                batch = gauge_batch(metric, recs)
                self.engine.memstore.ingest_routed(self.engine.dataset, batch, spread=3)
        return self._send(200, J.success({"ingested": n}))


def register_shard_stats_collector(engine: QueryEngine) -> None:
    """Scrape-time per-shard gauges in the shared Registry (reference
    TimeSeriesShardStats): refreshed on every /metrics render. Keyed per
    ENGINE (not just dataset) so two embedded nodes sharing a dataset name
    — the federation/bootstrap test topology — each keep refreshing their
    own shard slice; gauges are disjoint by shard label. The closure holds
    the memstore WEAKLY and self-unregisters once the store dies — the
    process-global registry must not pin a shut-down server's shards
    (staged chunks included) for the process lifetime."""
    import weakref

    ds = engine.dataset
    key = f"shard_stats:{ds}:{id(engine.memstore)}"
    memstore_ref = weakref.ref(engine.memstore)

    def collect():
        memstore = memstore_ref()
        if memstore is None:
            REGISTRY.unregister_collector(key)
            return
        for sh in memstore.shards(ds):
            ist = sh.index_stats()
            for name, v in (
                ("filodb_shard_partitions", sh.num_partitions),
                ("filodb_shard_rows_ingested", sh.stats.rows_ingested),
                ("filodb_shard_rows_skipped", sh.stats.rows_skipped),
                ("filodb_shard_partitions_evicted", sh.stats.partitions_evicted),
                ("filodb_shard_chunks_flushed", sh.stats.chunks_flushed),
                ("filodb_index_postings_bytes", ist.get("postings_bytes", 0)),
                ("filodb_index_dictionary_size", ist.get("dictionary_size", 0)),
            ):
                REGISTRY.gauge(name, dataset=ds, shard=str(sh.shard_num)).set(float(v))

    REGISTRY.register_collector(key, collect)


def make_server(engine: QueryEngine, host: str = "127.0.0.1", port: int = 9090,
                auth_token: str | None = None,
                local_engine: QueryEngine | None = None,
                flush_hook=None,
                dataset_engines: dict | None = None,
                standing=None, standing_system=None,
                rollups=None, alerting=None,
                cluster=None, result_plane: dict | None = None) -> ThreadingHTTPServer:
    # membership hooks (members_hook/join_hook) are wired as class attrs on
    # the returned server's RequestHandlerClass AFTER start — the registry
    # needs the bound port for its self URL (server.py seed bootstrap)
    register_shard_stats_collector(engine)
    attrs = {"engine": engine, "auth_token": auth_token, "local_engine": local_engine,
             "dataset_engines": dict(dataset_engines or {}),
             "standing": standing, "standing_system": standing_system,
             "rollups": rollups, "alerting": alerting,
             "cluster_hook": staticmethod(cluster) if cluster else None,
             "flush_hook": staticmethod(flush_hook) if flush_hook else None}
    if result_plane:  # config [result_plane] -> serving-edge knobs
        attrs["STREAM_MIN_SAMPLES"] = int(
            result_plane.get("stream_min_samples", PromApiHandler.STREAM_MIN_SAMPLES))
        attrs["STREAM_BLOCK_ROWS"] = int(
            result_plane.get("stream_block_rows", PromApiHandler.STREAM_BLOCK_ROWS))
        attrs["ARROW_EDGE"] = result_plane.get("peer_exchange", "arrow") == "arrow"
    handler = type("BoundHandler", (PromApiHandler,), attrs)
    return ThreadingHTTPServer((host, port), handler)


def device_facts() -> dict:
    """Where this process's kernels run, as jax reports it (the server's
    start-up log line and GET /admin/health)."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def serve_background(engine: QueryEngine, host: str = "127.0.0.1", port: int = 0,
                     auth_token: str | None = None,
                     local_engine: QueryEngine | None = None,
                     flush_hook=None, dataset_engines: dict | None = None,
                     standing=None, standing_system=None, rollups=None,
                     alerting=None, cluster=None, result_plane: dict | None = None):
    """Start the API server on a thread; returns (server, actual_port)."""
    srv = make_server(engine, host, port, auth_token, local_engine, flush_hook,
                      dataset_engines, standing, standing_system, rollups,
                      alerting, cluster, result_plane)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    return srv, srv.server_address[1]
