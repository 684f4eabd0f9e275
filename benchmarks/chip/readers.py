"""The reader kinds a per-layer metric's file can name. A metric is a data
file ``layer_metrics/<name>.json`` — ``layer``, ``unit``, ``moves`` and a
``source`` ``{"reader": <kind>, ...arguments}`` — and a reader is a function
here of ``(ctx, **arguments)`` that returns the number, or None when it
finds nothing to read (the harness then leaves the metric out of the line;
it never writes 0 for a share of a roofline). A later PR adds a reader as a
new module ``benchmarks/chip/<kind>.py`` with a function ``read``.

``ctx`` is what one traced run took. Of the window with the profiler OFF
(the profiler slows the host: a staging request read 1.25 s under it against
0.7 s): ``segments``, pairs of /metrics readings (``parse_metrics``) before
and after each stretch, and ``latencies_ms`` of the requests timed in them
by the child's clock. Of the traced sub-window: ``trace`` (from
``trace_reduce``), ``sub_requests``, ``sub_query_bytes`` (the mean of
``roofline.min_bytes`` over its requests), ``window_s``, ``querylog`` (its
records) and ``device_kind``.
"""

from __future__ import annotations

import importlib
import statistics

from benchmarks.chip import roofline

SERVING_PHASES = ("transfer", "render")  # booked by the HTTP edge, per caller


def find(kind: str):
    if kind in KINDS:
        return KINDS[kind]
    return importlib.import_module(f"benchmarks.chip.{kind}").read


def parse_metrics(text: str) -> dict:
    """{(name, frozenset(labels)): value} of a /metrics body's sample lines."""
    out = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        head, _, val = line.rpartition(" ")
        name, _, rest = head.partition("{")
        labels = frozenset(
            tuple(kv.split("=", 1)) for kv in rest.rstrip("}").split(",") if kv
        ) if rest else frozenset()
        try:
            out[(name, labels)] = float(val)
        except ValueError:
            pass
    return out


def total(counters: dict, name: str, **labels) -> float:
    want = {(k, f'"{v}"') for k, v in labels.items()}
    return sum(v for (n, ls), v in counters.items() if n == name and want <= ls)


def _delta(ctx, name: str, **labels) -> float:
    return sum(total(after, name, **labels) - total(before, name, **labels)
               for before, after in ctx["segments"])


def _phase_ms(ctx, phases) -> float:
    """Host wall the program booked to ``phases``, per timed request, ms."""
    return 1e3 * sum(_delta(ctx, "filodb_query_phase_seconds_sum", phase=p)
                     for p in phases) / len(ctx["latencies_ms"])


def phase_mean(ctx, phases):
    if not ctx["latencies_ms"]:
        return None
    return _phase_ms(ctx, phases)


def counter_delta(ctx, counter, **labels):
    return _delta(ctx, counter, **labels)


def counter_per_request(ctx, counter, scale=1.0, **labels):
    """``scale`` x the counter's increase per timed request."""
    if not ctx["latencies_ms"]:
        return None
    return scale * _delta(ctx, counter, **labels) / len(ctx["latencies_ms"])


def client_clock(ctx, stat):
    """The child's clock, sent -> body read: ``p95``, or
    ``mean_less_program``: what is left of the mean wall once the program's
    own clocks are taken off — socket, http.server, thread hand-over. The
    program's clocks are each CALLER's wall inside the engine
    (``filodb_query_latency_seconds``: a request that rides on another's
    execution waits there, and that wait is the scheduler's, not the
    edge's) and the serving phases after it, ``transfer`` and ``render``."""
    lat = ctx["latencies_ms"]
    if not lat:
        return None
    if stat == "p95":
        return percentile(lat, 95)
    if stat == "mean_less_program":
        inside = 1e3 * _delta(ctx, "filodb_query_latency_seconds_sum") / len(lat)
        return statistics.fmean(lat) - inside - _phase_ms(ctx, SERVING_PHASES)
    raise ValueError(f"client_clock: unknown stat {stat!r}")


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of all the values."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, -(-len(s) * q // 100) - 1))] if s else float("nan")


def querylog_share(ctx, stat):
    """Share (%) of the sub-window's querylog records whose ``stats[stat]``
    is above 0."""
    recs = ctx.get("querylog")
    if not recs:
        return None
    return 100.0 * sum(1 for r in recs if r["stats"].get(stat, 0) > 0) / len(recs)


def trace(ctx, field):
    """Of the traced sub-window. One event on the device's modules line is
    one execution of a compiled program; identical concurrent queries are
    coalesced into one, so ``kernel_ms`` and the roofline are taken per
    program run, not per request (4 identical clients read 3.8 requests a
    program on the chip: per request the roofline would read 3.8x too high)."""
    t = ctx.get("trace")
    if not t or not t["busy_s"] > 0 or not ctx.get("sub_requests"):
        return None
    if field == "idle_pct":
        return 100.0 * (1.0 - t["busy_s"] / ctx["window_s"])
    if not t["n_programs"]:
        return None
    if field == "kernel_ms":
        return 1e3 * t["busy_s"] / t["n_programs"]
    if field == "roofline_pct":
        return 100.0 * t["n_programs"] * roofline.min_seconds(
            ctx["sub_query_bytes"], ctx["device_kind"]) / t["busy_s"]
    raise ValueError(f"trace: unknown field {field!r}")


KINDS = {f.__name__: f for f in (phase_mean, counter_delta, counter_per_request,
                                 client_clock, querylog_share, trace)}
