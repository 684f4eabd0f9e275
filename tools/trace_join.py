#!/usr/bin/env python3
"""Join the program's spans with the device's ops on one profiler trace.

    python3 tools/trace_join.py <file.xplane.pb> [--platform tpu|cpu] [--top 10]

Every ``metrics.span`` holds a ``jax.profiler.TraceAnnotation`` of its own
name carrying its ``trace_id``, so a trace taken around live queries (the
benchmark's ``--trace 1 --keep-trace FILE``, or ``jax.profiler.start_trace``
in any process that serves them: doc/observability.md "Taking a trace") has
the span tree on the host plane, on the clock of the device plane. This tool
reads one such file by hand — it is not part of the benchmark and edits
nothing there — and prints, inside the ``bench_window`` marker when there is
one (else over the whole trace):

1. the stats of one event of the device's ops line, its metadata's string
   stats included (what the backend calls the op-name differs: ``tf_op`` on
   a v5e; look before trusting section 4). An executable that came out of
   the persistent compile cache carries the metadata of the commit that
   compiled it: trace with ``JAX_COMPILATION_CACHE_DIR`` set to an empty
   directory to see this tree's own scopes;
2. per program span name: events, their wall, and the device's busy time
   under them;
3. the longest idle gaps of the device, each with the program spans that
   cover it (share of the gap's length per span name, and the share covered
   by any program span at all);
4. device seconds per ``jax.named_scope`` stage (``range_fn``,
   ``group_reduce``, ``epilogue``), and the stage of each of the top ops.

``--json`` prints the same as one JSON object instead.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip.trace_reduce import (  # noqa: E402
    OPS_LINES, find_marker, short, union_seconds,
)

# the fused programs' stages, and the wide sum inside group_reduce (the
# innermost name wins, so its ops are cut out of the reduce's)
SCOPES = ("range_fn", "group_reduce", "epilogue", "wide_sum")


def clipped(intervals, clip):
    lo, hi = clip
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def merged(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def overlap_seconds(intervals, busy_merged) -> float:
    """Length of (union of ``intervals``) within (union ``busy_merged``)."""
    total = 0
    for a, b in merged(intervals):
        for c, d in busy_merged:
            if d <= a:
                continue
            if c >= b:
                break
            total += min(b, d) - max(a, c)
    return total / 1e9


def idle_gaps(busy_merged, clip):
    lo, hi = clip
    out, end = [], lo
    for a, b in busy_merged:
        if a > end:
            out.append((end, min(a, hi)))
        end = max(end, b)
    if end < hi:
        out.append((end, hi))
    return sorted((g for g in out if g[1] > g[0]), key=lambda g: g[0] - g[1])


def _fields(buf: bytes):
    """(field number, wire type, value) of one protobuf message: varints as
    ints, length-delimited fields as bytes. The XSpace schema is stable and
    small; jax's ``ProfileData`` does not hand out an event's METADATA stats
    (on a TPU the op-name lives there, as ``tf_op``), so they are read here
    from the file's own bytes, with nothing but the wire format."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                break
        num, wt = key >> 3, key & 7
        if wt == 0:
            v = shift = 0
            while True:
                b = buf[i]
                i += 1
                v |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
            yield num, wt, v
        elif wt == 2:
            ln = shift = 0
            while True:
                b = buf[i]
                i += 1
                ln |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
            yield num, wt, buf[i:i + ln]
            i += ln
        elif wt in (1, 5):
            size = 8 if wt == 1 else 4
            yield num, wt, buf[i:i + size]
            i += size
        else:
            raise ValueError(f"wire type {wt}")


def metadata_strings(path: str, plane_prefix: str) -> dict:
    """{event name: {stat name: string value}} of the event metadata of the
    planes whose name starts with ``plane_prefix`` (XSpace.planes=1;
    XPlane.name=2, event_metadata=4, stat_metadata=5; XEventMetadata.name=2,
    stats=5; XStat.metadata_id=1, str_value=5, ref_value=7)."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for num, _wt, plane in _fields(space):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for n, _w, v in _fields(plane):
            if n == 2:
                name = v.decode("utf-8", "replace")
            elif n in (4, 5):
                entry = dict((k, x) for k, _w2, x in _fields(v))
                if n == 4:
                    events.append(entry.get(2, b""))
                else:
                    md = dict((k, x) for k, _w2, x in _fields(entry.get(2, b"")))
                    stat_names[entry.get(1, 0)] = md.get(2, b"").decode()
        if not name.startswith(plane_prefix):
            continue
        for ev in events:
            ev_name, stats = "", {}
            for n, _w, v in _fields(ev):
                if n == 2:
                    ev_name = v.decode("utf-8", "replace")
                elif n == 5:
                    st = dict((k, x) for k, _w2, x in _fields(v))
                    if 5 in st:
                        stats[stat_names.get(st.get(1))] = st[5].decode(
                            "utf-8", "replace")
                    elif 7 in st:
                        stats[stat_names.get(st.get(1))] = stat_names.get(st[7], "")
            if stats:
                out.setdefault(ev_name, {}).update(stats)
    return out


_SCOPE = re.compile(r"(?:^|/)(%s)(?=/|$)" % "|".join(SCOPES))


def scope_of(stats: dict) -> str:
    """The innermost named stage in any string stat of an op event (the
    op-name metadata, e.g. ``jit(f)/epilogue/jit(g)/group_reduce/reduce``)."""
    best, at = "(no stage)", -1
    for v in stats.values():
        if isinstance(v, str):
            for m in _SCOPE.finditer(v):
                if m.start() > at:
                    best, at = m.group(1), m.start()
    return best


def join(planes, platform: str, top: int, metadata: dict | None = None) -> dict:
    """``metadata``: ``metadata_strings`` of the device plane, merged into
    each op event's own stats by the event's name."""
    planes = list(planes)
    plane_prefix, line_prefix = OPS_LINES[platform]
    ops, spans = [], []  # (name, start, end, stats) / (name, start, end)
    device = sorted((p for p in planes if p.name.startswith(plane_prefix)),
                    key=lambda p: p.name)[:1]
    for plane in device:
        for line in plane.lines:
            if line.name.startswith(line_prefix):
                ops += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         {**dict(e.stats), **(metadata or {}).get(e.name, {})})
                        for e in line.events]
    if platform == "cpu":  # the stand-in line also holds the runtime's own events
        ops = [o for o in ops if "hlo_op" in o[3]]
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                st = dict(e.stats)
                if "trace_id" in st:  # only the program's spans carry one
                    spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                  st["trace_id"]))
    clip = find_marker(planes)
    everything = [(a, b) for _n, a, b, *_ in ops + spans]
    if clip is None and everything:
        clip = (min(a for a, _ in everything), max(b for _, b in everything))
    if clip is None:
        raise SystemExit("the trace holds no device op and no program span")
    busy = merged(clipped([(a, b) for _n, a, b, _s in ops], clip))
    out = {"window_s": (clip[1] - clip[0]) / 1e9,
           "busy_s": sum(b - a for a, b in busy) / 1e9,
           "one_op_event": ({"name": ops[0][0][:200], "stats": {
               k: (v if not isinstance(v, str) else v[:200])
               for k, v in ops[0][3].items()}} if ops else None),
           "trace_ids": len({t for *_x, t in spans})}
    by_name = {}
    for n, a, b, _t in spans:
        by_name.setdefault(n, []).append((a, b))
    rows = []
    for n, iv in by_name.items():
        iv = clipped(iv, clip)
        if iv:
            rows.append({"span": n, "events": len(iv),
                         "wall_s": sum(b - a for a, b in iv) / 1e9,
                         "covers_s": union_seconds(iv),
                         "device_busy_under_s": overlap_seconds(iv, busy)})
    out["spans"] = sorted(rows, key=lambda r: -r["wall_s"])
    gaps = []
    for g0, g1 in idle_gaps(busy, clip)[:top]:
        shares = {}
        for n, iv in by_name.items():
            s = union_seconds(iv, (g0, g1))
            if s > 0:
                shares[n] = s * 1e9 / (g1 - g0)
        covered = union_seconds([(a, b) for _n, a, b, _t in spans], (g0, g1))
        gaps.append({"start_s": (g0 - clip[0]) / 1e9, "length_s": (g1 - g0) / 1e9,
                     "covered_by_program_spans": covered * 1e9 / (g1 - g0),
                     "spans": dict(sorted(shares.items(), key=lambda kv: -kv[1]))})
    out["idle_gaps"] = gaps
    by_scope, by_op = {}, {}
    for n, a, b, st in ops:
        lo, hi = max(a, clip[0]), min(b, clip[1])
        if hi > lo:
            sc = scope_of(st)
            by_scope[sc] = by_scope.get(sc, 0.0) + (hi - lo) / 1e9
            key = (short(n), sc)
            by_op[key] = by_op.get(key, 0.0) + (hi - lo) / 1e9
    out["device_s_by_stage"] = dict(sorted(by_scope.items(), key=lambda kv: -kv[1]))
    out["top_ops"] = [{"op": k[0], "stage": k[1], "device_s": v} for k, v in
                      sorted(by_op.items(), key=lambda kv: -kv[1])[:top]]
    return out


def show(out: dict) -> None:
    print(f"window {out['window_s']:.6f} s, device busy {out['busy_s']:.6f} s, "
          f"{out['trace_ids']} trace ids on the host plane")
    print("\n1. one event of the ops line:")
    print("   ", json.dumps(out["one_op_event"])[:1500])
    print("\n2. program spans (wall summed over events; covers = their union; "
          "device busy under them):")
    print(f"    {'span':34s} {'events':>7s} {'wall_s':>10s} {'covers_s':>10s} "
          f"{'busy_under_s':>12s}")
    for r in out["spans"]:
        print(f"    {r['span'][:34]:34s} {r['events']:7d} {r['wall_s']:10.6f} "
              f"{r['covers_s']:10.6f} {r['device_busy_under_s']:12.6f}")
    print("\n3. longest idle gaps of the device, and the program spans over them:")
    for g in out["idle_gaps"]:
        print(f"    at {g['start_s']:.6f} s, {g['length_s'] * 1e3:.3f} ms idle, "
              f"{100 * g['covered_by_program_spans']:.1f} % under a program span:")
        print("       " + ", ".join(f"{n} {100 * s:.0f}%"
                                    for n, s in list(g["spans"].items())[:12]))
    print("\n4. device seconds per named stage:")
    for sc, s in out["device_s_by_stage"].items():
        print(f"    {sc:14s} {s:.6f} s")
    for r in out["top_ops"]:
        print(f"    {r['device_s']:.6f} s  {r['stage']:12s}  {r['op']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane")
    ap.add_argument("--platform", choices=sorted(OPS_LINES), default="tpu")
    ap.add_argument("--top", type=int, default=10)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # reading a file needs no chip
    import warnings

    import jax

    # jax's ProfileData stat iterators warn of a missing __module__
    warnings.simplefilter("ignore", DeprecationWarning)

    planes = jax.profiler.ProfileData.from_file(args.xplane).planes
    out = join(planes, args.platform, args.top,
               metadata_strings(args.xplane, OPS_LINES[args.platform][0]))
    if args.json:
        print(json.dumps(out))
    else:
        show(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
