"""From a profiler trace (``*.xplane.pb``) to device busy time and the ops
that took it. Reads the file with jax alone (``jax.profiler.ProfileData``).

Busy time is the UNION of the event intervals of ONE line of a device plane
— the ops line — clipped to the window. Summing durations, or adding the
modules and steps lines (which cover the same time again) to the ops line,
counts time twice. The modules line is only COUNTED: one event there is one
execution of a compiled program, and the server coalesces identical
concurrent queries into one, so programs run are fewer than requests. The
window is the ``bench_window`` annotation that ``run.py`` holds open around
the sub-window's requests; it is on the same clock as the device events. No
device plane, or no event on its ops line, gives 0: the run's own check then
refuses the line.
"""

from __future__ import annotations

import re

MARKER = "bench_window"
# (plane name prefix, ops line name prefix) by platform. The CPU entry is the
# rehearsal's stand-in — XLA's CPU client threads — so that the traced path
# can be walked without a chip; a line read from it says platform: cpu.
OPS_LINES = {
    "tpu": ("/device:TPU:", "XLA Ops"),
    "cpu": ("/host:CPU", "tf_XLAPjRtCpuClient"),
}
PROGRAMS_LINE = "XLA Modules"  # device planes only; the CPU stand-in has none


def union_seconds(intervals, clip=None) -> float:
    """Length of the union of (start_ns, end_ns) intervals, within ``clip``."""
    if clip is not None:
        lo, hi = clip
        intervals = [(max(a, lo), min(b, hi)) for a, b in intervals]
    total, end = 0.0, None
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total / 1e9


_HLO = re.compile(r"%?(\S+) = \(?([a-z0-9]+\[[0-9,]*\])[^ ]*.*? ([a-z][a-z\-]*)\(")


def short(name: str) -> str:
    """An ops-line event is named by its whole HLO instruction; keep the
    instruction's name, its (first) result shape and its opcode."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)} {m.group(3)}" if m else name[:80]


def _gaps(intervals, clip):
    """Idle stretches inside ``clip``, longest first: (start_ns, length_ns)."""
    lo, hi = clip
    out, end = [], lo
    for a, b in sorted(intervals):
        if a > end:
            out.append((end, min(a, hi) - end))
        end = max(end, b)
        if end >= hi:
            break
    if end < hi:
        out.append((end, hi - end))
    return sorted((g for g in out if g[1] > 0), key=lambda g: -g[1])


def find_marker(planes):
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == MARKER:
                    return ev.start_ns, ev.start_ns + ev.duration_ns
    return None


def reduce_planes(planes, platform: str) -> dict:
    """``planes``: objects with ``.name`` and ``.lines`` (each with ``.name``
    and ``.events`` of ``.name``, ``.start_ns``, ``.duration_ns``). The cells
    take one chip: the first device plane by name is the one read."""
    planes = list(planes)
    plane_prefix, line_prefix = OPS_LINES[platform]
    marker = clip = find_marker(planes)
    events, programs, lines_seen = [], [], []
    mine = sorted((p for p in planes if p.name.startswith(plane_prefix)),
                  key=lambda p: p.name)[:1]
    for plane in mine:
        for line in plane.lines:
            lines_seen.append(f"{plane.name}|{line.name}")
            if line.name.startswith(line_prefix):
                events += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
            elif line.name == PROGRAMS_LINE:
                programs += [e.start_ns for e in line.events]
    iv = [(a, b) for _n, a, b in events]
    if clip is None and iv:
        clip = (min(a for a, _ in iv), max(b for _, b in iv))
    by_op, gaps = {}, []
    for n, a, b in events:
        lo, hi = max(a, clip[0]), min(b, clip[1])
        if hi > lo:
            by_op[short(n)] = by_op.get(short(n), 0.0) + (hi - lo) / 1e9
    if iv:
        starts = sorted((a, n) for n, a, _b in events)
        for g0, length in _gaps(iv, clip)[:10]:
            after = next((n for a, n in starts if a >= g0 + length), "window end")
            gaps.append([f"before {short(after)}", length / 1e9])
    return {
        "busy_s": union_seconds(iv, clip),
        "marker_s": (marker[1] - marker[0]) / 1e9 if marker else None,
        "n_events": len(events),
        "n_programs": sum(1 for t in programs if clip and clip[0] <= t < clip[1]),
        "device_ops": [[n, s] for n, s in
                       sorted(by_op.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": gaps,
        "lines_seen": lines_seen,
    }


def reduce_file(path: str, platform: str) -> dict:
    import jax

    return reduce_planes(jax.profiler.ProfileData.from_file(path).planes, platform)


def describe(path: str, n: int = 8) -> None:
    """Print a trace's planes, lines and first events: look at one by hand
    before trusting the reduction."""
    import jax

    for plane in jax.profiler.ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            span = ((min(e.start_ns for e in events),
                     max(e.start_ns + e.duration_ns for e in events))
                    if events else None)
            print(f"  LINE {line.name!r}: {len(events)} events, span {span}")
            for e in events[:n]:
                print(f"      {e.name[:90]!r} start {e.start_ns} dur {e.duration_ns}")


if __name__ == "__main__":
    import sys

    describe(sys.argv[1])
