"""The reduction from a trace to busy time: the interval union, one line of
the device plane only, clipped to the window's marker."""

from __future__ import annotations

import glob
import os
from types import SimpleNamespace as NS

import pytest

from benchmarks.chip import trace_reduce as tr


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def line(name, *events):
    return NS(name=name, events=list(events))


def plane(name, *lines):
    return NS(name=name, lines=list(lines))


@pytest.mark.parametrize("intervals,clip,want_ns", [
    ([(0, 10), (5, 15)], None, 15),               # overlap counts once
    ([(0, 10), (10, 20)], None, 20),              # touching
    ([(0, 10), (2, 4), (3, 5)], None, 10),        # nested
    ([(0, 10), (20, 30)], None, 20),              # a gap
    ([(0, 10), (20, 30)], (5, 25), 10),           # clipped at both ends
    ([(0, 10)], (20, 30), 0),                     # wholly outside the window
    ([], None, 0),
])
def test_union(intervals, clip, want_ns):
    assert tr.union_seconds(intervals, clip) == pytest.approx(want_ns / 1e9)


def test_two_lines_of_one_plane_do_not_add():
    """The modules and steps lines cover the ops again: only the ops line is
    read, and overlapping ops on it count once."""
    host = plane("/host:CPU", line("python", ev(tr.MARKER, 0, 1000)))
    dev = plane("/device:TPU:0",
                line("XLA Modules", ev("jit_kernel(1)", 100, 400)),
                line("Steps", ev("0", 100, 400)),
                line("XLA Ops", ev("fusion.1", 100, 200), ev("copy.2", 250, 150),
                     ev("fusion.3", 350, 150)))
    out = tr.reduce_planes([host, dev], "tpu")
    assert out["busy_s"] == pytest.approx(400e-9)  # not 1200, not 500
    assert out["marker_s"] == pytest.approx(1000e-9)
    assert out["n_programs"] == 1  # the modules line is counted, not added
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(200e-9)]
    assert sum(g[1] for g in out["idle_gaps"]) == pytest.approx(600e-9)


def test_events_outside_the_marker_are_clipped():
    host = plane("/host:CPU", line("python", ev(tr.MARKER, 1000, 1000)))
    dev = plane("/device:TPU:0", line("XLA Ops", ev("a", 0, 500), ev("b", 900, 200),
                                      ev("c", 1900, 500)))
    out = tr.reduce_planes([host, dev], "tpu")
    assert out["busy_s"] == pytest.approx(200e-9)
    assert out["busy_s"] <= out["marker_s"]


def test_no_device_plane_gives_zero():
    host = plane("/host:CPU", line("python", ev(tr.MARKER, 0, 1000)))
    assert tr.reduce_planes([host], "tpu")["busy_s"] == 0.0
    empty = plane("/device:TPU:0", line("XLA Ops"))
    assert tr.reduce_planes([host, empty], "tpu")["busy_s"] == 0.0


def test_one_device_plane_is_read():
    host = plane("/host:CPU", line("python", ev(tr.MARKER, 0, 1000)))
    d0 = plane("/device:TPU:0", line("XLA Ops", ev("a", 0, 400)))
    d1 = plane("/device:TPU:1", line("XLA Ops", ev("a", 0, 200)))
    assert tr.reduce_planes([host, d1, d0], "tpu")["busy_s"] == pytest.approx(400e-9)


RECORDED = sorted(glob.glob(os.path.join(os.path.dirname(__file__), "data", "*.xplane.pb")))


@pytest.mark.parametrize("path", RECORDED, ids=[os.path.basename(p) for p in RECORDED])
def test_a_trace_recorded_on_the_chip(path):
    """Traces ``run.py --trace 1 --keep-trace`` wrote on a v5e (PR 24)."""
    assert os.path.getsize(path) < 1 << 20
    out = tr.reduce_file(path, "tpu")
    assert out["marker_s"] and 0 < out["busy_s"] <= out["marker_s"]
    assert out["device_ops"] and out["n_events"] > 0
    # 4 clients x 50 identical requests, coalesced into fewer programs
    assert 0 < out["n_programs"] < 200
    assert any(s.endswith("|XLA Ops") for s in out["lines_seen"])
