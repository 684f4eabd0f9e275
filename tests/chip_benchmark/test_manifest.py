"""BENCHMARK.json against the contract's limits as far as a test can hold
them, every file a cell names, and the result-line checker's refusals."""

from __future__ import annotations

import copy
import json
import os
import re

import pytest

from benchmarks.chip import readers, result_line

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")
MAN = result_line.manifest()
CELLS = [w["name"] for w in MAN["workloads"]]
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


_json = result_line.chip_json


def test_top_level_keys_and_limits():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    assert isinstance(MAN["run_seconds"], int) and 1 <= MAN["run_seconds"] <= 51
    assert len(json.dumps(MAN)) < 64 * 1024
    for word in MAN["command"]:
        assert not word.startswith("/") and ".." not in word
    for p in MAN["paths"]:
        assert os.path.isdir(os.path.join(result_line.ROOT, p))
    four = sum(1 for w in MAN["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(MAN["workloads"]) // 2)
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25 for m in MAN["end_to_end"])


def test_names_units_and_lines():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in MAN[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
            for key in ("why", "layer", "source"):
                if key in e and not (group != "configs" and key == "source"):
                    assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] and "\t" not in e[key]
    assert len(names) == len(set(names)), "a name is used twice"
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in MAN["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MAN["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(pairs) == len(set(pairs))
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(w["config"] == c["name"] for w in MAN["workloads"])


@pytest.mark.parametrize("cell", CELLS)
def test_every_file_a_cell_names_exists_and_agrees(cell):
    w = result_line.cell_of(MAN, cell)
    tr = _json("workloads", f"{cell}.json")
    assert tr["name"] == cell and tr["config"] == w["config"]
    cfg_entry = next(c for c in MAN["configs"] if c["name"] == w["config"])
    with open(os.path.join(result_line.ROOT, cfg_entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["source"] == cfg_entry["source"]
    assert sorted(cfg["reduced"]) == sorted(cfg_entry["reduced"])
    from benchmarks.chip import generators, references

    assert callable(generators.find(cfg["generator"]))
    for panel in tr["panels"]:
        assert callable(references.find(panel["reference"]))
        assert panel["rel_err_limit"] > 0
    # the cell's per-layer metrics: a file each, the same facts in both places
    per_layer = result_line.per_layer_of(MAN, cell)
    assert sorted(per_layer) == sorted(tr["layer_metrics"])
    e2e = result_line.end_to_end_of(MAN, cell)
    assert "setup_s" in e2e and len(e2e) >= 2 and per_layer
    for name in per_layer:
        spec = _json("layer_metrics", f"{name}.json")
        entry = next(m for m in MAN["per_layer"] if m["name"] == name)
        assert (spec["layer"], spec["unit"], spec["moves"], spec["kind"]) == (
            entry["layer"], entry["unit"], entry["moves"], entry["source"])
        assert callable(readers.find(spec["source"]["reader"]))
        # a cell that reports a per-layer metric reports what it moves
        assert entry["moves"] in e2e, (name, entry["moves"])


def test_walks_of_the_traffic_generator():
    """No seed changes what is sent: the slide walks every position once,
    oldest first, strictly inside the history; clients of a repeat cell all
    ask the one thing, so the server may coalesce them."""
    from benchmarks.chip import traffic

    t_first, t_last = 1_700_000_000_000, 1_700_000_000_000 + 719 * 10_000
    slide = _json("workloads", "hists.slide.json")
    reqs, (walk,) = traffic.cycles(slide, t_first, t_last)
    assert walk == list(range(len(reqs))) and len({r[1] for r in reqs}) == len(reqs)
    assert reqs[0][1] == t_first + slide["window_ms"]  # strictly inside the history
    span = (slide["steps"] - 1) * slide["step_s"] * 1000
    assert reqs[-1][1] + span <= t_last < reqs[-1][1] + span + slide["step_s"] * 1000
    repeat = _json("workloads", "hists.repeat.json")
    reqs, walks = traffic.cycles(repeat, t_first, t_last)
    assert len(reqs) == 1 and walks == [[0]] * repeat["clients"]
    assert traffic.out_t(repeat, reqs[0][1])[-1] == t_last
    two = dict(slide, clients=2)  # a shared walk rotates the whole cycle
    reqs, walks = traffic.cycles(two, t_first, t_last)
    assert walks[1][0] == len(reqs) // 2
    assert all(sorted(w) == list(range(len(reqs))) for w in walks)


@pytest.mark.parametrize("stat,want", [("p95", 20.0), ("mean_less_program", 1.5)])
def test_the_edge_is_what_the_programs_own_clocks_leave(stat, want):
    """10 requests of 11 ms; the engine's per-caller latency 8 ms a request
    (a follower's wait for a shared execution included), transfer 1 ms,
    render 0.5 ms: 1.5 ms are the edge's."""
    lat = "filodb_query_latency_seconds_sum"
    ph = "filodb_query_phase_seconds_sum"
    before = {(lat, frozenset({("dataset", '"prometheus"')})): 1.0,
              (ph, frozenset({("phase", '"transfer"')})): 0.0}
    after = {(lat, frozenset({("dataset", '"prometheus"')})): 1.08,
             (ph, frozenset({("phase", '"transfer"')})): 0.010,
             (ph, frozenset({("phase", '"render"')})): 0.005,
             (ph, frozenset({("phase", '"stage"')})): 0.030}
    ctx = {"segments": [(before, after)], "latencies_ms": [10.0] * 9 + [20.0]}
    assert readers.client_clock(ctx, stat) == pytest.approx(want)
    assert readers.phase_mean(ctx, ["transfer"]) == pytest.approx(1.0)
    assert readers.phase_mean(ctx, ["render"]) == pytest.approx(0.5)
    assert readers.counter_per_request(ctx, lat, scale=1000.0) == pytest.approx(8.0)
    assert readers.client_clock(dict(ctx, latencies_ms=[]), stat) is None


# -- the checker ---------------------------------------------------------------


def _good_line(cell: str, traced: bool) -> dict:
    want = (result_line.per_layer_of if traced else result_line.end_to_end_of)(MAN, cell)
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
           "memory_peak_bytes": 10**9}
    if traced:
        dev.update(window_s=2.0, busy_s=0.5)
    return {"correct": True, "attempted": 10, "failed": 0,
            "metrics": {n: {"value": 1.5, "unit": u} for n, u in want.items()},
            "device": dev}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("traced", [False, True])
def test_checker_passes_a_whole_line(cell, traced):
    assert result_line.check(_good_line(cell, traced), MAN, cell, traced) == []


def _break(line, what):
    line = copy.deepcopy(line)
    first = next(iter(line["metrics"]))
    if what == "busy_zero":
        line["device"]["busy_s"] = 0.0
    elif what == "busy_over_window":
        line["device"]["busy_s"] = line["device"]["window_s"] * 1.01
    elif what == "no_busy":
        del line["device"]["busy_s"]
    elif what == "metric_missing":
        del line["metrics"][first]
    elif what == "no_unit":
        del line["metrics"][first]["unit"]
    elif what == "wrong_unit":
        line["metrics"][first]["unit"] = "furlongs"
    elif what == "nan":
        line["metrics"][first]["value"] = float("nan")
    elif what == "no_peak":
        line["device"]["memory_peak_bytes"] = 0
    elif what == "no_key":
        del line["failed"]
    elif what == "roofline_over_100":
        line["metrics"]["fused_kernel_roofline"]["value"] = 104.0
    elif what == "stray_metric":
        line["metrics"]["made_up"] = {"value": 1.0, "unit": "ms"}
    return line


@pytest.mark.parametrize("what", [
    "busy_zero", "busy_over_window", "no_busy", "metric_missing", "no_unit",
    "wrong_unit", "nan", "no_peak", "no_key", "roofline_over_100", "stray_metric"])
def test_checker_refuses_a_traced_line(what):
    line = _break(_good_line("hists.slide", True), what)
    assert result_line.check(line, MAN, "hists.slide", True), what


@pytest.mark.parametrize("what", ["metric_missing", "no_unit", "nan", "no_key"])
def test_checker_refuses_an_untraced_line(what):
    line = _break(_good_line("hists.repeat", False), what)
    assert result_line.check(line, MAN, "hists.repeat", False), what


def test_an_end_to_end_metric_may_not_be_zero():
    line = _good_line("hists.repeat", False)
    line["metrics"]["queries_per_s"]["value"] = 0.0
    assert result_line.check(line, MAN, "hists.repeat", False)
    line["correct"] = False  # all answers wrong: none completed right
    assert result_line.check(line, MAN, "hists.repeat", False) == []
