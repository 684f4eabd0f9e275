"""The cell ``scraped.repeat`` (configuration ``prom-scraped-counters``) as far
as a CPU can show it: its files carry what ISSUE 36 set, the tiny
``--cpu-rehearsal`` prints a line the checker passes with every launch booked
off the ladder, also with the newest scrape on a whole minute; a fourth panel
1e-5 high and one series kept on another clock are not correct; the control
is not correct either. The reference and the generator themselves are held
in tests/test_scraped_reference.py. Nothing here touches a TPU."""

from __future__ import annotations

import pytest
import rehearsal_cell
from rehearsal_cell import MAN, RUN

from benchmarks.chip import control, result_line

CELL = "scraped.repeat"
TRAFFIC = result_line.chip_json("workloads", f"{CELL}.json")
CONFIG = result_line.chip_json("configs", "prom-scraped-counters.json")
TWIN = result_line.chip_json("workloads", "counters.repeat.json")
NEW = [m for m in MAN["per_layer"] if m["name"].startswith("scraped_")]
# the rehearsal's 384 targets do not always fill the interval: with this seed
# their phases do, and the grid is ``irregular`` as 100 000 targets' always is
# (another seed's 384 can pass for a near-regular grid with holes)
SEED = "3000000061"


def test_the_traffic_is_counters_repeats_on_the_scraped_fleet():
    assert result_line.cell_of(MAN, CELL) == {
        "name": CELL, "config": "prom-scraped-counters", "traffic": "repeat",
        "chips": 1, "why": result_line.cell_of(MAN, CELL)["why"]}
    for key in ("clients", "steps", "step_s", "window_ms", "range"):
        assert TRAFFIC[key] == TWIN[key], key
    assert (TRAFFIC["clients"], TRAFFIC["steps"], TRAFFIC["step_s"]) == (4, 114, 60)
    assert TRAFFIC["range"] == {"mode": "newest"} and TRAFFIC["trace_requests"] == 3
    ours = [dict(p, reference=None) for p in TRAFFIC["panels"]]
    assert ours == [dict(p, reference=None) for p in TWIN["panels"]]
    assert {p["reference"] for p in TRAFFIC["panels"]} == {"scraped_panels"}
    assert [p["rel_err_limit"] for p in TRAFFIC["panels"]] == [1e-4, 1e-4, 1.5e-6, 1e-4]


def test_the_configuration_states_what_the_issue_set():
    assert (CONFIG["series"], CONFIG["samples_per_series"], CONFIG["interval_ms"]) == (
        100_000, 720, 10_000)
    assert CONFIG["phase"] == {"span_ms": CONFIG["interval_ms"]}
    assert CONFIG["late"] == {"share": 0.1, "tolerance_ms": 2, "mean_ms": 20, "cut_ms": 1000}
    assert CONFIG["missed"] == {"share": 0.005} and CONFIG["reduced"] == {}
    for key in ("deployment", "guarantees", "on_device_bytes", "rehearsal", "assumed"):
        assert CONFIG[key], key
    entry = next(c for c in MAN["configs"] if c["name"] == CONFIG["name"])
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == [] and entry is MAN["configs"][-1]


@pytest.mark.parametrize("metric", NEW, ids=lambda m: m["name"])
def test_every_new_metric_lists_its_one_cell_and_has_a_file(metric):
    assert metric["workloads"] == [CELL]
    spec = result_line.chip_json("layer_metrics", f"{metric['name']}.json")
    assert spec["name"] == metric["name"] and spec["source"]["reader"]
    assert metric["name"] in TRAFFIC["layer_metrics"]
    twin = metric["name"].replace("scraped_", "counters_")
    if twin in TWIN["layer_metrics"]:  # the same reader under the cell's own name
        assert spec["source"] == result_line.chip_json(
            "layer_metrics", f"{twin}.json")["source"]


def test_the_new_metrics_are_the_issues():
    assert [m["name"] for m in NEW] == TRAFFIC["layer_metrics"] == [
        f"scraped_{n}" for n in (
            "edge_ms", "render_ms", "engine_ms", "coalesced_pct", "stage_ms",
            "superblock_hit_pct", "dispatch_host_ms", "compiles_in_window",
            "device_wait_ms", "kernel_ms", "kernel_roofline", "device_idle_pct",
            "off_ladder_pct")]
    off = result_line.chip_json("layer_metrics", "scraped_off_ladder_pct.json")
    assert off["source"] == {"reader": "counter_per_request", "scale": 100.0,
                             "counter": "filodb_fused_dispatch_total",
                             "grid": "irregular"}
    assert (off["unit"], off["moves"]) == ("%", "query_p50_ms")


def _whole_line(line, traced):
    m = rehearsal_cell.whole_line(line, CELL, TRAFFIC, traced)
    if traced:
        assert m["scraped_compiles_in_window"] == 0
        assert m["scraped_superblock_hit_pct"] == 100.0
        # a request is one launch off the ladder, or a coalesced follower's none
        assert m["scraped_off_ladder_pct"] + m["scraped_coalesced_pct"] == (
            pytest.approx(100.0, abs=1e-6))
        assert m["scraped_off_ladder_pct"] > 50


@pytest.mark.parametrize("traced", [0, 1])
def test_cpu_rehearsal_prints_a_line_the_checker_passes(traced):
    proc = rehearsal_cell.run(RUN, CELL, "--seed", SEED, "--trace", str(traced))
    _whole_line(rehearsal_cell.last_line(proc), traced)


def test_the_rehearsal_does_not_mind_a_newest_scrape_on_a_whole_minute():
    """One run in six by the wall clock: the query grid lines up with a
    standing query's. The range end is fixed, so none is promoted."""
    proc = rehearsal_cell.run("on_the_minute.py", CELL, "--seed", SEED, "--trace", "1")
    _whole_line(rehearsal_cell.last_line(proc), 1)
    assert rehearsal_cell.newest_scrape_ms(proc) % 60_000 == 0


def test_a_fourth_panel_that_is_high_is_not_correct():
    proc = rehearsal_cell.run("high_fourth_panel.py", CELL, "--seed", "78", "--trace", "0")
    line = rehearsal_cell.last_line(proc)
    assert line["correct"] is False and 0 < line["failed"] < line["attempted"]
    for p in TRAFFIC["panels"]:
        c = line["compared"][f"rel_err.{p['name']}"]
        assert (c["value"] > c["limit"]) == (p["name"] == "avg_avg_over_time"), p


def test_a_series_kept_on_another_clock_is_not_correct():
    """Every timestamp of one series 1 s late in the store: its newest sample
    leaves every window, and the rate family sees it (``avg_over_time`` of
    100 000 000-sized readings does not)."""
    proc = rehearsal_cell.run("moved_series.py", CELL, "--seed", "79", "--trace", "0")
    line = rehearsal_cell.last_line(proc)
    assert line["correct"] is False and line["failed"] > 0
    assert line["compared"]["malformed"]["value"] == 0
    c = line["compared"]["rel_err.sum_irate"]
    assert c["value"] > c["limit"]
    c = line["compared"]["rel_err.avg_avg_over_time"]
    assert c["value"] <= c["limit"]


def test_the_control_is_not_correct_at_the_rehearsal_size():
    got = control.readings(CELL, seed=13, rehearsal=True)
    assert not control.passed(got)
    over = [k for k, c in got.items() if k.startswith("rel_err.") and c["value"] > c["limit"]]
    assert len(over) == len(TRAFFIC["panels"])  # bfloat16 staging fails every panel
    same = control.readings(CELL, seed=13, rehearsal=True, quantize=lambda x: x)
    assert control.passed(same) and all(c["value"] == 0 for c in same.values())
