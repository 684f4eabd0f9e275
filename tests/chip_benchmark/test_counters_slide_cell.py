"""The cell ``counters.slide`` (configuration ``filodb-dev-counters``) as far as
a CPU can show it: its files carry what ISSUE 36 set, the tiny
``--cpu-rehearsal`` prints a line the checker passes with every request a
miss of the superblock cache and every series staged by the Python tier,
also with the newest scrape on a whole minute; a scaled answer and one series
kept on another clock are not correct; the control is not correct either.
Nothing here touches a TPU."""

from __future__ import annotations

import pytest
import rehearsal_cell
from rehearsal_cell import MAN, RUN

from benchmarks.chip import control, result_line, traffic

CELL = "counters.slide"
TRAFFIC = result_line.chip_json("workloads", f"{CELL}.json")
CONFIG = result_line.chip_json("configs", "filodb-dev-counters.json")
HISTS = result_line.chip_json("workloads", "hists.slide.json")
REPEAT = result_line.chip_json("workloads", "counters.repeat.json")
NEW = [m for m in MAN["per_layer"] if m["name"].startswith("cslide_")]
T0 = 1_700_000_000_000


def test_the_traffic_is_hists_slides_walk_with_the_counter_panel():
    assert result_line.cell_of(MAN, CELL) == {
        "name": CELL, "config": "filodb-dev-counters", "traffic": "slide",
        "chips": 1, "why": result_line.cell_of(MAN, CELL)["why"]}
    for key in ("clients", "steps", "step_s", "window_ms", "range"):
        assert TRAFFIC[key] == HISTS[key], key
    assert (TRAFFIC["clients"], TRAFFIC["steps"], TRAFFIC["step_s"]) == (1, 30, 60)
    assert TRAFFIC["range"] == {"mode": "slide", "advance_steps": 1}
    assert TRAFFIC["panels"] == [REPEAT["panels"][0]] and TRAFFIC["trace_requests"] == 1
    assert TRAFFIC["panels"][0]["query"] == "sum(rate(http_requests_total[5m]))"
    assert TRAFFIC["panels"][0]["rel_err_limit"] == 1e-4


def test_the_slide_walks_the_counter_fleet_one_step_a_request():
    reqs, (walk,) = traffic.cycles(TRAFFIC, T0, T0 + 719 * 10_000)
    assert walk == list(range(len(reqs))) == list(range(86))
    assert {r[0] for r in reqs} == {0}
    assert reqs[1][1] - reqs[0][1] == TRAFFIC["step_s"] * 1000
    assert reqs[0][1] == T0 + TRAFFIC["window_ms"]  # the first window inside the history


@pytest.mark.parametrize("metric", NEW, ids=lambda m: m["name"])
def test_every_new_metric_lists_its_one_cell_and_has_a_file(metric):
    assert metric["workloads"] == [CELL]
    spec = result_line.chip_json("layer_metrics", f"{metric['name']}.json")
    assert spec["name"] == metric["name"] and spec["source"]["reader"]
    assert metric["name"] in TRAFFIC["layer_metrics"]
    twin = metric["name"].replace("cslide_", "counters_")
    if twin in REPEAT["layer_metrics"]:  # the same reader under the cell's own name
        assert spec["source"] == result_line.chip_json(
            "layer_metrics", f"{twin}.json")["source"]


def test_the_new_metrics_are_the_issues():
    assert [m["name"] for m in NEW] == TRAFFIC["layer_metrics"] == [
        f"cslide_{n}" for n in (
            "engine_ms", "stage_ms", "superblock_hit_pct", "compiles_in_window",
            "device_wait_ms", "kernel_ms", "kernel_roofline", "device_idle_pct",
            "stage_gather_ms", "stage_assemble_ms", "stage_python_series")]
    read = {n: result_line.chip_json("layer_metrics", f"cslide_{n}.json")["source"]
            for n in ("stage_gather_ms", "stage_assemble_ms", "stage_python_series")}
    for part in ("gather", "assemble"):
        assert read[f"stage_{part}_ms"] == {
            "reader": "counter_per_request", "scale": 1000.0, "part": part,
            "counter": "filodb_stage_part_seconds_sum"}
    assert read["stage_python_series"] == {
        "reader": "counter_per_request", "scale": 1.0, "how": "python",
        "counter": "filodb_stage_gather_series_total"}


# every run's window is 1 s (rehearsal_cell.run): ~25 of the walk's 86 positions,
# far from the newest, whose range a promoted standing query could answer where
# the clock lines the grids up


def _whole_line(line, traced):
    m = rehearsal_cell.whole_line(line, CELL, TRAFFIC, traced)
    if traced:
        assert m["cslide_compiles_in_window"] == 0
        assert m["cslide_superblock_hit_pct"] == 0.0  # every request restages
        # the whole rehearsal fleet through the Python tier, every timed request.
        # No upper bounds and no order between the clocks: on a loaded machine
        # a standing query promoted during the warm-up (this cell's range end
        # IS seen to advance) stages its state behind the window's requests,
        # and its stages are booked to the same counters (seen: two fleets a
        # request, and a stage phase above the callers' own engine wall)
        assert m["cslide_stage_python_series"] >= CONFIG["rehearsal"]["series"]
        assert min(m["cslide_stage_gather_ms"], m["cslide_stage_assemble_ms"]) > 0
        assert m["cslide_stage_ms"] > 0 and m["cslide_engine_ms"] > 0


@pytest.mark.parametrize("traced", [0, 1])
def test_cpu_rehearsal_prints_a_line_the_checker_passes(traced):
    proc = rehearsal_cell.run(RUN, CELL, "--seed", "3000000071", "--trace", str(traced))
    _whole_line(rehearsal_cell.last_line(proc), traced)


def test_the_rehearsal_does_not_mind_a_newest_scrape_on_a_whole_minute():
    """One run in six by the wall clock: the walk's grids line up with the
    grid a standing query keeps, and this cell's range end IS seen to
    advance (the warm-up asks for the walk's last three positions)."""
    proc = rehearsal_cell.run("on_the_minute.py", CELL, "--seed", "3000000072",
                              "--trace", "1")
    _whole_line(rehearsal_cell.last_line(proc), 1)
    assert rehearsal_cell.newest_scrape_ms(proc) % 60_000 == 0


def test_a_scaled_answer_is_not_correct():
    proc = rehearsal_cell.run("broken_run.py", CELL, "scaled", "--seed", "77",
                              "--trace", "0")
    line = rehearsal_cell.last_line(proc)
    assert line["correct"] is False and line["failed"] == line["attempted"] > 0
    c = line["compared"]["rel_err.sum_rate"]
    assert c["value"] > c["limit"]


def test_a_series_kept_on_another_clock_is_not_correct():
    """Every timestamp of one series of 384 1 s late in the store: at each
    step one increment enters its window and another leaves."""
    proc = rehearsal_cell.run("moved_series.py", CELL, "--seed", "79", "--trace", "0")
    line = rehearsal_cell.last_line(proc)
    assert line["correct"] is False and line["failed"] > 0
    assert line["compared"]["malformed"]["value"] == 0
    c = line["compared"]["rel_err.sum_rate"]
    assert c["value"] > c["limit"]


def test_the_control_is_not_correct_at_the_rehearsal_size():
    got = control.readings(CELL, seed=13, rehearsal=True)
    assert not control.passed(got)
    c = got["rel_err.sum_rate"]
    assert c["value"] > c["limit"]
    same = control.readings(CELL, seed=13, rehearsal=True, quantize=lambda x: x)
    assert control.passed(same) and all(c["value"] == 0 for c in same.values())
