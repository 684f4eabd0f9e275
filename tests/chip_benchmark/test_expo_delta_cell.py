"""The cell ``expo_delta.repeat`` (configuration ``otel-expo-delta-hists``) as
far as a CPU can show it: its files agree with each other and carry what the
deployment sets (checked by membership: a later configuration or cell is
appended after these); the tiny ``--cpu-rehearsal`` prints a line the
checker passes, every launch summing its windows, none falling back, points
merged down at ingest; three planted faults are not correct — a delta
point counted in two windows, one point merged onto the wrong scale, a sum
of f32 rates in place of whole counts; the control is not correct either. The generator and the reference themselves are held
in tests/test_expo_delta.py. Nothing here touches a TPU."""

from __future__ import annotations

import pytest
import rehearsal_cell
from rehearsal_cell import MAN, RUN

from benchmarks.chip import control, result_line

CELL = "expo_delta.repeat"
TRAFFIC = result_line.chip_json("workloads", f"{CELL}.json")
CONFIG = result_line.chip_json("configs", "otel-expo-delta-hists.json")
EXPO = result_line.chip_json("configs", "otel-expo-hists.json")
NEW = [m for m in MAN["per_layer"] if m["name"].startswith("edelta_")]
TWINS = ("edge_ms", "render_ms", "engine_ms", "coalesced_pct", "superblock_hit_pct",
         "compiles_in_window", "device_wait_ms", "kernel_ms", "kernel_roofline",
         "device_idle_pct", "rescaled_series")


def test_the_cell_and_its_traffic_are_expo_repeats():
    assert result_line.cell_of(MAN, CELL) == {
        "name": CELL, "config": CONFIG["name"], "traffic": "repeat", "chips": 1,
        "why": result_line.cell_of(MAN, CELL)["why"]}
    expo = result_line.chip_json("workloads", "expo.repeat.json")
    for key in ("clients", "steps", "step_s", "window_ms", "range", "trace_requests"):
        assert TRAFFIC[key] == expo[key], key
    assert [{k: v for k, v in p.items() if k != "reference"} for p in TRAFFIC["panels"]] \
        == [{k: v for k, v in p.items() if k != "reference"} for p in expo["panels"]]
    assert {p["reference"] for p in TRAFFIC["panels"]} == {"expo_delta_panels"}
    assert TRAFFIC["comment"] and "limit" in TRAFFIC["comment"]


def test_the_configuration_is_expo_hists_fleet_exported_delta():
    same = ("series", "services", "samples_per_series", "interval_ms", "max_buckets",
            "max_scale", "median_s", "sigma", "per_scrape", "timeout", "shards",
            "spread", "metric", "rehearsal")
    assert {k: CONFIG[k] for k in same} == {k: EXPO[k] for k in same}
    assert CONFIG["generator"] == "expo_delta_histograms" and "warmup_scrapes" not in CONFIG
    assert CONFIG["schema"].startswith("otel-exp-delta-histogram") and CONFIG["reduced"] == {}
    for key in ("deployment", "guarantees", "on_device_bytes", "assumed", "generated"):
        assert CONFIG[key], key
    assert {"temporality", "export_interval", "empty_interval"} <= set(CONFIG["assumed"])
    assert "departure" in CONFIG["guarantees"]
    # membership, not position: a later configuration is appended after it
    entry = next(c for c in MAN["configs"] if c["name"] == CONFIG["name"])
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == [] and entry["file"].endswith("otel-expo-delta-hists.json")


@pytest.mark.parametrize("metric", NEW, ids=lambda m: m["name"])
def test_every_new_metric_lists_its_one_cell_and_has_a_file(metric):
    assert metric["workloads"] == [CELL]
    spec = result_line.chip_json("layer_metrics", f"{metric['name']}.json")
    assert spec["name"] == metric["name"] and spec["source"]["reader"]
    assert metric["name"] in TRAFFIC["layer_metrics"]
    base = metric["name"][len("edelta_"):]
    if base in TWINS:  # expo.repeat's reader, under the cell's name
        twin = result_line.chip_json("layer_metrics", f"expo_{base}.json")
        assert {k: spec[k] for k in ("layer", "unit", "moves", "kind", "source")} == {
            k: twin[k] for k in ("layer", "unit", "moves", "kind", "source")}


def test_the_new_metrics_are_the_issues():
    assert [m["name"] for m in NEW] == TRAFFIC["layer_metrics"] == [
        f"edelta_{n}" for n in TWINS + ("window_sums_pct", "setup_merged_points")]
    sums = result_line.chip_json("layer_metrics", "edelta_window_sums_pct.json")
    assert sums["source"] == {"reader": "counter_per_request", "scale": 100.0,
                              "counter": "filodb_hist_window_total", "form": "sums"}
    merged = result_line.chip_json("layer_metrics", "edelta_setup_merged_points.json")
    assert merged["source"] == {"reader": "since_start",
                                "counter": "filodb_ingest_scheme_merged_total"}
    assert merged["moves"] == "setup_s"


@pytest.mark.parametrize("traced", [0, 1])
def test_cpu_rehearsal_prints_a_line_the_checker_passes(traced):
    proc = rehearsal_cell.run(RUN, CELL, "--seed", "81", "--trace", str(traced))
    m = rehearsal_cell.whole_line(rehearsal_cell.last_line(proc), CELL, TRAFFIC, traced)
    if traced:
        # every launch summed its windows (a follower launches nothing), series
        # were merged across scales, points were merged down at ingest
        assert m["edelta_window_sums_pct"] == pytest.approx(100 - m["edelta_coalesced_pct"])
        assert m["edelta_rescaled_series"] > 0 and m["edelta_setup_merged_points"] > 0
        assert m["edelta_compiles_in_window"] == 0
        assert m["edelta_superblock_hit_pct"] == 100.0


FAULTS = {
    # script: (seed, panels of which at least `need` must see it)
    "double_counted_point.py": ("82", {"fleet_p99", "fleet_p50", "service_p99", "service_p90"}, 4),
    "wrong_scale_point.py": ("83", {"service_p99", "service_p90"}, 1),
    # the program before the delta window sums came as whole counts
    "f32_rates.py": ("84", {"fleet_p99", "service_p99"}, 1),
}


@pytest.mark.parametrize("script", sorted(FAULTS))
def test_a_wrong_sum_is_not_correct(script):
    seed, panels, need = FAULTS[script]
    proc = rehearsal_cell.run(script, CELL, "--seed", seed, "--trace", "0")
    line = rehearsal_cell.last_line(proc)
    assert line["correct"] is False and line["failed"] > 0
    assert line["compared"]["malformed"]["value"] == 0
    over = {name for name in panels if line["compared"][f"rel_err.{name}"]["value"]
            > line["compared"][f"rel_err.{name}"]["limit"]}
    assert len(over) >= need, (script, line["compared"])


def test_the_control_is_not_correct_at_the_rehearsal_size():
    got = control.readings(CELL, seed=13, rehearsal=True)
    over = [k for k, c in got.items() if k.startswith("rel_err.") and c["value"] > c["limit"]]
    assert len(over) == len(TRAFFIC["panels"])  # bfloat16 running sums fail every panel
    same = control.readings(CELL, seed=13, rehearsal=True, quantize=lambda x: x)
    assert control.passed(same) and all(c["value"] == 0 for c in same.values())
