"""PR 38's reader and metric files, which wait for their entries in
BENCHMARK.json (PERF.md 7, "Third"): ``since_start`` reads what the program
had booked when the window opened, and each new file is a whole metric (one
file each, for a ``workloads`` list of all five cells: no twins)."""

from __future__ import annotations

import pytest

from benchmarks.chip import readers, result_line, since_start

MAN = result_line.manifest()
NEW = ("setup_start_s", "setup_ingest_s", "setup_query_s", "setup_stage_s",
       "setup_compile_s", "setup_prewarm_s", "group_ms", "other_ms",
       "queue_wait_ms", "handback_ms", "prewarm_in_window")
SETUP = tuple(n for n in NEW if n.startswith("setup_"))
KINDS = {"device_trace", "program_span", "program_counter", "host_clock"}

OPENED = readers.parse_metrics(
    'filodb_startup_seconds{stage="import"} 9.5\n'
    'filodb_startup_seconds{stage="backend"} 3\n'
    'filodb_startup_seconds{stage="store"} 1.25\n'
    'filodb_startup_seconds{stage="listen"} 0.25\n'
    'filodb_ingest_seconds_sum{dataset="prometheus"} 44.5\n'
    'filodb_ingest_seconds_count{dataset="prometheus"} 50\n'
    'filodb_query_phase_seconds_sum{dataset="prometheus",phase="stage"} 40\n'
    'filodb_query_phase_seconds_sum{dataset="prometheus",phase="group"} 0.5\n')
CLOSED = readers.parse_metrics(
    'filodb_startup_seconds{stage="import"} 9.5\n'
    'filodb_ingest_seconds_sum{dataset="prometheus"} 44.5\n'
    'filodb_query_phase_seconds_sum{dataset="prometheus",phase="stage"} 47\n'
    'filodb_prewarm_seconds_sum 70\n')
CTX = {"segments": [(OPENED, CLOSED), (CLOSED, CLOSED)], "latencies_ms": [7.0]}


def _spec(name: str) -> dict:
    return result_line.chip_json("layer_metrics", f"{name}.json")


def _read(name: str, ctx=CTX):
    src = dict(_spec(name)["source"])
    return readers.find(src.pop("reader"))(ctx, **src)


def test_since_start_is_found_as_a_reader_kind():
    assert "since_start" not in readers.KINDS
    assert readers.find("since_start") is since_start.read


@pytest.mark.parametrize("name,want", [
    ("setup_start_s", 14.0),    # the sum over the stages
    ("setup_ingest_s", 44.5),
    ("setup_stage_s", 40.0),    # the window's 7 s are not set-up
    ("setup_prewarm_s", 0.0),   # booked behind the window: not at its start
    ("setup_compile_s", 0.0),   # a family this program does not have
    ("setup_query_s", 0.0),
])
def test_a_setup_metric_reads_the_first_reading_and_not_the_delta(name, want):
    got = _read(name)
    assert isinstance(got, float) and got == want


def test_since_start_scales_and_selects_by_label():
    assert since_start.read(CTX, "filodb_query_phase_seconds_sum", scale=1e3,
                            phase="group") == 500.0
    assert since_start.read(CTX, "filodb_query_phase_seconds_sum") == 40.5
    assert since_start.read({"segments": [({}, {})]}, "filodb_nothing") == 0.0


def test_the_windows_metrics_still_read_the_delta():
    assert readers.phase_mean(CTX, ["stage"]) == pytest.approx(7000.0)
    assert _read("group_ms") == pytest.approx(-500.0)  # CLOSED has no group
    assert _read("prewarm_in_window") == 0.0


@pytest.mark.parametrize("name", NEW)
def test_each_new_metric_file_is_a_whole_metric(name):
    spec = _spec(name)
    assert set(spec) == {"name", "layer", "unit", "moves", "kind", "source"}
    assert spec["name"] == name and spec["kind"] in KINDS
    suffix = name.rsplit("_", 1)[1]
    assert spec["unit"] == (suffix if suffix in ("s", "ms") else "keys")
    # a layer the benchmark names, letter for letter, or this PR's one new
    assert spec["layer"] in {m["layer"] for m in MAN["per_layer"]} | {
        "server start-up and ingest (server.py, memstore/memstore.py)"}
    e2e = {m["name"] for m in MAN["end_to_end"]}
    assert spec["moves"] == ("setup_s" if name in SETUP else "query_p50_ms")
    assert spec["moves"] in e2e
    assert callable(readers.find(spec["source"]["reader"]))
    assert (spec["source"]["reader"] == "since_start") == (name in SETUP)
