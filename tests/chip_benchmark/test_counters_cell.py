"""The cell ``counters.repeat`` as far as a CPU can show it: its references
are ``chip_smoke.py``'s oracles, its generator loads what it says, the tiny
``--cpu-rehearsal`` prints a line the checker passes with the wide sum at
work, and a fourth panel that is 1e-5 high is not correct. Nothing here
touches a TPU."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.chip import counter_panels, regular_counters, result_line

ROOT = result_line.ROOT
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402

RUN = os.path.join(ROOT, "benchmarks", "chip", "run.py")
HERE = os.path.dirname(os.path.abspath(__file__))
MAN = result_line.manifest()
CELL = "counters.repeat"
TRAFFIC = result_line.chip_json("workloads", f"{CELL}.json")
CONFIG = result_line.chip_json("configs", "filodb-dev-counters.json")
T0 = 1_700_000_000_000
N = 200


def _both(seed: int):
    ours = regular_counters.make(CONFIG, N, np.random.default_rng(seed), T0)
    theirs = chip_smoke.make_scalar_set("main", N, np.random.default_rng(seed), T0)
    out_t = ours.ts[-1] - (TRAFFIC["steps"] - 1 - np.arange(TRAFFIC["steps"])
                           ) * TRAFFIC["step_s"] * 1000
    return ours, theirs, out_t


def test_the_generator_draws_what_chip_smoke_draws():
    ours, theirs, _ = _both(17)
    assert (ours.vals == theirs.vals).all() and (ours.ts == theirs.ts[0]).all()
    assert ours.tags == theirs.tags and ours.name == theirs.name
    assert (np.diff(ours.vals, axis=1) < 0).any()  # a reset is in the draw
    assert ours.n_samples == N * CONFIG["samples_per_series"] and ours.buckets == 1
    assert ours.samples_in(int(ours.ts[9]), int(ours.ts[19])) == 10 * N


@pytest.mark.parametrize("panel", TRAFFIC["panels"], ids=lambda p: p["name"])
def test_the_references_are_chip_smokes_oracles(panel):
    ours, theirs, out_t = _both(23)
    oracle = {"rate": chip_smoke.o_rate, "irate": chip_smoke.o_irate,
              "avg_over_time": chip_smoke.o_avg_over_time}[panel["fn"]]
    assert TRAFFIC["window_ms"] == chip_smoke.WINDOW_MS
    sj = oracle(theirs, out_t, T0)
    got = counter_panels.reference(ours, out_t, TRAFFIC["window_ms"], panel)
    zones = np.array([t["zone"] for t in theirs.tags])
    keys = ([frozenset({("zone", z)}) for z in sorted(set(zones))]
            if panel.get("by") else [frozenset()])
    assert set(got) == set(keys)
    for key in keys:
        rows = sj[zones == dict(key)["zone"]] if key else sj
        total, count = chip_smoke.nansum0(rows)
        want = total / count if panel["agg"] == "avg" else total
        assert (np.isnan(got[key]) == np.isnan(want)).all()
        np.testing.assert_allclose(got[key], want, rtol=1e-12)


def test_chip_smoke_holds_the_fourth_query_to_the_cells_limit():
    limit = next(p["rel_err_limit"] for p in TRAFFIC["panels"]
                 if p["name"] == "avg_avg_over_time")
    assert chip_smoke.WIDE_SUM_RTOL == limit < chip_smoke.RTOL / 1000


def test_the_references_import_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmarks", "chip", "counter_panels.py")) as f:
        text = f.read()
    assert "import filodb_tpu" not in text and "from filodb_tpu" not in text


def test_the_generator_loads_what_it_says():
    from filodb_tpu.coordinator.planner import QueryEngine
    from filodb_tpu.core.schemas import Dataset
    from filodb_tpu.memstore.memstore import TimeSeriesMemStore

    data = regular_counters.make(CONFIG, 64, np.random.default_rng(5), T0)
    ms = TimeSeriesMemStore()
    ms.setup(Dataset("prometheus"), list(range(CONFIG["shards"])))
    assert data.load(ms, CONFIG["spread"]) == data.n_samples == 64 * 720
    eng = QueryEngine(ms, "prometheus")
    t = int(data.ts[-1]) / 1000
    res = eng.query_range(f"count by (zone) (count_over_time({data.name}[2h]))", t, t, 60)
    rows = {l["zone"]: v[0] for g in res.grids for l, v in zip(g.labels, g.values_np())}
    assert rows == {f"z{z}": 8.0 for z in range(regular_counters.ZONES)}
    res = eng.query_range(f"sum(count_over_time({data.name}[2h]))", t, t, 60)
    assert res.grids[0].values_np()[0][0] == data.n_samples
    res = eng.query_range(f'last_over_time({data.name}{{instance="host-7"}}[1m])', t, t, 60)
    assert res.grids[0].values_np()[0][0] == np.float32(data.vals[7, -1])


def _run(script, *args):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    return subprocess.run(
        [sys.executable, script, "--workload", CELL, "--seconds", "2",
         "--cpu-rehearsal", *args],
        capture_output=True, text=True, cwd=ROOT, timeout=300, env=env)


def _last_line(proc):
    err = "".join(l for l in proc.stderr.splitlines(True) if "cpu_aot_loader" not in l)
    assert proc.returncode == 0, err[-4000:]
    lines = proc.stdout.strip().splitlines()
    assert len(lines) == 1, "stdout holds the result line and nothing else"
    return json.loads(lines[0])


@pytest.mark.parametrize("traced", [0, 1])
def test_cpu_rehearsal_prints_a_line_the_checker_passes(traced):
    line = _last_line(_run(RUN, "--seed", "3000000029", "--trace", str(traced)))
    assert result_line.check(line, MAN, CELL, bool(traced)) == []
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and list(line)[-1] == "compared"
    assert set(line["compared"]) == {"malformed", "absent_mismatch"} | {
        f"rel_err.{p['name']}" for p in TRAFFIC["panels"]}
    if traced:
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert m["counters_compiles_in_window"] == 0
        assert m["counters_superblock_hit_pct"] == 100.0
        assert 0 < m["wide_reduce_pct"] <= 25.0  # one panel in four, less the coalesced
        assert "mxu_kernel_roofline" not in m  # no CPU peak
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    else:
        assert set(line["metrics"]) == {"query_p50_ms", "queries_per_s", "setup_s"}


def test_a_fourth_panel_that_is_high_is_not_correct():
    """``avg(avg_over_time)`` 1e-5 high — a hundredth of what the parent's
    f32 segment sum read on the chip — fails that panel and no other."""
    proc = _run(os.path.join(HERE, "high_fourth_panel.py"), "--seed", "78",
                "--trace", "0")
    line = _last_line(proc)
    assert line["correct"] is False and 0 < line["failed"] < line["attempted"]
    for p in TRAFFIC["panels"]:
        c = line["compared"][f"rel_err.{p['name']}"]
        assert (c["value"] > c["limit"]) == (p["name"] == "avg_avg_over_time"), p


def test_every_client_starts_on_a_panel_of_its_own():
    from benchmarks.chip import traffic

    reqs, walks = traffic.cycles(TRAFFIC, T0, T0 + 719 * 10_000)
    assert [r[0] for r in reqs] == [0, 1, 2, 3] and len({r[1] for r in reqs}) == 1
    assert [w[0] for w in walks] == [0, 1, 2, 3]
    assert all(sorted(w) == [0, 1, 2, 3] for w in walks)
    assert "first_query_panel" not in TRAFFIC
