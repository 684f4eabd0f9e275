"""Not a test: what test_scraped_cell.py and test_counters_slide_cell.py share.
One ``--cpu-rehearsal`` run of a cell in a child process (through ``run.py`` or
a script beside this file that plants a fault or sets the clock and then runs
it), its result line, and what every such line has to be."""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.chip import result_line

ROOT = result_line.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
RUN = os.path.join(ROOT, "benchmarks", "chip", "run.py")
MAN = result_line.manifest()


def run(script: str, cell: str, *args: str):
    """``script`` is ``RUN`` or the name of a file here; a window of 1 s."""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    return subprocess.run(
        [sys.executable, os.path.join(HERE, script), *args, "--workload", cell,
         "--seconds", "1", "--cpu-rehearsal"],
        capture_output=True, text=True, cwd=ROOT, timeout=300, env=env)


def last_line(proc) -> dict:
    err = "".join(l for l in proc.stderr.splitlines(True) if "cpu_aot_loader" not in l)
    assert proc.returncode == 0, err[-4000:]
    lines = proc.stdout.strip().splitlines()
    assert len(lines) == 1, "stdout holds the result line and nothing else"
    return json.loads(lines[0])


def whole_line(line: dict, cell: str, traffic: dict, traced) -> dict:
    """The checks every good line passes; returns {metric: value}."""
    assert result_line.check(line, MAN, cell, bool(traced)) == []
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and list(line)[-1] == "compared"
    assert set(line["compared"]) == {"malformed", "absent_mismatch"} | {
        f"rel_err.{p['name']}" for p in traffic["panels"]}
    m = {k: v["value"] for k, v in line["metrics"].items()}
    if traced:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        # no CPU trace has a modules line or a peak: the two device readings stay out
        assert set(m) == {n for n in traffic["layer_metrics"]
                          if not n.endswith(("_kernel_ms", "_kernel_roofline"))}
    else:
        assert set(m) == {"query_p50_ms", "queries_per_s", "setup_s"}
    return m


def newest_scrape_ms(proc) -> int:
    return int(proc.stderr.split("t_last_ms=")[1].split()[0])
