"""``run.py`` end to end as far as a CPU can show it: the tiny
``--cpu-rehearsal`` prints a last line its own checker passes and labels
itself cpu; no TPU, or a directory that holds only the benchmark, is a
failure with no result line; the control and a broken timed path come out
as not correct. Nothing here touches a TPU or describes a topology."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.chip import control, result_line

ROOT = result_line.ROOT
RUN = os.path.join(ROOT, "benchmarks", "chip", "run.py")
BROKEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "broken_run.py")
MAN = result_line.manifest()
CELLS = [w["name"] for w in MAN["workloads"]]
ONE_DEVICE = {"XLA_FLAGS": "--xla_force_host_platform_device_count=1"}


def _run(script, *args, cwd=ROOT, timeout=300):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(ONE_DEVICE)
    return subprocess.run([sys.executable, script, *args], capture_output=True,
                          text=True, cwd=cwd, timeout=timeout, env=env)


def _stderr(proc):
    return "".join(l for l in proc.stderr.splitlines(True)
                   if "cpu_aot_loader" not in l)[-4000:]


def _last_line(proc):
    assert proc.returncode == 0, _stderr(proc)
    lines = proc.stdout.strip().splitlines()
    assert len(lines) == 1, "stdout holds the result line and nothing else"
    return json.loads(lines[0])


@pytest.mark.parametrize("cell,traced", [
    ("hists.slide", 0), ("hists.slide", 1), ("hists.repeat", 0),
    ("hists.repeat", 1)])
def test_cpu_rehearsal_prints_a_line_the_checker_passes(cell, traced):
    proc = _run(RUN, "--workload", cell, "--seed", "3000000019", "--seconds", "2",
                "--trace", str(traced), "--cpu-rehearsal")
    line = _last_line(proc)
    assert result_line.check(line, MAN, cell, bool(traced)) == []
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "compared"
    for text in proc.stderr.splitlines():
        if "cpu_aot_loader" in text or not text.strip():
            continue
        assert text.startswith("[platform: cpu REHEARSAL] "), text
    # each number compared stands beside its limit at the end of stderr
    assert f"compared malformed = 0 (limit 0)" in proc.stderr
    if traced:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert line["metrics"]["compiles_in_window"]["value"] == 0
        hit = line["metrics"]["superblock_hit_pct"]["value"]
        assert hit == (0.0 if cell == "hists.slide" else 100.0)
        assert "fused_kernel_roofline" not in line["metrics"]  # no CPU peak


def test_no_tpu_is_a_failure_with_no_result_line():
    assert os.environ.get("JAX_PLATFORMS") == "cpu"  # which run.py must not obey
    proc = _run(RUN, "--workload", "hists.slide", "--seed", "1", "--seconds", "1",
                "--trace", "0")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "tpu" in proc.stderr.lower()


def test_alone_with_the_manifest_it_fails(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under paths."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in MAN["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(str(tmp_path / "benchmarks" / "chip" / "run.py"), "--workload",
                "hists.slide", "--seed", "1", "--seconds", "1", "--trace", "0",
                "--cpu-rehearsal", cwd=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.parametrize("fault,number", [
    ("scaled", "rel_err.hist_quantile"), ("truncated", "malformed")])
def test_a_broken_timed_path_is_not_correct(fault, number):
    proc = _run(BROKEN, fault, "--workload", "hists.repeat", "--seed", "77",
                "--seconds", "1", "--trace", "0", "--cpu-rehearsal")
    line = _last_line(proc)
    assert line["correct"] is False
    assert line["failed"] == line["attempted"] > 0
    c = line["compared"][number]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_not_correct(cell):
    """bfloat16 staging fails the cell's limits; the reference itself passes."""
    got = control.readings(cell, seed=11, rehearsal=True)
    assert not control.passed(got)
    assert any(k.startswith("rel_err.") and c["value"] > c["limit"]
               for k, c in got.items())
    same = control.readings(cell, seed=11, rehearsal=True, quantize=lambda x: x)
    assert control.passed(same) and all(c["value"] == 0 for c in same.values())
