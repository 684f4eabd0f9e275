#!/usr/bin/env python
"""CPU bench smoke gate (make bench-smoke): small bench.py runs on the
CPU backend must not regress p50 by more than 25% against the checked-in
floors (benchmarks/bench_smoke_floor.json), and must keep match=True
against the numpy oracles. One floor entry per workload — the north-star
``sum(rate(...))`` and the fused histogram/epilogue pipeline's
``histogram_quantile(0.99, sum by (le) (rate(..._bucket[5m])))``.

This is the perf analog of the golden plan tests: small enough to run in CI
(~30 s total), big enough that losing the fused single-dispatch path, the
shared-window hist kernel, the superblock cache, or the staging cache shows
up as a multiple, not a blip. Update a floor deliberately — in the same PR
as a justified perf change — never to paper over a regression.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
FLOOR_FILE = os.path.join(REPO, "benchmarks", "bench_smoke_floor.json")
REGRESSION_TOLERANCE = 0.25  # fail beyond floor * (1 + this)


def run_entry(entry: dict, extra_env: dict | None = None,
              cpu: bool = True) -> tuple[bool, str, dict | None]:
    """Run one floor entry's bench worker. Returns ``(ok, verdict,
    measurement)`` — the parsed worker JSON rides along so callers beyond
    the smoke gate (tools/attest.py embeds floor verdicts + measurements
    into the attestation artifact) don't re-run the workload.

    ``cpu=False`` (the attestation harness off the smoke gate) runs the
    child on the device jax finds, and the child's ``"backend"`` field
    names it; the smoke gate itself always pins cpu (its floors are CPU
    numbers). This parent never touches jax, so the child can hold the
    device."""
    env = dict(
        os.environ,
        FILODB_BENCH_SERIES=str(entry["series"]),
        FILODB_BENCH_RUNS=str(entry["runs"]),
        **{k: str(v) for k, v in (entry.get("env") or {}).items()},
        **{k: str(v) for k, v in (extra_env or {}).items()},
    )
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")]
        + (["--cpu"] if cpu else []),
        env=env, capture_output=True, text=True, cwd=REPO, timeout=600,
    )
    sys.stderr.write(proc.stderr[-2000:])
    lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
    name = entry["metric"]
    if not lines:  # a non-matching run exits 1 WITH its line; judged below
        return False, f"{name}: bench.py failed rc={proc.returncode}", None
    got = json.loads(lines[-1])
    if got.get("metric") != name:
        return False, (
            f"{name}: FAIL worker emitted metric {got.get('metric')!r} — "
            "floor entry and bench.py METRIC out of sync"
        ), got
    value = float(got["value"])
    if not got.get("match", False):
        return False, f"{name}: FAIL result does not match the numpy oracle", got
    if value <= 0:
        return False, f"{name}: FAIL no measurement", got
    if "qps_floor_min" in entry:
        # HIGHER is better (throughput workloads): fail when the measured
        # value drops >25% below the checked-in floor
        floor = float(entry["qps_floor_min"])
        limit = floor * (1.0 - REGRESSION_TOLERANCE)
        if value < limit:
            return False, (
                f"{name}: FAIL {value:.1f} qps regresses >25% vs floor "
                f"{floor} qps (limit {limit:.1f} qps)"
            ), got
        return True, (
            f"{name}: OK {value:.1f} qps above limit {limit:.1f} qps "
            f"(floor {floor} qps, phases {got.get('phases_ms')})"
        ), got
    limit = float(entry["p50_ms_floor"]) * (1.0 + REGRESSION_TOLERANCE)
    if value > limit:
        return False, (
            f"{name}: FAIL p50 {value:.2f}ms regresses >25% vs floor "
            f"{entry['p50_ms_floor']}ms (limit {limit:.2f}ms)"
        ), got
    return True, (
        f"{name}: OK p50 {value:.2f}ms within limit {limit:.2f}ms "
        f"(floor {entry['p50_ms_floor']}ms, phases {got.get('phases_ms')})"
    ), got


def main() -> int:
    with open(FLOOR_FILE) as f:
        floor = json.load(f)
    entries = floor["entries"] if "entries" in floor else [floor]
    ok = True
    verdicts = []
    for entry in entries:
        good, verdict, _got = run_entry(entry)
        ok = ok and good
        verdicts.append(verdict)
    print("bench-smoke: " + "; ".join(verdicts))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
