#!/usr/bin/env python3
"""Runs of one cell, one after another in one call, and their spreads — the
builder's tool for setting bounds and limits; the driver does not call it.

    python3 benchmarks/chip/measure.py --workload <cell> --seeds 1,2,3 \
        [--seconds S] [--trace 0|1] [--out DIR]

Every run is ``BENCHMARK.json``'s command in a process of its own. For each
metric it prints the values, the median and the spread as the contract
defines it: the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median; and for
each number compared, the largest reading and its limit.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def spread(values) -> float | None:
    if len(values) < 2 or not statistics.median(values):
        return None
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(statistics.median(values))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default="")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="pass run.py its rehearsal switch (to debug this tool)")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    seconds = args.seconds or man["run_seconds"]
    lines, rc_all = [], 0
    for seed in args.seeds.split(","):
        cmd = man["command"] + ["--workload", args.workload, "--seed", seed,
                                "--seconds", str(seconds), "--trace", str(args.trace)]
        if args.cpu_rehearsal:
            cmd.append("--cpu-rehearsal")
        t0 = time.time()
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        wall = time.time() - t0
        last = (p.stdout.strip().splitlines() or [""])[-1]
        try:
            line = json.loads(last)
        except ValueError:
            line = None
        ok = p.returncode == 0 and line is not None and line.get("correct") is True
        rc_all |= int(not ok)
        print(f"seed {seed}: rc {p.returncode} correct "
              f"{line and line.get('correct')} wall {wall:.1f} s", flush=True)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            stem = os.path.join(args.out, f"{args.workload}.t{args.trace}.{seed}")
            with open(stem + ".err", "w") as f:
                f.write(p.stderr)
            with open(stem + ".out", "w") as f:
                f.write(p.stdout)
        if not ok:
            print(p.stderr[-3000:], flush=True)
        if line is not None:
            line["_seed"], line["_wall_s"] = seed, wall
            lines.append(line)
    summary = {"workload": args.workload, "seconds": seconds, "trace": args.trace,
               "runs": len(lines), "metrics": {}, "compared": {}}
    for name in sorted({n for ln in lines for n in ln["metrics"]}):
        vals = [ln["metrics"][name]["value"] for ln in lines if name in ln["metrics"]]
        summary["metrics"][name] = {
            "values": vals, "median": statistics.median(vals), "spread": spread(vals)}
    for name in sorted({n for ln in lines for n in ln.get("compared", {})}):
        vals = [ln["compared"][name]["value"] for ln in lines]
        summary["compared"][name] = {"max": max(vals), "values": vals,
                                     "limit": lines[0]["compared"][name]["limit"]}
    summary["device"] = [ln["device"] for ln in lines]
    summary["wall_s"] = [ln["_wall_s"] for ln in lines]
    if lines and "breakdown" in lines[-1]:
        summary["breakdown"] = lines[-1]["breakdown"]
    print(json.dumps(summary), flush=True)
    if args.out:
        with open(os.path.join(
                args.out, f"{args.workload}.t{args.trace}.summary.json"), "a") as f:
            f.write(json.dumps(summary) + "\n")
    return rc_all


if __name__ == "__main__":
    sys.exit(main())
