#!/usr/bin/env python3
"""The control of ``correct``: the reference put in the program's place and
computed in the nearest precision below the one the configuration states
(the store serves f32 values; below that is bfloat16). It has to come out as
NOT correct by the cell's own comparison and limits.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 [--rehearsal]

Needs no chip and no server: it makes the cell's data from each seed at the
cell's own size, answers every request of the cell's cycle with the
reference run on values staged in bfloat16 (``references.py``: offsets from
each series' first sample, rounded; the arithmetic after that stays f64, so
this is the least such a change could cost), and compares those answers
with the f64 reference exactly as ``run.py`` does. One JSON line per seed:
each number compared, its limit, and whether the control passed (it must not).
A benchmark run never runs this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmarks.chip import generators, references, result_line, traffic  # noqa: E402


def bfloat16(x: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return x.astype(ml_dtypes.bfloat16).astype(np.float64)


def readings(workload: str, seed: int, rehearsal: bool = False,
             quantize=bfloat16, max_positions: int = 12) -> dict:
    """{number: {"value", "limit"}} of the control on one seed's data."""
    tr = result_line.chip_json("workloads", f"{workload}.json")
    config = result_line.chip_json("configs", f"{tr['config']}.json")
    if rehearsal:
        config.update(config.get("rehearsal", {}))
    interval = int(config["interval_ms"])
    t_first = 1_700_000_000_000 // interval * interval
    t_last = t_first + (int(config["samples_per_series"]) - 1) * interval
    data = generators.find(config["generator"])(
        config, int(config["series"]), np.random.default_rng(seed), t_first)
    starts = traffic.positions(tr, t_first, t_last)
    pick = np.random.default_rng(seed).permutation(len(starts))[:max_positions]
    tally = references.Tally(tr["panels"])
    for start in (starts[i] for i in sorted(pick)):
        grid = traffic.out_t(tr, start)
        for panel in tr["panels"]:
            fn = references.find(panel["reference"])
            want = fn(data, grid, int(tr["window_ms"]), panel)
            got = fn(data, grid, int(tr["window_ms"]), panel, quantize)
            tally.add(panel, references.compare(got, want))
    return tally.compared()


def passed(compared: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    rc = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        compared = readings(args.workload, seed, args.rehearsal)
        ok = passed(compared)
        rc |= int(ok)  # a control that passes is the fault
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_passed": ok, "compared": compared}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
