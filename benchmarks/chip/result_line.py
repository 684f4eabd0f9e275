"""The result line: which metrics a cell's line must carry (from
BENCHMARK.json) and the check ``run.py`` makes of its own line before it
prints it. A line that fails is not printed; the run exits non-zero with the
reasons on earlier lines.
"""

from __future__ import annotations

import json
import math
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
KEYS = ("correct", "attempted", "failed", "metrics", "device")


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def chip_json(*parts) -> dict:
    """A data file of the benchmark: ``workloads/<cell>.json``,
    ``configs/<config>.json``, ``layer_metrics/<metric>.json``."""
    with open(os.path.join(ROOT, "benchmarks", "chip", *parts)) as f:
        return json.load(f)


def cell_of(man: dict, name: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"BENCHMARK.json has no workload {name!r}")


def end_to_end_of(man: dict, cell: str) -> dict:
    """{metric: unit} of the end-to-end metrics this cell reports."""
    return {m["name"]: m["unit"] for m in man["end_to_end"]
            if cell in m.get("workloads", [cell])}


def per_layer_of(man: dict, cell: str) -> dict:
    """{metric: unit} of the per-layer metrics this cell reports: those that
    list it, and those with no list whose ``moves`` this cell reports."""
    e2e = end_to_end_of(man, cell)
    return {m["name"]: m["unit"] for m in man["per_layer"]
            if (cell in m["workloads"] if "workloads" in m else m["moves"] in e2e)}


def check(line: dict, man: dict, cell: str, traced: bool) -> list[str]:
    """Reasons why ``line`` is not the contract's object for this cell."""
    bad = [f"key {k!r} is missing" for k in KEYS if k not in line]
    if bad:
        return bad
    if not isinstance(line["correct"], bool):
        bad.append("correct is not true or false")
    for k in ("attempted", "failed"):
        if not isinstance(line[k], int) or isinstance(line[k], bool) or line[k] < 0:
            bad.append(f"{k} is not a count")
    want = per_layer_of(man, cell) if traced else end_to_end_of(man, cell)
    metrics = line["metrics"]
    # a CPU rehearsal has no device trace and no peak to take a share of
    optional = set() if line["device"].get("platform") == "tpu" else {
        m["name"] for m in man["per_layer"] if m["source"] == "device_trace"}
    for name, unit in want.items():
        m = metrics.get(name)
        if not isinstance(m, dict):
            if name not in optional:
                bad.append(f"metric {name} is missing")
            continue
        v = m.get("value")
        if isinstance(v, bool) or not isinstance(v, (int, float)) or not math.isfinite(v):
            bad.append(f"metric {name} has no finite value")
        elif not traced and v <= 0 and line["correct"] is True:
            # (a run that is not correct may have completed nothing right)
            bad.append(f"end-to-end metric {name} is {v}: it may never be 0")
        elif (name.endswith("_roofline") or "mfu" in name.split("_")) and v > 100:
            bad.append(f"{name} reads {v} %: over 100, so the bytes are counted "
                       "too high or the time leaves out part of the work")
        if m.get("unit") != unit:
            bad.append(f"metric {name} has unit {m.get('unit')!r}, want {unit!r}")
    for name in metrics:
        if name not in want:
            bad.append(f"metric {name} is not one of this cell's "
                       f"{'per-layer' if traced else 'end-to-end'} metrics")
    dev = line["device"]
    for k in ("platform", "kind", "count", "memory_peak_bytes"):
        if k not in dev:
            bad.append(f"device.{k} is missing")
    if dev.get("platform") == "tpu" and not dev.get("memory_peak_bytes", 0) > 0:
        bad.append("device.memory_peak_bytes is not above 0")
    if traced:
        busy, window = dev.get("busy_s"), dev.get("window_s")
        if not all(isinstance(x, (int, float)) and not isinstance(x, bool)
                   for x in (busy, window)):
            bad.append("a traced line needs device.busy_s and device.window_s")
        elif not 0 < busy <= window:
            bad.append(f"device.busy_s {busy} is not above 0 and at most "
                       f"device.window_s {window}")
    return bad
