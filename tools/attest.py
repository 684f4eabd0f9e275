#!/usr/bin/env python
"""One-command hardware attestation (``make attest``).

A bare latency number is only trustworthy if the run can PROVE what
actually compiled, dispatched, and fell back. This command runs the
bench-smoke floor workloads + the MULTICHIP dryrun and emits ONE signed-off
``ATTEST_<backend>.json`` bundling:

- **platform inventory** — python/jax versions, device list (platform +
  kind), host facts — read in a child, so this parent never holds the
  device its workers need.
- **floor verdicts** — every benchmarks/bench_smoke_floor.json entry run
  through the same gate ``make bench-smoke`` applies (match-vs-oracle +
  floor), with the measurement embedded.
- **kernel-observatory snapshots** — each workload's per-executable
  registry (obs/kernels.py) captured via FILODB_KERNEL_SNAPSHOT: which
  fused executables compiled and dispatched, device p50/p99, which
  fallbacks fired, recompile storms. The PROOF half: "the fused path
  served this number" instead of "a number appeared".
- **MULTICHIP dryrun** — the sharded canonical query + hist_quantile
  executed end-to-end with the one-dispatch-across-the-mesh assertions
  (__graft_entry__.dryrun_multichip), with its own kernel snapshot.
- **verdict + digest** — pass/fail over all of the above and a sha256
  content digest (the sign-off: any later edit breaks it).

The floors run on the device jax finds, one child at a time; ``--backend
cpu`` (and ``--smoke``) pin the CPU backend. A child that reports ``cpu``
when CPU was not asked for FAILS the attestation — there is no degrade to
CPU.

Usage:
    python tools/attest.py                    # full run, ATTEST_<backend>.json
    python tools/attest.py --smoke            # fast machinery check (make bench-smoke)
    python tools/attest.py --only sum_rate_100k_series_range_query_p50
    python tools/attest.py --no-multichip --floor-file my_floors.json
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import bench_smoke  # noqa: E402 — sibling tool, shares the floor gate

ATTEST_VERSION = 1

# the artifact contract (doc/observability.md "Kernel & compile
# observatory" documents it; tests/test_kernel_obs.py validates against
# THIS table — one definition)
SCHEMA: dict[str, type] = {
    "version": int,
    "time": str,
    "backend": str,
    "platform": dict,
    "floors": list,
    "multichip": dict,
    "kernels": dict,
    "verdict": str,
    "digest": str,
}
FLOOR_FIELDS = ("metric", "ok", "verdict")


def validate_attestation(doc: dict) -> list[str]:
    """Schema check for an attestation artifact; returns violations."""
    out = []
    for field, typ in SCHEMA.items():
        if field not in doc:
            out.append(f"missing field {field!r}")
        elif not isinstance(doc[field], typ):
            out.append(
                f"field {field!r} is {type(doc[field]).__name__}, "
                f"want {typ.__name__}"
            )
    for i, fl in enumerate(doc.get("floors") or []):
        for f in FLOOR_FIELDS:
            if f not in fl:
                out.append(f"floors[{i}] missing {f!r}")
    if doc.get("verdict") not in ("pass", "fail"):
        out.append(f"verdict must be pass|fail, got {doc.get('verdict')!r}")
    body = {k: v for k, v in doc.items() if k != "digest"}
    want = hashlib.sha256(
        json.dumps(body, sort_keys=True).encode()
    ).hexdigest()
    if doc.get("digest") != want:
        out.append("digest does not match content")
    return out


def platform_inventory(cpu: bool, timeout_s: int = 90) -> dict:
    """Device/platform facts from a child (one process per chip: this
    parent stays off jax). ``cpu=False`` leaves the platform to jax so the
    inventory lists the devices the floors run on."""
    code = (
        "import json, os, platform, sys\n"
        "import jax\n"
        "print(json.dumps({\n"
        "  'python': sys.version.split()[0],\n"
        "  'jax': jax.__version__,\n"
        "  'platform': platform.platform(),\n"
        "  'hostname': platform.node(),\n"
        "  'cpu_count': os.cpu_count(),\n"
        "  'devices': [{'platform': d.platform, 'kind': d.device_kind,\n"
        "               'id': d.id} for d in jax.devices()],\n"
        "}))\n"
    )
    env = dict(os.environ)
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code], timeout=timeout_s,
            capture_output=True, text=True, env=env,
        )
        if proc.returncode == 0:
            return json.loads(proc.stdout.strip().splitlines()[-1])
        return {"error": f"probe rc={proc.returncode}: {proc.stderr[-400:]}"}
    except subprocess.TimeoutExpired:
        return {"error": f"platform probe timed out after {timeout_s}s"}


def _read_snapshot(path: str) -> dict | None:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def run_floors(entries: list[dict],
               cpu: bool = True) -> tuple[list[dict], dict]:
    """Run every floor entry with a kernel-snapshot capture; returns the
    floor verdicts (measurement + per-workload observatory totals embedded)
    and the aggregate kernel proof."""
    floors = []
    agg = {"dispatches": 0, "compiles": 0, "fused_families": set(),
           "fallbacks": {}, "storms": {}}
    for entry in entries:
        with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as tf:
            snap_path = tf.name
        try:
            ok, verdict, got = bench_smoke.run_entry(
                entry, extra_env={"FILODB_KERNEL_SNAPSHOT": snap_path},
                cpu=cpu,
            )
            snap = _read_snapshot(snap_path)
        finally:
            try:
                os.unlink(snap_path)
            except OSError:
                pass
        fl = {"metric": entry["metric"], "ok": bool(ok), "verdict": verdict,
              "measurement": got}
        if snap is not None:
            fl["kernels"] = {
                "totals": snap.get("totals"),
                "storms": (snap.get("kernels") or {}).get("storms", {}),
                "counters": snap.get("counters", {}),
            }
            tot = snap.get("totals") or {}
            agg["dispatches"] += int(tot.get("dispatches", 0))
            agg["compiles"] += int(tot.get("compiles", 0))
            agg["fused_families"].update(tot.get("fused_families", []))
            for k, v in (snap.get("counters") or {}).items():
                if k.startswith("filodb_fused_fallback"):
                    agg["fallbacks"][k] = agg["fallbacks"].get(k, 0) + v
            agg["storms"].update(
                (snap.get("kernels") or {}).get("storms", {})
            )
        floors.append(fl)
        print(f"attest: {verdict}", flush=True)
    agg["fused_families"] = sorted(agg["fused_families"])
    return floors, agg


def run_multichip(n_devices: int, timeout_s: int = 600) -> dict:
    """The MULTICHIP dryrun in a child, with its own kernel snapshot: the
    sharded canonical query + hist_quantile end-to-end, ONE dispatch each
    across the mesh (the dryrun asserts it; we record the proof)."""
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as tf:
        snap_path = tf.name
    code = (
        "import json, __graft_entry__ as g\n"
        f"g.dryrun_multichip({n_devices})\n"
        "from filodb_tpu.obs.kernels import KERNELS\n"
        f"json.dump({{'totals': KERNELS.totals(),"
        f" 'storms': KERNELS.snapshot()['storms']}},"
        f" open({snap_path!r}, 'w'))\n"
    )
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code], timeout=timeout_s,
            capture_output=True, text=True, cwd=REPO,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        snap = _read_snapshot(snap_path)
        out = {
            "ok": proc.returncode == 0,
            "devices": n_devices,
            "virtual_cpu": True,  # the dryrun forces a virtual CPU mesh
            "output": proc.stdout.strip()[-1500:],
        }
        if proc.returncode != 0:
            out["error"] = proc.stderr[-1500:]
        if snap is not None:
            out["kernels"] = snap
        return out
    except subprocess.TimeoutExpired:
        return {"ok": False, "devices": n_devices,
                "error": f"dryrun timed out after {timeout_s}s"}
    finally:
        try:
            os.unlink(snap_path)
        except OSError:
            pass


# the --smoke machinery check: one tiny canonical-query workload — proves
# the bench->snapshot->verdict->digest pipeline end to end in seconds
# without gating on a real floor (the real gate already ran in bench-smoke)
SMOKE_ENTRY = {
    "metric": "sum_rate_100k_series_range_query_p50",
    "series": 256,
    "runs": 1,
    "p50_ms_floor": 1e9,
    "env": {},
}


def build_artifact(floors: list[dict], agg: dict, multichip: dict,
                   platform: dict, backend: str) -> dict:
    floors_ok = bool(floors) and all(f["ok"] for f in floors)
    mc_ok = multichip.get("ok", False) if multichip.get("ran", True) else True
    fused_served = bool(agg.get("fused_families"))
    doc = {
        "version": ATTEST_VERSION,
        "time": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "backend": backend,
        "platform": platform,
        "floors": floors,
        "multichip": multichip,
        "kernels": {
            "proof": {
                "dispatches": agg.get("dispatches", 0),
                "compiles": agg.get("compiles", 0),
                "fused_families_dispatched": agg.get("fused_families", []),
                "fused_path_served": fused_served,
            },
            "fallbacks": agg.get("fallbacks", {}),
            "storms": agg.get("storms", {}),
        },
        "verdict": ("pass" if floors_ok and mc_ok and fused_served
                    else "fail"),
    }
    doc["digest"] = hashlib.sha256(
        json.dumps(doc, sort_keys=True).encode()
    ).hexdigest()
    return doc


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=None,
                    help="artifact path (default ATTEST_<backend>.json)")
    ap.add_argument("--floor-file", default=bench_smoke.FLOOR_FILE)
    ap.add_argument("--only", default=None,
                    help="comma-separated floor metrics to run")
    ap.add_argument("--no-multichip", action="store_true")
    ap.add_argument("--multichip-devices", type=int, default=8)
    ap.add_argument("--backend", choices=("auto", "cpu"), default="auto",
                    help="auto (default): the device jax finds — a floor "
                         "that then reports cpu fails the attestation; "
                         "cpu: pin the CPU backend")
    ap.add_argument("--smoke", action="store_true",
                    help="fast machinery check (one tiny workload, temp "
                         "artifact unless --out)")
    args = ap.parse_args(argv)

    if args.smoke:
        entries = [dict(SMOKE_ENTRY)]
    else:
        with open(args.floor_file) as f:
            floor = json.load(f)
        entries = floor["entries"] if "entries" in floor else [floor]
        if args.only:
            keep = {m.strip() for m in args.only.split(",")}
            entries = [e for e in entries if e["metric"] in keep]
            if not entries:
                print(f"attest: no floor entries match --only {args.only}")
                return 1

    cpu = args.smoke or args.backend == "cpu"
    platform = platform_inventory(cpu=cpu)
    floors, agg = run_floors(entries, cpu=cpu)
    if not cpu:
        for fl in floors:
            if (fl.get("measurement") or {}).get("backend") == "cpu":
                fl["ok"] = False
                fl["verdict"] += (" — FAIL ran on cpu, which was not asked "
                                  "for (--backend cpu pins it)")
                print(f"attest: {fl['verdict']}", flush=True)
    backend = next(
        (f["measurement"].get("backend") for f in floors
         if f.get("measurement") and f["measurement"].get("backend")),
        "cpu",
    )
    if args.no_multichip or args.smoke:
        multichip = {"ran": False, "ok": True,
                     "note": "skipped (--no-multichip/--smoke)"}
    else:
        multichip = {"ran": True, **run_multichip(args.multichip_devices)}

    doc = build_artifact(floors, agg, multichip, platform, backend)
    bad = validate_attestation(doc)
    if bad:
        print("attest: INTERNAL schema violations: " + "; ".join(bad))
        return 1

    if args.out:
        out_path = args.out
    elif args.smoke:
        out_path = os.path.join(tempfile.gettempdir(),
                                f"ATTEST_{backend}_smoke.json")
    else:
        out_path = os.path.join(REPO, f"ATTEST_{backend}.json")
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    n_ok = sum(1 for fl in floors if fl["ok"])
    print(
        f"attest: {doc['verdict'].upper()} — {n_ok}/{len(floors)} floors ok, "
        f"fused families {doc['kernels']['proof']['fused_families_dispatched']}"
        f", multichip "
        f"{'ok' if multichip.get('ok') else multichip.get('note', 'FAIL')}, "
        f"digest {doc['digest'][:12]}… -> {out_path}"
    )
    return 0 if doc["verdict"] == "pass" else 1


if __name__ == "__main__":
    sys.exit(main())
