#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json on the chip.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

ONE process holds the chip, as ``chip_smoke.py`` does: it starts the real
``FiloServer`` on the shipped ``config.py`` defaults, loads the
configuration's history made from ``--seed`` through
``TimeSeriesMemStore.ingest_routed``, sends the cell's queries until they are
warm (no compile, background pre-warm idle), then has a child process
(``loadgen.py``, standard library only) drive ``GET /api/v1/query_range`` in
closed loops for ``--seconds``. After the window: per-layer readings, the
device's peak memory, server shut down, then every timed answer is compared
with the plain numpy f64 reference (``references.py``), and the last line of
stdout is the result object. Nothing is written after it.

It FAILS (non-zero exit, no result line) when jax finds no TPU: the platform
is pinned before jax is imported. ``--cpu-rehearsal`` is the one explicit
switch for a tiny CPU run of the same steps; every line it prints says
``platform: cpu``, and so does its result line's ``device``.

Everything that belongs to one cell is data found by name: the cell in
``workloads/<cell>.json``, its configuration in ``configs/<config>.json``,
its per-layer metrics in ``layer_metrics/<metric>.json``; generators,
references and readers are functions looked up by the names those files give.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()  # set-up counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import urllib.parse  # noqa: E402
import urllib.request  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmarks.chip import (  # noqa: E402
    generators, readers, references, result_line, roofline, trace_reduce, traffic,
)

PREWARM_WAIT_S = 300.0
TRACE_AFTER_S = 3.0  # the traced sub-window opens this far into the window
WARM_PASSES = 6


class RunFailure(Exception):
    pass


def load_json(*parts) -> dict:
    try:
        return result_line.chip_json(*parts)
    except OSError as e:
        raise RunFailure(f"cannot read {os.path.join(*parts)}: {e}") from e


class Run:
    def __init__(self, args, jax, say):
        self.args, self.jax, self.say = args, jax, say
        self.man = result_line.manifest()
        self.cell = result_line.cell_of(self.man, args.workload)
        self.traffic = load_json("workloads", f"{args.workload}.json")
        self.config = load_json("configs", f"{self.cell['config']}.json")
        if args.cpu_rehearsal:
            self.traffic.update(self.traffic.get("rehearsal", {}))
            self.config.update(self.config.get("rehearsal", {}))
        self.window_ms = int(self.traffic["window_ms"])
        self.tmp = tempfile.mkdtemp(prefix="chipbench-")
        self._refs = {}   # (panel index, start_ms) -> reference answer
        self._bytes = {}  # start_ms -> roofline.min_bytes of that range

    # -- the server's own surfaces -------------------------------------------

    def get(self, path: str, **params) -> bytes:
        url = f"http://127.0.0.1:{self.port}{path}"
        if params:
            url += "?" + urllib.parse.urlencode(params)
        with urllib.request.urlopen(url, timeout=600) as r:
            return r.read()

    def debug(self, path: str, **params):
        out = json.loads(self.get(path, **params))
        if out.get("status") == "error":
            raise RunFailure(f"{path}: {str(out)[:300]}")
        return out.get("data", out)

    def counters(self) -> dict:
        return readers.parse_metrics(self.get("/metrics").decode())

    def prewarm_state(self) -> tuple[int, int]:
        """(eligible, done): recurrence-ring keys the server's background
        pre-warm will re-execute, and how many it has finished."""
        ring = self.debug("/debug/standing")["key_ring"]
        need = 1 if self.debug("/debug/kernels", limit=0)["storms"] else 3
        eligible = sum(1 for e in ring if e["count"] >= need
                       and (e.get("desc") or {}).get("promql"))
        return eligible, int(readers.total(self.counters(), "filodb_prewarm_total"))

    def wait_prewarm_idle(self) -> None:
        t0 = time.monotonic()
        while True:
            eligible, done = self.prewarm_state()
            if done >= eligible:
                waited = time.monotonic() - t0
                if waited > 1.0:
                    self.say(f"  waited {waited:.1f} s for the server's "
                             f"background pre-warm ({done} keys done)")
                return
            if time.monotonic() - t0 > PREWARM_WAIT_S:
                raise RunFailure(f"pre-warm never went idle: {done}/{eligible}")
            time.sleep(0.1)

    # -- set-up --------------------------------------------------------------

    def start_server(self) -> None:
        from filodb_tpu.ops import compile_cache
        from filodb_tpu.server import FiloServer

        cfg = {"http_port": 0}  # shipped defaults; only the port is ours
        if self.args.cpu_rehearsal:
            # the shipped 5 s pre-warm tick would be most of a rehearsal
            cfg["query"] = {"prewarm": {"interval_s": 0.25}}
        self.srv = FiloServer(cfg)
        self.port = self.srv.start()
        c = self.srv.config
        self.say(f"server: FiloServer on :{self.port}, shards={c['shards']} "
                 f"spread={c['spread']}; compile cache {compile_cache.cache_dir()}")
        if (c["shards"], c["spread"]) != (self.config["shards"], self.config["spread"]):
            raise RunFailure("the shipped defaults are not the configuration's "
                             f"{self.config['shards']} shards, spread {self.config['spread']}")

    def load(self) -> None:
        interval = int(self.config["interval_ms"])
        # the one input not from --seed: the newest scrape follows the wall
        # clock, because the server evicts by wall-clock retention
        self.t_last = int(time.time() * 1000) // interval * interval - interval
        self.t_first = self.t_last - (int(self.config["samples_per_series"]) - 1) * interval
        rng = np.random.default_rng(self.args.seed)
        t0 = time.perf_counter()
        make = generators.find(self.config["generator"])
        self.data = make(self.config, int(self.config["series"]), rng, self.t_first)
        t1 = time.perf_counter()
        got = self.data.load(self.srv.memstore, self.srv.spread)
        if got != self.data.n_samples:
            raise RunFailure(f"ingested {got} of {self.data.n_samples} samples")
        self.say(f"loaded {self.data.n_series} series, {got} samples of "
                 f"{self.config['metric']}: made in {t1 - t0:.1f} s, ingest_routed "
                 f"{time.perf_counter() - t1:.1f} s; t_last_ms={self.t_last}")

    def warm(self) -> float | None:
        """Send what the window will send until nothing compiles and the
        background pre-warm is idle. Returns the wall of the first query."""
        self.requests, self.walks = traffic.cycles(
            self.traffic, self.t_first, self.t_last)
        k = max(3, len(self.traffic["panels"]))
        # the requests that come just BEFORE each client's start: the window
        # opens mid-stream, and a range it will ask for is not left cached
        ahead = sorted({w[-j] for w in self.walks for j in range(1, min(k, len(w)) + 1)})
        first_s, self.first_answer = None, None
        name = self.traffic.get("first_query_panel")
        if name:
            p = [x["name"] for x in self.traffic["panels"]].index(name)
            i = next(i for i in ahead if self.requests[i][0] == p)
            t0 = time.perf_counter()
            body = self.get(self.requests[i][2])
            first_s = time.perf_counter() - t0
            self.first_answer = (i, body.decode("utf-8", "replace"))
            self.say(f"first query ({name}): {first_s:.3f} s")
        for _ in range(3):  # the pre-warm picks a key up at its third sight
            for i in ahead:
                self.get(self.requests[i][2])
        for _ in range(WARM_PASSES):
            self.wait_prewarm_idle()
            before = self.counters()
            for i in ahead:
                self.get(self.requests[i][2])
            compiles = (readers.total(self.counters(), "filodb_xla_compiles_total")
                        - readers.total(before, "filodb_xla_compiles_total"))
            eligible, done = self.prewarm_state()
            if compiles == 0 and done >= eligible:
                return first_s
        raise RunFailure(f"still compiling after {WARM_PASSES} warm passes")

    # -- the window ----------------------------------------------------------

    def window(self) -> None:
        traced = bool(self.args.trace)
        spec = {"port": self.port, "seconds": self.args.seconds,
                "cycles": [[self.requests[i][2] for i in w] for w in self.walks],
                "trace_requests": int(self.traffic["trace_requests"]) if traced else 0,
                "trace_after_s": min(TRACE_AFTER_S, self.args.seconds / 4)}
        spec_path = os.path.join(self.tmp, "spec.json")
        out_path = os.path.join(self.tmp, "out.json")
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        self.before = self.counters()
        child = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen.py"), spec_path, out_path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
        try:
            if traced:
                self.trace_sub_window(child)
            rc = child.wait(timeout=self.args.seconds + 300)
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
            child.stdin.close()
            child.stdout.close()
        self.after = self.counters()
        with open(out_path) as f:
            self.out = json.load(f)
        if self.args.requests_log:  # per request, for a look at one run by hand
            log = {"records": self.out["records"], "walks": self.walks,
                   "starts": [r[1] for r in self.requests],
                   "querylog": self.debug("/debug/querylog", limit=512)}
            os.makedirs(os.path.dirname(self.args.requests_log) or ".", exist_ok=True)
            with open(self.args.requests_log, "w") as f:
                json.dump(log, f)
        if rc != 0 or self.out["errors"]:
            raise RunFailure(f"load generator failed (rc {rc}): {self.out['errors']}")

    def expect(self, child, word: str) -> None:
        line = child.stdout.readline().strip()
        if line != word:
            raise RunFailure(f"load generator said {line!r}, expected {word!r}")

    def trace_sub_window(self, child) -> None:
        """Whole requests inside the trace: the child holds its clients at
        PAUSED, runs ``trace_requests`` each, and holds them at TRACED."""
        jax = self.jax
        self.expect(child, "PAUSED")
        self.at_paused = self.counters()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # keeps the file small
        opts.host_tracer_level = 1    # the bench_window annotation only
        trace_dir = os.path.join(self.tmp, "trace")
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            t0_wall, t0 = time.time(), time.perf_counter()
            with jax.profiler.TraceAnnotation(trace_reduce.MARKER):
                child.stdin.write("go\n")
                child.stdin.flush()
                self.expect(child, "TRACED")
            self.sub_window_s = time.perf_counter() - t0
        finally:
            jax.profiler.stop_trace()
        self.at_traced = self.counters()
        n = len(self.walks) * int(self.traffic["trace_requests"])
        recs = self.debug("/debug/querylog", limit=n)
        self.sub_querylog = [r for r in recs if r["time"] >= t0_wall]
        child.stdin.write("go\n")
        child.stdin.flush()
        files = [os.path.join(d, f) for d, _s, fs in os.walk(trace_dir)
                 for f in fs if f.endswith(".xplane.pb")]
        if len(files) != 1:
            raise RunFailure(f"the profiler left {len(files)} xplane files")
        self.trace_file = files[0]
        if self.args.keep_trace:
            os.makedirs(os.path.dirname(self.args.keep_trace) or ".", exist_ok=True)
            shutil.copyfile(files[0], self.args.keep_trace)

    def memory_peak(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.jax.devices()]
        return int(max(peaks))

    # -- after the window ----------------------------------------------------

    def judge(self) -> tuple[dict, list]:
        """Every timed answer against its reference. Returns the numbers
        compared, each with its limit, and per record whether it was right."""
        panels = self.traffic["panels"]
        answers = {}  # (request index, digest) -> comparison

        def judged(i: int, body: str, digest: str) -> dict:
            key = (i, digest)
            if key not in answers:
                p, start, _path = self.requests[i]
                grid = traffic.out_t(self.traffic, start)
                want = self.reference(p, start, grid)
                answers[key] = references.compare(
                    references.parse_matrix(body, grid), want)
            return answers[key]

        tally = references.Tally(panels)
        todo = [(self.walks[c][pos], status, self.out["bodies"][digest], digest)
                for c, pos, _s, _d, status, digest in self.out["records"]]
        n_timed = len(todo)
        if self.first_answer:
            todo.append((self.first_answer[0], 200, self.first_answer[1], "first"))
        right = [tally.add(panels[self.requests[i][0]],
                           judged(i, body, digest) if status == 200 else None)
                 for i, status, body, digest in todo]
        return tally.compared(), right[:n_timed]

    def reference(self, p: int, start: int, grid: np.ndarray) -> dict:
        key = (p, start)
        if key not in self._refs:
            panel = self.traffic["panels"][p]
            self._refs[key] = references.find(panel["reference"])(
                self.data, grid, self.window_ms, panel)
        return self._refs[key]

    def layer_metrics(self) -> tuple[dict, dict]:
        """(per-layer metrics of the line, the trace's summary)."""
        platform = self.jax.devices()[0].platform
        tr = trace_reduce.reduce_file(self.trace_file, platform)
        sub = self.out["sub"]
        in_sub = [r for r in self.out["records"]
                  if r[2] >= sub["start_released"] and r[3] <= sub["stop"]]
        self.say(f"trace: {os.path.getsize(self.trace_file)} B, lines "
                 f"{tr['lines_seen']}, {tr['n_events']} events on the ops line, "
                 f"{tr['n_programs']} programs run for {len(in_sub)} requests, "
                 f"marker {tr['marker_s']} s, busy {tr['busy_s']} s")
        span = (int(self.traffic["steps"]) - 1) * int(self.traffic["step_s"]) * 1000
        sub_bytes = 0
        for c, pos, *_ in in_sub:
            start = self.requests[self.walks[c][pos]][1]
            if start not in self._bytes:
                self._bytes[start] = roofline.min_bytes(
                    self.data, start, start + span, self.window_ms)
            sub_bytes += self._bytes[start]
        # host clocks and program spans: the stretches with the profiler off
        lat_ms = [1e3 * (r[3] - r[2]) for r in self.out["records"]
                  if r[3] <= sub["start"] or r[2] >= sub["stop_released"]]
        ctx = {"segments": [(self.before, self.at_paused), (self.at_traced, self.after)],
               "latencies_ms": lat_ms,
               "trace": tr, "sub_requests": len(in_sub),
               "sub_query_bytes": sub_bytes / max(1, len(in_sub)),
               "window_s": self.sub_window_s, "querylog": self.sub_querylog,
               "device_kind": self.jax.devices()[0].device_kind}
        if platform != "tpu":
            ctx["device_kind"] = None  # no peak for a CPU: the roofline stays out
        out = {}
        for name, unit in result_line.per_layer_of(self.man, self.args.workload).items():
            spec = load_json("layer_metrics", f"{name}.json")
            src = dict(spec["source"])
            reader = readers.find(src.pop("reader"))
            try:
                v = reader(ctx, **src)
            except KeyError as e:
                if platform == "tpu":
                    raise
                self.say(f"  {name}: not read on {platform}: {e}")
                v = None
            if v is not None:
                out[name] = {"value": float(v), "unit": unit}
        return out, tr

    def run(self) -> dict:
        args, jax = self.args, self.jax
        self.start_server()
        try:
            self.load()
            first_s = self.warm()
            self.say(f"set-up done {time.time() - T_PROCESS:.1f} s after process "
                     f"start; window of {args.seconds} s opens")
            self.window()
            peak = self.memory_peak()
        finally:
            self.srv.stop()
        out = self.out
        self.say(f"window closed: {len(out['records'])} requests in "
                 f"{out['window_s']:.3f} s; server stopped; comparing")
        t_ref = time.perf_counter()
        compared, right = self.judge()
        self.say(f"references and comparison: {time.perf_counter() - t_ref:.1f} s")
        lat_ms = [1e3 * (r[3] - r[2]) for r in out["records"]]
        d0 = jax.devices()[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(jax.devices()), "memory_peak_bytes": peak}
        line = {"correct": all(c["value"] <= c["limit"] for c in compared.values())
                and bool(right),
                "attempted": len(right), "failed": right.count(False)}
        if args.trace:
            line["metrics"], tr = self.layer_metrics()
            device["window_s"], device["busy_s"] = self.sub_window_s, tr["busy_s"]
            line["device"] = device
            line["breakdown"] = {"device_ops": tr["device_ops"],
                                 "idle_gaps": tr["idle_gaps"]}
        else:
            values = {
                "query_p50_ms": readers.percentile(lat_ms, 50),
                "query_p95_ms": readers.percentile(lat_ms, 95),
                "queries_per_s": right.count(True) / out["window_s"],
                "first_query_s": first_s,
                "setup_s": out["t0_wall"] - T_PROCESS,
            }
            line["metrics"] = {
                n: {"value": values[n], "unit": u} for n, u in
                result_line.end_to_end_of(self.man, args.workload).items()}
            line["device"] = device
            self.say(f"mean {statistics.fmean(lat_ms):.3f} ms over {len(lat_ms)} requests")
        line["compared"] = compared  # last, as the contract asks
        return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU backend, to debug the harness; "
                         "every line says platform: cpu")
    ap.add_argument("--requests-log", default="",
                    help="write every timed request and the querylog ring here")
    ap.add_argument("--keep-trace", default="",
                    help="copy the traced run's xplane file here")
    args = ap.parse_args(argv)

    want = "cpu" if args.cpu_rehearsal else "tpu"
    prefix = "[platform: cpu REHEARSAL] " if args.cpu_rehearsal else ""

    def say(text: str) -> None:
        for ln in text.split("\n"):
            print(prefix + ln, file=sys.stderr, flush=True)

    try:
        chips = result_line.cell_of(result_line.manifest(), args.workload)["chips"]
    except (OSError, KeyError, ValueError) as e:
        say(f"FAILED: {e}")
        return 2
    # pinned BEFORE jax is imported: with the platform named, a backend that
    # cannot initialize is an error, never a quiet drop to the CPU
    os.environ["JAX_PLATFORMS"] = want
    import jax

    devices = jax.devices()  # raises when the pinned platform has no device
    d0 = devices[0]
    if d0.platform != want or (want == "tpu" and len(devices) < chips):
        say(f"FAILED: jax found {len(devices)} {d0.platform} device(s); this cell "
            f"needs {chips} {want}")
        return 1
    say(f"platform: {d0.platform}  device_kind: {d0.device_kind}  devices: "
        f"{len(devices)}  jax {jax.__version__}  host cpus {os.cpu_count()}  "
        f"workload {args.workload}  seed {args.seed}  seconds {args.seconds}  "
        f"trace {args.trace}")
    run = None
    try:
        run = Run(args, jax, say)
        line = run.run()
        bad = result_line.check(line, run.man, args.workload, bool(args.trace))
        for reason in bad:
            say(f"FAILED: result line: {reason}")
        if bad:
            say("the line that failed: " + json.dumps(line))
            return 1
    except RunFailure as e:
        say(f"FAILED: {e}")
        return 1
    finally:
        if run is not None:
            shutil.rmtree(run.tmp, ignore_errors=True)
    for name, c in line["compared"].items():
        say(f"compared {name} = {c['value']} (limit {c['limit']})")
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    code = 1
    try:
        code = main()
    except BaseException:  # noqa: BLE001 — shown, then the hard exit below
        import traceback

        traceback.print_exc()
    finally:
        sys.stdout.flush()
        sys.stderr.flush()
        # no thread or atexit hook of the server or of jax writes after the line
        os._exit(code)
