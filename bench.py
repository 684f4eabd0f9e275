"""North-star benchmark (BASELINE.md): p50 latency of a 100k-series
``sum(rate(http_requests_total[5m]))`` range query, TPU engine vs a strong
vectorized-numpy CPU implementation of the identical computation (stand-in
for the reference's JVM+SIMD path — QueryInMemoryBenchmark.scala workload
shape scaled to the driver's 100k-series target).

Runs ONE workload (FILODB_BENCH_WORKLOAD) once, in this process, on the
device jax finds (``--cpu`` pins the CPU backend) and prints ONE JSON line:
{"metric", "value", "unit", "vs_baseline", "backend", "match", ...}.
value = p50 host wall (ms) of the full query path (PromQL parse -> plan ->
exec -> kernels -> result on the host) with warm device-staged windows;
"backend" is ``jax.devices()[0].platform`` — read it before reading the
value; vs_baseline = numpy_p50 / value (higher is better). Exit code is
non-zero when the result does not match the numpy oracle.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

N_SERIES = int(os.environ.get("FILODB_BENCH_SERIES", 100_000))
# workload: "sum_rate" (the north-star scalar query), "hist_quantile"
# (the fused histogram/epilogue pipeline: histogram_quantile(0.99,
# sum by (le) (rate(..._bucket[5m]))) over native [T, B] histograms),
# "ingest_impact" (warm canonical query p50 under a live 10-batches/s
# ingest stream vs its own idle baseline — the ratio the incremental
# superblock extension exists to hold near 1.0), or "fused_mesh"
# (single-device vs mesh-sharded fused p50 on a forced 8-device mesh:
# the sharded superblock's one-dispatch path, doc/perf.md "Mesh-sharded
# fused path"; value = sharded p50, vs_baseline = scaling ratio), or
# "standing_refresh" (registered standing query's delta-maintained
# live-edge refresh vs the pre-standing cold dashboard poll of the same
# sliding grid, both under live ingest — doc/operations.md "Standing
# queries & recording rules"; value = cold_p50 / standing_p50), or
# "failover_storm" (16-client query storm over an RF=2 replica cluster
# with one node killed mid-window — doc/robustness.md "Replicated shard
# plane"; value = during-kill qps, match = zero failures + bit-equal)
WORKLOAD = os.environ.get("FILODB_BENCH_WORKLOAD", "sum_rate")
# the ONE metric name per workload — emitted by both the success and error
# JSON paths, and matched against benchmarks/bench_smoke_floor.json entries
METRIC = {
    "hist_quantile": "hist_quantile_range_query_p50",
    "ingest_impact": "ingest_impact_on_query",
    "fused_mesh": "fused_mesh_sharded_query_p50",
    "concurrent_qps": "concurrent_qps_16clients_20k",
    "fused_jitter": "fused_jitter_holes_ratio",
    "standing_refresh": "standing_refresh_speedup",
    "index_regex": "index_regex_lookups_1000k",
    "query_hicard": "query_hicard_2000_of_8000_qps",
    "long_range_quantile": "long_range_quantile_30d_p50",
    "failover_storm": "failover_storm_qps_2k",
    "render_2m": "render_2m_stream_msamples",
    "mixed_cost_storm": "mixed_cost_storm_cheap_retained",
}.get(WORKLOAD, "sum_rate_100k_series_range_query_p50")
# concurrent_qps: client thread count, per-mode measurement window, and the
# batching window handed to the batched engine (the knob under test)
QPS_CLIENTS = int(os.environ.get("FILODB_BENCH_CLIENTS", 16))
QPS_DURATION_S = float(os.environ.get("FILODB_BENCH_QPS_DURATION_S", 6.0))
QPS_BATCH_WINDOW_MS = float(os.environ.get("FILODB_BENCH_BATCH_WINDOW_MS", 200.0))
# fused_mesh: virtual mesh width on the CPU backend (real accelerators use
# every visible device)
MESH_DEVICES = int(os.environ.get("FILODB_BENCH_MESH_DEVICES", 8))
# per-sample scrape-timestamp jitter as a fraction of the interval (e.g. 0.05
# = +/-5%): exercises the near-regular MXU path (ops/mxu_jitter.py) instead
# of the exact-shared-grid path
JITTER = float(os.environ.get("FILODB_BENCH_JITTER", 0.0))
N_SAMPLES = 720  # 2h @ 10s
INTERVAL_MS = 10_000
BASE = 1_600_000_000_000
WINDOW_MS = 300_000
STEP_S = 60.0
START_S = (BASE + 400_000) / 1000
# ingest_impact queries the LIVE EDGE: the range reaches past the newest
# sample so the streamed appends land inside it (the superblock must
# extend, not restage); other workloads keep the fully-covered range
MAX_APPEND_BATCHES = 600  # ingest_impact: 1 sample/series per batch
END_S = (
    (BASE + (N_SAMPLES + MAX_APPEND_BATCHES + 20) * INTERVAL_MS) / 1000
    if WORKLOAD == "ingest_impact"
    else (BASE + N_SAMPLES * INTERVAL_MS - 200_000) / 1000
)
N_SHARDS = 8
TIMED_RUNS = int(os.environ.get("FILODB_BENCH_RUNS", 15))


def build_memstore(jitter=None, hole_frac=0.0, phase_ms=0):
    """100k counter series across 8 shards, ingested through the normal path
    (bulk per-series ingestion; generation is vectorized). ``jitter``
    overrides the FILODB_BENCH_JITTER env fraction; ``hole_frac`` drops
    that fraction of interior scrapes per series (different slots per
    series — the missing-scrape grid); ``phase_ms`` shifts the nominal grid
    so it never lands a slot exactly on the 5m-aligned staging boundary
    (where jitter would clip it for SOME series and flip the grid class)."""
    from filodb_tpu.core.records import SeriesBatch
    from filodb_tpu.core.schemas import (
        Dataset, METRIC_TAG, PROM_COUNTER, shard_for,
    )
    from filodb_tpu.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.memstore.shard import StoreConfig

    jit = JITTER if jitter is None else jitter
    rng = np.random.default_rng(42)
    ts = BASE + phase_ms + np.arange(N_SAMPLES, dtype=np.int64) * INTERVAL_MS
    ms = TimeSeriesMemStore(StoreConfig(max_chunk_size=N_SAMPLES))
    ms.setup(Dataset("prometheus"), range(N_SHARDS))
    t0 = time.time()
    # vectorized value generation in blocks to bound memory
    blk = 10_000
    for b0 in range(0, N_SERIES, blk):
        n = min(blk, N_SERIES - b0)
        incr = rng.uniform(0, 10, size=(n, N_SAMPLES))
        vals = np.cumsum(incr, axis=1) + 1e9
        if jit > 0:
            dev = np.rint(
                rng.uniform(-jit, jit, size=(n, N_SAMPLES)) * INTERVAL_MS
            ).astype(np.int64)
        for i in range(n):
            tags = {
                METRIC_TAG: "http_requests_total",
                "_ws_": "demo",
                "_ns_": "App-2",
                "instance": f"host-{b0 + i}",
                # medium-cardinality dimension for grouped dashboard panels
                # (the concurrent_qps workload's by-variants)
                "zone": f"z{(b0 + i) % 8}",
            }
            shard = shard_for(tags, spread=3, num_shards=N_SHARDS)
            row_ts = ts + dev[i] if jit > 0 else ts
            row_vals = vals[i]
            if hole_frac > 0:
                keep = np.ones(N_SAMPLES, bool)
                keep[rng.choice(
                    np.arange(1, N_SAMPLES - 1),
                    max(1, int(hole_frac * N_SAMPLES)), replace=False,
                )] = False
                row_ts, row_vals = row_ts[keep], row_vals[keep]
            ms.shard("prometheus", shard).ingest_series(
                SeriesBatch(PROM_COUNTER, tags, row_ts, {"count": row_vals})
            )
    sys.stderr.write(
        f"ingest: {N_SERIES} series x {N_SAMPLES} samples in {time.time()-t0:.1f}s"
        + (f" (jitter +/-{jit:.0%}, holes {hole_frac:.1%})\n"
           if jit > 0 or hole_frac > 0 else "\n")
    )
    return ms, ts


N_BUCKETS = 12  # PROM_DEFAULT scheme width (11 finite bounds + Inf)


def build_memstore_hist():
    """Native cumulative histograms (N_SERIES series x N_SAMPLES x
    N_BUCKETS) across 8 shards — the canonical SRE latency workload."""
    from filodb_tpu.core.histograms import PROM_DEFAULT
    from filodb_tpu.core.records import SeriesBatch
    from filodb_tpu.core.schemas import (
        Dataset, METRIC_TAG, PROM_HISTOGRAM, shard_for,
    )
    from filodb_tpu.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.memstore.shard import StoreConfig

    rng = np.random.default_rng(42)
    ts = BASE + np.arange(N_SAMPLES, dtype=np.int64) * INTERVAL_MS
    ms = TimeSeriesMemStore(StoreConfig(max_chunk_size=N_SAMPLES))
    ms.setup(Dataset("prometheus"), range(N_SHARDS))
    les = PROM_DEFAULT.bounds()
    t0 = time.time()
    blk = 2_000
    for b0 in range(0, N_SERIES, blk):
        n = min(blk, N_SERIES - b0)
        incr = rng.poisson(2.0, size=(n, N_SAMPLES, N_BUCKETS)).astype(np.float64)
        incr[..., -1] = incr.sum(-1)  # +Inf bucket grows with everything
        hist = np.cumsum(np.cumsum(incr, axis=2), axis=1)
        count = hist[..., -1]
        total = np.cumsum(rng.uniform(0, 5, size=(n, N_SAMPLES)), axis=1)
        for i in range(n):
            tags = {
                METRIC_TAG: "http_request_latency",
                "_ws_": "demo",
                "_ns_": "App-2",
                "instance": f"host-{b0 + i}",
            }
            shard = shard_for(tags, spread=3, num_shards=N_SHARDS)
            ms.shard("prometheus", shard).ingest_series(SeriesBatch(
                PROM_HISTOGRAM, tags, ts,
                {"sum": total[i], "count": count[i], "h": hist[i]},
                bucket_les=les,
            ))
    sys.stderr.write(
        f"ingest: {N_SERIES} hist series x {N_SAMPLES} samples x "
        f"{N_BUCKETS} buckets in {time.time()-t0:.1f}s\n"
    )
    return ms, ts


def cpu_baseline_hist(ms, ts):
    """Strong CPU oracle for the hist_quantile workload: vectorized f64
    numpy per-bucket extrapolated rate -> bucket-wise sum across series ->
    histogram_quantile interpolation, identical semantics to
    ops/hist_kernels (per-bucket extrapolation, no zero cap; quantile
    interpolation with the +Inf top-bucket rule). Series are processed in
    blocks accumulating the [J, B] bucket sums, so memory stays bounded at
    100k-series scale."""
    from filodb_tpu.core.histograms import PROM_DEFAULT

    Q = 0.99
    les = PROM_DEFAULT.bounds()
    num_steps = int((END_S - START_S) // STEP_S) + 1
    out_t = (np.int64(START_S * 1000)
             + np.arange(num_steps, dtype=np.int64) * int(STEP_S * 1000))
    t0g = ts
    hi1 = np.searchsorted(t0g, out_t, side="right")
    lo1 = np.searchsorted(t0g, out_t - WINDOW_MS, side="right")
    cnt = hi1 - lo1
    T = len(t0g)
    lo_c = np.minimum(lo1, T - 1)
    hi_c = np.minimum(hi1 - 1, T - 1)
    tf = t0g[lo_c].astype(np.float64) / 1e3
    tl = t0g[hi_c].astype(np.float64) / 1e3
    sampled = tl - tf
    dur_start = tf - (out_t / 1e3 - WINDOW_MS / 1e3)
    dur_end = out_t / 1e3 - tl
    avg_dur = sampled / np.maximum(cnt - 1, 1)
    thresh = avg_dur * 1.1
    ds = np.where(dur_start >= thresh, avg_dur / 2, dur_start)
    de = np.where(dur_end >= thresh, avg_dur / 2, dur_end)
    factor = np.where(
        cnt >= 2, (sampled + ds + de) / np.maximum(sampled, 1e-30), np.nan
    )  # [J], shared by every series/bucket (shared regular grid)

    parts = [
        p for sh in ms.shards("prometheus") for p in sh.partitions.values()
    ]

    def run():
        bucket_sum = np.zeros((num_steps, len(les)), dtype=np.float64)
        blk = 4_000
        for b0 in range(0, len(parts), blk):
            H = np.stack([
                parts[i].samples_in_range(
                    int(t0g[0]), int(t0g[-1]), "h")[1]
                for i in range(b0, min(b0 + blk, len(parts)))
            ])  # [s, T, B] cumulative
            dlt = H[:, hi_c] - H[:, lo_c]  # [s, J, B]
            bucket_sum += np.nansum(
                dlt * factor[None, :, None] / (WINDOW_MS / 1e3), axis=0
            )
        # histogram_quantile interpolation over the summed buckets
        total = bucket_sum[:, -1]
        rank = Q * total
        meets = bucket_sum >= rank[:, None]
        idx = np.argmax(meets, axis=1)
        idx = np.where(meets.any(1), idx, len(les) - 1)
        c_hi = np.take_along_axis(bucket_sum, idx[:, None], axis=1)[:, 0]
        c_lo = np.where(
            idx > 0,
            np.take_along_axis(
                bucket_sum, np.maximum(idx - 1, 0)[:, None], axis=1)[:, 0],
            0.0,
        )
        le_hi = les[idx]
        le_lo = np.where(idx > 0, les[np.maximum(idx - 1, 0)],
                         0.0 if les[0] > 0 else -np.inf)
        frac = (rank - c_lo) / np.maximum(c_hi - c_lo, 1e-30)
        val = le_lo + (le_hi - le_lo) * frac
        val = np.where(idx == len(les) - 1, les[-2], val)
        return np.where((total > 0) & np.isfinite(total), val, np.nan)

    ref = run()
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        out = run()
        times.append(time.perf_counter() - t0)
    return float(np.median(times) * 1e3), ref


def cpu_baseline(ms, ts):
    """Strong CPU implementation: vectorized f64 numpy sum(rate) over the
    same data — a best-case stand-in for the reference's chunked-iterator +
    Rust SIMD CPU path. Handles per-series (jittered) timestamps with
    row-offset batched searchsorted; the shared-grid case uses one
    searchsorted for all series."""
    series_ts, series_v = [], []
    for sh in ms.shards("prometheus"):
        for part in sh.partitions.values():
            t, v = part.samples_in_range(int(ts[0] - INTERVAL_MS), int(ts[-1] + INTERVAL_MS), "count")
            series_ts.append(t)
            series_v.append(v)
    vals = np.stack(series_v)  # [S, T] f64
    tmat = np.stack(series_ts)  # [S, T] i64
    shared = not (tmat != tmat[0]).any()
    num_steps = int((END_S - START_S) // STEP_S) + 1
    out_t = (np.int64(START_S * 1000) + np.arange(num_steps, dtype=np.int64) * int(STEP_S * 1000))
    S, T = vals.shape

    def run():
        # reset correction (vectorized prefix)
        drops = np.where(vals[:, 1:] < vals[:, :-1], vals[:, :-1], 0.0)
        corr = np.concatenate([np.zeros((vals.shape[0], 1)), np.cumsum(drops, axis=1)], axis=1)
        cv = vals + corr
        if shared:
            # one 1-D searchsorted + column fancy-indexing for ALL series:
            # the strongest CPU form of the shared-grid workload (r02 form —
            # benchmark-integrity contract, VERDICT r3 weak #3: the baseline
            # must not silently pay the per-row gather cost here)
            t0 = tmat[0]
            hi1 = np.searchsorted(t0, out_t, side="right")
            lo1 = np.searchsorted(t0, out_t - WINDOW_MS, side="right")
            cnt = (hi1 - lo1)[None, :]
            lo_c = np.minimum(lo1, T - 1)
            hi_c = np.minimum(hi1 - 1, T - 1)
            tf = (t0[lo_c].astype(np.float64) / 1e3)[None, :]
            tl = (t0[hi_c].astype(np.float64) / 1e3)[None, :]
            vf = cv[:, lo_c]
            vl = cv[:, hi_c]
            raw_f = vals[:, lo_c]
        else:
            stride = np.int64(1) << 42
            row_off = (np.arange(S, dtype=np.int64) * stride)[:, None]
            flat = (tmat + row_off).ravel()
            hi = np.searchsorted(flat, (out_t[None, :] + row_off).ravel(), side="right")
            lo = np.searchsorted(flat, ((out_t - WINDOW_MS)[None, :] + row_off).ravel(), side="right")
            hi = hi.reshape(S, -1) - np.arange(S)[:, None] * T
            lo = lo.reshape(S, -1) - np.arange(S)[:, None] * T
            cnt = hi - lo
            tf = np.take_along_axis(tmat, np.minimum(lo, T - 1), 1).astype(np.float64) / 1e3
            tl = np.take_along_axis(tmat, np.minimum(hi - 1, T - 1), 1).astype(np.float64) / 1e3
            vf = np.take_along_axis(cv, np.minimum(lo, T - 1), 1)
            vl = np.take_along_axis(cv, np.minimum(hi - 1, T - 1), 1)
            raw_f = np.take_along_axis(vals, np.minimum(lo, T - 1), 1)
        dlt = vl - vf
        sampled = tl - tf
        dur_start = tf - (out_t / 1e3 - WINDOW_MS / 1e3)[None, :]
        dur_end = (out_t / 1e3)[None, :] - tl
        avg_dur = sampled / np.maximum(cnt - 1, 1)
        with np.errstate(divide="ignore", invalid="ignore"):
            dur_zero = np.where(dlt > 0, sampled * (raw_f / np.maximum(dlt, 1e-30)), np.inf)
            ds = np.minimum(dur_start, np.where(raw_f >= 0, dur_zero, np.inf))
            thresh = avg_dur * 1.1
            ds = np.where(ds >= thresh, avg_dur / 2, ds)
            de = np.where(dur_end >= thresh, avg_dur / 2, dur_end)
            factor = (sampled + ds + de) / np.maximum(sampled, 1e-30)
            rate = np.where(cnt >= 2, dlt * factor / (WINDOW_MS / 1e3), np.nan)
        return np.nansum(rate, axis=0)

    ref = run()
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        out = run()
        times.append(time.perf_counter() - t0)
    return float(np.median(times) * 1e3), ref


def _span_phase_ms(trace, out: dict) -> None:
    """Accumulate per-phase durations from the query's span tree.

    Phases (doc/perf.md): lookup/stage under ``fused:stage`` (index lookup +
    superblock build, split out as fused:lookup when present), ``dispatch``
    from the fused/kernel spans, ``merge`` from the partial-merge root when
    the reference tree ran. ``transfer`` is measured by the caller around
    the device->host fetch."""
    if trace is None:
        return

    def kernel_ms(sp) -> float:
        own = sp.duration_ms if sp.name.startswith("kernel:") else 0.0
        return own + sum(kernel_ms(c) for c in sp.children)

    name = trace.name
    if name.startswith("fused:lookup"):
        out["lookup"] = out.get("lookup", 0.0) + trace.duration_ms
    elif name.startswith("fused:stage"):
        out["stage"] = out.get("stage", 0.0) + trace.duration_ms
    elif name.startswith("fused:dispatch") or name.startswith("kernel:"):
        out["dispatch"] = out.get("dispatch", 0.0) + trace.duration_ms
    elif name in ("ReduceAggregateExec", "AggregatePresentExec"):
        child_ms = sum(c.duration_ms for c in trace.children)
        out["merge"] = out.get("merge", 0.0) + max(
            trace.duration_ms - child_ms, 0.0
        )
    elif name == "SelectRawPartitionsExec":
        # the leaf span covers staging AND its folded transformers' kernel
        # dispatches; attribute the kernel subtree to dispatch (handled by
        # the kernel: branch when recursion reaches it), not to stage
        out["stage"] = out.get("stage", 0.0) + max(
            trace.duration_ms - kernel_ms(trace), 0.0
        )
    for c in trace.children:
        _span_phase_ms(c, out)


def _enable_compile_cache():
    # persistent compile cache: the cold stage+compile warmup survives
    # process restarts (placement: ops/compile_cache.cache_dir)
    from filodb_tpu.ops.compile_cache import enable_compile_cache

    enable_compile_cache()


def tpu_query(ms):
    from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine

    _enable_compile_cache()
    # default engine: the planner fuses the multi-shard query into ONE
    # compiled dispatch over a device-resident superblock
    # (FusedAggregateExec; doc/perf.md) — for hist_quantile that one program
    # is hist rate -> per-bucket segment-sum -> quantile interpolation
    engine = QueryEngine(ms, "prometheus", PlannerParams())
    q = (
        "histogram_quantile(0.99, "
        "sum by (le) (rate(http_request_latency_bucket[5m])))"
        if WORKLOAD == "hist_quantile"
        else "sum(rate(http_requests_total[5m]))"
    )

    def run():
        res = engine.query_range(q, START_S, END_S, STEP_S)
        # force full materialization to host (honest end-to-end latency)
        t_f = time.perf_counter()
        out = [np.asarray(g.values_np()) for g in res.grids]
        return res, out, time.perf_counter() - t_f

    t0 = time.perf_counter()
    res, out, _tf = run()  # compile + stage + cache warm
    warmup_s = time.perf_counter() - t0
    sys.stderr.write(f"warmup (stage+compile): {warmup_s:.1f}s\n")
    times = []
    phases: dict = {}
    for i in range(TIMED_RUNS):
        t0 = time.perf_counter()
        res, out, transfer_s = run()
        times.append(time.perf_counter() - t0)
        # steady-state attribution from the LAST warm run's trace
        phases = {}
        _span_phase_ms(res.trace, phases)
        phases["transfer"] = transfer_s * 1e3
    vals = res.grids[0].values_np()[0]
    phases = {k: round(v, 3) for k, v in sorted(phases.items())}
    sys.stderr.write(f"phases_ms={json.dumps(phases)}\n")
    return float(np.median(times) * 1e3), vals, res, warmup_s, phases


def cpu_oracle_ragged(ms):
    """numpy f64 sum(rate) oracle that tolerates RAGGED per-series sample
    counts (dropped scrapes) — the per-series form of cpu_baseline's math,
    used by the fused_jitter workload's match check."""
    num_steps = int((END_S - START_S) // STEP_S) + 1
    out_t = (np.int64(START_S * 1000)
             + np.arange(num_steps, dtype=np.int64) * int(STEP_S * 1000))
    acc = np.zeros(num_steps, dtype=np.float64)
    for sh in ms.shards("prometheus"):
        for part in sh.partitions.values():
            ts, v = part.samples_in_range(
                int(out_t[0] - WINDOW_MS), int(out_t[-1]), "count"
            )
            if not len(ts):
                continue
            v = v.astype(np.float64)
            drops = np.where(v[1:] < v[:-1], v[:-1], 0.0)
            cv = v + np.concatenate([[0.0], np.cumsum(drops)])
            T = len(ts)
            hi = np.searchsorted(ts, out_t, side="right")
            lo = np.searchsorted(ts, out_t - WINDOW_MS, side="right")
            cnt = hi - lo
            lo_c = np.minimum(lo, T - 1)
            hi_c = np.minimum(hi - 1, T - 1)
            tf = ts[lo_c].astype(np.float64) / 1e3
            tl = ts[hi_c].astype(np.float64) / 1e3
            vf, vl, raw_f = cv[lo_c], cv[hi_c], v[lo_c]
            dlt = vl - vf
            sampled = tl - tf
            dur_start = tf - (out_t / 1e3 - WINDOW_MS / 1e3)
            dur_end = out_t / 1e3 - tl
            avg_dur = sampled / np.maximum(cnt - 1, 1)
            with np.errstate(divide="ignore", invalid="ignore"):
                dur_zero = np.where(
                    dlt > 0, sampled * (raw_f / np.maximum(dlt, 1e-30)),
                    np.inf,
                )
                ds = np.minimum(
                    dur_start, np.where(raw_f >= 0, dur_zero, np.inf)
                )
                thresh = avg_dur * 1.1
                ds = np.where(ds >= thresh, avg_dur / 2, ds)
                de = np.where(dur_end >= thresh, avg_dur / 2, dur_end)
                factor = (sampled + ds + de) / np.maximum(sampled, 1e-30)
                rate = np.where(
                    cnt >= 2, dlt * factor / (WINDOW_MS / 1e3), np.nan
                )
            acc += np.nan_to_num(rate, nan=0.0)
    return acc


def run_benchmark_fused_jitter():
    """Warm canonical-query p50 on jitter5pct and jitter+holes grids vs the
    regular-grid fused path — the jitter-tolerant fused kernels
    (doc/perf.md "Jitter-tolerant fused path") exist to hold these ratios
    near 1.0x (they measured 1.70x / 4.85x on the multi-pass general path).

    value = p50(jitter+holes) / p50(regular) (unit "x", LOWER is better —
    the smoke floor gates it); vs_baseline = the inverse; phases_ms carries
    all three p50s and both ratios. match = each variant agrees with the
    ragged numpy oracle, the superblock classifies into the EXPECTED grid
    class, AND the warm query stays exactly ONE kernel dispatch on the
    jittered variants (losing the jitter/masked fused variants flips
    match before it shows as latency)."""
    from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine
    from filodb_tpu.testkit import kernel_dispatch_total

    _enable_compile_cache()
    q = "sum(rate(http_requests_total[5m]))"
    variants = (
        ("regular", 0.0, 0.0),
        ("jitter5pct", 0.05, 0.0),
        ("jitter_holes", 0.05, 0.01),
    )
    expected_grid = {"regular": "regular", "jitter5pct": "jitter",
                     "jitter_holes": "holes"}
    ok = True
    warmup_s = 0.0
    engines = {}
    for label, jit, holes in variants:
        ms, _ts = build_memstore(
            jitter=jit, hole_frac=holes, phase_ms=INTERVAL_MS // 2
        )
        engine = QueryEngine(ms, "prometheus", PlannerParams())

        def run(engine=engine):
            res = engine.query_range(q, START_S, END_S, STEP_S)
            for g in res.grids:
                np.asarray(g.values_np())
            return res

        t0 = time.perf_counter()
        run()  # stage + compile + cache warm
        warmup_s += time.perf_counter() - t0
        before = kernel_dispatch_total()
        res = run()
        single = kernel_dispatch_total() - before == 1
        grid = {e.get("grid") for e in ms._superblock_cache.snapshot()}
        grid_ok = expected_grid[label] in grid
        oracle = cpu_oracle_ragged(ms)
        vals = res.grids[0].values_np()[0]
        n = min(len(vals), len(oracle))
        with np.errstate(invalid="ignore"):
            match = bool(np.allclose(vals[:n], oracle[:n], rtol=5e-3))
        ok = ok and match and single and grid_ok
        sys.stderr.write(
            f"{label}: single_dispatch={single} grid={sorted(grid)} "
            f"(want {expected_grid[label]}) match={match}\n"
        )
        engines[label] = (ms, run)
    # timed rounds INTERLEAVE the three variants so container noise hits
    # all of them equally, and the reported ratios are MEDIANS OF PER-ROUND
    # ratios: a noise burst inflates every variant of its round, so the
    # round's ratio stays honest, where a ratio of across-round medians
    # swings 2x with scheduler luck on a shared 2-vCPU box
    times: dict = {label: [] for label, _, _ in variants}
    for _ in range(TIMED_RUNS):
        for label, _, _ in variants:
            t0 = time.perf_counter()
            engines[label][1]()
            times[label].append(time.perf_counter() - t0)
    p50 = {label: float(np.median(ts) * 1e3) for label, ts in times.items()}
    for label in p50:
        sys.stderr.write(f"{label}: p50={p50[label]:.2f}ms\n")
    del engines
    reg = np.asarray(times["regular"])
    jitter_ratio = float(np.median(np.asarray(times["jitter5pct"]) / reg))
    holes_ratio = float(np.median(np.asarray(times["jitter_holes"]) / reg))
    import jax

    backend = jax.devices()[0].platform
    sys.stderr.write(
        f"jitter5pct={jitter_ratio:.2f}x jitter+holes={holes_ratio:.2f}x "
        f"vs regular (match={ok})\n"
    )
    return {
        "metric": METRIC,
        "value": round(holes_ratio, 3),
        "unit": "x",
        "vs_baseline": round(1.0 / holes_ratio, 3) if holes_ratio else 0.0,
        "backend": backend,
        "series": N_SERIES,
        "match": bool(ok),
        "warmup_s": round(warmup_s, 2),
        "phases_ms": {
            "regular_p50": round(p50["regular"], 3),
            "jitter_p50": round(p50["jitter5pct"], 3),
            "holes_p50": round(p50["jitter_holes"], 3),
            "jitter_ratio_x": round(jitter_ratio, 3),
            "holes_ratio_x": round(holes_ratio, 3),
        },
    }


def run_benchmark_ingest_impact():
    """Warm canonical query p50 under a live ingest stream vs idle.

    One 1-sample-per-series batch every 100 ms (the benchmarks/run.py
    QueryAndIngest cadence) lands INSIDE the query's live-edge range, so
    every batch overlaps the cached superblock: the interval-aware
    maintenance path must EXTEND it in place for the ratio to stay near
    1.0x (invalidate-and-restage measured 2.07x). value = busy_p50 /
    idle_p50 (unit "x"); match = final post-stream query vs the numpy
    oracle over the final store contents."""
    import threading

    from filodb_tpu.core.records import RecordBatch
    from filodb_tpu.core.schemas import METRIC_TAG, PROM_COUNTER

    ms, ts = build_memstore()
    from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine

    _enable_compile_cache()
    engine = QueryEngine(ms, "prometheus", PlannerParams())
    q = "sum(rate(http_requests_total[5m]))"

    def run_query():
        res = engine.query_range(q, START_S, END_S, STEP_S)
        return res, [np.asarray(g.values_np()) for g in res.grids]

    t0 = time.perf_counter()
    run_query()  # compile + stage + cache warm
    warmup_s = time.perf_counter() - t0
    idle = []
    for _ in range(TIMED_RUNS):
        t0 = time.perf_counter()
        run_query()
        idle.append(time.perf_counter() - t0)
    # MEAN, not median (same as benchmarks/run.py's dt_busy/dt_idle): the
    # maintenance cost under ingest lands on the one query per batch that
    # absorbs the append — a median over many runs hides it entirely,
    # while the mean is exactly "amortized query cost under the stream"
    idle_ms = float(np.mean(idle) * 1e3)

    # the ingest stream: deterministic, pre-derived tags, values monotone
    # above every series' build-time maximum (no artificial resets)
    # tag sets must match build_memstore EXACTLY (zone included): a differing
    # set would mint NEW series instead of appending to the existing ones
    tags_list = [
        {METRIC_TAG: "http_requests_total", "_ws_": "demo", "_ns_": "App-2",
         "instance": f"host-{i}", "zone": f"z{i % 8}"}
        for i in range(N_SERIES)
    ]
    stop = threading.Event()
    ingested = [0]

    def ingester():
        b = 0
        while not stop.is_set() and b < MAX_APPEND_BATCHES:
            t = BASE + (N_SAMPLES + b) * INTERVAL_MS
            vals = np.full(N_SERIES, 1e9 + 10.0 * (N_SAMPLES + b + 1))
            batch = RecordBatch(
                PROM_COUNTER, np.full(N_SERIES, t, np.int64),
                {"count": vals}, tags_list,
            )
            ingested[0] += ms.ingest_routed("prometheus", batch, spread=3)
            b += 1
            stop.wait(0.1)

    th = threading.Thread(target=ingester)
    th.start()
    busy = []
    try:
        for _ in range(TIMED_RUNS):
            t0 = time.perf_counter()
            run_query()
            busy.append(time.perf_counter() - t0)
    finally:
        stop.set()
        th.join()
    assert ingested[0] > 0, "ingester must actually run during the window"
    busy_ms = float(np.mean(busy) * 1e3)

    # correctness of the maintained superblock: final query vs the numpy
    # oracle over the FINAL store (appended region included). Steps whose
    # windows reach past the final head have no samples: the query side is
    # rate()-NaN there while the oracle's nansum over an all-NaN window
    # collapses to 0.0, so the comparison is restricted to steps at or
    # before the head (where both are finite).
    res, _out = run_query()
    n_appended = ingested[0] // N_SERIES
    ts_full = BASE + np.arange(N_SAMPLES + n_appended, dtype=np.int64) * INTERVAL_MS
    _cpu_ms, cpu_vals = cpu_baseline(ms, ts_full)
    tpu_vals = res.grids[0].values_np()[0]
    n = min(len(tpu_vals), len(cpu_vals))
    step_ts = (np.int64(START_S * 1000)
               + np.arange(n, dtype=np.int64) * int(STEP_S * 1000))
    ok_steps = np.isfinite(cpu_vals[:n]) & (step_ts <= ts_full[-1])
    with np.errstate(invalid="ignore"):
        ok = bool(ok_steps.any()) and bool(np.allclose(
            tpu_vals[:n][ok_steps], cpu_vals[:n][ok_steps], rtol=5e-3
        ))
    import jax

    backend = jax.devices()[0].platform
    ratio = busy_ms / idle_ms
    sys.stderr.write(
        f"idle_mean={idle_ms:.2f}ms busy_mean={busy_ms:.2f}ms "
        f"impact={ratio:.2f}x ingested={ingested[0]} match={ok}\n"
    )
    return {
        "metric": METRIC,
        "value": round(ratio, 3),
        "unit": "x",
        "vs_baseline": round(idle_ms / busy_ms, 2),
        "backend": backend,
        "series": N_SERIES,
        "match": bool(ok),
        "warmup_s": round(warmup_s, 2),
        "phases_ms": {"idle_mean": round(idle_ms, 3),
                      "busy_mean": round(busy_ms, 3)},
    }


def run_benchmark_fused_mesh():
    """Single-device fused vs mesh-sharded fused p50 of the canonical query.

    On the CPU backend this forces an 8-virtual-device mesh
    (XLA_FLAGS=--xla_force_host_platform_device_count, the MULTICHIP dryrun
    contract) — the scaling ratio there measures sharding OVERHEAD (8
    virtual devices time-slice the same cores), so the smoke floor gates
    the sharded p50, not the ratio; on real multi-chip hardware the same
    workload reports the near-linear scaling number. Also asserts the warm
    sharded query stays exactly ONE dispatch and matches the numpy oracle."""
    # force the virtual mesh BEFORE the first jax backend init (same
    # defense as __graft_entry__.dryrun_multichip — shared helper)
    from filodb_tpu.config import force_virtual_devices

    force_virtual_devices(MESH_DEVICES)
    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")

    ms, ts = build_memstore()
    from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine
    from filodb_tpu.parallel.mesh import make_mesh

    _enable_compile_cache()
    n_dev = min(MESH_DEVICES, len(jax.devices()))
    single = QueryEngine(ms, "prometheus", PlannerParams())
    sharded = QueryEngine(
        ms, "prometheus", PlannerParams(mesh=make_mesh(jax.devices()[:n_dev]))
    )
    q = "sum(rate(http_requests_total[5m]))"

    def p50_of(engine):
        def run():
            res = engine.query_range(q, START_S, END_S, STEP_S)
            out = [np.asarray(g.values_np()) for g in res.grids]
            return res, out

        t0 = time.perf_counter()
        run()  # stage + compile + cache warm
        warm_s = time.perf_counter() - t0
        times = []
        res = None
        for _ in range(TIMED_RUNS):
            t0 = time.perf_counter()
            res, _out = run()
            times.append(time.perf_counter() - t0)
        return float(np.median(times) * 1e3), res, warm_s

    from filodb_tpu.testkit import kernel_dispatch_total

    single_ms, _res_s, warm_single = p50_of(single)
    sharded_ms, res, warm_sharded = p50_of(sharded)
    before = kernel_dispatch_total()
    res = sharded.query_range(q, START_S, END_S, STEP_S)
    single_dispatch = kernel_dispatch_total() - before == 1
    cpu_ms, cpu_vals = cpu_baseline(ms, ts)
    tpu_vals = res.grids[0].values_np()[0]
    n = min(len(tpu_vals), len(cpu_vals))
    ok = bool(np.allclose(tpu_vals[:n], cpu_vals[:n], rtol=5e-3))
    scaling = single_ms / sharded_ms if sharded_ms > 0 else 0.0
    backend = jax.devices()[0].platform
    sys.stderr.write(
        f"single_p50={single_ms:.2f}ms sharded_p50={sharded_ms:.2f}ms "
        f"({n_dev} devices) scaling={scaling:.2f}x match={ok} "
        f"single_dispatch={single_dispatch}\n"
    )
    return {
        "metric": METRIC,
        "value": round(sharded_ms, 3),
        "unit": "ms",
        "vs_baseline": round(scaling, 3),
        "backend": backend,
        "devices": n_dev,
        "series": N_SERIES,
        "match": bool(ok and single_dispatch),
        "warmup_s": round(warm_single + warm_sharded, 2),
        "phases_ms": {"single_p50": round(single_ms, 3),
                      "sharded_p50": round(sharded_ms, 3),
                      "scaling_x": round(scaling, 3)},
    }


def run_benchmark_concurrent_qps():
    """N client threads hammering ONE hot superblock with VARIED dashboard
    queries (windows 2-5m x group-by variants over the same selector — the
    shape the engine-level identical-query single-flight can NOT collapse),
    cross-query batching on vs off. This is the workload the ROADMAP's
    ~222 qps / flat-beyond-16-clients number describes; the dispatch
    scheduler (query/scheduler.py) exists to move it.

    value = batched-mode throughput (qps, HIGHER is better — the smoke
    floor gates it via qps_floor_min); vs_baseline = batched/unbatched
    throughput ratio; phases_ms carries both modes' p50/p99 per-query
    latency and raw qps. match = per-variant batched results agree with
    the unbatched engine (allclose; the batched engine's plans stage an
    aligned superblock range, so counter-correction f32 rounding may
    differ in ulps from the unbatched engine's narrower block)."""
    import threading

    ms, _ts = build_memstore()
    from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine

    _enable_compile_cache()
    batched = QueryEngine(
        ms, "prometheus",
        PlannerParams(batch_window_ms=QPS_BATCH_WINDOW_MS,
                      batch_max=max(QPS_CLIENTS, 2)),
    )
    unbatched = QueryEngine(ms, "prometheus", PlannerParams())
    # the 16 panels of one dashboard: same selector, varied group-bys (all
    # landing in one pow2 group-count bucket so they coalesce) x varied
    # windows — distinct PromQL strings, so the engine-level identical-query
    # single-flight cannot collapse them; only cross-query batching can
    bys = [" by (zone)", " by (zone,_ns_)", " by (zone,_ws_)",
           " by (zone,_ns_,_ws_)"]
    wins = ["5m", "4m", "3m", "2m"]
    variants = [
        f"sum{bys[i % len(bys)]} "
        f"(rate(http_requests_total[{wins[(i // len(bys)) % len(wins)]}]))"
        for i in range(QPS_CLIENTS)
    ]

    def rows(res):
        return {
            tuple(sorted(l.items())): np.asarray(v)
            for g in res.grids for l, v in zip(g.labels, g.values_np())
        }

    # warmup + parity: every variant once per engine (stage + compile the
    # per-variant programs), then one full-width concurrent batched round
    # so the pow2-padded batched executable is compiled before timing
    ok = True
    for q in variants:
        ru = rows(unbatched.query_range(q, START_S, END_S, STEP_S))
        rb = rows(batched.query_range(q, START_S, END_S, STEP_S))
        if ru.keys() != rb.keys():
            ok = False
            continue
        for k in ru:
            na, nb = np.isnan(ru[k]), np.isnan(rb[k])
            if not (na == nb).all() or not np.allclose(
                ru[k][~na], rb[k][~nb], rtol=5e-3
            ):
                ok = False

    def measure(engine):
        lat: list[list[float]] = [[] for _ in range(QPS_CLIENTS)]
        start_gate = threading.Barrier(QPS_CLIENTS + 1)
        stop_at = [0.0]

        def client(i):
            q = variants[i]
            start_gate.wait()
            while time.perf_counter() < stop_at[0]:
                t0 = time.perf_counter()
                res = engine.query_range(q, START_S, END_S, STEP_S)
                # force materialization: latency must include the device
                # work, not just the async enqueue
                for g in res.grids:
                    np.asarray(g.values_np())
                lat[i].append(time.perf_counter() - t0)

        threads = [
            threading.Thread(target=client, args=(i,))
            for i in range(QPS_CLIENTS)
        ]
        for t in threads:
            t.start()
        stop_at[0] = time.perf_counter() + QPS_DURATION_S
        t_begin = time.perf_counter()
        start_gate.wait()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t_begin
        flat = [x for l in lat for x in l]
        if not flat:
            return 0.0, 0.0, 0.0
        return (
            len(flat) / elapsed,
            float(np.percentile(flat, 50) * 1e3),
            float(np.percentile(flat, 99) * 1e3),
        )

    # pre-compile the pow2 batch widths the run will see (group sizes
    # fluctuate as clients desync; a mid-measurement XLA compile would
    # poison p99 and qps) by running fixed-width concurrent rounds, then
    # one full free-running round
    def width_round(n, offset=0):
        gate = threading.Barrier(n)

        def one(i):
            gate.wait()
            batched.query_range(variants[offset + i], START_S, END_S, STEP_S)

        ths = [threading.Thread(target=one, args=(i,)) for i in range(n)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()

    for n in (2, 3, 4):
        width_round(min(n, QPS_CLIENTS))
    pre = measure(batched)
    sys.stderr.write(f"batched warm round: {pre[0]:.0f} qps\n")
    un_qps, un_p50, un_p99 = measure(unbatched)
    b_qps, b_p50, b_p99 = measure(batched)
    import jax

    backend = jax.devices()[0].platform
    speedup = b_qps / un_qps if un_qps > 0 else 0.0
    sys.stderr.write(
        f"clients={QPS_CLIENTS} unbatched={un_qps:.0f}qps "
        f"(p50={un_p50:.1f}ms p99={un_p99:.1f}ms) batched={b_qps:.0f}qps "
        f"(p50={b_p50:.1f}ms p99={b_p99:.1f}ms) speedup={speedup:.2f}x "
        f"match={ok}\n"
    )
    return {
        "metric": METRIC,
        "value": round(b_qps, 1),
        "unit": "qps",
        "vs_baseline": round(speedup, 3),
        "backend": backend,
        "series": N_SERIES,
        "clients": QPS_CLIENTS,
        "match": bool(ok and b_qps > 0),
        "phases_ms": {
            "batched_qps": round(b_qps, 1),
            "unbatched_qps": round(un_qps, 1),
            "batched_p50": round(b_p50, 2),
            "batched_p99": round(b_p99, 2),
            "unbatched_p50": round(un_p50, 2),
            "unbatched_p99": round(un_p99, 2),
        },
    }


def run_benchmark_mixed_cost_storm():
    """Device-second admission under a mixed-cost tenant storm
    (doc/operations.md "Admission control"): a cheap tenant (demo/App-1,
    64 series, 5m sum(rate)) shares the node with a monster tenant
    (demo/App-2, the full series set, 30m high-cardinality group-by).
    The monster floods; its tight device-second quota must shed it with a
    cost-derived Retry-After while the cheap tenant keeps its throughput.

    value = cheap-tenant qps during the flood / cheap-tenant solo qps
    (retained fraction, HIGHER is better — the smoke floor gates >= 0.8);
    match = cheap tenant saw zero sheds/errors, the monster was admitted
    at least once (it has SOME budget) and shed repeatedly, and every
    shed carried a positive predicted cost and a drain-derived
    Retry-After."""
    import threading

    ms, ts = build_memstore()
    # the cheap tenant's 64 series ride in the same memstore under its own
    # namespace — metering.tenant_of_plan resolves ws/ns from the selector
    from filodb_tpu.core.records import SeriesBatch
    from filodb_tpu.core.schemas import METRIC_TAG, PROM_COUNTER, shard_for
    rng = np.random.default_rng(7)
    for i in range(64):
        tags = {
            METRIC_TAG: "http_requests_total",
            "_ws_": "demo",
            "_ns_": "App-1",
            "instance": f"cheap-host-{i}",
            "zone": f"z{i % 8}",
        }
        shard = shard_for(tags, spread=3, num_shards=N_SHARDS)
        vals = np.cumsum(rng.uniform(0, 10, size=N_SAMPLES)) + 1e9
        ms.shard("prometheus", shard).ingest_series(
            SeriesBatch(PROM_COUNTER, tags, ts, {"count": vals})
        )
    from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine
    from filodb_tpu.query.scheduler import (
        AdmissionController, AdmissionRejected,
    )

    _enable_compile_cache()
    cheap_q = ('sum(rate(http_requests_total'
               '{_ws_="demo",_ns_="App-1"}[5m]))')
    monster_q = ('sum by (instance) (rate(http_requests_total'
                 '{_ws_="demo",_ns_="App-2"}[30m]))')
    # cheap tenant: effectively unmetered; monster: ~one full-burst query
    # per flood window, everything past that sheds on predicted cost
    ctl = AdmissionController({
        "demo/App-1": {"rate_device_s": 50.0, "burst_device_s": 50.0},
        "demo/App-2": {"rate_device_s": 0.005, "burst_device_s": 0.05},
    })
    # warm engine (no admission): compiles both shapes and teaches the
    # cost model each fingerprint's realized device-seconds WITHOUT
    # draining the gated buckets, so the flood starts from a full burst
    warm = QueryEngine(ms, "prometheus", PlannerParams())
    gated = QueryEngine(ms, "prometheus", PlannerParams(admission=ctl))
    for _ in range(3):
        warm.query_range(cheap_q, START_S, END_S, STEP_S)
    for _ in range(2):
        warm.query_range(monster_q, START_S, END_S, STEP_S)

    cheap_errors = [0]

    def cheap_phase(duration_s):
        n = [0]
        stop_at = time.perf_counter() + duration_s

        def client():
            while time.perf_counter() < stop_at:
                try:
                    res = gated.query_range(cheap_q, START_S, END_S, STEP_S)
                    for g in res.grids:
                        np.asarray(g.values_np())
                    n[0] += 1
                except Exception:
                    cheap_errors[0] += 1

        t0 = time.perf_counter()
        th = threading.Thread(target=client)
        th.start()
        th.join()
        return n[0] / (time.perf_counter() - t0)

    sheds: list[tuple[float, float, str]] = []
    admits = [0]

    def monster_client(stop_evt):
        while not stop_evt.is_set():
            try:
                gated.query_range(monster_q, START_S, END_S, STEP_S)
                admits[0] += 1
            except AdmissionRejected as e:
                sheds.append((
                    float(getattr(e, "retry_after_s", 0.0)),
                    float(getattr(e, "predicted_cost_s", 0.0)),
                    str(getattr(e, "outcome", "")),
                ))
                time.sleep(0.02)  # the flood ignores Retry-After
            except Exception:
                admits[0] += 0  # engine errors count as neither

    # interleaved solo/flood rounds: container qps drifts between phases,
    # so a single before/after pair is noise-bound — medians over
    # alternating rounds compare like with like (the fused_jitter idiom)
    rounds = 3
    dur = max(QPS_DURATION_S / rounds, 1.0)
    solo_rounds, flood_rounds = [], []
    for _ in range(rounds):
        solo_rounds.append(cheap_phase(dur))
        stop_evt = threading.Event()
        monsters = [
            threading.Thread(target=monster_client, args=(stop_evt,))
            for _ in range(2)
        ]
        for t in monsters:
            t.start()
        flood_rounds.append(cheap_phase(dur))
        stop_evt.set()
        for t in monsters:
            t.join()

    solo_qps = float(np.median(solo_rounds))
    flood_qps = float(np.median(flood_rounds))
    retained = flood_qps / solo_qps if solo_qps > 0 else 0.0
    cost_derived = bool(sheds) and all(
        r > 0 and c > 0 and o == "shed_rate" for r, c, o in sheds
    )
    ok = (
        cheap_errors[0] == 0 and admits[0] >= 1 and len(sheds) > 0
        and cost_derived and retained > 0
    )
    import jax

    backend = jax.devices()[0].platform
    sys.stderr.write(
        f"solo={solo_qps:.0f}qps flood={flood_qps:.0f}qps "
        f"retained={retained:.2f} monster_admits={admits[0]} "
        f"sheds={len(sheds)} cost_derived={cost_derived} "
        f"cheap_errors={cheap_errors[0]}\n"
    )
    return {
        "metric": METRIC,
        "value": round(retained, 3),
        "unit": "ratio",
        "vs_baseline": round(retained, 3),
        "backend": backend,
        "series": N_SERIES,
        "match": ok,
        "phases_ms": {
            "solo_qps": round(solo_qps, 1),
            "flood_qps": round(flood_qps, 1),
            "monster_admits": admits[0],
            "monster_sheds": len(sheds),
            "shed_retry_after_max_s": round(
                max((r for r, _, _ in sheds), default=0.0), 3),
            "shed_predicted_cost_max_s": round(
                max((c for _, c, _ in sheds), default=0.0), 4),
        },
    }


def run_benchmark_standing_refresh():
    """Standing-query live-edge refresh cost: the delta path vs a forced
    full re-dispatch of the same grid, under a live ingest stream
    (doc/operations.md "Standing queries & recording rules").

    A registered standing query refreshes through the delta path
    (aligned pinned staging range -> the ONE superblock entry extends in
    place under the append; suffix-only re-dispatch + retained-partial
    splice) while a 1-sample/series/100ms stream lands at the live edge
    (the ingest_impact cadence). The baseline is what the same dashboard
    panel pays TODAY without the standing engine: a plain query_range
    poll of the same sliding grid, whose moving end resolves to a NEW
    superblock cache key every refresh — full restage + full-grid
    dispatch (cross-query batching off, the default). value =
    cold_poll_p50 / standing_refresh_p50 (unit "x", HIGHER is better).
    match = after the stream quiesces, the delta-maintained partials are
    BIT-EQUAL to a forced full re-evaluation of the same grid AND the
    delta path actually ran (falling back to full re-dispatch per refresh
    collapses the ratio toward the warm-full line and flips match)."""
    import threading

    from filodb_tpu.core.records import RecordBatch
    from filodb_tpu.core.schemas import METRIC_TAG, PROM_COUNTER
    from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine
    from filodb_tpu.standing import StandingEngine

    ms, _ts = build_memstore()
    _enable_compile_cache()
    engine = QueryEngine(ms, "prometheus", PlannerParams())
    q = "sum by (zone) (rate(http_requests_total[5m]))"
    step_ms = 15_000
    span_ms = 5_400_000  # the "last 90m" dashboard panel (J = 361 steps)
    batches = [0]
    edge_clock = lambda: (  # noqa: E731 — tracks the ingest head
        BASE + (N_SAMPLES + batches[0]) * INTERVAL_MS + 5_000
    ) / 1e3
    se = StandingEngine(engine, {"default_span_ms": span_ms},
                        clock=edge_clock)
    sq = se.register(q, step_ms)
    twin = se.register(q, step_ms)
    assert sq.mode == "delta", sq.mode_reason
    t0 = time.perf_counter()
    se.refresh(sq)  # compile + stage + superblock warm
    se.refresh(twin, force_full=True)
    warmup_s = time.perf_counter() - t0

    tags_list = [
        {METRIC_TAG: "http_requests_total", "_ws_": "demo", "_ns_": "App-2",
         "instance": f"host-{i}", "zone": f"z{i % 8}"}
        for i in range(N_SERIES)
    ]
    stop = threading.Event()

    def ingester():
        while not stop.is_set() and batches[0] < MAX_APPEND_BATCHES:
            b = batches[0]
            t = BASE + (N_SAMPLES + b) * INTERVAL_MS
            vals = np.full(N_SERIES, 1e9 + 10.0 * (N_SAMPLES + b + 1))
            ms.ingest_routed("prometheus", RecordBatch(
                PROM_COUNTER, np.full(N_SERIES, t, np.int64),
                {"count": vals}, tags_list,
            ), spread=3)
            batches[0] = b + 1
            stop.wait(0.1)

    # the cold-poll baseline warms its jit/compile state once; its
    # superblock can never stay warm (that is the point being measured)
    engine.query_range(q, (BASE + 600_000) / 1e3,
                       (BASE + 600_000 + span_ms) / 1e3, step_ms / 1e3)
    th = threading.Thread(target=ingester)
    th.start()
    delta_s, cold_s = [], []

    def paced(measure, out, last_b):
        """One measurement per fresh append, so every round absorbs real
        live-edge work (never a free already-warm repeat)."""
        for _ in range(TIMED_RUNS):
            deadline = time.time() + 2.0
            while batches[0] == last_b and time.time() < deadline:
                time.sleep(0.005)
            last_b = batches[0]
            t0 = time.perf_counter()
            measure()
            out.append(time.perf_counter() - t0)
        return last_b

    try:
        # phase A: the standing engine serving the panel alone (extension
        # + suffix dispatch + render per append)
        last_b = paced(lambda: se.refresh(sq), delta_s, batches[0])
        # phase B: the same panel served the pre-standing way, alone under
        # the same stream — each poll's moving end is a new superblock
        # cache key, so every refresh restages + dispatches the full grid
        paced(
            lambda: engine.query_range(
                q, edge_clock() - span_ms / 1e3, edge_clock(),
                step_ms / 1e3,
            ),
            cold_s, last_b,
        )
    finally:
        stop.set()
        th.join()
    # quiesced parity: the delta-maintained partials vs a forced full
    # re-evaluation of the same grid over the same aligned superblock
    se.refresh(sq)
    t0 = time.perf_counter()
    se.refresh(twin, force_full=True)
    warmfull_ms = (time.perf_counter() - t0) * 1e3
    biteq = (sq.grid_end_ms == twin.grid_end_ms
             and sq.labels == twin.labels
             and sq.retained.tobytes() == twin.retained.tobytes())
    delta_p50 = float(np.median(delta_s) * 1e3)
    cold_p50 = float(np.median(cold_s) * 1e3)
    ratio = cold_p50 / delta_p50 if delta_p50 > 0 else 0.0
    ok = bool(biteq) and sq.stats["delta"] > 0 and sq.stats["errors"] == 0
    import jax

    backend = jax.devices()[0].platform
    sys.stderr.write(
        f"standing_p50={delta_p50:.2f}ms cold_poll_p50={cold_p50:.2f}ms "
        f"warmfull={warmfull_ms:.2f}ms speedup={ratio:.2f}x "
        f"delta={sq.stats['delta']} retained={sq.stats['retained']} "
        f"reset={sq.stats['reset']} biteq={biteq}\n"
    )
    return {
        "metric": METRIC,
        "value": round(ratio, 3),
        "unit": "x",
        "vs_baseline": round(ratio, 2),
        "backend": backend,
        "series": N_SERIES,
        "match": ok,
        "warmup_s": round(warmup_s, 2),
        "phases_ms": {
            "standing_p50": round(delta_p50, 3),
            "cold_poll_p50": round(cold_p50, 3),
            "warm_full_ms": round(warmfull_ms, 3),
            "delta_refreshes": sq.stats["delta"],
            "retained_refreshes": sq.stats["retained"],
            "steps_computed": sq.stats["steps_computed"],
            "steps_retained": sq.stats["steps_retained"],
        },
    }


def run_benchmark_index_regex():
    """General anchored-regex selector resolution at 1M part keys on the
    vectorized posting-bitmap index (doc/perf.md "Vectorized part-key
    index") — the workload the set-arithmetic index measured at ~6.8k
    lookups/s (BENCH_LOCAL index_regex_lookups_1000k; ISSUE 14 bar: >=5x).

    Probe shape matches benchmarks/run.py bench_index_1m: the 5-tag
    schema, general anchored regexes with a literal prefix + tail class
    over the 10k-value host dictionary, full-retention range, a 64-pattern
    Grafana-storm pool (repeated selectors — the per-label match cache is
    part of the path under test, invalidated by any ingest to the label).
    match = every pool pattern's id set identical to the retained
    set-based oracle, plus eq + literal-alt + negative spot probes."""
    from filodb_tpu.core.filters import ColumnFilter, equals, regex
    from filodb_tpu.memstore.index import PartKeyIndex, SetBasedPartKeyIndex

    n = N_SERIES
    t0 = time.perf_counter()
    idx = PartKeyIndex()
    oracle = SetBasedPartKeyIndex()
    for i in range(n):
        tags = {
            "_metric_": f"metric_{i % 1000}", "host": f"h{i % 10_000}",
            "dc": f"dc{i % 10}", "_ws_": "demo", "_ns_": f"ns{i % 20}",
        }
        idx.add_partkey(i, tags, 0)
        oracle.add_partkey(i, tags, 0)
    warmup_s = time.perf_counter() - t0
    sys.stderr.write(f"index build 2x{n}: {warmup_s:.1f}s\n")

    pool = [[regex("host", f"h1{i:02d}[0-9]?")] for i in range(64)]
    probes = pool + [
        [equals("_metric_", "metric_5")],
        [regex("host", "h123.*")],
        [regex("host", "h1|h2|h33")],
        [equals("_ws_", "demo"), regex("host", "h77[0-9]?")],
        [ColumnFilter("dc", "!=", "dc3"), equals("_ns_", "ns7")],
    ]
    ok = all(
        idx.part_ids_from_filters(f, 0, 2**62).tolist()
        == oracle.part_ids_from_filters(f, 0, 2**62).tolist()
        for f in probes
    )

    for f in pool:  # warm: dictionary pass + match-cache fill
        idx.part_ids_from_filters(f, 0, 2**62)
    reps = 2000
    t0 = time.perf_counter()
    for k in range(reps):
        idx.part_ids_from_filters(pool[k % len(pool)], 0, 2**62)
    dt = time.perf_counter() - t0
    rate = reps / dt

    # secondary visibility: eq + cold-cache (first-touch) rates
    f_eq = [equals("_metric_", "metric_5")]
    idx.part_ids_from_filters(f_eq, 0, 2**62)
    t0 = time.perf_counter()
    for _ in range(reps):
        idx.part_ids_from_filters(f_eq, 0, 2**62)
    eq_rate = reps / (time.perf_counter() - t0)
    cold = [[regex("host", f"h2{i:02d}[0-9]?")] for i in range(64)]
    t0 = time.perf_counter()
    for f in cold:
        idx.part_ids_from_filters(f, 0, 2**62)
    cold_rate = len(cold) / (time.perf_counter() - t0)

    sys.stderr.write(
        f"regex warm={rate:.0f}/s cold={cold_rate:.0f}/s eq={eq_rate:.0f}/s "
        f"match={ok}\n"
    )
    return {
        "metric": METRIC,
        "value": round(rate, 1),
        "unit": "lookups/s",
        # vs the recorded set-arithmetic baseline (BENCH_LOCAL 6818.8/s)
        "vs_baseline": round(rate / 6818.8, 2),
        "backend": "host",
        "series": n,
        "match": bool(ok),
        "warmup_s": round(warmup_s, 2),
        "phases_ms": {
            "eq_lookups_per_s": round(eq_rate, 1),
            "cold_regex_per_s": round(cold_rate, 1),
        },
    }


def run_benchmark_query_hicard():
    """End-to-end hicard query throughput with the bitmap index in the
    selector path: 8000 series (4 tenants x 2000), 2000 queried —
    benchmarks/run.py bench_query_hicard's shape (recorded ~98 qps on the
    set-based index at PR 13; ISSUE 14 bar: >=2x). match = the bitmap-index
    engine's matrix is IDENTICAL (bit-equal, NaNs aligned) to a second
    engine over the same data with index_backend="set" — the new index in
    the path must not change a single sample."""
    from filodb_tpu.coordinator.planner import QueryEngine
    from filodb_tpu.core.schemas import Dataset
    from filodb_tpu.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.memstore.shard import StoreConfig
    from filodb_tpu.testkit import counter_batch

    _enable_compile_cache()

    def build(backend: str):
        ms = TimeSeriesMemStore(StoreConfig(index_backend=backend))
        ms.setup(Dataset("prometheus"), range(8))
        for ns in range(4):
            ms.ingest_routed(
                "prometheus",
                counter_batch(n_series=2000, n_samples=120, start_ms=BASE,
                              ns=f"App-{ns}"),
                spread=3,
            )
        return QueryEngine(ms, "prometheus")

    t0 = time.perf_counter()
    engine = build("python")
    engine_set = build("set")
    warmup_s = time.perf_counter() - t0
    start, end = (BASE + 400_000) / 1000, (BASE + 1_100_000) / 1000
    q = 'sum(rate(http_requests_total{_ns_="App-1"}[5m]))'

    def run(eng):
        res = eng.query_range(q, start, end, 60)
        return np.asarray(res.grids[0].values_np())

    got = run(engine)
    want = run(engine_set)
    ok = got.shape == want.shape and bool(
        np.array_equal(got, want, equal_nan=True)
    )

    times = []
    for _ in range(max(TIMED_RUNS, 10)):
        t0 = time.perf_counter()
        run(engine)
        times.append(time.perf_counter() - t0)
    p50_ms = float(np.median(times) * 1e3)
    qps = 1e3 / p50_ms
    import jax

    backend = jax.devices()[0].platform
    sys.stderr.write(
        f"hicard p50={p50_ms:.2f}ms qps={qps:.1f} match={ok}\n"
    )
    return {
        "metric": METRIC,
        "value": round(qps, 1),
        "unit": "qps",
        # vs the recorded pre-bitmap measurement (BENCH_LOCAL ~98 qps)
        "vs_baseline": round(qps / 98.0, 2),
        "backend": backend,
        "series": 8000,
        "match": bool(ok),
        "warmup_s": round(warmup_s, 2),
        "phases_ms": {"p50_ms": round(p50_ms, 3)},
    }


def run_benchmark_long_range_quantile():
    """Sketch rollup tier on the long-range dashboard shape (doc/perf.md
    "Sketch rollup tier"): 30-day span at 1h step, `quantile_over_time`
    over gauges + `histogram_quantile` over classic bucket counters.

    One memstore, two engines: the rollup engine substitutes the
    per-period summary blocks (O(periods) per query — 719 rollup periods
    here), the raw engine reads every sample (O(raw) — 43,200 samples per
    series). value = rollup-path p50 of the quantile_over_time query
    (ms, LOWER is better); vs_baseline = raw_p50 / rollup_p50. match
    requires ALL of: both rollup-engine queries recorded querylog
    path=rollup and both raw-engine queries did not; every
    quantile_over_time cell within the sketch's 2^(1/32)-1 relative
    error bound of the numpy quantile bracket over the SAME
    period-mapped windows; histogram_quantile parity vs the raw path
    (identical NaN masks, values within the documented rate-boundary
    tolerance); and raw_p50 >= 10x rollup_p50 (the ISSUE acceptance
    bar — losing the substitution flips match before it shows as
    latency)."""
    from filodb_tpu.core.records import SeriesBatch
    from filodb_tpu.core.schemas import (
        Dataset, GAUGE, METRIC_TAG, PROM_COUNTER, shard_for,
    )
    from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine
    from filodb_tpu.downsample.rollup import RollupManager
    from filodb_tpu.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.memstore.shard import StoreConfig
    from filodb_tpu.obs.querylog import QUERY_LOG
    from filodb_tpu.query import logical as L
    from filodb_tpu.query.promql import query_range_to_logical_plan

    RES = 3_600_000  # the 1h rollup resolution under test
    DAYS, IVL = 30, 60_000
    T = DAYS * 24 * 60  # minute samples per series
    S_GAUGE, S_INST = 8, 16
    LES = ["0.1", "0.25", "0.5", "1", "2.5", "+Inf"]
    # hour-aligned data origin (BASE itself is NOT aligned: BASE % 1h =
    # 1.6e6 ms) — rollup eligibility requires start % resolution == 0
    align0 = BASE + (RES - BASE % RES)
    ts = align0 + np.arange(T, dtype=np.int64) * IVL
    rng = np.random.default_rng(42)
    ms = TimeSeriesMemStore(StoreConfig(max_chunk_size=T))
    ms.setup(Dataset("prometheus"), range(N_SHARDS))
    t0 = time.time()
    gvals = 100.0 * np.exp(0.4 * rng.standard_normal((S_GAUGE, T)))
    for i in range(S_GAUGE):
        tags = {METRIC_TAG: "disk_usage", "_ws_": "demo", "_ns_": "App-2",
                "instance": f"host-{i}"}
        # single-shard placement for the gauge metric: the raw baseline's
        # per-series tree walk costs ~140ms PER WINDOW PER SHARD-GRID on
        # the 1-cpu bench box (719 windows x 4 shards would blow the
        # bench-smoke budget on its own); placement is an ingest-routing
        # detail, not query semantics, and the rollup path is
        # placement-independent either way
        ms.shard("prometheus", 0).ingest_series(
            SeriesBatch(GAUGE, tags, ts, {"value": gvals[i]}))
    # classic cumulative bucket counters: le-cumulative, time-cumulative
    incr = rng.poisson(3.0, size=(S_INST, T, len(LES))).astype(np.float64)
    bvals = np.cumsum(np.cumsum(incr, axis=2), axis=1)
    for i in range(S_INST):
        for b, le in enumerate(LES):
            tags = {METRIC_TAG: "http_request_duration_seconds_bucket",
                    "_ws_": "demo", "_ns_": "App-2",
                    "instance": f"host-{i}", "le": le}
            ms.shard("prometheus",
                     shard_for(tags, spread=3, num_shards=N_SHARDS)
                     ).ingest_series(
                SeriesBatch(PROM_COUNTER, tags, ts, {"count": bvals[i, :, b]}))
    sys.stderr.write(
        f"ingest: {S_GAUGE} gauge + {S_INST * len(LES)} bucket series x "
        f"{T} samples in {time.time() - t0:.1f}s\n"
    )
    _enable_compile_cache()
    q1 = "quantile_over_time(0.99, disk_usage[1h])"
    q2 = ("histogram_quantile(0.99, sum by (le) "
          "(rate(http_request_duration_seconds_bucket[1h])))")
    # start leaves TWO lead periods (rate needs one before the window)
    start_s = (align0 + 2 * RES) / 1e3
    end_s = (align0 + DAYS * 24 * RES) / 1e3
    step_s = RES / 1e3
    rollups = RollupManager(ms)
    t0 = time.perf_counter()
    for q in (q1, q2):
        plan = query_range_to_logical_plan(q, start_s, end_s, step_s)
        node = plan
        while isinstance(node, (L.Aggregate, L.ApplyInstantFunction)):
            node = node.inner
        rollups.ensure("prometheus", node.raw.filters, RES, build=True)
    fold_s = time.perf_counter() - t0
    eng_ru = QueryEngine(ms, "prometheus", PlannerParams(rollups=rollups))
    eng_raw = QueryEngine(ms, "prometheus", PlannerParams())

    def timed(eng, q, runs):
        # latency = time to MATERIALIZED values: result grids hold lazy
        # device arrays, so stopping the clock at query_range() return
        # would credit the raw path with work it merely enqueued (the
        # async backlog then stalls whoever syncs next)
        out, paths = [], []
        for _ in range(runs):
            t0 = time.perf_counter()
            res = eng.query_range(q, start_s, end_s, step_s)
            for g in res.grids:
                np.asarray(g.values_np())
            out.append(time.perf_counter() - t0)
            paths.append(QUERY_LOG.entries(1)[0].get("path"))
        return res, float(np.median(out) * 1e3), paths, out

    t0 = time.perf_counter()
    for eng, q in ((eng_ru, q1), (eng_ru, q2), (eng_raw, q2)):
        # compile + stage warmup; raw q1 (the O(raw-samples) tree path,
        # ~minutes per pass on the 1-cpu bench box) warms inside its own
        # timed runs instead — its first-run compile share is reported
        # separately via the min/median split below
        res = eng.query_range(q, start_s, end_s, step_s)
        for g in res.grids:
            np.asarray(g.values_np())
    warmup_s = time.perf_counter() - t0
    res1_ru, ru1_ms, p1_ru, _ = timed(eng_ru, q1, TIMED_RUNS)
    res2_ru, ru2_ms, p2_ru, _ = timed(eng_ru, q2, TIMED_RUNS)
    # ONE raw q1 pass: the O(raw-samples) tree walk costs minutes per run
    # and re-running it would not move the needle on a >=10x acceptance
    # bar (warm runs measured within ~15% of cold — the cost is per-window
    # dispatch, not compile)
    res1_raw, _, p1_raw, t1_raw = timed(eng_raw, q1, 1)
    raw1_ms = float(min(t1_raw) * 1e3)
    res2_raw, raw2_ms, p2_raw, _ = timed(eng_raw, q2, min(TIMED_RUNS, 3))
    paths_ok = (all(p == "rollup" for p in p1_ru + p2_ru)
                and all(p != "rollup" for p in p1_raw + p2_raw))
    # quantile_over_time oracle over the SAME period-mapped windows: with
    # window == step == resolution every output step j covers exactly the
    # samples of hour j+1, so the sketch's bin bound applies cleanly
    hours = gvals.reshape(S_GAUGE, DAYS * 24, 60)
    lo = np.quantile(hours, 0.99, axis=2, method="lower")[:, 1:]
    hi = np.quantile(hours, 0.99, axis=2, method="higher")[:, 1:]
    bound = 2.0 ** (1.0 / 32.0) - 1.0 + 1e-6
    g1 = res1_ru.grids[0]
    est = np.asarray(g1.values_np(), dtype=np.float64)
    order = [int(lbl["instance"].split("-")[1]) for lbl in g1.labels]
    lo, hi = lo[order], hi[order]
    q_ok = bool(est.shape == lo.shape and np.all(
        (est >= lo * (1 - bound)) & (est <= hi * (1 + bound))
    ))
    # histogram_quantile parity vs the raw path: rollup rate is a period-
    # boundary difference vs PromQL's window-edge extrapolation — the
    # extrapolation factor cancels in the quantile's rank ratio, leaving
    # O(interval/window) boundary effects
    h_ru = np.asarray(res2_ru.grids[0].values_np(), dtype=np.float64)
    h_raw = np.asarray(res2_raw.grids[0].values_np(), dtype=np.float64)
    with np.errstate(invalid="ignore"):
        h_ok = bool(
            h_ru.shape == h_raw.shape
            and np.array_equal(np.isnan(h_ru), np.isnan(h_raw))
            and np.allclose(h_ru, h_raw, rtol=0.06, equal_nan=True)
        )
    speedup = raw1_ms / ru1_ms if ru1_ms > 0 else 0.0
    ok = paths_ok and q_ok and h_ok and speedup >= 10.0
    import jax

    backend = jax.devices()[0].platform
    sys.stderr.write(
        f"rollup_p50={ru1_ms:.2f}ms raw_p50={raw1_ms:.2f}ms "
        f"speedup={speedup:.1f}x hist rollup={ru2_ms:.2f}ms "
        f"raw={raw2_ms:.2f}ms paths_ok={paths_ok} quantile_ok={q_ok} "
        f"hist_ok={h_ok}\n"
    )
    return {
        "metric": METRIC,
        "value": round(ru1_ms, 3),
        "unit": "ms",
        "vs_baseline": round(speedup, 2),
        "backend": backend,
        "series": S_GAUGE + S_INST * len(LES),
        "match": bool(ok),
        "warmup_s": round(warmup_s, 2),
        "phases_ms": {
            "rollup_quantile_p50": round(ru1_ms, 3),
            "raw_quantile_p50": round(raw1_ms, 3),
            "rollup_hist_p50": round(ru2_ms, 3),
            "raw_hist_p50": round(raw2_ms, 3),
            "fold_s": round(fold_s, 2),
        },
    }


def run_benchmark_failover_storm():
    """Replicated shard plane under a node kill (doc/robustness.md
    "Replicated shard plane"): an RF=2 in-process replica cluster at
    N_SERIES series, QPS_CLIENTS client threads looping the canonical
    dashboard aggregation through the front coordinator's ReplicaRouter
    (one shard-pinned gRPC leg per shard, siblings attached). Three
    measured windows: ``before`` (both nodes up), ``during`` (one node
    killed mid-window — in-flight legs re-pin to their sibling replica),
    ``after`` (steady state on the survivor).

    value = during-kill throughput (qps, HIGHER is better — the smoke
    floor gates it via qps_floor_min); vs_baseline = during/before qps
    ratio; phases_ms carries all three windows' qps + p50/p99. match =
    ZERO failed queries across all windows with partial results OFF and
    every result BIT-equal to the pre-kill baseline (per-shard legs keep
    the merge tree invariant, so failover may not change a single bit)."""
    import threading

    from filodb_tpu.testkit import machine_metrics, replica_cluster

    n_samples = 360  # 1h @ 10s; RF=2 doubles resident data
    batch = machine_metrics(n_series=N_SERIES, n_samples=n_samples)
    c = replica_cluster(batch=batch, n_shards=N_SHARDS)
    promql = "sum(heap_usage0)"
    q_start = BASE / 1000.0
    q_end = (BASE + (n_samples - 1) * INTERVAL_MS) / 1000.0

    def rows(res):
        return sorted(
            (tuple(sorted(l.items())), np.asarray(v).tobytes())
            for g in res.grids for l, v in zip(g.labels, g.values_np())
        )

    try:
        assert c.engine.planner.params.allow_partial_results is False
        baseline = rows(c.engine.query_range(promql, q_start, q_end, STEP_S))
        failures = [0]
        mismatches = [0]

        def measure(kill: str | None = None):
            lat: list[list[float]] = [[] for _ in range(QPS_CLIENTS)]
            gate = threading.Barrier(QPS_CLIENTS + 1)
            stop_at = [0.0]

            def client(i):
                gate.wait()
                while time.perf_counter() < stop_at[0]:
                    t0 = time.perf_counter()
                    try:
                        res = c.engine.query_range(promql, q_start, q_end,
                                                   STEP_S)
                    except Exception:
                        failures[0] += 1
                        continue
                    lat[i].append(time.perf_counter() - t0)
                    if rows(res) != baseline:
                        mismatches[0] += 1

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(QPS_CLIENTS)]
            for t in threads:
                t.start()
            stop_at[0] = time.perf_counter() + QPS_DURATION_S
            t_begin = time.perf_counter()
            gate.wait()
            if kill is not None:
                # the kill lands mid-window, under in-flight queries
                time.sleep(QPS_DURATION_S / 3.0)
                c.kill(kill)
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - t_begin
            flat = [x for l in lat for x in l]
            if not flat:
                return 0.0, 0.0, 0.0
            return (
                len(flat) / elapsed,
                float(np.percentile(flat, 50) * 1e3),
                float(np.percentile(flat, 99) * 1e3),
            )

        b_qps, b_p50, b_p99 = measure()
        d_qps, d_p50, d_p99 = measure(kill="node-0")
        a_qps, a_p50, a_p99 = measure()
    finally:
        c.stop()
    import jax

    backend = jax.devices()[0].platform
    ok = failures[0] == 0 and mismatches[0] == 0 and d_qps > 0
    sys.stderr.write(
        f"clients={QPS_CLIENTS} before={b_qps:.1f}qps (p99={b_p99:.1f}ms) "
        f"during-kill={d_qps:.1f}qps (p99={d_p99:.1f}ms) "
        f"after={a_qps:.1f}qps (p99={a_p99:.1f}ms) "
        f"failures={failures[0]} mismatches={mismatches[0]} match={ok}\n"
    )
    return {
        "metric": METRIC,
        "value": round(d_qps, 1),
        "unit": "qps",
        "vs_baseline": round(d_qps / b_qps, 3) if b_qps > 0 else 0.0,
        "backend": backend,
        "series": N_SERIES,
        "clients": QPS_CLIENTS,
        "match": bool(ok),
        "phases_ms": {
            "before_qps": round(b_qps, 1),
            "during_qps": round(d_qps, 1),
            "after_qps": round(a_qps, 1),
            "before_p50": round(b_p50, 2),
            "before_p99": round(b_p99, 2),
            "during_p50": round(d_p50, 2),
            "during_p99": round(d_p99, 2),
            "after_p50": round(a_p50, 2),
            "after_p99": round(a_p99, 2),
        },
    }


def run_benchmark_render_2m():
    """Result-plane streaming render (doc/perf.md "Result plane"): a ~2M
    sample per-series matrix (rate() without aggregation at native 10s
    step) served over live HTTP through the chunked-streaming edge —
    stream_matrix pulls device blocks through the double-buffered D2H
    prefetcher while earlier blocks encode and hit the socket.

    value = end-to-end body throughput in Msamples/s (HIGHER is better;
    qps_floor_min gates it). phases_ms carries first-byte latency (must
    land well before the body completes — the streaming claim), total
    body wall, and the encoder's prefetch-stall count for the measured
    runs (dispatch-stall ~0 when D2H keeps ahead of encode). match =
    the streamed body's data.result is IDENTICAL (exact decimal strings)
    to an in-process buffered render of the same engine result, AND the
    warm CANONICAL query (fused sum(rate(...))) over the same data stays
    exactly ONE kernel dispatch with the streaming edge on — the
    prefetcher's per-block device slicing must not show up as dispatches.
    (The 2M per-series matrix itself legitimately dispatches per shard —
    its per-query count rides phases_ms for the record.)"""
    import http.client
    import urllib.parse

    from filodb_tpu.api import promjson as PJ
    from filodb_tpu.api.http import serve_background
    from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine
    from filodb_tpu.metrics import REGISTRY
    from filodb_tpu.testkit import kernel_dispatch_total

    def stall_total() -> float:
        total = 0.0
        with REGISTRY._lock:
            for (name, _lbls), m in REGISTRY._metrics.items():
                if name == "filodb_render_stream_stalls":
                    total += m.value
        return total

    ms, _ts = build_memstore()
    _enable_compile_cache()
    engine = QueryEngine(ms, "prometheus", PlannerParams())
    srv, port = serve_background(engine)
    step_s = INTERVAL_MS / 1000.0  # native resolution: per-series matrix
    q = urllib.parse.quote("rate(http_requests_total[5m])")
    path = (f"/api/v1/query_range?query={q}"
            f"&start={START_S}&end={END_S}&step={step_s}")

    def fetch():
        """One streamed request; returns (body, first_byte_s, total_s)."""
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
        t0 = time.perf_counter()
        conn.request("GET", path, headers={"Accept-Encoding": "identity"})
        r = conn.getresponse()
        first = r.read(1)
        t_first = time.perf_counter() - t0
        body = first + r.read()
        t_total = time.perf_counter() - t0
        chunked = r.getheader("Transfer-Encoding") == "chunked"
        conn.close()
        return body, t_first, t_total, chunked

    t0 = time.perf_counter()
    body, _, _, chunked0 = fetch()  # compile + stage + cache warm
    warmup_s = time.perf_counter() - t0
    n_samples = sum(len(s["values"])
                    for s in json.loads(body)["data"]["result"])
    sys.stderr.write(
        f"warmup {warmup_s:.1f}s, body {len(body) / 1e6:.1f}MB, "
        f"{n_samples / 1e6:.2f}M samples, chunked={chunked0}\n")
    before_dispatch = kernel_dispatch_total()
    before_stalls = stall_total()
    firsts, totals = [], []
    for _ in range(TIMED_RUNS):
        body, t_first, t_total, _ck = fetch()
        firsts.append(t_first)
        totals.append(t_total)
    warm_dispatches = kernel_dispatch_total() - before_dispatch
    stalls = stall_total() - before_stalls
    # canonical-query invariant with the streaming edge enabled: warm
    # fused sum(rate(...)) stays exactly ONE dispatch
    canon = urllib.parse.quote("sum(rate(http_requests_total[5m]))")
    canon_path = (f"/api/v1/query_range?query={canon}"
                  f"&start={START_S}&end={END_S}&step={STEP_S}")
    for _ in range(2):  # compile + stage warm
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
        conn.request("GET", canon_path)
        conn.getresponse().read()
        conn.close()
    before_canon = kernel_dispatch_total()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    conn.request("GET", canon_path)
    conn.getresponse().read()
    conn.close()
    single = kernel_dispatch_total() - before_canon == 1
    # oracle: buffered in-process render of the same engine result — the
    # streamed body's payload must be exactly it (same decimal strings)
    res = engine.query_range("rate(http_requests_total[5m])", START_S, END_S,
                             step_s)
    oracle = json.loads(b"".join(PJ.stream_matrix(res)))["data"]["result"]
    got = json.loads(body)["data"]["result"]
    key = lambda s: json.dumps(s["metric"], sort_keys=True)  # noqa: E731
    payload_eq = ({key(s): s["values"] for s in got}
                  == {key(s): s["values"] for s in oracle})
    streamed = chunked0 and float(np.median(firsts)) < float(
        np.median(totals)) / 2.0
    srv.shutdown()
    p50_total = float(np.median(totals))
    msps = n_samples / p50_total / 1e6
    ok = payload_eq and single and streamed
    import jax

    backend = jax.devices()[0].platform
    sys.stderr.write(
        f"render_2m: {msps:.2f} Msamples/s first_byte_p50="
        f"{np.median(firsts) * 1e3:.1f}ms total_p50={p50_total * 1e3:.0f}ms "
        f"stalls={stalls:.0f} matrix_dispatches={warm_dispatches}/"
        f"{len(totals)} canonical_single_dispatch={single} "
        f"payload_eq={payload_eq} streamed={streamed}\n")
    return {
        "metric": METRIC,
        "value": round(msps, 3),
        "unit": "Msamples/s",
        "backend": backend,
        "series": N_SERIES,
        "match": bool(ok),
        "warmup_s": round(warmup_s, 2),
        "phases_ms": {
            "first_byte_p50": round(float(np.median(firsts)) * 1e3, 2),
            "total_p50": round(p50_total * 1e3, 2),
            "stream_stalls": round(stalls, 1),
            "samples_m": round(n_samples / 1e6, 3),
            "matrix_dispatches_per_query": round(warm_dispatches / max(len(totals), 1), 1),
        },
    }


def run_benchmark():
    if WORKLOAD == "render_2m":
        return run_benchmark_render_2m()
    if WORKLOAD == "failover_storm":
        return run_benchmark_failover_storm()
    if WORKLOAD == "long_range_quantile":
        return run_benchmark_long_range_quantile()
    if WORKLOAD == "standing_refresh":
        return run_benchmark_standing_refresh()
    if WORKLOAD == "ingest_impact":
        return run_benchmark_ingest_impact()
    if WORKLOAD == "concurrent_qps":
        return run_benchmark_concurrent_qps()
    if WORKLOAD == "mixed_cost_storm":
        return run_benchmark_mixed_cost_storm()
    if WORKLOAD == "fused_mesh":
        return run_benchmark_fused_mesh()
    if WORKLOAD == "fused_jitter":
        return run_benchmark_fused_jitter()
    if WORKLOAD == "index_regex":
        return run_benchmark_index_regex()
    if WORKLOAD == "query_hicard":
        return run_benchmark_query_hicard()
    if WORKLOAD == "hist_quantile":
        ms, ts = build_memstore_hist()
    else:
        ms, ts = build_memstore()
    tpu_ms, tpu_vals, res, warmup_s, phases = tpu_query(ms)
    if WORKLOAD == "hist_quantile":
        cpu_ms, cpu_vals = cpu_baseline_hist(ms, ts)
    else:
        cpu_ms, cpu_vals = cpu_baseline(ms, ts)
    # cross-check: TPU result must match the CPU oracle. Only hist_quantile
    # legitimately produces aligned NaNs (quantile of an empty window); for
    # the scalar workload any NaN stays a mismatch, as before
    n = min(len(tpu_vals), len(cpu_vals))
    with np.errstate(invalid="ignore"):
        ok = np.allclose(tpu_vals[:n], cpu_vals[:n], rtol=5e-3,
                         equal_nan=WORKLOAD == "hist_quantile")
    import jax

    backend = jax.devices()[0].platform
    sys.stderr.write(
        f"{backend}_p50={tpu_ms:.2f}ms numpy_p50={cpu_ms:.2f}ms match={ok} "
        f"series/sec={N_SERIES / (tpu_ms / 1e3):.3g}\n"
    )
    return {
        "metric": METRIC,
        "value": round(tpu_ms, 3),
        "unit": "ms",
        "vs_baseline": round(cpu_ms / tpu_ms, 2),
        "backend": backend,
        "series": N_SERIES,
        "match": bool(ok),
        "warmup_s": round(warmup_s, 2),
        "phases_ms": phases,
    }


def _dump_kernel_snapshot() -> None:
    """Write the worker's kernel-observatory snapshot (obs/kernels.py) to
    FILODB_KERNEL_SNAPSHOT when set — the attestation harness
    (tools/attest.py) collects these to PROVE which executables actually
    compiled/dispatched during each floor workload (fused paths served,
    which fallbacks fired) instead of trusting latency numbers alone."""
    path = os.environ.get("FILODB_KERNEL_SNAPSHOT")
    if not path:
        return
    try:
        from filodb_tpu.metrics import REGISTRY
        from filodb_tpu.obs.kernels import KERNELS

        snap = {
            "totals": KERNELS.totals(),
            "kernels": KERNELS.snapshot(limit=64),
            "counters": REGISTRY.counter_samples(
                "filodb_fused_fallback", "filodb_compile_cache_hits",
                "filodb_compile_cache_misses", "filodb_xla_recompile_storms",
            ),
        }
        with open(path, "w") as f:
            json.dump(snap, f)
    except Exception as e:  # noqa: BLE001 — the snapshot must not fail a bench
        sys.stderr.write(f"kernel snapshot failed: {e}\n")


def main(argv=None) -> int:
    """Run the workload FILODB_BENCH_WORKLOAD selects once, in this
    process, on the device jax finds, and print its ONE JSON line
    (``"backend"`` names that device's platform). ``--cpu`` pins the CPU
    backend. Exits non-zero when the result does not match its oracle; a
    workload that raises ends the process with the traceback."""
    argv = sys.argv[1:] if argv is None else argv
    if "--cpu" in argv:
        os.environ["JAX_PLATFORMS"] = "cpu"  # before the first jax import
    result = run_benchmark()
    _dump_kernel_snapshot()
    print(json.dumps(result), flush=True)
    return 0 if result.get("match") else 1


if __name__ == "__main__":
    sys.exit(main())
