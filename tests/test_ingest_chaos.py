"""Ingest-chaos tests (doc/robustness.md "superblock consistency model"):
the fused single-dispatch path and the downsample tier must stay CORRECT
and LIVE under sustained concurrent ingest.

Three families, mirroring the failure modes this suite exists to pin:

- staging-cache liveness: a block staged concurrently with DISJOINT-range
  ingest must still be cached (the old version-equality insert guard
  starved the cache under fine-grained ingest), and a warm superblock must
  survive disjoint ingest (revalidate) or absorb overlapping live-edge
  appends in place (extend) — the warm canonical query stays exactly ONE
  kernel dispatch across an overlapping append;
- queries racing fine-grained ingest: threaded soak with a seeded stream,
  checked by invariants (final parity vs the reference tree, a warm
  single-dispatch query after quiesce);
- downsample maintenance: the _release TOCTOU (deterministically
  reproduced via the race hook), claim-steal storms, crash-mid-commit
  redo, and the merge-commit contract (batch output must never wipe
  streaming-downsampled segments).

Everything is seeded; the threaded soak asserts only schedule-independent
invariants, so the suite is tier-1 safe.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine
from filodb_tpu.core.schemas import Dataset
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.metrics import REGISTRY
from filodb_tpu.testkit import counter_batch

pytestmark = pytest.mark.ingest_chaos

BASE = 1_600_000_000_000
N_SHARDS = 8
N_SERIES = 48
N_SAMPLES = 300
HEAD_MS = BASE + N_SAMPLES * 10_000  # first timestamp past the seed data
START = (BASE + 600_000) / 1000
STEP = 60
Q = "sum by (job) (rate(http_requests_total[5m]))"


def _dispatch_total() -> int:
    total = 0
    with REGISTRY._lock:
        for (name, _labels), m in REGISTRY._metrics.items():
            if name == "filodb_kernel_dispatch_seconds":
                total += m.total
    return total


def _counter_sum(name: str) -> float:
    with REGISTRY._lock:
        return sum(
            m.value for (n, _labels), m in REGISTRY._metrics.items()
            if n == name
        )


def _counter(name: str, **labels) -> float:
    return REGISTRY.counter(name, **labels).value


def _make_store(n_samples: int = N_SAMPLES):
    ms = TimeSeriesMemStore()
    ms.setup(Dataset("ds"), list(range(N_SHARDS)))
    ms.ingest_routed(
        "ds",
        counter_batch(n_series=N_SERIES, n_samples=n_samples, start_ms=BASE),
        spread=3,
    )
    return ms


def _append(ms, n_batches: int = 1, start_ms: int = HEAD_MS,
            n_series: int = N_SERIES, seed: int = 7):
    """Live-edge continuation batches: same tag set as the seed data (same
    seed => same series), timestamps past the current head."""
    for b in range(n_batches):
        ms.ingest_routed(
            "ds",
            counter_batch(n_series=n_series, n_samples=1,
                          start_ms=start_ms + b * 10_000, seed=seed),
            spread=3,
        )


def _rows(res):
    out = {}
    for g in res.grids:
        for lbls, vals in zip(g.labels, g.values_np()):
            out[tuple(sorted(lbls.items()))] = np.asarray(vals)
    return out


def _assert_parity(fused_res, ref_res):
    a, b = _rows(fused_res), _rows(ref_res)
    assert a.keys() == b.keys()
    for k in a:
        na, nb = np.isnan(a[k]), np.isnan(b[k])
        assert (na == nb).all(), (k, "NaN masks differ")
        np.testing.assert_allclose(a[k][~na], b[k][~nb], rtol=2e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# superblock maintenance: extend / revalidate / single-dispatch guarantee


def test_warm_query_single_dispatch_across_overlapping_append():
    """THE acceptance bar: an overlapping live-edge append must be absorbed
    by extending the device superblock in place — the next warm canonical
    query issues exactly ONE kernel dispatch (not a re-stage), and its
    result matches the reference tree bit-for-bit-ish."""
    ms = _make_store()
    fused = QueryEngine(ms, "ds")
    ref = QueryEngine(ms, "ds", PlannerParams(fused_aggregate=False))
    end = (HEAD_MS + 120_000) / 1000  # live-edge range: reaches past head
    fused.query_range(Q, START, end, STEP)  # cold: build + cache
    fused.query_range(Q, START, end, STEP)  # warm hit

    before_ext = _counter("filodb_superblock_maintenance", outcome="extend")
    for i in range(3):  # repeated scrapes: every one must extend, not restage
        _append(ms, start_ms=HEAD_MS + i * 10_000)
        before = _dispatch_total()
        rf = fused.query_range(Q, START, end, STEP)
        assert _dispatch_total() - before == 1, (
            "warm query across an overlapping append must stay ONE dispatch"
        )
    assert _counter("filodb_superblock_maintenance", outcome="extend") \
        == before_ext + 3
    _assert_parity(rf, ref.query_range(Q, START, end, STEP))


def test_superblock_survives_disjoint_ingest():
    """Fine-grained ingest whose effect interval is DISJOINT from a warm
    superblock's range must not evict it: the entry revalidates via the
    effect log and the query stays one dispatch with zero re-staging."""
    import filodb_tpu.query.exec.plans as plans

    ms = _make_store()
    fused = QueryEngine(ms, "ds")
    hist_end = (BASE + (N_SAMPLES - 60) * 10_000) / 1000  # ends before head
    fused.query_range(Q, START, hist_end, STEP)
    fused.query_range(Q, START, hist_end, STEP)

    stages = [0]
    orig = plans.ST.stage_from_shard

    def counting(*a, **kw):
        stages[0] += 1
        return orig(*a, **kw)

    before_rv = _counter("filodb_superblock_maintenance", outcome="revalidate")
    plans.ST.stage_from_shard = counting
    try:
        for i in range(20):  # 20 fine-grained disjoint live-edge batches
            _append(ms, start_ms=HEAD_MS + i * 10_000)
            before = _dispatch_total()
            fused.query_range(Q, START, hist_end, STEP)
            assert _dispatch_total() - before == 1
    finally:
        plans.ST.stage_from_shard = orig
    assert stages[0] == 0, "disjoint ingest must not force any re-stage"
    assert _counter("filodb_superblock_maintenance", outcome="revalidate") \
        == before_rv + 20


def test_extension_aborts_cleanly_on_new_series():
    """An ingest that CREATES a series records a full-clear effect: the
    stale superblock must rebuild (never extend across a row-set change),
    and the rebuilt result includes the new series."""
    ms = _make_store()
    fused = QueryEngine(ms, "ds")
    ref = QueryEngine(ms, "ds", PlannerParams(fused_aggregate=False))
    end = (HEAD_MS + 120_000) / 1000
    fused.query_range(Q, START, end, STEP)
    fused.query_range(Q, START, end, STEP)
    # continuation batch with MORE series: existing ones get a live-edge
    # append, brand-new ones appear in-range
    _append(ms, n_series=N_SERIES + 8)
    rf = fused.query_range(Q, START, end, STEP)
    _assert_parity(rf, ref.query_range(Q, START, end, STEP))


# ---------------------------------------------------------------------------
# staging-cache liveness: the interval-aware insert guard


def _mid_stage_ingest_engine(ms, batch_for_call):
    """Engine whose staging path ingests ``batch_for_call(i)`` into the
    store mid-stage (between version_at_stage and the cache insert) — the
    deterministic reproduction of 'a block staged concurrently with
    ingest'."""
    import filodb_tpu.query.exec.plans as plans

    orig = plans.ST.stage_from_shard
    calls = [0]

    def racing(*a, **kw):
        block = orig(*a, **kw)
        i = calls[0]
        calls[0] += 1
        batch = batch_for_call(i)
        if batch is not None:
            ms.ingest_routed("ds", batch, spread=3)
        return block

    return orig, racing, calls


def test_disjoint_mid_stage_ingest_no_longer_starves_cache():
    """Regression for the round-5 advisor finding (plans.py insert guard):
    sustained fine-grained DISJOINT-range ingest racing every stage used to
    drop every insert — the cache starved and every query re-paid the full
    stage. Now: 100 small batches racing the stages, insert success rate
    stays >0 (all inserts succeed), and the historical query re-stages at
    most once (the first, cold stage)."""
    import filodb_tpu.query.exec.plans as plans

    ms = _make_store()
    fused = QueryEngine(ms, "ds")
    hist_end = (BASE + (N_SAMPLES - 60) * 10_000) / 1000
    drops0 = _counter_sum("filodb_stage_cache_insert_dropped")

    seq = [0]

    def disjoint_batch(_i):
        b = counter_batch(n_series=N_SERIES, n_samples=1,
                          start_ms=HEAD_MS + seq[0] * 10_000)
        seq[0] += 1
        return b

    orig, racing, calls = _mid_stage_ingest_engine(ms, disjoint_batch)
    plans.ST.stage_from_shard = racing
    try:
        fused.query_range(Q, START, hist_end, STEP)  # cold: one stage/shard
        first_stages = calls[0]
        assert first_stages > 0
        # keep the fine-grained stream racing every subsequent operation
        for _ in range(100 // max(first_stages, 1)):
            fused.query_range(Q, START, hist_end, STEP)
    finally:
        plans.ST.stage_from_shard = orig
    assert calls[0] == first_stages, (
        "historical query re-staged under disjoint ingest: cache starved"
    )
    # every staged block was inserted despite the racing version bumps
    assert all(
        len(ms.shard("ds", s).stage_cache) > 0 for s in range(N_SHARDS)
    )
    drops1 = _counter_sum("filodb_stage_cache_insert_dropped")
    assert drops1 == drops0, "disjoint-range ingest must not drop inserts"


def test_overlapping_mid_stage_ingest_still_guards_insert():
    """The flip side: an ingest whose range OVERLAPS the staged block must
    still block the insert (the staged block cannot have seen it) — with
    the drop reason exported."""
    import filodb_tpu.query.exec.plans as plans

    ms = _make_store()
    fused = QueryEngine(ms, "ds")
    hist_end = (BASE + (N_SAMPLES - 60) * 10_000) / 1000
    overlap_ms = BASE + (N_SAMPLES - 100) * 10_000  # inside the query range

    before = _counter("filodb_stage_cache_insert_dropped", reason="overlap")
    orig, racing, calls = _mid_stage_ingest_engine(
        ms,
        lambda i: counter_batch(n_series=4, n_samples=1,
                                start_ms=overlap_ms + i * 10_000)
        if i < N_SHARDS else None,
    )
    plans.ST.stage_from_shard = racing
    try:
        fused.query_range(Q, START, hist_end, STEP)
    finally:
        plans.ST.stage_from_shard = orig
    assert _counter("filodb_stage_cache_insert_dropped", reason="overlap") \
        > before


# ---------------------------------------------------------------------------
# threaded soak: queries racing a seeded fine-grained stream


def test_queries_racing_fine_grained_ingest():
    """Seeded ingest stream (1-sample continuation batches, no sleeps)
    racing a query loop. Schedule-independent invariants: no exceptions
    escape, the final post-quiesce result matches the reference tree over
    the final store contents, and after at most one maintenance query the
    warm query is back to ONE dispatch."""
    ms = _make_store()
    fused = QueryEngine(ms, "ds")
    ref = QueryEngine(ms, "ds", PlannerParams(fused_aggregate=False))
    end = (HEAD_MS + 80 * 10_000) / 1000
    fused.query_range(Q, START, end, STEP)

    errors = []
    n_batches = 60

    def ingester():
        try:
            for b in range(n_batches):
                _append(ms, start_ms=HEAD_MS + b * 10_000)
        except Exception as e:  # pragma: no cover - failure surface
            errors.append(e)

    th = threading.Thread(target=ingester)
    th.start()
    try:
        for _ in range(40):
            fused.query_range(Q, START, end, STEP)
    finally:
        th.join()
    assert not errors, errors

    # quiesced: one maintenance query (extend or rebuild), then warm
    rf = fused.query_range(Q, START, end, STEP)
    _assert_parity(rf, ref.query_range(Q, START, end, STEP))
    before = _dispatch_total()
    fused.query_range(Q, START, end, STEP)
    assert _dispatch_total() - before == 1


# ---------------------------------------------------------------------------
# downsample maintenance races


def _seed_raw_store(root, n_shards=2, n_series=6, n_samples=400):
    from filodb_tpu.memstore.shard import StoreConfig
    from filodb_tpu.store.columnstore import LocalColumnStore
    from filodb_tpu.store.flush import FlushCoordinator
    from filodb_tpu.testkit import machine_metrics

    store = LocalColumnStore(str(root))
    ms = TimeSeriesMemStore(StoreConfig(max_chunk_size=100))
    ms.setup(Dataset("ds"), range(n_shards))
    for s in range(n_shards):
        ms.ingest("ds", s, machine_metrics(
            n_series=n_series, n_samples=n_samples, start_ms=BASE + s,
        ))
    fc = FlushCoordinator(ms, store)
    for s in range(n_shards):
        fc.flush_shard("ds", s)
    return store, ms


def test_release_toctou_reproduced_and_closed(tmp_path):
    """Deterministic reproduction of the old _release read-then-unlink
    TOCTOU: the owner's claim goes stale and is stolen+re-created by a new
    owner INSIDE the release window (via the race hook). The old code
    unlinked the NEW owner's claim, re-opening the shard to a third worker
    mid-redo; the tombstone discipline must detect the steal from the
    renamed file and put the new owner's claim back untouched."""
    from filodb_tpu.downsample import distributed as dd

    job = str(tmp_path / "job")
    os.makedirs(job)
    path = dd._claim_path(job, 0)
    with open(path, "w") as f:
        json.dump({"worker": "w1", "t": 0.0}, f)

    def steal(shard):
        # the interleaved stealer: atomically breaks w1's stale claim and
        # re-creates it as w2 — exactly what _try_claim's steal path does
        os.rename(path, path + ".stolen-w2")
        os.unlink(path + ".stolen-w2")
        with open(path, "w") as f:
            json.dump({"worker": "w2", "t": 1.0}, f)

    before = _counter("filodb_downsample_claims", event="tombstone_restored")
    dd._release_race_hook = steal
    try:
        dd._release(job, 0, "w1")
    finally:
        dd._release_race_hook = None
    # the new owner's claim SURVIVES the racing release (old code: unlinked)
    assert os.path.exists(path), "release deleted the stolen claim"
    with open(path) as f:
        assert json.load(f)["worker"] == "w2"
    assert _counter("filodb_downsample_claims", event="tombstone_restored") \
        == before + 1
    assert not [p for p in os.listdir(job) if ".release-" in p], (
        "tombstone leaked"
    )


def test_release_without_race_removes_own_claim(tmp_path):
    from filodb_tpu.downsample import distributed as dd

    job = str(tmp_path / "job")
    os.makedirs(job)
    path = dd._claim_path(job, 0)
    with open(path, "w") as f:
        json.dump({"worker": "w1", "t": 0.0}, f)
    dd._release(job, 0, "w1")
    assert not os.path.exists(path)
    # releasing someone ELSE's claim is a no-op
    with open(path, "w") as f:
        json.dump({"worker": "w2", "t": 0.0}, f)
    dd._release(job, 0, "w1")
    assert os.path.exists(path)


def test_claim_steal_storm_single_winner(tmp_path):
    """8 workers race to break the same stale claim: the atomic-rename
    steal admits exactly ONE winner, and the surviving claim file names
    that winner."""
    from filodb_tpu.downsample import distributed as dd

    job = str(tmp_path / "job")
    os.makedirs(job)
    path = dd._claim_path(job, 0)
    with open(path, "w") as f:
        json.dump({"worker": "stale", "t": 0.0}, f)
    os.utime(path, (1.0, 1.0))  # ancient heartbeat

    winners = []
    barrier = threading.Barrier(8)

    def racer(i):
        rep = dd.WorkerReport(worker_id=f"w{i}")
        barrier.wait()
        if dd._try_claim(job, 0, f"w{i}", stale_s=5.0, report=rep):
            winners.append(f"w{i}")

    threads = [threading.Thread(target=racer, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(winners) == 1, winners
    with open(path) as f:
        assert json.load(f)["worker"] == winners[0]


def test_crash_mid_commit_then_redo_recovers(tmp_path):
    """Worker dies BETWEEN committing a shard's downsample output and
    writing the done marker (FILODB_DS_CRASH_MID_COMMIT). The redo by a
    second worker re-commits equivalent output under the same
    deterministic batch segment names (os.replace: last writer wins), so
    the final store equals the single-process oracle — no double-counted
    and no lost samples."""
    from filodb_tpu.downsample.distributed import (
        _claim_path, _job_dir, job_complete, run_worker,
    )
    from test_distributed_downsample import _oracle_totals, _recovered_totals

    store, ms = _seed_raw_store(tmp_path)
    want = _oracle_totals(store, ms, 2)
    env = dict(os.environ, FILODB_DS_CRASH_MID_COMMIT="1",
               JAX_PLATFORMS="cpu")
    code = (
        "import jax; jax.config.update('jax_platforms','cpu')\n"
        "from filodb_tpu.downsample.distributed import run_worker\n"
        f"run_worker({str(tmp_path)!r}, 'ds', range(2), (300000,), "
        "worker_id='victim')\n"
    )
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True)
    assert p.returncode == 19, p.stderr[-500:]
    job = _job_dir(str(tmp_path), "ds", "default")
    # crashed post-commit, pre-done: output present, marker absent
    assert not os.path.exists(os.path.join(job, "shard-1.done"))
    assert os.path.exists(_claim_path(job, 1)), "victim died holding claim"
    committed = os.path.join(str(tmp_path), "ds_5m", "shard-1")
    assert any(f.startswith("chunks-batch-") for f in os.listdir(committed))
    old = os.path.getmtime(_claim_path(job, 1)) - 120
    os.utime(_claim_path(job, 1), (old, old))
    r = run_worker(str(tmp_path), "ds", range(2), (300_000,),
                   worker_id="rescuer", stale_s=60.0)
    assert 1 in r.shards_done and 1 in r.claims_broken
    assert job_complete(str(tmp_path), "ds", range(2))
    assert _recovered_totals(tmp_path, 2) == want


def test_batch_commit_preserves_streaming_downsample_segments(tmp_path):
    """The round-5 advisor race: the batch job used to COMMIT by
    rmtree+rename over the live '{ds}_5m/shard-N' dir — wiping newer
    segments flushed there by the ingest-time streaming downsampler. The
    merge commit must leave streaming 'chunks-g*.seg' files in place and
    recovery must still see their samples."""
    from filodb_tpu.core.records import SeriesBatch
    from filodb_tpu.downsample.downsampler import DS_GAUGE
    from filodb_tpu.downsample.distributed import run_worker
    from filodb_tpu.store.columnstore import LocalColumnStore
    from filodb_tpu.store.flush import FlushCoordinator, recover_shard

    store, _ms = _seed_raw_store(tmp_path, n_shards=1)
    # a streaming-downsample flush into the live ds_5m shard dir, with a
    # sentinel series the batch job cannot produce (distinct tags) and
    # timestamps NEWER than anything in the raw store
    dsm = TimeSeriesMemStore()
    dsm.setup(Dataset("ds_5m", schemas=[DS_GAUGE]), [0])
    sent_ts = np.array([BASE + 10**9, BASE + 10**9 + 300_000], dtype=np.int64)
    dsm.shard("ds_5m", 0).ingest_series(SeriesBatch(
        DS_GAUGE, {"__name__": "streamed_only", "src": "live"},
        sent_ts, {"avg": np.array([1.5, 2.5]), "min": np.array([1.0, 2.0]),
                  "max": np.array([2.0, 3.0]), "count": np.array([2.0, 2.0]),
                  "sum": np.array([3.0, 5.0])},
    ))
    FlushCoordinator(dsm, store).flush_shard("ds_5m", 0)
    live = os.path.join(str(tmp_path), "ds_5m", "shard-0")
    streaming_segs = {f for f in os.listdir(live) if f.startswith("chunks-g")}
    assert streaming_segs, "precondition: streaming flush wrote segments"

    r = run_worker(str(tmp_path), "ds", [0], (300_000,), worker_id="batch")
    assert r.shards_done == [0]
    # streaming segments survived the batch commit...
    now = set(os.listdir(live))
    assert streaming_segs <= now, "batch commit wiped streaming segments"
    assert any(f.startswith("chunks-batch-") for f in now)
    # ...and recovery still sees the streaming samples alongside batch ones
    rec = TimeSeriesMemStore()
    rec.setup(Dataset("ds_5m", schemas=[DS_GAUGE]), [0])
    recover_shard(rec, LocalColumnStore(str(tmp_path)), "ds_5m", 0)
    sh = rec.shard("ds_5m", 0)
    from filodb_tpu.core.filters import equals

    pids = sh.lookup_partitions(
        [equals("__name__", "streamed_only")], 0, 2**62
    )
    assert len(pids) == 1, "streaming-downsampled series lost by batch commit"
    ts, vals = sh.partition(int(pids[0])).samples_in_range(0, 2**62, "avg")
    assert list(ts) == list(sent_ts)
    assert list(vals) == [1.5, 2.5]


def test_reconcile_chunks_overlap_later_end_wins():
    """Unit contract of store/flush._reconcile_chunks: per timestamp the
    chunk with the LATER end_ts wins, exact duplicates collapse, and
    non-overlapping chunk sets are untouched."""
    from filodb_tpu.memstore.partition import Chunk
    from filodb_tpu.store.flush import _reconcile_chunks

    class P:  # minimal partition stand-in
        pass

    def chunk(ts, vals):
        ts = np.asarray(ts, dtype=np.int64)
        return Chunk(int(ts[0]), int(ts[-1]), len(ts),
                     {"timestamp": ts, "avg": np.asarray(vals, float)})

    # partial early chunk superseded by a later, more complete one
    p = P()
    p.chunks = [chunk([0, 100], [1.0, 2.0]),
                chunk([0, 100, 200], [10.0, 20.0, 30.0])]
    _reconcile_chunks(p)
    got = {int(t): float(v) for c in p.chunks
           for t, v in zip(c.column("timestamp"), c.column("avg"))}
    assert got == {0: 10.0, 100: 20.0, 200: 30.0}

    # exact duplicates (a redo re-committing the same output) collapse
    p = P()
    p.chunks = [chunk([0, 100], [1.0, 2.0]), chunk([0, 100], [1.0, 2.0])]
    _reconcile_chunks(p)
    assert len(p.chunks) == 1
    assert [int(t) for t in p.chunks[0].column("timestamp")] == [0, 100]

    # disjoint chunks: untouched (the normal raw path)
    p = P()
    before = [chunk([0, 100], [1.0, 2.0]), chunk([200, 300], [3.0, 4.0])]
    p.chunks = list(before)
    _reconcile_chunks(p)
    assert p.chunks == before
